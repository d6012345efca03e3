"""Bulk loading: insert a record set through any `HashStore` in bounded,
shape-stable batches."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.continuity import KEY_LANES, VAL_LANES


def bulk_load(store, table, keys, vals, *, batch: int):
    """Insert ``keys``/``vals`` in order, ``batch`` ops per call.

    Every call has the same (batch, lanes) shape and a mask (the last
    batch is zero-padded and masked off), so the store compiles one
    program whatever the record count, and device memory per call is
    bounded by the batch.  Batch boundaries do not change the result: the
    stores apply a batch in batch order.  At most two calls are in
    flight: each call's output is a whole new table, and a host that runs
    ahead of the device holds one per queued call (an unbounded load of
    a 2^25-slot store on a v5e peaked at 15.6 GiB of its 16).
    Returns ``(table, ok)`` with
    ``ok`` the (N,) host array of per-record success flags, fetched once
    at the end."""
    keys = np.asarray(keys, np.uint32).reshape(-1, KEY_LANES)
    vals = np.asarray(vals, np.uint32).reshape(-1, VAL_LANES)
    n = keys.shape[0]
    ok = []
    for lo in range(0, n, batch):
        m = min(batch, n - lo)
        pad = ((0, batch - m), (0, 0))
        mask = jnp.asarray(np.arange(batch) < m)
        table, res = store.insert(
            table, jnp.asarray(np.pad(keys[lo:lo + m], pad)),
            jnp.asarray(np.pad(vals[lo:lo + m], pad)), mask)
        ok.append(res.ok)
        if len(ok) > 2:
            ok[-3].block_until_ready()
    ok =np.concatenate(jax.device_get(ok)) if ok else np.zeros(0, bool)
    return table, ok[:n]
