"""Jit'd public wrappers around the Pallas kernels.

``probe_table`` adapts a ``ContinuityTable`` into the segment-probe
kernel's layout (flat contiguous rows + parity priority table) and
returns the main-segment probe of ``repro.core.continuity.lookup``.
``probe_lookup`` is a FULL lookup (values + extension slots + fetch
accounting) on the lookup kernel (`repro.kernels.lookup`) or its jnp
oracle, selected through ``repro.api.ExecPolicy(probe="pallas" |
"reference")``; ``lookup`` is the default policy's read path, the kernel
on a TPU and the jnp gather elsewhere.  ``paged_attention`` is
re-exported with TPU-alignment padding for the q-head-group dimension.

The kernels read the table's own row storage (``ContinuityTable.keys``
is already one 128-lane row per pair), so no call repacks the table.
Whether a kernel runs compiled or interpreted follows the platform
(`repro.kernels.platform`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.continuity import ContinuityConfig, ContinuityTable, KEY_LANES
from repro.kernels import lookup as _lookup
from repro.kernels import mutate as _mutate
from repro.kernels import mutate_ref as _mutate_ref
from repro.kernels import paged_attn as _pa
from repro.kernels import platform as _platform
from repro.kernels import probe as _probe
from repro.kernels import probe_ref as _probe_ref

BIG = 0x7FFFFFFF


@functools.lru_cache(maxsize=None)
def priority_table(cfg: ContinuityConfig) -> np.ndarray:
    """(2, SLOTS) probe rank per parity over MAIN slots (ext handled outside).

    Even homes: bucket then SBuckets, left->right. Odd homes: bucket then
    SBuckets, right->left (paper §III-C's directional scans).
    """
    S, bs, seg = cfg.slots_per_pair, cfg.bucket_slots, cfg.seg_slots
    prio = np.full((2, S), BIG, np.int32)
    prio[0, :seg] = np.arange(seg)
    odd_order = list(range(S - 1, bs - 1, -1))
    prio[1, odd_order] = np.arange(seg)
    return prio


def probe_table(cfg: ContinuityConfig, table: ContinuityTable, keys,
                *, use_kernel: bool = True,
                qblock: int = 8, use_fp: bool = False):
    """Probe the main segments of ``table`` for a batch of keys.

    ``qblock`` queries share one grid step (one VPU pass over their
    DMA-gathered segment rows). ``use_fp`` enables the fingerprint-word
    pre-filter (same results — visible slots always carry the correct
    field — but models the paper-style compare-reduction). Returns
    (match_slot, empty_slot, pair, parity); slots are -1 on miss/full.
    """
    from repro.core import continuity as ch  # local import to avoid cycle
    keys = jnp.asarray(keys, jnp.uint32).reshape(-1, KEY_LANES)
    pair, parity = ch.locate(cfg, keys)
    prio = jnp.asarray(priority_table(cfg))
    fps = table.fp if use_fp else None
    qfp = ch.fingerprint(keys) if use_fp else None
    if use_kernel:
        match, empty = _probe.probe_segments(
            table.keys, table.indicator, prio, pair, parity, keys, fps, qfp,
            qblock=qblock)
    else:
        match, empty = _probe_ref.probe_ref(table.keys, table.indicator,
                                            prio, pair, parity, keys, fps,
                                            qfp)
    return match, empty, pair, parity


def mutation_plan(cfg: ContinuityConfig, table: ContinuityTable, keys,
                  *, use_kernel: bool = True,
                  qblock: int = 8):
    """Resolve the main-segment mutation plan for a batch of keys.

    The write-path peer of ``probe_table``: one contiguous row DMA per
    query resolves both the MATCH slot (the key's current home — the bit
    update/delete clears) and the VICTIM slot (first empty probe candidate
    — the bit update sets), plus ``flip``, the one-word XOR commit mask an
    uncontended update would store.  The fingerprint filter is always on
    (pure compare-reduction; visible slots carry correct fields).  The
    fused mutation engine (``continuity.update``/``delete`` with
    ``probe="pallas"``) consumes the match side and replays victim
    allocation only for multi-op pairs.  Returns (match, victim, flip),
    each (B,), slots -1 on miss/full.
    """
    from repro.core import continuity as ch  # local import to avoid cycle
    keys = jnp.asarray(keys, jnp.uint32).reshape(-1, KEY_LANES)
    pair, parity = ch.locate(cfg, keys)
    prio = jnp.asarray(priority_table(cfg))
    qfp = ch.fingerprint(keys)
    args = (table.keys, table.indicator, table.fp, prio, pair, parity, keys,
            qfp)
    if use_kernel:
        return _mutate.mutate_segments(*args, qblock=qblock)
    return _mutate_ref.mutate_ref(*args)


def fp_filter_stats(cfg: ContinuityConfig, table: ContinuityTable, keys):
    """Main-segment key compares a probe batch performs with vs without the
    fingerprint pre-filter (the paper's Figs 7/14 quantity).

    Without the filter every OCCUPIED probe-candidate slot costs a 16-byte
    key compare; with it only slots whose 2-bit field equals the query's
    fingerprint do.  Returns a host-side dict with both totals and the
    reduction ratio — run it on a negative-search batch to reproduce the
    paper's claim (positive searches stop at the match either way).
    """
    from repro.core import continuity as ch
    keys = jnp.asarray(keys, jnp.uint32).reshape(-1, KEY_LANES)
    pair, parity = ch.locate(cfg, keys)
    S = cfg.slots_per_pair
    iota = jnp.arange(S, dtype=jnp.uint32)[None, :]
    bits = (table.indicator[pair][:, None] >> iota) & jnp.uint32(1)
    prio = jnp.asarray(priority_table(cfg))
    pr = jnp.where(parity[:, None] == 0, prio[0][None, :], prio[1][None, :])
    occ = (bits == jnp.uint32(1)) & (pr < BIG)
    lane = jnp.where(iota < jnp.uint32(16),
                     table.fp[pair, 0:1], table.fp[pair, 1:2])
    field = (lane >> (jnp.uint32(2) * (iota % jnp.uint32(16)))) & jnp.uint32(3)
    qfp = ch.fingerprint(keys)
    pass_fp = occ & (field == qfp[:, None])
    no_fp = int(jnp.sum(occ))
    with_fp = int(jnp.sum(pass_fp))
    return {
        "queries": int(keys.shape[0]),
        "compares_no_fp": no_fp,
        "compares_with_fp": with_fp,
        "reduction": 1.0 - (with_fp / no_fp if no_fp else 0.0),
    }


def probe_lookup(cfg: ContinuityConfig, table: ContinuityTable, keys,
                 *, use_kernel: bool = True, use_fp: bool = True):
    """Full continuity lookup with a Pallas-path probe stage;
    byte-identical to ``repro.core.continuity.lookup``.

    ``use_kernel``: the lookup kernel (`repro.kernels.lookup`) fetches
    each query's pair row, value row and extension rows by DMA, matches
    them and returns the value; it compares the whole fetched key row, so
    it has no use for the fingerprint word.  Otherwise the kernel's jnp
    oracle runs: ``probe_ref``'s directional main-segment scan (with the
    fingerprint pre-filter folded into the match rank when ``use_fp``)
    and a jnp gather over the extension candidates.  Stash-enabled
    geometries get the same one-contiguous-fetch stash tail as the
    reference either way."""
    from repro.core import continuity as ch
    with jax.named_scope("lookup.probe"):
        keys = jnp.asarray(keys, jnp.uint32).reshape(-1, KEY_LANES)
        pair, parity = ch.locate(cfg, keys)
        if use_kernel:
            found, values, slot, reads = _lookup.lookup_probe(
                table.keys, table.vals, table.ext_keys, table.ext_vals,
                table.indicator, table.ext_map,
                jnp.asarray(priority_table(cfg)), pair, parity, keys,
                ext_slots=cfg.ext_slots)
        else:
            found, values, slot, reads = _probe_oracle(
                cfg, table, keys, pair, parity, use_fp)
    if cfg.stash_slots:                        # the same stash stage as ch.lookup
        found, values, slot, reads = ch._stash_tail(
            cfg, table, keys, pair, found, values, slot, reads)
    return ch.LookupResult(found, values, slot, pair, reads)


def _probe_oracle(cfg: ContinuityConfig, table: ContinuityTable, keys, pair,
                  parity, use_fp: bool):
    """The lookup kernel's jnp oracle: (found, values, slot, reads)."""
    from repro.core import continuity as ch
    prio = jnp.asarray(priority_table(cfg))
    fps, qfp = ((table.fp, ch.fingerprint(keys)) if use_fp else (None, None))
    match, _ = _probe_ref.probe_ref(table.keys, table.indicator, prio, pair,
                                    parity, keys, fps, qfp)
    found_main = match >= 0
    vals_main = ch.row_get(table.vals, pair, jnp.maximum(match, 0))

    # extension tail: slots S..S+E-1, ascending for BOTH parities (probe
    # order puts them last), only addressable when the pair is extended
    S, E = cfg.slots_per_pair, cfg.ext_slots
    eidx = table.ext_map[pair]                             # (B,)
    has_ext = eidx >= 0
    if E:
        ebits = (table.indicator[pair][:, None]
                 >> (S + jnp.arange(E, dtype=jnp.uint32))[None]) \
            & jnp.uint32(1)
        safe_e = jnp.maximum(eidx, 0)
        ekeys = ch.row_slots(table.ext_keys[safe_e], E)   # (B, E, KL)
        ematch = has_ext[:, None] & (ebits == 1) & \
            jnp.all(ekeys == keys[:, None, :], axis=-1)
        efound = jnp.any(ematch, axis=-1)
        efirst = jnp.argmax(ematch, axis=-1)
        evals = ch.row_get(table.ext_vals, safe_e, efirst)
    else:
        efound = jnp.zeros_like(found_main)
        efirst = jnp.zeros(keys.shape[0], jnp.int32)
        evals = jnp.zeros_like(vals_main)

    found = found_main | efound
    slot = jnp.where(found_main, match, jnp.where(efound, S + efirst, -1))
    values = jnp.where(found_main[:, None], vals_main,
                       jnp.where(efound[:, None], evals, 0))
    reads = 1 + (has_ext & ~found_main).astype(jnp.int32)
    return found, values, slot, reads


def lookup(cfg: ContinuityConfig, table: ContinuityTable, keys):
    """The default store's lookup, resolved by the platform it is lowered
    for: the lookup kernel on a TPU, ``continuity.lookup``'s jnp gather on
    any other (the CPU, where the kernel would only be interpreted)."""
    from repro.core import continuity as ch
    return _platform.by_platform(
        table, keys, default=lambda t, k: ch.lookup(cfg, t, k),
        tpu=lambda t, k: probe_lookup(cfg, t, k))


def paged_attention(q, kpool, vpool, page_table, seq_lens, *,
                    scale: float | None = None, use_kernel: bool = True):
    """Paged GQA decode attention; pads the q-head group dim to >=8 sublanes
    so the kernel block shapes are TPU-tileable, then unpads."""
    if not use_kernel:
        from repro.kernels.paged_attn_ref import paged_attention_ref
        return paged_attention_ref(q, kpool, vpool, page_table, seq_lens,
                                   scale=scale)
    B, H, D = q.shape
    KVH = kpool.shape[1]
    G = H // KVH
    pad = 0
    if G < 8:
        pad = 8 - G
        qg = q.reshape(B, KVH, G, D)
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q = qg.reshape(B, KVH * (G + pad), D)
    out = _pa.paged_attention(q, kpool, vpool, page_table, seq_lens,
                              scale=scale)
    if pad:
        out = out.reshape(B, KVH, G + pad, D)[:, :, :G].reshape(B, H, D)
    return out
