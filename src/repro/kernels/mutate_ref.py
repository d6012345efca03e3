"""Pure-jnp reference for the Pallas mutation-plan kernel.

Same contract as ``mutate.mutate_segments`` — the interpret-mode oracle
the identity tests diff the kernel against, and the backend ``ops``
selects when the kernel is disabled (``use_kernel=False``).  Mirrors
``probe_ref.probe_ref`` structurally: gather the per-query segment row,
run the directional fp-filtered rank math as one (B, S) pass.
"""

from __future__ import annotations

import jax.numpy as jnp

U32 = jnp.uint32
I32 = jnp.int32
BIG = 0x7FFFFFFF


def mutate_ref(rows, indicators, fps, prio, pairs, parity, qkeys, qfp):
    """Returns (match_slot, victim_slot, flip); see mutate.mutate_segments."""
    B, KL = qkeys.shape
    S = prio.shape[1]
    seg = rows[pairs][:, :S * KL].reshape(B, S, KL)
    eq = jnp.all(seg == qkeys[:, None, :], axis=-1)           # (B, S)
    iota = jnp.arange(S, dtype=U32)[None, :]
    bits = (indicators.reshape(-1)[pairs][:, None] >> iota) & U32(1)  # (B, S)
    lane = jnp.where(iota < U32(16), fps[pairs, 0:1], fps[pairs, 1:2])
    field = (lane >> (U32(2) * (iota % U32(16)))) & U32(3)
    eq = eq & (field == qfp.astype(U32)[:, None])             # fp pre-filter
    pr = jnp.where(parity[:, None] == 0, prio[0][None, :], prio[1][None, :])
    cand = pr < BIG
    mrank = jnp.where(eq & (bits == U32(1)) & cand, pr, BIG)
    vrank = jnp.where((bits == U32(0)) & cand, pr, BIG)
    mslot = jnp.argmin(mrank, axis=-1).astype(I32)
    vslot = jnp.argmin(vrank, axis=-1).astype(I32)
    mfound = jnp.min(mrank, -1) < BIG
    vfound = jnp.min(vrank, -1) < BIG
    match = jnp.where(mfound, mslot, -1)
    victim = jnp.where(vfound, vslot, -1)
    flip = (jnp.where(mfound, U32(1) << mslot.astype(U32), U32(0))
            | jnp.where(vfound, U32(1) << vslot.astype(U32), U32(0)))
    return match, victim, flip
