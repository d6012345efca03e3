"""Pure-jnp oracle for the continuity segment-probe kernel."""

from __future__ import annotations

import jax.numpy as jnp

U32 = jnp.uint32
BIG = 0x7FFFFFFF  # python int: safe to create at import time inside a trace


def probe_ref(rows: jnp.ndarray, indicators: jnp.ndarray, prio: jnp.ndarray,
              pairs: jnp.ndarray, parity: jnp.ndarray, qkeys: jnp.ndarray,
              fps: jnp.ndarray | None = None,
              qfp: jnp.ndarray | None = None):
    """Reference segment probe.

    Args:
      rows:       (P, R) uint32 — contiguous segment-pair key rows, slot s at
                  lanes [s*KL, (s+1)*KL), R >= SLOTS*KL (the table pads
                  rows to 128 lanes)
      indicators: (P,) or (P, 1) uint32
      prio:       (2, SLOTS) int32 probe rank per parity (BIG = not a candidate)
      pairs:      (B,) int32 — home pair per query
      parity:     (B,) int32
      qkeys:      (B, KL) uint32
      fps:        optional (P, 2) uint32 fingerprint-word lanes (2-bit field
                  per main slot); with ``qfp`` (B,) the probe pre-filters on
                  the field before the full key compare — never drops a true
                  match because visible slots always carry the correct field
      qfp:        optional (B,) uint32 query fingerprints
    Returns:
      match_slot (B,) int32 (-1 = miss), empty_slot (B,) int32 (-1 = full)
    """
    B, KL = qkeys.shape
    S = prio.shape[1]
    seg = rows[pairs][:, :S * KL].reshape(B, S, KL)
    eq = jnp.all(seg == qkeys[:, None, :], axis=-1)
    ind = indicators.reshape(-1)[pairs]
    bits = (ind[:, None] >> jnp.arange(S, dtype=U32)[None]) & U32(1)
    if fps is not None:
        s = jnp.arange(S)
        lane = jnp.where(s[None] < 16, fps[pairs, 0:1], fps[pairs, 1:2])
        field = (lane >> U32(2 * (s % 16))[None]) & U32(3)   # (B, S)
        eq = eq & (field == qfp.astype(U32)[:, None])
    pr = prio[parity]                                    # (B, S)
    cand = pr < BIG
    mrank = jnp.where(eq & (bits == 1) & cand, pr, BIG)
    erank = jnp.where((bits == 0) & cand, pr, BIG)
    mbest = jnp.min(mrank, -1)
    ebest = jnp.min(erank, -1)
    match_slot = jnp.where(mbest < BIG, jnp.argmin(mrank, -1), -1)
    empty_slot = jnp.where(ebest < BIG, jnp.argmin(erank, -1), -1)
    return match_slot.astype(jnp.int32), empty_slot.astype(jnp.int32)
