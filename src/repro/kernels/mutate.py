"""Pallas TPU kernel: batched continuity mutation plan (update/delete).

Peer of ``probe.py`` for the WRITE path.  A mutation against a continuity
pair needs exactly two facts about the cohort's contiguous segment row:

  * the MATCH slot — the key's current home (the bit an update/delete
    clears), resolved by the same directional fp-filtered scan the probe
    kernel runs; and
  * the VICTIM slot — the first empty probe candidate in direction order
    (the bit an update sets for its out-of-place copy; insert's target).

Both live in the one region a single HBM->VMEM row DMA fetches (the RDMA
single-READ analogue), so the kernel resolves them in-register per grid
step and emits a dense commit plan: ``(match_slot, victim_slot, flip)``
rows, where ``flip`` is the one-word XOR mask an uncontended op would
commit (old-bit | new-bit for update, old-bit alone for delete).  The
host-side fused pass consumes the match side directly and replays victim
allocation only for pairs that receive multiple ops in one batch (the
plan's victim is pre-state-exact for the single-op-per-pair common case).

The kernel body, DMA structure and layout are ``probe.py``'s (one shared
`probe._segment_kernel`); the fingerprint filter is ALWAYS on here —
mutations must never act on a wrong slot, and visible slots always carry
the correct field, so the filter is a pure compare-reduction.
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.probe import segment_call


@functools.partial(jax.jit, static_argnames=("qblock",))
def mutate_segments(rows, indicators, fps, prio, pairs, parity, qkeys, qfp,
                    *, qblock: int = 8):
    """Resolve the mutation plan for one contiguous segment row per query.

    Args mirror ``probe.probe_segments`` with the fp word mandatory.
    Returns ``(match_slot, victim_slot, flip)``: (B,) int32/int32/uint32
    with -1 for miss/full and ``flip`` the one-word commit XOR mask.
    """
    return segment_call(rows, indicators, prio, pairs, parity, qkeys, fps,
                        qfp, qblock=qblock, emit_flip=True)
