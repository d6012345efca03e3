"""Pallas TPU kernel: batched continuity-segment probe.

The defining property of continuity hashing — every candidate position of a
key lives in ONE contiguous memory region (the segment) — maps onto the TPU
as follows: the table's key rows stay in HBM (``pl.ANY``) and each query
issues exactly ONE contiguous HBM->VMEM DMA of its pair's key row (the
analogue of the paper's single one-sided RDMA read).

Each grid step processes a BLOCK of ``qblock`` queries: the per-query row
DMAs are issued back-to-back into a VMEM scratch tile (the analogue of RDMA
doorbell batching) and the probe math for the whole block then runs as one
vectorized VPU pass — amortizing grid/dispatch overhead over the block
while preserving the one-contiguous-DMA-per-segment property.

Layout, as the chip's compiler requires it:
  * ``rows`` is the table's own key storage, (P, ROW_LANES) uint32 with
    slot s at lanes [s*KL, (s+1)*KL) and the row padded to a multiple of
    128 lanes, so each DMA moves whole tile rows and no call repacks the
    table;
  * the pair's indicator word (and fingerprint word) are gathered per
    query by the wrapper, O(B), and stream in with the query block;
  * the math stays in the lane domain (Q, ROW_LANES): two lane rotations
    AND a slot's KL key lanes onto its first lane, ranks sit on those
    lanes, and the best slot is the lowest lane holding the minimum rank
    (no lane-splitting reshape, no integer argmin);
  * compute per step is a few hundred VPU ops — the kernel is DMA-bound by
    design (it is a memory-streaming index probe, like the RDMA original).

How the kernel runs (compiled on TPU, interpreted on CPU) is decided by
`repro.kernels.platform`, never by the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

U32 = jnp.uint32
I32 = jnp.int32
BIG = 0x7FFFFFFF  # python int: stays a kernel-embedded literal
SIDE = 8          # per-query side words: ind, fp0, fp1, parity, qfp, pad


def _segment_kernel(pairs_ref, rows_ref, prio_ref, side_ref, qk_ref, *refs,
                    key_lanes: int, qblock: int, use_fp: bool,
                    emit_flip: bool):
    """Shared body of the probe and mutation-plan kernels.

    Outputs (Q, 1) int32 ``match`` and ``empty`` slots (-1 = miss/full)
    and, with ``emit_flip``, the (Q, 1) uint32 one-word commit mask."""
    outs = refs[:3 if emit_flip else 2]
    seg_vmem, sem = refs[len(outs):]
    i = pl.program_id(0)

    # ONE contiguous DMA per query: the pair's key row.  All copies are
    # STARTED before any wait — the block's DMAs are in flight together
    # (the doorbell-batching analogue), so single-query latency is not
    # serialized across the block.
    def start(q, carry):
        p = pairs_ref[i * qblock + q]
        pltpu.make_async_copy(rows_ref.at[p], seg_vmem.at[q], sem).start()
        return carry

    def wait(q, carry):
        p = pairs_ref[i * qblock + q]
        pltpu.make_async_copy(rows_ref.at[p], seg_vmem.at[q], sem).wait()
        return carry

    jax.lax.fori_loop(0, qblock, start, 0)
    jax.lax.fori_loop(0, qblock, wait, 0)

    R = seg_vmem.shape[1]
    log_kl = key_lanes.bit_length() - 1        # key_lanes is a power of two
    eq = (seg_vmem[...] == qk_ref[...]).astype(I32)           # (Q, R)
    width = 1
    while width < key_lanes:          # lane KL*s ends up ANDing slot s's lanes
        eq = eq & pltpu.roll(eq, R - width, 1)
        width *= 2
    lane = jax.lax.broadcasted_iota(I32, (qblock, R), 1)
    slot = lane >> log_kl
    shift = jnp.minimum(slot, 31).astype(U32)
    side = side_ref[...]                                      # (Q, SIDE)
    bits = (side[:, 0:1] >> shift) & U32(1)
    pr = jnp.where(side[:, 3:4] == U32(0), prio_ref[0:1, :], prio_ref[1:2, :])
    cand = pr < BIG                    # only lane KL*s of a candidate slot
    hit = (eq == 1) & (bits == U32(1)) & cand
    if use_fp:
        # the 8-byte fp word rides with the indicator: the match rank gains
        # a 2-bit field pre-filter (never drops a true match — visible
        # slots always carry the correct field)
        word = jnp.where(shift < U32(16), side[:, 1:2], side[:, 2:3])
        field = (word >> (U32(2) * (shift % U32(16)))) & U32(3)
        hit = hit & (field == side[:, 4:5])
    mrank = jnp.where(hit, pr, BIG)
    erank = jnp.where((bits == U32(0)) & cand, pr, BIG)

    def first(rank):                  # slot of the minimum rank, -1 if none
        best = jnp.min(rank, axis=1, keepdims=True)
        at = jnp.min(jnp.where(rank == best, lane, R), axis=1, keepdims=True)
        return jnp.where(best < BIG, at >> log_kl, -1)

    match, empty = first(mrank), first(erank)
    outs[0][...] = match
    outs[1][...] = empty
    if emit_flip:
        outs[2][...] = (
            jnp.where(match >= 0, U32(1) << jnp.maximum(match, 0).astype(U32),
                      U32(0))
            | jnp.where(empty >= 0, U32(1) << jnp.maximum(empty, 0).astype(U32),
                        U32(0)))


def segment_call(rows, indicators, prio, pairs, parity, qkeys, fps, qfp, *,
                 qblock: int, emit_flip: bool):
    """Launch `_segment_kernel` over a query batch (probe/mutate wrappers)."""
    P, R = rows.shape
    B, KL = qkeys.shape
    S = prio.shape[1]
    assert R % 128 == 0 and S * KL <= R and KL & (KL - 1) == 0, (R, S, KL)
    use_fp = fps is not None
    nb = max(1, -(-B // qblock))
    n = nb * qblock
    pad = n - B
    pairs = jnp.pad(pairs.astype(I32), (0, pad))
    zero = jnp.zeros((n,), U32)
    fpw = fps[pairs].astype(U32) if use_fp else jnp.zeros((n, 2), U32)
    side = jnp.stack(
        [indicators.reshape(-1)[pairs].astype(U32), fpw[:, 0], fpw[:, 1],
         jnp.pad(parity.astype(U32), (0, pad)),
         jnp.pad(qfp.astype(U32), (0, pad)) if use_fp else zero]
        + [zero] * (SIDE - 5), axis=-1)                      # (n, SIDE)
    qk = jnp.tile(jnp.pad(qkeys.astype(U32), ((0, pad), (0, 0))),
                  (1, R // KL))                               # (n, R)
    prio_l = jnp.full((2, R), BIG, I32).at[
        :, jnp.arange(S) * KL].set(prio.astype(I32))         # rank on lane KL*s
    blk = lambda w: pl.BlockSpec((qblock, w), lambda i, pairs: (i, 0))
    n_out = 3 if emit_flip else 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # pairs drive the row DMAs
        grid=(nb,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),     # key rows stay in HBM
            pl.BlockSpec((2, R), lambda i, pairs: (0, 0)),
            blk(SIDE),
            blk(R),
        ],
        out_specs=[blk(1)] * n_out,
        scratch_shapes=[pltpu.VMEM((qblock, R), U32),   # per-block rows
                        pltpu.SemaphoreType.DMA(())],
    )
    out_shape = [jax.ShapeDtypeStruct((n, 1), I32)] * 2
    if emit_flip:
        out_shape.append(jax.ShapeDtypeStruct((n, 1), U32))
    kernel = functools.partial(_segment_kernel, key_lanes=KL, qblock=qblock,
                               use_fp=use_fp, emit_flip=emit_flip)
    outs = pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape)(
        pairs, rows, prio_l, side, qk)
    return tuple(o[:B, 0] for o in outs)


@functools.partial(jax.jit, static_argnames=("qblock",))
def probe_segments(rows, indicators, prio, pairs, parity, qkeys,
                   fps=None, qfp=None, *, qblock: int = 8):
    """Probe one contiguous segment row per query, ``qblock`` queries per
    grid step.

    Args mirror ``probe_ref.probe_ref``; ``fps``/``qfp`` (both or neither)
    enable the fingerprint pre-filter.  Returns (match_slot, empty_slot),
    each (B,) int32 with -1 for miss/full.
    """
    return segment_call(rows, indicators, prio, pairs, parity, qkeys, fps,
                        qfp, qblock=qblock, emit_flip=False)
