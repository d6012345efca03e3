"""Pallas TPU kernel: the whole probe stage of a continuity lookup.

A key's candidates are its pair's one row, plus the pair's extension
group when it has one: the paper's single contiguous READ (and the rare
second one).  The kernel maps each of those reads onto HBM->VMEM DMAs of
the table's own 128-lane rows (``pl.ANY``): for every query the pair's
key row and its value row, and where ``ext_map[pair] >= 0`` the group's
key and value rows too.  It matches in VMEM and returns the value, so no
XLA gather of rows, and no relayout of slots, follows it.

The batch runs in blocks of hundreds of queries (``block_size``), one
grid step each.  A step first starts the NEXT block's row copies into the
other half of a double buffer, then waits for its own block's copies
(started one step earlier) and computes: the copies of one block are in
flight while the previous block is matched, so DMA latency is paid once
per batch, not once per block.

The match stays in the lane domain, (Q, 128) per row kind: two lane
rotations AND a slot's ``key_lanes`` key lanes onto its first lane, the
indicator bit and the parity's probe rank sit on that lane, and the
lowest rank wins, main row before extension row.  The winning slot's
value lanes are masked out of its value row and OR-folded onto lanes
0..3 by lane rotations.

Outputs are the probe stage of ``repro.core.continuity.lookup``
byte for byte: ``found``, ``values``, ``slot`` (ext slots numbered from
``main_slots``) and ``reads`` (1, +1 iff the pair is extended and the
main row missed).  Whether the kernel runs compiled or interpreted is
decided by `repro.kernels.platform`.
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
BIG = 0x7FFFFFFF       # python int: stays a kernel-embedded literal
BLOCK_MAX = 512        # queries per grid step, at most


def block_size(batch: int) -> int:
    """Queries per grid step for a batch: the whole batch up to
    ``BLOCK_MAX``, split into equal blocks of a multiple of 8 above it."""
    nb = -(-batch // BLOCK_MAX)
    return max(8, -(-batch // (8 * nb)) * 8)


def _and_lanes(eq, key_lanes: int):
    """Lane ``key_lanes * s`` of the result ANDs slot s's key lanes."""
    R = eq.shape[1]
    width = 1
    while width < key_lanes:
        eq = eq & pltpu.roll(eq, R - width, 1)
        width *= 2
    return eq


def _first_lane(rank, lane):
    """Per row: the lowest lane holding the row's minimum rank, and that
    rank (BIG where no lane ranks)."""
    best = jnp.min(rank, axis=1, keepdims=True)
    at = jnp.min(jnp.where(rank == best, lane, rank.shape[1]), axis=1,
                 keepdims=True)
    return at, best


def _lookup_kernel(pair_ref, ext_ref, keys_hbm, vals_hbm, ekeys_hbm,
                   evals_hbm, prio_ref, side_ref, qk_ref,
                   vout_ref, sout_ref, rout_ref,
                   kbuf, vbuf, ekbuf, evbuf, sem,
                   *, block: int, key_lanes: int, main_slots: int,
                   ext_slots: int):
    i = pl.program_id(0)
    nb = pl.num_programs(0)

    def copies(b, half, q):
        """The row copies of query q of block b into buffer half ``half``:
        (key row, value row), (ext key row, ext value row), its ext index."""
        g = b * block + q
        p, e = pair_ref[g], ext_ref[g]
        s = sem.at[half]
        main = (pltpu.make_async_copy(keys_hbm.at[p], kbuf.at[half, q], s),
                pltpu.make_async_copy(vals_hbm.at[p], vbuf.at[half, q], s))
        ee = jnp.maximum(e, 0)
        ext = (pltpu.make_async_copy(ekeys_hbm.at[ee], ekbuf.at[half, q], s),
               pltpu.make_async_copy(evals_hbm.at[ee], evbuf.at[half, q], s))
        return main, ext, e

    def each_query(b, half, act):
        def body(q, carry):
            main, ext, e = copies(b, half, q)
            for c in main:
                act(c)

            @pl.when(e >= 0)
            def _():
                for c in ext:
                    act(c)
            return carry
        jax.lax.fori_loop(0, block, body, 0)

    @pl.when(i == 0)
    def _():
        each_query(0, 0, lambda c: c.start())

    @pl.when(i + 1 < nb)
    def _():
        each_query(i + 1, (i + 1) % 2, lambda c: c.start())

    half = i % 2
    each_query(i, half, lambda c: c.wait())

    R = kbuf.shape[2]
    log_kl = key_lanes.bit_length() - 1        # key_lanes is a power of two
    lane = jax.lax.broadcasted_iota(I32, (block, R), 1)
    slot = lane >> log_kl
    side = side_ref[...]                     # (Q, 3): indicator, parity, ext
    ind, extended = side[:, 0:1], side[:, 2:3] != U32(0)
    qk = qk_ref[...]

    def hits(rows, first_slot):
        eq = _and_lanes((rows == qk).astype(I32), key_lanes)
        bit = (ind >> jnp.minimum(slot + first_slot, 31).astype(U32)) & U32(1)
        return (eq == 1) & (bit == U32(1))

    # main row: the parity's probe ranks on the slots' first lanes
    pr = jnp.where(side[:, 1:2] == U32(0), prio_ref[0:1, :], prio_ref[1:2, :])
    m_at, m_best = _first_lane(jnp.where(hits(kbuf[half], 0), pr, BIG), lane)
    # extension row: its slots ascending, for both parities
    er = jnp.where(((lane & (key_lanes - 1)) == 0) & (slot < ext_slots),
                   slot, BIG)
    x_hit = hits(ekbuf[half], main_slots) & extended
    x_at, x_best = _first_lane(jnp.where(x_hit, er, BIG), lane)

    in_main, in_ext = m_best < BIG, x_best < BIG
    m_slot, x_slot = m_at >> log_kl, x_at >> log_kl
    picked = (jnp.where(in_main & (slot == m_slot), vbuf[half], U32(0))
              | jnp.where(~in_main & in_ext & (slot == x_slot), evbuf[half],
                          U32(0)))
    shift = R // 2
    while shift >= key_lanes:          # fold the one slot onto lanes 0..KL-1
        picked = picked | pltpu.roll(picked, shift, 1)
        shift //= 2
    vout_ref[...] = picked[:, :vout_ref.shape[1]]
    sout_ref[...] = jnp.where(in_main, m_slot,
                              jnp.where(in_ext, main_slots + x_slot, -1))
    rout_ref[...] = 1 + (extended & ~in_main).astype(I32)


@functools.partial(jax.jit, static_argnames=("ext_slots",))
def lookup_probe(keys, vals, ext_keys, ext_vals, indicators, ext_map, prio,
                 pairs, parity, qkeys, *, ext_slots: int):
    """Probe each query's pair row and extension row in one kernel.

    Args: the table's (P, R) ``keys``/``vals`` and (PE, R)
    ``ext_keys``/``ext_vals`` rows, (P,) ``indicators`` and ``ext_map``,
    ``prio`` (2, S) probe rank per parity over the main slots (BIG = not a
    candidate), and per query ``pairs``, ``parity`` (B,) and ``qkeys``
    (B, KL).  Returns (found (B,) bool, values (B, KL) uint32, slot (B,)
    int32, reads (B,) int32); a miss has slot -1 and value 0.
    """
    R = keys.shape[1]
    B, KL = qkeys.shape
    S = prio.shape[1]
    assert R % 128 == 0 and max(S, ext_slots) * KL <= R, (R, S, ext_slots)
    assert KL & (KL - 1) == 0 and S + ext_slots <= 32, (KL, S, ext_slots)
    Q = block_size(B)
    nb = -(-B // Q)
    pad = nb * Q - B
    pairs = jnp.pad(pairs.astype(I32), (0, pad))
    eidx = jnp.pad(ext_map[pairs[:B]].astype(I32), (0, pad),
                   constant_values=-1)
    side = jnp.stack([indicators.reshape(-1)[pairs].astype(U32),
                      jnp.pad(parity.astype(U32), (0, pad)),
                      (eidx >= 0).astype(U32)], axis=-1)     # (n, 3)
    qk = jnp.tile(jnp.pad(qkeys.astype(U32), ((0, pad), (0, 0))),
                  (1, R // KL))                              # (n, R)
    prio_l = jnp.full((2, R), BIG, I32).at[
        :, jnp.arange(S) * KL].set(prio.astype(I32))        # rank on lane KL*s
    blk = lambda w: pl.BlockSpec((Q, w), lambda i, p, e: (i, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)                 # rows stay in HBM
    rows = pltpu.VMEM((2, Q, R), U32)                       # double buffer
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                # pairs and ext groups drive DMAs
        grid=(nb,),
        in_specs=[hbm, hbm, hbm, hbm,
                  pl.BlockSpec((2, R), lambda i, p, e: (0, 0)),
                  blk(3), blk(R)],
        out_specs=[blk(KL), blk(1), blk(1)],
        scratch_shapes=[rows, rows, rows, rows,
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out_shape = [jax.ShapeDtypeStruct((nb * Q, KL), U32),
                 jax.ShapeDtypeStruct((nb * Q, 1), I32),
                 jax.ShapeDtypeStruct((nb * Q, 1), I32)]
    kernel = functools.partial(_lookup_kernel, block=Q, key_lanes=KL,
                               main_slots=S, ext_slots=ext_slots)
    values, slot, reads = pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),   # steps hand DMAs on
    )(pairs, eidx, keys, vals, ext_keys, ext_vals, prio_l, side, qk)
    slot = slot[:B, 0]
    return slot >= 0, values[:B], slot, reads[:B, 0]
