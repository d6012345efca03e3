"""Pallas TPU kernel: paged decode attention over a hash-indexed page pool.

This is where the paper's technique meets the model hot path. The serving
engine stores the KV cache in fixed-size physical pages; the logical->physical
mapping comes from the continuity-hash page table. Each (sequence, kv-head,
logical-page) grid step scalar-prefetches the PHYSICAL page id and the
``BlockSpec`` index map turns it into ONE contiguous (page_size, head_dim)
HBM->VMEM DMA — the TPU rendering of "all positions of an item are in one
contiguous region, fetched with a single one-sided read" (paper §III-A), with
Pallas double-buffering playing the role of RDMA doorbell pipelining.

Online-softmax accumulation across pages (flash-attention style) keeps VMEM
residency at one page per buffer: VMEM working set =
``2 * page_size * head_dim * bytes + G * head_dim * 4`` (~132 KB for
page_size=128, D=128, bf16 double-buffered) — far under the ~16 MB v5e VMEM,
leaving room to raise page_size or pipeline depth.

Validated in interpret mode against ``paged_attn_ref.paged_attention_ref``;
dimensions are MXU/VPU aligned for real TPUs (D=128 lanes, page_size a
multiple of 8 sublanes; q-head group dim padded to >= 8 by ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG_INF = -1e30


def _paged_attn_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref, *, page_size: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)
    maxp = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)             # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)             # (PS, D)
    v = v_ref[0, 0].astype(jnp.float32)             # (PS, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * scale                                   # (G, PS)

    seq_len = len_ref[b]
    page_ok = pt_ref[b, p] >= 0
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    live = (pos < seq_len) & page_ok                # (1, PS)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[...]                             # (G, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)                       # (G, PS)
    pexp = jnp.where(live, pexp, 0.0)
    l_new = alpha * l_ref[...] + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(p == maxp - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_attention(q, kpool, vpool, page_table, seq_lens, *,
                    scale: float | None = None):
    """Paged GQA decode attention.

    Args:
      q:          (B, H, D)
      kpool:      (NP, KVH, PS, D) — physical pages, contiguous per (page, head)
      vpool:      (NP, KVH, PS, D)
      page_table: (B, MAXP) int32 physical page ids (-1 = absent)
      seq_lens:   (B,) int32
    Returns: (B, H, D)
    """
    B, H, D = q.shape
    NP, KVH, PS, _ = kpool.shape
    MAXP = page_table.shape[1]
    G = H // KVH
    if scale is None:
        scale = float(1.0 / (D ** 0.5))
    qg = q.reshape(B, KVH, G, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # page_table, seq_lens
        grid=(B, KVH, MAXP),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, p, pt, sl: (b, h, 0, 0)),
            # ONE contiguous physical page per step, selected via the
            # hash-page-table (the single one-sided read of a segment):
            pl.BlockSpec((1, 1, PS, D),
                         lambda b, h, p, pt, sl: (jnp.maximum(pt[b, p], 0), h, 0, 0)),
            pl.BlockSpec((1, 1, PS, D),
                         lambda b, h, p, pt, sl: (jnp.maximum(pt[b, p], 0), h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, p, pt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),        # running max m
            pltpu.VMEM((G, 1), jnp.float32),        # running denom l
            pltpu.VMEM((G, D), jnp.float32),        # output accumulator
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, page_size=PS, scale=scale)
    out = pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      qg, kpool, vpool)
    return out.reshape(B, H, D)
