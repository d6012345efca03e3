"""Distributed continuity KV store over a device mesh (shard_map).

Maps the paper's deployment onto a TPU pod:
  * the table's segment pairs are block-partitioned over the DATA axis —
    each data shard is one "server" owning a contiguous pair range
    (its "PM region");
  * CLIENT READS (paper §III-B): each device batches its lookups, routes the
    16-byte keys to owners with ONE all_to_all, owners respond with the RAW
    SEGMENT PAYLOAD (keys row + vals row + indicator) with a second
    all_to_all, and the CLIENT probes locally — the one-sided RDMA semantics:
    the owner CPU does no probing, bytes-on-wire = one segment per lookup.
    Compare level hashing: up to FOUR non-contiguous bucket fetches per
    lookup = 4x response payload (bench_access_amp / the collective roofline
    term make this visible);
  * SERVER WRITES: insert/update/delete requests are routed to owners
    (write-with-immediate), applied scan-serialized per owner (lock order =
    batch order), acknowledged in the return all_to_all.

Routing uses fixed per-destination capacity buckets (all_to_all needs static
shapes); overflowing keys are reported for retry — the RDMA analogue of a
full send queue.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import continuity as ch
from repro.core import pmem
from repro.core.continuity import (FP_BYTES, INDICATOR_BYTES, KEY_LANES, SLOT_BYTES,
                                   VAL_LANES, ContinuityConfig,
                                   ContinuityTable, _commit_indicator,
                                   _gather_candidates, _scatter_payload,
                                   locate)
from repro.rdma import verbs as rv

U32 = jnp.uint32
I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    table: ContinuityConfig       # GLOBAL table geometry
    num_shards: int               # servers (= product of sharded axes)
    capacity_factor: float = 2.0  # routing bucket headroom
    axis_names: tuple = ("data",)  # mesh axes the store shards over

    def __post_init__(self):
        assert self.table.num_pairs % self.num_shards == 0
        assert self.table.ext_frac == 0.0 and self.table.stash_frac == 0.0, \
            "distributed store uses ext- and stash-free tables (DESIGN.md §5)"

    @property
    def pairs_per_shard(self) -> int:
        return self.table.num_pairs // self.num_shards

    @property
    def local_cfg(self) -> ContinuityConfig:
        return dataclasses.replace(self.table,
                                   num_buckets=2 * self.pairs_per_shard)

    def cap(self, batch_per_shard: int) -> int:
        c = int(batch_per_shard / self.num_shards * self.capacity_factor) + 1
        return min(c, batch_per_shard)


def create_sharded(cfg: StoreConfig, mesh) -> ContinuityTable:
    """Global table as one pytree, created already sharded: each device
    materializes only its own pair range (dim 0 over the store axes)."""
    shardings = jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                             table_pspec(cfg.axis_names))
    return jax.jit(lambda: ch.create(cfg.table), out_shardings=shardings)()


def table_pspec(axes=("data",)) -> ContinuityTable:
    """Pair-indexed leaves shard over the store axes; the (unused, ext-free)
    extension pool and the scalar counters stay replicated. Live-item counting
    in distributed mode is ``sharded_count`` (indicator popcount)."""
    d = P(axes)
    return ContinuityTable(keys=d, vals=d, indicator=d, version=d,
                           ext_keys=P(), ext_vals=P(), ext_map=d,
                           ext_count=P(), count=P(), fp=d,
                           stash_keys=P(), stash_vals=P(), stash_meta=P())


def sharded_count(table: ContinuityTable) -> jnp.ndarray:
    """Live items from indicator popcounts (count scalar is not maintained
    across shards)."""
    bits = (table.indicator[:, None] >>
            jnp.arange(32, dtype=U32)[None]) & U32(1)
    return jnp.sum(bits).astype(I32)


def _route(cfg: StoreConfig, payload, owner, mask):
    """Scatter ``payload`` (B, F) into per-destination capacity buckets and
    all_to_all them. Returns (recv (S, CAP, F), recv_slot bookkeeping)."""
    axis = cfg.axis_names
    B = owner.shape[0]
    S = cfg.num_shards
    CAP = cfg.cap(B)
    # rank of each key within its destination bucket
    onehot = (owner[:, None] == jnp.arange(S)[None]) & mask[:, None]
    rank = jnp.cumsum(onehot, axis=0) - 1
    rank = jnp.sum(rank * onehot, axis=1)                    # (B,)
    ok = mask & (rank < CAP)
    drop = jnp.iinfo(I32).max
    o = jnp.where(ok, owner, drop)
    r = jnp.where(ok, rank, drop)
    send = jnp.zeros((S, CAP) + payload.shape[1:], payload.dtype)
    send = send.at[o, r].set(payload, mode="drop")
    live = jnp.zeros((S, CAP), jnp.bool_).at[o, r].set(ok, mode="drop")
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=False)
    rlive = jax.lax.all_to_all(live, axis, 0, 0, tiled=False)
    return recv, rlive, (o, r, ok)


def _route_back(cfg: StoreConfig, reply, route_meta):
    """Inverse all_to_all + gather each key's reply back to its batch slot."""
    axis = cfg.axis_names
    o, r, ok = route_meta
    back = jax.lax.all_to_all(reply, axis, 0, 0, tiled=False)  # (S, CAP, F)
    safe_o = jnp.minimum(o, cfg.num_shards - 1)
    safe_r = jnp.minimum(r, back.shape[1] - 1)
    out = back[safe_o, safe_r]
    return out, ok


class DLookupResult(NamedTuple):
    found: jnp.ndarray     # (B,) bool
    values: jnp.ndarray    # (B, VAL_LANES)
    routed: jnp.ndarray    # (B,) bool — False = routing overflow, retry
    ledger: pmem.CostLedger  # GLOBAL client-batch wire ledger (verb-plan-
    #                          derived, psum-replicated over the mesh)


def _client_probe(cfg: ContinuityConfig, seg_keys, seg_vals, indicator,
                  parity, qkeys, live):
    """Client-side probe of fetched segments (one per query)."""
    import numpy as np
    from repro.core.continuity import _probe_order
    probe = jnp.asarray(_probe_order(cfg))[:, :cfg.seg_slots]  # main slots
    cand = probe[parity]                                       # (B, C)
    bits = (indicator[:, None] >> cand.astype(U32)) & U32(1)
    ck = jnp.take_along_axis(seg_keys, cand[..., None], 1)
    cv = jnp.take_along_axis(seg_vals, cand[..., None], 1)
    match = (bits == 1) & jnp.all(ck == qkeys[:, None, :], -1) & live[:, None]
    found = jnp.any(match, -1)
    first = jnp.argmax(match, -1)
    vals = jnp.take_along_axis(cv, first[:, None, None], 1)[:, 0]
    return found, jnp.where(found[:, None], vals, 0)


def make_lookup(cfg: StoreConfig, mesh):
    """Build the jitted distributed lookup:
    (table, keys (B,4), mask (B,)) -> DLookupResult. ``keys`` sharded over
    the store axes on dim 0 (each device = one client batch). Routing uses
    fixed capacity buckets; retry unrouted keys with an updated ``mask``
    (deterministic ranks mean identical batches overflow identically)."""
    S = cfg.num_shards
    Ppairs = cfg.pairs_per_shard
    lcfg = cfg.local_cfg
    SL = cfg.table.slots_per_pair

    def impl(table: ContinuityTable, keys, mask):
        keys = keys.reshape(-1, KEY_LANES)
        pair, parity = locate(cfg.table, keys)          # GLOBAL pair ids
        owner = pair // Ppairs
        req = jnp.concatenate([pair[:, None].astype(U32),
                               parity[:, None].astype(U32)], 1)
        recv, rlive, meta = _route(cfg, req, owner, mask)

        # owner side: fetch raw segment payload (NO probing — one-sided read)
        lp = jnp.maximum(recv[..., 0].astype(I32) % Ppairs, 0)
        seg_k = ch.row_prefix(table.keys[lp], SL)       # (S, CAP, SL*KL)
        seg_v = ch.row_prefix(table.vals[lp], SL)
        ind = table.indicator[lp]                       # (S, CAP)
        reply = jnp.concatenate([seg_k, seg_v, ind[..., None]], -1)
        out, ok = _route_back(cfg, reply, meta)

        # client side: local probe of the fetched segment
        B = keys.shape[0]
        rkeys = ch.row_slots(out, SL)                   # (B, SL, KL)
        rvals = ch.row_slots(out[:, seg_k.shape[-1]:], SL)
        rind = out[:, -1]
        found, vals = _client_probe(cfg.table, rkeys, rvals, rind, parity,
                                    keys, ok)
        # wire accounting via the verb plan (one whole-row READ per routed
        # key, addressed by GLOBAL pair), same helper as the local stores;
        # unrouted/masked rows count neither reads nor ops (the CostLedger
        # contract), and psum makes the ledger genuinely replicated (its
        # out-spec is P())
        row_bytes = INDICATOR_BYTES + FP_BYTES + SL * SLOT_BYTES
        plan = rv.pack(B, [(jnp.where(ok, rv.READ, rv.NOOP), rv.REGION_TABLE,
                            pair * row_bytes, row_bytes, 0, False)])
        ledger = rv.ledger_from_plan(plan)._replace(
            ops=jnp.sum(ok.astype(jnp.int32)))
        ledger = jax.tree.map(
            lambda x: jax.lax.psum(x, cfg.axis_names), ledger)
        return DLookupResult(found, vals, ok, ledger)

    ax = P(cfg.axis_names)
    sm = jax.shard_map(impl, mesh=mesh,
                       in_specs=(table_pspec(cfg.axis_names), ax, ax),
                       out_specs=DLookupResult(
                           ax, ax, ax,
                           pmem.CostLedger(P(), P(), P(), P())),
                       check_vma=False)
    jitted = jax.jit(sm)

    def lookup(table, keys, mask=None):
        if mask is None:
            mask = jnp.ones((keys.shape[0],), jnp.bool_)
        return jitted(table, keys, mask)
    return lookup


OP_INSERT, OP_UPDATE, OP_DELETE = 1, 2, 3


def _apply_routed_writes(lcfg: ContinuityConfig, table: ContinuityTable,
                         pair_l, parity, op, keys, vals, live):
    """Owner-side scan-serialized write application with indicator commits.

    Works on LOCAL pair ids with the GLOBAL parity (segment geometry is
    per-pair, so locality only changes the pair index)."""
    def one(table, x):
        pr, pa, o, k, v, lv = x
        can_alloc = jnp.zeros((1,), jnp.bool_)          # ext-free tables
        cand, ckeys, cvals, valid, slot_ok, is_ext, _ = _gather_candidates(
            lcfg, table, pr[None], pa[None], ext_allowed=can_alloc)
        match = valid & jnp.all(ckeys == k[None, None, :], -1)
        mfound = jnp.any(match, -1)[0]
        mfirst = jnp.argmax(match, -1)
        mslot = jnp.take_along_axis(cand, mfirst[:, None], 1)[0, 0]
        empty = (~valid) & slot_ok
        has_empty = jnp.any(empty, -1)[0]
        efirst = jnp.argmax(empty, -1)
        eslot = jnp.take_along_axis(cand, efirst[:, None], 1)[0, 0]
        word = table.indicator[pr]

        ins = lv & (o == OP_INSERT) & has_empty & ~mfound
        upd = lv & (o == OP_UPDATE) & mfound & has_empty
        dele = lv & (o == OP_DELETE) & mfound

        wslot = jnp.where(dele, 0, eslot)
        do_payload = ins | upd
        table = _scatter_payload(table, do_payload, pr, wslot,
                                 jnp.zeros((), I32), k, v, lcfg.slots_per_pair)
        bit_new = U32(1) << eslot.astype(U32)
        bit_old = U32(1) << jnp.maximum(mslot, 0).astype(U32)
        word = jnp.where(ins, word | bit_new, word)
        word = jnp.where(upd, (word | bit_new) ^ bit_old, word)
        word = jnp.where(dele, word & ~bit_old, word)
        table = _commit_indicator(table, ins | upd | dele, pr, word)
        status = jnp.where(ins | upd | dele, 1, 0).astype(U32)
        return table, status

    table, status = jax.lax.scan(
        one, table, (pair_l, parity, op, keys, vals, live))
    return table, status


def make_write(cfg: StoreConfig, mesh):
    """Jitted distributed write: (table, op (B,), keys, vals) ->
    (table, ok (B,), routed (B,))."""
    Ppairs = cfg.pairs_per_shard
    lcfg = cfg.local_cfg

    def impl(table, op, keys, vals):
        keys = keys.reshape(-1, KEY_LANES)
        vals = vals.reshape(-1, VAL_LANES)
        pair, parity = locate(cfg.table, keys)
        owner = pair // Ppairs
        mask = op > 0
        req = jnp.concatenate([
            pair[:, None].astype(U32), parity[:, None].astype(U32),
            op[:, None].astype(U32), keys, vals], 1)
        recv, rlive, meta = _route(cfg, req, owner, mask)
        S, CAP, F = recv.shape
        flat = recv.reshape(S * CAP, F)
        table, status = _apply_routed_writes(
            lcfg, table,
            (flat[:, 0].astype(I32) % Ppairs),
            flat[:, 1].astype(I32),
            flat[:, 2].astype(I32),
            flat[:, 3:3 + KEY_LANES],
            flat[:, 3 + KEY_LANES:3 + KEY_LANES + VAL_LANES],
            rlive.reshape(S * CAP))
        reply = status.reshape(S, CAP, 1)
        out, ok = _route_back(cfg, reply, meta)
        return table, (out[:, 0] == 1) & ok, ok

    ax = P(cfg.axis_names)
    sm = jax.shard_map(impl, mesh=mesh,
                       in_specs=(table_pspec(cfg.axis_names), ax, ax, ax),
                       out_specs=(table_pspec(cfg.axis_names), ax, ax),
                       check_vma=False)
    return jax.jit(sm, donate_argnums=0)


# ---------------------------------------------------------------------------
# level-hashing-style distributed lookup (for the access-amplification
# comparison at pod scale — EXPERIMENTS.md §Paper-validation)
# ---------------------------------------------------------------------------

def make_lookup_multifetch(cfg: StoreConfig, mesh, fetches: int = 4):
    """A lookup that must fetch ``fetches`` NON-CONTIGUOUS candidate rows per
    key (level hashing's four buckets / CCEH's directory+bucket), issued in
    parallel like independent one-sided reads. Wire cost per key =
    ``fetches`` x (request + bucket-row payload) and ``fetches`` x the
    message count, vs continuity's single segment. Rows are derived with
    independent hashes; the reply payload is one BUCKET row (a quarter
    segment) per fetch. This function exists purely to measure the
    collective-term difference — it is not a functional store."""
    from repro.core.hashfn import hash128
    Ppairs = cfg.pairs_per_shard
    SL = cfg.table.slots_per_pair
    bucket_lanes = SL // 4 * (KEY_LANES + VAL_LANES)   # quarter row

    def impl(table: ContinuityTable, keys, mask):
        keys = keys.reshape(-1, KEY_LANES)
        B = keys.shape[0]
        reps = []
        for f in range(fetches):
            h = hash128(keys, seed=(0x9E3779B9 * (f + 1)) & 0xFFFFFFFF)
            pair = (h % jnp.uint32(cfg.table.num_pairs)).astype(I32)
            owner = pair // Ppairs
            req = pair[:, None].astype(U32)
            recv, rlive, meta = _route(cfg, req, owner, mask)
            lp = jnp.maximum(recv[..., 0].astype(I32) % Ppairs, 0)
            rowk = ch.row_prefix(table.keys[lp], SL // 4)
            rowv = ch.row_prefix(table.vals[lp], SL // 4)
            reply = jnp.concatenate(
                [rowk, rowv, table.indicator[lp][..., None]], -1)
            out, ok = _route_back(cfg, reply, meta)
            reps.append((out, ok))
        found = jnp.zeros((B,), jnp.bool_)
        for out, ok in reps:     # client-side probe of each fetched bucket
            rk = ch.row_slots(out, SL // 4)
            hit = jnp.any(jnp.all(rk == keys[:, None, :], -1), -1) & ok
            found = found | hit
        return found

    ax = P(cfg.axis_names)
    sm = jax.shard_map(impl, mesh=mesh,
                       in_specs=(table_pspec(cfg.axis_names), ax, ax),
                       out_specs=ax, check_vma=False)
    jitted = jax.jit(sm)

    def lookup(table, keys, mask=None):
        if mask is None:
            mask = jnp.ones((keys.shape[0],), jnp.bool_)
        return jitted(table, keys, mask)
    return lookup
