"""Continuity hashing (Liu, Hua, Bai — CS.DC 2021) as a functional JAX data structure.

Structure (paper §III-A), defaults ``bucket_slots=4, sbuckets=3``::

      slot ids within one segment-pair row (SLOTS = 20):
      [ B_even: 0..3 | shared SBuckets: 4..15 | B_odd: 16..19 ]   + ext: 20..31

  * segment(even) = slots [0, 16)   — home bucket + shared region
  * segment(odd)  = slots [4, 20)   — shared region + home bucket
  * the two segments of a pair overlap on the SBuckets — exactly the paper's
    layout, flattened so that one row = one contiguous memory region and a
    segment fetch is ONE contiguous read (the RDMA-friendliness property).
  * a 32-bit ``indicator`` word per pair holds one valid-bit per slot
    (20 main + 12 extension bits — the paper's Fig. 3), committed with a
    single atomic store AFTER the slot payload: log-free failure atomicity.

Probe order (paper §III-C): even homes scan left->right (bucket, then
SBuckets); odd homes scan right->left (bucket, then SBuckets in reverse);
extension slots come last for both parities.

All operations are pure functions ``(table, ...) -> (table, result, counters)``
and jit-compile with the config static.

Server-side mutation batches run on the **wave-vectorized mutation engine**
(``insert`` / ``update`` / ``delete``): one stable packed sort by pair index
groups the batch into per-pair cohorts, a segment scan assigns each op its
intra-cohort rank, and ops of equal rank ("waves") touch pairwise-distinct
pairs — so a wave is one batched probe, one batched payload scatter
(phase 1) and one batched round of independent one-word indicator commits
(phase 2): the deterministic TPU analogue of the paper's per-slot
spin-locks, preserving lock-acquisition order == batch order and the
log-free crash-atomicity split.  Because insert-only occupancy grows
monotonically, ``insert`` executes ALL of its waves in one fused
rank-indexed bit-select pass over the indicator words (a residual wave
``while_loop`` exactly resolves the rare parity-contended cohorts);
``update``/``delete`` run their waves in a ``while_loop`` whose trip count
is max_collisions_per_pair.  Extension groups are granted by prefix sum in
batch order and the pool relabelled to serial allocation order, so the
engine produces tables byte-identical to the ``lax.scan`` reference paths
(``insert_serial`` / ``update_serial`` / ``delete_serial``, kept for
crash-recovery tests and as the equivalence oracle).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pmem
from repro.core.hashfn import hash128, hash128_2

U32 = jnp.uint32
I32 = jnp.int32

KEY_LANES = 4   # 16-byte keys (paper: 16 B)
VAL_LANES = 4   # 16-byte value slots (paper: values <= 15 B + metadata byte)
SLOT_BYTES = (KEY_LANES + VAL_LANES) * 4
INDICATOR_BYTES = 8  # stored/committed as one 8-byte atomic unit
FP_BYTES = 8         # fingerprint word, adjacent to the indicator (Dash-style)
FP_SLOT_BITS = 2     # fingerprint bits per main slot
FP_MASK = (1 << FP_SLOT_BITS) - 1
_FPW = 32 // FP_SLOT_BITS            # fp fields per 32-bit lane
STASH_CNT_SHIFT = 24                 # per-pair stash count byte (fp lane 1)
STASH_META_BYTES = 8                 # per-stash-entry meta word (atomic commit)
# Key and value storage is one ROW per pair (or extension group): slot s
# holds lanes [s*KEY_LANES, (s+1)*KEY_LANES), padded to one 128-lane TPU
# tile row.  A row is then one contiguous HBM region the probe kernel
# fetches with a single DMA, and a slot store touches one row — a
# (pairs, slots, lanes) array would be laid out pair-minor on the chip,
# scattering a row across the array and making every slot store relayout
# the whole table.
ROW_LANES = 128
STASH_QUERIES = 128     # ops per stash-scan chunk (see _stash_find)
STASH_BLOCK = 32768     # stash entries per stash-scan step
STASH_SCAN_ROW = 1024   # stash entries per free-count block (_nth_free)
RESIDUAL_WIDTH = 64     # ops per residual-wave trip (see _residual_waves)


@dataclasses.dataclass(frozen=True)
class ContinuityConfig:
    """Static geometry of a continuity hash table."""

    num_buckets: int                 # N numbered buckets (must be even)
    bucket_slots: int = 4            # slots per bucket (paper: 4)
    sbuckets: int = 3                # shared SBuckets per pair (paper: 3)
    ext_frac: float = 1.0 / 10.0     # max fraction of pairs with added SBuckets
    ext_groups: int = 1              # added SBucket groups per extended pair
    stash_frac: float = 0.0          # stash slots as a fraction of main slots

    def __post_init__(self):
        assert self.num_buckets >= 2 and self.num_buckets % 2 == 0
        assert self.total_bits <= 32, (
            f"indicator must fit one atomic word: {self.total_bits} bits")
        # fp lane 1 keeps its top byte for the per-pair stash count, so main
        # slot fields must fit the remaining 56 bits of the fingerprint word
        assert self.slots_per_pair * FP_SLOT_BITS <= 64 - 8, (
            f"fingerprint fields overflow the fp word: {self.slots_per_pair}")
        # total_bits <= 32 already bounds both rows to one 128-lane tile
        assert max(self.slots_per_pair, self.ext_slots) * KEY_LANES <= ROW_LANES

    # -- derived geometry ---------------------------------------------------
    @property
    def num_pairs(self) -> int:
        return self.num_buckets // 2

    @property
    def slots_per_pair(self) -> int:          # main row width
        return (2 + self.sbuckets) * self.bucket_slots

    @property
    def seg_slots(self) -> int:               # slots per segment
        return (1 + self.sbuckets) * self.bucket_slots

    @property
    def ext_slots(self) -> int:               # slots per extension group
        return self.sbuckets * self.bucket_slots * self.ext_groups

    @property
    def total_bits(self) -> int:
        return self.slots_per_pair + self.ext_slots

    @property
    def ext_pool_pairs(self) -> int:
        return max(1, int(np.ceil(self.num_pairs * self.ext_frac)))

    @property
    def n_cand(self) -> int:
        return self.seg_slots + self.ext_slots

    @property
    def segment_bytes(self) -> int:
        """Payload of one one-sided segment fetch (indicator + fingerprint
        word + segment slots — the fp word rides in the segments' overlap)."""
        return INDICATOR_BYTES + FP_BYTES + self.seg_slots * SLOT_BYTES

    @property
    def row_bytes(self) -> int:
        """One full pair row: [B_even | indicator | fp | SBuckets | B_odd]."""
        return INDICATOR_BYTES + FP_BYTES + self.slots_per_pair * SLOT_BYTES

    @property
    def ext_bytes(self) -> int:
        return self.ext_slots * SLOT_BYTES

    @property
    def stash_slots(self) -> int:
        if self.stash_frac <= 0:
            return 0
        return max(1, int(np.ceil(
            self.num_pairs * self.slots_per_pair * self.stash_frac)))

    @property
    def stash_bytes(self) -> int:
        """The whole stash region (fetched as ONE contiguous READ)."""
        return self.stash_slots * (STASH_META_BYTES + SLOT_BYTES)

    def grow(self, factor: int = 2) -> "ContinuityConfig":
        return dataclasses.replace(self, num_buckets=self.num_buckets * factor)


@functools.lru_cache(maxsize=None)
def _probe_order(cfg: ContinuityConfig) -> np.ndarray:
    """(2, n_cand) int32: slot ids in probe-priority order per home parity."""
    bs, sp, seg = cfg.bucket_slots, cfg.slots_per_pair, cfg.seg_slots
    even = list(range(0, seg))                       # B_even then SBuckets, L->R
    odd = list(range(sp - 1, bs - 1, -1))            # B_odd then SBuckets, R->L
    ext = list(range(sp, sp + cfg.ext_slots))        # extension last, both
    return np.asarray([even + ext, odd + ext], dtype=np.int32)


class ContinuityTable(NamedTuple):
    """Functional table state. All arrays; geometry travels separately."""

    keys: jnp.ndarray        # (P, ROW_LANES) uint32 — slot s at lanes
    #   [s*KEY_LANES, (s+1)*KEY_LANES); `row_slots` gives the (.., S, KL) view
    vals: jnp.ndarray        # (P, ROW_LANES) uint32, same slot lanes
    indicator: jnp.ndarray   # (P,) uint32 — one valid bit per slot (+ext bits)
    version: jnp.ndarray     # (P,) uint32 — per-pair committed-op counter; the
    #   upper half of the 8B atomic indicator word (total_bits <= 32 leaves it
    #   free), bumped by the SAME store that flips the bits.  A bare indicator
    #   word is ABA-prone (two updates can walk a key back to its slot); the
    #   counter makes (version << 32 | indicator) a safe client version stamp.
    ext_keys: jnp.ndarray    # (PE, ROW_LANES) uint32 — one row per group
    ext_vals: jnp.ndarray    # (PE, ROW_LANES) uint32
    ext_map: jnp.ndarray     # (P,) int32 — pair -> ext group index, -1 = none
    ext_count: jnp.ndarray   # () int32 — allocated extension groups
    count: jnp.ndarray       # () int32 — live items
    fp: jnp.ndarray          # (P, 2) uint32 — the 8B fingerprint word next to
    #   the indicator: FP_SLOT_BITS per main slot (lane s//16, field s%16) and
    #   the per-pair stash count in lane 1's top byte.  Pure probe metadata:
    #   uncommitted stores never make an item visible (the indicator bit does),
    #   so fp writes are not PM-write-counted and Table I is unchanged.
    stash_keys: jnp.ndarray  # (T, KEY_LANES) uint32 — shared overflow stash
    stash_vals: jnp.ndarray  # (T, VAL_LANES) uint32
    stash_meta: jnp.ndarray  # (T,) uint32 — home pair + 1; 0 = free.  The 8B
    #   atomic commit word of a stash entry (payload first, meta second).


def create(cfg: ContinuityConfig) -> ContinuityTable:
    P, PE = cfg.num_pairs, cfg.ext_pool_pairs
    T = max(cfg.stash_slots, 1)
    return ContinuityTable(
        keys=jnp.zeros((P, ROW_LANES), U32),
        vals=jnp.zeros((P, ROW_LANES), U32),
        indicator=jnp.zeros((P,), U32),
        version=jnp.zeros((P,), U32),
        ext_keys=jnp.zeros((PE, ROW_LANES), U32),
        ext_vals=jnp.zeros((PE, ROW_LANES), U32),
        ext_map=jnp.full((P,), -1, I32),
        ext_count=jnp.zeros((), I32),
        count=jnp.zeros((), I32),
        fp=jnp.zeros((P, 2), U32),
        stash_keys=jnp.zeros((T, KEY_LANES), U32),
        stash_vals=jnp.zeros((T, VAL_LANES), U32),
        stash_meta=jnp.zeros((T,), U32),
    )


def row_prefix(rows: jnp.ndarray, n: int) -> jnp.ndarray:
    """(..., ROW_LANES) rows -> (..., n * KEY_LANES) lanes of their first n
    slots, flat."""
    return rows[..., :n * KEY_LANES]


def row_slots(rows: jnp.ndarray, n: int) -> jnp.ndarray:
    """(..., ROW_LANES) rows -> (..., n, KEY_LANES) view of their first n slots."""
    return row_prefix(rows, n).reshape(rows.shape[:-1] + (n, KEY_LANES))


def slot_lanes(slot: int) -> slice:
    """Lanes of slot ``slot`` within its row, as a host-side index."""
    return slice(slot * KEY_LANES, (slot + 1) * KEY_LANES)


def _slot_lanes(slot: jnp.ndarray) -> jnp.ndarray:
    """(..., KEY_LANES) lane indices of ``slot`` within its row."""
    return (jnp.asarray(slot, I32)[..., None] * KEY_LANES
            + jnp.arange(KEY_LANES, dtype=I32))


def row_get(rows: jnp.ndarray, row, slot) -> jnp.ndarray:
    """Slot payloads (..., KEY_LANES) at (row, slot)."""
    return rows[jnp.expand_dims(row, -1), _slot_lanes(slot)]


def _row_put(rows: jnp.ndarray, row, slot, x) -> jnp.ndarray:
    """Store payloads ``x`` at (row, slot): KEY_LANES single-lane stores
    per op.  Out-of-range rows are dropped (callers mask an op off by
    passing a huge row index).  Lane-point stores, not one 4-lane window
    per op: on the v5e the window scatter of 4096 ops into the 2^25-slot
    table took 17 ms, the point scatter 4 ms."""
    lanes = _slot_lanes(slot)
    return rows.at[jnp.expand_dims(jnp.asarray(row, I32), -1), lanes].set(
        jnp.asarray(x, rows.dtype).reshape(lanes.shape), mode="drop")


def capacity(cfg: ContinuityConfig, table: ContinuityTable) -> jnp.ndarray:
    """Total allocated storage units (paper's load-factor denominator)."""
    return (cfg.num_pairs * cfg.slots_per_pair + cfg.stash_slots
            + table.ext_count * cfg.ext_slots).astype(jnp.float32)


def load_factor(cfg: ContinuityConfig, table: ContinuityTable) -> jnp.ndarray:
    return table.count.astype(jnp.float32) / capacity(cfg, table)


def locate(cfg: ContinuityConfig, keys: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. (1): home bucket number -> (pair index, parity)."""
    h = hash128(keys)
    bno = h % U32(cfg.num_buckets)
    return (bno >> U32(1)).astype(I32), (bno & U32(1)).astype(I32)


def fingerprint(keys: jnp.ndarray) -> jnp.ndarray:
    """(B,) uint32 slot fingerprint from the second hash function (so it is
    independent of the bucket number, which the first hash determines)."""
    return hash128_2(jnp.asarray(keys, U32).reshape(-1, KEY_LANES)) & U32(FP_MASK)


def stash_count(table: ContinuityTable, pair: jnp.ndarray) -> jnp.ndarray:
    """Per-pair stash occupancy byte (fp lane 1, top byte).  May briefly read
    HIGH of the true count (insert bumps it before the meta commit, delete
    decrements after) — a conservative overcount only ever costs an extra
    stash READ, never a missed item."""
    return (table.fp[pair, 1] >> U32(STASH_CNT_SHIFT)) & U32(0xFF)


def _fp_lanes(fp: jnp.ndarray, update) -> jnp.ndarray:
    """Rebuild the (P, 2) fp words from ``update(lane, column) -> column``.

    fp writes go lane by lane on (P,) columns: a scatter whose target is
    the (P, 2) array itself makes XLA:TPU relayout the words (a compile
    that grows with the table and table-sized temporaries)."""
    return jnp.stack([update(w, fp[:, w]) for w in range(2)], axis=1)


def _fp_count_add(fp: jnp.ndarray, pair, delta: int) -> jnp.ndarray:
    """Bump the stash count byte (fp lane 1) of ``pair`` by ``delta`` (+1
    or -1); masked ops pass an out-of-range pair."""
    inc = U32(1) << U32(STASH_CNT_SHIFT)
    return _fp_lanes(fp, lambda w, col: col if w == 0 else col.at[pair].add(
        inc if delta > 0 else -inc, mode="drop"))


def _fp_apply(fp: jnp.ndarray, ok, pair, slot, fpv) -> jnp.ndarray:
    """Set the fp field of (pair, slot) to ``fpv`` for every ``ok`` op
    (main slots only; callers mask).  Ops claim pairwise-distinct
    (pair, slot), so their 2-bit fields are disjoint and two scatter-adds
    per lane (clear masks, then new bits) compose exactly like per-op
    read-modify-writes of the server's 4-byte fp-lane store (uncounted
    metadata)."""
    P = fp.shape[0]
    lane = slot // _FPW
    sh = U32(FP_SLOT_BITS) * (slot % _FPW).astype(U32)
    drop = jnp.iinfo(I32).max

    def update(w, col):
        idx = jnp.where(ok & (lane == w), pair, drop)
        clear = jnp.zeros((P,), U32).at[idx].add(U32(FP_MASK) << sh,
                                                 mode="drop")
        new = jnp.zeros((P,), U32).at[idx].add(
            (fpv & U32(FP_MASK)) << sh, mode="drop")
        return (col & ~clear) | new
    return _fp_lanes(fp, update)


# ---------------------------------------------------------------------------
# candidate gathering — the "one contiguous segment fetch" primitive
# ---------------------------------------------------------------------------

def _cand_payload(cfg: ContinuityConfig, rows, ext_rows, pair, ext_idx, cand):
    """(B, C, KL) payloads of each op's candidate slots in probe order: ONE
    row fetch of the pair, plus one of its extension group."""
    S, E = cfg.slots_per_pair, cfg.ext_slots
    slots = row_slots(rows[pair], S)                          # (B, S, KL)
    if E:
        slots = jnp.concatenate([slots, row_slots(ext_rows[ext_idx], E)], 1)
    return jnp.take_along_axis(slots, cand[..., None], 1)


def _gather_candidates(cfg: ContinuityConfig, table: ContinuityTable,
                       pair: jnp.ndarray, parity: jnp.ndarray,
                       ext_allowed: jnp.ndarray):
    """Fetch each key's candidate slots in probe order.

    Returns (cand_ids, cand_keys, cand_vals, valid, empty_ok, is_ext, has_ext):
      cand_ids  (B, C) int32   slot ids (>= SLOTS means extension slot)
      cand_keys (B, C, KL)     key lanes per candidate
      cand_vals (B, C, VL)
      valid     (B, C) bool    indicator bit set AND slot addressable
      slot_ok   (B, C) bool    slot addressable (main always; ext iff allowed)
    """
    probe = jnp.asarray(_probe_order(cfg))           # (2, C)
    cand = probe[parity]                             # (B, C)
    S = cfg.slots_per_pair
    is_ext = cand >= S

    ind = table.indicator[pair]                      # (B,)
    bits = (ind[:, None] >> cand.astype(U32)) & U32(1)

    eidx = table.ext_map[pair]                       # (B,)
    has_ext = eidx >= 0
    safe_e = jnp.maximum(eidx, 0)
    cand_keys = _cand_payload(cfg, table.keys, table.ext_keys, pair, safe_e,
                              cand)                  # (B, C, KL)
    cand_vals = _cand_payload(cfg, table.vals, table.ext_vals, pair, safe_e,
                              cand)

    slot_ok = jnp.where(is_ext, (has_ext | ext_allowed)[:, None], True)
    valid = (bits == 1) & slot_ok & jnp.where(is_ext, has_ext[:, None], True)
    return cand, cand_keys, cand_vals, valid, slot_ok, is_ext, has_ext


# ---------------------------------------------------------------------------
# client read path — single one-sided fetch (paper §III-B)
# ---------------------------------------------------------------------------

class LookupResult(NamedTuple):
    found: jnp.ndarray   # (B,) bool
    values: jnp.ndarray  # (B, VAL_LANES) uint32
    slot: jnp.ndarray    # (B,) int32 — matched slot id (or -1); stash hits
    #   report cfg.total_bits + stash_index
    pair: jnp.ndarray    # (B,) int32
    reads: jnp.ndarray   # (B,) int32 — contiguous fetches this lookup needed


@functools.partial(jax.jit, static_argnums=0)
def lookup(cfg: ContinuityConfig, table: ContinuityTable,
           keys: jnp.ndarray) -> LookupResult:
    """Batched client read: ONE contiguous segment fetch per key (+1 iff the
    pair has added SBuckets and the main segment missed, +1 iff the pair's
    stash count byte is non-zero and both main and extension missed)."""
    with jax.named_scope("lookup.probe"):
        keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
        pair, parity = locate(cfg, keys)
        f = jnp.zeros((keys.shape[0],), jnp.bool_)
        cand, ckeys, cvals, valid, _, is_ext, has_ext = _gather_candidates(
            cfg, table, pair, parity, ext_allowed=f)
        match = valid & jnp.all(ckeys == keys[:, None, :], axis=-1)
        found = jnp.any(match, axis=-1)
        first = jnp.argmax(match, axis=-1)                   # probe-priority
        slot = jnp.where(found,
                         jnp.take_along_axis(cand, first[:, None], 1)[:, 0], -1)
        values = jnp.take_along_axis(cvals, first[:, None, None], 1)[:, 0]
        values = jnp.where(found[:, None], values, 0)
        found_main = jnp.any(match & ~is_ext, axis=-1)
        reads = 1 + (has_ext & ~found_main).astype(I32)
    if cfg.stash_slots:
        found, values, slot, reads = _stash_tail(cfg, table, keys, pair,
                                                 found, values, slot, reads)
    return LookupResult(found, values, slot, pair, reads)


def _stash_tail(cfg, table: ContinuityTable, keys, pair, found, values, slot,
                reads):
    """Stash stage of a lookup whose main + extension probe gave ``found``:
    one dependent stash READ iff the pair's count byte is non-zero and the
    key missed; probe priority stays main > extension > stash (commits
    clear the stash entry LAST)."""
    with jax.named_scope("lookup.stash"):
        srd = (stash_count(table, pair) > 0) & ~found
        sfound, sidx = _stash_find(cfg, table, keys, pair, ~found)
        values = jnp.where(sfound[:, None], table.stash_vals[sidx], values)
        slot = jnp.where(sfound, cfg.total_bits + sidx, slot)
        return found | sfound, values, slot, reads + srd.astype(I32)


def _stash_find(cfg: ContinuityConfig, table: ContinuityTable, keys, pair,
                need):
    """First stash entry holding each key homed at its pair, for the ops in
    ``need``; the others report a miss.  Returns (found, sidx), (B,) each.

    The stash is one shared region, so finding an entry is a scan.  Only
    ops in ``need`` whose pair's count byte is non-zero take part (the
    byte never reads low, so no entry is missed).  They are packed to the
    front in batch order and compared in (STASH_QUERIES x STASH_BLOCK)
    tiles, so memory is bounded by one tile, not by batch x stash, and a
    batch with no such op does no scan at all."""
    B = keys.shape[0]
    need = need & (stash_count(table, pair) > 0)
    T = cfg.stash_slots
    Q, blk = min(STASH_QUERIES, B), min(STASH_BLOCK, T)
    nq = -(-B // Q) * Q
    drop = jnp.iinfo(I32).max
    rank = jnp.cumsum(need.astype(I32)) - 1
    order = jnp.full((nq,), B, I32).at[jnp.where(need, rank, drop)].set(
        jnp.arange(B, dtype=I32), mode="drop")       # needy ops first
    home = pair.astype(U32) + U32(1)
    meta, skeys = table.stash_meta, table.stash_keys

    def chunk(c, best):
        q = jax.lax.dynamic_slice(order, (c * Q,), (Q,))
        live = q < B
        qs = jnp.minimum(q, B - 1)
        qh, qk = home[qs], keys[qs]

        def block(j, bq):
            s0 = jnp.minimum(j * blk, T - blk)
            m = jax.lax.dynamic_slice(meta, (s0,), (blk,))
            k = jax.lax.dynamic_slice(skeys, (s0, 0), (blk, KEY_LANES))
            hit = live[:, None] & (m[None, :] == qh[:, None])
            for lane in range(KEY_LANES):
                hit = hit & (k[None, :, lane] == qk[:, lane, None])
            at = jnp.where(hit, s0 + jnp.arange(blk, dtype=I32), T)
            return jnp.minimum(bq, jnp.min(at, axis=1))

        bq = jax.lax.fori_loop(0, -(-T // blk), block,
                               jnp.full((Q,), T, I32))
        return best.at[jnp.where(live, q, drop)].set(bq, mode="drop")

    n_chunks = -(-jnp.sum(need.astype(I32)) // Q)
    best = jax.lax.fori_loop(0, n_chunks, chunk, jnp.full((B,), T, I32))
    found = best < T
    return found, jnp.where(found, best, 0)


def lookup_plan(cfg: ContinuityConfig, table: ContinuityTable, keys,
                res: LookupResult):
    """Verb plan of a lookup batch (paper §III-B): ONE contiguous segment
    READ per key — home bucket + neighbouring SBuckets in a single
    one-sided fetch, misses included — plus one DEPENDENT extension-group
    READ iff the pair has added SBuckets and the main segment missed, and
    one dependent stash-region READ iff the pair's stash count byte (read
    for free inside the fp word of the first fetch) is non-zero and both
    prior fetches missed.  The `CostLedger` every caller sees is derived
    from this plan (`repro.rdma.verbs.ledger_from_plan`)."""
    from repro.rdma import verbs as rv
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    pair, parity = locate(cfg, keys)
    # modeled row layout: [B_even | indicator | fp | SBuckets | B_odd] — the
    # indicator and fingerprint words sit in the two segments' OVERLAP, so
    # BOTH parities' fetches are genuinely contiguous ranges that include
    # them: even = [row, row + segment_bytes), odd = [row +
    # bucket_slots*SLOT_BYTES, row_end); a replay against a linear memory
    # image stays valid
    row_bytes = cfg.row_bytes
    seg_off = pair * row_bytes + parity * (cfg.bucket_slots * SLOT_BYTES)
    found_main = res.found & (res.slot >= 0) & (res.slot < cfg.slots_per_pair)
    ext = (table.ext_map[pair] >= 0) & ~found_main
    eidx = jnp.maximum(table.ext_map[pair], 0)
    lanes = [
        (rv.READ, rv.REGION_TABLE, seg_off, cfg.segment_bytes, 0, False),
        (jnp.where(ext, rv.READ, rv.NOOP), rv.REGION_EXT,
         eidx * cfg.ext_bytes, cfg.ext_bytes, 1, False),
    ]
    if cfg.stash_slots:
        found_me = res.found & (res.slot >= 0) & (res.slot < cfg.total_bits)
        srd = (stash_count(table, pair) > 0) & ~found_me
        lanes.append((jnp.where(srd, rv.READ, rv.NOOP), rv.REGION_STASH,
                      0, cfg.stash_bytes,
                      jnp.where(ext, 2, 1).astype(I32), False))
    return rv.pack(keys.shape[0], lanes)


def scan_plan(cfg: ContinuityConfig, table: ContinuityTable, keys, spans):
    """Verb plan of a YCSB-E short-scan batch: ONE contiguous multi-segment
    READ per scan, whatever the span.

    Continuity's SBuckets are CONTIGUOUS in PM — bucket pairs and their
    shared SBuckets lie in one linear row, rows adjacent — so scanning
    ``span`` records from the start key's row is a single one-sided READ
    of ``ceil(span / slots_per_pair)`` consecutive rows (indicator words
    ride along in the same range).  This is the access-pattern advantage
    YCSB-E exists to show: the multi-probe baselines pay one scattered
    READ per record, continuity pays one verb per scan."""
    from repro.rdma import verbs as rv
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    spans = jnp.maximum(jnp.asarray(spans, I32).reshape(-1), 1)
    pair, _ = locate(cfg, keys)
    row_bytes = cfg.row_bytes
    rows = -(-spans // cfg.slots_per_pair)          # ceil: rows crossed
    # clamp to the table's tail so the range stays a valid remote region
    start = jnp.minimum(pair, jnp.maximum(cfg.num_pairs - rows, 0))
    return rv.pack(keys.shape[0], [
        (rv.READ, rv.REGION_TABLE, start * row_bytes, rows * row_bytes,
         0, False)])


def version_stamp(cfg: ContinuityConfig, table: ContinuityTable, keys):
    """(B, 2) uint32 version stamp per key: ``[version, indicator]`` of the
    key's home pair — the two halves of the ONE 8-byte word every committed
    mutation atomically stores.  A client that caches a value together with
    this stamp can later validate the entry with a single 8-byte READ
    (`version_read_plan`): any committed insert/update/delete on the pair
    bumped ``version``, so stamp equality proves the cached value is the
    value a fresh lookup would return.  The counter half is what makes the
    check ABA-proof — indicator bits alone can walk back to a prior pattern
    (update a key twice and it returns to its original slot)."""
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    pair, _ = locate(cfg, keys)
    return jnp.stack([table.version[pair], table.indicator[pair]], axis=-1)


def version_read_plan(cfg: ContinuityConfig, table: ContinuityTable, keys):
    """Verb plan of a stamp validation batch: ONE depth-0 8-byte READ per key
    at the home pair's indicator-word offset.  This is the whole point of
    indicator-word validation: it costs `INDICATOR_BYTES` on the wire versus
    `segment_bytes` for a full lookup, with no server-side invalidation
    protocol at all.  (``table`` is unused — the plan depends only on the
    geometry — but rides along for the unified ``(cfg, table, keys)`` plan
    signature shared by every scheme module.)"""
    from repro.rdma import verbs as rv
    del table
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    pair, _ = locate(cfg, keys)
    return rv.single_read_plan(keys.shape[0], rv.REGION_TABLE,
                               pair * cfg.row_bytes, INDICATOR_BYTES)


# ---------------------------------------------------------------------------
# server write path — log-free failure atomicity (paper §III-C)
# ---------------------------------------------------------------------------
# Each op is split into explicit phases so tests can crash between them:
#   phase 1: write slot payload (key+value)        — PM write #1
#   phase 2: commit indicator with ONE atomic store — PM write #2
# A crash after phase 1 leaves the bit clear -> the partial write is invisible.

def _scatter_payload(table: ContinuityTable, ok, pair, slot_id, ext_idx,
                     key, val, slots_per_pair) -> ContinuityTable:
    """Phase 1: payload store (dropped when not ok via OOB index)."""
    S = slots_per_pair
    is_ext = slot_id >= S
    m_pair = jnp.where(ok & ~is_ext, pair, jnp.iinfo(I32).max)
    m_slot = jnp.minimum(slot_id, S - 1)
    e_idx = jnp.where(ok & is_ext, ext_idx, jnp.iinfo(I32).max)
    e_slot = jnp.maximum(slot_id - S, 0)
    return table._replace(
        keys=_row_put(table.keys, m_pair, m_slot, key),
        vals=_row_put(table.vals, m_pair, m_slot, val),
        ext_keys=_row_put(table.ext_keys, e_idx, e_slot, key),
        ext_vals=_row_put(table.ext_vals, e_idx, e_slot, val))


def _commit_indicator(table: ContinuityTable, ok, pair, new_word) -> ContinuityTable:
    """Phase 2: ONE atomic word store commits the operation.

    The same 8-byte store carries the per-pair version counter in its upper
    half, so the bump costs zero extra PM writes (Table I unchanged)."""
    m_pair = jnp.where(ok, pair, jnp.iinfo(I32).max)
    return table._replace(
        indicator=table.indicator.at[m_pair].set(new_word, mode="drop"),
        version=table.version.at[m_pair].add(U32(1), mode="drop"))


def _find_insert_slot(cfg, table, key):
    """Probe for the first empty candidate slot of ``key`` (paper's directional
    scan), allowing extension slots if allocated or allocatable."""
    key = key[None]
    pair, parity = locate(cfg, key)
    if cfg.ext_frac > 0:
        can_alloc = (table.ext_count < cfg.ext_pool_pairs)[None]
    else:
        can_alloc = jnp.zeros((1,), jnp.bool_)
    cand, _, _, valid, slot_ok, is_ext, has_ext = _gather_candidates(
        cfg, table, pair, parity, ext_allowed=can_alloc)
    empty = (~valid) & slot_ok
    ok = jnp.any(empty, axis=-1)[0]
    first = jnp.argmax(empty, axis=-1)
    slot = jnp.take_along_axis(cand, first[:, None], 1)[0, 0]
    need_alloc = ok & (slot >= cfg.slots_per_pair) & ~has_ext[0]
    ext_idx = jnp.where(need_alloc, table.ext_count, jnp.maximum(table.ext_map[pair[0]], 0))
    return pair[0], slot, ok, need_alloc, ext_idx


def _nth_free(meta: jnp.ndarray, nth: jnp.ndarray):
    """Index of the (nth+1)-th free stash entry (meta word 0), ascending,
    for each ``nth``; and the number of free entries.

    Free counts per block of STASH_SCAN_ROW entries, a search over the
    block totals, then one row scan per query: O(T) work and O(B x row)
    memory, where a sort or a whole-stash prefix sum compiles slowly."""
    T = meta.shape[0]
    blk = min(STASH_SCAN_ROW, T)
    nb = -(-T // blk)
    free = jnp.pad((meta == U32(0)).astype(I32), (0, nb * blk - T))
    free = free.reshape(nb, blk)
    upto = jnp.cumsum(jnp.sum(free, axis=1))          # free through block b
    b = jnp.minimum(jnp.searchsorted(upto, nth + 1), nb - 1)
    before = jnp.where(b > 0, upto[jnp.maximum(b - 1, 0)], 0)
    row = jnp.cumsum(free[b], axis=1)                 # (B, blk)
    j = jnp.sum(row < (nth + 1 - before)[:, None], axis=1)
    return jnp.minimum(b * blk + j, T - 1).astype(I32), upto[-1]


def _stash_insert_one(cfg, table: ContinuityTable, key, val, want):
    """Stash fallback of one insert (``want`` = probe failed, op active).

    Record order for crash atomicity: fp count bump (uncounted metadata,
    may overcount) -> payload store -> version bump -> meta word commit.
    The 8B meta word is the atomic commit point; a crash before it leaves
    the entry invisible.  3 counted PM writes."""
    pair, _ = locate(cfg, key[None])
    free = table.stash_meta == U32(0)
    sok = want & jnp.any(free)
    sidx = jnp.argmax(free).astype(I32)
    drop = jnp.iinfo(I32).max
    w = jnp.where(sok, sidx, drop)
    pw = jnp.where(sok, pair[0], drop)
    table = table._replace(
        fp=_fp_count_add(table.fp, pw, 1),
        stash_keys=table.stash_keys.at[w].set(key, mode="drop"),
        stash_vals=table.stash_vals.at[w].set(val, mode="drop"),
        version=table.version.at[pw].add(U32(1), mode="drop"),
        stash_meta=table.stash_meta.at[w].set(
            pair[0].astype(U32) + U32(1), mode="drop"),
        count=table.count + sok.astype(I32))
    return table, sok


def _insert_one(cfg, table: ContinuityTable, key, val, active=None):
    pair, slot, ok, need_alloc, ext_idx = _find_insert_slot(cfg, table, key)
    act = jnp.ones((), jnp.bool_) if active is None else active
    ok = ok & act
    need_alloc = need_alloc & act
    # extension allocation is metadata (rebuilt on recovery from ext_map scan)
    ext_map = table.ext_map.at[jnp.where(need_alloc, pair, jnp.iinfo(I32).max)].set(
        ext_idx, mode="drop")
    table = table._replace(ext_map=ext_map,
                           ext_count=table.ext_count + need_alloc.astype(I32))
    table = _scatter_payload(table, ok, pair, slot, ext_idx, key, val,
                             cfg.slots_per_pair)
    # fingerprint field of the NEW slot lands before the commit (main only)
    table = table._replace(fp=_fp_apply(
        table.fp, ok & (slot < cfg.slots_per_pair), pair, slot,
        fingerprint(key[None])[0]))
    new_word = table.indicator[pair] | jnp.where(ok, U32(1) << slot.astype(U32), U32(0))
    table = _commit_indicator(table, ok, pair, new_word)
    table = table._replace(count=table.count + ok.astype(I32))
    pm = jnp.where(ok, 2, 0).astype(I32)
    if cfg.stash_slots:
        table, sok = _stash_insert_one(cfg, table, key, val, act & ~ok)
        ok = ok | sok
        pm = pm + jnp.where(sok, 3, 0).astype(I32)
    return table, ok, pm


def _delete_one(cfg, table: ContinuityTable, key, active=None):
    res = lookup(cfg, table, key[None])
    ok, pair, slot = res.found[0], res.pair[0], res.slot[0]
    if active is not None:
        ok = ok & active
    in_stash = ok & (slot >= cfg.total_bits)
    okm = ok & ~in_stash
    safe = jnp.minimum(jnp.maximum(slot, 0), cfg.total_bits - 1).astype(U32)
    new_word = table.indicator[pair] & ~jnp.where(okm, U32(1) << safe, U32(0))
    table = _commit_indicator(table, okm, pair, new_word)
    pm = jnp.where(okm, 1, 0).astype(I32)
    if cfg.stash_slots:
        # stash delete: version bump -> meta clear (the atomic commit) ->
        # fp count decrement (uncounted, AFTER the commit so the count byte
        # never reads LOW of the true occupancy at any crash prefix)
        drop = jnp.iinfo(I32).max
        sidx = jnp.where(in_stash, slot - cfg.total_bits, drop)
        pw = jnp.where(in_stash, pair, drop)
        table = table._replace(
            version=table.version.at[pw].add(U32(1), mode="drop"),
            stash_meta=table.stash_meta.at[sidx].set(U32(0), mode="drop"))
        table = table._replace(
            fp=_fp_count_add(table.fp, pw, -1))
        pm = pm + jnp.where(in_stash, 2, 0).astype(I32)
    return table._replace(count=table.count - ok.astype(I32)), ok, pm


def _update_one(cfg, table: ContinuityTable, key, val, active=None):
    """Out-of-place update: both bit-flips land in ONE atomic indicator store.

    A key living in the stash relocates into an empty main/SBucket slot
    (payload -> fp -> indicator commit makes the new copy win by probe
    priority -> stash meta clear); with no empty candidate the update
    fails rather than tearing the stash entry in place."""
    res = lookup(cfg, table, key[None])
    found, pair, old_slot = res.found[0], res.pair[0], res.slot[0]
    if active is not None:
        found = found & active
    _, parity = locate(cfg, key[None])
    no = jnp.zeros((1,), jnp.bool_)
    cand, _, _, valid, slot_ok, _, _ = _gather_candidates(
        cfg, table, pair[None], parity, ext_allowed=no)
    empty = (~valid) & slot_ok
    has_empty = jnp.any(empty, axis=-1)[0]
    first = jnp.argmax(empty, axis=-1)
    new_slot = jnp.take_along_axis(cand, first[:, None], 1)[0, 0]
    in_stash = found & (old_slot >= cfg.total_bits)
    ok = found & has_empty
    okm = ok & ~in_stash
    oks = ok & in_stash
    ext_idx = jnp.maximum(table.ext_map[pair], 0)
    table = _scatter_payload(table, ok, pair, new_slot, ext_idx, key, val,
                             cfg.slots_per_pair)
    table = table._replace(fp=_fp_apply(
        table.fp, ok & (new_slot < cfg.slots_per_pair), pair, new_slot,
        fingerprint(key[None])[0]))
    safe_old = jnp.minimum(jnp.maximum(old_slot, 0), cfg.total_bits - 1)
    flip = jnp.where(okm, U32(1) << safe_old.astype(U32), U32(0)) | \
        (U32(1) << new_slot.astype(U32))
    new_word = table.indicator[pair] ^ jnp.where(ok, flip, U32(0))
    table = _commit_indicator(table, ok, pair, new_word)
    pm = jnp.where(okm, 2, 0).astype(I32)
    if cfg.stash_slots:
        drop = jnp.iinfo(I32).max
        sidx = jnp.where(oks, old_slot - cfg.total_bits, drop)
        pw = jnp.where(oks, pair, drop)
        table = table._replace(
            stash_meta=table.stash_meta.at[sidx].set(U32(0), mode="drop"),
            fp=_fp_count_add(table.fp, pw, -1))
        pm = pm + jnp.where(oks, 3, 0).astype(I32)
    return table, ok, pm


def _scan_op(cfg, one_fn):
    def step(carry, kv):
        table, ctr = carry
        *args, active = kv
        table, ok, pm = one_fn(cfg, table, *args, active)
        # masked-off ops count neither writes nor the ops denominator, so
        # per-op ledger averages stay meaningful for masked batches
        ctr = ctr.add(pm_writes=pm, ops=jnp.where(active, 1, 0))
        return (table, ctr), ok
    return step


def _active_mask(keys, mask):
    B = keys.shape[0]
    return (jnp.ones((B,), jnp.bool_) if mask is None
            else jnp.asarray(mask).reshape(B).astype(jnp.bool_))


@functools.partial(jax.jit, static_argnums=0)
def insert_serial(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
                  mask=None):
    """Reference ``lax.scan`` insert (batch-order deterministic). 2 PM
    writes/op (3 on the stash-fallback path). Kept as the crash-recovery
    path and equivalence oracle for the wave engine; production batches
    use ``insert``."""
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    vals = jnp.asarray(vals, U32).reshape(-1, VAL_LANES)
    (table, ctr), ok = jax.lax.scan(
        _scan_op(cfg, _insert_one), (table, pmem.CostLedger.zero()),
        (keys, vals, _active_mask(keys, mask)))
    return table, ok, ctr


@functools.partial(jax.jit, static_argnums=0)
def delete_serial(cfg: ContinuityConfig, table: ContinuityTable, keys,
                  mask=None):
    """Reference ``lax.scan`` delete. 1 PM write/op (indicator bit clear;
    2 for stash entries: version bump + meta clear)."""
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    (table, ctr), ok = jax.lax.scan(
        _scan_op(cfg, _delete_one), (table, pmem.CostLedger.zero()),
        (keys, _active_mask(keys, mask)))
    return table, ok, ctr


@functools.partial(jax.jit, static_argnums=0)
def update_serial(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
                  mask=None):
    """Reference ``lax.scan`` out-of-place update. 2 PM writes/op (3 when
    the op relocates a stash entry into the main row)."""
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    vals = jnp.asarray(vals, U32).reshape(-1, VAL_LANES)
    (table, ctr), ok = jax.lax.scan(
        _scan_op(cfg, _update_one), (table, pmem.CostLedger.zero()),
        (keys, vals, _active_mask(keys, mask)))
    return table, ok, ctr


# ---------------------------------------------------------------------------
# wave-vectorized mutation engine
# ---------------------------------------------------------------------------
# A batch of B mutations is scheduled into "waves": one stable sort by pair
# index clusters same-pair ops (keeping batch order inside a cluster), a
# segment scan assigns each op its intra-pair rank, and wave w holds every
# op of rank w.  All ops in a wave touch pairwise-distinct pairs, so a wave
# is one batched probe, one batched payload scatter (phase 1) and one
# batched set of independent one-word indicator stores (phase 2) — exactly
# B_w conflict-free applications of the paper's write protocol; same-pair
# ops serialize across waves in batch order (lock order == batch order).
#
# Execution strategy per op kind:
#   * ``insert``: occupancy per pair only GROWS, so every wave is
#     determined by the pre-batch indicator word — the op of intra-cohort
#     rank r takes the (r+1)-th empty candidate in its own probe order.
#     All waves therefore run FUSED in a single rank-indexed bit-select
#     pass over the 32-bit indicator words.  The one case where waves
#     genuinely interact — both parities of one pair contending for the
#     same middle SBucket slots — is detected exactly (see
#     ``_insert_fused``) and resolved by a residual wave ``while_loop``.
#   * ``update`` / ``delete``: occupancy mutates non-monotonically (bits
#     clear, items relocate), but with distinct keys every op's MATCH slot
#     is fixed by the pre-batch table — a slot's bit is only cleared by its
#     own unique matcher — so both ops also run FUSED from one pre-state
#     match pass.  ``delete`` needs no sequencing at all (clear masks of
#     distinct slots compose by OR); ``update``'s new-slot choices evolve
#     with the pair word, so a tiny rank loop over a (P,) word COPY
#     replays the allocation order — O(B) vector work per trip, none of
#     the table-wide gathers/scatters the old per-wave loop paid.  The one
#     genuine serialization point is a duplicate target (two ops resolving
#     to the SAME slot/stash row, i.e. the same key twice in a batch):
#     those run the exact residual wave ``while_loop``, whose trip count
#     is bounded by the contended cohorts alone — a hot pair no longer
#     serializes the full batch width (the old loop ran every cohort
#     ``max_collisions_per_pair`` heavy waves).

def _stable_order(cls: jnp.ndarray, num_class: int):
    """Stable ascending order of small int class ids.

    Packs (class, position) into ONE uint32 sort key when the product fits
    (single-array sort is ~2-3x faster on CPU/TPU than a key+payload sort),
    falling back to a stable argsort otherwise.  Returns ``(cls_s, idx_s)``.
    """
    B = cls.shape[0]
    width = 1 << max(1, (B - 1).bit_length())
    if (num_class + 1) * width < 2 ** 31:
        sk = jax.lax.sort(cls.astype(U32) * U32(width)
                          + jnp.arange(B, dtype=U32))
        return (sk // U32(width)).astype(I32), (sk & U32(width - 1)).astype(I32)
    idx = jnp.argsort(cls, stable=True).astype(I32)
    return cls[idx].astype(I32), idx


def _cohort_ranks(cls_s: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element within its (sorted, contiguous) class run."""
    B = cls_s.shape[0]
    ii = jnp.arange(B, dtype=I32)
    head = jnp.concatenate([jnp.ones((1,), jnp.bool_), cls_s[1:] != cls_s[:-1]])
    return ii - jax.lax.cummax(jnp.where(head, ii, 0))


def _plan_waves(cfg: ContinuityConfig, keys: jnp.ndarray, active: jnp.ndarray):
    """Group a batch into per-pair cohorts with ONE stable packed sort.

    Returns ``(pair, parity, rank, num_waves)``: ``rank[i]`` is op i's
    position among active same-pair ops in batch order (-1 if inactive);
    ops of equal rank touch pairwise-distinct pairs.
    """
    B = keys.shape[0]
    pair, parity = locate(cfg, keys)
    cls = jnp.where(active, pair, cfg.num_pairs)
    cls_s, order = _stable_order(cls, cfg.num_pairs)
    rank = jnp.zeros((B,), I32).at[order].set(_cohort_ranks(cls_s))
    rank = jnp.where(active, rank, -1)
    return pair, parity, rank, jnp.max(rank) + 1


def _residual_waves(cfg: ContinuityConfig, keys, unsafe, wave, state):
    """Run the exact residual wave loop over the ``unsafe`` ops.

    Waves run in rank order and each wave's ops touch pairwise-distinct
    pairs, so a wave may be applied in any split.  Each trip takes the
    next up-to-RESIDUAL_WIDTH ops of the current wave, compacted, so a
    trip's gathers and scatters scale with those ops, not with the batch:
    a hot key repeated k times costs k narrow trips.
    ``wave(table, idx, m) -> (table, ok, pm)`` applies the ops ``idx``
    (masked by ``m``); ``state`` is ``(table, ok, pm)`` and is returned
    with the waves applied."""
    B = keys.shape[0]
    W = min(RESIDUAL_WIDTH, B)
    _, _, rank, _ = _plan_waves(cfg, keys, unsafe)
    rank_s, order = _stable_order(jnp.where(unsafe, rank, B), B)
    order = jnp.concatenate([order, jnp.full((W,), B, I32)])
    rank_s = jnp.concatenate([rank_s, jnp.full((W,), B, I32)])
    drop = jnp.iinfo(I32).max

    def body(c):
        pos, t, ok, pm = c
        idx = jax.lax.dynamic_slice(order, (pos,), (W,))
        r = jax.lax.dynamic_slice(rank_s, (pos,), (W,))
        m = (r == r[0]) & (idx < B)
        idx = jnp.minimum(idx, B - 1)
        t, wok, wpm = wave(t, idx, m)
        ok = ok.at[jnp.where(m, idx, drop)].set(ok[idx] | wok, mode="drop")
        return pos + jnp.sum(m).astype(I32), t, ok, pm + wpm

    _, table, ok, pm = jax.lax.while_loop(
        lambda c: c[0] < jnp.sum(unsafe).astype(I32), body,
        (jnp.zeros((), I32),) + tuple(state))
    return table, ok, pm


@jax.custom_batching.custom_vmap
def _pin(xs):
    """Identity that pins its operands as materialized values.

    XLA CPU loop fusion re-computes a producer chain inside every consumer
    fusion; without this the sort/probe chain above a commit phase runs once
    PER SCATTER (~2x wall time at batch 512).  ``optimization_barrier`` has
    no batching rule in this jax version, so supply one (the barrier applies
    unchanged to the batched arrays)."""
    return jax.lax.optimization_barrier(xs)


@_pin.def_vmap
def _pin_vmap(axis_size, in_batched, xs):
    return jax.lax.optimization_barrier(xs), in_batched[0]


def _bitreverse32(v: jnp.ndarray) -> jnp.ndarray:
    c = U32
    v = ((v >> c(1)) & c(0x55555555)) | ((v & c(0x55555555)) << c(1))
    v = ((v >> c(2)) & c(0x33333333)) | ((v & c(0x33333333)) << c(2))
    v = ((v >> c(4)) & c(0x0F0F0F0F)) | ((v & c(0x0F0F0F0F)) << c(4))
    v = ((v >> c(8)) & c(0x00FF00FF)) | ((v & c(0x00FF00FF)) << c(8))
    return (v >> c(16)) | (v << c(16))


def _canonical_occupancy(cfg: ContinuityConfig, ind: jnp.ndarray,
                         parity: jnp.ndarray) -> jnp.ndarray:
    """Rearrange indicator words so bit p = the op's p-th probe candidate.

    Even homes probe slots 0..seg-1 ascending (bits pass through); odd homes
    probe slots S-1..S-seg descending (one vectorized bit-reversal); the
    extension bits follow at positions seg.. for both parities.
    """
    S, seg, E = cfg.slots_per_pair, cfg.seg_slots, cfg.ext_slots
    main = jnp.where(parity == 0, ind, _bitreverse32(ind) >> U32(32 - S))
    canon = main & U32((1 << seg) - 1)
    if E:
        canon = canon | (((ind >> U32(S)) & U32((1 << E) - 1)) << U32(seg))
    return canon


def _select_bit(word: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Position of the (n+1)-th set bit of each uint32 word (branch-free
    5-step binary descend on popcounts; valid iff n < popcount(word))."""
    pos = jnp.zeros_like(word)
    rem = n.astype(U32)
    for width in (16, 8, 4, 2, 1):
        low = (word >> pos) & U32((1 << width) - 1)
        cnt = jax.lax.population_count(low)
        go = rem >= cnt
        rem = jnp.where(go, rem - cnt, rem)
        pos = jnp.where(go, pos + U32(width), pos)
    return pos.astype(I32)


def _insert_wave_plan(cfg: ContinuityConfig, table: ContinuityTable,
                      pair, parity, m):
    """Probe phase of one insert wave: pick each active op's slot and grant
    extension groups by prefix sum over batch order (== serial grant order).

    Returns ``(slot, ok, grant, ext_idx)``.
    """
    B = pair.shape[0]
    if cfg.ext_frac > 0:
        pool_left = cfg.ext_pool_pairs - table.ext_count
    else:
        pool_left = jnp.zeros((), I32)
    opt = jnp.broadcast_to(pool_left > 0, (B,))      # optimistic ext candidacy
    cand, _, _, valid, slot_ok, is_ext, has_ext = _gather_candidates(
        cfg, table, pair, parity, ext_allowed=opt)
    empty = (~valid) & slot_ok
    first = jnp.argmax(empty, axis=-1)
    slot = jnp.take_along_axis(cand, first[:, None], 1)[:, 0]
    want = m & jnp.any(empty, -1) & (slot >= cfg.slots_per_pair) & ~has_ext
    grant = want & (jnp.cumsum(want.astype(I32)) - 1 < pool_left)
    # pool-denied allocators fall back to main-segment candidates only
    denied = want & ~grant
    empty = jnp.where(denied[:, None], empty & ~is_ext, empty)
    ok = m & jnp.any(empty, -1)
    first = jnp.argmax(empty, axis=-1)
    slot = jnp.take_along_axis(cand, first[:, None], 1)[:, 0]
    new_idx = table.ext_count + jnp.cumsum(grant.astype(I32)) - 1
    ext_idx = jnp.where(grant, new_idx, jnp.maximum(table.ext_map[pair], 0))
    return slot, ok, grant, ext_idx


def _insert_wave(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
                 pair, parity, m):
    """Execute one insert wave (active ops have distinct pairs)."""
    slot, ok, grant, ext_idx = _insert_wave_plan(cfg, table, pair, parity, m)
    ext_map = table.ext_map.at[jnp.where(grant, pair, jnp.iinfo(I32).max)].set(
        ext_idx, mode="drop")
    table = table._replace(
        ext_map=ext_map, ext_count=table.ext_count + jnp.sum(grant).astype(I32))
    table = _scatter_payload(table, ok, pair, slot, ext_idx, keys, vals,
                             cfg.slots_per_pair)                    # phase 1
    table = table._replace(fp=_fp_apply(
        table.fp, ok & (slot < cfg.slots_per_pair), pair, slot,
        fingerprint(keys)))
    word = table.indicator[pair] | jnp.where(
        ok, U32(1) << slot.astype(U32), U32(0))
    table = _commit_indicator(table, ok, pair, word)                # phase 2
    return table._replace(count=table.count + jnp.sum(ok).astype(I32)), \
        ok, grant, ext_idx


def _reorder_ext_pool(cfg: ContinuityConfig, table: ContinuityTable,
                      alloc_pos, alloc_idx):
    """Relabel extension groups granted this batch into batch-position order.

    Waves grant pool rows in (wave, batch) order while the serial reference
    grants in pure batch order; both grant the SAME pair set, so a pure
    metadata permutation of the pool rows + ``ext_map`` makes the wave
    result byte-identical to the serial one.
    """
    B = alloc_pos.shape[0]
    PE = cfg.ext_pool_pairs
    did = alloc_pos >= 0
    order = jnp.argsort(jnp.where(did, alloc_pos, jnp.iinfo(I32).max),
                        stable=True)                 # granters first
    did_s = did[order]
    old_s = alloc_idx[order]
    new_s = (table.ext_count - jnp.sum(did).astype(I32)
             + jnp.arange(B, dtype=I32))
    fwd = jnp.arange(PE, dtype=I32).at[
        jnp.where(did_s, old_s, PE)].set(new_s, mode="drop")
    inv = jnp.arange(PE, dtype=I32).at[
        jnp.where(did_s, new_s, PE)].set(old_s, mode="drop")
    ext_map = jnp.where(table.ext_map >= 0,
                        fwd[jnp.maximum(table.ext_map, 0)], -1)
    return table._replace(ext_keys=table.ext_keys[inv],
                          ext_vals=table.ext_vals[inv], ext_map=ext_map)


def _batch_arrays(keys, vals=None, mask=None):
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    B = keys.shape[0]
    if vals is not None:
        vals = jnp.asarray(vals, U32).reshape(-1, VAL_LANES)
    active = (jnp.ones((B,), jnp.bool_) if mask is None
              else jnp.asarray(mask).reshape(B).astype(jnp.bool_))
    return keys, vals, active


def _insert_fused(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
                  active):
    """All insert waves fused into one rank-indexed bit-select pass.

    For an insert-only batch, a pair's occupancy only grows, so the op of
    intra-cohort rank r takes the (r+1)-th empty candidate of the PRE-batch
    indicator word — every wave is computable up front.  The single genuine
    inter-wave interaction is a pair whose two home parities contend for the
    same middle SBucket slots; a cohort is contention-free (closed form ==
    serial for every interleaving) iff it is single-parity, or no op leaves
    its main segment AND the two directional claims fit disjointly:
    ``n_even + n_odd <= popcount(empty main slots)`` (claims from opposite
    ends of one ordered slot list can only collide if they outnumber it).
    Contended cohorts are flagged and returned for the residual wave loop.

    Returns ``(table, ok, unsafe_sorted, idx_s, grant_pos, grant_idx)`` —
    ``unsafe_sorted``/``idx_s`` flag contended cohorts (in sorted op order),
    and the grant records (batch position / pool row) feed the final
    serial-order pool relabel.
    """
    B = keys.shape[0]
    P = cfg.num_pairs
    S, seg, E = cfg.slots_per_pair, cfg.seg_slots, cfg.ext_slots
    pair, parity = locate(cfg, keys)
    drop = jnp.iinfo(I32).max

    # plan: one stable packed sort by (pair, parity); batch order within
    cls = jnp.where(active, pair * 2 + parity, 2 * P)
    cls_s, idx_s = _stable_order(cls, 2 * P)
    act = cls_s < 2 * P
    pair_s = jnp.minimum(cls_s >> 1, P - 1)
    par_s = cls_s & 1
    r2 = _cohort_ranks(cls_s)                 # rank within (pair, parity)
    # barriers pin each stage's results: XLA CPU otherwise re-fuses the
    # producer chain into every downstream scatter/gather (see EXPERIMENTS)
    act, pair_s, par_s, r2, idx_s = _pin((act, pair_s, par_s, r2, idx_s))

    ind = table.indicator[pair_s]
    has_ext = table.ext_map[pair_s] >= 0
    main_mask = U32((1 << seg) - 1)
    canon = _canonical_occupancy(cfg, ind, par_s)
    own_empty = jax.lax.population_count(~canon & main_mask).astype(I32)
    spill = act & (r2 >= own_empty)           # would leave its main segment
    canon, own_empty, spill = _pin((canon, own_empty, spill))

    # cohort safety: per-(pair, parity) op count + spill flag, ONE scatter
    rec = jnp.where(act, 1 + (spill.astype(I32) << 16), 0)
    cnt = jnp.zeros((2 * P,), I32).at[2 * pair_s + par_s].add(rec)
    own = cnt[2 * pair_s + par_s]
    oth = cnt[2 * pair_s + 1 - par_s]
    pair_empty = jax.lax.population_count(
        ~ind & U32((1 << S) - 1)).astype(I32)
    unsafe = act & (oth > 0) & (
        ((own >> 16) + (oth >> 16) > 0)
        | ((own & 0xFFFF) + (oth & 0xFFFF) > pair_empty))
    go = act & ~unsafe

    # extension grants, in batch order (== serial grant order); a spilling
    # op in a safe cohort is necessarily single-parity, and the trigger is
    # the first such op (rank == #empty main candidates).  The grant branch
    # also produces the (batch position, pool row) records for the final
    # pool relabel; batches without ext pressure skip all of it.
    no_grant = (jnp.zeros((B,), jnp.bool_), jnp.zeros((B,), I32),
                jnp.full((B,), -1, I32), jnp.full((B,), -1, I32))
    if cfg.ext_frac > 0 and E:
        pool_left = cfg.ext_pool_pairs - table.ext_count
        want = go & (r2 == own_empty) & ~has_ext
        def grants(_):
            wb = jnp.zeros((B,), jnp.bool_).at[idx_s].set(want)
            grank = jnp.cumsum(wb.astype(I32)) - 1
            gb = wb & (grank < pool_left)
            gi = jnp.where(gb, table.ext_count + grank, -1)
            return gb[idx_s], (table.ext_count + grank)[idx_s], \
                jnp.where(gb, jnp.arange(B, dtype=I32), -1), gi
        grant, new_eidx, gpos, gidx = jax.lax.cond(
            jnp.any(want) & (pool_left > 0), grants, lambda _: no_grant, 0)
        ext_map = table.ext_map.at[
            jnp.where(grant, pair_s, drop)].set(new_eidx, mode="drop")
        table = table._replace(
            ext_map=ext_map,
            ext_count=table.ext_count + jnp.sum(grant).astype(I32))
    else:
        grant, new_eidx, gpos, gidx = no_grant
    eidx = table.ext_map[pair_s]

    # rank-indexed slot selection on the canonical empty word
    ext_bits = U32(((1 << E) - 1) << seg) if E else U32(0)
    empty = ~canon & (main_mask | jnp.where(eidx >= 0, ext_bits, U32(0)))
    ok = go & (r2 < jax.lax.population_count(empty).astype(I32))
    pos = _select_bit(empty, r2)
    slot = jnp.where(pos < seg,
                     jnp.where(par_s == 0, pos, S - 1 - pos),
                     S + (pos - seg))

    # materialize the plan once: without this barrier XLA re-fuses the whole
    # sort/probe chain into EVERY commit scatter below (~2x the work)
    ok, slot, eidx, pair_s, idx_s, unsafe, k_s, v_s = _pin(
        (ok, slot, eidx, pair_s, idx_s, unsafe, keys[idx_s], vals[idx_s]))

    # phase 1: payload rows (ext rows cond-skipped)
    is_ext = slot >= S
    mrow = jnp.where(ok & ~is_ext, pair_s, drop)
    mslot = jnp.minimum(slot, S - 1)
    tkeys = _row_put(table.keys, mrow, mslot, k_s)
    tvals = _row_put(table.vals, mrow, mslot, v_s)

    def ext_rows(kv):
        ek, ev = kv
        erow = jnp.where(ok & is_ext, jnp.maximum(eidx, 0), drop)
        eslot = jnp.maximum(slot - S, 0)
        return _row_put(ek, erow, eslot, k_s), _row_put(ev, erow, eslot, v_s)
    tek, tev = jax.lax.cond(jnp.any(ok & is_ext), ext_rows,
                            lambda kv: kv, (table.ext_keys, table.ext_vals))

    # fingerprint fields of the committed main slots (pairwise-distinct
    # (pair, slot) claims, see `_fp_apply`)
    okm = ok & ~is_ext
    fp = _fp_apply(table.fp, okm, pair_s, jnp.minimum(slot, S - 1),
                   fingerprint(k_s))

    # phase 2: one-word indicator commits (bits of one pair are disjoint,
    # so a scatter-add is the batch of independent atomic ORs)
    add = jnp.zeros((P,), U32).at[jnp.where(ok, pair_s, drop)].add(
        U32(1) << slot.astype(U32), mode="drop")
    # version bumps ride the same per-pair commit scatter: one bump per
    # committed op, and per-pair counts are order-independent sums, so the
    # fused path stays byte-identical to the serial oracle
    vadd = jnp.zeros((P,), U32).at[jnp.where(ok, pair_s, drop)].add(
        U32(1), mode="drop")
    table = table._replace(
        keys=tkeys, vals=tvals, ext_keys=tek, ext_vals=tev,
        indicator=table.indicator | add,
        version=table.version + vadd, fp=fp,
        count=table.count + jnp.sum(ok).astype(I32))

    okb = jnp.zeros((B,), jnp.bool_).at[idx_s].set(ok)
    return table, okb, unsafe, idx_s, gpos, gidx


@functools.partial(jax.jit, static_argnums=0)
def insert(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
           mask=None):
    """Server-side batched insert on the wave engine. 2 PM writes/op.

    Byte-identical tables and counters to ``insert_serial`` (masked ops are
    skipped); same-pair ops execute in batch order. The one permitted
    divergence is extension-pool exhaustion mid-batch: grants are a true
    serialization point, so when the pool runs dry in a batch that also has
    parity-contended cohorts, a different set of pairs may win the last
    groups than under the serial order — and with them the admitted ops,
    ``ok`` flags and PM-write totals. Batches that do not exhaust the pool
    (every sweep/test config here) are exactly serial.
    """
    keys, vals, active = _batch_arrays(keys, vals, mask)
    B = keys.shape[0]
    table, ok, unsafe_s, idx_s, gpos, gidx = _insert_fused(
        cfg, table, keys, vals, active)

    def contended(args):
        # residual wave loop: only parity-contended cohorts (rare) run here
        table, ok, gpos, gidx = args
        unsafe = jnp.zeros((B,), jnp.bool_).at[idx_s].set(unsafe_s)
        pair, parity, rank, num_waves = _plan_waves(cfg, keys, unsafe)

        def body(c):
            w, t, okw, ap, ai = c
            t, wok, wgrant, weidx = _insert_wave(cfg, t, keys, vals, pair,
                                                 parity, rank == w)
            ap = jnp.where(wgrant, jnp.arange(B, dtype=I32), ap)
            ai = jnp.where(wgrant, weidx, ai)
            return w + 1, t, okw | wok, ap, ai

        _, table, ok, gpos, gidx = jax.lax.while_loop(
            lambda c: c[0] < num_waves, body,
            (jnp.zeros((), I32), table, ok, gpos, gidx))
        return table, ok, gpos, gidx

    table, ok, gpos, gidx = jax.lax.cond(
        jnp.any(unsafe_s), contended, lambda a: a, (table, ok, gpos, gidx))

    n_stash = jnp.zeros((), I32)
    if cfg.stash_slots:
        # stash fallback AFTER all main waves: probe outcomes never depend
        # on stash state, so deferring the failed ops preserves serial
        # byte-identity — op i's stash slot is the (rank_i+1)-th free slot
        # in ascending order, exactly what the serial first-free scan picks
        def stash_pass(args):
            t, okb = args
            fail = active & ~okb
            nth = jnp.cumsum(fail.astype(I32)) - 1       # batch-order rank
            sidx, nfree = _nth_free(t.stash_meta, nth)
            sok = fail & (nth < nfree)
            drop = jnp.iinfo(I32).max
            w = jnp.where(sok, sidx, drop)
            pair, _ = locate(cfg, keys)
            pw = jnp.where(sok, pair, drop)
            t = t._replace(
                fp=_fp_count_add(t.fp, pw, 1),
                stash_keys=t.stash_keys.at[w].set(keys, mode="drop"),
                stash_vals=t.stash_vals.at[w].set(vals, mode="drop"),
                version=t.version.at[pw].add(U32(1), mode="drop"),
                stash_meta=t.stash_meta.at[w].set(
                    pair.astype(U32) + U32(1), mode="drop"),
                count=t.count + jnp.sum(sok).astype(I32))
            return t, okb | sok, jnp.sum(sok).astype(I32)

        table, ok, n_stash = jax.lax.cond(
            jnp.any(active & ~ok), stash_pass,
            lambda a: (a[0], a[1], jnp.zeros((), I32)), (table, ok))

    if cfg.ext_frac > 0:
        # relabel pool rows into batch-grant order (== serial pool layout)
        table = jax.lax.cond(
            jnp.any(gpos >= 0),
            lambda t: _reorder_ext_pool(cfg, t, gpos, gidx),
            lambda t: t, table)
    ctr = pmem.CostLedger.zero().add(pm_writes=2 * jnp.sum(ok) + n_stash,
                                     ops=jnp.sum(active))
    return table, ok, ctr


def _gather_candidate_keys(cfg: ContinuityConfig, table: ContinuityTable,
                           pair, parity, ext_allowed):
    """``_gather_candidates`` minus the value gathers — the write-path waves
    only match/probe on keys (values are scattered, never read)."""
    probe = jnp.asarray(_probe_order(cfg))           # (2, C)
    cand = probe[parity]                             # (B, C)
    S = cfg.slots_per_pair
    is_ext = cand >= S
    ind = table.indicator[pair]
    bits = (ind[:, None] >> cand.astype(U32)) & U32(1)
    eidx = table.ext_map[pair]
    has_ext = eidx >= 0
    cand_keys = _cand_payload(cfg, table.keys, table.ext_keys, pair,
                              jnp.maximum(eidx, 0), cand)
    slot_ok = jnp.where(is_ext, (has_ext | ext_allowed)[:, None], True)
    valid = (bits == 1) & slot_ok & jnp.where(is_ext, has_ext[:, None], True)
    return cand, cand_keys, valid, slot_ok


def _delete_wave(cfg: ContinuityConfig, table: ContinuityTable, keys,
                 pair, parity, m):
    B = keys.shape[0]
    no = jnp.zeros((B,), jnp.bool_)
    cand, ckeys, valid, _ = _gather_candidate_keys(
        cfg, table, pair, parity, ext_allowed=no)
    match = valid & jnp.all(ckeys == keys[:, None, :], axis=-1)
    ok = m & jnp.any(match, -1)
    slot = jnp.take_along_axis(cand, jnp.argmax(match, -1)[:, None], 1)[:, 0]
    ok, slot = _pin((ok, slot))
    word = table.indicator[pair] & ~jnp.where(
        ok, U32(1) << jnp.maximum(slot, 0).astype(U32), U32(0))
    table = _commit_indicator(table, ok, pair, word)    # the ONE PM write
    pm = jnp.sum(ok).astype(I32)
    if cfg.stash_slots:
        # stash delete (probe priority: only when the main row missed);
        # active ops have distinct pairs, and a stash row belongs to one
        # pair, so the scatters below are conflict-free
        sok, sidx = _stash_find(cfg, table, keys, pair, m & ~ok)
        drop = jnp.iinfo(I32).max
        w = jnp.where(sok, sidx, drop)
        pw = jnp.where(sok, pair, drop)
        table = table._replace(
            version=table.version.at[pw].add(U32(1), mode="drop"),
            stash_meta=table.stash_meta.at[w].set(U32(0), mode="drop"))
        table = table._replace(
            fp=_fp_count_add(table.fp, pw, -1))
        ok = ok | sok
        pm = pm + 2 * jnp.sum(sok).astype(I32)
    return table._replace(count=table.count - jnp.sum(ok).astype(I32)), ok, pm


def _mutation_match(cfg: ContinuityConfig, table: ContinuityTable, keys,
                    pair, parity, *, probe="gather", qblock=8):
    """Pre-batch match resolution shared by the fused update/delete passes.

    Returns ``(found, mslot)``: the first main/extension slot (pair
    coordinates, probe order) holding each key, -1 on miss.  ``probe``
    selects the backend: ``"gather"`` is the pure-jnp candidate gather;
    ``"pallas"``/``"reference"`` run the mutation-plan kernel
    (`repro.kernels.mutate`) / its jnp oracle over the main segment (with
    the fingerprint pre-filter) plus the same jnp extension tail the
    kernel lookup path uses.  All backends are result-identical — visible
    slots always carry correct fingerprint fields."""
    B = keys.shape[0]
    if probe == "gather":
        no = jnp.zeros((B,), jnp.bool_)
        cand, ckeys, valid, _ = _gather_candidate_keys(
            cfg, table, pair, parity, ext_allowed=no)
        match = valid & jnp.all(ckeys == keys[:, None, :], axis=-1)
        found = jnp.any(match, -1)
        mslot = jnp.where(found, jnp.take_along_axis(
            cand, jnp.argmax(match, -1)[:, None], 1)[:, 0], -1)
        return found, mslot
    from repro.kernels import ops as K        # deferred: pallas import
    mmain, _, _ = K.mutation_plan(cfg, table, keys,
                                  use_kernel=probe == "pallas",
                                  qblock=qblock)
    found_m = mmain >= 0
    S, E = cfg.slots_per_pair, cfg.ext_slots
    if E:
        eidx = table.ext_map[pair]
        has_ext = eidx >= 0
        ebits = (table.indicator[pair][:, None]
                 >> (S + jnp.arange(E, dtype=U32))[None]) & U32(1)
        ekeys = row_slots(table.ext_keys[jnp.maximum(eidx, 0)], E)
        ematch = has_ext[:, None] & (ebits == 1) & jnp.all(
            ekeys == keys[:, None, :], axis=-1)
        efound = jnp.any(ematch, -1)
        eslot = S + jnp.argmax(ematch, -1).astype(I32)
    else:
        efound = jnp.zeros((B,), jnp.bool_)
        eslot = jnp.zeros((B,), I32)
    found = found_m | efound
    return found, jnp.where(found_m, mmain, jnp.where(efound, eslot, -1))


def _dup_targets(cfg: ContinuityConfig, pair, cm, mslot, cs, sidx):
    """Per-op flag: does another active op resolve to the SAME target (main
    or extension slot, or stash row)?

    A slot holds one key and pre-state probes of equal keys are identical,
    so duplicate targets <=> duplicate keys in the batch — the one case
    where update/delete waves genuinely interact.  One sort of the ops'
    flat (P * total_bits + stash) locations: equal neighbours are
    duplicates (O(B log B), nothing the size of the table)."""
    P, TB = cfg.num_pairs, cfg.total_bits
    B = pair.shape[0]
    loc = jnp.where(cm, pair * TB + jnp.maximum(mslot, 0),
                    jnp.where(cs, P * TB + sidx, jnp.iinfo(I32).max))
    hit = cm | cs
    loc_s, idx = jax.lax.sort((loc, jnp.arange(B, dtype=I32)), num_keys=1)
    same = loc_s[1:] == loc_s[:-1]
    no = jnp.zeros((1,), jnp.bool_)
    dup_s = jnp.concatenate([no, same]) | jnp.concatenate([same, no])
    return hit & jnp.zeros((B,), jnp.bool_).at[idx].set(dup_s)


def _delete_fused(cfg: ContinuityConfig, table: ContinuityTable, keys,
                  active, *, probe, qblock):
    """All delete waves fused into one pass.

    With distinct keys, each op's match slot comes from the PRE-batch table
    (a slot's bit is only ever cleared by its own unique matcher), cleared
    bits of one pair are disjoint (they OR-compose in any order), and
    version bumps are order-independent per-pair sums — so the whole batch
    commits in one scatter round.  Ops with duplicate targets (same key
    twice) are flagged ``unsafe`` and left untouched for the residual wave
    loop.  Returns ``(table, ok, pm, unsafe)``."""
    B = keys.shape[0]
    P = cfg.num_pairs
    drop = jnp.iinfo(I32).max
    pair, parity = locate(cfg, keys)
    found, mslot = _mutation_match(cfg, table, keys, pair, parity,
                                   probe=probe, qblock=qblock)
    cm = active & found
    if cfg.stash_slots:
        cs, sidx = _stash_find(cfg, table, keys, pair, active & ~found)
    else:
        cs = jnp.zeros((B,), jnp.bool_)
        sidx = jnp.zeros((B,), I32)
    unsafe = _dup_targets(cfg, pair, cm, mslot, cs, sidx)
    okm = cm & ~unsafe
    oks = cs & ~unsafe
    okm, oks, mslot, sidx, pair = _pin((okm, oks, mslot, sidx, pair))

    # phase 2 only — a delete's ONE counted PM write is the indicator
    # commit; committed ops clear pairwise-distinct bits, so a scatter-add
    # composes them exactly like the serial per-op stores.  ONE flat
    # scatter carries both halves of the 8-byte word (bit clears in [0,P),
    # version bumps in [P,2P)) — scatter dispatch is most of this pass's
    # cost on CPU, so the fewer the better
    idx = jnp.concatenate([jnp.where(okm, pair, drop),
                           jnp.where(okm | oks, pair + P, drop)])
    upd = jnp.concatenate([U32(1) << jnp.maximum(mslot, 0).astype(U32),
                           jnp.ones((B,), U32)])
    buf = jnp.zeros((2 * P,), U32).at[idx].add(upd, mode="drop")
    table = table._replace(indicator=table.indicator & ~buf[:P],
                           version=table.version + buf[P:])
    pm = jnp.sum(okm).astype(I32)
    if cfg.stash_slots:
        # stash tail gated on an actual stash hit: the common all-main
        # batch skips both scatters
        def stash_tail(sm_fp):
            sm, fp = sm_fp
            w = jnp.where(oks, sidx, drop)
            pw = jnp.where(oks, pair, drop)
            return (sm.at[w].set(U32(0), mode="drop"),
                    _fp_count_add(fp, pw, -1))
        sm, fp = jax.lax.cond(jnp.any(oks), stash_tail, lambda x: x,
                              (table.stash_meta, table.fp))
        table = table._replace(stash_meta=sm, fp=fp)
        pm = pm + 2 * jnp.sum(oks).astype(I32)
    ok = okm | oks
    table = table._replace(count=table.count - jnp.sum(ok).astype(I32))
    return table, ok, pm, unsafe


@functools.partial(jax.jit, static_argnums=0,
                   static_argnames=("probe", "qblock"))
def delete(cfg: ContinuityConfig, table: ContinuityTable, keys, mask=None,
           *, probe: str = "gather", qblock: int = 8):
    """Server-side batched delete on the wave engine. 1 PM write/op
    (2 for stash entries).

    One fused pass commits the whole batch; only duplicate-target cohorts
    (the same key deleted twice in one batch) fall back to the exact
    residual wave loop, whose trip count is bounded by those cohorts alone.
    ``probe`` selects the match backend (see `_mutation_match`)."""
    keys, _, active = _batch_arrays(keys, mask=mask)
    table, ok, pm, unsafe = _delete_fused(cfg, table, keys, active,
                                          probe=probe, qblock=qblock)

    # residual wave loop: ranks are planned over the UNSAFE ops alone, so
    # the trip count is bounded by the contended cohorts (zero trips — the
    # loop body never executes — for the common duplicate-free batch)
    pair, parity = locate(cfg, keys)
    table, ok, pm = _residual_waves(
        cfg, keys, unsafe,
        lambda t, i, m: _delete_wave(cfg, t, keys[i], pair[i], parity[i], m),
        (table, ok, pm))
    ctr = pmem.CostLedger.zero().add(pm_writes=pm, ops=jnp.sum(active))
    return table, ok, ctr


def _update_wave(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
                 pair, parity, m):
    B = keys.shape[0]
    no = jnp.zeros((B,), jnp.bool_)
    cand, ckeys, valid, slot_ok = _gather_candidate_keys(
        cfg, table, pair, parity, ext_allowed=no)
    match = valid & jnp.all(ckeys == keys[:, None, :], axis=-1)
    found = jnp.any(match, -1)
    old = jnp.take_along_axis(cand, jnp.argmax(match, -1)[:, None], 1)[:, 0]
    empty = (~valid) & slot_ok
    new = jnp.take_along_axis(cand, jnp.argmax(empty, -1)[:, None], 1)[:, 0]
    has_empty = jnp.any(empty, -1)
    if cfg.stash_slots:
        in_stash, sidx = _stash_find(cfg, table, keys, pair, m & ~found)
        found = found | in_stash
    else:
        in_stash = jnp.zeros((B,), jnp.bool_)
        sidx = jnp.zeros((B,), I32)
    ok = m & found & has_empty
    okm = ok & ~in_stash
    oks = ok & in_stash
    ext_idx = jnp.maximum(table.ext_map[pair], 0)
    ok, okm, oks, old, new, ext_idx = _pin((ok, okm, oks, old, new, ext_idx))
    table = _scatter_payload(table, ok, pair, new, ext_idx, keys, vals,
                             cfg.slots_per_pair)                    # phase 1
    table = table._replace(fp=_fp_apply(
        table.fp, ok & (new < cfg.slots_per_pair), pair, new,
        fingerprint(keys)))
    flip = jnp.where(okm, U32(1) << jnp.maximum(old, 0).astype(U32), U32(0)) \
        | (U32(1) << new.astype(U32))
    word = table.indicator[pair] ^ jnp.where(ok, flip, U32(0))
    table = _commit_indicator(table, ok, pair, word)                # phase 2
    pm = 2 * jnp.sum(okm).astype(I32)
    if cfg.stash_slots:
        # stash relocation tail: the commit above made the main copy win by
        # probe priority, so the meta clear only removes a shadowed entry
        drop = jnp.iinfo(I32).max
        w = jnp.where(oks, sidx, drop)
        pw = jnp.where(oks, pair, drop)
        table = table._replace(
            stash_meta=table.stash_meta.at[w].set(U32(0), mode="drop"),
            fp=_fp_count_add(table.fp, pw, -1))
        pm = pm + 3 * jnp.sum(oks).astype(I32)
    return table, ok, pm


def _update_fused(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
                  active, *, probe, qblock):
    """All update waves fused into one rank-indexed pass.

    With distinct keys, each op's OLD slot is fixed by the pre-batch table
    (only an op's own matcher frees its slot), so the one state that
    genuinely evolves mid-batch is the pair's occupancy word: op r's new
    slot is the first empty probe candidate of the word AFTER ranks < r
    applied.  That allocation order is replayed on a (P,) COPY of the
    indicator words — O(B) gathers + one (P,) scatter per trip, none of
    the table-wide key/value traffic the old per-wave loop paid — and the
    batch then commits in one scatter round: payload stores to
    pairwise-distinct slots (each slot is freed at most once, by its
    unique matcher, and claimed at most once), fingerprint fields as two
    disjoint scatter-adds, indicator words from the evolved copy, version
    bumps as per-pair sums.  Duplicate-target cohorts poison their whole
    pair (allocation order entangles every op of the pair) and fall back
    to the residual wave loop.  Returns ``(table, ok, pm, unsafe)``."""
    B = keys.shape[0]
    P = cfg.num_pairs
    S, seg, E = cfg.slots_per_pair, cfg.seg_slots, cfg.ext_slots
    drop = jnp.iinfo(I32).max
    pair, parity = locate(cfg, keys)
    found, mslot = _mutation_match(cfg, table, keys, pair, parity,
                                   probe=probe, qblock=qblock)
    if cfg.stash_slots:
        in_stash, sidx = _stash_find(cfg, table, keys, pair,
                                       active & ~found)
    else:
        in_stash = jnp.zeros((B,), jnp.bool_)
        sidx = jnp.zeros((B,), I32)
    cm = active & found
    cs = active & in_stash
    dup = _dup_targets(cfg, pair, cm, mslot, cs, sidx)
    # unlike delete, a duplicate target serializes its WHOLE pair: new-slot
    # allocation threads through every op of the cohort in batch order
    pdup = jnp.zeros((P,), jnp.bool_).at[
        jnp.where(dup, pair, drop)].set(True, mode="drop")
    unsafe = active & pdup[pair]
    cand_op = (cm | cs) & ~unsafe
    cand_op, found, mslot, in_stash, sidx = _pin(
        (cand_op, found, mslot, in_stash, sidx))

    # rank-sequential new-slot allocation on the word copy
    _, _, rank, num_waves = _plan_waves(cfg, keys, cand_op)
    main_mask = U32((1 << seg) - 1)
    ext_bits = U32(((1 << E) - 1) << seg) if E else U32(0)
    has_ext = table.ext_map[pair] >= 0
    is_m = cand_op & found                   # main/ext match frees its bit

    def body(c):
        w, evo, new_slot, okv = c
        sel = cand_op & (rank == w)
        word = evo[pair]
        canon = _canonical_occupancy(cfg, word, parity)
        empty = ~canon & (main_mask | jnp.where(has_ext, ext_bits, U32(0)))
        okw = sel & (empty != U32(0))
        pos = _select_bit(empty, jnp.zeros((B,), I32))
        ns = jnp.where(pos < seg,
                       jnp.where(parity == 0, pos, S - 1 - pos),
                       S + (pos - seg))
        flip = (U32(1) << ns.astype(U32)) | jnp.where(
            is_m, U32(1) << jnp.maximum(mslot, 0).astype(U32), U32(0))
        evo = evo.at[jnp.where(okw, pair, drop)].set(
            word ^ flip, mode="drop")
        return w + 1, evo, jnp.where(okw, ns, new_slot), okv | okw

    _, evo, new_slot, ok = jax.lax.while_loop(
        lambda c: c[0] < num_waves, body,
        (jnp.zeros((), I32), table.indicator, jnp.zeros((B,), I32),
         jnp.zeros((B,), jnp.bool_)))
    okm = ok & ~in_stash
    oks = ok & in_stash
    eidx = jnp.maximum(table.ext_map[pair], 0)
    ok, okm, oks, new_slot, eidx, evo = _pin(
        (ok, okm, oks, new_slot, eidx, evo))

    # phase 1: payload rows (ext rows cond-skipped)
    is_ext = new_slot >= S
    mrow = jnp.where(ok & ~is_ext, pair, drop)
    mslot_new = jnp.minimum(new_slot, S - 1)
    tkeys = _row_put(table.keys, mrow, mslot_new, keys)
    tvals = _row_put(table.vals, mrow, mslot_new, vals)

    def ext_rows(kv):
        ek, ev = kv
        erow = jnp.where(ok & is_ext, eidx, drop)
        eslot = jnp.maximum(new_slot - S, 0)
        return _row_put(ek, erow, eslot, keys), _row_put(ev, erow, eslot, vals)
    tek, tev = jax.lax.cond(jnp.any(ok & is_ext), ext_rows,
                            lambda kv: kv, (table.ext_keys, table.ext_vals))

    # fingerprint fields of the claimed slots (disjoint 2-bit fields, see
    # `_fp_apply`) and the per-pair version bumps
    fp = _fp_apply(table.fp, ok & ~is_ext, pair, mslot_new, fingerprint(keys))
    vadd = jnp.zeros((P,), U32).at[jnp.where(ok, pair, drop)].add(
        U32(1), mode="drop")

    # phase 2: indicator words straight from the evolved copy (equal to the
    # serial per-op XOR chain), version bumps as per-pair sums
    table = table._replace(
        keys=tkeys, vals=tvals, ext_keys=tek, ext_vals=tev,
        indicator=evo, version=table.version + vadd, fp=fp)
    pm = 2 * jnp.sum(okm).astype(I32)
    if cfg.stash_slots:
        # stash relocation tail (commit first: the main copy wins by probe
        # priority, so the meta clear only removes a shadowed entry),
        # gated on an actual relocation so all-main batches skip it
        def stash_tail(sm_fp):
            sm, fp = sm_fp
            w = jnp.where(oks, sidx, drop)
            pw = jnp.where(oks, pair, drop)
            return (sm.at[w].set(U32(0), mode="drop"),
                    _fp_count_add(fp, pw, -1))
        sm, fp = jax.lax.cond(jnp.any(oks), stash_tail, lambda x: x,
                              (table.stash_meta, table.fp))
        table = table._replace(stash_meta=sm, fp=fp)
        pm = pm + 3 * jnp.sum(oks).astype(I32)
    return table, ok, pm, unsafe


@functools.partial(jax.jit, static_argnums=0,
                   static_argnames=("probe", "qblock"))
def update(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
           mask=None, *, probe: str = "gather", qblock: int = 8):
    """Server-side batched out-of-place update on the wave engine.
    2 PM writes/op; both bit-flips land in ONE atomic indicator store
    (3 writes when the op relocates a stash entry into the main row).

    One fused pass commits the whole batch (new-slot allocation replayed
    on a (P,) word copy); only pairs with duplicate targets fall back to
    the exact residual wave loop, whose trip count is bounded by those
    cohorts alone.  ``probe`` selects the match backend
    (see `_mutation_match`)."""
    keys, vals, active = _batch_arrays(keys, vals, mask)
    table, ok, pm, unsafe = _update_fused(cfg, table, keys, vals, active,
                                          probe=probe, qblock=qblock)

    # residual wave loop: ranks are planned over the UNSAFE (duplicate-
    # target-pair) ops alone, so the trip count is bounded by the
    # contended cohorts — zero trips for the common duplicate-free batch
    pair, parity = locate(cfg, keys)
    table, ok, pm = _residual_waves(
        cfg, keys, unsafe,
        lambda t, i, m: _update_wave(cfg, t, keys[i], vals[i], pair[i],
                                     parity[i], m),
        (table, ok, pm))
    ctr = pmem.CostLedger.zero().add(pm_writes=pm, ops=jnp.sum(active))
    return table, ok, ctr


# ---------------------------------------------------------------------------
# parallel (conflict-resolved) insert — one wave of the engine; used by the
# serving page table, where a batch touches mostly-distinct pairs.  Same-pair
# duplicates past the first are reported for retry (batch-order priority ==
# lock order).  Unlike the old O(B^2) all-pairs conflict matrix this costs
# one argsort, and extension groups CAN be granted (prefix-sum allocation).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=0)
def insert_parallel(cfg: ContinuityConfig, table: ContinuityTable, keys, vals,
                    mask=None):
    keys, vals, active = _batch_arrays(keys, vals, mask)
    pair, parity, rank, _ = _plan_waves(cfg, keys, active)
    table, ok, _, _ = _insert_wave(cfg, table, keys, vals, pair, parity,
                                   rank == 0)
    retry = active & ~ok
    return table, ok, retry


# ---------------------------------------------------------------------------
# resizing (paper §III-C "Log-free Resizing") + recovery
# ---------------------------------------------------------------------------

def extract_items(cfg: ContinuityConfig, table: ContinuityTable):
    """All live (key, value) slots as flat arrays + validity mask (jittable)."""
    P, S, E = cfg.num_pairs, cfg.slots_per_pair, cfg.ext_slots
    bits = (table.indicator[:, None] >> jnp.arange(S, dtype=U32)[None]) & U32(1)
    mkeys = row_slots(table.keys, S).reshape(P * S, KEY_LANES)
    mvals = row_slots(table.vals, S).reshape(P * S, VAL_LANES)
    mmask = (bits == 1).reshape(P * S)
    ebits = (table.indicator[:, None] >> (S + jnp.arange(E, dtype=U32))[None]) & U32(1)
    has = table.ext_map >= 0
    PE = cfg.ext_pool_pairs
    # scatter pair-order ext validity into pool order
    pool_mask = jnp.zeros((PE, E), jnp.bool_).at[
        jnp.where(has, table.ext_map, PE), :].set(
        (ebits == 1) & has[:, None], mode="drop")
    ekeys = row_slots(table.ext_keys, E).reshape(PE * E, KEY_LANES)
    evals = row_slots(table.ext_vals, E).reshape(PE * E, VAL_LANES)
    keys = jnp.concatenate([mkeys, ekeys], 0)
    vals = jnp.concatenate([mvals, evals], 0)
    mask = jnp.concatenate([mmask, pool_mask.reshape(PE * E)], 0)
    if cfg.stash_slots:
        keys = jnp.concatenate([keys, table.stash_keys], 0)
        vals = jnp.concatenate([vals, table.stash_vals], 0)
        mask = jnp.concatenate([mask, table.stash_meta != U32(0)], 0)
    return keys, vals, mask


def resize(cfg: ContinuityConfig, table: ContinuityTable, factor: int = 2):
    """Rehash into a table with ``factor``x buckets (fast batched path).

    The crash-faithful per-item path (insert-to-new THEN delete-from-old, two
    indicator commits in that order) is ``resize_stepwise``; this batched path
    produces the same final state and is what production resizing uses.
    """
    new_cfg = cfg.grow(factor)
    new = create(new_cfg)
    # seed versions strictly above the old table's max: stamps cached against
    # the old geometry can then never compare equal to a post-resize stamp
    new = new._replace(version=jnp.full(
        (new_cfg.num_pairs,), jnp.max(table.version) + U32(1), U32))
    keys, vals, mask = extract_items(cfg, table)
    new, _, _ = insert(new_cfg, new, keys, vals, mask)
    return new_cfg, new


def resize_stepwise(cfg, table, new_cfg, new_table, max_items: int):
    """Move up to ``max_items`` live items old->new, one at a time, with the
    paper's ordering: insert into new, commit, then delete from old. Returns
    (old, new, moved). Used by crash-recovery tests (host loop)."""
    moved = 0
    for _ in range(max_items):
        keys, vals, mask = extract_items(cfg, table)
        idx = int(jnp.argmax(mask))
        if not bool(mask[idx]):
            break
        k, v = keys[idx], vals[idx]
        new_table, ok, _ = _insert_one(new_cfg, new_table, k, v)
        table, _, _ = _delete_one(cfg, table, k)
        moved += int(ok)
    return table, new_table, moved


def recover(cfg, old_table, new_cfg, new_table):
    """Paper §III-C recovery: after restart mid-resize, for each item still in
    the old table, delete it if it already reached the new table, otherwise
    move it (insert-to-new then delete-from-old); finishes the resize."""
    keys, vals, mask = extract_items(cfg, old_table)
    kn, vn, mn = np.asarray(keys), np.asarray(vals), np.asarray(mask)
    for i in np.nonzero(mn)[0]:
        k = jnp.asarray(kn[i])
        v = jnp.asarray(vn[i])
        res = lookup(new_cfg, new_table, k[None])
        if not bool(res.found[0]):
            new_table, _, _ = _insert_one(new_cfg, new_table, k, v)
        old_table, _, _ = _delete_one(cfg, old_table, k)
    return old_table, new_table


def items_host(cfg, table):
    """Live items as a python dict {key_bytes: value_bytes} (tests only)."""
    keys, vals, mask = extract_items(cfg, table)
    kn, vn, mn = np.asarray(keys), np.asarray(vals), np.asarray(mask)
    out = {}
    for i in np.nonzero(mn)[0]:
        out[kn[i].tobytes()] = vn[i].tobytes()
    return out


# ---------------------------------------------------------------------------
# incremental split — online resize, one bucket-group cohort per step
# ---------------------------------------------------------------------------
# The intra-node port of cluster/migration.py's copy -> token-cutover ->
# cleanup protocol.  Growing ``num_buckets`` by an even factor preserves a
# key's bucket parity and maps every item homed at old pair p into a new
# pair of the form p + k*P (k < factor), so ONE old pair is a closed
# rehash cohort: copy its items into the new table (insert-if-absent, so a
# replayed step is idempotent), flip the pair's 8-byte split token with ONE
# atomic store — the commit point that switches routing — then delete the
# moved items from the old table as cleanup.  Live traffic routes purely
# by token: lookups and writes for a key go to the new table iff
# ``token[old_pair] != 0``, so at every crash prefix the union of
# {old items, token==0} and {new items, token==1} is exactly the original
# item set, with zero log records (see repro.consistency.split).

class SplitState(NamedTuple):
    """In-flight incremental resize (functional, host-stepped)."""

    token: jnp.ndarray      # (P_old,) uint32 — 1 = cohort cut over
    next_pair: jnp.ndarray  # () int32 — first pair not yet moved


def split_begin(cfg: ContinuityConfig, table: ContinuityTable,
                factor: int = 2):
    """Open an incremental split to a ``factor``x table.  Returns
    ``(new_cfg, new_table, state)``; the old table is untouched."""
    assert factor >= 2 and factor % 2 == 0, "parity-preserving factors only"
    new_cfg = cfg.grow(factor)
    new = create(new_cfg)
    # seed versions strictly above the old table's max: stamps cached against
    # the old geometry can then never compare equal to a post-split stamp
    new = new._replace(version=jnp.full(
        (new_cfg.num_pairs,), jnp.max(table.version) + U32(1), U32))
    state = SplitState(token=jnp.zeros((cfg.num_pairs,), U32),
                       next_pair=jnp.zeros((), I32))
    return new_cfg, new, state


@functools.partial(jax.jit, static_argnums=0)
def cohort_items(cfg: ContinuityConfig, table: ContinuityTable, pair):
    """Fixed-shape candidate rows of ONE pair: (keys, vals, live) where the
    row count S+E+T is static — so every split step jits to one program."""
    S, E, T = cfg.slots_per_pair, cfg.ext_slots, cfg.stash_slots
    pair = jnp.asarray(pair, I32)
    ind = table.indicator[pair]
    mmask = ((ind >> jnp.arange(S, dtype=U32)) & U32(1)) == 1
    eidx = table.ext_map[pair]
    ebits = ((ind >> (U32(S) + jnp.arange(E, dtype=U32))) & U32(1)) == 1
    emask = ebits & (eidx >= 0)
    safe_e = jnp.maximum(eidx, 0)
    keys = jnp.concatenate([row_slots(table.keys[pair], S),
                            row_slots(table.ext_keys[safe_e], E)], 0)
    vals = jnp.concatenate([row_slots(table.vals[pair], S),
                            row_slots(table.ext_vals[safe_e], E)], 0)
    mask = jnp.concatenate([mmask, emask], 0)
    if T:
        smask = table.stash_meta == pair.astype(U32) + U32(1)
        keys = jnp.concatenate([keys, table.stash_keys], 0)
        vals = jnp.concatenate([vals, table.stash_vals], 0)
        mask = jnp.concatenate([mask, smask], 0)
    return keys, vals, mask


def split_step(cfg: ContinuityConfig, table: ContinuityTable,
               new_cfg: ContinuityConfig, new_table: ContinuityTable,
               state: SplitState, budget: int = 1):
    """Move up to ``budget`` cohorts (host loop; each cohort is the paper's
    insert-to-new -> commit -> delete-from-old ordering, with the token
    flip as the single routing commit point).  Returns
    ``(table, new_table, state, moved)``."""
    P = cfg.num_pairs
    start = int(state.next_pair)
    token = state.token
    moved = 0
    for p in range(start, min(start + int(budget), P)):
        kc, vc, mc = cohort_items(cfg, table, p)
        already = lookup(new_cfg, new_table, kc).found
        new_table, okn, _ = insert(new_cfg, new_table, kc, vc,
                                   mc & ~already)       # idempotent copy
        token = token.at[p].set(U32(1))                 # atomic cutover
        table, _, _ = delete(cfg, table, kc, mc)        # cleanup
        moved += int(jnp.sum(mc))
    state = SplitState(token=token,
                       next_pair=jnp.asarray(min(start + int(budget), P), I32))
    return table, new_table, state, moved


def split_done(cfg: ContinuityConfig, state: SplitState) -> bool:
    return int(state.next_pair) >= cfg.num_pairs


def split_route(cfg: ContinuityConfig, state: SplitState, keys):
    """(B,) bool — True where the key's cohort has cut over (route to new)."""
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    pair, _ = locate(cfg, keys)
    return state.token[pair] != U32(0)


def split_lookup(cfg: ContinuityConfig, table: ContinuityTable,
                 new_cfg: ContinuityConfig, new_table: ContinuityTable,
                 state: SplitState, keys) -> LookupResult:
    """Token-routed dual read during a split: each key consults exactly the
    table its token names (the copy phase holds items in BOTH tables, but
    the un-flipped token keeps the old copy authoritative until cutover)."""
    keys = jnp.asarray(keys, U32).reshape(-1, KEY_LANES)
    cut = split_route(cfg, state, keys)
    r_old = lookup(cfg, table, keys)
    r_new = lookup(new_cfg, new_table, keys)
    pick = lambda a, b: jnp.where(
        cut.reshape(cut.shape + (1,) * (a.ndim - 1)), b, a)
    return LookupResult(*(pick(a, b) for a, b in zip(r_old, r_new)))
