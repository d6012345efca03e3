"""End-to-end YCSB client/server simulation over the RDMA transport.

The paper's headline numbers (1.45x–2.43x throughput, ~1.7x latency) are
end-to-end: a client runs a YCSB mix against a remote PM server, and the
scheme decides what every op puts on the wire.  This module closes that
loop: the scheme executes (jitted, exact), its `OpResult.plan` is posted
through one `RemoteMemory` endpoint with doorbell batching, and the
analytical `LinkModel` prices the batch — yielding per-scheme throughput
and p50/p99 latency whose RELATIVE ordering is the reproducible claim
(continuity > level > pfarm on read-heavy mixes; absolutes depend on the
calibration constants, all in `LinkModel`).

Reads are priced from the scheme's exact verb plan.  Writes are priced
from a plan SYNTHESIZED from the scheme's own `CostLedger`: one ordered
remote WRITE (+ remote-persist fence, Kashyap et al.) per PM write the op
charges — payload stores as slot-sized WRITEs, the final 8-byte commit
word last.  That reproduces the write-side round-trip asymmetry exactly
where the paper locates it (continuity 2 fenced writes vs P-FaRM-KV's 5
RECIPE-logged writes).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np

from repro import obs
from repro.core.continuity import SLOT_BYTES
from repro.data import ycsb
from repro.rdma import verbs as rv
from repro.rdma.transport import LinkModel, RemoteMemory

COMMIT_BYTES = 8        # the 8-byte atomic indicator/token commit word

# YCSB mixes the simulation drives (paper §V-A): A/B/C the paper's trio,
# D read-latest (95% read / 5% insert, reads skewed to newest keys),
# E short scans (95% scan / 5% insert — continuity's contiguous-SBucket
# showcase), F read-modify-write (50% read / 50% RMW on the SAME key)
SIM_WORKLOADS = ("A", "B", "C", "D", "E", "F")


def write_plan(B: int, pm_per_op: int, extra_ops: int = 0,
               payload_bytes: int = SLOT_BYTES) -> rv.VerbPlan:
    """Synthesize the remote-write verb plan for B ops: each op issues its
    PM-write count as ordered slot-sized WRITEs ending in the 8-byte
    commit WRITE, every store followed by a remote-persist fence (each
    fenced store is a dependent round — DESIGN.md §8's ordering rule for
    correct remote persistence).

    The last ``extra_ops`` rows charge ``pm_per_op + 1`` writes (the
    scheme's fallback/logged path), the rest ``pm_per_op`` — so a batch
    whose ledger mixes paths keeps its EXACT PM-write total and a
    distinct latency tail, instead of a rounded uniform mean."""
    import jax.numpy as jnp
    pm = max(1, int(pm_per_op))
    extra_ops = min(max(0, int(extra_ops)), B)
    counts = jnp.where(jnp.arange(B) >= B - extra_ops, pm + 1, pm)
    lanes = []
    for d in range(pm + (1 if extra_ops else 0)):
        active = d < counts
        lanes.append((jnp.where(active, rv.WRITE, rv.NOOP), rv.REGION_TABLE,
                      0, jnp.where(d == counts - 1, COMMIT_BYTES,
                                   payload_bytes), d, True))
    return rv.pack(B, lanes)


def post_ledger_writes(mem: RemoteMemory, n_ok: int, total_pm: int):
    """Post the exact-total fenced write plan a batch's `CostLedger`
    implies: ``floor(total_pm / n_ok)`` writes per op with the remainder
    ops charging one more (the scheme's logged/fallback-path tail), so
    Σ per-op counts == the ledger.  The ONE apportioning rule every
    driver (this sim's update/insert paths, the cluster store's replica
    fan-out) shares.  Returns the `Completion`, or None for an empty or
    write-free batch."""
    if not (n_ok and total_pm):
        return None
    lo = max(1, total_pm // n_ok)
    return mem.post(write_plan(n_ok, lo, extra_ops=total_pm - lo * n_ok))


def _mix_counts(workload: str, batch: int):
    """(reads, updates, inserts, scans, rmw) per batch.  An RMW op counts
    toward BOTH reads and updates (it posts a read round then a fenced
    write round on the same key); ``rmw`` is the overlap so callers can
    count logical ops as ``reads + updates + inserts + scans - rmw``."""
    mix = dict(ycsb.WORKLOADS[workload])
    n_rmw = int(batch * mix.get(ycsb.OP_RMW, 0))
    n_read = int(batch * mix.get(ycsb.OP_READ, 0)) + n_rmw
    n_upd = int(batch * mix.get(ycsb.OP_UPDATE, 0)) + n_rmw
    n_ins = int(batch * mix.get(ycsb.OP_INSERT, 0))
    n_scan = int(batch * mix.get(ycsb.OP_SCAN, 0))
    return n_read, n_upd, n_ins, n_scan, n_rmw


def run_ycsb(scheme: str, workload: str, *, num_records: int = 3000,
             num_ops: int = 4000, batch: int = 500,
             load_factor: float = 0.7, link: Optional[LinkModel] = None,
             seed: int = 0) -> Dict[str, float]:
    """One scheme x workload cell: load ``num_records``, run ``num_ops`` of
    the mix in doorbell-batched rounds, return simulated throughput and
    latency percentiles.  Deterministic given the seed (the transport
    model has no noise terms), so CI can band the relative ordering.
    """
    from repro import api
    assert workload in SIM_WORKLOADS, workload
    n_read, n_upd, n_ins, n_scan, n_rmw = _mix_counts(workload, batch)
    n_logical = n_read + n_upd + n_ins + n_scan - n_rmw
    rounds = -(-num_ops // max(1, n_logical))
    slots = int(np.ceil((num_records + n_ins * rounds) / load_factor))
    store = api.make_store(scheme, table_slots=slots,
                           policy=api.ExecPolicy(transport="sim"))
    mem = RemoteMemory.from_policy(store.policy, link)
    assert mem is not None

    rng = np.random.RandomState(seed)
    K = ycsb.make_key(np.arange(num_records))
    V = ycsb.make_value(rng, num_records)
    # load in the same bounded, shape-stable batches the rounds use
    table, load_ok = api.bulk_load(store, store.create(), K, V, batch=batch)
    loaded = np.flatnonzero(load_ok)                # read only resident keys
    zipf = ycsb.Zipf(len(loaded))
    # YCSB scrambles zipfian ranks over the keyspace: popularity must be
    # independent of insertion order (rank==id would make the hottest keys
    # the FIRST inserted, i.e. the best-placed, flattering the multi-probe
    # baselines with an empty-table placement no aged store has)
    scramble = rng.permutation(len(loaded))
    order_ids = list(loaded)      # insertion order (D's read-latest axis)
    next_id = num_records

    # per-op-type latency sketches (local per cell; folded into the
    # installed obs registry at the end so a traced run exports them
    # under e2e.op_us{scheme,workload,op})
    h_read, h_write = obs.Histogram(), obs.Histogram()
    ops_done = 0
    while ops_done < num_ops:
        if workload == "D":
            # read-latest: popularity IS recency, so the zipf ranks index
            # the insertion order from the newest end (no scramble)
            zipf_d = ycsb.Zipf(len(order_ids))
            ids = np.asarray(order_ids)[len(order_ids) - 1
                                        - zipf_d.sample(rng, n_read)]
        elif n_read:
            ids = loaded[scramble[zipf.sample(rng, n_read)]]
        if n_read:
            hits = store.lookup(table, ycsb.make_key(ids))
            comp = mem.post(hits.plan, tag="read")
            h_read.record_many(comp.op_us)
        if n_scan:
            # YCSB-E short scans: start key zipf-ranked, span uniform.
            # The scan's wire cost IS the scan plan (the start record
            # rides inside the fetched range — nothing else is posted);
            # the jitted lookup runs for start-key correctness only.
            starts = loaded[scramble[zipf.sample(rng, n_scan)]]
            spans = ycsb.scan_lengths(rng, n_scan)
            skeys = ycsb.make_key(starts)
            store.lookup(table, skeys)
            comp = mem.post(store.scan_plan(table, skeys, spans),
                            tag="scan")
            h_read.record_many(comp.op_us)
        if n_ins:
            ins_ids = np.arange(next_id, next_id + n_ins)
            next_id += n_ins
            table, ires = store.insert(table, ycsb.make_key(ins_ids),
                                       ycsb.make_value(rng, n_ins))
            iok = np.asarray(ires.ok)
            order_ids.extend(int(i) for i in ins_ids[iok])
            comp = post_ledger_writes(mem, int(iok.sum()),
                                      int(ires.ledger.pm_writes))
            if comp is not None:
                h_write.record_many(comp.op_us)
        if n_upd:
            # F's updates are the write half of read-modify-write: they
            # target the keys the SAME round just read (the RMW tail of
            # the read batch), not an independent zipf draw
            ids = (ids[-n_upd:] if n_rmw
                   else loaded[scramble[zipf.sample(rng, n_upd)]])
            table, ures = store.update(table, ycsb.make_key(ids),
                                       ycsb.make_value(rng, n_upd))
            comp = post_ledger_writes(mem, int(np.asarray(ures.ok).sum()),
                                      int(ures.ledger.pm_writes))
            if comp is not None:
                h_write.record_many(comp.op_us)
        ops_done += n_logical
    jax.block_until_ready(table)

    # all percentiles come from the merged sketch — the same buckets the
    # obs export carries, so bench numbers and exports cannot disagree
    merged = obs.Histogram()
    merged.merge(h_read)
    merged.merge(h_write)
    reg = obs.get_registry()
    reg.histogram("e2e.op_us", scheme=scheme, workload=workload,
                  op="read").merge(h_read)
    reg.histogram("e2e.op_us", scheme=scheme, workload=workload,
                  op="write").merge(h_write)
    out = {
        "ops_per_s": ops_done / mem.total_us * 1e6,
        "p50_us": merged.percentile(50),
        "p99_us": merged.percentile(99),
        "doorbells": float(mem.doorbells),
        "verbs_per_op": mem.total_verbs / ops_done,
        "bytes_per_op": mem.total_bytes / ops_done,
    }
    if h_read.count:
        out["read_p50_us"] = h_read.percentile(50)
    if h_write.count:
        out["write_p50_us"] = h_write.percentile(50)
    return out


def run_all(schemes=None, workloads=SIM_WORKLOADS, **kw) -> Dict[str, dict]:
    """{scheme: {workload: cell}} over the registered schemes."""
    from repro import api
    out: Dict[str, dict] = {}
    for s in (schemes or api.available_schemes()):
        for wl in workloads:
            out.setdefault(s, {})[wl] = run_ycsb(s, wl, **kw)
    return out
