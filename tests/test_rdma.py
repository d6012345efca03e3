"""RDMA transport layer: verb plans, doorbell batching, latency model,
plan-derived accounting (byte-identical to the removed hand-tallies),
per-scheme read counts through the plan, remote-persist fences, and the
end-to-end YCSB ordering."""

import numpy as np
import pytest

from repro import api, rdma
from repro.data import ycsb
from repro.rdma import sim
from repro.rdma import verbs as rv

SCHEMES = ("continuity", "level", "pfarm", "dense")


def _loaded_store(scheme, n=600, slots=900, seed=0):
    """Store at ~2/3 load (extension groups / chains / spreads form)."""
    rng = np.random.RandomState(seed)
    store = api.make_store(scheme, table_slots=slots)
    K = ycsb.make_key(np.arange(n))
    V = ycsb.make_value(rng, n)
    table, res = store.insert(store.create(), K, V)
    return store, table, K[np.asarray(res.ok)], rng


# ---------------------------------------------------------------------------
# plan-derived ledger == the pre-refactor hand-tallied accounting
# ---------------------------------------------------------------------------

def _hand_tally(scheme, cfg, reads):
    """The four removed per-scheme ``read_counters`` formulas, kept here as
    the byte-identity oracle for the verb-plan-derived ledger."""
    n = reads.shape[0]
    if scheme == "continuity":
        return reads.sum(), n * cfg.segment_bytes + (reads - 1).sum() * cfg.ext_bytes
    if scheme == "level":
        return reads.sum(), reads.sum() * cfg.bucket_bytes
    if scheme == "pfarm":
        return (reads.sum(),
                n * cfg.window_bytes + (reads - 1).sum() * cfg.block_bytes)
    return reads.sum(), n * cfg.table_bytes


@pytest.mark.parametrize("scheme", SCHEMES)
def test_plan_ledger_byte_identical_to_hand_tally(scheme):
    store, table, K, rng = _loaded_store(scheme)
    NK = ycsb.negative_keys(rng, len(K), 400)
    for keys in (K, NK):
        res = store.lookup(table, keys)
        reads = np.asarray(res.reads)
        r_old, b_old = _hand_tally(scheme, store.cfg, reads)
        assert int(res.ledger.rdma_reads) == int(r_old)
        assert int(res.ledger.bytes_fetched) == int(b_old)
        assert int(res.ledger.ops) == keys.shape[0]
        # the plan itself is on the result and agrees with the per-op trace
        assert res.plan is not None
        assert (np.asarray(rv.reads_per_op(res.plan)) == reads).all()


# ---------------------------------------------------------------------------
# per-scheme negative-lookup read counts, asserted through the verb plan
# (paper §II-C2 — not through scheme-internal counters)
# ---------------------------------------------------------------------------

def test_negative_lookup_continuity_always_one_contiguous_read():
    # misses included: the home segment fetch answers the lookup in ONE
    # contiguous READ whenever the pair has no added SBuckets
    store, table, K, rng = _loaded_store("continuity", n=400, slots=900)
    NK = ycsb.negative_keys(rng, 400, 500)
    plan = store.lookup(table, NK).plan
    per_op = np.asarray(rv.reads_per_op(plan))
    assert per_op.min() >= 1
    if int(table.ext_count) == 0:
        assert (per_op == 1).all()
    # ext-free config: ALWAYS exactly one, by construction
    free = api.make_store("continuity", table_slots=900, ext_frac=0.0)
    t = free.create()
    t, _ = free.insert(t, K, ycsb.make_value(rng, len(K)))
    plan = free.lookup(t, NK).plan
    assert (np.asarray(rv.reads_per_op(plan)) == 1).all()
    # and the one verb is the contiguous segment fetch
    assert (np.asarray(plan.verb)[:, 0] == rv.READ).all()
    assert (np.asarray(plan.nbytes)[:, 0] == free.cfg.segment_bytes).all()


def test_negative_lookup_level_scans_all_distinct_candidates():
    store, table, K, rng = _loaded_store("level")
    NK = ycsb.negative_keys(rng, len(K), 500)
    plan = store.lookup(table, NK).plan
    per_op = np.asarray(rv.reads_per_op(plan))
    assert per_op.max() <= 4
    # negative search never stops early: it reads every DISTINCT candidate
    from repro.core import level as lv
    import jax.numpy as jnp
    cand = np.asarray(lv._cand_buckets(
        store.cfg, jnp.asarray(NK).reshape(-1, 4)))
    distinct = (1 + (cand[:, 1] != cand[:, 0])
                + 1 + (cand[:, 3] != cand[:, 2]))
    assert (per_op == distinct).all()
    assert per_op.max() == 4          # hash collisions of all four are rare
    # sequential probing: depths of active lanes are 0..reads-1
    depth = np.asarray(plan.depth)
    active = np.asarray(plan.verb) == rv.READ
    for b in (0, 1, 2):
        assert sorted(depth[b][active[b]]) == list(range(per_op[b]))


def test_negative_lookup_pfarm_reads_window_plus_chain():
    store, table, K, rng = _loaded_store("pfarm", n=700, slots=900)
    NK = ycsb.negative_keys(rng, len(K), 500)
    res = store.lookup(table, NK)
    per_op = np.asarray(rv.reads_per_op(res.plan))
    assert (per_op == np.asarray(res.reads)).all()
    assert per_op.min() >= 1
    assert per_op.max() <= 1 + store.cfg.max_chain
    # chain hops are DEPENDENT verbs: depth == hop index
    depth = np.asarray(res.plan.depth)
    verb = np.asarray(res.plan.verb)
    assert (depth[:, 0] == 0).all()
    for k in range(1, res.plan.lanes):
        lane_active = verb[:, k] == rv.READ
        assert (depth[lane_active, k] == k).all()


# ---------------------------------------------------------------------------
# transport: doorbell batching + latency model
# ---------------------------------------------------------------------------

def test_doorbell_batching_coalesces_independent_verbs():
    link = rdma.LinkModel()
    mem = rdma.RemoteMemory(link)
    B = 64
    plan = rv.pack(B, [(rv.READ, rv.REGION_TABLE, 0, 520, 0, False)])
    comp = mem.post(plan)
    # 64 independent READs = ONE doorbell = one RTT for the whole batch
    assert comp.rounds == 1
    assert comp.verbs == B
    expected = link.rtt_us + B * (
        link.verb_us + 520 / link.nic_bytes_per_us
        + 520 / link.pm_read_bytes_per_us)
    assert comp.batch_us == pytest.approx(expected)
    # unloaded per-op latency: one RTT + the op's own verb cost
    assert comp.op_us[0] == pytest.approx(
        link.rtt_us + link.verb_us + 520 / link.nic_bytes_per_us
        + 520 / link.pm_read_bytes_per_us)


def test_dependent_depths_cost_extra_round_trips():
    mem = rdma.RemoteMemory()
    B = 8
    chained = rv.pack(B, [
        (rv.READ, rv.REGION_TABLE, 0, 100, 0, False),
        (rv.READ, rv.REGION_EXT, 0, 100, 1, False)])
    flat = rv.pack(B, [
        (rv.READ, rv.REGION_TABLE, 0, 100, 0, False),
        (rv.READ, rv.REGION_EXT, 0, 100, 0, False)])
    c1 = mem.post(chained)
    c2 = mem.post(flat)
    assert c1.rounds == 2 and c2.rounds == 1
    assert c1.batch_us == pytest.approx(c2.batch_us + mem.link.rtt_us)
    assert int(rv.round_trips(chained)) == 2
    assert mem.doorbells == 3 and mem.posts == 2


def test_fenced_writes_price_remote_persistence():
    link = rdma.LinkModel()
    mem = rdma.RemoteMemory(link)
    plan = sim.write_plan(4, pm_per_op=2)
    comp = mem.post(plan)
    assert comp.rounds == 2                       # payload round, commit round
    # each op: 2 RTTs + 2 fenced WRITEs + media/wire time
    per_op = 2 * link.rtt_us + 2 * (link.verb_us + link.fence_us) \
        + (32 + 8) / link.nic_bytes_per_us \
        + (32 + 8) / link.pm_write_bytes_per_us
    assert comp.op_us[0] == pytest.approx(per_op)


def test_transport_selection_through_exec_policy():
    assert rdma.RemoteMemory.from_policy(api.ExecPolicy()) is None
    mem = rdma.RemoteMemory.from_policy(api.ExecPolicy(transport="sim"))
    assert isinstance(mem, rdma.RemoteMemory)
    with pytest.raises(AssertionError):
        api.ExecPolicy(transport="infiniband")


# ---------------------------------------------------------------------------
# remote-persist fences: the WRITE-visible vs persisted cut
# ---------------------------------------------------------------------------

def test_remote_crash_commit_fences_leave_no_durability_gap():
    from repro import consistency as C
    store, table, K, rng = _loaded_store("continuity", n=32, slots=400)
    h = C.HANDLERS["continuity"]
    base = h.init_state(store.cfg, table)
    NK = ycsb.negative_keys(rng, 64, 8)
    _, tres = store.trace_insert(table, NK, ycsb.make_value(rng, 8))
    states = list(C.remote_crash_states(base, tres.trace))
    assert len(states) == len(tres.trace.records) + 1
    for cs in states:
        # under the commit-fence discipline nothing observable is lost...
        assert C.unpersisted_commits(tres.trace, cs) == 0
        # ...and the persisted image recovers to a consistent table whose
        # visible items are exactly the fenced commits' items
        recovered, _ = store.recover(cs.persisted)
        vis = h.visible(store.cfg, h.init_state(store.cfg, recovered))
        committed = sum(1 for i, r in enumerate(tres.trace.records)
                        if i < cs.fenced_done and r.kind in C.COMMIT_KINDS)
        assert len(vis) == len(h.visible(store.cfg, base)) + committed


def test_remote_crash_unfenced_delivery_detected():
    from repro import consistency as C
    store, table, K, rng = _loaded_store("continuity", n=16, slots=400)
    h = C.HANDLERS["continuity"]
    base = h.init_state(store.cfg, table)
    NK = ycsb.negative_keys(rng, 32, 4)
    _, tres = store.trace_insert(table, NK, ycsb.make_value(rng, 4))
    # write-combined delivery: NO fences until the end of the batch — a cut
    # after a visible commit loses it (the injector must expose the gap)
    gaps = [C.unpersisted_commits(tres.trace, cs)
            for cs in C.remote_crash_states(base, tres.trace, fences=())]
    assert max(gaps) >= 1
    # strict per-store fencing closes it again
    gaps = [C.unpersisted_commits(tres.trace, cs)
            for cs in C.remote_crash_states(
                base, tres.trace, fences=C.fence_every_store(tres.trace))]
    assert max(gaps) == 0


# ---------------------------------------------------------------------------
# end-to-end YCSB: the paper's headline ordering
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_end_to_end_ordering_read_heavy():
    cells = {s: {wl: sim.run_ycsb(s, wl, num_records=800, num_ops=1000,
                                  batch=250)
                 for wl in ("B", "C")}
             for s in ("continuity", "level", "pfarm")}
    for wl in ("B", "C"):
        c = cells["continuity"][wl]["ops_per_s"]
        l = cells["level"][wl]["ops_per_s"]
        p = cells["pfarm"][wl]["ops_per_s"]
        assert c >= l >= p, (wl, c, l, p)
    # latency: continuity's p99 beats both baselines on read-heavy mixes
    # (one contiguous fetch has no multi-probe/chain tail)
    assert (cells["continuity"]["C"]["p99_us"]
            <= cells["level"]["C"]["p99_us"])
    assert (cells["continuity"]["C"]["p99_us"]
            <= cells["pfarm"]["C"]["p99_us"])


def test_scheduler_step_is_the_doorbell_flush_boundary():
    import jax
    from repro.configs import smoke_config
    from repro.models import transformer as T
    from repro.models.config import ShapeConfig
    from repro.serving import kvcache as KC
    from repro.serving.scheduler import ContinuousBatcher, Request

    cfg = smoke_config("yi-6b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    shape = ShapeConfig("s", seq_len=64, global_batch=2, kind="decode")
    geom = KC.make_geometry(
        cfg, shape, shards=1, page_size=16,
        policy=api.ExecPolicy(transport="sim"))
    batcher = ContinuousBatcher(cfg, geom, params)
    assert batcher.transport is not None      # selected via ExecPolicy
    batcher.submit(Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                           max_new_tokens=3))
    steps = 0
    while batcher.step():
        steps += 1
    # one post (>= one doorbell) per decode step — the flush boundary
    assert batcher.transport.posts == steps + 1
    assert batcher.transport.doorbells >= steps
    # every translation is one verb; batch x max_pages lanes per step
    assert batcher.transport.total_verbs > 0


def test_run_ycsb_loads_in_bounded_batches(monkeypatch):
    """The sim's load goes through `api.bulk_load`: every insert call has
    the round's batch shape, however many records are loaded."""
    shapes = []
    orig = api.ContinuityStore.insert

    def spy(self, table, keys, vals, mask=None):
        shapes.append(np.shape(keys))
        return orig(self, table, keys, vals, mask)

    monkeypatch.setattr(api.ContinuityStore, "insert", spy)
    out = sim.run_ycsb("continuity", "C", num_records=300, num_ops=128,
                       batch=64)
    assert len(shapes) == 5 and set(shapes) == {(64, 4)}
    assert out["ops_per_s"] > 0
