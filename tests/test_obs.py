"""Telemetry subsystem: sketch exactness, span determinism, attribution.

The contracts the obs layer sells to the rest of the repo:

  * the log-scale `Histogram` tracks sorted-list percentiles within its
    advertised relative bound |sketch - exact| <= exact * (GROWTH - 1)
    (fixed cases + a seeded property), and merging sketches is EXACTLY
    the sketch of the concatenation;
  * `Tracer` span nesting carries causal parent ids, the injectable
    `TickClock` makes a traced run's export a pure function of its call
    sequence — two same-seed runs export byte-identical JSON;
  * `MetricsRegistry.merge` rolls node registries up (counters add,
    histograms merge, gauges keep the max) — `ClusterStore.metrics_view`
    equals the sum of its per-node endpoints;
  * the transport buckets retries/timeouts PER TAG in ``by_tag`` and
    keeps its legacy `stats()` shape;
  * maintenance-step SLO accounting burns under a tiny SLO and stays
    clean at the defaults.
"""

import json

import numpy as np
import pytest

from _propcheck import given, settings, st
from repro import obs
from repro.cluster.store import ClusterStore
from repro.data import ycsb
from repro.obs.metrics import GROWTH, Histogram, MetricsRegistry
from repro.rdma import verbs as rv
from repro.rdma.transport import FaultInjector, RemoteMemory, RetryPolicy

pytestmark = pytest.mark.obs

PCTS = (50.0, 90.0, 99.0, 99.9)


def _assert_tracks(values, pcts=PCTS):
    """Sketch percentiles within the advertised relative bound."""
    h = Histogram()
    h.record_many(values)
    a = np.asarray(values, np.float64)
    for p in pcts:
        exact = float(np.percentile(a, p))
        got = h.percentile(p)
        assert abs(got - exact) <= abs(exact) * (GROWTH - 1) + 1e-12, \
            f"p{p}: sketch {got} vs exact {exact}"


# ---------------------------------------------------------------------------
# histogram sketch: exactness, merge, serialization
# ---------------------------------------------------------------------------

def test_histogram_tracks_exact_percentiles_fixed():
    _assert_tracks([1.0, 2.0, 3.0, 4.0, 5.0])
    _assert_tracks(np.linspace(0.5, 500.0, 997))
    _assert_tracks(np.random.RandomState(7).lognormal(2.0, 1.5, 2000))
    # bimodal read/write mix whose p50 IS the boundary interpolation
    _assert_tracks([2.8] * 2000 + [14.5] * 2000)


@settings(max_examples=40, deadline=None)
@given(st.tuples(st.integers(min_value=0, max_value=2 ** 31 - 1),
                 st.integers(min_value=2, max_value=400)))
def test_histogram_tracks_exact_percentiles_property(case):
    seed, n = case
    rng = np.random.RandomState(seed)
    values = np.exp(rng.uniform(np.log(1e-2), np.log(1e5), n))
    _assert_tracks(values)


def test_histogram_merge_equals_concatenation():
    rng = np.random.RandomState(3)
    a, b = rng.lognormal(1.0, 1.0, 500), rng.lognormal(3.0, 0.5, 700)
    ha, hb, hc = Histogram(), Histogram(), Histogram()
    ha.record_many(a)
    hb.record_many(b)
    hc.record_many(np.concatenate([a, b]))
    ha.merge(hb)
    assert ha.to_dict() == hc.to_dict()      # bucket-exact, not approximate


def test_histogram_roundtrip_and_edge_cases():
    h = Histogram()
    assert h.percentile(50) == 0.0           # empty sketch
    h.record(0.0)                            # underflow bucket
    h.record(1e12)                           # overflow: reported as max
    assert h.percentile(100) == pytest.approx(1e12)
    # fractional rank interpolates toward the overflow max, exactly as
    # np.percentile would over the two order stats
    assert h.percentile(99.9) == pytest.approx(
        float(np.percentile([0.0, 1e12], 99.9)))
    d = h.to_dict()
    assert set(d["percentiles"]) == {"p50", "p90", "p99", "p999"}
    h2 = Histogram.from_dict(json.loads(json.dumps(d)))
    for p in PCTS:
        assert h2.percentile(p) == h.percentile(p)


def test_record_many_matches_record_loop():
    values = np.random.RandomState(11).lognormal(0.0, 2.0, 300)
    h1, h2 = Histogram(), Histogram()
    h1.record_many(values)
    for v in values:
        h2.record(v)
    d1, d2 = h1.to_dict(), h2.to_dict()
    # np's pairwise summation vs the sequential loop: sum matches only
    # to float tolerance; every discrete field must match exactly
    assert d1.pop("sum") == pytest.approx(d2.pop("sum"))
    assert d1 == d2


# ---------------------------------------------------------------------------
# tracer: nesting, causal links, clock injection, scope isolation
# ---------------------------------------------------------------------------

def test_span_nesting_and_parent_ids():
    t = obs.Tracer(obs.TickClock())
    with t.span("outer", node="pm0") as s_out:
        with t.span("inner") as s_in:
            t.event("ring", n=3)
        assert s_in.parent_id == s_out.span_id
    assert s_out.parent_id is None
    assert [s.name for s in t.spans] == ["inner", "outer"]   # finish order
    assert s_in.events[0]["name"] == "ring"
    assert s_out.t1_us > s_out.t0_us >= 1.0   # TickClock: counted calls
    t.event("orphan")                         # no open span: counted, kept out
    assert t.dropped_events == 1


def test_scope_installs_and_restores():
    assert obs.get_tracer() is None
    outer_reg = obs.get_registry()
    with obs.scope() as (tracer, reg):
        assert obs.get_tracer() is tracer
        assert obs.get_registry() is reg
        with obs.span("x"):
            obs.event("e")
    assert obs.get_tracer() is None
    assert obs.get_registry() is outer_reg
    assert [s.name for s in tracer.spans] == ["x"]
    # the free functions are no-ops outside a scope (shared null span)
    with obs.span("ignored"):
        obs.event("ignored")
    assert [s.name for s in tracer.spans] == ["x"]


def _traced_mini_run(seed: int):
    from repro.rdma.sim import run_ycsb
    with obs.scope(obs.Tracer(obs.TickClock())) as (tracer, reg):
        with obs.span("e2e.cell", scheme="continuity", workload="A"):
            run_ycsb("continuity", "A", num_records=200, num_ops=200,
                     batch=100, seed=seed)
        return obs.export_strings(tracer, reg, meta={"seed": seed})


def test_same_seed_exports_are_byte_identical():
    t1, m1 = _traced_mini_run(5)
    t2, m2 = _traced_mini_run(5)
    assert t1 == t2 and m1 == m2
    t3, m3 = _traced_mini_run(6)
    assert m3 != m1                          # different seed, different data


# ---------------------------------------------------------------------------
# registry merge: the cross-node roll-up
# ---------------------------------------------------------------------------

def test_registry_merge_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("c", node="pm0").inc(2)
    b.counter("c", node="pm0").inc(3)
    b.counter("c", node="pm1").inc(1)
    a.gauge("g").set(5.0)
    b.gauge("g").set(3.0)
    a.histogram("h").record(1.0)
    b.histogram("h").record(100.0)
    a.merge(b)
    assert a.counter("c", node="pm0").value == 5
    assert a.counter("c", node="pm1").value == 1
    assert a.gauge("g").max == 5.0           # merge keeps the worst observed
    assert a.histogram("h").count == 2


def test_cluster_metrics_view_sums_node_endpoints():
    cluster = ClusterStore("continuity", nodes=3, replicas=2,
                           node_slots=512)
    rng = np.random.RandomState(0)
    keys = ycsb.make_key(np.arange(96))
    cluster.insert(keys, ycsb.make_value(rng, 96))
    cluster.lookup(keys[:32])
    view = cluster.metrics_view()
    per_node = [n.mem.metrics for n in cluster._nodes.values()
                if n.mem is not None]
    want_posts = sum(r.counter("rdma.posts").value for r in per_node)
    assert want_posts > 0
    assert view.counter("rdma.posts").value == want_posts
    assert view.histogram("rdma.post_us").count \
        == sum(r.histogram("rdma.post_us").count for r in per_node)
    # the roll-up is a fresh registry: node endpoints stay intact
    assert per_node[0].counter("rdma.posts").value <= want_posts


# ---------------------------------------------------------------------------
# transport: per-tag attribution + legacy stats() shape
# ---------------------------------------------------------------------------

def test_by_tag_buckets_retries_and_timeouts():
    mem = RemoteMemory(faults=FaultInjector(drop_p=0.4, seed=3),
                       retry=RetryPolicy(max_attempts=8))
    plan = rv.single_read_plan(8, rv.REGION_TABLE, 0, 64)
    for _ in range(4):
        mem.post(plan, tag="probe")
    assert mem.retries > 0
    bt = mem.stats()["by_tag"]["probe"]
    assert bt["retries"] == mem.retries      # every drop hit tagged posts
    assert bt["timeouts"] == mem.timeouts
    assert bt["posts"] == 4 and bt["verbs"] > 0
    mem.post(plan)                           # untagged traffic: the global
    bt2 = mem.stats()["by_tag"]["probe"]     # counters may grow, the tag
    assert bt2["retries"] == bt["retries"]   # bucket must not


def test_stats_shape_is_unchanged():
    mem = RemoteMemory()
    mem.post(rv.single_read_plan(4, rv.REGION_TABLE, 0, 64), tag="read")
    s = mem.stats()
    assert {"simulated_us", "posts", "doorbells", "verbs", "bytes",
            "by_tag"} <= set(s)
    assert "retries" not in s                # fault block only when faulty
    assert set(s["by_tag"]) == {"read"}
    assert {"posts", "doorbells", "verbs", "bytes", "simulated_us",
            "retries", "timeouts"} <= set(s["by_tag"]["read"])


# ---------------------------------------------------------------------------
# maintenance SLO accounting
# ---------------------------------------------------------------------------

def _filled_single_shard():
    cluster = ClusterStore("continuity", nodes=1, replicas=1,
                           node_slots=256)
    rng = np.random.RandomState(0)
    node = next(iter(cluster._nodes.values()))
    next_id = 0
    while float(node.store.load_factor(node.table)) <= 0.86 \
            and next_id < 2048:
        ids = np.arange(next_id, next_id + 64)
        next_id += 64
        cluster.insert(ycsb.make_key(ids), ycsb.make_value(rng, len(ids)))
    return cluster


def test_slo_burn_counts_under_tiny_slo_and_not_at_defaults():
    with obs.scope() as (_, reg):
        cluster = _filled_single_shard()
        for _ in range(200):
            if not cluster.maintenance_step(budget=2,
                                            step_slo_us=1e-3):
                break
        assert cluster.maintenance["steps"] >= 1
        assert cluster.maintenance["slo_burns"] >= 1
        assert reg.counter("maintenance.slo_burn").value \
            == cluster.maintenance["slo_burns"]
        assert reg.gauge("maintenance.step_us", node="pm0").max > 1e-3
    with obs.scope() as (_, reg):
        cluster = _filled_single_shard()
        for _ in range(200):
            if not cluster.maintenance_step(budget=2):
                break
        assert cluster.maintenance["steps"] >= 1
        assert cluster.maintenance["slo_burns"] == 0
        assert reg.counter("maintenance.slo_burn").value == 0


# ---------------------------------------------------------------------------
# spans on the device trace's clock: the JAX profiler
# ---------------------------------------------------------------------------

def _profiled(tmp_path, fn, prefix="t."):
    import glob
    import os
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return {e.name: dict(e.stats) for p in pd.planes
            if not p.name.startswith("/device:")
            for line in p.lines for e in line.events
            if e.name.startswith(prefix)}


def test_spans_go_to_the_profiler_when_no_tracer_records(tmp_path):
    """`obs.device_span` goes to the recording profiler, with its
    attributes, whether or not a tracer is installed, and never to the
    tracer; `obs.span` goes to the tracer alone."""
    def body():
        with obs.span("t.span", n=3):
            with obs.device_span("t.device", out_buffers=15):
                pass
        with obs.scope() as (tracer, _):
            with obs.span("t.recorded"):
                with obs.device_span("t.device_only"):
                    pass
        assert [s.name for s in tracer.spans] == ["t.recorded"]
    events = _profiled(tmp_path, body)
    assert events == {"t.device": {"out_buffers": 15}, "t.device_only": {}}


def test_spans_cost_no_formatting_while_nothing_records():
    class Loud:
        def __str__(self):
            raise AssertionError("formatted")
        __repr__ = __format__ = __str__

    import jax
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with obs.span("t.off", x=Loud()) as a:
        with obs.device_span("t.off", x=Loud()) as b:
            assert a is None and b is None


def test_tickclock_export_is_the_same_under_the_profiler(tmp_path):
    """A recording profiler leaves the `Tracer`'s export byte-identical:
    the lookups the drill makes write their ``api.*`` spans to the
    profiler alone."""
    plain = _traced_mini_run(5)
    profiled = []
    events = _profiled(tmp_path, lambda: profiled.append(_traced_mini_run(5)),
                       prefix="api.")
    assert profiled[0] == plain
    assert "api.lookup" not in plain[0] and "api.lookup" in events
