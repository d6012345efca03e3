"""The benchmark harness on the CPU at a tiny size (2^14 slots).

Runs each cell's traffic through the harness for a short window, checks
the per-layer reductions on a trace recorded here and on hand-built
events, the roofline byte counts against hand counts, that a new
configuration, mix and metric are found by name, that a device missing
from ``peaks.json`` is an error, and that the benchmark's copy of the
YCSB generators gives the program's streams.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

import harness
import xplane
import ycsb
from conftest import make_tiny

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def run_cell(root, cell, trace=False, seconds=1.0, seed=2 ** 31 + 11,
             **kw):
    return harness.run(cell, seed, seconds, trace, t_start=time.perf_counter(),
                       root=root, require_chip=False, **kw)


def expected_metrics(root, cell, trace):
    spec = harness.load_spec(root)
    return {m["name"] for m in harness.cell_metrics(
        spec, harness.find_cell(spec, cell), trace)}


@pytest.mark.parametrize("cell", ["ycsb-zipf.A", "ycsb-zipf.C",
                                  "micro-uniform.update"])
def test_cell_runs_and_is_correct(tiny, cell):
    r = run_cell(tiny, cell)
    assert RESULT_KEYS <= set(r) and list(r)[-1] == "checks"
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and 0 <= r["failed"] < r["attempted"]
    assert set(r["metrics"]) == expected_metrics(tiny, cell, False)
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("cell,idle", [("ycsb-zipf.A", "device_idle_pct"),
                                       ("ycsb-zipf.C", "device_idle_pct.read")])
def test_traced_run_reports_per_layer_metrics(tiny, cell, idle):
    r = run_cell(tiny, cell, trace=True)
    assert r["correct"] is True
    assert set(r["metrics"]) == expected_metrics(tiny, cell, True)
    dev = r["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert 0 < r["metrics"][idle]["value"] < 100
    bd = r["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


# -- reductions --------------------------------------------------------------

def _view(calls=(), trace=None, batch=4, kind="TPU v5 lite"):
    peaks = harness.device_peaks(kind)
    return harness.RunView(list(calls), 1.0, 2.0, trace, peaks, batch)


def test_reductions_on_hand_built_trace():
    ms = 1_000_000
    t = xplane.TraceView(
        ops=[[("a", 0, 2 * ms), ("b", 1 * ms, 3 * ms), ("a", 6 * ms, 7 * ms),
              ("c", 12 * ms, 13 * ms)]],
        modules=[("jit__jit_lookup(7)", 0, 3 * ms), ("jit_update", 6 * ms, 7 * ms),
                 ("jit__jit_lookup_other", 6 * ms, 9 * ms)],
        spans=[("bench.window", 0, 10 * ms), ("bench.dispatch.lookup", 0, ms),
               ("bench.fetch.lookup", 3 * ms, 5 * ms),
               ("bench.dispatch.update", 5 * ms, 6 * ms),
               ("bench.generate", 7 * ms, 9 * ms)],
        window=(0, 10 * ms))
    assert t.window_s == 0.01
    assert t.busy_s == pytest.approx(0.004)          # [0,3] + [6,7]
    assert t.module_runs("jit__jit_lookup") == [3 * ms]
    assert t.module_runs("jit_update") == [ms]
    bd = t.breakdown()
    assert bd["device_ops"] == [["a", 0.003], ["b", 0.002]]   # c is outside
    # idle [3,6] under fetch then dispatch, [7,10] under generate then none
    assert sorted(bd["idle_gaps"]) == [
        ["dispatch.update", 0.001], ["fetch.lookup", 0.002],
        ["generate", 0.002], ["outside any span", 0.001]]
    assert t.host_spans() == pytest.approx(
        {"dispatch.lookup": 0.001, "fetch.lookup": 0.002,
         "dispatch.update": 0.001, "generate": 0.002})
    run = _view(trace=t, batch=4096)
    read = lambda name: harness.load_reader(name)(run)
    assert read("device_idle_pct") == (pytest.approx(60.0), 4)
    assert read("dispatch_ms") == (pytest.approx(1.0), 2)
    assert read("dispatch_ms.read") == read("dispatch_ms")
    assert read("device_idle_pct.read") == read("device_idle_pct")
    assert read("lookup_device_ms") == (pytest.approx(3.0), 1)
    assert read("update_device_ms") == (pytest.approx(1.0), 1)
    bw = run.peaks["hbm_bytes_per_s"]
    assert read("lookup_roofline_pct")[0] == pytest.approx(
        100 * 4096 * 356 / (0.003 * bw))
    assert read("update_roofline_pct")[0] == pytest.approx(
        100 * 4096 * 388 / (0.001 * bw))
    assert read("ops_per_s") is not None and read("read_p95_ms") is None
    assert harness.load_reader("lookup_device_ms")(_view()) is None


def test_roofline_bytes_by_hand():
    look = harness.load_reader("lookup_roofline_pct").__globals__["op_bytes"]
    upd = harness.load_reader("update_roofline_pct").__globals__["op_bytes"]
    # 20 key slots of 16 B + indicator/version 8 + fp 8 + ext_map 4 + value 16
    assert look() == 320 + 8 + 8 + 4 + 16 == 356
    # reads 340; writes key 16 + value 16 + indicator 8 + fp 8
    assert upd() == 340 + 48 == 388


def test_reductions_on_recorded_trace(tmp_path):
    """A trace recorded here: two programs named as the store's are, run
    under the benchmark's spans; the readers find both and the window."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _jit_lookup(x):
        return jnp.sort(x * 3)

    @jax.jit
    def update(x):
        return jnp.cumsum(x + 1)

    x = jnp.arange(1 << 16, dtype=jnp.float32)
    jax.block_until_ready((_jit_lookup(x), update(x)))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch.lookup"):
                y = _jit_lookup(x)
            with jax.profiler.TraceAnnotation("bench.fetch.lookup"):
                jax.device_get(y)
            with jax.profiler.TraceAnnotation("bench.dispatch.update"):
                y = update(x)
            with jax.profiler.TraceAnnotation("bench.fetch.update"):
                jax.device_get(y)
            time.sleep(0.002)
    jax.profiler.stop_trace()
    t = xplane.read_dir(str(tmp_path))
    assert 0 < t.busy_s < t.window_s
    assert len(t.module_runs("jit__jit_lookup")) == 3
    assert len(t.module_runs("jit_update")) == 3
    run = _view(trace=t)
    for name in ("device_idle_pct", "dispatch_ms", "lookup_device_ms",
                 "update_device_ms", "lookup_roofline_pct",
                 "update_roofline_pct"):
        value, samples = harness.load_reader(name)(run)
        assert value > 0 and samples > 0, name
    assert harness.load_reader("dispatch_ms")(run)[1] == 6


# -- found by name -------------------------------------------------------------

def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    root = make_tiny(tmp_path)
    before = _digests(root)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "micro-uniform.json")) as f:
        cfg = json.load(f)
    cfg["load_factor"] = 0.5
    cfg["records"] = int(0.5 * cfg["table_slots"])
    with open(os.path.join(b, "configs", "half-full.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "B.json"), "w") as f:
        json.dump({"batch": 128, "calls": [{"op": "lookup"}] * 3
                   + [{"op": "update"}, {"op": "lookup", "keys": "absent"}]}, f)
    with open(os.path.join(b, "metrics", "lookup_calls.py"), "w") as f:
        f.write("def read(run):\n"
                "    n = sum(c.op == 'lookup' for c in run.calls)\n"
                "    return float(n), n\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "half-full", "source": "test",
                            "file": "bench/configs/half-full.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "half-full.B", "config": "half-full",
                              "traffic": "B", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "lookup_calls", "unit": "calls",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["half-full.B"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    r = run_cell(root, "half-full.B")
    assert r["correct"] is True, r["checks"]
    assert r["metrics"]["lookup_calls"]["value"] > 0
    assert set(r["metrics"]) == {"lookup_calls", "setup_s"}
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before


def test_device_missing_from_peaks_is_an_error(tmp_path):
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.device_peaks("TPU v99")
    root = make_tiny(tmp_path)
    shutil.copy(os.path.join(harness.HERE, "peaks.json"),
                os.path.join(root, "bench", "peaks.json"))     # no "cpu"
    with pytest.raises(KeyError, match="cpu"):
        run_cell(root, "ycsb-zipf.C")


def test_no_tpu_exits_nonzero_without_result(capsys):
    import run
    assert run.main(["--workload", "ycsb-zipf.C", "--seed", "1",
                     "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""


def test_ycsb_copy_gives_the_programs_streams():
    from repro.data import ycsb as orig
    ids = np.arange(0, 5_000_000, 997)
    assert np.array_equal(ycsb.make_key(ids), orig.make_key(ids))
    a, b = np.random.RandomState(5), np.random.RandomState(5)
    assert np.array_equal(ycsb.make_value(a, 999), orig.make_value(b, 999))
    za, zb = ycsb.Zipf(1_000_003), orig.Zipf(1_000_003)
    assert np.array_equal(za.sample(a, 4096), zb.sample(b, 4096))
    assert np.array_equal(ycsb.negative_keys(a, 1000, 77),
                          orig.negative_keys(b, 1000, 77))


def test_traffic_is_the_same_for_every_seed():
    import generator
    cfg = {"request_distribution": "zipfian", "zipf_theta": 0.99}
    mix = {"batch": 256, "calls": [{"op": "lookup"}, {"op": "update"}]}
    a, b = (generator.Traffic(mix, cfg, 10_000) for _ in range(2))
    other = generator.Traffic(mix, cfg, 10_000, traffic_seed=7)
    for call in mix["calls"]:
        ids = a.ids(call, a.ranks)
        assert np.array_equal(ids, b.ids(call, b.ranks))
        assert not np.array_equal(ids, other.ids(call, other.ranks))


def test_every_committed_cell_resolves_by_name():
    """Each cell of the committed ``BENCHMARK.json`` finds its
    configuration, its mix and a reader for every metric it reports, and
    reports ``setup_s``, another end-to-end metric and a per-layer one."""
    spec = harness.load_spec(harness.ROOT)
    for cell in spec["workloads"]:
        harness.load_config(spec, cell)
        harness.load_traffic(cell["traffic"])
        ends = {m["name"] for m in harness.cell_metrics(spec, cell, False)}
        layers = harness.cell_metrics(spec, cell, True)
        assert "setup_s" in ends and len(ends) >= 2 and layers, cell["name"]
        assert all(m["moves"] in ends for m in layers), cell["name"]
        for name in ends | {m["name"] for m in layers}:
            assert callable(harness.load_reader(name)), name
