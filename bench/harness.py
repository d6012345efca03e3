"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix or metric is
found by name: the cell in ``BENCHMARK.json``, its configuration in the
file that entry names, its mix in ``traffic/<name>.json`` and each metric's
reader in ``metrics/<name>.py`` (``read(run) -> (value, samples) | None``).
Device peaks come from ``peaks.json``, keyed by ``device_kind``.

The system under test is the continuity store of ``repro.api``, driven
as a closed loop with one call outstanding: the client draws a batch,
hands it to the store, waits for the result on the host, and only then
draws the next.  Set-up creates the table, loads it through
``api.bulk_load`` and calls each op of the mix twice; the window then
runs whole rounds of the mix until ``seconds`` have passed.  Once the
window has closed every answer of the window is compared with the plain
reference of ``reference.py``, and so is the value of every record the
window updated and of a sample of the records it did not, read back
through the store.  An update the store refuses (it answers not ok)
counts as failed and must leave the record unchanged; the share of
updates refused has a limit of its own.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Optional

import numpy as np

import generator
import reference
import ycsb

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# Each compared number and its limit (PERF.md gives the readings each
# limit was set from).  The counts of wrong answers are exact comparisons.
LIMITS = {"load_unacked": 0, "lookup_wrong": 0, "update_acked_absent": 0,
          "readback_wrong": 0, "untouched_wrong": 0,
          "update_refused_pct": 30.0}
# Records the window did not update, drawn from the seed and read back
# after it (with replacement, so a little fewer once repeats go).
UNTOUCHED_SAMPLE = 1 << 21


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Call:
    """One call of the window, as the client saw it."""
    op: str
    ids: np.ndarray
    vals: Optional[np.ndarray]      # update payloads
    found: np.ndarray               # lookup found flags / update acks
    values: Optional[np.ndarray]    # lookup values
    t_issue: float
    t_done: float


@dataclasses.dataclass
class RunView:
    """What a metric reader may read."""
    calls: list
    window_s: float
    setup_s: float
    trace: object                   # xplane.TraceView, or None
    peaks: dict
    batch: int


class ApiStore:
    """The continuity store through the public ``repro.api`` calls."""

    def __init__(self, slots: int):
        from repro import api
        self.api = api
        self.store = api.make_store("continuity", table_slots=slots)

    def create(self):
        return self.store.create()

    def load(self, table, keys, vals, batch):
        return self.api.bulk_load(self.store, table, keys, vals, batch=batch)

    def lookup(self, table, keys):
        res = self.store.lookup(table, keys)
        return res.ok, res.values

    def update(self, table, keys, vals):
        table, res = self.store.update(table, keys, vals)
        return table, res.ok


# -- finding things by name ---------------------------------------------------

def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(spec: dict, cell: dict, root: str = ROOT) -> dict:
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def load_traffic(name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def cell_metrics(spec: dict, cell: dict, trace: bool) -> list:
    """The metric entries this cell reports in a run with or without
    ``--trace``: those whose ``workloads`` name the cell, or that have
    no ``workloads``."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_reader(name: str, root: str = ROOT) -> Callable:
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def device_peaks(kind: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def seeded(seed: int, n: int) -> list:
    """``n`` independent RandomStates from one seed of any size."""
    return [np.random.RandomState(s.generate_state(4))
            for s in np.random.SeedSequence(seed).spawn(n)]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the backend compiles JAX reports, and their seconds."""

    def __init__(self):
        import jax.monitoring
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs


# -- one run ------------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: str = ROOT, require_chip: bool = True,
        make_store: Optional[Callable] = None,
        traffic_seed: int = generator.TRAFFIC_SEED) -> dict:
    """Run one cell once and return its result line as a dict.

    ``make_store(slots)`` replaces the system under test (the control and
    the faults); ``require_chip=False`` lets a test run on the CPU;
    ``traffic_seed`` sends other traffic (``control.py`` only)."""
    spec = load_spec(root)
    cell = find_cell(spec, workload)
    config = load_config(spec, cell, root)
    mix = load_traffic(cell["traffic"], root)
    readers = {m["name"]: load_reader(m["name"], root)
               for m in cell_metrics(spec, cell, trace)}

    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        raise NoChip(f"the cell needs {cell['chips']} TPU chip(s); JAX "
                     f"found {len(devices)} x {devices[0].platform}")
    dev = devices[0]
    peaks = device_peaks(dev.device_kind, root)
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()
    log(f"device: {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    # -- set-up ----------------------------------------------------------------
    data_rng, traffic_rng, warm_rng, check_rng = seeded(seed, 4)
    slots, batch = int(config["table_slots"]), int(mix["batch"])
    records = int(config["records"])
    t0 = time.perf_counter()
    store = (make_store or ApiStore)(slots)
    table = store.create()
    jax.block_until_ready(table)
    t_create = time.perf_counter() - t0
    t0 = time.perf_counter()
    keys = ycsb.make_key(np.arange(records))
    vals = ycsb.make_value(data_rng, records)
    traffic = generator.Traffic(mix, config, records, traffic_seed)
    t_data = time.perf_counter() - t0
    t0, c0 = time.perf_counter(), compiles.seconds
    load_batch = int(config["load_batch"])
    table, load_ok = store.load(table, keys, vals, load_batch)
    jax.block_until_ready(table)
    t_load = time.perf_counter() - t0
    log(f"load: {records} records in {-(-records // load_batch)} calls of "
        f"{load_batch}, {t_load:.3f} s (compile {compiles.seconds - c0:.3f}"
        f" s); {int((~load_ok).sum())} not acknowledged")
    ref = reference.ValueByRecord(vals)
    del keys, vals

    t0, c0 = time.perf_counter(), compiles.seconds
    for _ in range(2):                      # the mix's own shapes only
        for op in traffic.ops:
            k = jax.device_put(ycsb.make_key(traffic.ids({"op": op}, warm_rng)))
            if op == "lookup":
                out = store.lookup(table, k)
            else:
                out = store.update(table, k,
                                   jax.device_put(traffic.values(warm_rng)))
            jax.block_until_ready(out)
            del out
    t_warm = time.perf_counter() - t0
    log(f"warm-up: {' '.join(traffic.ops)} at {batch} ops a call, twice, "
        f"{t_warm:.3f} s (compile {compiles.seconds - c0:.3f} s)")
    log(f"set-up: create {t_create:.3f} s, data {t_data:.3f} s, load "
        f"{t_load:.3f} s, warm-up {t_warm:.3f} s; {compiles.n} compiles, "
        f"{compiles.seconds:.3f} s")

    # -- the window --------------------------------------------------------
    span = jax.profiler.TraceAnnotation
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the benchmark's spans only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    calls = []
    n_compiles = compiles.n
    t_begin = time.perf_counter()
    setup_s = t_begin - t_start
    with span("bench.window"):
        while time.perf_counter() - t_begin < seconds:
            for call in traffic.calls:
                op = call["op"]
                with span("bench.generate"):
                    ids = traffic.ids(call, traffic.ranks)
                    k = ycsb.make_key(ids)
                    v = traffic.values(traffic_rng) if op == "update" else None
                t_issue = time.perf_counter()
                with span(f"bench.put.{op}"):
                    kd = jax.device_put(k)
                    vd = None if v is None else jax.device_put(v)
                with span(f"bench.dispatch.{op}"):
                    if op == "lookup":
                        out = store.lookup(table, kd)
                    else:
                        table, out = store.update(table, kd, vd)
                with span(f"bench.fetch.{op}"):
                    out = jax.device_get(out)
                t_done = time.perf_counter()
                with span("bench.record"):
                    found, values = (out if op == "lookup" else (out, None))
                    calls.append(Call(op, ids, v, np.asarray(found),
                                      values, t_issue, t_done))
    window_s = calls[-1].t_done - t_begin
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t_stop = time.perf_counter() - t0
    window_compiles = compiles.n - n_compiles
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    # -- per-layer trace reduction -------------------------------------------
    view = None
    if trace:
        import xplane
        t0 = time.perf_counter()
        try:
            view = xplane.read_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: stopped in {t_stop:.3f} s, read in "
            f"{time.perf_counter() - t0:.3f} s; {len(view.ops[0]) if view.ops else 0}"
            f" device ops, {len(view.spans)} spans; host seconds by span "
            + json.dumps(view.host_spans()))

    # -- read back, through the store, every record the window updated and
    # a sample of those it did not; all calls issued, then fetched at once
    updated = np.unique(np.concatenate(
        [c.ids for c in calls if c.op == "update"] or [np.zeros(0, int)]))
    untouched = np.setdiff1d(
        check_rng.randint(0, records, size=UNTOUCHED_SAMPLE), updated)
    back_ids = np.concatenate([updated, untouched])
    outs = []
    for lo in range(0, len(back_ids), batch):
        pad = np.resize(back_ids[lo:lo + batch], batch)
        outs.append(store.lookup(table, jax.device_put(ycsb.make_key(pad))))
    outs = jax.device_get(outs)
    n = len(back_ids)
    back_found = np.concatenate([np.asarray(f) for f, _ in outs])[:n]
    back_vals = np.concatenate([np.asarray(v) for _, v in outs])[:n]
    del table, store, outs

    # -- the check against the plain reference --------------------------------
    wrong = reference.check_calls(ref, calls)
    checks = {"load_unacked": int((~load_ok).sum())}
    if any(c.op == "lookup" for c in calls):
        checks["lookup_wrong"] = wrong["lookup"]
    found, want = ref.lookup(back_ids)
    bad = (back_found != found) | (found & np.any(back_vals != want, -1))
    n_updates = sum(len(c.ids) for c in calls if c.op == "update")
    refused = sum(int((~c.found).sum()) for c in calls if c.op == "update")
    if n_updates:
        checks["update_acked_absent"] = wrong["update"]
        checks["update_refused_pct"] = 100.0 * refused / n_updates
        checks["readback_wrong"] = int(bad[:len(updated)].sum())
    checks["untouched_wrong"] = int(bad[len(updated):].sum())
    limits = {name: LIMITS[name] for name in checks}
    correct = all(checks[n] <= limits[n] for n in checks)
    attempted = sum(len(c.ids) for c in calls)

    # -- metrics ---------------------------------------------------------------
    rv = RunView(calls, window_s, setup_s, view, peaks, batch)
    metrics = {}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    for name, read in readers.items():
        got = read(rv)
        if got is None:
            log(f"metric {name}: nothing to read")
            continue
        value, samples = got
        log(f"metric {name}: {value} {units[name]} from {samples} samples")
        metrics[name] = {"value": value, "unit": units[name]}
    log(f"window: {window_s:.3f} s, {len(calls)} calls, {attempted} ops, "
        f"{window_compiles} compiles inside it; set-up {setup_s:.3f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    log(f"updates refused by the store: {refused} of {n_updates}; read "
        f"back {len(updated)} updated and {len(untouched)} other records")
    result = {"correct": correct, "attempted": attempted,
              "failed": wrong["lookup"] + wrong["update"] + refused,
              "metrics": metrics, "device": device}
    if view is not None:
        device["busy_s"] = view.busy_s
        device["window_s"] = view.window_s
        result["breakdown"] = view.breakdown()
    result["checks"] = {n: {"value": checks[n], "limit": limits[n]}
                        for n in checks}
    for n in checks:
        log(f"check {n}: {checks[n]} (limit {limits[n]})")
    return result
