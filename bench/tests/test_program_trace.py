"""The readers of the program's own marks in a profiler trace
(``program_trace.py``): its ``api.*`` host spans and the named scope of
each device op, on a trace of the store recorded here on the CPU at 2^14
slots and on hand-built views.  The readers that were there before read
the same values whether or not the marks are attached to the view.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import glob
import os
import re

import numpy as np
import pytest

import harness
import program_trace
import xplane
import ycsb
from conftest import TINY_BATCH, TINY_SLOTS

NEW = ("launch_ms.read", "lookup_api_ms", "lookup_out_buffers",
       "lookup_probe_ms", "lookup_stash_ms", "lookup_plan_ms")
SCOPED = ("lookup_probe_ms", "lookup_stash_ms", "lookup_plan_ms")
CALLS = 6
MS = 1_000_000


def _run(trace):
    return harness.RunView([], 1.0, 2.0, trace,
                           harness.device_peaks("TPU v5 lite"), TINY_BATCH)


def _read(name, trace):
    return harness.load_reader(name)(_run(trace))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace of CALLS lookups through ``repro.api`` under the
    benchmark's window span, the lookup compiled before it started, with
    other lookup programs loaded in the process."""
    import jax
    from repro import api
    store = api.make_store("continuity", table_slots=TINY_SLOTS)
    rng = np.random.RandomState(3)
    n = int(0.7 * TINY_SLOTS)
    table, _ = store.insert(store.create(), ycsb.make_key(np.arange(n)),
                            ycsb.make_value(rng, n))
    keys = jax.device_put(ycsb.make_key(rng.randint(0, n, TINY_BATCH)))
    jax.block_until_ready(store.lookup(table, keys).ok)
    # another program of the same module, loaded but not run in the trace
    other = api.make_store("level", table_slots=TINY_SLOTS)
    jax.block_until_ready(other.lookup(other.create(), keys).ok)
    out = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(CALLS):
            with jax.profiler.TraceAnnotation("bench.dispatch.lookup"):
                res = store.lookup(table, keys)
            with jax.profiler.TraceAnnotation("bench.fetch.lookup"):
                jax.device_get((res.ok, res.values))
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return path, store, table, keys


def _plain_read(path):
    return getattr(xplane.read, "__wrapped__", xplane.read)(path)


def test_new_readers_on_recorded_trace(recorded):
    program_trace.install()
    view = xplane.read(recorded[0])
    got = {name: _read(name, view) for name in NEW}
    launch, n = got["launch_ms.read"]
    api_ms, n_api = got["lookup_api_ms"]
    assert n == n_api == CALLS and launch > 0 and api_ms > 0
    assert launch + api_ms <= _read("dispatch_ms.read", view)[0]
    # 5 LookupResult + 6 VerbPlan + 4 CostLedger
    assert got["lookup_out_buffers"] == (15.0, CALLS)
    runs = _read("lookup_device_ms", view)[1]
    assert runs == CALLS
    for name in SCOPED:
        value, n = got[name]
        assert value > 0 and n == runs, name


def test_existing_readers_read_the_same_with_program_marks(recorded):
    program_trace.install()
    plain, marked = _plain_read(recorded[0]), xplane.read(recorded[0])
    assert not hasattr(plain, "program_spans") and marked.program_spans
    for field in ("ops", "modules", "spans", "window"):
        assert getattr(plain, field) == getattr(marked, field), field
    assert plain.breakdown() == marked.breakdown()
    assert plain.host_spans() == marked.host_spans()
    metrics = os.path.join(harness.HERE, "metrics")
    names = sorted(f[:-3] for f in os.listdir(metrics) if f.endswith(".py"))
    old = [n for n in names if n not in NEW and n != "launch_ms"]
    assert {"dispatch_ms.read", "lookup_device_ms",
            "lookup_roofline_pct"} <= set(old)
    for name in old:
        assert _read(name, plain) == _read(name, marked), name
    for name in NEW:
        assert _read(name, plain) is None, name


def test_op_names_are_the_compiled_programs(recorded):
    """The op names decoded from the trace are those of the lookup
    program as compiled: every instruction, with its ``op_name``, and the
    three scopes among them; its output count is that of its result."""
    import jax
    from repro.api import stores
    path, store, table, keys = recorded
    loaded = {name: p for name, p in program_trace.hlo_programs(path).items()
              if name.startswith("jit__jit_lookup(")}
    assert len(loaded) >= 2
    text = stores._jit_lookup.lower(store, table, keys).compile().as_text()
    want = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([^\s=]+) = ", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            want[m.group(1)] = op.group(1) if op else ""
    same = [p for p in loaded.values() if p.op_names == want]
    assert same
    paths = {p for n in want.values() for p in n.split("/")}
    assert {"lookup.probe", "lookup.stash", "lookup.plan"} <= paths
    out = jax.eval_shape(stores._jit_lookup, store, table, keys)
    assert {p.outputs for p in same} == {len(jax.tree.leaves(out))} == {15}


def test_fields_decodes_each_wire_type():
    # 1: varint 150, 2: "hi", 3: fixed64 5, 4: fixed32 7, 5: nested {1: 1}
    msg = bytes([0x08, 0x96, 0x01, 0x12, 2]) + b"hi" \
        + bytes([0x19]) + (5).to_bytes(8, "little") \
        + bytes([0x25]) + (7).to_bytes(4, "little") \
        + bytes([0x2A, 2, 0x08, 0x01])
    got = [(k, bytes(v) if isinstance(v, memoryview) else v)
           for k, v in program_trace.fields(msg)]
    assert got == [(1, 150), (2, b"hi"), (3, 5), (4, 7), (5, b"\x08\x01")]
    with pytest.raises(ValueError, match="wire type 3"):
        list(program_trace.fields(bytes([0x0B])))


def _hand_view(programs, ran=()):
    """One lookup execution [0, 5) ms: a loop [0, 2) whose body op is the
    leaf, then fused ops, a copy with no op name and a gap; an update
    [6, 7) ms whose op is not the lookup's.  ``programs`` maps a program
    to its op names (a lookup program returns 15 buffers, any other 3);
    ``ran`` names the programs whose ops ran (the CPU's ids)."""
    t = xplane.TraceView(
        ops=[[("while.1", 0, 2 * MS), ("body.1", 0, 2 * MS),
              ("fusion.1", 2 * MS, 3 * MS), ("fusion.2", 3 * MS, 4 * MS),
              ("copy-done", 4 * MS, 4.5 * MS), ("fusion.9", 6 * MS, 7 * MS)]],
        modules=[("jit__jit_lookup(7)", 0, 5 * MS),
                 ("jit_update(9)", 6 * MS, 7 * MS)],
        spans=[("bench.window", 0, 10 * MS)], window=(0, 10 * MS))
    t.programs = {
        name: program_trace.Program(
            ops, 15 if name.startswith("jit__jit_lookup(") else 3)
        for name, ops in programs.items()}
    t.programs_run = set(ran)
    t.program_spans = [
        ("api.lookup", 0, 2 * MS, {}),
        ("api.lookup.launch", MS // 2, MS, {}),
        ("api.lookup.launch", 12 * MS, 13 * MS, {})]
    return t


LOOKUP_OPS = {
    "while.1": "jit(_jit_lookup)/jit(lookup)/lookup.stash/while",
    "body.1": "jit(_jit_lookup)/jit(lookup)/lookup.stash/while/body/eq",
    "fusion.1": "jit(_jit_lookup)/jit(lookup)/lookup.probe/gather",
    # nested scopes: the innermost counts
    "fusion.2": "jit(_jit_lookup)/lookup.plan/lookup.probe/add",
    "copy-done": "",
}
OTHER_OPS = dict(LOOKUP_OPS, **{"fusion.1": "x"})


@pytest.mark.parametrize("module,hlo,ran", [
    # a TPU names an execution by its program
    ("jit__jit_lookup(7)", {"jit__jit_lookup(7)": LOOKUP_OPS,
                            "jit__jit_lookup(8)": {"fusion.1": "other"},
                            "jit_update(9)": {"fusion.9": "jit(update)/add"}},
     ()),
    # the CPU by its module: the program whose id its ops carried
    ("jit__jit_lookup", {"jit__jit_lookup(6)": LOOKUP_OPS,
                         "jit__jit_lookup(4)": OTHER_OPS},
     ("jit__jit_lookup(6)", "jit_update(9)")),
    # or two of the module that ran, naming its ops alike
    ("jit__jit_lookup", {"jit__jit_lookup(3)": LOOKUP_OPS,
                         "jit__jit_lookup(4)": dict(LOOKUP_OPS, extra="x"),
                         "jit__jit_lookup(5)": OTHER_OPS},
     ("jit__jit_lookup(3)", "jit__jit_lookup(4)")),
])
def test_scope_attribution_on_hand_built_view(module, hlo, ran):
    t = _hand_view(hlo, ran)
    t.modules[0] = (module,) + t.modules[0][1:]
    assert _read("lookup_probe_ms", t) == (pytest.approx(2.0), 1)
    assert _read("lookup_stash_ms", t) == (pytest.approx(2.0), 1)
    assert _read("lookup_plan_ms", t) == (pytest.approx(0.0), 1)
    assert _read("launch_ms.read", t) == (pytest.approx(0.5), 1)
    assert _read("lookup_api_ms", t) == (pytest.approx(1.5), 1)
    assert _read("lookup_out_buffers", t) == (15.0, 1)


@pytest.mark.parametrize("module,hlo,ran", [
    ("jit__jit_lookup(7)", {}, ()),                        # no HLO at all
    ("jit__jit_lookup(7)", {"jit__jit_lookup(7)": {
        k: v for k, v in LOOKUP_OPS.items() if k != "fusion.2"}}, ()),  # gone
    ("jit__jit_lookup(7)", {"jit__jit_lookup(7)": {
        k: re.sub(r"lookup\.\w+/", "", v)
        for k, v in LOOKUP_OPS.items()}}, ()),            # no scopes
    ("jit__jit_lookup(7)", {"jit__jit_lookup(8)": LOOKUP_OPS}, ()),  # not it
    ("jit__jit_lookup", {"jit__jit_lookup(3)": LOOKUP_OPS,      # two ran
                         "jit__jit_lookup(4)": OTHER_OPS},      # that disagree
     ("jit__jit_lookup(3)", "jit__jit_lookup(4)")),
    # the CPU's ops carried an id whose HLO the trace does not hold (an
    # executable loaded from the persistent cache)
    ("jit__jit_lookup", {"jit__jit_lookup(3)": LOOKUP_OPS},
     ("jit__jit_lookup(6)",)),
])
def test_scoped_readers_find_nothing_rather_than_guess(module, hlo, ran):
    t = _hand_view(hlo, ran)
    t.modules[0] = (module,) + t.modules[0][1:]
    for name in SCOPED:
        assert _read(name, t) is None, name


@pytest.mark.parametrize("outputs,want", [
    ((15,), (15.0, 1)), ((15, 15), (15.0, 1)), ((15, 3), None), ((), None)])
def test_out_buffers_from_the_program_that_ran(outputs, want):
    """The CPU's execution resolves to the programs of its module that
    ran: one count between them is read, two are not."""
    t = _hand_view({})
    t.modules[0] = ("jit__jit_lookup",) + t.modules[0][1:]
    t.programs = {f"jit__jit_lookup({i})": program_trace.Program({}, n)
                  for i, n in enumerate(outputs)}
    t.programs_run = set(t.programs)
    assert _read("lookup_out_buffers", t) == want


def test_readers_without_program_marks_find_nothing():
    t = _hand_view({"jit__jit_lookup(7)": LOOKUP_OPS})
    del t.program_spans, t.programs, t.programs_run
    for name in NEW:
        assert _read(name, t) is None, name
    assert _read("lookup_device_ms", t) == (pytest.approx(5.0), 1)
