"""The `repro.api` contract, registry-parametrized over EVERY scheme.

Three layers:
  * protocol conformance — each registered store satisfies `HashStore` and
    the uniform create/insert/update/delete/lookup/resize/load_factor/stats
    round-trip, including masked batches;
  * accounting — `CostLedger` PM-write averages reproduce paper Table I
    (continuity 2/2/1, level 2/~2/1, pfarm 5/5/5) and read amplification
    orders (continuity 1 <= level <= 4);
  * execution policy — `ExecPolicy(serial)` vs `ExecPolicy(wave)` produce
    byte-identical tables/counters through the API, and the Pallas probe
    strategies match the gather lookup exactly.
"""

import dataclasses
import re

import jax
import numpy as np
import pytest

from repro import api
from repro.data import ycsb

SLOTS = 1024
N = 300


def keys_vals(n=N, seed=0, start=0):
    rng = np.random.RandomState(seed)
    return ycsb.make_key(np.arange(start, start + n)), ycsb.make_value(rng, n)


@pytest.fixture(params=api.available_schemes())
def scheme(request):
    return request.param


@pytest.fixture
def store(scheme):
    return api.make_store(scheme, table_slots=SLOTS)


def test_registry_lists_builtin_schemes():
    names = api.available_schemes()
    for expected in ("continuity", "level", "pfarm", "dense"):
        assert expected in names


def test_registry_rejects_unknown_and_duplicate():
    with pytest.raises(ValueError, match="unknown scheme"):
        api.make_store("cuckoo")
    with pytest.raises(ValueError, match="already registered"):
        api.register_scheme("dense", api.DenseStore.from_slots)


def test_store_satisfies_protocol(store):
    assert isinstance(store, api.HashStore)
    for method in ("create", "insert", "update", "delete", "lookup",
                   "resize", "load_factor", "stats"):
        assert callable(getattr(store, method)), method
    assert isinstance(store.policy, api.ExecPolicy)
    # hashable + frozen: usable as jit static / inside frozen configs
    assert hash(store) == hash(dataclasses.replace(store))


def test_crud_roundtrip(store):
    K, V = keys_vals()
    t = store.create()
    t, ins = store.insert(t, K, V)
    assert bool(ins.ok.all())
    assert int(t.count) == N

    hit = store.lookup(t, K)
    assert bool(hit.ok.all())
    np.testing.assert_array_equal(np.asarray(hit.values), V)
    assert int(hit.ledger.ops) == N
    assert bool((np.asarray(hit.reads) >= 1).all())

    neg = ycsb.negative_keys(np.random.RandomState(9), N, 64)
    assert not bool(store.lookup(t, neg).ok.any())

    V2 = keys_vals(seed=5)[1]
    t, upd = store.update(t, K, V2)
    assert bool(upd.ok.all())
    np.testing.assert_array_equal(np.asarray(store.lookup(t, K).values), V2)

    t, dele = store.delete(t, K[: N // 2])
    assert bool(dele.ok.all())
    assert int(t.count) == N - N // 2
    assert not bool(store.lookup(t, K[: N // 2]).ok.any())
    assert bool(store.lookup(t, K[N // 2:]).ok.all())

    lf = float(store.load_factor(t))
    assert 0.0 < lf < 1.0
    info = store.stats(t)
    assert info["scheme"] == store.name
    assert info["count"] == N - N // 2
    assert info["total_slots"] >= SLOTS - 20  # sized to ~table_slots


def test_masked_mutations(store):
    """Masked-off ops must neither write nor count, for every scheme —
    what lets ANY registered scheme back the serving page table."""
    K, V = keys_vals(n=64)
    mask = np.arange(64) % 2 == 0
    t = store.create()
    t, ins = store.insert(t, K, V, mask)
    assert bool((np.asarray(ins.ok) == mask).all())
    assert int(t.count) == mask.sum()
    # masked batch pays exactly what inserting only the survivors pays,
    # and the ops denominator counts only ACTIVE ops (per-op averages of a
    # masked batch match the unmasked equivalent)
    _, ref = store.insert(store.create(), K[mask], V[mask])
    assert int(ins.ledger.pm_writes) == int(ref.ledger.pm_writes)
    assert int(ins.ledger.ops) == int(mask.sum())
    assert ins.ledger.pm_per_op() == ref.ledger.pm_per_op()
    hit = store.lookup(t, K)
    assert bool((np.asarray(hit.ok) == mask).all())
    t, dele = store.delete(t, K, ~mask)
    assert not bool(dele.ok.any()) and int(t.count) == mask.sum()
    t, dele = store.delete(t, K, mask)
    assert int(t.count) == 0


def test_resize_preserves_members(store):
    K, V = keys_vals(n=128)
    t = store.create()
    t, _ = store.insert(t, K, V)
    t, _ = store.delete(t, K[:32])
    big, bt = store.resize(t, factor=2)
    assert big.total_slots(bt) >= 2 * (store.total_slots(t) - 40)
    assert int(bt.count) == 96
    assert not bool(big.lookup(bt, K[:32]).ok.any())
    hit = big.lookup(bt, K[32:])
    assert bool(hit.ok.all())
    np.testing.assert_array_equal(np.asarray(hit.values), V[32:])


# ---------------------------------------------------------------------------
# accounting: paper Table I through the unified ledger
# ---------------------------------------------------------------------------

TABLE_I = {  # scheme -> (insert, update, delete) PM writes per op
    "continuity": (2.0, 2.0, 1.0),
    "pfarm": (5.0, 5.0, 5.0),
}


def test_ledger_reproduces_paper_table1(scheme):
    K, V = keys_vals()
    store = api.make_store(scheme, table_slots=4096)
    t = store.create()
    t, ins = store.insert(t, K, V)
    t, upd = store.update(t, K, keys_vals(seed=3)[1])
    t, dele = store.delete(t, K[: N // 2])
    cells = (ins.ledger.pm_per_op(), upd.ledger.pm_per_op(),
             dele.ledger.pm_per_op())
    if scheme in TABLE_I:
        assert cells == pytest.approx(TABLE_I[scheme])
    elif scheme == "level":
        # paper reports insert 2–2.01, update 2–5 (logged fallback), delete 1
        assert cells[0] == pytest.approx(2.0, abs=0.05)
        assert 2.0 <= cells[1] <= 5.0
        assert cells[2] == pytest.approx(1.0)


def test_read_amplification_ordering():
    """Continuity: 1 fetch/lookup; level: up to 4 — the paper's §II claim,
    measured through one ledger."""
    K, V = keys_vals()
    reads = {}
    for scheme in ("continuity", "level", "pfarm"):
        store = api.make_store(scheme, table_slots=4096)
        t, _ = store.insert(store.create(), K, V)
        reads[scheme] = store.lookup(t, K).ledger.reads_per_op()
    assert reads["continuity"] == pytest.approx(1.0)
    assert 1.0 <= reads["level"] <= 4.0
    assert reads["continuity"] <= reads["level"]
    assert reads["pfarm"] >= 1.0


# ---------------------------------------------------------------------------
# execution policy: one boundary, interchangeable strategies
# ---------------------------------------------------------------------------

def _tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_policy_serial_vs_wave_byte_identical():
    K, V = keys_vals()
    V2 = keys_vals(seed=7)[1]
    wave = api.make_store("continuity", table_slots=SLOTS)
    serial = wave.with_policy(api.ExecPolicy(engine="serial"))
    tw, rw = wave.insert(wave.create(), K, V)
    ts, rs = serial.insert(serial.create(), K, V)
    _tree_equal(tw, ts)
    _tree_equal(rw, rs)
    tw2, uw = wave.update(tw, K[::3], V2[::3])
    ts2, us = serial.update(ts, K[::3], V2[::3])
    _tree_equal(tw2, ts2)
    _tree_equal(uw, us)
    tw3, dw = wave.delete(tw2, K[1::2])
    ts3, ds = serial.delete(ts2, K[1::2])
    _tree_equal(tw3, ts3)
    _tree_equal(dw, ds)


@pytest.mark.parametrize("probe", ["reference", "pallas"])
def test_policy_probe_strategies_match_gather(probe):
    n = 96
    K, V = keys_vals(n=n)
    gather = api.make_store("continuity", table_slots=512)
    t, _ = gather.insert(gather.create(), K, V)
    kern = gather.with_policy(api.ExecPolicy(probe=probe, qblock=8))
    for q in (K, ycsb.negative_keys(np.random.RandomState(2), n, 32)):
        a = kern.lookup(t, q)
        b = gather.lookup(t, q)
        _tree_equal(a, b)


def test_policy_validation():
    with pytest.raises(AssertionError):
        api.ExecPolicy(engine="quantum")
    with pytest.raises(AssertionError):
        api.ExecPolicy(probe="telepathy")


def test_custom_scheme_registration_roundtrip():
    """The registry is the extension seam: a new scheme registered at
    runtime is immediately usable through the same surface."""
    def tiny_dense(table_slots, policy, **kw):
        return api.DenseStore.from_slots(max(8, table_slots // 4), policy)

    api.register_scheme("dense_quarter", tiny_dense)
    try:
        st = api.make_store("dense_quarter", table_slots=64)
        assert st.cfg.capacity == 16
        K, V = keys_vals(n=8)
        t, res = st.insert(st.create(), K, V)
        assert bool(res.ok.all())
        assert bool(st.lookup(t, K).ok.all())
    finally:
        from repro.api import registry as _r
        _r._REGISTRY.pop("dense_quarter", None)


@pytest.mark.parametrize("scheme", api.available_schemes())
def test_bulk_load_matches_one_shot_insert(scheme):
    """Loading in bounded, masked batches gives the one-shot table."""
    store = api.make_store(scheme, table_slots=SLOTS)
    K, V = keys_vals(N + 7)
    one, res = store.insert(store.create(), K, V)
    bulk, ok = api.bulk_load(store, store.create(), K, V, batch=64)
    np.testing.assert_array_equal(ok, np.asarray(res.ok))
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(bulk)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the lookup path's own marks: host spans and named scopes
# ---------------------------------------------------------------------------

LOOKUP_SCOPES = ("lookup.probe", "lookup.stash", "lookup.plan")


def _tiny_lookup(probe="gather", n=2048):
    store = api.make_store("continuity", table_slots=1 << 14).with_policy(
        api.ExecPolicy(probe=probe))
    K, V = keys_vals(n=n)
    table, _ = store.insert(store.create(), K, V)
    return store, table, jax.numpy.asarray(K[:256])


def _profiled(tmp_path, fn):
    import glob
    import os
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return jax.profiler.ProfileData.from_file(path)


def _host_events(pd, prefix):
    return sorted(((e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for p in pd.planes if not p.name.startswith("/device:")
                   for line in p.lines for e in line.events
                   if e.name.startswith(prefix)), key=lambda e: e[1])


def test_lookup_writes_its_spans_into_the_profiler_trace(tmp_path):
    """``api.lookup`` spans the call and holds ``api.lookup.launch``, the
    jitted call; neither carries an attribute, and a lookup made while
    the profiler is off writes neither."""
    store, table, keys = _tiny_lookup()
    jax.block_until_ready(store.lookup(table, keys).ok)   # compiled here
    pd = _profiled(tmp_path, lambda: jax.block_until_ready(
        [store.lookup(table, keys).ok for _ in range(3)]))
    outer = [e for e in _host_events(pd, "api.") if e[0] == "api.lookup"]
    launch = [e for e in _host_events(pd, "api.")
              if e[0] == "api.lookup.launch"]
    assert len(outer) == len(launch) == 3
    for (_, s0, e0, a0), (_, s1, e1, a1) in zip(outer, launch):
        assert s0 <= s1 < e1 <= e0
        assert a0 == a1 == {}


@pytest.mark.parametrize("probe", ["gather", "pallas"])
def test_lookup_program_carries_its_scopes(probe):
    """The compiled lookup names each op's phase in its ``op_name``: the
    probe (gather or Pallas kernel), the stash scan and the verb plan."""
    from repro.api import stores
    store, table, keys = _tiny_lookup(probe)
    text = stores._jit_lookup.lower(store, table, keys).compile().as_text()
    names = [n.split("/") for n in re.findall(r'op_name="([^"]*)"', text)]
    for scope in LOOKUP_SCOPES:
        assert any(scope in n for n in names), scope
    # the stash's chunk loop is inside its scope, and the scopes do not nest
    assert any("lookup.stash" in n and "while" in n for n in names)
    assert not any(sum(s in n for s in LOOKUP_SCOPES) > 1 for n in names)
