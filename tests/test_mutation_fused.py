"""The fused mutation engine (ISSUE 9): update/delete as single-pass
rank-indexed commits, the Pallas mutation-plan kernel, the `ExecPolicy`
mutate/use_fp knobs, and the resize-step SLO controller.

The load-bearing contract: `ch.update`/`ch.delete` (every match backend)
stay BYTE-identical to the `update_serial`/`delete_serial` oracles on
every table field, across batch sizes, stash on/off, duplicate keys, and
masked batches — that is what lets the bench's `wave >= serial on every
op x batch cell` band replace the serial path without a semantic rider.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import ExecPolicy
from repro.core import continuity as ch
from repro.data import ycsb
from repro.kernels import ops as K


def keys_vals(ids, seed=0):
    rng = np.random.RandomState(seed)
    ids = np.asarray(ids)
    return (jnp.asarray(ycsb.make_key(ids)),
            jnp.asarray(ycsb.make_value(rng, len(ids))))


def table_diff(a, b):
    for f in a._fields:
        if not bool((getattr(a, f) == getattr(b, f)).all()):
            return f
    return None


def _cfg(num_buckets=1024, stash=True):
    return ch.ContinuityConfig(num_buckets=num_buckets,
                               stash_frac=(1 / 8 if stash else 0.0))


def _mutation_ids(batch, rng):
    """Mixed workload: live keys, absent keys, duplicates."""
    ids = np.arange(batch)
    ids[batch - batch // 8:] = rng.randint(0, batch // 2,
                                           size=batch // 8)  # duplicates
    return ids


# ---------------------------------------------------------------------------
# byte-identity sweep: {64, 512, 4096} x {stash on/off} x {update, delete}
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stash", [True, False], ids=["stash", "nostash"])
@pytest.mark.parametrize("batch", [64, 512, 4096])
@pytest.mark.parametrize("op", ["update", "delete"])
def test_fused_matches_serial_sweep(op, batch, stash):
    cfg = _cfg(num_buckets=max(32, batch // 4), stash=stash)
    rng = np.random.RandomState(batch + stash)
    kb, vb = keys_vals(np.arange(3 * batch // 4))   # live prefix
    table = ch.create(cfg)
    table, okb, _ = ch.insert(cfg, table, kb, vb)
    assert bool(okb.all())

    ids = _mutation_ids(batch, rng)                 # live + absent + dups
    keys, vals = keys_vals(ids, seed=1)
    mask = jnp.asarray(rng.random_sample(batch) > 0.1)
    if op == "update":
        ts, oks, cs = ch.update_serial(cfg, table, keys, vals, mask)
        tf, okf, cf = ch.update(cfg, table, keys, vals, mask)
    else:
        ts, oks, cs = ch.delete_serial(cfg, table, keys, mask)
        tf, okf, cf = ch.delete(cfg, table, keys, mask)
    assert table_diff(ts, tf) is None
    assert bool((oks == okf).all())
    assert int(cs.pm_writes) == int(cf.pm_writes)
    assert int(oks.sum()) > 0


# ---------------------------------------------------------------------------
# kernel backends: plan identity + fused identity through every backend
# ---------------------------------------------------------------------------

def _loaded(n=200, stash=True):
    cfg = _cfg(num_buckets=64, stash=stash)
    keys, vals = keys_vals(np.arange(n))
    table = ch.create(cfg)
    table, ok, _ = ch.insert(cfg, table, keys, vals)
    return cfg, table, keys, vals, ok


def test_mutation_plan_kernel_matches_ref():
    cfg, table, keys, _, _ = _loaded()
    nkeys, _ = keys_vals(np.arange(500, 560))       # negatives too
    for qs in (keys, nkeys):
        mk, vk, fk = K.mutation_plan(cfg, table, qs, use_kernel=True)
        mr, vr, fr = K.mutation_plan(cfg, table, qs, use_kernel=False)
        assert bool((mk == mr).all())
        assert bool((vk == vr).all())
        assert bool((fk == fr).all())


def test_mutation_plan_matches_probe_and_lookup():
    """The plan's match side agrees with the probe kernel and the full
    lookup on main-segment hits; flip is exactly old-bit | victim-bit."""
    cfg, table, keys, _, _ = _loaded()
    m, v, f = K.mutation_plan(cfg, table, keys, use_kernel=False)
    pm, pe, _, _ = K.probe_table(cfg, table, keys, use_kernel=False,
                                 use_fp=True)
    assert bool((m == pm).all())
    assert bool((v == pe).all())
    exp = (jnp.where(m >= 0, jnp.uint32(1) << jnp.maximum(m, 0).astype(
        jnp.uint32), jnp.uint32(0))
        | jnp.where(v >= 0, jnp.uint32(1) << jnp.maximum(v, 0).astype(
            jnp.uint32), jnp.uint32(0)))
    assert bool((f == exp).all())


@pytest.mark.parametrize("probe", ["pallas", "reference"])
@pytest.mark.parametrize("op", ["update", "delete"])
def test_fused_kernel_backends_match_serial(op, probe):
    cfg, table, keys, vals, _ = _loaded()
    rng = np.random.RandomState(3)
    ids = _mutation_ids(160, rng)
    keys, vals = keys_vals(ids, seed=2)
    if op == "update":
        ts, oks, _ = ch.update_serial(cfg, table, keys, vals)
        tf, okf, _ = ch.update(cfg, table, keys, vals, probe=probe)
    else:
        ts, oks, _ = ch.delete_serial(cfg, table, keys)
        tf, okf, _ = ch.delete(cfg, table, keys, probe=probe)
    assert table_diff(ts, tf) is None
    assert bool((oks == okf).all())


def test_fused_with_stash_hits_matches_serial():
    """Overflow a tiny table so mutations actually hit stash entries
    (delete-from-stash and update's stash->main relocation)."""
    cfg = _cfg(num_buckets=4, stash=True)
    keys, vals = keys_vals(np.arange(90))
    table = ch.create(cfg)
    table, ok, _ = ch.insert(cfg, table, keys, vals)
    assert int(ch.stash_count(table, jnp.arange(cfg.num_pairs)).sum()) > 0
    _, vals2 = keys_vals(np.arange(90), seed=9)
    ts, oks, _ = ch.update_serial(cfg, table, keys, vals2)
    tf, okf, _ = ch.update(cfg, table, keys, vals2)
    assert table_diff(ts, tf) is None and bool((oks == okf).all())
    ts, oks, _ = ch.delete_serial(cfg, table, keys)
    tf, okf, _ = ch.delete(cfg, table, keys)
    assert table_diff(ts, tf) is None and bool((oks == okf).all())


# ---------------------------------------------------------------------------
# residual trip bound: ranks only count ACTIVE (unsafe) ops, so one hot
# pair no longer serializes every cohort (satellite: trip-count pessimism)
# ---------------------------------------------------------------------------

def test_residual_waves_bounded_by_contended_cohort():
    cfg = _cfg(num_buckets=32)
    ids = np.concatenate([np.zeros(5, np.int64), np.arange(1, 40)])
    keys, _ = keys_vals(ids)
    dup_only = jnp.asarray(np.concatenate(
        [np.ones(5, bool), np.zeros(39, bool)]))
    _, _, rank, num_waves = ch._plan_waves(cfg, keys, dup_only)
    assert int(num_waves) == 5                     # the dup cohort alone
    _, _, _, all_waves = ch._plan_waves(
        cfg, keys, jnp.ones(len(ids), bool))
    assert int(all_waves) >= int(num_waves)


@pytest.mark.parametrize("op", ["update", "delete"])
def test_zipf_hot_keys_match_serial(op):
    """YCSB-A skew: a zipf(0.99) batch repeats its hottest key dozens of
    times and holds more duplicate-target ops than one residual trip
    takes (RESIDUAL_WIDTH), so waves run deep and split across trips."""
    cfg = _cfg(num_buckets=2048)
    n, batch = 3000, 2048
    kb, vb = keys_vals(np.arange(n))
    table = ch.create(cfg)
    table, okb, _ = ch.insert(cfg, table, kb, vb)
    assert bool(okb.all())
    rng = np.random.RandomState(7)
    ids = ycsb.Zipf(n).sample(rng, batch)
    _, counts = np.unique(ids, return_counts=True)
    assert counts.max() > 50 and counts[counts > 1].sum() > ch.RESIDUAL_WIDTH
    keys, vals = keys_vals(ids, seed=3)
    if op == "update":
        ts, oks, cs = ch.update_serial(cfg, table, keys, vals)
        tf, okf, cf = ch.update(cfg, table, keys, vals)
    else:
        ts, oks, cs = ch.delete_serial(cfg, table, keys)
        tf, okf, cf = ch.delete(cfg, table, keys)
    assert table_diff(ts, tf) is None
    assert bool((oks == okf).all())
    assert int(cs.pm_writes) == int(cf.pm_writes)


# ---------------------------------------------------------------------------
# ExecPolicy: mutate/use_fp knobs through the store API
# ---------------------------------------------------------------------------

def test_policy_defaults_fp_on_and_validates():
    p = ExecPolicy()
    assert p.use_fp is True
    assert p.mutate == "gather"
    with pytest.raises(AssertionError):
        ExecPolicy(mutate="bogus")


@pytest.mark.parametrize("mutate", ["gather", "pallas", "reference"])
def test_store_mutate_backends_identical(mutate):
    serial = api.make_store("continuity", table_slots=512,
                            policy=ExecPolicy(engine="serial"))
    store = api.make_store("continuity", table_slots=512,
                           policy=ExecPolicy(mutate=mutate))
    keys, vals = keys_vals(np.arange(120))
    t0 = store.create()
    t0, _ = store.insert(t0, keys, vals)
    _, vals2 = keys_vals(np.arange(120), seed=5)
    tu_s, ru_s = serial.update(t0, keys, vals2)
    tu_w, ru_w = store.update(t0, keys, vals2)
    assert table_diff(tu_s, tu_w) is None
    assert bool((ru_s.ok == ru_w.ok).all())
    td_s, rd_s = serial.delete(t0, keys)
    td_w, rd_w = store.delete(t0, keys)
    assert table_diff(td_s, td_w) is None
    assert bool((rd_s.ok == rd_w.ok).all())


@pytest.mark.parametrize("probe", ["pallas", "reference"])
def test_fp_on_off_probe_identity(probe):
    """use_fp is a pure compare-reduction: lookups are result-identical
    with the filter on and off, for hits and misses."""
    on = api.make_store("continuity", table_slots=512,
                        policy=ExecPolicy(probe=probe, use_fp=True))
    off = dataclasses.replace(
        on, policy=ExecPolicy(probe=probe, use_fp=False))
    keys, vals = keys_vals(np.arange(150))
    t = on.create()
    t, _ = on.insert(t, keys, vals)
    miss, _ = keys_vals(np.arange(900, 980))
    for qs in (keys, miss):
        a = on.lookup(t, qs)
        b = off.lookup(t, qs)
        assert bool((a.ok == b.ok).all())
        assert bool((a.values == b.values).all())
        assert bool((a.reads == b.reads).all())


def test_fp_filter_reduces_negative_compares():
    cfg, table, keys, _, _ = _loaded()
    miss, _ = keys_vals(np.arange(2000, 2400))
    s = K.fp_filter_stats(cfg, table, miss)
    assert s["compares_with_fp"] < s["compares_no_fp"]
    assert 0.0 < s["reduction"] <= 1.0
    # 2-bit fields pass ~1/4 of occupied slots on true negatives
    assert s["reduction"] > 0.5


# ---------------------------------------------------------------------------
# crash consistency: fused update/delete through the wave-order matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["update", "delete"])
def test_fused_ops_pass_crash_matrix(op):
    from repro.consistency import matrix
    r = matrix.run_cell("continuity", op)
    assert r.consistent, r.violations[:5]
    assert r.log_used_points == 0


# ---------------------------------------------------------------------------
# resize-step SLO controller
# ---------------------------------------------------------------------------

def test_cohort_move_cost_model():
    from repro.rdma.transport import LinkModel
    lm = LinkModel()
    c = lm.cohort_move_us(320.0, 336.0)
    assert c > lm.rtt_us
    assert lm.cohort_move_us(640.0, 672.0) > c


def test_begin_resize_slo_budget():
    store = api.make_store("continuity", table_slots=512)
    keys, vals = keys_vals(np.arange(200))
    t = store.create()
    t, _ = store.insert(t, keys, vals)
    tight = store.begin_resize(t, step_slo_us=1.0)
    loose = store.begin_resize(t, step_slo_us=500.0)
    assert tight.step_budget == 1                 # floor: always progresses
    assert loose.step_budget > tight.step_budget
    none = store.begin_resize(t)
    assert none.step_budget is None

    # budget=None consumes the controller's choice; the split completes
    # and cuts over exactly as the fixed-budget path does
    rs, steps = loose, 0
    while not rs.done and steps < 10_000:
        rs = store.resize_step(rs)
        steps += 1
    assert rs.done
    new_store, new_table = store.resize_cutover(rs)
    assert int(new_table.count) == 200
    res = new_store.lookup(new_table, keys)
    assert bool(res.ok.all())


def test_cluster_maintenance_slo_mode():
    from repro.cluster import ClusterStore
    cs = ClusterStore("continuity", nodes=2, replicas=1, node_slots=256,
                      policy=api.ExecPolicy())
    keys, vals = keys_vals(np.arange(360))
    res = cs.insert(keys, vals)
    assert bool(np.asarray(res.ok).all())
    moved_any = False
    for _ in range(600):
        acts = cs.maintenance_step(budget=None, trigger_lf=0.6,
                                   step_slo_us=200.0)
        moved_any = moved_any or any(a["action"] in ("step", "cutover")
                                     for a in acts)
        if not acts and moved_any:
            break
    assert moved_any
    assert cs.maintenance["cohorts_moved"] > 0
    res = cs.lookup(keys)
    assert bool(np.asarray(res.found).all())
