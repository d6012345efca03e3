"""The comparison that decides ``correct`` can fail.

At the tiny CPU size: the control (``reference.LaggingStore``, a plain
store in the program's place that acknowledges each write before it
applies it) comes out not correct in every cell, and so does a run with
each fault a cell can have planted under the harness (``faults.py``):
an update that returns the table unchanged, half of a batch left out,
each acknowledged or answered not ok; a payload or an answer altered
where it is produced; a write that overwrites other records.  The
harness's look for a chip is skipped; the rest of a run is driven as on
the chip.

``control.py`` runs the control and the faults on the chip at the cells'
own size.
"""

import time

import pytest

import faults
import harness
import reference

SEEDS = (3, 2 ** 31 + 77, 4_000_000_007)


def run_with(tiny, cell, make_store, seed):
    return harness.run(cell, seed, 1.0, False, t_start=time.perf_counter(),
                       root=tiny, require_chip=False, make_store=make_store)


UPDATE_FAULTS = ["update_unchanged", "update_unchanged_refused",
                 "update_half", "update_half_refused", "update_altered",
                 "update_clobbers"]
FAULTS = {
    "ycsb-zipf.A": UPDATE_FAULTS + ["lookup_altered", "lookup_half"],
    "ycsb-zipf.C": ["lookup_altered", "lookup_half"],
    "micro-uniform.update": UPDATE_FAULTS,
}


@pytest.mark.parametrize("cell", sorted(FAULTS))
@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(tiny, cell, seed):
    r = run_with(tiny, cell, lambda slots: reference.LaggingStore(), seed)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(FAULTS)
                                        for f in FAULTS[c]])
def test_fault_is_not_correct(tiny, cell, fault):
    r = run_with(tiny, cell, lambda slots: faults.Faulty(slots, fault),
                 SEEDS[1])
    assert r["correct"] is False, (fault, r["checks"])


@pytest.mark.parametrize("fault,check", [
    ("update_unchanged_refused", "update_refused_pct"),
    ("update_half_refused", "update_refused_pct"),
    ("update_clobbers", "untouched_wrong")])
def test_fault_is_caught_by_its_check(tiny, fault, check):
    """The faults that answer truthfully (a refusal, or a write that
    reports nothing) are caught by the check made for them."""
    r = run_with(tiny, "ycsb-zipf.A",
                 lambda slots: faults.Faulty(slots, fault), SEEDS[0])
    c = r["checks"][check]
    assert c["value"] > c["limit"], r["checks"]


def test_every_planted_fault_is_tested():
    assert sorted({f for fs in FAULTS.values() for f in fs}) \
        == sorted(faults.FAULTS)


@pytest.mark.parametrize("cell", sorted(FAULTS))
def test_sound_program_is_correct(tiny, cell):
    r = run_with(tiny, cell, None, SEEDS[2])
    assert r["correct"] is True, r["checks"]
