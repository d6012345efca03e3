"""Shared fixture of the benchmark's tests: a copy of the benchmark cut to
a CPU-sized table (2^14 slots, 256 ops a call), with the CPU added to its
peaks table so that the traced reductions have a bandwidth to divide by,
and with the cells of ``deferred_cells.json`` added to its
``BENCHMARK.json``.  The copy's ``src`` points at this checkout's
program."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

TINY_SLOTS = 2 ** 14
TINY_BATCH = 256


def _edit(path, **changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def make_tiny(root) -> str:
    root = str(root)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(BENCH, "tests", "deferred_cells.json")) as f:
        deferred = json.load(f)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        spec[group] += deferred[group]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    for name in os.listdir(os.path.join(root, "bench", "configs")):
        _edit(os.path.join(root, "bench", "configs", name),
              table_slots=TINY_SLOTS, records=int(0.70 * TINY_SLOTS),
              load_batch=1024)
    for name in os.listdir(os.path.join(root, "bench", "traffic")):
        _edit(os.path.join(root, "bench", "traffic", name), batch=TINY_BATCH)
    peaks = os.path.join(root, "bench", "peaks.json")
    with open(peaks) as f:
        kinds = json.load(f)
    _edit(peaks, cpu=dict(kinds["TPU v5 lite"], source="test only"))
    return root


@pytest.fixture(scope="session")
def tiny(tmp_path_factory):
    return make_tiny(tmp_path_factory.mktemp("bench"))
