"""Benchmark harness: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Sections:
  * hash          — everything below (Table I + Figs 4–18 + access-amp)
  * pm_writes     — Table I (PM writes per op, via repro.api CostLedger)
  * access_amp    — contiguous fetches + bytes per lookup
  * search        — positive/negative search micro (Figs 6/7 + 13/14)
  * update_micro  — 100% updates (Figs 10/17)
  * ycsb          — YCSB-A/B/C/D/F throughput + latency (Figs 4–10/11–17,
                    CPU wall clock of the jitted ops)
  * end_to_end    — per-scheme YCSB-A/B/C throughput + p50/p99 latency over
                    the RDMA transport simulation (repro.rdma: verb plans,
                    doorbell batching, analytical LinkModel) — the paper's
                    headline 1.45–2.43x ordering; --e2e-scale smoke shrinks
                    it for CI
  * load_factor   — load factor at each resize (Fig 18; emitted to the
                    BENCH json and banded against the paper's claim — the
                    fingerprint/stash tier lifts the first trigger past
                    ~0.85 — by validate_bench.py)
  * resize        — online-resize stalls: steps per cutover and worst
                    per-step pause of the incremental cohort split vs the
                    stop-the-world rehash (emitted to the BENCH json;
                    validate_bench.py gates the non-blocking claim)
  * cluster       — N-node replicated cluster YCSB with a mid-run join
                    (live migration) and primary kill (failover), plus
                    the replicated-durability and migration crash drills
                    (repro.cluster; --e2e-scale smoke shrinks it for CI)
  * cache         — 100-client fan-in: version-stamped client caches vs
                    the uncached request-per-post edge under membership
                    chaos (repro.cache; doorbell/p99 collapse + the
                    zero-stale gate; --e2e-scale smoke shrinks it)
  * obs           — telemetry-sketch headline numbers (repro.obs): the
                    e2e scheme trio's p50/p99 read back OUT of the
                    e2e.op_us registry histograms, plus the
                    maintenance-SLO drill (validate_bench gates the
                    YCSB-A ordering chain and zero SLO burns)
  * crash_consistency — recovery work per scheme from the crash/scheme
                    matrix (repro.consistency; EXPERIMENTS.md §Crash)
  * bench_serving — technique-on-the-hot-path serving numbers

The serial-vs-wave write-batch sweep always runs and is written to
``--bench-json`` (default BENCH_hash.json; ops/s + PM-write counters at
``--sweep-batches``) so successive PRs accumulate a perf trajectory — see
EXPERIMENTS.md §Perf.  ``benchmarks/validate_bench.py`` checks the emitted
artifact against its schema (CI runs it on the smoke sweep).  ``--merge``
updates the existing artifact in place with just this run's sections; an
EMPTY ``--sweep-batches`` under ``--merge`` skips the sweep and keeps the
artifact's committed one.
"""

from __future__ import annotations

import argparse
import json

HASH_SECTIONS = ("pm_writes", "access_amp", "search", "update_micro",
                 "ycsb", "end_to_end", "load_factor", "resize")
SECTIONS = HASH_SECTIONS + ("cluster", "cache", "obs", "crash_consistency",
                            "hash", "serving")


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--sections", default="hash,serving",
                   help="comma-separated subset of "
                        f"{', '.join(SECTIONS)} "
                        "(the write-batch sweep always runs)")
    p.add_argument("--bench-json", default="BENCH_hash.json",
                   help="where to write the write-batch sweep artifact")
    p.add_argument("--sweep-batches", default="64,512,4096",
                   help="batch sizes for the serial-vs-wave sweep "
                        "(smoke CI uses a small subset)")
    p.add_argument("--e2e-scale", default="full", choices=("full", "smoke"),
                   help="workload sizes for the end_to_end section")
    p.add_argument("--merge", action="store_true",
                   help="load the existing --bench-json and update it "
                        "with this run's sections (instead of rewriting "
                        "the whole artifact) — lets a single section "
                        "refresh without regenerating the sweep")
    args = p.parse_args(argv)
    sections = {s for s in args.sections.split(",") if s}
    unknown = sections - set(SECTIONS)
    if unknown:
        p.error(f"unknown sections {sorted(unknown)}; valid: "
                f"{', '.join(SECTIONS)} (or empty for sweep only)")
    if "hash" in sections:
        sections |= set(HASH_SECTIONS)
    batches = tuple(int(b) for b in args.sweep_batches.split(",") if b)
    if not batches and not args.merge:
        p.error("an empty --sweep-batches (skip the sweep) requires "
                "--merge: the artifact must keep its existing sweep")

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    table1 = crash = e2e = lf = rz = cluster = cache = obs_sec = None
    from benchmarks import (bench_cache, bench_cluster, bench_crash,
                            bench_hash, bench_obs, bench_serving)
    if "pm_writes" in sections:
        table1 = bench_hash.bench_pm_writes(rows)
    if "crash_consistency" in sections:
        crash = bench_crash.run(rows)
    if "end_to_end" in sections:
        e2e = bench_hash.bench_end_to_end(rows, scale=args.e2e_scale)
    if "cluster" in sections:
        cluster = bench_cluster.run(rows, scale=args.e2e_scale)
    if "cache" in sections:
        cache = bench_cache.run(rows, scale=args.e2e_scale)
    if "obs" in sections:
        obs_sec = bench_obs.run(rows, scale=args.e2e_scale)
    if "access_amp" in sections:
        bench_hash.bench_access_amp(rows)
    if "search" in sections:
        bench_hash.bench_search_micro(rows)
    if "update_micro" in sections:
        bench_hash.bench_update_micro(rows)
    if "ycsb" in sections:
        bench_hash.bench_ycsb(rows)
    if "load_factor" in sections:
        lf = bench_hash.bench_load_factor(rows)
    if "resize" in sections:
        rz = bench_hash.bench_resize(rows)
    if "serving" in sections:
        bench_serving.run(rows)
    payload = (bench_hash.bench_write_batch_sweep(rows, batches=batches)
               if batches else {})
    if args.merge:
        with open(args.bench_json) as f:
            base = json.load(f)
        base.update(payload)
        payload = base
    if table1 is not None:
        payload["table1"] = table1
    if crash is not None:
        payload["crash_consistency"] = crash
    if e2e is not None:
        payload["end_to_end"] = e2e
    if lf is not None:
        payload["load_factor"] = lf
    if rz is not None:
        payload["resize"] = rz
    if cluster is not None:
        payload["cluster"] = cluster
    if cache is not None:
        payload["cache"] = cache
    if obs_sec is not None:
        payload["obs"] = obs_sec
    with open(args.bench_json, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
