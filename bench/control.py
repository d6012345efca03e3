#!/usr/bin/env python3
"""Run a cell at its own size with another store in the program's place,
on several seeds in one process.

    python3 bench/control.py --workload ycsb-zipf.C --seconds 10 --seeds 1 2 3
    python3 bench/control.py --workload ycsb-zipf.C --seconds 10 --seeds 1 \\
        --store lookup_half
    python3 bench/control.py --workload ycsb-zipf.C --seconds 51 --seeds 1 \\
        --store program --traffic-seed 7

``--store lagging`` (the default) is the control, ``reference.LaggingStore``:
a plain key-value store that acknowledges each write call at once but
applies it only when the next write call arrives.  ``--store <fault>``
plants one of ``faults.FAULTS`` in the program.  Every such run has to come
out not correct.  ``--store program`` runs the program itself, with the
traffic of ``--traffic-seed`` (ranks, and which records are hot) in place
of the benchmark's fixed traffic, and has to come out correct.  Each run
prints its compared numbers beside their limits, and its metrics, as one
JSON line.  The benchmark's own runs never run this.  Like them it asks
for the chip the cell names.
"""

import argparse
import json
import sys
import time

import generator


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--store", default="lagging")
    ap.add_argument("--traffic-seed", type=int,
                    default=generator.TRAFFIC_SEED)
    args = ap.parse_args(argv)
    import faults
    import harness
    import reference
    if args.store == "lagging":
        make_store = lambda slots: reference.LaggingStore()
    elif args.store == "program":
        make_store = None
    elif args.store in faults.FAULTS:
        make_store = lambda slots: faults.Faulty(slots, args.store)
    else:
        ap.error(f"unknown --store {args.store!r}")
    want = args.store == "program"
    as_expected = True
    for seed in args.seeds:
        r = harness.run(args.workload, seed, args.seconds, False,
                        t_start=time.perf_counter(), make_store=make_store,
                        traffic_seed=args.traffic_seed)
        as_expected &= r["correct"] is want
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "store": args.store,
                          "traffic_seed": args.traffic_seed,
                          "correct": r["correct"], "checks": r["checks"],
                          "metrics": {k: m["value"]
                                      for k, m in r["metrics"].items()}}),
              flush=True)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
