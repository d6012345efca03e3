"""The one traffic generator: reads a mix from ``traffic/<name>.json``
and draws each call's keys and payloads.

A mix file holds ``batch`` (ops per call) and ``calls``, the calls of one
round in order, each ``{"op": "lookup" | "update", "keys": "present" |
"absent"}``.  Present keys follow the configuration's
``request_distribution`` over the loaded records: ``zipfian`` (YCSB's
scrambled zipfian, ``zipf_theta``) or ``uniform``.  Absent keys are ids
past the loaded range, as YCSB's negative search draws them.

Every run sends the same traffic: the ranks each call draws and the map
from ranks to records (which record is hot) both come from
``TRAFFIC_SEED``, not from the run's seed, which draws the loaded values
and the update payloads.  Both decide the work: a zipf update batch's
repeats set its residual-wave trips, and where the hottest records sit
(a full segment refuses their updates; on a TPU v5 lite one layout in
six raised the lookup p95 from 5.3 to 7.6 ms), so a seed of their own
would change the work and not only the data.
"""

from __future__ import annotations

import numpy as np

import ycsb

OPS = ("lookup", "update")
KEYS = ("present", "absent")
TRAFFIC_SEED = 20_210_714


class Traffic:
    def __init__(self, mix: dict, config: dict, records: int,
                 traffic_seed: int = TRAFFIC_SEED):
        self.batch = int(mix["batch"])
        self.calls = [dict(c) for c in mix["calls"]]
        for c in self.calls:
            if c["op"] not in OPS or c.get("keys", "present") not in KEYS:
                raise ValueError(f"unknown call in traffic mix: {c}")
        self.records = records
        dist = config["request_distribution"]
        if dist == "zipfian":
            self.zipf = ycsb.Zipf(records, float(config["zipf_theta"]))
        elif dist != "uniform":
            raise ValueError(f"unknown request_distribution {dist!r}")
        self.dist = dist
        layout, ranks = (np.random.RandomState(c.generate_state(4)) for c
                         in np.random.SeedSequence(traffic_seed).spawn(2))
        # ranks -> record ids, as YCSB's ScrambledZipfian spreads hot
        # records over the table
        self.scramble = layout.permutation(records)
        self.ranks = ranks      # the window's rank stream

    @property
    def ops(self) -> list:
        """The ops this mix issues, in first-use order."""
        return list(dict.fromkeys(c["op"] for c in self.calls))

    def ids(self, call: dict, ranks: np.random.RandomState) -> np.ndarray:
        """Record ids of one call, drawn from the rank stream ``ranks``."""
        if call.get("keys", "present") == "absent":
            return (self.records + 10_000_000
                    + ranks.randint(0, 2 ** 30, size=self.batch)).astype(np.int64)
        if self.dist == "zipfian":
            return self.scramble[self.zipf.sample(ranks, self.batch)]
        return self.scramble[ranks.randint(0, self.records, size=self.batch)]

    def values(self, rng: np.random.RandomState) -> np.ndarray:
        return ycsb.make_value(rng, self.batch)
