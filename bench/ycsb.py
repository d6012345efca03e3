"""YCSB key, value and request generators of the benchmark.

Copied from ``src/repro/data/ycsb.py`` (``Zipf``, ``make_key``,
``make_value``, ``negative_keys``) so that the yardstick stays fixed
when the program's copy changes; ``tests/test_bench_harness.py`` beside
this file checks that both give the same streams for one seed.
"""

from __future__ import annotations

import numpy as np


def make_key(ids: np.ndarray) -> np.ndarray:
    """64-bit record ids -> (N, 4) uint32 16-byte keys (YCSB 'user###' style:
    deterministic, well-spread)."""
    ids = ids.astype(np.uint64)
    lo = (ids & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (ids >> np.uint64(32)).astype(np.uint32)
    salt = (lo * np.uint32(2654435761)) ^ np.uint32(0xDEADBEEF)
    return np.stack([lo, hi, salt, np.uint32(0x59435342)
                     * np.ones_like(lo)], -1)


def make_value(rng: np.random.RandomState, n: int) -> np.ndarray:
    return rng.randint(0, 2 ** 31, size=(n, 4)).astype(np.uint32)


class Zipf:
    """Gray et al. zipfian generator over [0, n) with theta=0.99 (YCSB)."""

    def __init__(self, n: int, theta: float = 0.99):
        self.n = n
        self.theta = theta
        zetan = np.sum(1.0 / np.arange(1, n + 1) ** theta)
        self.zetan = zetan
        self.alpha = 1.0 / (1.0 - theta)
        zeta2 = np.sum(1.0 / np.arange(1, 3) ** theta)
        self.eta = (1 - (2.0 / n) ** (1 - theta)) / (1 - zeta2 / zetan)

    def sample(self, rng: np.random.RandomState, size: int) -> np.ndarray:
        u = rng.random_sample(size)
        uz = u * self.zetan
        out = np.where(uz < 1.0, 0,
                       np.where(uz < 1.0 + 0.5 ** self.theta, 1,
                                (self.n * (self.eta * u - self.eta + 1)
                                 ** self.alpha).astype(np.int64)))
        return np.clip(out, 0, self.n - 1)


def negative_keys(rng: np.random.RandomState, num_records: int,
                  n: int) -> np.ndarray:
    """Keys guaranteed absent (ids beyond the loaded range)."""
    ids = num_records + 10_000_000 + rng.randint(0, 2 ** 30, size=n)
    return make_key(ids.astype(np.int64))
