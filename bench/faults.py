"""Faults planted under the harness, each of which a cell's check has to
catch: the store with one fault in its answers or its writes.

``bench/tests/test_control.py`` runs each at the tiny CPU size, and
``control.py --store <fault>`` at a cell's own size on the chip.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import harness

FAULTS = (
    "update_unchanged",          # acknowledged, the table returned unchanged
    "update_unchanged_refused",  # the table unchanged, every op answered not ok
    "update_half",               # half the batch left out, all acknowledged
    "update_half_refused",       # half the batch left out, answered not ok
    "update_altered",            # one payload altered where it is written
    "update_clobbers",           # each call also overwrites 16 other records
    "lookup_altered",            # one answer altered where it is produced
    "lookup_half",               # half the batch answered not found
)


class Faulty(harness.ApiStore):
    """The program's store with the fault ``fault`` planted."""

    def __init__(self, slots: int, fault: str):
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        super().__init__(slots)
        self.fault = fault
        self.loaded = None
        self.rng = np.random.RandomState(12345)

    def load(self, table, keys, vals, batch):
        self.loaded = np.asarray(keys)
        return super().load(table, keys, vals, batch)

    def lookup(self, table, keys):
        found, vals = super().lookup(table, keys)
        if self.fault == "lookup_altered":
            vals = vals.at[0, 0].set(vals[0, 0] ^ 1)
        elif self.fault == "lookup_half":
            found = found.at[keys.shape[0] // 2:].set(False)
        return found, vals

    def update(self, table, keys, vals):
        b = keys.shape[0]
        first = jnp.arange(b) < b // 2
        if self.fault == "update_unchanged":
            _, ok = super().update(table, keys, vals)
            return table, ok
        if self.fault == "update_unchanged_refused":
            return table, jnp.zeros(b, bool)
        if self.fault == "update_half":
            table, res = self.store.update(table, keys, vals, first)
            return table, jnp.where(first, res.ok, True)
        if self.fault == "update_half_refused":
            table, res = self.store.update(table, keys, vals, first)
            return table, res.ok & first
        if self.fault == "update_altered":
            vals = vals.at[0, 0].set(vals[0, 0] ^ 1)
        if self.fault == "update_clobbers":
            victims = self.loaded[self.rng.randint(0, len(self.loaded), b)]
            table, _ = self.store.update(table, jnp.asarray(victims),
                                         jnp.zeros_like(vals),
                                         jnp.arange(b) < 16)
        return super().update(table, keys, vals)
