"""Device time per lookup call of the ops under the named scope
``lookup.plan``: the modelled verb plan and its cost ledger
(``lookup_plan`` and ``ledger_from_plan`` in
``repro.api.stores._jit_lookup``).  Counted as ``lookup_probe_ms``
counts."""

from metrics.lookup_probe_ms import scope_read


def read(run):
    return scope_read(run, "lookup.plan")
