"""`repro.api` — the scheme-agnostic hash-store interface.

One typed surface over every index this repo implements (the paper's
continuity hashing, its two baselines, and the dense block-table
reference), so that the serving page table, the YCSB harness, the
benchmarks and the tests all program against ONE protocol and the
comparative claims (1 RDMA read per lookup, Table I PM-write counts) fall
out of one shared `CostLedger` instead of per-module counters.

    from repro import api

    store = api.make_store("continuity", table_slots=4096)
    table = store.create()
    table, res = store.insert(table, keys, vals)
    hits = store.lookup(table, keys)
    print(res.ledger.pm_per_op(), hits.ledger.reads_per_op())

Execution strategy is picked at this boundary via `ExecPolicy` (wave
engine vs serial scan oracle; jnp gather vs Pallas probe kernel), and new
schemes plug in through `register_scheme` — see DESIGN.md §6.

Every store also exposes the crash-consistency surface (DESIGN.md §7):
``store.trace_insert/trace_update/trace_delete`` emit the op's ordered PM
store trace for `repro.consistency`'s crash injector, and
``store.recover`` runs the scheme's restart procedure.
"""

from repro.api.load import bulk_load
from repro.api.registry import (available_schemes, get_scheme, make_store,
                                register_scheme)
from repro.api.stores import (ContinuityStore, DenseStore, LevelStore,
                              PFarmStore, _register_builtin)
from repro.api.types import (CostLedger, ExecPolicy, HashStore, OpResult,
                             store_shard_axes)

_register_builtin(register_scheme)

__all__ = [
    "bulk_load",
    "available_schemes", "get_scheme", "make_store", "register_scheme",
    "ContinuityStore", "DenseStore", "LevelStore", "PFarmStore",
    "CostLedger", "ExecPolicy", "HashStore", "OpResult", "store_shard_axes",
    "ClusterStore",
]


def __getattr__(name):
    # `ClusterStore` (the sharded/replicated multi-node front end over any
    # registered scheme — DESIGN.md §9) lives in `repro.cluster`, which
    # itself programs against this package; the deferred import keeps the
    # layering acyclic while `api.ClusterStore` stays the documented entry.
    if name == "ClusterStore":
        from repro.cluster.store import ClusterStore
        return ClusterStore
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
