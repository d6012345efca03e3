"""The typed hash-store surface: ``HashStore`` protocol, ``ExecPolicy``,
``OpResult`` and the unified ``CostLedger``.

Every scheme (continuity, level, pfarm, dense, and anything registered
later) is exposed as a *store*: a frozen, hashable dataclass bundling the
static table geometry with an execution policy.  Table STATE stays a pure
pytree (a flat NamedTuple of arrays) that threads through jit/vmap/scan;
the store itself is static — safe to close over in jitted callables, use
as a jit static argument, or embed in other frozen configs (the serving
``PageGeometry`` does exactly that).

Calling convention (uniform across schemes):

    table            = store.create()
    table, res       = store.insert(table, keys, vals[, mask])
    table, res       = store.update(table, keys, vals[, mask])
    table, res       = store.delete(table, keys[, mask])
    res              = store.lookup(table, keys)
    rs               = store.begin_resize(table, factor)
    rs               = store.resize_step(rs, budget)   # incremental
    store2, table2   = store.resize_cutover(rs)
    lf               = store.load_factor(table)
    info             = store.stats(table)          # host-side dict

(``store.resize(table, factor)`` survives as a deprecated one-shot shim
over the begin/step/cutover triple.)  ``ResizeState`` is the maintenance
handle the incremental API threads: continuity advances a real cohort-at-
a-time split (serving reads and writes throughout, routed by its per-pair
cutover tokens); the baselines complete the whole rehash in their first
``resize_step`` — the protocol is uniform, the increment is the paper
scheme's advantage.

    table, tres      = store.trace_insert(table, keys, vals)   # + PM trace
    table2, report   = store.recover(crashed_state)            # restart

``res`` is an `OpResult`; ``res.ledger`` is the `CostLedger` every scheme
reports in the same units, which is what makes the paper's Table I an
apples-to-apples subtraction: ``res.ledger.pm_per_op()``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.pmem import CostLedger

ENGINES = ("wave", "serial")
PROBES = ("auto", "gather", "pallas", "reference")
MUTATES = ("gather", "pallas", "reference")
TRANSPORTS = ("none", "sim")


@dataclasses.dataclass(frozen=True)
class ExecPolicy:
    """Execution strategy, selected at the API boundary (not per-call kwargs).

    * ``engine`` — server-side mutation strategy: ``"wave"`` (the batch-
      vectorized wave engine where the scheme has one; continuity does) or
      ``"serial"`` (the ``lax.scan`` reference order).  Schemes with a
      single strategy (level, pfarm, dense) accept either value and run
      their one batched path — results are engine-independent by
      construction.
    * ``probe`` — client-side read strategy for schemes with a kernel.
      The default, ``"auto"``, resolves by the platform the lookup is
      lowered for (`repro.kernels.platform`): the Pallas lookup kernel
      (``kernels/lookup.py``) on a TPU, the pure-jnp vector gather on the
      CPU.  The explicit values pin one path, for tests and identity
      checks: ``"gather"`` (the jnp gather), ``"pallas"`` (the lookup
      kernel; interpreted on the CPU), ``"reference"`` (the kernel's jnp
      oracle).
    * ``mutate`` — match backend of the fused wave-engine update/delete
      (continuity only): same three values, selecting the mutation-plan
      kernel (``kernels/mutate.py``) / its jnp oracle / the vector
      gather.  Ignored by the serial engine and kernel-less schemes.
    * ``use_fp`` — fingerprint pre-filter of the ``"reference"`` probe
      (default ON: result-identical — visible slots always carry the
      correct 2-bit field — and cuts negative-search key compares, paper
      Figs 7/14).  The lookup kernel compares the whole key row it
      fetches and does not filter; the mutation plan always filters
      regardless of this knob.
    * ``qblock`` — queries per grid step of the mutation-plan kernel.
      The lookup kernel derives its block from the batch.
      Whether a kernel runs compiled or interpreted is not a policy: it
      follows the platform (`repro.kernels.platform`).
    * ``transport`` — which transport host-side drivers attach to the verb
      plans ops emit: ``"none"`` (plans price the `CostLedger` only) or
      ``"sim"`` (a `repro.rdma.RemoteMemory` endpoint with doorbell
      batching and the analytical latency model;
      ``RemoteMemory.from_policy(policy)`` builds it).  Lookups ALWAYS
      carry their plan on `OpResult.plan`; the policy decides whether
      anything executes/prices it.
    """

    engine: str = "wave"
    probe: str = "auto"
    mutate: str = "gather"
    use_fp: bool = True
    qblock: int = 8
    transport: str = "none"

    def __post_init__(self):
        assert self.engine in ENGINES, self.engine
        assert self.probe in PROBES, self.probe
        assert self.mutate in MUTATES, self.mutate
        assert self.qblock >= 1
        assert self.transport in TRANSPORTS, self.transport


class OpResult(NamedTuple):
    """Uniform per-batch op result.

    ``ok``     (B,) bool — per-item success (write) / found (lookup).
    ``ledger`` accumulated `CostLedger` for the batch.
    ``values`` (B, VAL_LANES) uint32 — lookup payloads (None on writes).
    ``reads``  (B,) int32 — contiguous fetches per lookup (None on writes).
    ``plan``   `repro.rdma.VerbPlan` — the one-sided verb plan the lookup
               emitted (None on writes); ``ledger``'s read counters are
               derived from it, and host-side drivers post it to the
               transport `ExecPolicy.transport` selects.
    """

    ok: jnp.ndarray
    ledger: CostLedger
    values: Optional[jnp.ndarray] = None
    reads: Optional[jnp.ndarray] = None
    plan: Optional[Any] = None


@dataclasses.dataclass(frozen=True)
class ResizeState:
    """Handle of one in-flight incremental resize (begin -> step* -> cutover).

    ``store``/``table`` are the SOURCE geometry and its (draining) state;
    ``new_store``/``new_table`` the grown target.  ``opaque`` is the
    scheme's private cursor (continuity: its per-pair cutover-token split
    state); ``done`` flips when every cohort has moved; ``moved`` counts
    relocated items and ``n_items`` records the live count at begin (the
    cutover loss check).  ``step_budget`` is the per-step cohort count the
    SLO controller chose at begin (``begin_resize(step_slo_us=...)`` sizes
    it from the `LinkModel` so one step's foreground stall stays under the
    target; None means the caller passes an explicit budget).  The handle
    is immutable — each step returns a new one — so a crash between steps
    simply resumes from the last handle (or from recovery's token scan)."""

    store: "HashStore"
    new_store: "HashStore"
    table: Any
    new_table: Any
    factor: int = 2
    opaque: Any = None
    done: bool = False
    n_items: int = 0
    moved: int = 0
    step_budget: Optional[int] = None


@runtime_checkable
class HashStore(Protocol):
    """Structural type every registered scheme satisfies (see module doc
    for the calling convention).  ``name`` is the registry key; ``policy``
    the store's `ExecPolicy`."""

    name: str
    policy: ExecPolicy

    def create(self) -> Any: ...

    def insert(self, table: Any, keys, vals, mask=None) -> Tuple[Any, OpResult]: ...

    def update(self, table: Any, keys, vals, mask=None) -> Tuple[Any, OpResult]: ...

    def delete(self, table: Any, keys, mask=None) -> Tuple[Any, OpResult]: ...

    def lookup(self, table: Any, keys) -> OpResult: ...

    # incremental maintenance surface: begin one resize, advance it a
    # bounded number of cohorts at a time (foreground traffic keeps
    # flowing between steps), then cut over.  ``resize`` is the deprecated
    # one-shot shim over the triple.
    def begin_resize(self, table: Any, factor: int = 2,
                     step_slo_us: Optional[float] = None) -> ResizeState: ...

    def resize_step(self, state: ResizeState,
                    budget: Optional[int] = None) -> ResizeState: ...

    def resize_cutover(self, state: ResizeState) -> Tuple["HashStore", Any]: ...

    def resize(self, table: Any, factor: int = 2) -> Tuple["HashStore", Any]: ...

    def load_factor(self, table: Any) -> jnp.ndarray: ...

    def stats(self, table: Any) -> dict: ...

    # crash-consistency surface (`repro.consistency`): traced twins of the
    # write ops — same (table, result) contract, but the result carries the
    # ordered PM store trace the crash injector replays — and the scheme's
    # restart procedure (returns (table, RecoveryReport)).
    def trace_insert(self, table: Any, keys, vals, mask=None) -> Tuple[Any, Any]: ...

    def trace_update(self, table: Any, keys, vals, mask=None) -> Tuple[Any, Any]: ...

    def trace_delete(self, table: Any, keys, mask=None) -> Tuple[Any, Any]: ...

    def recover(self, table_or_state: Any) -> Tuple[Any, Any]: ...


def store_shard_axes(table: Any, axis: str):
    """Logical-axis tree for a store state carrying one leading shard dim.

    Every leaf of ``table`` (already broadcast to ``(shards,) + ...``) maps
    to ``(axis, None, ..., None)`` — the generic form of the hand-written
    per-scheme axis trees the serving cache used to maintain."""
    leaves, treedef = jax.tree.flatten(table)
    return jax.tree.unflatten(
        treedef, [(axis,) + (None,) * (leaf.ndim - 1) for leaf in leaves])
