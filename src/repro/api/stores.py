"""Registered `HashStore` adapters for the four built-in schemes.

Each adapter is a frozen dataclass (hashable — safe as jit static / inside
other frozen configs) binding a scheme module's pure functions to the
protocol's calling convention, the unified `OpResult`/`CostLedger`, and an
`ExecPolicy`.  Registration happens at import of ``repro.api``:

  * ``continuity`` — the paper's scheme; `ExecPolicy.engine` selects the
    wave-vectorized mutation engine vs the serial ``lax.scan`` oracle, and
    `ExecPolicy.probe` selects the lookup's read path: by platform (the
    default: the Pallas lookup kernel on a TPU, the pure-jnp gather on
    the CPU), or pinned to one of the gather, the kernel, its jnp oracle;
  * ``level``  — Level hashing (OSDI'18), the paper's PM-friendly baseline;
  * ``pfarm``  — P-FaRM-KV (FaRM-KV x RECIPE), the paper's RDMA baseline;
  * ``dense``  — the dense block-table reference (vLLM-style), the
    correctness oracle and the non-hashed serving page-table backend.

Factories size the table to ``table_slots`` storage units so cross-scheme
numbers compare at equal capacity (the paper's evaluation setup).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.api.types import ExecPolicy, OpResult, ResizeState
from repro.core import continuity as ch
from repro.core import dense as dn
from repro.core import level as lv
from repro.core import pfarm as pf
from repro.core.continuity import KEY_LANES, VAL_LANES


# The read-side entry points compile ONCE per (store, shape): stores are
# frozen dataclasses (hashable), so they ride as jit statics and the many
# small per-node / per-client calls the cluster and cache layers make pay
# dispatch, not retracing, after the first call at each batch shape.
@functools.partial(jax.jit, static_argnums=0)
def _jit_lookup(store: "_ModuleStore", table, keys):
    from repro.rdma import verbs as rv
    res = store._lookup_res(table, keys)
    with jax.named_scope("lookup.plan"):
        plan = store._mod.lookup_plan(store.cfg, table, keys, res)
        ledger = rv.ledger_from_plan(plan)
    return res, plan, ledger


@functools.partial(jax.jit, static_argnums=0)
def _jit_stamp(store: "_ModuleStore", table, keys):
    return store._stamp_impl(table, keys)


@functools.partial(jax.jit, static_argnums=0)
def _jit_stamp_plan(store: "_ModuleStore", table, keys):
    return store._vplan_impl(table, keys)


def _check_resize_lossless(name: str, old_table, new_table) -> None:
    lost = int(old_table.count) - int(new_table.count)
    if lost:
        raise RuntimeError(
            f"resize dropped {lost} live item(s) from the {name!r} store "
            f"({int(old_table.count)} -> {int(new_table.count)}); grow by a "
            f"larger factor or rehash manually")


@dataclasses.dataclass(frozen=True)
class _ModuleStore:
    """Shared plumbing: scheme-module functions -> protocol methods."""

    cfg: Any
    policy: ExecPolicy = ExecPolicy()

    name: ClassVar[str] = "?"

    # -- per-scheme hooks ---------------------------------------------------
    @property
    def _mod(self):
        raise NotImplementedError

    def _insert_fn(self):
        return self._mod.insert

    def _update_fn(self):
        return self._mod.update

    def _delete_fn(self):
        return self._mod.delete

    def _lookup_res(self, table, keys):
        return self._mod.lookup(self.cfg, table, keys)

    def _extract(self, table):
        """(keys, vals, live_mask) of every storage slot — generic resize."""
        raise NotImplementedError

    def total_slots(self, table=None) -> float:
        raise NotImplementedError

    # -- protocol -----------------------------------------------------------
    def with_policy(self, policy: ExecPolicy) -> "_ModuleStore":
        return dataclasses.replace(self, policy=policy)

    def create(self):
        return self._mod.create(self.cfg)

    def insert(self, table, keys, vals, mask=None) -> Tuple[Any, OpResult]:
        table, ok, ctr = self._insert_fn()(self.cfg, table, keys, vals, mask)
        return table, OpResult(ok=ok, ledger=ctr)

    def update(self, table, keys, vals, mask=None) -> Tuple[Any, OpResult]:
        table, ok, ctr = self._update_fn()(self.cfg, table, keys, vals, mask)
        return table, OpResult(ok=ok, ledger=ctr)

    def delete(self, table, keys, mask=None) -> Tuple[Any, OpResult]:
        table, ok, ctr = self._delete_fn()(self.cfg, table, keys, mask)
        return table, OpResult(ok=ok, ledger=ctr)

    def lookup(self, table, keys) -> OpResult:
        # ONE accounting path for every scheme: the lookup emits its verb
        # plan (continuity: one contiguous segment READ; level: scattered
        # bucket READs; pfarm: window + chained READs; dense: whole-table
        # READ) and the ledger is derived from the plan — this replaced
        # the four per-scheme hand-tallied ``read_counters`` blocks.
        #
        # ``api.lookup`` spans the call; ``api.lookup.launch`` the jitted
        # call alone: static-argument hashing, argument flattening, the
        # executable's launch and its output-buffer allocation.
        with obs.device_span("api.lookup"):
            with obs.device_span("api.lookup.launch"):
                res, plan, ledger = _jit_lookup(self, table, keys)
            return OpResult(ok=res.found, ledger=ledger,
                            values=res.values, reads=res.reads, plan=plan)

    def scan_plan(self, table, keys, spans):
        """Verb plan of a YCSB-E short-scan batch: ``spans[i]`` records
        read starting from ``keys[i]``'s position.  Continuity emits ONE
        contiguous multi-segment READ per scan (its SBuckets are linear
        in PM); the scattered baselines degenerate to one READ per
        record — the asymmetry YCSB-E measures."""
        return self._mod.scan_plan(self.cfg, table, keys, spans)

    # -- incremental maintenance surface ------------------------------------
    # begin_resize/resize_step/resize_cutover: the protocol's resize is a
    # steppable background job.  The generic implementation completes the
    # whole rehash in the FIRST step (a stop-the-world move is all the
    # scattered baselines can offer — their candidate buckets change
    # wholesale at the new size); continuity overrides the triple with a
    # real cohort-at-a-time split.

    def begin_resize(self, table, factor: int = 2,
                     step_slo_us: Optional[float] = None) -> ResizeState:
        # the baselines can't increment (their first step moves everything),
        # so a stall SLO is unsatisfiable — accepted for protocol uniformity
        new = dataclasses.replace(self, cfg=self.cfg.grow(factor))
        return ResizeState(store=self, new_store=new, table=table,
                           new_table=new.create(), factor=factor,
                           n_items=int(table.count))

    def resize_step(self, state: ResizeState,
                    budget: Optional[int] = None) -> ResizeState:
        if state.done:
            return state
        keys, vals, live = self._extract(state.table)
        new_table, _ = state.new_store.insert(state.new_table, keys, vals,
                                              live)
        return dataclasses.replace(
            state, new_table=new_table, done=True,
            moved=int(jnp.asarray(live).sum()))

    def resize_cutover(self, state: ResizeState) -> Tuple["_ModuleStore", Any]:
        """Finish any remaining steps and hand over the grown store.

        Raises if any live item failed to reinsert (possible for the
        bucketed baselines when candidate buckets collide even at the
        larger size) instead of dropping it."""
        while not state.done:
            state = self.resize_step(state, budget=1 << 30)
        _check_resize_lossless(self.name, state.table, state.new_table)
        return state.new_store, state.new_table

    def resize(self, table, factor: int = 2) -> Tuple["_ModuleStore", Any]:
        """DEPRECATED one-shot resize: begin + step-to-completion + cutover.

        Kept as a shim for callers that can afford to block; new code
        should drive ``begin_resize``/``resize_step`` from its maintenance
        loop and ``resize_cutover`` when the split has drained."""
        warnings.warn(
            "HashStore.resize() is deprecated; use begin_resize()/"
            "resize_step()/resize_cutover()", DeprecationWarning,
            stacklevel=2)
        return self.resize_cutover(self.begin_resize(table, factor))

    # -- cache-validation surface (repro.cache) -----------------------------
    # A stamp is an opaque (B, S) integer array, one row per key, compared
    # row-wise: rows equal  <=>  a fresh lookup returns exactly the value
    # observed when the stamp was taken.  The DEFAULT is value-based —
    # ``[found, value lanes]`` — which is correct for every scheme but
    # prices validation at a FULL lookup plan (there is no cheap version
    # word to read).  Continuity overrides both with its 8-byte indicator
    # word; the cost asymmetry is the cache subsystem's whole argument.

    def version_stamp(self, table, keys) -> jnp.ndarray:
        return _jit_stamp(self, table, keys)

    def version_read_plan(self, table, keys):
        """Verb plan pricing ONE stamp-validation batch."""
        return _jit_stamp_plan(self, table, keys)

    def _stamp_impl(self, table, keys) -> jnp.ndarray:
        res = self._lookup_res(table, keys)
        return jnp.concatenate(
            [res.found[:, None].astype(jnp.uint32),
             res.values.astype(jnp.uint32)], axis=-1)

    def _vplan_impl(self, table, keys):
        # uniform delegation: every scheme module exposes the unified
        # ``version_read_plan(cfg, table, keys)`` (continuity: one depth-0
        # 8-byte word READ per key; the value-stamp baselines: their full
        # lookup plan — there is no cheap version word to poll)
        return self._mod.version_read_plan(self.cfg, table, keys)

    # -- crash-consistency surface (repro.consistency) ----------------------
    # Traced twins of the write ops: same table-out/ok-out contract, plus
    # the ordered PM store trace (`TraceResult.trace`) the crash injector
    # replays.  ``recover`` is the scheme's restart procedure; it accepts a
    # table pytree or a crash-injected state (`CrashState.state`).

    def trace_insert(self, table, keys, vals, mask=None):
        from repro import consistency
        return consistency.trace_store_op(self, table, "insert", keys, vals,
                                          mask)

    def trace_update(self, table, keys, vals, mask=None):
        from repro import consistency
        return consistency.trace_store_op(self, table, "update", keys, vals,
                                          mask)

    def trace_delete(self, table, keys, mask=None):
        from repro import consistency
        return consistency.trace_store_op(self, table, "delete", keys, None,
                                          mask)

    def recover(self, table_or_state):
        from repro import consistency
        return consistency.recover_store(self, table_or_state)

    def load_factor(self, table) -> jnp.ndarray:
        return self._mod.load_factor(self.cfg, table)

    def stats(self, table) -> dict:
        """Host-side diagnostics (blocks on device values)."""
        return {
            "scheme": self.name,
            "count": int(table.count),
            "total_slots": float(self.total_slots(table)),
            "load_factor": float(self.load_factor(table)),
        }


@dataclasses.dataclass(frozen=True)
class ContinuityStore(_ModuleStore):
    """The paper's continuity hashing behind the protocol.

    ``policy.engine``: ``wave`` -> the fused wave-vectorized mutation
    engine; ``serial`` -> the byte-identical ``lax.scan`` reference.
    ``policy.probe``: ``auto`` -> by platform (`repro.kernels.ops.lookup`);
    ``gather`` -> pure-jnp lookup; ``pallas`` / ``reference`` -> the
    Pallas lookup kernel / its jnp oracle
    (`repro.kernels.ops.probe_lookup`), the oracle's fingerprint
    pre-filter per ``policy.use_fp`` (default on).  ``policy.mutate``
    picks the match backend of the fused update/delete (the Pallas
    mutation-plan kernel / its oracle / the jnp gather)."""

    cfg: ch.ContinuityConfig = ch.ContinuityConfig(num_buckets=256)
    name: ClassVar[str] = "continuity"

    @property
    def _mod(self):
        return ch

    def _insert_fn(self):
        return ch.insert_serial if self.policy.engine == "serial" else ch.insert

    def _update_fn(self):
        if self.policy.engine == "serial":
            return ch.update_serial
        return functools.partial(ch.update, probe=self.policy.mutate,
                                 qblock=self.policy.qblock)

    def _delete_fn(self):
        if self.policy.engine == "serial":
            return ch.delete_serial
        return functools.partial(ch.delete, probe=self.policy.mutate,
                                 qblock=self.policy.qblock)

    def _lookup_res(self, table, keys):
        probe = self.policy.probe
        if probe == "gather":
            return ch.lookup(self.cfg, table, keys)
        from repro.kernels import ops as K          # deferred: pallas import
        if probe == "auto":
            return K.lookup(self.cfg, table, keys)
        return K.probe_lookup(self.cfg, table, keys,
                              use_kernel=probe == "pallas",
                              use_fp=self.policy.use_fp)

    def _extract(self, table):
        return ch.extract_items(self.cfg, table)

    def _stamp_impl(self, table, keys) -> jnp.ndarray:
        # (B, 2) [version, indicator]: the ONE 8-byte word every committed
        # mutation on the key's pair atomically rewrites — ABA-proof via
        # the counter half (see ch.version_stamp)
        return ch.version_stamp(self.cfg, table, keys)

    def begin_resize(self, table, factor: int = 2,
                     step_slo_us: Optional[float] = None) -> ResizeState:
        # the paper's log-free resize as an ONLINE split: per-pair cutover
        # tokens route traffic while cohorts move one at a time
        new_cfg, new_table, split = ch.split_begin(self.cfg, table, factor)
        step_budget = None
        if step_slo_us is not None:
            # SLO controller: cohorts per step = how many single-cohort
            # moves fit in the stall budget under the calibrated LinkModel
            # (each move reads one source row and writes its items + words
            # + the cutover token); always >= 1 so the split progresses
            from repro.rdma.transport import LinkModel
            per = LinkModel().cohort_move_us(
                read_bytes=float(self.cfg.row_bytes),
                write_bytes=float(self.cfg.row_bytes + 16))
            step_budget = max(1, int(step_slo_us / per))
        return ResizeState(
            store=self, new_store=dataclasses.replace(self, cfg=new_cfg),
            table=table, new_table=new_table, factor=factor, opaque=split,
            n_items=int(table.count), step_budget=step_budget)

    def resize_step(self, state: ResizeState,
                    budget: Optional[int] = None) -> ResizeState:
        if state.done:
            return state
        if budget is None:
            budget = state.step_budget or 1
        table, new_table, split, moved = ch.split_step(
            self.cfg, state.table, state.new_store.cfg, state.new_table,
            state.opaque, budget)
        return dataclasses.replace(
            state, table=table, new_table=new_table, opaque=split,
            moved=state.moved + moved,
            done=bool(ch.split_done(self.cfg, split)))

    def resize_cutover(self, state: ResizeState):
        while not state.done:
            state = self.resize_step(state, budget=self.cfg.num_pairs)
        left = int(state.table.count)
        if left:
            raise RuntimeError(
                f"resize cutover with {left} item(s) still in the source "
                f"{self.name!r} table — the split did not drain")
        return state.new_store, state.new_table

    # -- mid-split routing (the maintenance loop's read/write path) ---------
    def resize_lookup(self, state: ResizeState, keys) -> OpResult:
        """Dual-read during a split: probe old and new, pick by the
        cohort's cutover token (one extra READ only for in-flight pairs)."""
        res = ch.split_lookup(self.cfg, state.table,
                              state.new_store.cfg, state.new_table,
                              state.opaque, keys)
        from repro.rdma import verbs as rv
        plan = ch.lookup_plan(self.cfg, state.table, keys,
                              ch.lookup(self.cfg, state.table, keys))
        return OpResult(ok=res.found, ledger=rv.ledger_from_plan(plan),
                        values=res.values, reads=res.reads, plan=plan)

    def resize_write(self, state: ResizeState, op: str, keys, vals=None,
                     mask=None) -> Tuple[ResizeState, OpResult]:
        """Route one write batch by the split tokens: moved cohorts write
        the new table, unmoved the old (whose items the split will carry
        over).  Keeps insert-during-split lossless and duplicate-free."""
        keys = jnp.asarray(keys, jnp.uint32).reshape(-1, KEY_LANES)
        to_new = ch.split_route(self.cfg, state.opaque, keys)
        m = (jnp.ones(keys.shape[0], bool) if mask is None
             else jnp.asarray(mask).reshape(-1))
        fn = {"insert": self.insert, "update": self.update,
              "delete": self.delete}[op]
        nfn = {"insert": state.new_store.insert,
               "update": state.new_store.update,
               "delete": state.new_store.delete}[op]
        args_old = (keys,) if op == "delete" else (keys, vals)
        table, r_old = fn(state.table, *args_old, mask=m & ~to_new)
        new_table, r_new = nfn(state.new_table, *args_old, mask=m & to_new)
        ok = jnp.where(to_new, r_new.ok, r_old.ok)
        return (dataclasses.replace(state, table=table, new_table=new_table),
                OpResult(ok=ok, ledger=r_old.ledger.merge(r_new.ledger)))

    def total_slots(self, table=None) -> float:
        if table is None:
            return float(self.cfg.num_pairs * self.cfg.slots_per_pair)
        return float(ch.capacity(self.cfg, table))

    def stats(self, table) -> dict:
        out = super().stats(table)
        out["ext_groups"] = int(table.ext_count)
        return out

    @classmethod
    def from_slots(cls, table_slots: int, policy: ExecPolicy = ExecPolicy(),
                   **overrides) -> "ContinuityStore":
        per_pair = ch.ContinuityConfig(2).slots_per_pair
        pairs = max(2, -(-table_slots // per_pair))   # ceil: >= table_slots
        # a 1/8 stash tier by default: costs nothing until the main slots
        # fill (the lane stays NOOP while the count byte is 0) and lifts
        # the first-trigger load factor past the paper's ~0.85 band
        overrides.setdefault("stash_frac", 1 / 8)
        cfg = dataclasses.replace(
            ch.ContinuityConfig(num_buckets=2 * pairs), **overrides)
        return cls(cfg=cfg, policy=policy)


def _token_mask(tok: jnp.ndarray, bucket_slots: int) -> jnp.ndarray:
    bits = (tok[:, None] >> jnp.arange(bucket_slots, dtype=jnp.uint8)) \
        & jnp.uint8(1)
    return (bits == 1).reshape(-1)


@dataclasses.dataclass(frozen=True)
class LevelStore(_ModuleStore):
    """Level hashing baseline (single batched strategy: the scan order —
    ``policy.engine`` is accepted and irrelevant by construction)."""

    cfg: lv.LevelConfig = lv.LevelConfig(num_top=64)
    name: ClassVar[str] = "level"

    @property
    def _mod(self):
        return lv

    def _extract(self, table):
        keys = jnp.concatenate([table.tkeys.reshape(-1, KEY_LANES),
                                table.bkeys.reshape(-1, KEY_LANES)])
        vals = jnp.concatenate([table.tvals.reshape(-1, VAL_LANES),
                                table.bvals.reshape(-1, VAL_LANES)])
        live = jnp.concatenate([_token_mask(table.ttok, self.cfg.bucket_slots),
                                _token_mask(table.btok, self.cfg.bucket_slots)])
        return keys, vals, live

    def total_slots(self, table=None) -> float:
        return float(self.cfg.total_slots)

    @classmethod
    def from_slots(cls, table_slots: int, policy: ExecPolicy = ExecPolicy(),
                   **overrides) -> "LevelStore":
        top = int(table_slots / 1.5 / 4)
        cfg = dataclasses.replace(
            lv.LevelConfig(num_top=top + top % 2), **overrides)
        return cls(cfg=cfg, policy=policy)


@dataclasses.dataclass(frozen=True)
class PFarmStore(_ModuleStore):
    """P-FaRM-KV baseline (RECIPE logging: 5 PM writes per mutation)."""

    cfg: pf.PFarmConfig = pf.PFarmConfig(num_buckets=64)
    name: ClassVar[str] = "pfarm"

    @property
    def _mod(self):
        return pf

    def _extract(self, table):
        keys = jnp.concatenate([table.keys.reshape(-1, KEY_LANES),
                                table.okeys.reshape(-1, KEY_LANES)])
        vals = jnp.concatenate([table.vals.reshape(-1, VAL_LANES),
                                table.ovals.reshape(-1, VAL_LANES)])
        live = jnp.concatenate([_token_mask(table.tok, self.cfg.bucket_slots),
                                _token_mask(table.otok, self.cfg.bucket_slots)])
        return keys, vals, live

    def total_slots(self, table=None) -> float:
        return float(self.cfg.total_slots)

    @classmethod
    def from_slots(cls, table_slots: int, policy: ExecPolicy = ExecPolicy(),
                   **overrides) -> "PFarmStore":
        cfg = dataclasses.replace(
            pf.PFarmConfig(num_buckets=int(table_slots / 1.25 / 4)),
            **overrides)
        return cls(cfg=cfg, policy=policy)


@dataclasses.dataclass(frozen=True)
class DenseStore(_ModuleStore):
    """Dense block-table reference (no hashing; whole-table lookups)."""

    cfg: dn.DenseConfig = dn.DenseConfig(capacity=256)
    name: ClassVar[str] = "dense"

    @property
    def _mod(self):
        return dn

    def _extract(self, table):
        return dn.extract_items(self.cfg, table)

    def total_slots(self, table=None) -> float:
        return float(self.cfg.capacity)

    @classmethod
    def from_slots(cls, table_slots: int, policy: ExecPolicy = ExecPolicy(),
                   **overrides) -> "DenseStore":
        cfg = dataclasses.replace(dn.DenseConfig(capacity=table_slots),
                                  **overrides)
        return cls(cfg=cfg, policy=policy)


def _register_builtin(registry_register) -> None:
    for cls in (ContinuityStore, LevelStore, PFarmStore, DenseStore):
        registry_register(cls.name, cls.from_slots)
