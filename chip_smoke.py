#!/usr/bin/env python3
"""Smoke test of the continuity store on a TPU, at real size.

    python3 chip_smoke.py [--seed N]      # one chip
    python3 chip_smoke.py --chips 4       # the sharded store, four chips

One chip.  A 2^25-slot store with the `repro.api` defaults (1/8 stash,
fingerprint filter on) is loaded with YCSB records (16 B keys, 16 B
values, made from ``--seed``) to 0.70 of its slots through
`api.bulk_load`, 4096 ops per call.  Then, with B = 4096 ops per call:

  * YCSB-A: ROUNDS rounds of one zipf(0.99) read batch and one
    zipf(0.99) update batch, through ``store.lookup`` / ``store.update``;
  * kernels: the A rounds replayed from the post-load table with
    ``ExecPolicy(probe="pallas", mutate="pallas")`` — the compiled Pallas
    kernels — whose results and final table must equal the gather
    path's byte for byte;
  * YCSB-C: as many read-only rounds, plus one batch of absent keys;
  * delete: 1% of the records deleted, then looked up (all must miss);
  * read-back: every record looked up once more.

Every read is checked against a plain reference, a numpy value array
indexed by record id, which takes each acknowledged update in batch
order.  Each op is compiled ahead of time, and its compiled memory
(argument, output and temporary bytes) is printed and held to the bound
that op memory stays within twice the table's bytes.

Four chips (``--chips 4``).  ``repro.core.distributed``'s ``make_write``
and ``make_lookup`` on a four-chip ("data",) mesh, the table created
sharded, against single-chip ``ch.insert`` / ``ch.lookup`` on the same
keys; nothing else runs.

Lines before the last are set-up facts (times are wall clock, not
metrics).  The last line, printed only when every check passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or on any failed check, the script exits nonzero and
prints no result line.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SLOTS = 2 ** 25
LOAD = 0.70
B = 4096
ROUNDS = 200
GiB = 2 ** 30


class CheckFailed(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def log(msg: str) -> None:
    """Print a set-up fact with device 0's peak bytes in use so far, so
    the phase that set the peak can be read off the log."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"{msg} [peak {stats.get('peak_bytes_in_use')} B]", flush=True)


def last_wins(ids: np.ndarray, vals: np.ndarray):
    """(ids, vals) keeping only each id's last occurrence in batch order."""
    _, rev = np.unique(ids[::-1], return_index=True)
    keep = len(ids) - 1 - rev
    return ids[keep], vals[keep]


def store_op(fn):
    """(jitted function, static keyword arguments) of a store's op, as
    ``store._update_fn()`` and its peers hand it out."""
    if isinstance(fn, functools.partial):
        return fn.func, dict(fn.keywords)
    return fn, {}


def compile_ops(ops, table_bytes: int) -> None:
    """Compile each (name, jitted, args, kwargs) ahead of time; print its
    compile seconds and memory; hold temporaries to 2x the table."""
    for name, fn, args, kwargs in ops:
        t0 = time.perf_counter()
        compiled = fn.lower(*args, **kwargs).compile()
        sec = time.perf_counter() - t0
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes)
        log(f"compile {name}: {sec:.2f} s; argument "
            f"{m.argument_size_in_bytes} B, output {m.output_size_in_bytes}"
            f" B, temp {m.temp_size_in_bytes} B "
            f"({m.temp_size_in_bytes / table_bytes:.3f} x table)")
        check(m.temp_size_in_bytes <= 2 * table_bytes,
              f"{name}: temporaries exceed twice the table")
        check(total <= 12 * GiB, f"{name}: {total} B does not fit 12 GiB")


def run_one_chip(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro import api
    from repro.api.stores import _jit_lookup
    from repro.core import continuity as ch
    from repro.data import ycsb

    store = api.make_store("continuity", table_slots=SLOTS)
    kstore = store.with_policy(api.ExecPolicy(probe="pallas",
                                              mutate="pallas"))
    cfg = store.cfg
    table = store.create()
    table_bytes = sum(x.nbytes for x in jax.tree.leaves(table))
    log(f"table: {SLOTS} slots, {cfg.num_pairs} pairs, {cfg.stash_slots} "
        f"stash entries, {cfg.ext_pool_pairs} extension groups; "
        f"{table_bytes} B ({table_bytes / GiB:.3f} GiB)")

    # -- ahead-of-time compiles: seconds and memory of every op ----------
    kb = jax.ShapeDtypeStruct((B, ch.KEY_LANES), jnp.uint32)
    mb = jax.ShapeDtypeStruct((B,), jnp.bool_)
    # the same callables and static arguments the store's own calls use:
    # insert(cfg, table, keys, vals, mask), update with mask=None as the A
    # rounds call it, delete(cfg, table, keys, mask)
    fn, kw = store_op(store._insert_fn())
    ops = [("insert", fn, (cfg, table, kb, kb, mb), kw)]
    for tag, st in (("gather", store), ("pallas", kstore)):
        ops.append((f"lookup[{tag}]", _jit_lookup, (st, table, kb), {}))
        fn, kw = store_op(st._update_fn())
        ops.append((f"update[{tag}]", fn, (cfg, table, kb, kb, None), kw))
        fn, kw = store_op(st._delete_fn())
        ops.append((f"delete[{tag}]", fn, (cfg, table, kb, mb), kw))
    t0 = time.perf_counter()
    compile_ops(ops, table_bytes)
    log(f"compile total: {time.perf_counter() - t0:.2f} s")

    # -- load --------------------------------------------------------------
    rng = np.random.RandomState(args.seed)
    n = int(LOAD * SLOTS)
    t0 = time.perf_counter()
    keys_all = ycsb.make_key(np.arange(n))
    ref = ycsb.make_value(rng, n)              # reference: value by record id
    log(f"data: {n} records generated in {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    table, ok = api.bulk_load(store, table, keys_all, ref, batch=B)
    jax.block_until_ready(table)
    log(f"load: {n} inserts in {-(-n // B)} batches, "
        f"{time.perf_counter() - t0:.2f} s; load factor "
        f"{float(store.load_factor(table)):.4f} "
        f"({n / SLOTS:.2f} of main slots), "
        f"{int(table.ext_count)} extension groups, "
        f"{int(jnp.sum(table.stash_meta != 0))} stash entries")
    check(ok.all(), f"{int((~ok).sum())} load inserts failed")
    check(int(table.count) == n, "live count differs from records loaded")
    ref = ref.copy()
    alive = np.ones(n, bool)

    zipf = ycsb.Zipf(n)
    scramble = rng.permutation(n)
    keys = lambda ids: jnp.asarray(ycsb.make_key(ids))

    def zipf_ids():
        return scramble[zipf.sample(rng, B)]

    def check_reads(res, ids, what):
        ok = np.asarray(res.ok)
        vals = np.asarray(res.values)
        check(np.array_equal(ok, alive[ids]), f"{what}: found != reference")
        check(np.array_equal(vals[ok], ref[ids][ok]),
              f"{what}: values != reference")

    # -- YCSB-A on the gather path ------------------------------------------
    snapshot = table                 # ops never donate: the post-load table
    rounds, recorded = [], []
    acked = 0
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        rid, uid = zipf_ids(), zipf_ids()
        uval = ycsb.make_value(rng, B)
        res = store.lookup(table, keys(rid))
        table, ures = store.update(table, keys(uid), jnp.asarray(uval))
        res, ures = jax.device_get((res, ures))
        check_reads(res, rid, "YCSB-A read")
        uok = np.asarray(ures.ok)
        acked += int(uok.sum())
        i, v = last_wins(uid[uok], uval[uok])
        ref[i] = v
        rounds.append((rid, uid, uval))
        recorded.append((res, ures))
    jax.block_until_ready(table)
    log(f"YCSB-A: {ROUNDS} rounds, {ROUNDS * B} reads, "
        f"{ROUNDS * B} updates ({acked} acknowledged), "
        f"{time.perf_counter() - t0:.2f} s")

    # -- the same rounds through the compiled Pallas kernels -----------------
    kt = snapshot
    t0 = time.perf_counter()
    for (rid, uid, uval), want in zip(rounds, recorded):
        res = kstore.lookup(kt, keys(rid))
        kt, ures = kstore.update(kt, keys(uid), jnp.asarray(uval))
        got = jax.device_get((res, ures))
        same = jax.tree.map(np.array_equal, got, want)
        check(all(jax.tree.leaves(same)),
              f"kernel path OpResult differs from the gather path "
              f"(lookup, update leaves equal: {same})")
    fields = ch.ContinuityTable._fields
    diff = [f for f, a, b in zip(fields, kt, table)
            if not np.array_equal(np.asarray(a), np.asarray(b))]
    check(not diff, f"kernel path table differs in {diff}")
    del kt, snapshot
    log(f"kernels: {ROUNDS} rounds ({2 * ROUNDS * B} ops) "
        f"identical to the gather path, every OpResult and all "
        f"{len(fields)} table fields; {time.perf_counter() - t0:.2f} s")

    # -- YCSB-C ----------------------------------------------------------------
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        rid = zipf_ids()
        check_reads(store.lookup(table, keys(rid)), rid, "YCSB-C read")
    neg = store.lookup(table, jnp.asarray(ycsb.negative_keys(rng, n, B)))
    check(not np.asarray(neg.ok).any(), "an absent key was found")
    log(f"YCSB-C: {ROUNDS} rounds, {ROUNDS * B} reads + {B} "
        f"absent-key reads, {time.perf_counter() - t0:.2f} s")

    # -- delete 1% ---------------------------------------------------------
    t0 = time.perf_counter()
    dead = rng.choice(n, n // 100, replace=False)
    for lo in range(0, len(dead), B):
        ids = dead[lo:lo + B]
        m = len(ids)
        pad = np.pad(ids, (0, B - m))
        table, dres = store.delete(table, keys(pad),
                                   jnp.asarray(np.arange(B) < m))
        check(np.asarray(dres.ok)[:m].all(), "a delete was not acknowledged")
        alive[ids] = False
    for lo in range(0, len(dead), B):
        ids = dead[lo:lo + B]
        pad = np.pad(ids, (0, B - len(ids)), mode="edge")
        check(not np.asarray(store.lookup(table, keys(pad)).ok).any(),
              "a deleted key was found")
    check(int(table.count) == n - len(dead), "live count after deletes")
    log(f"delete: {len(dead)} deletes, each looked up after, "
        f"{time.perf_counter() - t0:.2f} s")

    # -- read every record back ------------------------------------------------
    t0 = time.perf_counter()
    for lo in range(0, n, B):
        ids = np.arange(lo, min(lo + B, n))
        pad = np.pad(ids, (0, B - len(ids)), mode="edge")
        check_reads(store.lookup(table, keys(pad)), pad, "read-back")
    log(f"read-back: {n} records ({int(alive.sum())} live), "
        f"{time.perf_counter() - t0:.2f} s")


def run_four_chips(args) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro.core.distributed as D
    from repro.core import continuity as ch
    from repro.data import ycsb
    from repro.launch.mesh import make_debug_mesh

    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, found "
          f"{len(jax.devices())}")
    mesh = make_debug_mesh((4,), ("data",))
    # global 2^27 slots: each chip holds a quarter (about the one-chip
    # store's table), and the single-chip reference holds all of it
    slots = 4 * SLOTS
    pairs = -(-slots // ch.ContinuityConfig(2).slots_per_pair)
    pairs += -pairs % 4
    tcfg = ch.ContinuityConfig(num_buckets=2 * pairs, ext_frac=0.0)
    scfg = D.StoreConfig(table=tcfg, num_shards=4)
    t0 = time.perf_counter()
    dt = D.create_sharded(scfg, mesh)
    jax.block_until_ready(dt)
    shard_bytes = sum(s.data.nbytes for x in jax.tree.leaves(dt)
                      for s in x.addressable_shards[:1])
    log(f"sharded table: {slots} slots, {pairs} pairs over 4 chips; "
        f"{shard_bytes} B on each chip; created in "
        f"{time.perf_counter() - t0:.2f} s")
    write = D.make_write(scfg, mesh)
    lookup = D.make_lookup(scfg, mesh)
    lt = ch.create(tcfg)                  # single-chip reference, device 0
    # the reference is donated: two copies of the whole table would not fit
    insert1 = jax.jit(functools.partial(ch.insert, tcfg), donate_argnums=0)

    rng = np.random.RandomState(args.seed)
    # 2^22 records: make_write applies each chip's routed ops one scan
    # step at a time (about 27 us a step on a v5e), so the load, not the
    # table, sets this phase's time
    n = slots // 32
    GB = 4 * B                            # global batch: B per chip
    data = NamedSharding(mesh, P("data"))
    K = ycsb.make_key(np.arange(n))
    V = ycsb.make_value(rng, n)
    t0 = time.perf_counter()
    for lo in range(0, n, GB):
        k, v = K[lo:lo + GB], V[lo:lo + GB]
        todo = np.ones(GB, bool)
        while todo.any():                 # retry routing overflow
            op = jnp.where(jnp.asarray(todo), D.OP_INSERT, 0).astype(jnp.int32)
            dt, ok, routed = write(dt, jax.device_put(op, data),
                                   jax.device_put(k, data),
                                   jax.device_put(v, data))
            routed = np.asarray(routed)
            check(np.asarray(ok)[todo & routed].all(),
                  "a routed distributed insert failed")
            todo &= ~routed
        lt, lok, _ = insert1(lt, jnp.asarray(k), jnp.asarray(v))
        check(np.asarray(lok).all(), "a single-chip insert failed")
        if (lo // GB) % 256 == 255:
            log(f"  {lo + GB} records loaded, "
                f"{time.perf_counter() - t0:.2f} s")
    jax.block_until_ready((dt, lt))
    log(f"load: {n} records through make_write (4 chips) and ch.insert "
        f"(1 chip), {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    probes = 0
    for lo in range(0, n, 16 * GB):
        ids = np.concatenate([np.arange(lo, lo + GB // 2) % n,
                              n + rng.randint(0, n, GB // 2)])
        k = ycsb.make_key(ids)
        dres = lookup(dt, jax.device_put(k, data))
        lres = ch.lookup(tcfg, lt, jnp.asarray(k))
        check(np.asarray(dres.routed).all(), "a lookup was not routed")
        found = np.asarray(dres.found)
        check(np.array_equal(found, np.asarray(lres.found)),
              "distributed found != single-chip found")
        check(np.array_equal(found, ids < n), "found != loaded set")
        check(np.array_equal(np.asarray(dres.values)[found],
                             V[ids[found]]), "distributed values != loaded")
        probes += GB
    log(f"lookup: {probes} keys (half absent) through make_lookup and "
        f"ch.lookup agree, {time.perf_counter() - t0:.2f} s")
    same = {f: bool(np.array_equal(np.asarray(getattr(dt, f)),
                                   np.asarray(getattr(lt, f))))
            for f in ("indicator", "version")}
    log(f"sharded vs single-chip table fields equal: {same}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    from repro.runtime.compile_cache import enable_compile_cache
    log(f"devices: {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    (run_four_chips if args.chips == 4 else run_one_chip)(args)
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
        f"(device 0); total {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
