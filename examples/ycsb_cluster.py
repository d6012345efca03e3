"""Distributed continuity KV store under YCSB-A on a simulated 8-device mesh,
the end-to-end RDMA transport comparison (`repro.rdma`), and the N-node
replicated cluster with live failover (`repro.cluster`).

The paper's deployment: each data shard is a 'server' owning a pair range;
clients batch reads (one contiguous segment fetch each, via all_to_all
routing) and route writes to owners.  Wire accounting is verb-plan-derived
(`DLookupResult.ledger`); the second section drives the same YCSB mixes
through the analytical transport (`repro.rdma.sim`) and prints the
per-scheme throughput/latency ordering the paper reports; the third runs
an elastic `ClusterStore` — rendezvous-sharded, replica-fenced writes —
and (with ``--kill-primary``) crashes a primary mid-run to exercise
heartbeat detection, replica promotion with indicator-based recovery,
and the zero-committed-loss audit.

NOTE: sets XLA_FLAGS for 8 host devices — run as its own process.

Run: PYTHONPATH=src python examples/ycsb_cluster.py \
        [--smoke] [--nodes N] [--kill-primary]
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def run_mesh(smoke: bool) -> None:
    import repro.core.distributed as D
    from repro.core import continuity as ch
    from repro.data import ycsb
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh((8,), ("data",))
    scfg = D.StoreConfig(
        table=ch.ContinuityConfig(num_buckets=1 << (10 if smoke else 14),
                                  ext_frac=0.0),
        num_shards=8)
    print(f"store: {scfg.table.num_buckets} buckets over {scfg.num_shards} "
          f"servers ({scfg.pairs_per_shard} pairs each)")
    table = D.create_sharded(scfg, mesh)
    lookup = D.make_lookup(scfg, mesh)
    write = D.make_write(scfg, mesh)

    n = 1536 if smoke else 20_000      # batches must divide the 8-way mesh
    B = 512 if smoke else 4096
    rounds = 2 if smoke else 8
    rng = np.random.RandomState(0)
    K = ycsb.make_key(np.arange(n))
    V = ycsb.make_value(rng, n)

    with mesh:
        t0 = time.time()
        done = np.zeros(n, bool)
        for lo in range(0, n, B):
            hi = min(lo + B, n)
            table, ok, _ = write(table, jnp.full((hi - lo,), D.OP_INSERT,
                                                 jnp.int32),
                                 jnp.asarray(K[lo:hi]), jnp.asarray(V[lo:hi]))
            done[lo:hi] = np.asarray(ok)
        print(f"load: {done.sum()}/{n} inserted in {time.time()-t0:.1f}s; "
              f"count={int(D.sharded_count(table))}")

        # YCSB-A: 50% reads / 50% updates, zipfian
        zipf = ycsb.Zipf(n)
        t0 = time.time()
        reads = bytes_fetched = 0
        for r in range(rounds):
            rk = ycsb.make_key(zipf.sample(rng, B))
            res = lookup(table, jnp.asarray(rk))
            reads += int(res.ledger.rdma_reads)
            bytes_fetched += int(res.ledger.bytes_fetched)
            uk = ycsb.make_key(zipf.sample(rng, B))
            table, uok, _ = write(table, jnp.full((B,), D.OP_UPDATE, jnp.int32),
                                  jnp.asarray(uk), jnp.asarray(
                                      ycsb.make_value(rng, B)))
        jax.block_until_ready(table)
        dt = time.time() - t0
        nops = rounds * B * 2
        print(f"YCSB-A: {nops} ops in {dt:.1f}s = {nops/dt:.0f} ops/s "
              f"(8 simulated devices on one CPU); global wire ledger: "
              f"{reads} one-sided reads, {bytes_fetched} B fetched "
              f"(verb-plan-derived)")

        # consistency: all loaded keys still resolve with correct liveness
        res = lookup(table, jnp.asarray(K[:B]))
        assert bool(np.asarray(res.found)[done[:B]].all())
        print("consistency check passed: every committed insert is visible")


def run_transport(smoke: bool) -> None:
    """End-to-end per-scheme YCSB over the one-sided transport simulation:
    the paper's headline throughput/latency ordering."""
    from repro.rdma import sim

    kw = (dict(num_records=800, num_ops=1000, batch=250) if smoke
          else dict(num_records=3000, num_ops=4000, batch=500))
    print("\nRDMA transport end-to-end (doorbell batching + analytical "
          "latency model):")
    print(f"{'scheme':12s} {'wl':2s} {'ops/s':>10s} {'p50 us':>8s} "
          f"{'p99 us':>8s} {'verbs/op':>9s}")
    order = {}
    for s in ("continuity", "level", "pfarm"):
        for wl in sim.SIM_WORKLOADS:
            r = sim.run_ycsb(s, wl, **kw)
            order.setdefault(wl, []).append(r["ops_per_s"])
            print(f"{s:12s} {wl:2s} {r['ops_per_s']:10.0f} "
                  f"{r['p50_us']:8.2f} {r['p99_us']:8.2f} "
                  f"{r['verbs_per_op']:9.2f}")
    for wl in ("B", "C"):
        c, l, p = order[wl]
        assert c >= l >= p, (wl, order[wl])
    print("ordering check passed: continuity >= level >= pfarm on "
          "read-heavy workloads")


def run_failover(smoke: bool, nodes: int, kill_primary: bool) -> None:
    """The N-node replicated cluster: rendezvous routing, fenced replica
    writes, heartbeat-driven failover with indicator-based recovery."""
    from repro.cluster import ClusterStore, FailoverController
    from repro.data import ycsb

    n = 400 if smoke else 2000
    B = 100 if smoke else 400
    rounds = 4 if smoke else 10
    cluster = ClusterStore("continuity", nodes=nodes, replicas=2,
                           node_slots=max(512, 3 * 2 * n // nodes))
    clock = [0.0]
    ctl = FailoverController(cluster, timeout_s=3.0,
                             clock=lambda: clock[0])

    print(f"\nN-node cluster ({nodes} PM nodes, R=2, rendezvous "
          f"directory, fenced replica writes):")
    rng = np.random.RandomState(0)
    acked = {}
    for lo in range(0, n, B):
        ids = np.arange(lo, min(lo + B, n))
        vals = ycsb.make_value(rng, len(ids))
        res = cluster.insert(ycsb.make_key(ids), vals)
        for i, v in zip(ids[np.asarray(res.ok)], vals[np.asarray(res.ok)]):
            acked[int(i)] = v
    print(f"load: {len(acked)}/{n} committed (primary + replica fenced)")

    zipf = ycsb.Zipf(n)
    victim = None
    for r in range(rounds):
        clock[0] += 1.0
        ctl.beat(r)
        for rep in ctl.tick():
            print(f"failover: {rep.dead} promoted away "
                  f"({rep.promoted_keys} keys re-primaried, "
                  f"{rep.recopied} copies restored, recovery log-free="
                  f"{rep.recovery_log_free()})")
        if kill_primary and r == rounds // 2:
            hot = ycsb.make_key(np.array([0]))
            victim = str(cluster.directory.replica_names(hot)[0, 0])
            cluster.kill(victim)
            print(f"killed {victim} (primary of the hottest key) mid-run")
        ids = zipf.sample(rng, B)
        vals = ycsb.make_value(rng, B)
        res = cluster.update(ycsb.make_key(ids), vals)
        okn = np.asarray(res.ok)
        for i, v in zip(ids[okn], vals[okn]):
            acked[int(i)] = v
    for extra in range(5):          # let detection + promotion drain
        clock[0] += 1.0
        ctl.beat(rounds + extra)
        for rep in ctl.tick():
            print(f"failover: {rep.dead} promoted away "
                  f"({rep.promoted_keys} keys re-primaried, "
                  f"{rep.recopied} copies restored, recovery log-free="
                  f"{rep.recovery_log_free()})")

    ids = np.array(sorted(acked))
    lost = 0
    for lo in range(0, len(ids), B):
        sub = ids[lo:lo + B]
        res = cluster.lookup(ycsb.make_key(sub))
        want = np.stack([acked[int(i)] for i in sub])
        good = np.asarray(res.found) & (res.values == want).all(axis=1)
        lost += int((~good).sum())
    assert lost == 0, f"{lost} committed ops lost"
    if kill_primary:
        assert victim is not None and victim not in cluster.node_names()
    print(f"failover check passed: {len(acked)} committed ops, 0 lost "
          f"(nodes: {', '.join(cluster.node_names())})")


def main(smoke: bool = False, nodes: int = 4, kill_primary: bool = False):
    run_mesh(smoke)
    run_transport(smoke)
    run_failover(smoke, nodes, kill_primary)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for the examples smoke test")
    ap.add_argument("--nodes", type=int, default=4,
                    help="PM nodes in the replicated cluster section")
    ap.add_argument("--kill-primary", action="store_true",
                    help="crash a primary mid-run and exercise failover")
    args = ap.parse_args()
    main(args.smoke, args.nodes, args.kill_primary)
