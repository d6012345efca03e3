"""Distributed KV store + model sharding on 8 fake devices.

Device count is locked at first jax init, so these run in a SUBPROCESS with
XLA_FLAGS set — the main pytest process keeps 1 device (per the dry-run
isolation contract).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str):
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8'\n"
        + body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_store_roundtrip_and_counters():
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
import repro.core.distributed as D
from repro.core import continuity as ch
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh((4, 2), ("data", "model"))
scfg = D.StoreConfig(table=ch.ContinuityConfig(num_buckets=256, ext_frac=0.0),
                     num_shards=4)
table = D.create_sharded(scfg, mesh)
lookup = D.make_lookup(scfg, mesh)
write = D.make_write(scfg, mesh)
rng = np.random.RandomState(0)
B = 64
K = rng.randint(0, 2**31, size=(B, 4)).astype(np.uint32)
V = rng.randint(0, 2**31, size=(B, 4)).astype(np.uint32)
with mesh:
    table, ok, routed = write(table, jnp.full((B,), D.OP_INSERT, jnp.int32),
                              jnp.asarray(K), jnp.asarray(V))
    assert int(ok.sum()) == B
    res = lookup(table, jnp.asarray(K))
    assert bool(np.asarray(res.found).all())
    assert (np.asarray(res.values) == V).all()
    assert int(D.sharded_count(table)) == B
    neg = lookup(table, jnp.asarray(rng.randint(0, 2**31, size=(B, 4)).astype(np.uint32)))
    assert int(neg.found.sum()) == 0
    table, dok, _ = write(table, jnp.full((B,), D.OP_DELETE, jnp.int32),
                          jnp.asarray(K), jnp.asarray(V))
    assert int(dok.sum()) == B and int(D.sharded_count(table)) == 0
print("STORE-OK")
""")
    assert "STORE-OK" in out


def test_store_matches_local_semantics():
    """Distributed ops produce the same member set as the local table."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
import repro.core.distributed as D
from repro.core import continuity as ch
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh((8,), ("data",))
tcfg = ch.ContinuityConfig(num_buckets=512, ext_frac=0.0)
scfg = D.StoreConfig(table=tcfg, num_shards=8)
dt = D.create_sharded(scfg, mesh)
write = D.make_write(scfg, mesh)
lookup = D.make_lookup(scfg, mesh)
lt = ch.create(tcfg)
rng = np.random.RandomState(1)
B = 128
K = rng.randint(0, 2**31, size=(B, 4)).astype(np.uint32)
V = rng.randint(0, 2**31, size=(B, 4)).astype(np.uint32)
with mesh:
    # clients retry routing-capacity overflows (the RDMA full-send-queue
    # analogue) until every insert lands
    pending = jnp.full((B,), D.OP_INSERT, jnp.int32)
    done = np.zeros((B,), bool)
    for _ in range(6):
        dt, dok, _ = write(dt, pending, jnp.asarray(K), jnp.asarray(V))
        done |= np.asarray(dok)
        pending = jnp.where(jnp.asarray(done), 0, D.OP_INSERT).astype(jnp.int32)
        if done.all():
            break
lt, lok, _ = ch.insert(tcfg, lt, K, V)
assert done.sum() == int(lok.sum()) == B
found = np.zeros((B,), bool)
resolved = np.zeros((B,), bool)
vals = np.zeros((B, 4), np.uint32)
with mesh:
    for _ in range(6):   # retry unrouted keys with an updated mask
        res = lookup(dt, jnp.asarray(K), jnp.asarray(~resolved))
        routed = np.asarray(res.routed)
        f = np.asarray(res.found)
        take = routed & ~resolved
        found[take] = f[take]
        vals[take & f] = np.asarray(res.values)[take & f]
        resolved |= routed
        if resolved.all():
            break
assert resolved.all()
lres = ch.lookup(tcfg, lt, K)
assert (found == np.asarray(lres.found)).all()
assert (vals[found] == np.asarray(lres.values)[found]).all()
print("SEMANTICS-OK")
""")
    assert "SEMANTICS-OK" in out


def test_sharded_train_step_matches_single_device():
    """A tiny model trained 2 steps under a (2,4) mesh == unsharded run."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import smoke_config
from repro.distribution.sharding import use_mesh
from repro.launch.mesh import make_debug_mesh
from repro.models import transformer as T
from repro.training import optimizer as O
from repro.training.train_step import make_train_step
cfg = smoke_config("yi-6b")
params = T.init_params(cfg, jax.random.PRNGKey(0))
state = O.init(params)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab)
batch = {"inputs": toks, "labels": jnp.roll(toks, -1, 1)}
step = make_train_step(cfg, O.OptConfig(lr=1e-3))
# unsharded reference
p1, s1, st1 = jax.jit(step)(params, state, batch)
# sharded
mesh = make_debug_mesh((2, 4), ("data", "model"))
with use_mesh(mesh):
    p2, s2, st2 = jax.jit(step)(params, state, batch)
assert abs(float(st1["loss"]) - float(st2["loss"])) < 1e-3
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-3)
print("TRAIN-SHARD-OK")
""")
    assert "TRAIN-SHARD-OK" in out


@pytest.mark.slow
def test_dryrun_cell_small():
    """The dry-run driver itself lowers a debug-scale cell end to end."""
    out = run_sub("""
from repro.launch.dryrun import collective_bytes
# parse a synthetic HLO line
line = ('  %all-gather.3 = bf16[16,4096,1024]{2,1,0} all-gather(%p), '
        'channel_id=4, replica_groups=[16,16]<=[256], dimensions={0}')
c = collective_bytes(line)
assert c["all-gather"]["count"] == 1
assert c["all-gather"]["bytes"] == 16*4096*1024*2 // 16
print("PARSE-OK")
""")
    assert "PARSE-OK" in out
