"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attn import paged_attention
from repro.kernels.paged_attn_ref import paged_attention_ref
from repro.kernels.probe import probe_segments
from repro.kernels.probe_ref import probe_ref

BIG = 0x7FFFFFFF


def make_probe_case(rng, P, S, KL, B, planted_frac=0.5):
    # key rows padded to a whole 128-lane tile row, as the table stores them
    rows = rng.randint(0, 2 ** 31, size=(P, 128)).astype(np.uint32)
    ind = rng.randint(0, 2 ** S if S < 31 else 2 ** 31,
                      size=(P, 1)).astype(np.uint32)
    seg = (S * 4) // 5
    prio = np.full((2, S), BIG, np.int32)
    prio[0, :seg] = np.arange(seg)
    odd = list(range(S - 1, S - 1 - seg, -1))
    prio[1, odd] = np.arange(seg)
    pairs = rng.randint(0, P, size=(B,)).astype(np.int32)
    parity = rng.randint(0, 2, size=(B,)).astype(np.int32)
    qkeys = rng.randint(0, 2 ** 31, size=(B, KL)).astype(np.uint32)
    for i in range(0, B, max(int(1 / max(planted_frac, 1e-9)), 1)):
        s = rng.randint(0, S)
        qkeys[i] = rows[pairs[i], s * KL:(s + 1) * KL]
    return rows, ind, prio, pairs, parity, qkeys


@pytest.mark.parametrize("P,S,B", [(8, 20, 16), (32, 20, 64), (16, 10, 33),
                                   (64, 30, 128), (4, 20, 7)])
def test_probe_kernel_matches_oracle(P, S, B):
    rng = np.random.RandomState(P * 1000 + B)
    args = [jnp.asarray(a) for a in make_probe_case(rng, P, S, 4, B)]
    m1, e1 = probe_segments(*args)
    m2, e2 = probe_ref(*args)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


def test_probe_kernel_full_and_empty_tables():
    rng = np.random.RandomState(0)
    rows, ind, prio, pairs, parity, qkeys = make_probe_case(rng, 8, 20, 4, 32)
    for fill in (0, 0xFFFFF):   # empty / all-20-main-bits-set
        indc = np.full_like(ind, fill)
        m1, e1 = probe_segments(*[jnp.asarray(a) for a in
                                  (rows, indc, prio, pairs, parity, qkeys)])
        m2, e2 = probe_ref(*[jnp.asarray(a) for a in
                             (rows, indc, prio, pairs, parity, qkeys)])
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))
        np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 6e-2)])
@pytest.mark.parametrize("B,H,KVH,D,PS,MAXP", [
    (2, 4, 1, 16, 8, 3),
    (3, 8, 2, 32, 16, 4),
    (1, 16, 4, 64, 32, 2),
    (4, 4, 4, 16, 8, 5),       # MHA (G=1, padded to 8 by ops wrapper)
])
def test_paged_attention_matches_oracle(dtype, tol, B, H, KVH, D, PS, MAXP):
    rng = np.random.RandomState(B * 100 + H)
    NP = B * MAXP + 2
    q = (rng.randn(B, H, D) * 0.5).astype(np.float32)
    kp = (rng.randn(NP, KVH, PS, D) * 0.3).astype(np.float32)
    vp = rng.randn(NP, KVH, PS, D).astype(np.float32)
    pt = np.full((B, MAXP), -1, np.int32)
    lens = rng.randint(1, MAXP * PS, size=(B,)).astype(np.int32)
    perm = rng.permutation(NP)
    c = 0
    for b in range(B):
        for p in range(int(np.ceil(lens[b] / PS))):
            pt[b, p] = perm[c]
            c += 1
    args = (jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
            jnp.asarray(vp, dtype), jnp.asarray(pt), jnp.asarray(lens))
    from repro.kernels.ops import paged_attention as pa_padded
    o1 = pa_padded(*args)
    o2 = paged_attention_ref(*args)
    err = np.max(np.abs(np.asarray(o1, np.float32)
                        - np.asarray(o2, np.float32)))
    assert err < tol, err


def test_paged_attention_ignores_dead_pages():
    """Garbage in unmapped pool pages must not leak into the output."""
    rng = np.random.RandomState(7)
    B, H, KVH, D, PS, MAXP, NP = 2, 4, 2, 16, 8, 4, 16
    q = rng.randn(B, H, D).astype(np.float32)
    kp = rng.randn(NP, KVH, PS, D).astype(np.float32)
    vp = rng.randn(NP, KVH, PS, D).astype(np.float32)
    pt = np.full((B, MAXP), -1, np.int32)
    pt[:, 0] = [0, 1]
    lens = np.array([5, 3], np.int32)
    from repro.kernels.ops import paged_attention as pa
    base = np.asarray(pa(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                         jnp.asarray(pt), jnp.asarray(lens)))
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[2:] = 1e3
    vp2[2:] = -1e3                      # poison every unmapped page
    out = np.asarray(pa(jnp.asarray(q), jnp.asarray(kp2), jnp.asarray(vp2),
                        jnp.asarray(pt), jnp.asarray(lens)))
    np.testing.assert_allclose(out, base, rtol=1e-6)


def _inserted(cfg, n, queries):
    """``n`` records inserted through the wave engine; ``queries`` keys
    from id 0 on, so those past ``n`` (and any the table refused) miss."""
    import repro.core.continuity as ch
    from repro.data import ycsb
    t = ch.create(cfg)
    if n:
        K = ycsb.make_key(np.arange(n))
        V = ycsb.make_value(np.random.RandomState(3), n)
        t, _, _ = ch.insert(cfg, t, K, V)
    return t, ycsb.make_key(np.arange(queries))


def _random_state(cfg, queries):
    """A table state drawn at random, not built by inserts: random key and
    value rows, indicator words empty, full or random, an extension group
    on about half the pairs, and queries planted in their home pair's
    main and extension slots (visible or not), in two slots at once, in
    the lanes past a row's slots, beside random keys and the zero key,
    whose pair has no extension group (stale rows must not match it)."""
    import repro.core.continuity as ch
    rng = np.random.RandomState(11)
    P, PE = cfg.num_pairs, cfg.ext_pool_pairs
    rows = lambda n: rng.randint(0, 2 ** 31, size=(n, 128)).astype(np.uint32)
    keys, ext_keys = rows(P), rows(PE)
    full = (1 << cfg.total_bits) - 1
    ind = rng.choice([0, full, full ^ 1, 0x5555, 0],
                     size=P).astype(np.uint32)
    ind[P // 2:] = rng.randint(0, 2 ** 31, size=P - P // 2) | (1 << 31)
    ext_map = np.where(rng.rand(P) < 0.5, rng.randint(0, PE, size=P), -1)
    S, E = cfg.slots_per_pair, cfg.ext_slots
    q = rng.randint(0, 2 ** 31, size=(queries, 4)).astype(np.uint32)
    q[-8:] = 0
    pairs = np.asarray(ch.locate(cfg, jnp.asarray(q))[0])
    ext_map[pairs[-1]], ind[pairs[-1]] = -1, 0xFFFFFFFF

    def put(row, s, i):
        row[4 * s:4 * s + 4] = q[i]

    for i, p in enumerate(pairs[:queries * 3 // 4]):
        e = ext_map[p]
        if e >= 0 and i % 3 == 0:
            put(ext_keys[e], rng.randint(0, E), i)
            if i % 2 == 0:                # in the main row too
                put(keys[p], rng.randint(0, S), i)
        elif i % 11 == 0:                 # just past the row's slots
            put(ext_keys[e] if e >= 0 else keys[p], E if e >= 0 else S, i)
            ind[p] |= 1 << 31
        else:
            s = rng.randint(0, S)
            put(keys[p], s, i)
            if i % 7 == 0:                # the same key in a second slot
                put(keys[p], (s + 3) % S, i)
    t = ch.create(cfg)._replace(
        keys=jnp.asarray(keys), vals=jnp.asarray(rows(P)),
        indicator=jnp.asarray(ind), ext_keys=jnp.asarray(ext_keys),
        ext_vals=jnp.asarray(rows(PE)),
        ext_map=jnp.asarray(ext_map.astype(np.int32)))
    return t, q


# (geometry, table, query batch): hits in home buckets, SBuckets,
# extension groups and the stash; misses; both parities; empty and full
# pairs; batches that are not a multiple of the kernel's block, one of
# them over two blocks
PROBE_CASES = {
    "sparse": (dict(num_buckets=64), lambda c: _inserted(c, 120, 120)),
    "full-ext-stash-2blocks": (dict(num_buckets=64, stash_frac=1 / 8),
                               lambda c: _inserted(c, 700, 760)),
    "buckets-of-2": (dict(num_buckets=64, bucket_slots=2),
                     lambda c: _inserted(c, 300, 333)),
    "empty": (dict(num_buckets=64), lambda c: _inserted(c, 0, 40)),
    "random-state": (dict(num_buckets=128, ext_frac=0.5),
                     lambda c: _random_state(c, 600)),
}


@pytest.mark.parametrize("case", list(PROBE_CASES))
def test_probe_table_consistent_with_lookup(case):
    """The lookup kernel's path (``probe_lookup``) is byte-identical to
    ``continuity.lookup`` in all five result leaves, and the segment
    probe's match is the lookup's slot wherever that slot is a main one."""
    import repro.core.continuity as ch
    from repro.kernels import ops as K
    from repro.kernels import probe_table
    geometry, fill = PROBE_CASES[case]
    cfg = ch.ContinuityConfig(**geometry)
    t, q = fill(cfg)
    res = ch.lookup(cfg, t, q)
    got = K.probe_lookup(cfg, t, q, use_kernel=True)
    for name, a, b in zip(res._fields, got, res):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    match, empty, pair, parity = probe_table(cfg, t, q)
    slot = np.asarray(res.slot)
    main = (slot >= 0) & (slot < cfg.slots_per_pair)
    np.testing.assert_array_equal(np.asarray(match)[main], slot[main])
