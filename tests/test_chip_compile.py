"""Compiles for a described TPU v5e (no chip needed) at real widths.

The kernels and a store op are compiled by the TPU compiler for a
``v5e:2x2`` topology that is described, not attached: what the chip's
compiler refuses (unaligned DMAs, unsupported vector ops) or what does not
fit its memory fails here at no chip time.  The topology is described
inside a fixture, never at import, and skips where it cannot be.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import continuity as ch
from repro.kernels import ops as K
from repro.kernels.mutate import mutate_segments
from repro.kernels.probe import probe_segments
from repro.kernels.probe_ref import probe_ref

P, B, S = 2 ** 20, 4096, 20      # real widths: 2^20 pairs, one 4096-op batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("kernel", ["probe", "probe_fp", "mutate"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    u32 = lambda *s: _spec(s, jnp.uint32, one_chip)
    i32 = lambda *s: _spec(s, jnp.int32, one_chip)
    rows, ind, prio = u32(P, ch.ROW_LANES), u32(P), i32(2, S)
    pairs, parity, qk, fps, qfp = i32(B), i32(B), u32(B, 4), u32(P, 2), u32(B)
    if kernel == "mutate":
        lowered = mutate_segments.lower(rows, ind, fps, prio, pairs, parity,
                                        qk, qfp)
    elif kernel == "probe_fp":
        lowered = probe_segments.lower(rows, ind, prio, pairs, parity, qk,
                                       fps, qfp)
    else:
        lowered = probe_segments.lower(rows, ind, prio, pairs, parity, qk)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text      # the compiled kernel, not the interpreter


@pytest.mark.parametrize("op", ["lookup", "update"])
def test_store_op_memory_bounded_by_batch(one_chip, op):
    """At 2^22 slots (api defaults: 1/8 stash) an op's compiled temporaries
    stay within twice the table — they grow with the batch, not the
    table."""
    from repro import api
    cfg = api.make_store("continuity", table_slots=2 ** 22).cfg
    table = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                         jax.eval_shape(lambda: ch.create(cfg)))
    table_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(table))
    kb = _spec((B, ch.KEY_LANES), jnp.uint32, one_chip)
    if op == "lookup":
        lowered = ch.lookup.lower(cfg, table, kb)
    else:
        lowered = ch.update.lower(cfg, table, kb, kb)
    temp = lowered.compile().memory_analysis().temp_size_in_bytes
    assert temp <= 2 * table_bytes, (temp, table_bytes)


def test_kernels_interpret_on_cpu_untold():
    """On the CPU the kernels run in the interpreter with nothing passed:
    the CPU program holds no compiled kernel, and results match the
    oracle."""
    rng = np.random.RandomState(0)
    p, b = 16, 24
    rows = rng.randint(0, 2 ** 31, size=(p, ch.ROW_LANES)).astype(np.uint32)
    ind = rng.randint(0, 2 ** 20, size=(p,)).astype(np.uint32)
    prio = np.asarray(K.priority_table(ch.ContinuityConfig(num_buckets=2)))
    pairs = rng.randint(0, p, size=(b,)).astype(np.int32)
    parity = rng.randint(0, 2, size=(b,)).astype(np.int32)
    qk = rows[pairs, 4:8].copy()             # slot 1 of each home row
    args = [jnp.asarray(a) for a in (rows, ind, prio, pairs, parity, qk)]
    assert jax.default_backend() == "cpu"
    assert "tpu_custom_call" not in probe_segments.lower(*args).as_text()
    for got, want in zip(probe_segments(*args), probe_ref(*args)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("env", [None, "/somewhere/else"])
def test_compile_cache_dir(monkeypatch, env):
    """On an accelerator, entry points cache compiles where
    JAX_COMPILATION_CACHE_DIR says, setting no directory, else in
    ``.jax_cache/`` inside the checkout; the program's metadata is part
    of the key either way."""
    from repro.runtime import compile_cache
    before = jax.config.jax_compilation_cache_dir
    meta = jax.config.jax_compilation_cache_include_metadata_in_key
    canon = jax.config.jax_hlo_source_file_canonicalization_regex
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = compile_cache.enable_compile_cache()
        if env is None:
            assert got == str(compile_cache.CHECKOUT / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
        else:
            assert got == env
            assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_enable_compilation_cache
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        here = os.path.abspath(compile_cache.__file__)
        assert re.sub(jax.config.jax_hlo_source_file_canonicalization_regex,
                      "", here) == "src/repro/runtime/compile_cache.py"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          meta)
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          canon)


@pytest.mark.parametrize("env", [None, "/somewhere/else"])
def test_compile_cache_off_on_the_cpu(monkeypatch, env):
    """On the CPU the persistent cache is off, even where
    JAX_COMPILATION_CACHE_DIR names one, and no directory is set."""
    from repro.runtime import compile_cache
    before = jax.config.jax_compilation_cache_dir
    enabled = jax.config.jax_enable_compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        assert compile_cache.enable_compile_cache() is None
        assert not jax.config.jax_enable_compilation_cache
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
