import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST be the first two lines: jax locks the device count on first init.
# The dry-run (and ONLY the dry-run) sees 512 placeholder CPU devices so the
# production meshes can be built; smoke tests and benches see 1 device.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod);
  2. builds the step function (train_step / prefill / serve_step) and
     ShapeDtypeStruct stand-ins for params, optimizer state, caches, inputs
     (jax.eval_shape — no allocation);
  3. ``jit(...).lower(...).compile()`` with explicit NamedShardings derived
     from the logical-axis rules;
  4. records memory_analysis (bytes/device), cost_analysis (FLOPs + bytes
     accessed, per device), and the collective bytes parsed from the
     compiled HLO — the three §Roofline inputs — into one JSON per cell
     under experiments/dryrun/.

Also dry-runs the paper's own artifact (the distributed continuity KV
service) as pseudo-arch ``continuity-kv`` with read/write "shapes".

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

# hardware constants: TPU v5e
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)"
                       r"\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective accounting from the per-device optimized HLO.

    Optimized HLO prints operands as bare names, so sizes are derived from
    the RESULT shape + replica-group size g:
      operand bytes: all-gather = result/g; reduce-scatter = result*g;
                     others = result.
      wire bytes (ring model, per device): all-reduce 2*r*(g-1)/g;
        all-gather r*(g-1)/g; reduce-scatter r*(g-1); all-to-all r*(g-1)/g;
        collective-permute r.
    The roofline collective term uses wire bytes.
    """
    out = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "= " not in line:
            continue
        op = m.group(1)
        shapes = [_shape_bytes(d, s)
                  for d, s in _SHAPE_RE.findall(line[:m.start()])]
        if not shapes:
            continue
        r = max(shapes)
        g = _group_size(line)
        if op == "all-gather":
            operand, wire = r // g, r * (g - 1) // g
        elif op == "reduce-scatter":
            operand, wire = r * g, r * (g - 1)
        elif op == "all-reduce":
            operand, wire = r, 2 * r * (g - 1) // g
        elif op == "all-to-all":
            operand, wire = r, r * (g - 1) // g
        else:  # collective-permute
            operand, wire = r, r
        rec = out.setdefault(op, {"count": 0, "bytes": 0, "wire_bytes": 0})
        rec["count"] += 1
        rec["bytes"] += operand
        rec["wire_bytes"] += wire
    return out


_COMP_RE = re.compile(r"^(ENTRY )?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?\)\s*,\s*condition=%?([\w\.\-]+)\s*,\s*"
                       r"body=%?([\w\.\-]+)")
_CALLEE_RE = re.compile(r"(?:to_apply|body|condition|branch_computations)="
                        r"\{?%?([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)\}?")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(text: str):
    """HLO text -> ({name: [lines]}, entry_name)."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and ("{" in line):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps, entry


def _trip_count(cond_lines) -> int:
    """Scan-style while conditions compare the induction var to a constant:
    the largest (sane) integer constant in the condition is the trip count."""
    best = 1
    for line in cond_lines:
        for m in _CONST_RE.finditer(line):
            v = int(m.group(1))
            if v <= 1_000_000:           # ignore sentinel/mask constants
                best = max(best, v)
    return best


def collective_bytes_weighted(text: str) -> dict:
    """Collective accounting with while-bodies weighted by their trip counts
    (cost_analysis and naive text scans count scan bodies once — see
    EXPERIMENTS.md §Methodology)."""
    comps, entry = _split_computations(text)
    if entry is None:
        return collective_bytes(text)
    out = {}

    def add(line, mult):
        m = _COLL_RE.search(line)
        if not m or "= " not in line:
            return
        op = m.group(1)
        shapes = [_shape_bytes(d, s)
                  for d, s in _SHAPE_RE.findall(line[:m.start()])]
        if not shapes:
            return
        r = max(shapes)
        g = _group_size(line)
        if op == "all-gather":
            operand, wire = r // g, r * (g - 1) // g
        elif op == "reduce-scatter":
            operand, wire = r * g, r * (g - 1)
        elif op == "all-reduce":
            operand, wire = r, 2 * r * (g - 1) // g
        elif op == "all-to-all":
            operand, wire = r, r * (g - 1) // g
        else:
            operand, wire = r, r
        rec = out.setdefault(op, {"count": 0, "bytes": 0, "wire_bytes": 0})
        rec["count"] += mult
        rec["bytes"] += operand * mult
        rec["wire_bytes"] += wire * mult

    def walk(name, mult, depth=0):
        if name not in comps or depth > 32:   # HLO call graphs are DAGs
            return
        for line in comps[name]:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trip = _trip_count(comps.get(cond, []))
                walk(body, mult * trip, depth + 1)
                continue
            add(line, mult)
            cm = _CALLEE_RE.search(line)
            if cm and "while(" not in line:
                for callee in cm.group(1).replace("%", "").split(","):
                    walk(callee.strip(), mult, depth + 1)

    walk(entry, 1)
    return out


def build_mesh(multi_pod: bool):
    from repro.launch.mesh import make_production_mesh
    return make_production_mesh(multi_pod=multi_pod)


def _named(tree_axes, tree_structs):
    from repro.distribution.sharding import named_sharding
    return jax.tree.map(
        lambda ax, s: None if s is None else named_sharding(
            *(ax if ax is not None else (None,) * s.ndim), size_of=s.shape),
        tree_axes, tree_structs,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and
                                        all(isinstance(e, (str, type(None)))
                                            for e in x)))


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None):
    """Build + lower + compile one cell; returns (record, compiled)."""
    from repro.configs import get_arch
    from repro.distribution.sharding import use_mesh, named_sharding
    from repro.models import transformer as T
    from repro.models.config import SHAPES, input_specs, shape_applicable
    from repro.serving import engine as E
    from repro.serving import kvcache as KC
    from repro.training import optimizer as O
    from repro.training.train_step import make_train_step

    cfg = get_arch(arch)
    if overrides:
        fields = {f.name for f in dataclasses.fields(cfg)}
        cfg_over = {k: v for k, v in overrides.items() if k in fields}
        if "moe_impl" in overrides and cfg.moe is not None:
            cfg_over["moe"] = dataclasses.replace(
                cfg.moe, impl=overrides["moe_impl"])
        if cfg_over:
            cfg = dataclasses.replace(cfg, **cfg_over)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": why}, None

    mesh = build_mesh(multi_pod)
    chips = mesh.devices.size
    dp = chips // 16                      # pod x data extent

    # sequence parallelism (Megatron-SP): shard the residual stream's seq
    # dim over the model axis -> GSPMD decomposes the TP all-reduces into
    # reduce-scatter + all-gather (half the wire bytes) and distributes norms
    rules = ({"seq": ("model",)} if (overrides or {}).get("seq_parallel")
             else None)
    with use_mesh(mesh, rules):
        params_s = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
        p_axes = T.param_logical_axes(cfg, params_s)
        p_shard = _named(p_axes, params_s)
        batch_s = input_specs(cfg, shape)
        t0 = time.time()

        if shape.kind == "train":
            opt_cfg = O.OptConfig()
            opt_s = jax.eval_shape(O.init, params_s)
            o_axes = O.OptState(
                m=O.opt_logical_axes(p_axes, params_s, dp, opt_cfg.zero1),
                v=O.opt_logical_axes(p_axes, params_s, dp, opt_cfg.zero1),
                step=())
            o_shard = _named(o_axes, opt_s)
            b_axes = {k: ("batch",) + (None,) * (v.ndim - 1)
                      for k, v in batch_s.items()}
            b_shard = _named(b_axes, batch_s)
            step = make_train_step(cfg, opt_cfg,
                                   num_micro=(overrides or {}).get("num_micro", 1))
            jitted = jax.jit(step,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_s, opt_s, batch_s)

        elif shape.kind == "prefill" and cfg.family in ("ssm", "hybrid"):
            # recurrent archs: prefill = full forward (state extraction is a
            # free by-product; no paged pool exists for these families)
            x_ax = ("batch",) + (None,) * (batch_s["inputs"].ndim - 1)
            x_shard = named_sharding(*x_ax, size_of=batch_s["inputs"].shape)
            fn = lambda p, x: T.logits_fn(cfg, p, T.forward(cfg, p, x)[0][:, -1])
            jitted = jax.jit(fn, in_shardings=(p_shard, x_shard))
            lowered = jitted.lower(params_s, batch_s["inputs"])

        elif shape.kind == "prefill":
            geom = KC.make_geometry(cfg, shape, shards=dp,
                                    page_size=(overrides or {}).get("page_size", 512),
                                    oversub=(overrides or {}).get("oversub", 1.0),
                                    kv_dtype=(overrides or {}).get("kv_dtype"))
            cache_s = jax.eval_shape(lambda: KC.create_cache(geom))
            c_axes = KC.cache_logical_axes(geom, cache_s)
            c_shard = _named(c_axes, cache_s)
            x_ax = ("batch",) + (None,) * (batch_s["inputs"].ndim - 1)
            b_shard = {"inputs": named_sharding(*x_ax,
                                                size_of=batch_s["inputs"].shape)}
            fn = lambda p, x, c: E.prefill(cfg, geom, p, x, c)
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard["inputs"], c_shard),
                             out_shardings=(None, c_shard),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_s, batch_s["inputs"], cache_s)

        else:  # decode
            if cfg.family in ("ssm", "hybrid"):
                cache_s = jax.eval_shape(
                    lambda: KC.create_state_cache(cfg, shape.global_batch,
                                                  shape.seq_len,
                                                  dtype=jnp.bfloat16))
                c_axes = KC.state_cache_logical_axes(cfg, cache_s)
                c_shard = _named(c_axes, cache_s)
                geom = None
            else:
                geom = KC.make_geometry(cfg, shape, shards=dp,
                                        page_size=(overrides or {}).get("page_size", 512),
                                        oversub=(overrides or {}).get("oversub", 1.0),
                                        kv_dtype=(overrides or {}).get("kv_dtype"),
                                        merged_attn=(overrides or {}).get("paged_merged", False))
                cache_s = jax.eval_shape(lambda: KC.create_cache(geom))
                c_axes = KC.cache_logical_axes(geom, cache_s)
                c_shard = _named(c_axes, cache_s)
            tok_shard = named_sharding("batch",
                                       size_of=batch_s["inputs"].shape)
            if (overrides or {}).get("serve_bf16"):
                # serving reads bf16 weights (no optimizer here; the f32
                # masters live with the trainer)
                params_s = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(
                        s.shape, jnp.bfloat16 if s.dtype == jnp.float32
                        else s.dtype), params_s)
                p_shard = _named(p_axes, params_s)
            fn = lambda p, t, c: E.serve_step(cfg, geom, p, t, c)
            jitted = jax.jit(fn, in_shardings=(p_shard, tok_shard, c_shard),
                             out_shardings=(None, c_shard),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_s, batch_s["inputs"], cache_s)

        compiled = lowered.compile()
        compile_s = time.time() - t0

    from repro.launch.analytic import model_cell
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    colls = collective_bytes_weighted(compiled.as_text())
    coll_total = sum(v["wire_bytes"] for v in colls.values())

    # analytic model is the primary compute/memory input: cost_analysis
    # counts scan bodies ONCE (recorded below as the per-iteration floor)
    kvb = 1 if (overrides or {}).get("kv_dtype") == "int8" else 2
    am = model_cell(cfg, shape, chips, tp=16, kv_bytes=kvb)
    flops_dev = am.flops_total / chips
    bytes_dev = am.hbm_bytes_dev
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_total / ICI_BW,
    }
    dominant = max(terms, key=terms.get)
    step_s = max(sum(terms.values()), 1e-30)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "status": "ok",
        "compile_seconds": round(compile_s, 1),
        "overrides": overrides or {},
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_estimate_per_device":
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes,
        },
        "cost_hlo_floor": {"flops_per_device": float(cost.get("flops", 0.0)),
                           "bytes_accessed_per_device":
                               float(cost.get("bytes accessed", 0.0))},
        "analytic": {"flops_total": am.flops_total,
                     "flops_useful": am.flops_useful,
                     "hbm_bytes_per_device": am.hbm_bytes_dev,
                     "notes": am.notes},
        "collectives": colls,
        "collective_wire_bytes_per_device": coll_total,
        "roofline": {**terms, "dominant": dominant,
                     "bound_fraction": terms[dominant] / step_s},
        "model_flops": am.flops_useful,
        "useful_flops_ratio": am.flops_useful / max(am.flops_total, 1.0),
        # fraction of hardware peak the USEFUL flops achieve at the modeled
        # step time (the §Perf score: higher = closer to roofline)
        "roofline_fraction": am.flops_useful / chips / PEAK_FLOPS / step_s,
    }
    return rec, compiled


def lower_kv_cell(shape_name: str, multi_pod: bool):
    """Dry-run the distributed continuity KV service itself."""
    import repro.core.distributed as D
    from repro.core import continuity as ch

    mesh = build_mesh(multi_pod)
    chips = mesh.devices.size
    dp = chips // 16
    # production-scale service: 2^22 buckets (~42M slot capacity), 4096
    # requests per client device batch
    scfg = D.StoreConfig(
        table=ch.ContinuityConfig(num_buckets=1 << 22, ext_frac=0.0),
        num_shards=dp,
        axis_names=("pod", "data") if multi_pod else ("data",))
    table_s = jax.eval_shape(lambda: D.create_sharded(scfg, mesh))
    B = 4096 * dp
    keys_s = jax.ShapeDtypeStruct((B, 4), jnp.uint32)
    vals_s = jax.ShapeDtypeStruct((B, 4), jnp.uint32)
    ops_s = jax.ShapeDtypeStruct((B,), jnp.int32)
    t0 = time.time()
    with mesh:
        if shape_name == "kv_read":
            fn = D.make_lookup(scfg, mesh)
            mask_s = jax.ShapeDtypeStruct((B,), jnp.bool_)
            lowered = jax.jit(fn).lower(table_s, keys_s, mask_s)
        elif shape_name == "kv_read_level":
            # level-hashing-style 4-fetch lookup: the access-amplification
            # comparison measured as collective wire bytes at pod scale
            fn = D.make_lookup_multifetch(scfg, mesh, fetches=4)
            mask_s = jax.ShapeDtypeStruct((B,), jnp.bool_)
            lowered = jax.jit(fn).lower(table_s, keys_s, mask_s)
        else:
            fn = D.make_write(scfg, mesh)
            lowered = fn.lower(table_s, ops_s, keys_s, vals_s)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    colls = collective_bytes_weighted(compiled.as_text())
    coll_total = sum(v["wire_bytes"] for v in colls.values())
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    terms = {"compute_s": flops_dev / PEAK_FLOPS,
             "memory_s": bytes_dev / HBM_BW,
             "collective_s": coll_total / ICI_BW}
    rec = {
        "arch": "continuity-kv", "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "status": "ok", "compile_seconds": round(time.time() - t0, 1),
        "memory": {"argument_bytes_per_device": mem.argument_size_in_bytes,
                   "temp_bytes_per_device": mem.temp_size_in_bytes},
        "cost": {"flops_per_device": flops_dev,
                 "bytes_accessed_per_device": bytes_dev},
        "collectives": colls,
        "collective_bytes_per_device": coll_total,
        "roofline": {**terms, "dominant": max(terms, key=terms.get)},
    }
    return rec, compiled


def run_cell(arch, shape, multi_pod, outdir, force=False, overrides=None,
             tag=""):
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    name = f"{arch}_{shape}_{mesh_tag}{tag}.json"
    path = os.path.join(outdir, name)
    if os.path.exists(path) and not force:
        print(f"[skip-cached] {name}")
        return json.load(open(path))
    t0 = time.time()
    try:
        if arch == "continuity-kv":
            rec, _ = lower_kv_cell(shape, multi_pod)
        else:
            rec, _ = lower_cell(arch, shape, multi_pod, overrides)
    except Exception as e:  # a failure here is a bug in the system
        rec = {"arch": arch, "shape": shape, "mesh": mesh_tag,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    os.makedirs(outdir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    extra = ""
    if status == "ok":
        r = rec["roofline"]
        extra = (f" dom={r['dominant']} comp={r['compute_s']:.2e}s "
                 f"mem={r['memory_s']:.2e}s coll={r['collective_s']:.2e}s")
    print(f"[{status}] {name} ({time.time()-t0:.0f}s){extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    from repro.configs import ARCHS
    from repro.models.config import SHAPES

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
        cells += [("continuity-kv", "kv_read"), ("continuity-kv", "kv_write"),
                  ("continuity-kv", "kv_read_level")]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    for mp in meshes:
        for arch, shape in cells:
            run_cell(arch, shape, mp, args.out, force=args.force)


if __name__ == "__main__":
    main()
