import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST run before any jax import (jax locks the device
# count at first init). Everything else follows.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
(No `from __future__` here: the XLA_FLAGS lines must stay first.)

For each cell this script:
  1. builds the production mesh (16,16) or (2,16,16);
  2. resolves logical-axis shardings for params / optimizer state / batch
     / caches;
  3. jits the right step (train_step / prefill / serve_step) with explicit
     in/out shardings and ``.lower().compile()``s it with
     ShapeDtypeStruct inputs — no arrays are ever allocated;
  4. records memory_analysis(), cost_analysis(), HLO collective bytes
     (repro.launch.hlo_analysis) and the three roofline terms.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.json
  python -m repro.launch.dryrun --arch dbrx-132b --shape train_4k --multi-pod

Exit code 0 iff every attempted cell compiled.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp


def _top_collectives(hlo: str, n: int = 10):
    """Aggregate wire bytes per (kind, shape, group) — the §Perf profile."""
    import re
    from collections import defaultdict
    from repro.launch import hlo_analysis as ha
    comps = ha._split_computations(hlo)
    entry = ha._entry_name(hlo)
    mult = ha._multiplicities(comps, entry)
    agg = defaultdict(float)
    for name, lines in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        for ln in lines:
            opm = re.match(r"(?:ROOT\s+)?%?[\w\.\-]+\s*=\s*(\([^=]*?\)|\S+)"
                           r"\s+(all-gather|all-reduce|reduce-scatter|"
                           r"all-to-all|collective-permute)", ln)
            if not opm:
                continue
            kind = opm.group(2)
            rb = ha.shape_bytes(opm.group(1))
            g = ha._group_size(ln)
            agg[(kind, opm.group(1)[:48], g)] += m * ha._wire_bytes(kind, rb, g)
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [{"kind": k, "shape": s, "group": g, "gib": round(b / 2**30, 2)}
            for (k, s, g), b in top]


def _cell(arch: str, shape_name: str, multi_pod: bool,
          rules_name: str = "default", attn_impl: str = "flash_xla",
          grad_accum: int = 1, diag: bool = False,
          remat: str = None, param_dtype: str = None) -> dict:
    from repro import configs, sharding
    from repro.configs.base import shape_applicable
    from repro.launch import hlo_analysis as ha
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
    from repro.models import model as M
    from repro.optim import adamw
    from repro.train import steps

    cfg = configs.get(arch)
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    if param_dtype:
        cfg = dataclasses.replace(cfg, param_dtype=param_dtype)
    shape = configs.get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = _rules(rules_name)
    t0 = time.time()

    import math
    shapes_p, axes_p = M.param_shapes(cfg)
    n_params = sum(math.prod(s.shape) for s in jax.tree.leaves(shapes_p))
    specs = M.input_specs(cfg, shape)
    baxes = M.batch_axes(cfg, shape)
    batch_sh = sharding.tree_shardings(baxes, specs, mesh, rules)
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())

    tc = steps.TrainConfig(attn_impl=attn_impl, grad_accum=grad_accum)
    if shape.kind == "train":
        state_shapes = steps.TrainState.shapes(shapes_p, use_ef=False)
        state_axes = steps.TrainState.axes(axes_p, use_ef=False)
        state_sh = sharding.tree_shardings(state_axes, state_shapes, mesh,
                                           rules)
        fn = steps.make_train_step(cfg, tc)

        def wrapped(state, batch):
            with sharding.use_mesh(mesh, rules):
                return fn(state, batch)

        jfn = jax.jit(wrapped, in_shardings=(state_sh, batch_sh),
                      out_shardings=(state_sh, None))
        lowered = jfn.lower(state_shapes, specs)
    elif shape.kind == "prefill":
        param_sh = sharding.tree_shardings(axes_p, shapes_p, mesh, rules)
        cshapes, caxes = M.cache_shapes(cfg, shape.global_batch,
                                        shape.seq_len)
        cache_sh = sharding.tree_shardings(caxes, cshapes, mesh, rules)
        fn = steps.make_prefill(cfg, max_len=shape.seq_len,
                                attn_impl=attn_impl)

        def wrapped(params, batch):
            with sharding.use_mesh(mesh, rules):
                return fn(params, batch)

        jfn = jax.jit(wrapped, in_shardings=(param_sh, batch_sh),
                      out_shardings=(None, cache_sh))
        lowered = jfn.lower(shapes_p, specs)
    else:  # decode
        param_sh = sharding.tree_shardings(axes_p, shapes_p, mesh, rules)
        cshapes, caxes = M.cache_shapes(cfg, shape.global_batch,
                                        shape.seq_len)
        cache_sh = sharding.tree_shardings(caxes, cshapes, mesh, rules)
        fn = steps.make_serve_step(cfg)

        def wrapped(params, cache, batch):
            with sharding.use_mesh(mesh, rules):
                return fn(params, cache, batch)

        # donate the cache: decode_32k caches are GB-scale; without
        # donation the updated cache double-counts in live memory
        jfn = jax.jit(wrapped, in_shardings=(param_sh, cache_sh, batch_sh),
                      out_shardings=(None, cache_sh), donate_argnums=(1,))
        lowered = jfn.lower(shapes_p, cshapes, specs)

    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):     # one-dict-per-device on old jax
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = ha.collective_bytes(hlo)

    n_dev = mesh.size
    # XLA's cost_analysis counts while bodies once (no trip multiplication)
    # — scan-stacked layers would be ~n_layers x under-reported. dot_flops
    # re-counts matmuls with trip accounting; take the max of both.
    flops_xla = float(cost.get("flops", 0.0))
    flops_dots = ha.dot_flops(hlo)
    flops_dev = max(flops_xla, flops_dots)
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    rl = ha.roofline(flops_dev, bytes_dev, coll.total_bytes,
                     ha.hardware(PRODUCTION_DEVICE_KIND))

    # MODEL_FLOPS: 6 N D (train) / 2 N D (inference), N = active params
    n_active = _active_params(cfg, n_params)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    model_flops = (6 if shape.kind == "train" else 2) * n_active * tokens
    hlo_flops_total = flops_dev * n_dev

    out = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": "multi_pod" if multi_pod else "single_pod",
        "rules": rules_name,
        "n_devices": n_dev,
        "compile_s": round(t_compile, 1),
        "n_params": n_params,
        "n_active_params": n_active,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "cost": {"flops_per_device": flops_dev,
                 "flops_per_device_xla": flops_xla,
                 "flops_per_device_dots": flops_dots,
                 "hbm_bytes_per_device": bytes_dev},
        "collectives": {"bytes_by_kind": coll.bytes_by_kind,
                        "count_by_kind": coll.count_by_kind,
                        "total_bytes_per_device": coll.total_bytes},
        "roofline": {
            "compute_s": rl.compute_s, "memory_s": rl.memory_s,
            "collective_s": rl.collective_s, "dominant": rl.dominant,
            "bound_s": rl.bound_s,
        },
        "model_flops": {
            "model_flops_total": model_flops,
            "hlo_flops_total": hlo_flops_total,
            "useful_ratio": (model_flops / hlo_flops_total
                             if hlo_flops_total else 0.0),
        },
    }
    if diag:
        out["top_collectives"] = _top_collectives(hlo)
    return out


def _active_params(cfg, n_params: int) -> int:
    """Active parameters per token (MoE: only top_k experts count)."""
    if cfg.moe is None:
        return n_params
    # expert weights: 3 matrices per layer (wi, wg, wo) x experts
    d_ff = cfg.moe.d_ff_expert or cfg.d_ff
    per_expert = 3 * cfg.d_model * d_ff
    expert_total = cfg.n_layers * cfg.moe.n_experts * per_expert
    expert_active = cfg.n_layers * cfg.moe.top_k * per_expert
    return n_params - expert_total + expert_active


def _rules(name: str):
    from repro import sharding
    if name == "default":
        return sharding.ShardingRules()
    if name == "pure_dp":              # batch over EVERY axis; no TP at all
        return sharding.ShardingRules().replace(
            batch=("pod", "data", "model"), embed=None, mlp=None,
            heads=None, kv_heads=None, vocab=None, experts=None,
            kv_seq=None)
    if name == "dp_fsdp":              # batch over all axes + FSDP weights
        return sharding.ShardingRules().replace(
            batch=("pod", "data", "model"), embed="data", mlp="model",
            heads=None, kv_heads=None, vocab="model", experts=None,
            kv_seq=None)
    if name == "no_fsdp":              # embed replicated (pure TP + DP)
        return sharding.ShardingRules().replace(embed=None)
    if name == "seq_data":             # decode cache sharded on data axis
        return sharding.ShardingRules().replace(kv_seq="data")
    if name == "fsdp_model":           # embed sharded on model axis instead
        return sharding.ShardingRules().replace(embed="model", mlp="data",
                                                heads="data", kv_heads="data",
                                                vocab="data", experts="data")
    raise KeyError(name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rules", default="default")
    ap.add_argument("--attn-impl", default="flash_xla")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--remat", default=None,
                    choices=[None, "full", "dots", "attn", "none"])
    ap.add_argument("--param-dtype", default=None,
                    choices=[None, "float32", "bfloat16"])
    ap.add_argument("--diag", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from repro import configs

    cells = []
    if args.all:
        for a in configs.ARCH_NAMES:
            for s in configs.SHAPES:
                cells.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    results = []
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch} x {shape} x {'multi' if mp else 'single'}"
            try:
                r = _cell(arch, shape, mp, rules_name=args.rules,
                          attn_impl=args.attn_impl, grad_accum=args.accum,
                          diag=args.diag, remat=args.remat,
                          param_dtype=args.param_dtype)
            except Exception as e:
                traceback.print_exc()
                r = {"arch": arch, "shape": shape, "status": "error",
                     "mesh": "multi_pod" if mp else "single_pod",
                     "error": f"{type(e).__name__}: {e}"}
                failures += 1
            results.append(r)
            status = r["status"]
            extra = ""
            if status == "ok":
                rl = r["roofline"]
                extra = (f" dominant={rl['dominant']}"
                         f" bound={rl['bound_s']:.4f}s"
                         f" compile={r['compile_s']}s")
            elif status == "skipped":
                extra = f" ({r['reason'][:60]})"
            print(f"[dryrun] {tag}: {status}{extra}", flush=True)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[dryrun] wrote {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
