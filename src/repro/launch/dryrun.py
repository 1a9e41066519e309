import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture × input shape × mesh)
combination lowers + compiles with coherent sharding, and extract the
memory/cost/collective numbers feeding EXPERIMENTS.md §Dry-run/§Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      [--arch all|<id>] [--shape all|<name>] [--mesh single|multi|both] \
      [--out results/dryrun] [--list]

One real CPU device backs 512 placeholder devices (the XLA_FLAGS line
above MUST precede any jax import — device count locks on first init).
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (INPUT_SHAPES, RLConfig, SHAPES_BY_NAME,
                          ShapeConfig, TrainConfig)
from repro.configs import ALL, ARCHS, get_config, supports_shape
from repro.launch import step_fns as sf
from repro.launch.costmodel import bytes_estimate, flops_estimate
from repro.parallel import ExecutionPlan, data_axes, make_production_mesh
from repro.parallel.mesh import PRODUCTION_DEVICE_KIND
from repro.parallel.axes import act_sharding_for
from repro.launch.roofline import (entry_io_bytes, model_flops,
                                   normalize_cost_analysis,
                                   parse_collective_bytes,
                                   parse_collectives_loop_aware, roofline)


def _mode_for(shape: ShapeConfig) -> str:
    if shape.kind == "train":
        return "train"
    return "long" if shape.name == "long_500k" else "serve"


def _tree_bytes(tree: Any) -> int:
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


def lower_combo(arch: str, shape_name: str, mesh, *,
                rl: Optional[RLConfig] = None,
                optimized: bool = False,
                verbose: bool = True) -> Dict[str, Any]:
    """Lower + compile one (arch, shape) on a mesh; return the §Dry-run /
    §Roofline record. ``optimized`` applies the beyond-baseline §Perf
    configuration (shard_map expert-parallel MoE)."""
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mode = _mode_for(shape)
    n_dev = mesh.devices.size
    rl = rl or RLConfig(group_size=8)
    dp_prod = 1
    for ax in data_axes(mesh):
        dp_prod *= mesh.shape[ax]
    # micro-batches must still cover the data axes
    accum = max(1, min(sf.grad_accum_for(cfg),
                       shape.global_batch // dp_prod))
    tc = TrainConfig(grad_accum=accum)

    pmode = mode                    # parameter-sharding mode
    if optimized and mode == "train" and not cfg.num_experts:
        pmode = "train_fsdp"        # §Perf H-A3: pure ZeRO-3, no TP
        tc = TrainConfig(grad_accum=1)
    # the same ExecutionPlan type the runtime executes with — the dry-run
    # only *lowers* against its sharding trees instead of re-deriving them
    plan = ExecutionPlan(mesh=mesh, mode=pmode)
    act = act_sharding_for(pmode, mesh)
    cfg = dataclasses.replace(cfg, act_sharding=act)
    if optimized and shape.kind == "decode" and "local" in cfg.block_pattern:
        # §Perf H-G1: ring-buffer KV for sliding-window layers
        cfg = dataclasses.replace(cfg, local_ring_kv=True)
    if optimized and cfg.num_experts and shape.kind in ("train", "prefill"):
        # EP MoE only where the token count is large; decode steps route
        # B tokens — the GSPMD path is already cheap there (measured:
        # EP at long_500k replicates the 500k-token dispatch, 18 GiB).
        cfg = dataclasses.replace(
            cfg, moe_ep=("train" if mode == "train" else "serve"),
            ep_dp_axes=data_axes(mesh))

    t0 = time.time()
    from repro.runtime_context import mesh_context
    with mesh_context(mesh):
        if mode == "train":
            step = sf.make_train_fn(cfg, rl, tc, plan=plan)
            state = sf.abstract_state(cfg)
            batch = sf.abstract_batch(cfg, shape)
            in_sh = (plan.state_shardings(cfg, sf.optimizer_for(cfg)),
                     plan.batch_shardings(cfg, batch))
            out_sh = (in_sh[0], None)
            lowered = jax.jit(step, in_shardings=in_sh,
                              out_shardings=out_sh).lower(state, batch)
        elif mode in ("serve", "long") and shape.kind == "prefill":
            step = sf.make_prefill_fn(cfg, shape.seq_len)
            params = sf.abstract_params(cfg)
            batch = {k: v for k, v in sf.abstract_batch(cfg, shape).items()
                     if k in ("tokens", "frames", "image_embeds")}
            cache = sf.abstract_cache(cfg, shape.global_batch,
                                      shape.seq_len)
            dp = data_axes(mesh)
            in_sh = (plan.param_shardings(cfg),
                     plan.batch_shardings(cfg, batch))
            out_sh = (NamedSharding(mesh, P(dp)),
                      plan.cache_shardings(cfg, cache))
            lowered = jax.jit(step, in_shardings=in_sh,
                              out_shardings=out_sh).lower(params, batch)
        else:                                        # decode
            step = sf.make_decode_fn(cfg)
            params = sf.abstract_params(cfg)
            cache = sf.abstract_cache(cfg, shape.global_batch,
                                      shape.seq_len)
            token = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            dp = data_axes(mesh)
            tok_spec = P() if mode == "long" else P(dp)
            csh = plan.cache_shardings(cfg, cache)
            in_sh = (plan.param_shardings(cfg), csh,
                     NamedSharding(mesh, tok_spec), NamedSharding(mesh, P()))
            out_sh = (NamedSharding(mesh, tok_spec), csh)
            lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=(1,)).lower(params, cache,
                                                         token, pos)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = normalize_cost_analysis(compiled.cost_analysis())
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    mem = compiled.memory_analysis()
    mem_rec = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            mem_rec[attr] = int(getattr(mem, attr, 0) or 0)
    hlo = compiled.as_text()
    coll = parse_collectives_loop_aware(hlo)
    coll_once = parse_collective_bytes(hlo)
    coll_total = float(sum(coll.values()))
    arg_b, out_b = entry_io_bytes(hlo)

    # compute/memory terms from the analytic cost model (cost_analysis
    # counts while bodies once — see costmodel.py docstring); collective
    # term from the loop-aware HLO parse.
    flops_impl = flops_estimate(cfg, shape) / n_dev
    flops_ideal = flops_estimate(cfg, shape, ideal=True) / n_dev
    byt = bytes_estimate(cfg, shape, n_dev,
                         optimizer=sf.optimizer_for(cfg))
    terms = roofline(flops_impl, byt["total"], coll_total,
                     PRODUCTION_DEVICE_KIND)
    mflops = model_flops(cfg, shape)
    record = {
        "arch": arch, "shape": shape_name, "mode": mode,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "devices": int(n_dev),
        "flops_per_dev": flops_impl,
        "flops_per_dev_ideal": flops_ideal,
        "bytes_per_dev": byt["total"],
        "bytes_breakdown": {k: v for k, v in byt.items() if k != "total"},
        "collective_bytes_per_dev": coll_total,
        "collectives": {k: int(v) for k, v in coll.items()},
        "collectives_body_once": {k: int(v) for k, v in coll_once.items()},
        "raw_cost_analysis": {"flops": raw_flops, "bytes": raw_bytes},
        "memory_analysis": mem_rec,
        "roofline": terms,
        "model_flops_global": mflops,
        "model_flops_per_dev": mflops / n_dev,
        "useful_flops_frac": (mflops / n_dev) / flops_impl
        if flops_impl else None,
        "entry_arg_bytes_per_dev": arg_b,
        "entry_out_bytes_per_dev": out_b,
        "hbm_fit_16g": (arg_b + mem_rec.get("temp_size_in_bytes", 0)
                        ) / 2**30 < 16.0 if mem_rec else None,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    if verbose:
        ma = mem_rec.get("argument_size_in_bytes", 0)
        mt = mem_rec.get("temp_size_in_bytes", 0)
        print(f"[dryrun] {arch} × {shape_name} × {record['mesh']}: "
              f"COMPILED in {t_compile:.1f}s | "
              f"args={ma/2**30:.2f}GiB temp={mt/2**30:.2f}GiB "
              f"fit16G={record['hbm_fit_16g']} | "
              f"flops/dev={flops_impl:.3e} bytes/dev={byt['total']:.3e} "
              f"coll/dev={coll_total:.3e} -> {terms['bottleneck']}",
              flush=True)
        print(f"         memory_analysis: {mem_rec}")
        print(f"         cost_analysis(raw): flops={raw_flops:.4e} "
              f"bytes={raw_bytes:.4e} | useful_frac="
              f"{record['useful_flops_frac']:.3f}")
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--include-paper-archs", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply §Perf beyond-baseline config (EP MoE)")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    archs = (list(ALL) if args.include_paper_archs else list(ARCHS)) \
        if args.arch == "all" else [args.arch]
    shapes = [s.name for s in INPUT_SHAPES] if args.shape == "all" \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    combos = [(a, s, m) for a in archs for s in shapes for m in meshes]
    if args.list:
        for a, s, m in combos:
            sup = supports_shape(a, s)
            print(f"{a} × {s} × {'2x16x16' if m else '16x16'}"
                  f"{'' if sup else '   [SKIP: sub-quadratic gate]'}")
        return

    os.makedirs(args.out, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for a, s, m in combos:
        mesh_name = "2x16x16" if m else "16x16"
        if not supports_shape(a, s):
            print(f"[dryrun] {a} × {s} × {mesh_name}: SKIP "
                  f"(full-attention arch, no sub-quadratic variant — "
                  f"see DESIGN.md §Arch-applicability)", flush=True)
            n_skip += 1
            continue
        mesh = make_production_mesh(multi_pod=m)
        try:
            rec = lower_combo(a, s, mesh, optimized=args.optimized)
            suffix = "__opt" if args.optimized else ""
            fn = os.path.join(args.out,
                              f"{a}__{s}__{mesh_name}{suffix}.json")
            with open(fn, "w") as f:
                json.dump(rec, f, indent=1)
            n_ok += 1
        except Exception:
            print(f"[dryrun] {a} × {s} × {mesh_name}: FAILED", flush=True)
            traceback.print_exc()
            n_fail += 1
    print(f"[dryrun] done: {n_ok} compiled, {n_skip} skipped, "
          f"{n_fail} failed", flush=True)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
