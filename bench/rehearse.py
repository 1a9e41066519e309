#!/usr/bin/env python3
"""Compile a cell's window programs for a described TPU v5e, with no chip
attached, and print what the compiler says they need.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <name> [--micro 8,16]

``learn`` cells: the program's GEPO train step at the cell's batch, once
per micro-batch size given (default: the configuration's), with its
``memory_analysis()`` (arguments, outputs, temporaries), then the plain
reference's gradient block at the widest block and its optimizer step,
split over the cell's chips as a run splits them. ``rollout``
cells: the engine's decode-chunk program at its widest block table and
the widest prefill. A compile that passes is not a chip run: it gives
bytes, never a time. The topology is ``v5e:2x2``; one-chip cells use its
first device.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def gib(n: int) -> str:
    return f"{n / 2 ** 30:.2f} GiB"


def report(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    args = m.argument_size_in_bytes
    out = m.output_size_in_bytes
    tmp = m.temp_size_in_bytes
    alias = m.alias_size_in_bytes
    print(f"{name}: arguments {gib(args)}, outputs {gib(out)} "
          f"(aliased {gib(alias)}), temporaries {gib(tmp)}; "
          f"peak about {gib(args + out - alias + tmp)}", flush=True)


def mesh_of(topo, shape):
    import numpy as np
    from jax.sharding import AxisType, Mesh
    n = shape[0] * shape[1]
    return Mesh(np.array(topo.devices[:n]).reshape(shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def with_sharding(avals, shardings):
    import jax
    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        avals, shardings)


def rehearse_learn(cell, topo, micros) -> None:
    import jax
    import jax.numpy as jnp

    from bench.kinds import learn as learn_kind
    from bench.lib import program
    from repro.models import abstract_params
    from repro.optim import adafactor_init
    from repro.parallel.plan import ExecutionPlan
    from repro.training import TrainState, train_step

    c, t = cell.config, cell.traffic
    cfg = program.model_config(c)
    shape = tuple(int(x) for x in c["learner"]["mesh"].split("x"))
    plan = ExecutionPlan(mesh=mesh_of(topo, shape), mode="train")
    rows, width = t["prompts"] * t["group_size"], t["width"]
    optimizer = c["learner"]["optimizer"]
    for mb in micros:
        cc = dict(c, learner=dict(c["learner"], micro_batch_rows=mb))
        rl, tc = learn_kind.settings(dataclasses.replace(cell, config=cc),
                                     rows)
        p = abstract_params(cfg)
        state = TrainState(params=p, opt=jax.eval_shape(adafactor_init, p),
                           step=jax.ShapeDtypeStruct((), jnp.int32))
        state_sh = plan.state_shardings(cfg, optimizer)
        batch = {"tokens": jax.ShapeDtypeStruct((rows, width), jnp.int32),
                 "mask": jax.ShapeDtypeStruct((rows, width - 1), jnp.float32),
                 "sampler_lp": jax.ShapeDtypeStruct((rows, width - 1),
                                                    jnp.float32),
                 "rewards": jax.ShapeDtypeStruct((rows,), jnp.float32)}
        batch_sh = plan.batch_shardings(cfg, batch)
        mb_con = plan.microbatch_constraint(cfg, tc.grad_accum)

        def step(s, b):
            return train_step(cfg, rl, tc, s, b, optimizer=optimizer,
                              mb_constraint=mb_con)

        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
        from repro.runtime_context import mesh_context
        with mesh_context(plan.mesh):
            compiled = fn.lower(with_sharding(state, state_sh),
                                with_sharding(batch, batch_sh)).compile()
        report(f"{cell.name} train step, micro-batch {mb} rows "
               f"(grad_accum {tc.grad_accum}, logprob "
               f"{tc.logprob_impl}, mesh {c['learner']['mesh']})", compiled)
        text = compiled.as_text()
        print(f"  tpu_custom_call in program: {'tpu_custom_call' in text}; "
              f"all-gather {text.count('all-gather')}, all-reduce "
              f"{text.count('all-reduce')}, reduce-scatter "
              f"{text.count('reduce-scatter')}", flush=True)


def rehearse_reference(cell, topo) -> None:
    import jax
    import jax.numpy as jnp

    from bench.lib import placement, program, reference, spec
    from bench.lib import weights as W

    c, t = cell.config, cell.traffic
    cfg = program.model_config(c)
    m = placement.mesh(cell.chips, topo.devices)
    whole = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def sds(name, shape, dt):
        sh = whole if m is None else placement.leaf_sharding(m, name, shape)
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    shapes = spec.family(c).leaf_shapes(c, cfg.padded_vocab)
    dt = jnp.dtype(c["torch_dtype"])
    wts = {n: sds(n, s, dt) for n, s in shapes.items()}
    acc = {n: sds(n, s, jnp.float32) for n, s in shapes.items()}
    vr, vc = ({n: sds(n, f(s), jnp.float32) for n, s in shapes.items()}
              for f in (lambda s: s[:-1] if len(s) >= 2 else s,
                        lambda s: s[:-2] + s[-1:] if len(s) >= 2 else (1,)))
    rows, width = 4, reference._width_bucket(t["width"] - 1)
    rep = whole if m is None else jax.sharding.NamedSharding(
        m, jax.sharding.PartitionSpec())
    row = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    compiled = reference._block_grad.lower(
        wts, acc, row((rows, width + 1), jnp.int32),
        row((rows, width), jnp.float32), row((rows, width), jnp.float32),
        *(row((rows,), jnp.float32) for _ in range(4)),
        citems=W.config_items(c), rl_items=tuple(sorted(t["rl"].items())),
        mm="f32", mesh=m).compile()
    report(f"{cell.name} reference gradient block ({rows} rows x {width}, "
           f"{cell.chips} chips)", compiled)
    compiled = reference._adafactor.lower(wts, acc, vr, vc, lr=1e-3,
                                          clip=1.0, mesh=m).compile()
    report(f"{cell.name} reference Adafactor step", compiled)


def rehearse_rollout(cell, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench.lib import program
    from repro.config import RLConfig, ServeConfig
    from repro.models import abstract_params
    from repro.sampling import continuous as cont
    from repro.sampling.paged_cache import init_paged_pool, pages_for

    c, t = cell.config, cell.traffic
    cfg = program.model_config(c)
    sp = t["sampling"]
    rl = RLConfig(temperature=sp["temperature"], top_k=sp["top_k"],
                  top_p=sp["top_p"])
    serve = ServeConfig(**t["serve"])
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    params = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                    abstract_params(cfg))
    pool = jax.eval_shape(lambda: init_paged_pool(
        cfg, serve.resolved_num_pages, serve.page_size))
    pool = jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), pool)
    n = serve.num_slots
    width = pages_for(serve.max_total_tokens, serve.page_size)
    args = (params, pool, sds((n, width), jnp.int32),
            sds((n, cfg.padded_vocab), jnp.float32), sds((n,), jnp.int32),
            sds((n,), jnp.bool_), sds((n, 2), jnp.uint32),
            sds((n,), jnp.int32), sds((n,), jnp.int32))
    compiled = cont._decode_chunk_jit.lower(
        cfg, rl, *args, vocab_limit=c["vocab_size"],
        sync_every=serve.sync_every, plan=None).compile()
    report(f"{cell.name} decode chunk ({n} slots, {width} pages of "
           f"{serve.page_size}, {serve.resolved_num_pages} pool pages, "
           f"paged_attn_impl {cfg.paged_attn_impl})", compiled)
    plen = t["prompt_len"]["high"]
    pw = cont._live_width(pages_for(plen, serve.page_size),
                          serve.pages_per_slot)
    compiled = cont._prefill_chunk_jit.lower(
        cfg, params, pool, sds((1, pw), jnp.int32), sds((1, plen), jnp.int32),
        sds((), jnp.int32), plan=None).compile()
    report(f"{cell.name} prefill ({plen} tokens)", compiled)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--micro", default="",
                    help="comma-separated micro-batch rows (learn cells)")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies

    import repro.kernels.ops as ops
    from bench.lib import spec

    # the program picks its TPU kernels by asking the backend; here the
    # backend is the CPU, and the compile is for the described chip
    ops.on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if cell.kind == "learn":
        micros = ([int(x) for x in args.micro.split(",")] if args.micro
                  else [cell.config["learner"]["micro_batch_rows"]])
        rehearse_learn(cell, topo, micros)
        rehearse_reference(cell, topo)
    else:
        rehearse_rollout(cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
