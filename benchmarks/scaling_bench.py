"""Data-parallel scaling of the sharded train step on forced host devices.

    PYTHONPATH=src python -m benchmarks.scaling_bench [--data 1,2,4]

For each data-axis size D a fresh subprocess forces
``--xla_force_host_platform_device_count=D`` (device count locks on first
jax init, so the parent never imports with the override), builds an
``ExecutionPlan`` on a (D, 1) mesh and times ``make_sharded_train_step``
over a fixed global batch. On one physical CPU all fake devices share a
core, so tokens/s is a *plumbing* benchmark (sharded-step dispatch +
collective overhead at D>1), not a speedup claim — the point is that the
same code path runs at every D and the overhead stays bounded. On real
multi-chip hardware the same harness measures true scaling.

CSV: scaling,D=<n>,tokens_per_s,step_ms
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List

SMOKE = os.environ.get("BENCH_SMOKE", "0") == "1"
FULL = os.environ.get("BENCH_FULL", "0") == "1"


def _worker(n_data: int, steps: int, batch: int, seq: int) -> None:
    """Runs inside the subprocess (XLA_FLAGS already set by the parent)."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.config import RLConfig, TrainConfig, ModelConfig, ATTN, MLP
    from repro.models import init_params
    from repro.parallel import (ExecutionPlan, make_mesh,
                                make_sharded_train_step)
    from repro.training import init_state

    cfg = ModelConfig(name="scaling-lm", family="dense", num_layers=2,
                      d_model=96, num_heads=4, num_kv_heads=2, d_ff=192,
                      vocab_size=64, block_pattern=(ATTN,),
                      ffn_pattern=(MLP,), dtype="float32",
                      attn_impl="naive", remat=False, rope_theta=1e4)
    rl = RLConfig(loss_type="gepo", group_size=4, beta_kl=0.0)
    tc = TrainConfig(learning_rate=1e-3, total_steps=steps + 1)
    mesh = make_mesh((n_data, 1), ("data", "model"))
    plan = ExecutionPlan(mesh=mesh, mode="train")

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    b = {
        "tokens": jax.random.randint(ks[0], (batch, seq), 0, 64),
        "mask": jnp.ones((batch, seq - 1)),
        "sampler_lp": -jnp.abs(jax.random.normal(ks[1], (batch, seq - 1))),
        "rewards": (jax.random.uniform(ks[2], (batch,)) > 0.5).astype(
            jnp.float32),
    }
    b = plan.device_put_batch(cfg, b)
    state = init_state(cfg, tc, init_params(cfg, ks[3]), plan=plan)
    step = make_sharded_train_step(cfg, rl, tc, plan)

    state, m = step(state, b)                      # compile + warmup
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, b)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    tokens = batch * (seq - 1) * steps
    print(json.dumps({"data": n_data, "tokens_per_s": tokens / dt,
                      "step_ms": 1e3 * dt / steps}))


def run(sizes=None, steps=None, batch=None, seq=None) -> List[str]:
    sizes = sizes or ([1, 2] if SMOKE else [1, 2, 4] + ([8] if FULL else []))
    steps = steps or (3 if SMOKE else 10)
    batch = batch or 16
    seq = seq or 17
    rows = ["table,setting,tokens_per_s,step_ms"]
    for d in sizes:
        # a virtual-device rehearsal on the CPU: never a second process
        # onto an accelerator the parent may hold
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={d}",
            PYTHONPATH=os.pathsep.join(
                [p for p in (os.environ.get("PYTHONPATH"),) if p]
                + [os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src"),
                   os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]))
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.scaling_bench", "--worker",
             str(d), "--steps", str(steps), "--batch", str(batch),
             "--seq", str(seq)],
            capture_output=True, text=True, env=env, timeout=420)
        if out.returncode != 0:
            raise RuntimeError(f"scaling worker D={d} failed:\n"
                               f"{out.stderr[-2000:]}")
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        rows.append(f"scaling,D={d},{rec['tokens_per_s']:.1f},"
                    f"{rec['step_ms']:.1f}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=0,
                    help="internal: run the timed loop at this data size")
    ap.add_argument("--data", default=None,
                    help="comma-separated data-axis sizes (driver mode)")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=17)
    args = ap.parse_args()
    if args.worker:
        _worker(args.worker, args.steps or 10, args.batch, args.seq)
        return
    sizes = ([int(s) for s in args.data.split(",")] if args.data else None)
    for r in run(sizes=sizes, steps=args.steps or None):
        print(r, flush=True)


if __name__ == "__main__":
    main()
