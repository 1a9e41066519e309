"""Production training driver.

Runs real RL training end-to-end with any of the paper's loss types,
online or heterogeneous: at CPU scale with the arch's reduced (smoke)
config by default, or at its published width with ``--full-width`` (the
config ``repro.configs.get_config`` returns; random-init weights, the
built-in arithmetic task and tokenizer). At full width the learner uses
Adafactor: AdamW's two f32 moments do not fit Qwen3-1.7B on one 16 GB
TPU v5e. ``dryrun.py`` lowers the full configs on the production mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
      --loss gepo --steps 200 --mode hetero --max-delay 64

One TPU v5e at full width:
  PYTHONPATH=src python -m repro.launch.train --full-width --sft-steps 2 \
      --steps 3 --eval-every 1000

Multi-device (one unified ExecutionPlan drives SFT, RL learner and
samplers; on CPU export the host-device override first):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --mesh 4x2 --sampler-mesh 1x2
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.config import HeteroConfig, RLConfig, TrainConfig
from repro.configs import config_for
from repro.core.diagnostics import best_last_gap
from repro.data import ArithmeticTask, Tokenizer
from repro.data.tasks import EOS
from repro.hetero import HeteroRuntime, run_online
from repro.parallel import plan_from_flag
from repro.training import init_state, jit_sft_step, optimizer_of


def make_eval_fn(cfg, rl, task, tok, n_prompts=32, seed=1234):
    """Pass@1-style eval on held-out problems (greedy-ish sampling)."""
    from repro.data import score_rollouts
    from repro.sampling import generate
    eval_task = ArithmeticTask(max_operand=task.max_operand, ops=task.ops,
                               prompt_width=task.prompt_width, seed=seed)
    probs = eval_task.sample_batch(n_prompts)
    from repro.data.tasks import encode_prompts
    prompts = jnp.asarray(np.repeat(encode_prompts(tok, probs), 2, axis=0))
    key = jax.random.PRNGKey(seed)

    def eval_fn(params) -> float:
        roll = generate(cfg, rl, params, prompts, key,
                        vocab_limit=tok.vocab_size)
        rewards = score_rollouts(eval_task, tok, probs,
                                 np.asarray(roll["completions"]), 2)
        return float(rewards.mean())
    return eval_fn


def sft_warmstart(cfg, tc, task, tok, state, steps=400, batch=64, seed=0):
    """Supervised warm start (the paper RL-tunes a pretrained model)."""
    rng = np.random.default_rng(seed)
    step_fn = jit_sft_step(cfg, tc, optimizer=optimizer_of(state))
    width = task.prompt_width + 8
    for _ in range(steps):
        probs = task.sample_batch(batch)
        rows, masks = [], []
        for p in probs:
            ids = tok.encode(p.prompt) + tok.encode(p.answer) + [EOS]
            m = ([0.0] * (len(tok.encode(p.prompt)) - 1)
                 + [1.0] * (len(tok.encode(p.answer)) + 1))
            ids += [0] * (width - len(ids))
            m += [0.0] * (width - 1 - len(m))
            rows.append(ids[:width])
            masks.append(m[:width - 1])
        state, loss = step_fn(state, jnp.asarray(rows, jnp.int32),
                              jnp.asarray(masks, jnp.float32))
    return state, float(loss)


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--full-width", action="store_true",
                    help="the arch's published config instead of its "
                         "smoke-sized variant; the learner uses Adafactor")
    ap.add_argument("--loss", default="gepo")
    ap.add_argument("--mode", default="online",
                    choices=["online", "hetero"])
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--sft-steps", type=int, default=400)
    ap.add_argument("--group-size", type=int, default=8)
    ap.add_argument("--prompts", type=int, default=8)
    ap.add_argument("--max-delay", type=int, default=64)
    ap.add_argument("--delay-dist", default="lognormal")
    ap.add_argument("--num-samplers", type=int, default=4)
    ap.add_argument("--beta-kl", type=float, default=None)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--logprob-impl", default="fused",
                    choices=["fused", "pallas", "chunked", "naive"],
                    help="learner token-logprob backend (see "
                         "TrainConfig.logprob_impl)")
    ap.add_argument("--mesh", default="1x1",
                    help="learner mesh DxM (data×model), e.g. 2x2; needs "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         " on CPU")
    ap.add_argument("--sampler-mesh", default="1x1",
                    help="sampler-node mesh DxM (serve-mode tensor "
                         "parallel)")
    ap.add_argument("--paged-attn-impl", default=None,
                    choices=["auto", "pallas", "ref", "gather"],
                    help="sampler paged-decode backend for hetero A/B "
                         "sweeps (HeteroConfig.paged_attn_impl; default "
                         "keeps the arch's ModelConfig knob)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--out", default=None)
    return ap.parse_args(argv)


def train(args: argparse.Namespace):
    """SFT warm start, then RL as ``args`` say. Returns (summary, metrics
    history, learner node)."""
    cfg = config_for(args.arch, args.full_width)
    beta = args.beta_kl if args.beta_kl is not None else (
        0.0 if args.mode == "online" else 0.005)   # paper §4.1
    rl = RLConfig(loss_type=args.loss, group_size=args.group_size,
                  beta_kl=beta, max_new_tokens=6, temperature=1.0,
                  top_k=0, top_p=1.0)
    tok = Tokenizer()
    task = ArithmeticTask(max_operand=20, ops="+", prompt_width=6,
                          seed=args.seed)

    # one ExecutionPlan per role; the same plan drives SFT warm start,
    # the RL learner step and (via HeteroConfig) every sampler node
    learner_plan = plan_from_flag(args.mesh, "train")
    sampler_plan = plan_from_flag(args.sampler_mesh, "serve")
    print(f"[train] learner {learner_plan.describe()} | "
          f"samplers {sampler_plan.describe()}")

    key = jax.random.PRNGKey(args.seed)
    params = learner_plan.init_params(cfg, key)
    tc_sft = TrainConfig(learning_rate=1e-2, total_steps=args.sft_steps,
                         logprob_impl=args.logprob_impl, mesh=args.mesh)
    optimizer = "adafactor" if args.full_width else "adamw"
    state = init_state(cfg, tc_sft, params, optimizer=optimizer,
                       plan=learner_plan)
    del params                  # the state owns them; SFT donates it
    t0 = time.time()
    state, sft_loss = sft_warmstart(cfg, tc_sft, task, tok, state,
                                    steps=args.sft_steps, seed=args.seed)
    print(f"[train] SFT warm start done: loss={sft_loss:.3f} "
          f"({time.time()-t0:.0f}s)")

    tc = TrainConfig(learning_rate=args.lr, total_steps=args.steps,
                     logprob_impl=args.logprob_impl, mesh=args.mesh)
    state = state._replace(step=jnp.zeros((), jnp.int32))
    eval_fn = make_eval_fn(cfg, rl, task, tok)

    if args.mode == "online":
        hist, evals, learner = run_online(
            cfg, rl, tc, task, tok, state, num_steps=args.steps,
            prompts_per_batch=args.prompts, seed=args.seed,
            eval_fn=eval_fn, eval_every=args.eval_every,
            learner_plan=learner_plan, sampler_plan=sampler_plan)
    else:
        hcfg = HeteroConfig(num_samplers=args.num_samplers,
                            max_delay_steps=args.max_delay,
                            delay_distribution=args.delay_dist,
                            delay_median_s=300.0, seed=args.seed,
                            sampler_mesh=args.sampler_mesh,
                            paged_attn_impl=args.paged_attn_impl)
        rt = HeteroRuntime(cfg, rl, tc, hcfg, task, tok, state,
                           prompts_per_batch=args.prompts,
                           eval_fn=eval_fn, eval_every=args.eval_every)
        hist = rt.run(args.steps)
        evals = rt.eval_scores
        learner = rt.learner

    best, last, gap = best_last_gap(evals)
    summary = {
        "arch": args.arch, "loss": args.loss, "mode": args.mode,
        "steps": learner.step,
        "reward_mean_last20": float(np.mean(hist.get("reward_mean")[-20:])),
        "iw_var_mean": float(np.nanmean(hist.get("iw_var"))),
        "kl_mean": float(np.nanmean(hist.get("kl"))),
        "eval_best": best, "eval_last": last, "best_to_last_gap": gap,
        "staleness_mean": float(np.nanmean(hist.get("staleness"))),
        "wall_s": round(time.time() - t0, 1),
    }
    return summary, hist, learner


def main(argv: Optional[Sequence[str]] = None) -> None:
    enable_compile_cache()
    args = parse_args(argv)
    summary, _, _ = train(args)
    print("[train] " + json.dumps(summary, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)


if __name__ == "__main__":
    main()
