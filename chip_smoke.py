#!/usr/bin/env python3
"""Smoke run of the GEPO system's main path on a TPU, through the entry
points a user calls, at full model width with random-init weights.

  python3 chip_smoke.py              # one chip
  python3 chip_smoke.py --chips 4    # four chips: the sharded learner only

One chip, two phases:

- trainer: ``repro.launch.train`` at full ``qwen3-1.7b`` width — a couple
  of SFT warm-start steps, then 3 online GEPO steps of 8 prompts × group 8
  with the Pallas fused-logprob kernel. Loss, reward, ``iw_var`` and
  ``kl`` must be finite at every step.
- serve: the continuous engine of ``repro.launch.serve`` at the same width
  answers a few greedy requests with the in-place Pallas paged-attention
  kernels; its tokens must equal those of the ``ref`` backend.

``--chips 4`` runs one phase: a GEPO learner step of ``qwen3-8b`` (whose
bf16 params alone exceed one chip) on a 2×2 data×model mesh and on a 1×4
mesh, from the same params and batch; loss and grad norm must agree.

The script runs in one process and starts no other. It exits non-zero,
printing no result, when JAX finds no TPU or any phase fails. The times
it prints are from a smoke run (host clock, compilation included where
said), not a benchmark. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


def compile_seconds() -> float:
    """Seconds spent in XLA backend compiles so far, as the repo's compile
    listener (``repro.analysis.sentinel``) counts them into ``repro.obs``."""
    from repro import obs
    return obs.metrics.counter("xla_compile_seconds_total").value


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def memory(devices) -> str:
    """Per-device bytes in use now, and at the peak since the process
    started (the allocator keeps no per-phase peak)."""
    stats = [d.memory_stats() or {} for d in devices]
    return (f"bytes_in_use {[m.get('bytes_in_use') for m in stats]} "
            f"peak_bytes_in_use {[m.get('peak_bytes_in_use') for m in stats]}")


def require_finite(name: str, values) -> None:
    bad = [v for v in values if not math.isfinite(v)]
    if bad:
        raise AssertionError(f"{name} not finite: {list(values)}")


# ---------------------------------------------------------------- one chip


def trainer_phase(arch: str = "qwen3-1.7b",
                  full_width: bool = True) -> None:
    import jax

    from repro.kernels.ops import logprob_backend, logprob_tiles
    from repro.launch import train as train_cli
    from repro.training import optimizer_of

    argv = ["--arch", arch, "--sft-steps", "2", "--steps", "3",
            "--prompts", "8", "--group-size", "8",
            # forced: the kernel runs, or the step raises for a shape it
            # cannot tile; it never falls back to another backend
            "--logprob-impl", "pallas",
            "--eval-every", "1000000"]
    args = train_cli.parse_args(argv + (["--full-width"] if full_width
                                        else []))
    c0, t0 = compile_seconds(), time.perf_counter()
    summary, hist, learner = train_cli.train(args)
    wall = time.perf_counter() - t0
    cfg = learner.cfg
    log(f"trainer: {cfg.name} d_model={cfg.d_model} layers={cfg.num_layers}"
        f" vocab={cfg.padded_vocab} optimizer={optimizer_of(learner.state)}")

    rows, width = learner.batch_shape
    shape = (rows, width - 1, cfg.padded_vocab)
    backend = logprob_backend(shape, "pallas")
    tiles = logprob_tiles(rows * (width - 1), cfg.padded_vocab)
    log(f"trainer: learner logprob backend={backend} logits={shape} "
        f"tiles={tiles} interpret={jax.default_backend() != 'tpu'}")
    if backend != "pallas":
        raise AssertionError(f"learner logprob backend {backend!r}")

    steps = hist.get("step")
    cols = ("loss", "reward_mean", "iw_var", "kl", "grad_norm", "step_s")
    for i, step in enumerate(steps):
        vals = {k: float(hist.get(k)[i]) for k in cols}
        log(f"trainer: GEPO step {int(step)} " + " ".join(
            f"{k}={v!r}" for k, v in vals.items()))
    for k in ("loss", "reward_mean", "iw_var", "kl"):
        require_finite(k, hist.get(k))
    if len(steps) != 3:
        raise AssertionError(f"{len(steps)} GEPO steps, want 3")
    step_s = hist.get("step_s")
    log(f"trainer: smoke-run timings, not a benchmark: wall {wall!r} s, "
        f"XLA compiles {compile_seconds() - c0!r} s; learner step 1 "
        f"(compile included) {float(step_s[0])!r} s, later steps "
        f"{[float(s) for s in step_s[1:]]!r} s")
    log(f"trainer: {memory(jax.devices()[:1])}")


def serve_phase(arch: str = "qwen3-1.7b",
                full_width: bool = True) -> None:
    import dataclasses

    import jax

    from repro.data import Tokenizer
    from repro.launch import serve as serve_cli
    from repro.sampling import build_engine
    from repro.serving.api import SamplingParams

    argv = ["--arch", arch, "--engine", "continuous", "--batch", "4",
            "--slots", "4", "--max-new", "8", "--temperature", "0",
            "--top-k", "0", "--top-p", "1"]
    args = serve_cli.parse_args(argv + (["--full-width"] if full_width
                                        else []))
    dep = serve_cli.load(args)
    tok = Tokenizer()
    sp = SamplingParams.from_rl(dep.rl)
    _, reqs = serve_cli.make_requests(serve_cli.make_task(dep.serve.seed),
                                      tok, args.batch, sp)
    out = {}
    for impl in ("pallas", "ref"):
        cfg = dataclasses.replace(dep.cfg, paged_attn_impl=impl)
        engine = build_engine(cfg, dep.params, dep.serve, rl=dep.rl,
                              vocab_limit=tok.vocab_size, plan=dep.plan,
                              key=dep.key)
        c0, t0 = compile_seconds(), time.perf_counter()
        results = engine.generate(reqs, key=dep.key)
        first = time.perf_counter() - t0
        c1, t1 = compile_seconds(), time.perf_counter()
        engine.generate(reqs, key=dep.key)
        again = time.perf_counter() - t1
        out[impl] = {r.rid: r for r in results}
        n = sum(r.gen_count for r in results)
        log(f"serve[{impl}]: {len(results)} requests, {n} tokens; "
            f"smoke-run timings, not a benchmark: first call {first!r} s "
            f"(XLA compiles {c1 - c0!r} s), repeat {again!r} s")
    for rid, want in out["ref"].items():
        got = out["pallas"][rid]
        log(f"serve: request {rid} pallas={got.tokens.tolist()} "
            f"ref={want.tokens.tolist()}")
        if not np.array_equal(got.tokens, want.tokens):
            raise AssertionError(f"request {rid}: pallas tokens differ "
                                 f"from the ref backend")
        require_finite(f"request {rid} logps", got.logps)
    log(f"serve: {memory(jax.devices()[:1])}")


# ------------------------------------------------------------- four chips


def rollout_batch(cfg, rows: int, group: int, width: int, seed: int):
    """A GEPO batch from the arithmetic task: in every group half the
    completions are the right answer (reward 1), half a wrong one; the
    sampler log-probs are those of a uniform policy."""
    from repro.data import ArithmeticTask, Tokenizer
    from repro.data.tasks import EOS

    tok = Tokenizer()
    task = ArithmeticTask(max_operand=20, ops="+", prompt_width=6, seed=seed)
    tokens = np.zeros((rows, width), np.int32)
    mask = np.zeros((rows, width - 1), np.float32)
    rewards = np.zeros((rows,), np.float32)
    for i, prob in enumerate(task.sample_batch(rows // group)):
        prompt = tok.encode(prob.prompt)
        for j in range(group):
            right = j % 2 == 0
            answer = prob.answer if right else str(int(prob.answer) + 1)
            ids = prompt + tok.encode(answer) + [EOS]
            r = i * group + j
            tokens[r, :len(ids)] = ids
            mask[r, len(prompt) - 1:len(ids) - 1] = 1.0
            rewards[r] = float(right)
    sampler_lp = np.where(mask > 0, -math.log(cfg.padded_vocab),
                          0.0).astype(np.float32)
    return {"tokens": tokens, "mask": mask, "sampler_lp": sampler_lp,
            "rewards": rewards}


def mesh_phase(arch: str = "qwen3-8b",
               full_width: bool = True, meshes=("2x2", "1x4")) -> None:
    """One GEPO learner step of ``arch`` on each mesh, from the same
    params and batch. Returns nothing; raises when they disagree."""
    import jax
    import jax.numpy as jnp

    from repro.config import RLConfig, TrainConfig
    from repro.configs import config_for
    from repro.parallel import plan_from_flag
    from repro.training import init_state, jit_train_step

    cfg = config_for(arch, full_width)
    rl = RLConfig(loss_type="gepo", group_size=8, beta_kl=0.005)
    # chunked: GSPMD partitions it; a pallas_call has no partitioning rule
    tc = TrainConfig(learning_rate=1e-6, total_steps=2,
                     logprob_impl="chunked")
    batch = rollout_batch(cfg, rows=64, group=8, width=24, seed=0)
    key = jax.random.PRNGKey(0)
    log(f"mesh: {cfg.name} d_model={cfg.d_model} layers={cfg.num_layers} "
        f"params={cfg.param_count() / 1e9!r}e9 optimizer=adafactor "
        f"batch={batch['tokens'].shape}")
    seen = {}
    for spec in meshes:
        plan = plan_from_flag(spec, "train")
        devices = list(plan.mesh.devices.flat)
        params = plan.init_params(cfg, key)
        fingerprint = float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                                for x in jax.tree_util.tree_leaves(params)))
        state = init_state(cfg, tc, params, optimizer="adafactor",
                           plan=plan)
        del params
        step = jit_train_step(cfg, rl, tc, optimizer="adafactor", plan=plan)
        jb = plan.device_put_batch(cfg, {k: jnp.asarray(v)
                                         for k, v in batch.items()})
        c0, t0 = compile_seconds(), time.perf_counter()
        state, metrics = step(state, jb)
        first = {k: float(v) for k, v in metrics.items()}
        t1 = time.perf_counter()
        state, metrics = step(state, jb)
        jax.block_until_ready(state)
        t2 = time.perf_counter()
        seen[spec] = (fingerprint, first)
        log(f"mesh[{spec}]: {plan.describe()} param |sum|={fingerprint!r} "
            f"loss={first['loss']!r} grad_norm={first['grad_norm']!r} "
            f"iw_var={first['iw_var']!r} kl={first['kl']!r}")
        log(f"mesh[{spec}]: smoke-run timings, not a benchmark: step 1 "
            f"{t1 - t0!r} s (XLA compiles {compile_seconds() - c0!r} s), "
            f"step 2 {t2 - t1!r} s")
        log(f"mesh[{spec}]: {memory(devices)}")
        for k in ("loss", "grad_norm", "iw_var", "kl"):
            require_finite(f"{spec} {k}", [first[k]])
        del state, metrics, jb
        gc.collect()
    (fa, ma), (fb, mb) = (seen[s] for s in meshes)
    if not math.isclose(fa, fb, rel_tol=1e-6):
        raise AssertionError(f"params differ across meshes: {fa} vs {fb}")
    for k in ("loss", "grad_norm"):
        # bf16 params and activations, reduced in a different order
        if not math.isclose(ma[k], mb[k], rel_tol=2e-2, abs_tol=1e-3):
            raise AssertionError(f"{k}: {meshes[0]} {ma[k]} vs "
                                 f"{meshes[1]} {mb[k]}")
    log(f"mesh: {meshes[0]} and {meshes[1]} agree: loss {ma['loss']!r} vs "
        f"{mb['loss']!r}, grad_norm {ma['grad_norm']!r} vs "
        f"{mb['grad_norm']!r}")


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: trainer and serve phases on one chip; 4: the "
                         "sharded qwen3-8b learner step, 2x2 vs 1x4 mesh")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's backend is {backend!r}",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    sys.path.insert(0, SRC)
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache}, {entries} entries at start")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    from repro import obs
    from repro.analysis.sentinel import install_metrics_listener
    obs.metrics.enabled = True
    install_metrics_listener()

    phases = ([trainer_phase, serve_phase] if args.chips == 1
              else [mesh_phase])
    failed = []
    for phase in phases:
        log(f"--- {phase.__name__}")
        try:
            phase()
        except Exception:
            traceback.print_exc()
            failed.append(phase.__name__)
        gc.collect()
        live = jax.live_arrays()
        log(f"after {phase.__name__}: {len(live)} live arrays, "
            f"{sum(a.nbytes for a in live)} bytes; {memory(devices)}")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
