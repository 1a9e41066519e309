"""Cells of kind ``learn``: the GEPO learner step, as the launcher runs it.

Set-up builds one object, the program's compiled train step with its
state on the plan, and drives it from the seed through its first
``check_steps`` steps through the same call and feed as the window,
reading after step 1 the gradient as the optimizer got it (from the
Adafactor state) and after the last the weights' change. The window then
runs the same step on fresh batches for ``--seconds``, finishing the step
in flight, and the plain float32 reference follows the first steps once
the program's state is freed.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import placement, program, reference, spec, traffic, trace
from bench.lib import weights as W


def settings(cell, n_rows: int):
    from repro.config import RLConfig, TrainConfig
    c, t = cell.config, cell.traffic
    ln = c["learner"]
    rl = RLConfig(**t["rl"])
    tc = TrainConfig(grad_accum=n_rows // ln["micro_batch_rows"],
                     mesh=ln["mesh"], **ln["train"])
    return rl, tc


def _adafactor_grad_norms(fam, state, decay: float = 0.999
                          ) -> Dict[str, float]:
    """Each leaf's gradient norm as the optimizer got it at step 1, worked
    out from the Adafactor state: after one step the row statistic is
    (1 - decay) * mean over the last axis of g^2."""
    vr = fam.program_leaf_names(state.opt.vr)
    shapes = fam.program_leaf_names(state.params)
    out = {}
    for n, r in vr.items():
        cols = shapes[n].shape[-1] if shapes[n].ndim >= 2 else 1
        out[n] = math.sqrt(max(float(jnp.sum(r)) * cols / (1 - decay), 0.0))
    return out


def _change_norms(c, seed: int, v_pad: int, leaves: Dict[str, Any]
                  ) -> Dict[str, float]:
    """Norm of each leaf's change from the seed's initial weights, each
    initial leaf drawn again where the leaf lives."""
    @jax.jit
    def norm(now, init):
        return jnp.sqrt(jnp.sum(jnp.square(now.astype(jnp.float32)
                                           - init.astype(jnp.float32))))
    return {n: float(norm(a, W.make_leaf(c, seed, v_pad, n,
                                         placement.spread(a))))
            for n, a in leaves.items()}


def _gap(prog: Dict[str, float], ref: Dict[str, float],
         skip=frozenset()) -> Dict[str, float]:
    """Per leaf: the gap between the two norms over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    med = float(np.median([ref[n] for n in ref if n not in skip]))
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
            for n in ref if n not in skip}


def reference_readings(c, t, seed: int, v_pad: int, tc, mm: str = "f32",
                       half: bool = False, mesh=None, log=lambda s: None
                       ) -> Dict[str, Any]:
    """The reference's readings over the first ``check_steps`` batches:
    each step's loss, each leaf's gradient norm as the optimizer got it at
    step 1, and each leaf's change after the last step. ``mm="fp8"`` is
    the control; ``half`` is the fault of half the batch left out, the
    mean taken over the rest (its first half: whole groups). ``mesh``
    (``bench.lib.placement``) splits the reference's state over the
    cell's chips."""
    batches = [traffic.learn_batch(t, c["vocab_size"], seed, i)
               for i in range(t["check_steps"])]
    micro = c["learner"]["micro_batch_rows"]
    if half:
        n = len(batches[0]["rewards"]) // 2
        batches = [{k: a[:n] for k, a in b.items()} for b in batches]
        micro = min(micro, n)
    ref = reference.learn_steps(c, t["rl"], W.make(c, seed, v_pad, mesh),
                                batches, lr=tc.learning_rate,
                                clip=tc.grad_clip, micro_rows=micro, mm=mm,
                                mesh=mesh, log=log)
    ref["change_norms"] = _change_norms(c, seed, v_pad, ref.pop("weights"))
    return ref


def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            log=lambda s: None) -> Dict[str, float]:
    """The numbers compared: the largest gap of a step's loss, and of a
    leaf's gradient and change norms (see ``_gap``). Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    g_ref = ref["grad_norms"]
    med = float(np.median(list(g_ref.values())))
    still = frozenset(n for n, g in g_ref.items() if g < 1e-3 * med)
    grad_gaps = _gap(prog["grad_norms"], g_ref)
    change_gaps = _gap(prog["change_norms"], ref["change_norms"], skip=still)
    for n in sorted(g_ref):
        log(f"leaf {n}: grad {prog['grad_norms'][n]!r} vs {g_ref[n]!r}; "
            f"change {prog['change_norms'][n]!r} vs "
            f"{ref['change_norms'][n]!r}")
    log(f"losses {prog['losses']!r} vs {ref['losses']!r}; "
        f"left out of the change: {sorted(still)}")
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                   ref["losses"])),
        "grad_gap": max(grad_gaps.values()),
        "update_gap": max(change_gaps.values()),
    }


def run(cell, args, env) -> Dict[str, Any]:
    from repro.parallel import plan_from_flag
    from repro.training import init_state, jit_train_step

    c, t = cell.config, cell.traffic
    log = env.log
    cfg = program.model_config(c)
    program.check_layout(cfg, c)
    fam = spec.family(c)
    v = c["vocab_size"]
    n_rows = t["prompts"] * t["group_size"]
    rl, tc = settings(cell, n_rows)
    optimizer = c["learner"]["optimizer"]
    k_check = t["check_steps"]
    plan = plan_from_flag(tc.mesh, "train")
    multi = plan.num_devices > 1
    seed = args.seed

    params = program.make_program_weights(cfg, c, seed,
                                          plan if multi else None)
    state = init_state(cfg, tc, params, optimizer=optimizer, plan=plan)
    del params
    step = jit_train_step(cfg, rl, tc, optimizer=optimizer, plan=plan)

    def feed(i: int):
        with trace.span("build_batch"):
            hb = traffic.learn_batch(t, v, seed, i)
        with trace.span("device_put"):
            db = plan.device_put_batch(cfg, {
                k: jnp.asarray(hb[k])
                for k in ("tokens", "mask", "sampler_lp", "rewards")})
        return hb, db

    # --- the first steps, through the window's own call and feed --------
    prog_losses: List[float] = []
    grad_prog = None
    for i in range(k_check):
        _, db = feed(i)
        state, m = step(state, db)
        prog_losses.append(float(m["loss"]))
        if i == 0:
            grad_prog = _adafactor_grad_norms(fam, state)
        log(f"learn: setup step {i + 1} loss {prog_losses[-1]!r} "
            f"grad_norm {float(m['grad_norm'])!r}")
    change_prog = _change_norms(c, seed, cfg.padded_vocab,
                                fam.program_leaf_names(state.params))
    env.setup_done()

    # --- the window ----------------------------------------------------
    window_tokens, window_lengths, losses = 0, [], []
    with env.window() as win:
        i, prev = k_check, None
        while True:
            hb, db = feed(i)
            with trace.span("dispatch"):
                state, m = step(state, db)
            if prev is not None:
                with trace.span("wait"):
                    losses.append(float(prev["loss"]))
            prev = m
            window_tokens += traffic.learn_tokens(hb)
            window_lengths.append(hb["lengths"].tolist())
            i += 1
            if win.elapsed() >= args.seconds:
                break
        with trace.span("wait"):
            losses.append(float(prev["loss"]))
            jax.block_until_ready(state)
    env.read_memory()
    del state, prev, m, db
    gc.collect()

    # --- the reference ---------------------------------------------------
    t_ref = time.perf_counter()
    ref = reference_readings(c, t, seed, cfg.padded_vocab, tc,
                             mesh=placement.mesh(cell.chips), log=log)
    log(f"learn: reference took {time.perf_counter() - t_ref!r} s")
    prog = {"losses": prog_losses, "grad_norms": grad_prog,
            "change_norms": change_prog}
    checks = compare(prog, ref, log)
    failed = sum(1 for x in losses if not math.isfinite(x))
    return {
        "checks": checks,
        "attempted": len(losses), "failed": failed,
        "e2e": {"learner_tokens_per_s": window_tokens / win.seconds},
        "record": {"kind": "learn", "window_s": win.seconds,
                   "steps": len(losses), "lengths": window_lengths,
                   "micro_batch_rows": c["learner"]["micro_batch_rows"],
                   "grad_accum": tc.grad_accum,
                   "logit_tokens": c["learner"]["micro_batch_rows"]
                   * (t["width"] - 1),
                   "padded_vocab": cfg.padded_vocab},
    }
