"""Cells of kind ``rollout``: closed-loop group rollouts, as a sampler node
runs them. Each batch is ``prompts x group_size`` requests through
``ContinuousEngine.generate``; the next batch starts when all of them
finish. Set-up builds the engine and runs one batch of the same sizes,
which compiles (or loads) every prefill and decode program the window
uses. After the window the plain float32 reference reads, over a sample
of finished requests drawn from the seed with the longest among them,
the log-prob of every served token and how far each served token's
Gumbel-perturbed logit lies below the best one: the engine draws token
``t`` of request ``rid`` as argmax(logits + Gumbel(fold_in(fold_in(key,
rid), t))), so at temperature 1 a served token must be the reference's
best under the same noise, up to rounding.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from bench.lib import placement, program, reference, traffic, trace
from bench.lib import weights as W


def engine_key(seed: int, batch: int) -> jax.Array:
    return jax.random.fold_in(W.base_key(seed), 1_000_003 + batch)


def draw_keys(key: jax.Array, rid: int, n: int) -> np.ndarray:
    """Key data of draws 0..n-1 of request ``rid``: (n, 2) uint32."""
    rk = jax.random.fold_in(key, rid)
    return np.asarray(jax.vmap(lambda i: jax.random.fold_in(rk, i))(
        np.arange(n, dtype=np.uint32)))


def check_rows(c: Dict[str, Any], served: List[Dict[str, Any]], width: int
               ) -> Dict[str, np.ndarray]:
    """Rows for the reference: prompt + served tokens padded to
    ``width + 1``; at each position the draw's key and whether a served
    token was drawn there."""
    n = len(served)
    tokens = np.zeros((n, width + 1), np.int32)
    keys = np.zeros((n, width, 2), np.uint32)
    valid = np.zeros((n, width), bool)
    lps = np.zeros((n, width), np.float32)
    for i, s in enumerate(served):
        p, g = s["prompt"], s["tokens"]
        row = np.concatenate([p, g])
        tokens[i, :row.size] = row
        a = p.size - 1                     # position whose logits drew g[0]
        keys[i, a:a + g.size] = draw_keys(s["key"], s["rid"], g.size)
        valid[i, a:a + g.size] = True
        lps[i, a:a + g.size] = s["logps"]
    return {"tokens": tokens, "keys": keys, "valid": valid, "logps": lps}


def reference_readings(c: Dict[str, Any], seed: int, v_pad: int,
                       rows: Dict[str, np.ndarray], mm: str = "f32",
                       mesh=None) -> Dict[str, np.ndarray]:
    """Per position of the check rows, under the reference computed in
    ``mm``: the served token's log-prob, the reference's best perturbed
    token, and the served token's gap below it. With ``mm="fp8"`` (the
    control) the f32 reference also reads the gap of the token the
    control puts first. ``mesh`` (``bench.lib.placement``) splits the
    weights over the cell's chips."""
    wts = W.make(c, seed, v_pad, mesh)
    served = rows["tokens"][:, 1:]
    lp, best, gap = reference.served_readings(
        c, wts, rows["tokens"], rows["keys"], served, rows["valid"], mm=mm,
        mesh=mesh)
    out = {"logp": lp, "best": best, "gap": gap}
    if mm != "f32":
        _, _, out["gap_of_best"] = reference.served_readings(
            c, wts, rows["tokens"], rows["keys"], best, rows["valid"],
            mesh=mesh)
    return out


def compare(rows: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
            ) -> Dict[str, float]:
    v = rows["valid"]
    return {"logp_gap": float(np.max(np.abs(rows["logps"] - ref["logp"])[v])),
            "token_gap": float(np.max(ref["gap"][v]))}


def warm_budgets(reqs: List[Dict[str, Any]], serve) -> List[Dict[str, Any]]:
    """The warm-up batch: the window's prompts (every prefill shape), one
    token each, except the group of the shortest prompt, which decodes
    from that prompt up past half of ``max_total_tokens``: the engine's
    block table then grows through every power-of-two width a window
    batch can use, and each decode program is compiled (or loaded) once."""
    short = min(r["prompt"].size for r in reqs)
    reach = serve.max_total_tokens // 2 + 2 * serve.sync_every - short
    return [dict(r, max_new=reach if r["prompt"].size == short else 1)
            for r in reqs]


def sample_served(results: List[Dict[str, Any]], n: int, seed: int
                  ) -> List[Dict[str, Any]]:
    """``n`` finished requests drawn from the seed, the longest first."""
    order = sorted(range(len(results)),
                   key=lambda i: -results[i]["tokens"].size)
    rest = order[1:]
    r = traffic.rng(seed, 7)
    pick = [order[0]] + [rest[j] for j in r.permutation(len(rest))[:n - 1]]
    return [results[i] for i in pick]


def run(cell, args, env) -> Dict[str, Any]:
    from repro.config import RLConfig, ServeConfig
    from repro.sampling import build_engine
    from repro.serving.api import Request, SamplingParams

    c, t = cell.config, cell.traffic
    log = env.log
    cfg = program.model_config(c)
    program.check_layout(cfg, c)
    v = c["vocab_size"]
    seed = args.seed
    sp = t["sampling"]
    rl = RLConfig(temperature=sp["temperature"], top_k=sp["top_k"],
                  top_p=sp["top_p"])
    serve = ServeConfig(**t["serve"])
    params = program.make_program_weights(cfg, c, seed)
    engine = build_engine(cfg, params, serve, rl=rl, vocab_limit=v)

    def one_batch(b: int, warm: bool = False
                  ) -> Tuple[List[Dict[str, Any]], int]:
        with trace.span("build_requests"):
            reqs = traffic.rollout_requests(t, v, seed, b)
            if warm:
                reqs = warm_budgets(reqs, serve)
            key = engine_key(seed, b)
            rq = [Request(rid=r["rid"], prompt=r["prompt"],
                          params=SamplingParams(
                              temperature=sp["temperature"],
                              top_k=sp["top_k"], top_p=sp["top_p"],
                              max_new_tokens=r["max_new"]))
                  for r in reqs]
        with trace.span("generate"):
            res = engine.generate(rq, key=key)
        out = [{"rid": q.rid, "prompt": np.asarray(q.prompt),
                "tokens": np.asarray(x.tokens), "logps": np.asarray(x.logps),
                "finish": x.finish_reason, "key": key, "max_new": r["max_new"]}
               for q, x, r in zip(rq, res, reqs)]
        return out, len(rq)

    # --- set-up: one batch of the window's sizes --------------------------
    t0 = time.perf_counter()
    warm, _ = one_batch(0, warm=True)
    log(f"rollout: warm-up batch {time.perf_counter() - t0!r} s, "
        f"{sum(s['tokens'].size for s in warm)} tokens")
    env.setup_done()

    # --- the window -------------------------------------------------------
    st0 = dict(engine.stats())
    results, attempted, batches = [], 0, []
    with env.window() as win:
        b = 1
        while True:
            out, n = one_batch(b)
            results.extend(out)
            attempted += n
            batches.append(([s["prompt"].size for s in out],
                             [s["tokens"].size for s in out]))
            b += 1
            if win.elapsed() >= args.seconds:
                break
    st1 = dict(engine.stats())
    env.read_memory()
    gen_tokens = sum(s["tokens"].size for s in results)
    failed = sum(1 for s in results
                 if s["finish"] not in ("length", "eos")
                 or (s["finish"] == "length" and s["tokens"].size != s["max_new"]))
    del engine, params
    gc.collect()

    # --- the reference ----------------------------------------------------
    t_ref = time.perf_counter()
    served = sample_served(results, t["check_requests"], seed)
    rows = check_rows(c, served, serve.max_total_tokens)
    ref = reference_readings(c, seed, cfg.padded_vocab, rows,
                             mesh=placement.mesh(cell.chips))
    checks = compare(rows, ref)
    log(f"rollout: reference over {int(rows['valid'].sum())} served tokens "
        f"of {len(served)} requests took {time.perf_counter() - t_ref!r} s")
    d = lambda k: st1.get(k, 0) - st0.get(k, 0)
    return {
        "checks": checks, "attempted": attempted, "failed": failed,
        "served": served,
        "e2e": {"rollout_tokens_per_s": gen_tokens / win.seconds},
        "record": {"kind": "rollout", "window_s": win.seconds,
                   "batches": batches, "num_slots": serve.num_slots,
                   "decode_steps": d("decode_steps"),
                   "decode_slot_steps": d("decode_slot_steps"),
                   "generated_tokens": gen_tokens},
    }
