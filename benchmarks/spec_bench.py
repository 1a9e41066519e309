"""Speculative decoding benchmark: drafted+verified vs sequential decode.

Drives the continuous engine over the same request set with speculation
off (the sequential decode-chunk path) and on (prompt-lookup drafts +
k-token paged verification), and reports steady-state decode tokens/s —
compile warmup excluded, the serve_throughput convention. Two workloads,
deliberately at the two ends of the drafter's operating range:

  - templated — greedy decoding. Untrained tiny models fall into short
    repetition cycles, exactly the looping/templated shape (system
    prompts, JSON scaffolding, code boilerplate) prompt-lookup drafting
    exists for: the n-gram drafter locks onto the cycle and acceptance
    climbs. The acceptance bar — the ISSUE target — is >=1.5x tokens/s
    at k=4 with accept-rate >=0.6.
  - random — temperature-1.0 sampling over near-uniform logits:
    incompressible output, accept-rate ~0. This row is the honest floor
    (~1x): verification costs one window forward per round either way,
    and each round still commits >=1 token (the replayed draw), so spec
    decode degrades toward the sequential rate instead of collapsing.

Exactness is not benched here — tests/test_spec.py pins greedy
bit-identity and the target-logp contract; this file only times.

  PYTHONPATH=src python -m benchmarks.spec_bench [--smoke]

Output: CSV rows ``spec,<workload>,k<k>,<tok/s>,accept<rate>,x<speedup>``
plus a ``BENCH_spec.json`` artifact (path: $BENCH_SPEC_JSON) with a
``headline`` block (templated k=4 speedup) for BENCH_summary.json.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List

import jax
import numpy as np

from repro.config import ATTN, MLP, ModelConfig, RLConfig, ServeConfig
from repro.models import init_params
from repro.sampling import build_engine
from repro.serving.api import Request, SamplingParams

SMOKE_ENV = os.environ.get("BENCH_SMOKE", "0") == "1"
JSON_PATH = os.environ.get("BENCH_SPEC_JSON", "BENCH_spec.json")

# Big enough that the forward dominates per-dispatch overhead — the
# regime speculative decoding exists for (on accelerators decode is
# memory-bound; here model compute plays that role). Short prompts +
# long generations keep prefill out of the decode-rate denominator.
CFG = ModelConfig(name="spec-lm", family="dense", num_layers=4, d_model=256,
                  num_heads=8, num_kv_heads=4, d_ff=512, vocab_size=64,
                  block_pattern=(ATTN,), ffn_pattern=(MLP,),
                  dtype="float32", attn_impl="naive", remat=False,
                  rope_theta=1e4)

GREEDY = dict(temperature=1.0, top_k=1, top_p=1.0)
RANDOM = dict(temperature=1.0, top_k=0, top_p=1.0)


def _prompts(n: int, width: int, seed: int = 0) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(4, 30, size=width).astype(np.int32)
            for _ in range(n)]


def _measure(params, profile: Dict, prompts: List[np.ndarray], *,
             max_new: int, spec_k: int, epochs: int) -> Dict[str, float]:
    """Steady-state decode rate: one warmup epoch (jit compile + width
    buckets), then ``epochs`` timed epochs over fresh request ids."""
    rl = RLConfig(max_new_tokens=max_new, engine="continuous", **profile)
    serve = ServeConfig(engine="continuous", num_slots=4, page_size=16,
                        sync_every=8, prefix_cache=False,
                        max_total_tokens=len(prompts[0]) + max_new,
                        spec_k=spec_k, seed=0)
    eng = build_engine(CFG, params, serve, rl=rl,
                       vocab_limit=CFG.vocab_size,
                       key=jax.random.PRNGKey(0))
    sp = SamplingParams.from_rl(rl)
    rid = 0

    def epoch():
        nonlocal rid
        reqs = [Request(rid=rid + i, prompt=p, params=sp)
                for i, p in enumerate(prompts)]
        rid += len(reqs)
        return eng.generate(reqs)

    epoch()                                          # warmup (compiles)
    base = eng.stats()
    tokens, t0 = 0, time.perf_counter()
    for _ in range(epochs):
        tokens += sum(r.gen_count for r in epoch())
    dt = time.perf_counter() - t0
    st = eng.stats()
    drafted = st["drafted_tokens_total"] - base["drafted_tokens_total"]
    accepted = st["accepted_tokens_total"] - base["accepted_tokens_total"]
    return {"tok_s": tokens / dt, "tokens": tokens, "seconds": dt,
            "accept_rate": accepted / max(drafted, 1),
            "drafted": int(drafted)}


def run_bench(smoke: bool) -> List[str]:
    n, width = 8, 8
    # long generations over short prompts: decode-rate measurement with
    # no prefill dilution, and a live context long enough that the
    # per-step K/V gather (the memory-bound share) is in play
    max_new = 128
    epochs = 2 if smoke else 3
    params = init_params(CFG, jax.random.PRNGKey(0))
    ks = (4,) if smoke else (2, 4, 8)

    rows, out_rows = [], []

    def record(workload, k, res, base_tok_s):
        speedup = res["tok_s"] / base_tok_s
        row = {"workload": workload, "spec_k": k, **res,
               "speedup_x": round(speedup, 3)}
        out_rows.append(row)
        rows.append(f"spec,{workload},k{k},{res['tok_s']:.1f} tok/s,"
                    f"accept{res['accept_rate']:.2f},x{speedup:.2f}")
        return speedup

    headline = {}
    for workload, profile in (("templated", GREEDY), ("random", RANDOM)):
        base = _measure(params, profile, _prompts(n, width),
                        max_new=max_new, spec_k=0, epochs=epochs)
        record(workload, 0, base, base["tok_s"])
        for k in ks:
            res = _measure(params, profile, _prompts(n, width),
                           max_new=max_new, spec_k=k, epochs=epochs)
            speedup = record(workload, k, res, base["tok_s"])
            if workload == "templated" and k == 4:
                headline = {"metric": "templated_speedup_x_k4",
                            "value": round(speedup, 3),
                            "accept_rate": round(res["accept_rate"], 3)}
                # acceptance bar: templated k=4 must clear 1.5x with
                # accept-rate >= 0.6 (the ISSUE target)
                assert speedup >= 1.5, \
                    f"templated k=4 speedup {speedup:.2f}x < 1.5x"
                assert res["accept_rate"] >= 0.6, \
                    f"accept rate {res['accept_rate']:.2f} < 0.6"

    artifact = {
        "bench": "spec_decode",
        "meta": {"smoke": smoke, "requests": n, "max_new": max_new,
                 "epochs": epochs, "model": CFG.name,
                 "num_layers": CFG.num_layers, "vocab": CFG.vocab_size},
        "rows": out_rows,
        "headline": headline,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(artifact, f, indent=1)
    rows.append(f"# wrote {JSON_PATH}")
    return rows


def run() -> List[str]:
    return run_bench(SMOKE_ENV)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast configuration for CI")
    args = ap.parse_args()
    for r in run_bench(args.smoke or SMOKE_ENV):
        print(r, flush=True)


if __name__ == "__main__":
    main()
