"""Benchmark harness entrypoint: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig2,table1,...] [--smoke]

Budget knobs: BENCH_STEPS (default 30), BENCH_FULL=1 for paper-scale runs.
``--smoke`` runs a tiny fast subset (<60 s CPU) so CI can exercise the
benchmark entrypoints without burning minutes.
Output: CSV rows `table,setting,metrics...` on stdout.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import time
import traceback

MODULES = [
    ("fig2", "benchmarks.fig2_variance"),
    ("theory", "benchmarks.theory_bounds"),
    ("table14", "benchmarks.table14_localized"),
    ("roofline", "benchmarks.roofline_table"),
    ("table1", "benchmarks.table1_online"),
    ("table2", "benchmarks.table2_hetero"),
    ("fig5", "benchmarks.fig5_latency"),
    ("table12", "benchmarks.table12_async"),
    ("table13", "benchmarks.table13_ablation"),
    ("hyperparams", "benchmarks.hyperparams"),
    ("serve", "benchmarks.serve_throughput"),
    ("serve_lat", "benchmarks.serve_latency"),
    ("logprob", "benchmarks.logprob_bench"),
    ("scaling", "benchmarks.scaling_bench"),
    ("sync", "benchmarks.sync_bench"),
    ("sentinel", "benchmarks.recompile_bench"),
    ("spec", "benchmarks.spec_bench"),
]

# modules cheap enough for the CI smoke job ("serve" stays out: CI
# exercises benchmarks.serve_throughput --smoke as its own step;
# "logprob" rides here so the CI benchmark-smoke covers the hot path;
# "scaling" proves the sharded train step runs at data-axis sizes >1 —
# its workers are subprocesses, so the forced device count never leaks;
# "sync" asserts the chunked weight transport beats whole-blob sync and
# stays byte-identical — its mesh part subprocesses when devices < 4;
# "serve_lat" drives the admission-controlled front door under Poisson/
# bursty/overload open-loop load and emits BENCH_serve.json;
# "sentinel" asserts the engine's pow2-bucketed executable bound under
# the recompile sentinel (cold run <= bound, steady run compiles zero);
# "spec" A/Bs speculative decoding (prompt-lookup drafts + k-token paged
# verification) against sequential decode and asserts the templated k=4
# speedup/accept-rate bars (emits BENCH_spec.json)
SMOKE_MODULES = ("fig2", "theory", "logprob", "scaling", "sync",
                 "serve_lat", "sentinel", "spec")


# One headline metric per legacy BENCH_*.json artifact (newer artifacts
# carry an explicit "headline" block instead and need no entry here).
_HEADLINE_PICKERS = {
    "BENCH_serve.json": lambda d: {
        "metric": "poisson_slo_tokens_per_s",
        "value": d["poisson"]["slo"]["tokens_per_s"]},
}


def write_summary(smoke: bool, path: str = "BENCH_summary.json") -> int:
    """Aggregate one headline metric from every BENCH_*.json in cwd into
    ``BENCH_summary.json`` — the single artifact a dashboard (or a human
    diffing two CI runs) reads instead of N per-bench files. Artifacts
    either carry their own ``headline`` block (the convention for new
    benches) or get a picker above; files matching neither are listed
    without a metric rather than dropped."""
    headlines = {}
    for fp in sorted(glob.glob("BENCH_*.json")):
        if fp == path:
            continue
        try:
            with open(fp) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            headlines[fp] = {"error": str(e)}
            continue
        if isinstance(data.get("headline"), dict) and data["headline"]:
            headlines[fp] = data["headline"]
        elif fp in _HEADLINE_PICKERS:
            try:
                headlines[fp] = _HEADLINE_PICKERS[fp](data)
            except (KeyError, ValueError) as e:
                headlines[fp] = {"error": f"picker failed: {e}"}
        else:
            headlines[fp] = {"metric": None,
                             "note": "no headline block or picker"}
    with open(path, "w") as f:
        json.dump({"bench": "summary", "smoke": smoke,
                   "headlines": headlines}, f, indent=1)
    return len(headlines)


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast subset for CI (<60 s CPU)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.smoke:
        # must be set before the modules import benchmarks.common
        os.environ["BENCH_SMOKE"] = "1"
        os.environ.setdefault("BENCH_STEPS", "4")
        os.environ.setdefault("BENCH_SFT_STEPS", "20")
        if only is None:
            only = set(SMOKE_MODULES)

    t0 = time.time()
    failures = []
    for name, mod_name in MODULES:
        if only and name not in only:
            continue
        t1 = time.time()
        try:
            import importlib

            import jax
            jax.clear_caches()          # executables from prior modules
            mod = importlib.import_module(mod_name)
            rows = mod.run()
            for r in rows:
                print(r, flush=True)
            print(f"# {name} done in {time.time()-t1:.1f}s", flush=True)
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED:", flush=True)
            traceback.print_exc()
    n = write_summary(bool(args.smoke))
    print(f"# BENCH_summary.json aggregates {n} artifact headline(s)",
          flush=True)
    print(f"# total {time.time()-t0:.1f}s; failures: {failures or 'none'}",
          flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
