#!/usr/bin/env python3
"""The engine's host phases in one run of a rollout cell, read from a
profile of its window; not part of a benchmark run.

  python3 bench/phases.py --workload <name> --seed <n> --seconds <s> \\
      [--trace 0|1] [--save <file.json>]

It runs the cell as ``bench/run.py`` does (set-up, window, checks) and
prints one JSON line: the window's end-to-end metrics and checks, and
with ``--trace 1`` the device's idle share over the window,
``engine_host_gap_share`` and ``engine_host_ms_per_chunk``
(``bench/lib/engine_phases.py``), each engine phase's self time per
decode chunk, and the longest engine steps by phase. ``--save`` also
writes a few steps of the window with their readings
(``bench/data/trace_small_engine.json`` is one).
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import run as harness
    from bench.lib import engine_phases as EP
    from bench.lib import spec, trace
    from repro.compile_cache import enable_compile_cache
    cell = spec.load_cell(args.workload)
    if cell.kind != "rollout" or jax.devices()[0].platform != "tpu":
        harness.log("needs a rollout cell and a TPU")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    tmp = tempfile.mkdtemp(prefix="phases_") if args.trace else None
    env = harness.Env(tmp)
    env.chips = cell.chips
    out = spec.kind_module(cell.kind).run(cell, args, env)
    res = {"workload": args.workload, "seed": args.seed,
           "e2e": out["e2e"], "setup_s": env.setup_s,
           "window_compiles": env.window_compiles, "checks": out["checks"],
           "limits": cell.limits}
    if tmp is not None:
        tr, prog = trace.load(tmp), EP.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        lo, hi = tr.window()
        chunks = sum(1 for n, s, _ in prog
                     if n in EP.CHUNKS and lo <= s < hi)
        res.update(EP.readings(tr, prog))
        res["device_idle_share"] = 100.0 * (1.0 - trace.busy_share(tr))
        res["window_s"] = (hi - lo) * 1e-9
        res["chunks"] = chunks
        res["phase_ms_per_chunk"] = {
            k: v / max(chunks, 1)
            for k, v in sorted(EP.phase_ms(prog, lo, hi).items())}
        res["longest_steps"] = EP.longest_steps(prog, lo, hi)
        res["host_spans_kept_by_trace_load"] = sorted(
            {n for n, _, _ in tr.host})
        if args.save:
            res["saved"] = EP.save_small(tr, prog, args.save)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
