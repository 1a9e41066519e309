#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, taken on the chip at the
cell's own size; not part of a benchmark run.

  python3 bench/readings.py --workload <name> --seeds 11,12,13

For each seed it prints one JSON line with the numbers that the cell
compares, and the fullest chip's peak memory so far (the reference's own,
for a ``learn`` cell), as read by:

- ``control``: the reference computed in float8 (e4m3, absmax-scaled
  weights and inputs of every linear layer) put in the program's place,
  against the float32 reference (the upper reading of a limit);
- ``learn`` cells also ``half_batch``: the reference with half the batch
  left out, the mean taken over the rest, against the full reference;
- ``rollout`` cells also ``program``: the engine's served tokens of one
  batch at the cell's load against the reference, as a run compares them.

A step that returns its state unchanged reads 1 on the change of every
moved leaf by construction and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(f"[readings] {msg}", file=sys.stderr, flush=True)


def learn_readings(cell, seed: int) -> dict:
    from bench.kinds import learn
    from bench.lib import placement, program
    c, t = cell.config, cell.traffic
    cfg = program.model_config(c)
    _, tc = learn.settings(cell, t["prompts"] * t["group_size"])
    mesh = placement.mesh(cell.chips)
    ref = learn.reference_readings(c, t, seed, cfg.padded_vocab, tc,
                                   mesh=mesh, log=log)
    out = {}
    for name, kw in (("control", {"mm": "fp8"}), ("half_batch", {"half": True})):
        t0 = time.perf_counter()
        other = learn.reference_readings(c, t, seed, cfg.padded_vocab, tc,
                                         mesh=mesh, log=log, **kw)
        out[name] = learn.compare(other, ref, log)
        log(f"{name} seed {seed}: {out[name]} ({time.perf_counter() - t0:.1f} s)")
        gc.collect()
    return out


def rollout_readings(cell, seed: int) -> dict:
    import numpy as np

    from bench.kinds import rollout
    from bench.lib import placement, program, traffic
    from repro.config import RLConfig, ServeConfig
    from repro.sampling import build_engine
    from repro.serving.api import Request, SamplingParams

    c, t = cell.config, cell.traffic
    cfg = program.model_config(c)
    v = c["vocab_size"]
    sp = t["sampling"]
    serve = ServeConfig(**t["serve"])
    params = program.make_program_weights(cfg, c, seed)
    engine = build_engine(cfg, params, serve, rl=RLConfig(
        temperature=sp["temperature"], top_k=sp["top_k"], top_p=sp["top_p"]),
        vocab_limit=v)
    reqs = traffic.rollout_requests(t, v, seed, 1)
    key = rollout.engine_key(seed, 1)
    res = engine.generate([Request(rid=r["rid"], prompt=r["prompt"],
                                   params=SamplingParams(
                                       temperature=sp["temperature"],
                                       top_k=sp["top_k"], top_p=sp["top_p"],
                                       max_new_tokens=r["max_new"]))
                           for r in reqs], key=key)
    results = [{"rid": r["rid"], "prompt": r["prompt"],
                "tokens": np.asarray(x.tokens), "logps": np.asarray(x.logps),
                "key": key} for r, x in zip(reqs, res)]
    del engine, params
    gc.collect()
    served = rollout.sample_served(results, t["check_requests"], seed)
    rows = rollout.check_rows(c, served, serve.max_total_tokens)
    mesh = placement.mesh(cell.chips)
    ref = rollout.reference_readings(c, seed, cfg.padded_vocab, rows,
                                     mesh=mesh)
    ctl = rollout.reference_readings(c, seed, cfg.padded_vocab, rows,
                                     mm="fp8", mesh=mesh)
    vm = rows["valid"]
    return {"program": rollout.compare(rows, ref),
            "control": {
                "logp_gap": float(np.max(np.abs(ctl["logp"] - ref["logp"])[vm])),
                "token_gap": float(np.max(ctl["gap_of_best"][vm]))},
            "tokens_compared": int(vm.sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench.lib import spec
    from bench.run import Env
    from repro.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        log("needs a TPU")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = spec.load_cell(args.workload)
    fn = learn_readings if cell.kind == "learn" else rollout_readings
    env = Env(None)
    env.chips = cell.chips
    for s in args.seeds.split(","):
        out = fn(cell, int(s))
        env.read_memory()
        print(json.dumps({"workload": cell.name, "seed": int(s), **out,
                          "memory_peak_bytes": env.memory_peak}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
