#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` on the chips of this host.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It makes the cell's weights and traffic from ``--seed``, warms every
program the window uses (set-up), measures for ``--seconds`` (the step or
batch in flight at the end is finished and counted), checks what the
window produced against the plain float32 reference, and prints one JSON
line last on stdout: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window), ``device``, with ``--trace 1``
a ``breakdown``, and ``checks`` (each number compared, with its limit).
The same numbers end standard error.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for. JAX's persistent compilation cache lives in
``.jax_cache`` of the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Window:
    """The measured window: host clock from its start to the end of its
    last whole step or batch."""

    def __init__(self) -> None:
        self.t0 = self.t1 = 0.0

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Env:
    """What a cell runner gets from the harness: logging, the end of
    set-up, the window (traced and compile-counted), and the device's
    peak memory."""

    def __init__(self, trace_dir) -> None:
        self.log = log
        self.trace_dir = trace_dir
        self.setup_s = None
        self.window_compiles = 0
        self.memory_peak = 0
        self.window_obj = Window()

    def setup_done(self) -> None:
        self.setup_s = time.monotonic() - T_START
        log(f"set-up {self.setup_s!r} s")

    @contextlib.contextmanager
    def window(self):
        from repro.analysis.sentinel import RecompileSentinel

        from bench.lib import trace
        w = self.window_obj
        with RecompileSentinel("window") as sentinel:
            with trace.capture(self.trace_dir), trace.span("window"):
                w.t0 = time.perf_counter()
                yield w
                w.t1 = time.perf_counter()
        self.window_compiles = sentinel.compiles
        log(f"window {w.seconds!r} s, {sentinel.compiles} compiles in it")

    def read_memory(self) -> None:
        """The peak on the fullest chip. The TPU runtime counts buffers
        (``peak_bytes_in_use``) apart from the memory it reserves for the
        programs' temporaries (``peak_bytes_reserved``); the peak is both."""
        peaks = []
        for d in self.devices:
            st = d.memory_stats() or {}
            log(f"memory {d}: {st}")
            peaks.append(st.get("peak_bytes_in_use", 0)
                         + st.get("peak_bytes_reserved", 0))
        self.memory_peak = int(max(peaks))

    @property
    def devices(self):
        import jax
        return jax.devices()[:self.chips]


def judge(found: Dict[str, float], limits: Dict[str, float],
          window_compiles: int) -> Tuple[Dict[str, float], Dict[str, float],
                                         bool]:
    """The numbers compared, their limits, and whether every one is
    finite and at or under its limit. A number that the cell's limits
    file leaves out is not compared in that cell; compiles in the window
    are compared in every cell, with the limit 0."""
    checks = {k: v for k, v in found.items() if k in limits}
    for k in sorted(set(found) - set(checks)):
        log(f"not compared in this cell: {k} {found[k]!r}")
    checks["window_compiles"] = float(window_compiles)
    limits = dict(limits, window_compiles=0.0)
    correct = all(math.isfinite(v) and v <= limits[k]
                  for k, v in checks.items())
    return checks, limits, correct


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None,
                    help="with --trace 1: also write a trimmed copy of the "
                         "reduced trace to this JSON file")
    args = ap.parse_args(argv)

    from bench.lib import spec
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"needs a TPU; JAX found {devices[0].platform!r}")
        return 2
    if len(devices) < cell.chips:
        log(f"{args.workload} needs {cell.chips} chips; JAX found "
            f"{len(devices)}")
        return 2

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench.lib import peaks as peak_table
    from bench.lib import trace
    kind = spec.kind_module(cell.kind)
    tmp = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    env = Env(tmp)
    env.chips = cell.chips
    out = kind.run(cell, args, env)

    checks, limits, correct = judge(out["checks"], cell.limits,
                                    env.window_compiles)
    d0 = env.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(env.devices), "memory_peak_bytes": env.memory_peak}
    metrics = {}
    breakdown = None
    if args.trace:
        import shutil
        t_load = time.perf_counter()
        tr = trace.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
        log(f"trace read in {time.perf_counter() - t_load!r} s")
        log("trace:\n" + trace.summary(tr))
        if args.save_trace:
            trace.save_small(tr, args.save_trace)
        lo, hi = tr.window()
        device["window_s"] = (hi - lo) * 1e-9
        device["busy_s"] = trace.busy_share(tr) * device["window_s"]
        breakdown = {"device_ops": trace.top_ops(tr),
                     "idle_gaps": trace.longest_gaps(tr)}
        rec = dict(out["record"], trace=tr, config=cell.config,
                   chips=cell.chips, peaks=peak_table.peaks(d0.device_kind),
                   e2e=out["e2e"])
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            value = (env.setup_s if m["name"] == "setup_s"
                     else out["e2e"][m["name"]])
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for k in sorted(checks):
        log(f"check {k} {checks[k]!r} limit {limits[k]!r}")
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in sorted(checks)}
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
