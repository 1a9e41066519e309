"""The engine's host phases on the profiler's clock, and two readings of
them against the device's idle time.

While a profiler session runs, every ``repro.obs`` span also lands on the
host plane as a ``repro.<track>.<name>`` annotation. The engine's are on
track ``engine``: ``repro.engine.step`` holds ``admit``, one ``prefill``
per prompt chunk, ``decode`` or ``verify`` (the chunk's inputs and
dispatch), ``sync`` (the host waiting for the chunk's results) and
``commit`` (the per-slot token loop). ``load`` reads them from a profile;
the reductions read them against a ``bench.lib.trace.Trace`` of the same
profile, inside its window:

- ``host_gap_share``: time in which the first device is idle while the
  host is inside an engine step but outside its sync, over the window, in
  %: how much of the host's work blocks the device.
- ``host_ms_per_chunk``: engine-step time less its sync time, per decode
  or verify chunk, in ms: how much host work there is.

``bench.lib.trace.load`` keeps only the benchmark's own ``bench.`` spans,
so the run's result line does not carry these; ``bench/phases.py`` runs a
cell and reads them.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from bench.lib import trace as T

PREFIX = "repro."
STEP = "repro.engine.step"
SYNC = "repro.engine.sync"
CHUNKS = ("repro.engine.decode", "repro.engine.verify")

Span = Tuple[str, float, float]                 # (name, start_ns, dur_ns)
Intervals = List[Tuple[float, float]]


def base_name(name: str) -> str:
    """An annotation's name without TraceMe's ``#key=value#`` metadata."""
    return name.split("#", 1)[0]


def load(log_dir: str) -> List[Span]:
    """The program's spans (host events named ``repro.*``) of the newest
    ``.xplane.pb`` under ``log_dir``, on the clock of ``trace.load``."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    return [(base_name(e.name), float(e.start_ns), float(e.duration_ns))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


# --------------------------------------------------------------------------
# interval arithmetic


def union(spans: Sequence[Span], names: Sequence[str], lo: float,
          hi: float) -> Intervals:
    """Merged intervals of the spans named in ``names``, clipped to
    [lo, hi]."""
    iv = sorted((s, s + d) for n, s, d in spans if n in names)
    return T._union(iv, lo, hi)


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def minus(a: Intervals, b: Intervals) -> Intervals:
    """``a`` less ``b``, both sorted lists of disjoint intervals."""
    out = []
    for s, e in a:
        for bs, be in b:
            if be <= s or bs >= e:
                continue
            if bs > s:
                out.append((s, bs))
            s = max(s, be)
        if e > s:
            out.append((s, e))
    return out


def length(iv: Intervals) -> float:
    return sum(e - s for s, e in iv)


# --------------------------------------------------------------------------
# readings


def host_gap_share(trace: T.Trace, program: Sequence[Span]
                   ) -> Optional[float]:
    """% of the window in which the first device is idle and the host is
    inside ``repro.engine.step`` but outside every ``repro.engine.sync``;
    None when the trace has no engine steps or no device."""
    if not trace.devices or not any(n == STEP for n, _, _ in program):
        return None
    lo, hi = trace.window()
    host = minus(union(program, (STEP,), lo, hi),
                 union(program, (SYNC,), lo, hi))
    dev = trace.devices[sorted(trace.devices)[0]]
    return 100.0 * length(intersect(T.idle_gaps(dev, lo, hi), host)) / (
        hi - lo)


def host_ms_per_chunk(trace: T.Trace, program: Sequence[Span]
                      ) -> Optional[float]:
    """The window's ``repro.engine.step`` time less its
    ``repro.engine.sync`` time, in ms, over the number of decode and
    verify chunks that start in the window; None without chunks."""
    lo, hi = trace.window()
    chunks = sum(1 for n, s, _ in program if n in CHUNKS and lo <= s < hi)
    if not chunks:
        return None
    steps = union(program, (STEP,), lo, hi)
    syncs = intersect(union(program, (SYNC,), lo, hi), steps)
    return (length(steps) - length(syncs)) * 1e-6 / chunks


def phase_ms(program: Sequence[Span], lo: float, hi: float
             ) -> Dict[str, float]:
    """Self time (duration less that of the spans nested directly in it)
    of each phase that starts in [lo, hi), in ms, by name without the
    ``repro.engine.`` prefix."""
    spans = sorted((x for x in program if lo <= x[1] < hi),
                   key=lambda x: (x[1], -x[2]))
    own = [x[2] for x in spans]
    stack: List[int] = []
    for i, (_, s, d) in enumerate(spans):
        while stack and s >= spans[stack[-1]][1] + spans[stack[-1]][2]:
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    out: Dict[str, float] = {}
    for (n, _, _), ns in zip(spans, own):
        k = n.rsplit(".", 1)[-1]
        out[k] = out.get(k, 0.0) + ns * 1e-6
    return out


def longest_steps(program: Sequence[Span], lo: float, hi: float,
                  k: int = 3) -> List[Dict]:
    """The ``k`` longest engine steps in [lo, hi): each one's ms, its
    start from ``lo`` in ms, and its phases' self time."""
    steps = sorted((x for x in program if x[0] == STEP and lo <= x[1] < hi),
                   key=lambda x: -x[2])[:k]
    return [{"ms": d * 1e-6, "at_ms": (s - lo) * 1e-6,
             "phases": phase_ms(program, s, s + d)} for _, s, d in steps]


# --------------------------------------------------------------------------
# a small recorded trace (tests)


def outermost(ops: Sequence[Span]) -> List[Span]:
    """The ops that no earlier op of the same device encloses (a decode
    chunk's ``while`` and not its body): the same busy union."""
    out: List[Span] = []
    end = -float("inf")
    for o in sorted(ops, key=lambda o: (o[1], -o[2])):
        if o[1] + o[2] > end:
            out.append(o)
            end = o[1] + o[2]
    return out


def cut(trace: T.Trace, program: Sequence[Span], first: int = 1,
        steps: int = 4) -> Tuple[T.Trace, List[Span]]:
    """The window cut to engine steps ``first`` .. ``first + steps - 1``:
    the devices' outermost ops, programs and host spans that overlap it,
    and the program spans inside it."""
    lo, hi = trace.window()
    starts = sorted((s, s + d) for n, s, d in program
                    if n == STEP and lo <= s < hi)[first:first + steps]
    if not starts:
        raise ValueError("no engine steps in the window")
    c0, c1 = starts[0][0], starts[-1][1]
    over = lambda s, d: s < c1 and s + d > c0
    devs = {k: T.Device(ops=[o for o in outermost(d.ops) if over(o[1], o[2])],
                        modules=[m for m in d.modules if over(m[1], m[2])])
            for k, d in trace.devices.items()}
    host = [h for h in trace.host if h[0] != T.WINDOW and over(h[1], h[2])]
    prog = [p for p in program if c0 <= p[1] and p[1] + p[2] <= c1]
    return (T.Trace(devices=devs, host=[(T.WINDOW, c0, c1 - c0)] + host),
            prog)


def readings(trace: T.Trace, program: Sequence[Span]) -> Dict:
    return {"engine_host_gap_share": host_gap_share(trace, program),
            "engine_host_ms_per_chunk": host_ms_per_chunk(trace, program)}


def save_small(trace: T.Trace, program: Sequence[Span], path: str,
               **kw) -> Dict:
    """Write ``cut(trace, program)`` with its readings; returns them."""
    tr, prog = cut(trace, program, **kw)
    got = readings(tr, prog)
    with open(path, "w") as f:
        json.dump({"trace": tr.to_json(), "program": [list(p) for p in prog],
                   "readings": got}, f)
    return got


def load_small(path: str) -> Tuple[T.Trace, List[Span], Dict]:
    with open(path) as f:
        obj = json.load(f)
    return (T.Trace.from_json(obj["trace"]),
            [tuple(p) for p in obj["program"]], obj["readings"])
