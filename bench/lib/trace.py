"""Profiler traces: capture around the window, and the reduction from a
trace to busy time, idle gaps and time by name.

A trace is read once into a ``Trace``: for each device, the intervals in
which an operation ran (``XLA Ops``) with the operation's HLO name (a
kernel's name for a Pallas call), and the programs (``XLA Modules``); for
the host, the benchmark's own ``jax.profiler.TraceAnnotation`` spans,
whose names start with ``bench.``. Every reduction below works on that
object, so a small recorded trace (``bench/data/trace_small.json``)
tests the same code the runs use.
"""
from __future__ import annotations

import contextlib
import functools
import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# control-flow ops whose events enclose the events of their bodies
CONTAINERS = ("while", "conditional", "call")
# collectives by opcode; an asynchronous one's ``-start`` and ``-done``
# halves count as it, and a fusion that calls one (the TPU's
# ``kind=kCustom, calls=%all-reduce-scatter.4``) is named by what it calls
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "all-reduce-scatter", "collective-permute", "all-to-all")
_OPCODE = re.compile(r"\s([a-z][\w.-]*)\(")
_CALLS = re.compile(r"calls=%?([a-z][a-z-]*)")


@functools.lru_cache(maxsize=None)
def short_name(name: str) -> str:
    """``%fusion.12 fusion`` for an XLA op event named by its whole HLO
    text (``%fusion.12 = bf16[...]{...} fusion(...), ...``);
    ``%fusion.7 all-reduce-scatter`` for a fusion that calls a
    collective."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE.search(" " + rhs)
    if not m:
        return lhs
    called = _CALLS.search(rhs)
    if called and called.group(1) in COLLECTIVES:
        return f"{lhs} {called.group(1)}"
    return f"{lhs} {m.group(1)}"


def is_container(name: str) -> bool:
    return name.rsplit(" ", 1)[-1] in CONTAINERS


def is_collective(name: str) -> bool:
    op = name.rsplit(" ", 1)[-1]
    return op.removesuffix("-start").removesuffix("-done") in COLLECTIVES


def _intervals(ops: List[Tuple[str, float, float]]) -> np.ndarray:
    if not ops:
        return np.zeros((0, 2))
    a = np.array([(s, s + d) for _, s, d in ops], float)
    return a[np.argsort(a[:, 0], kind="stable")]


@dataclass
class Device:
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)

    def intervals(self) -> np.ndarray:
        return _intervals(self.ops)


@dataclass
class Trace:
    devices: Dict[str, Device]
    host: List[Tuple[str, float, float]]       # (name, start_ns, dur_ns)

    def window(self) -> Tuple[float, float]:
        spans = [(s, s + d) for n, s, d in self.host if n == WINDOW]
        if not spans:
            raise ValueError("trace has no bench.window span")
        return min(s for s, _ in spans), max(e for _, e in spans)

    # -- serialization of a small recorded trace (tests) -------------------
    def to_json(self) -> Dict:
        return {"devices": {k: {"ops": [list(o) for o in d.ops],
                                "modules": [list(m) for m in d.modules]}
                            for k, d in self.devices.items()},
                "host": [list(h) for h in self.host]}

    @staticmethod
    def from_json(obj: Dict) -> "Trace":
        return Trace(
            devices={k: Device(ops=[tuple(o) for o in d["ops"]],
                               modules=[tuple(m) for m in d["modules"]])
                     for k, d in obj["devices"].items()},
            host=[tuple(h) for h in obj["host"]])


@contextlib.contextmanager
def capture(log_dir: Optional[str]) -> Iterator[None]:
    """Profile the block into ``log_dir`` (no-op when None)."""
    if log_dir is None:
        yield
        return
    import jax
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str):
    """A host span on the profiler's clock (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    devices: Dict[str, Device] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = Device()
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend((short_name(e.name), float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in line.events)
                elif line.name == MODULES_LINE:
                    dev.modules.extend((e.name, float(e.start_ns),
                                        float(e.duration_ns))
                                       for e in line.events)
            if dev.ops or dev.modules:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return Trace(devices=devices, host=host)


# --------------------------------------------------------------------------
# reductions


def _union(iv: np.ndarray, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals of ``iv`` (sorted by start) clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in iv:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(dev: Device, lo: float, hi: float) -> float:
    return sum(e - s for s, e in _union(dev.intervals(), lo, hi))


def busy_share(trace: Trace) -> float:
    """Union of device-busy intervals over the window, averaged over the
    devices."""
    lo, hi = trace.window()
    if not trace.devices or hi <= lo:
        return float("nan")
    return float(np.mean([busy_ns(d, lo, hi) / (hi - lo)
                          for d in trace.devices.values()]))


def _overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
             ) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def collective_exposed_ns(dev: Device, lo: float, hi: float) -> float:
    """Time in [lo, hi] inside collective ops that no other op on the
    device covers (control-flow ops, which enclose everything in their
    bodies, cover nothing)."""
    coll = _union(_intervals([o for o in dev.ops if is_collective(o[0])]),
                  lo, hi)
    rest = _union(_intervals([o for o in dev.ops
                              if not is_collective(o[0])
                              and not is_container(o[0])]), lo, hi)
    return sum(e - s for s, e in coll) - _overlap(coll, rest)


def collective_exposed_share(trace: Trace) -> Optional[float]:
    """``collective_exposed_ns`` over the window, averaged over the
    devices; None when no device ran a collective in the window."""
    lo, hi = trace.window()
    if hi <= lo or not any(is_collective(n) and s < hi and s + d > lo
                           for dev in trace.devices.values()
                           for n, s, d in dev.ops):
        return None
    return float(np.mean([collective_exposed_ns(d, lo, hi) / (hi - lo)
                          for d in trace.devices.values()]))


def idle_gaps(dev: Device, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    gaps, t = [], lo
    for s, e in _union(dev.intervals(), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label(trace: Trace, t0: float, t1: float) -> str:
    """The innermost benchmark span (other than the window) that covers
    the middle of [t0, t1]: what the host was doing in that gap."""
    mid = (t0 + t1) / 2
    best, best_start = "host: no benchmark span", -np.inf
    for n, s, d in trace.host:
        if n != WINDOW and s <= mid <= s + d and s > best_start:
            best, best_start = n[len(SPAN_PREFIX):], s
    return best


def longest_gaps(trace: Trace, k: int = 10) -> List[List]:
    """The ``k`` longest idle gaps of the first device, in seconds, each
    named by what the host was doing."""
    lo, hi = trace.window()
    name = sorted(trace.devices)[0]
    gaps = sorted(idle_gaps(trace.devices[name], lo, hi),
                  key=lambda g: g[1] - g[0], reverse=True)[:k]
    return [[label(trace, s, e), float(e - s) * 1e-9] for s, e in gaps]


def op_seconds(trace: Trace, match: Optional[Sequence[str]] = None
               ) -> Dict[str, float]:
    """Device seconds per operation name inside the window, summed over
    devices and divided by their number; with ``match``, only operations
    whose name contains one of the strings. Control-flow ops,
    whose events enclose their bodies' events, are left out."""
    lo, hi = trace.window()
    out: Dict[str, float] = {}
    for dev in trace.devices.values():
        for n, s, d in dev.ops:
            if s < lo or s + d > hi or is_container(n):
                continue
            if match and not any(m in n for m in match):
                continue
            out[n] = out.get(n, 0.0) + d * 1e-9
    nd = max(len(trace.devices), 1)
    return {k: v / nd for k, v in out.items()}


def top_ops(trace: Trace, k: int = 10) -> List[List]:
    ops = op_seconds(trace)
    return [[n, s] for n, s in sorted(ops.items(), key=lambda x: -x[1])[:k]]


def module_seconds(trace: Trace, match: str) -> Tuple[float, int]:
    """Device seconds and count of the programs whose name contains
    ``match`` inside the window (per device, averaged)."""
    lo, hi = trace.window()
    total, count = 0.0, 0
    for dev in trace.devices.values():
        for n, s, d in dev.modules:
            if match in n and s >= lo and s + d <= hi:
                total += d * 1e-9
                count += 1
    nd = max(len(trace.devices), 1)
    return total / nd, count // nd


def summary(trace: Trace) -> str:
    """Planes, event counts and the busiest names: one look at a trace."""
    lines = []
    for k, d in sorted(trace.devices.items()):
        lines.append(f"{k}: {len(d.ops)} ops, {len(d.modules)} modules")
        mods: Dict[str, float] = {}
        for n, _, dur in d.modules:
            mods[n] = mods.get(n, 0.0) + dur * 1e-9
        for n, s in sorted(mods.items(), key=lambda x: -x[1])[:8]:
            lines.append(f"  module {n!r}: {s!r} s")
    for n, s in top_ops(trace, 12):
        lines.append(f"  op {n!r}: {s!r} s")
    coll = {n: s for n, s in op_seconds(trace).items() if is_collective(n)}
    lines.append(f"collectives: {len(coll)} ops, {sum(coll.values())!r} s "
                 f"a device; busiest {sorted(coll, key=coll.get)[-3:]}")
    names: Dict[str, int] = {}
    for n, _, _ in trace.host:
        names[n] = names.get(n, 0) + 1
    lines.append(f"host spans: {names}")
    return "\n".join(lines)


def trimmed(trace: Trace, max_ops: int = 400) -> Trace:
    """The window's first ``max_ops`` operations of each device, with the
    window cut to end where the last of them ends: a small recorded trace
    that reduces like the whole one."""
    lo, _ = trace.window()
    devs = {k: sorted((o for o in d.ops if o[1] >= lo),
                      key=lambda o: o[1])[:max_ops]
            for k, d in trace.devices.items()}
    hi = max((o[1] + o[2] for ops in devs.values() for o in ops), default=lo)
    keep = lambda s, d: s < hi and s + d > lo
    host = [h for h in trace.host if h[0] != WINDOW and keep(h[1], h[2])]
    return Trace(devices={k: Device(ops=devs[k], modules=[
                     m for m in trace.devices[k].modules if keep(m[1], m[2])])
                 for k in devs},
                 host=[(WINDOW, lo, hi - lo)] + host)


def save_small(trace: Trace, path: str, max_ops: int = 400) -> None:
    """Write a trimmed copy of a recorded trace (for the tests)."""
    with open(path, "w") as f:
        json.dump(trimmed(trace, max_ops).to_json(), f)
