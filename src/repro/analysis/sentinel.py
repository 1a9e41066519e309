"""Runtime recompile sentinel — the dynamic twin of RA002.

Counts XLA backend compiles via :mod:`jax.monitoring` event listeners so
tests and benchmarks can assert compile *budgets*, not just eyeball them:
the continuous engine's pow2-bucketed block tables promise O(log)
executables over a steady run, and this is where that claim is enforced.

Usage::

    with RecompileSentinel() as s:
        engine.step(); engine.step()
    assert s.compiles <= bound

Listeners in jax.monitoring are append-only (there is no unregister), so
a single module-level listener is registered on first use and fans out to
every active sentinel. Nested sentinels each see the compiles that happen
while they are open.
"""
from __future__ import annotations

import threading
from typing import List

import jax

from repro import obs

# Event key emitted once per XLA backend compile (observed on jax 0.4.x
# CPU and TPU backends alike). Duration listeners fire with
# (event_name, duration_secs, **kwargs).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_active: List["RecompileSentinel"] = []
_active_lock = threading.Lock()
_registered = False

# Unified-registry mirror: every observed compile also increments this
# counter (and a compile-seconds counter), so steady-state recompiles
# surface on a scraped /metrics endpoint — paging an operator — instead
# of only failing tests/test_recompile.py after the fact. No-op while
# the registry is disabled.
_M_COMPILES = obs.metrics.counter(
    "xla_compiles_total", "XLA backend compiles observed")
_M_COMPILE_SECONDS = obs.metrics.counter(
    "xla_compile_seconds_total", "seconds spent in XLA backend compiles")


def _on_event(event: str, duration: float, **kwargs) -> None:
    if _COMPILE_EVENT not in event:
        return
    _M_COMPILES.inc()
    _M_COMPILE_SECONDS.inc(max(float(duration), 0.0))
    with _active_lock:
        for s in _active:
            s._record(event)


def _ensure_listener() -> None:
    global _registered
    with _active_lock:
        if _registered:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _registered = True


def install_metrics_listener() -> None:
    """Start counting XLA backend compiles into the unified registry
    without opening a sentinel — long-lived processes (the serving front
    door, hetero runtimes) call this once so ``xla_compiles_total`` is
    live for their whole lifetime."""
    _ensure_listener()


class RecompileSentinel:
    """Context manager counting XLA backend compiles while open."""

    def __init__(self, label: str = "") -> None:
        self.label = label
        self.compiles = 0
        self.events: List[str] = []
        self._lock = threading.Lock()

    def _record(self, event: str) -> None:
        with self._lock:
            self.compiles += 1
            self.events.append(event)

    def __enter__(self) -> RecompileSentinel:
        _ensure_listener()
        with _active_lock:
            _active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        with _active_lock:
            if self in _active:
                _active.remove(self)

    def assert_bound(self, bound: int, context: str = "") -> None:
        if self.compiles > bound:
            where = f" [{context or self.label}]" if (context or self.label) \
                else ""
            raise AssertionError(
                f"recompile sentinel{where}: {self.compiles} XLA compiles "
                f"observed, bound is {bound}")


def pow2_bucket_count(max_pages: int) -> int:
    """Number of distinct block-table widths the engine's pow2 bucketing
    (`_live_width` in sampling/continuous.py) can produce for a cap of
    ``max_pages`` pages — the analytic executable bound per (phase,
    batch-shape) family. Mirrors `_live_width` exactly: widths are
    min(next_pow2(need), cap) for need in 1..cap.
    """
    widths = set()
    for need in range(1, max_pages + 1):
        w = 1
        while w < need:
            w *= 2
        widths.add(min(w, max_pages))
    return len(widths)


def executable_bound(max_pages: int, phases: int = 3, slack: int = 4) -> int:
    """Conservative compile-count bound for a steady engine run:
    ``phases`` shape families (prefill chunk / decode chunk / page copy),
    each over the pow2 width buckets, plus ``slack`` for one-off helper
    jits (sampling kernels, logprob gather).
    """
    return phases * pow2_bucket_count(max_pages) + slack


def spec_verify_width_buckets(spec_k: int) -> int:
    """Distinct jitted verify widths speculative decoding can request.
    Mirrors the width computation in ``_spec_round``
    (sampling/continuous.py): the window holds 1 pending token plus
    0..spec_k drafts, bucketed through the same pow2 rounding as
    `_live_width` with a floor of 2 (width-1 windows would route to the
    decode path instead of the prefill-shaped one). Cross-checked
    against ``repro.sampling.spec.verify_width_buckets`` in tests.
    """
    widths = set()
    for k in range(spec_k + 1):
        need = 1 + k
        w = 1
        while w < need:
            w *= 2
        widths.add(max(2, min(w, spec_k + 1)))
    return len(widths)


def spec_verify_executable_bound(spec_k: int, max_pages: int) -> int:
    """Analytic ceiling on the spec engine's jitted round executables:
    verify compiles (``_verify_chunk_jit``) key on (verify width bucket,
    pow2 block-table width bucket), and no-draft fallback chunks
    (``_spec_decode_chunk_jit``) add one more family over the table-width
    buckets. Varying per-round acceptance lengths change neither key, so
    a steady spec-decode epoch compiles nothing new — the property
    tests/test_recompile.py asserts with this bound.
    """
    if spec_k <= 0:
        return 0
    return ((spec_verify_width_buckets(spec_k) + 1)
            * pow2_bucket_count(max_pages))


def prefill_executable_bound(prefill_chunk: int, max_pages: int) -> int:
    """Analytic ceiling on jitted prefill-chunk executables
    (``_prefill_chunk_jit``): each compile is keyed by
    (chunk width, pow2 block-table width bucket). Chunk widths are the
    configured ``prefill_chunk`` plus every shorter final tail a prompt
    can leave — at most ``prefill_chunk`` distinct values; table widths
    bucket through ``_live_width`` exactly as decode's do. Pass the
    engine's ``prefill_chunk`` (``None``/0 — whole-prompt prefill —
    degenerates to one width per distinct prompt length; this bound
    covers the chunked configuration the engine runs in production).
    """
    return (prefill_chunk or 1) * pow2_bucket_count(max_pages)


__all__ = ["RecompileSentinel", "pow2_bucket_count", "executable_bound",
           "prefill_executable_bound", "spec_verify_width_buckets",
           "spec_verify_executable_bound", "install_metrics_listener"]
