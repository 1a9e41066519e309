"""Tests for the unified observability spine (repro.obs): registry
semantics, tracer span/flow behavior, Chrome-trace export validity, the
bounded-reservoir SLO percentiles, and the recompile-sentinel mirror."""
from __future__ import annotations

import json
import math
import threading

import numpy as np
import pytest

from repro.obs import (MetricsRegistry, Reservoir, Tracer, chrome_trace,
                       validate_chrome_trace, write_chrome_trace)
from repro.obs.registry import MAX_CHILDREN_PER_FAMILY
from repro.serving.api import GenerationResult
from repro.serving.telemetry import ServeTelemetry, percentile


# ---------------------------------------------------------------------------
class TestPercentile:
    def test_empty_is_nan(self):
        assert math.isnan(percentile([], 50))

    def test_all_nan_is_nan(self):
        assert math.isnan(percentile([float("nan")] * 3, 99))

    def test_single_element_any_q(self):
        for q in (0, 1, 50, 99, 100):
            assert percentile([7.0], q) == 7.0

    def test_q0_is_min_q100_is_max(self):
        vals = [5.0, 1.0, 3.0, 2.0, 4.0]
        assert percentile(vals, 0) == 1.0
        assert percentile(vals, 100) == 5.0

    def test_nearest_rank_median(self):
        assert percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.0
        assert percentile([1.0, 2.0, 3.0], 50) == 2.0

    def test_nan_values_filtered(self):
        assert percentile([float("nan"), 2.0, 1.0], 100) == 2.0

    def test_out_of_range_q_raises(self):
        with pytest.raises(ValueError):
            percentile([1.0], -0.1)
        with pytest.raises(ValueError):
            percentile([1.0], 100.1)

    def test_accepts_any_iterable(self):
        assert percentile(iter((3.0, 1.0)), 100) == 3.0


# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("reqs_total", "requests")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        g = reg.gauge("depth")
        g.set(4)
        g.add(-1)
        assert g.value == 3.0
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert h.counts == [1, 1, 1] and h.count == 3
        assert h.sum == pytest.approx(5.55)

    def test_counter_negative_inc_raises(self):
        reg = MetricsRegistry(enabled=True)
        with pytest.raises(ValueError):
            reg.counter("c").inc(-1)

    def test_gauge_add_from_unset_starts_at_value(self):
        reg = MetricsRegistry(enabled=True)
        g = reg.gauge("g")
        g.add(2.0)                 # NaN start must not propagate
        assert g.value == 2.0

    def test_histogram_skips_nan(self):
        reg = MetricsRegistry(enabled=True)
        h = reg.histogram("h")
        h.observe(float("nan"))
        assert h.count == 0

    def test_handles_are_idempotent_and_label_scoped(self):
        reg = MetricsRegistry(enabled=True)
        a = reg.counter("syncs_total", sampler=0)
        b = reg.counter("syncs_total", sampler=0)
        other = reg.counter("syncs_total", sampler=1)
        assert a is b and a is not other
        a.inc()
        assert other.value == 0.0

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry(enabled=True)
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_label_cardinality_capped(self):
        reg = MetricsRegistry(enabled=True)
        for i in range(MAX_CHILDREN_PER_FAMILY):
            reg.counter("burst_total", rid=i)
        with pytest.raises(ValueError):
            reg.counter("burst_total", rid=MAX_CHILDREN_PER_FAMILY)

    def test_disabled_mutators_are_noops(self):
        reg = MetricsRegistry(enabled=False)
        c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
        c.inc(5)
        g.set(1)
        h.observe(0.2)
        reg.set_many("pfx", {"a": 1.0})
        assert c.value == 0.0 and math.isnan(g.value) and h.count == 0

    def test_late_enable_flips_bound_handles(self):
        reg = MetricsRegistry(enabled=False)
        c = reg.counter("c")         # bound while disabled
        c.inc()
        reg.enabled = True
        c.inc()
        assert c.value == 1.0

    def test_clear_resets_values_but_keeps_bound_handles(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("c")
        h = reg.histogram("h", buckets=(1.0,))
        c.inc(3)
        h.observe(0.5)
        reg.clear()
        assert c.value == 0.0 and h.count == 0
        c.inc()                   # the pre-clear handle still records...
        assert reg.snapshot()["c"] == 1.0   # ...and exporters still see it

    def test_set_many_fans_into_gauges(self):
        reg = MetricsRegistry(enabled=True)
        reg.set_many("learner", {"kl": 0.1, "skipme": "not-a-number"},
                     sampler=2)
        snap = reg.snapshot()
        assert snap['learner_kl{sampler="2"}'] == pytest.approx(0.1)
        assert not any("skipme" in k for k in snap)

    def test_prometheus_text_format(self):
        reg = MetricsRegistry(enabled=True)
        reg.counter("reqs_total", "requests served").inc(2)
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.prometheus_text()
        assert "# HELP reqs_total requests served" in text
        assert "# TYPE reqs_total counter" in text
        assert "reqs_total 2" in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text     # cumulative
        assert 'lat_seconds_bucket{le="1"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 3' in text
        assert "lat_seconds_count 3" in text

    def test_name_sanitized(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("bad-name.with spaces")
        assert c.name == "bad_name_with_spaces"

    def test_concurrent_incs_are_exact(self):
        reg = MetricsRegistry(enabled=True)
        c = reg.counter("c")

        def worker():
            for _ in range(1000):
                c.inc()
        ts = [threading.Thread(target=worker) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 4000.0


# ---------------------------------------------------------------------------
class TestReservoir:
    def test_exact_below_capacity(self):
        r = Reservoir(capacity=10)
        for v in range(5):
            r.append(v)
        assert r.values == [0.0, 1.0, 2.0, 3.0, 4.0] and r.n == 5

    def test_bounded_beyond_capacity(self):
        r = Reservoir(capacity=16, seed=3)
        for v in range(10_000):
            r.add(v)
        assert len(r) == 16 and r.n == 10_000
        assert all(0 <= v < 10_000 for v in r)

    def test_seed_determinism(self):
        a, b = Reservoir(8, seed=7), Reservoir(8, seed=7)
        for v in range(1000):
            a.add(v)
            b.add(v)
        assert a.values == b.values

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            Reservoir(capacity=0)


# ---------------------------------------------------------------------------
class _FakeSim:
    def __init__(self):
        self.now = 0.0


class TestTracer:
    def test_disabled_span_is_shared_noop(self):
        tr = Tracer(enabled=False)
        s1, s2 = tr.span("a"), tr.span("b", slot=1)
        assert s1 is s2                     # no allocation when disabled
        with s1:
            pass
        assert len(tr) == 0
        tr.complete("c", 0.0, 1.0)
        tr.async_begin("f", 1)
        assert len(tr) == 0

    def test_span_records_duration_and_args(self):
        tr = Tracer(enabled=True)
        with tr.span("prefill", track="engine", slot=3):
            pass
        (ev,) = tr.events()
        assert ev["ph"] == "X" and ev["name"] == "prefill"
        assert ev["dur"] >= 0.0 and ev["track"] == "engine"
        assert ev["args"]["slot"] == 3

    def test_span_nesting_orders_child_first(self):
        tr = Tracer(enabled=True)
        with tr.span("outer", track="learner"):
            with tr.span("inner", track="learner"):
                pass
        inner, outer = tr.events()
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["track"] == outer["track"] == "learner"
        assert outer["dur"] >= inner["dur"]
        assert outer["ts"] <= inner["ts"]

    def test_span_exception_safe_and_tagged(self):
        tr = Tracer(enabled=True)
        with pytest.raises(RuntimeError):
            with tr.span("step"):
                raise RuntimeError("boom")
        (ev,) = tr.events()
        assert ev["args"]["error"] == "RuntimeError"
        assert ev["dur"] >= 0.0             # still closed with a duration

    def test_sim_clock_drives_timestamps(self):
        tr = Tracer(enabled=True)
        sim = _FakeSim()
        tr.use_sim(sim)
        sim.now = 5.0
        with tr.span("gen"):
            sim.now = 7.5
        (ev,) = tr.events()
        assert ev["ts"] == 5.0 and ev["dur"] == 2.5
        tr.use_wall_clock()
        assert tr.now() != 5.0 or tr.now() >= 0.0

    def test_complete_emits_explicit_window(self):
        tr = Tracer(enabled=True)
        tr.complete("step_window", 10.0, 38.125, track="learner", step=3)
        (ev,) = tr.events()
        assert ev["ts"] == 10.0 and ev["dur"] == pytest.approx(28.125)

    def test_async_flow_ids_are_unique(self):
        tr = Tracer(enabled=True)
        ids = {tr.next_flow_id() for _ in range(100)}
        assert len(ids) == 100
        fid = tr.next_flow_id()
        tr.async_begin("chunk", fid, cat="transport", ts=1.0, bytes=64)
        tr.async_end("chunk", fid, cat="transport", ts=2.0)
        b, e = tr.events()
        assert b["ph"] == "b" and e["ph"] == "e" and b["id"] == e["id"]

    def test_ring_buffer_bounds_memory(self):
        tr = Tracer(enabled=True, max_events=8)
        for i in range(100):
            tr.complete(f"i{i}", 0.0, 1.0)
        assert len(tr) == 8
        assert tr.events()[0]["name"] == "i92"   # oldest fell off


# ---------------------------------------------------------------------------
def _profiled_host_events(log_dir):
    """(name, stats) of every host event in the newest profile under
    ``log_dir``."""
    import glob
    import os

    import jax
    f = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)[-1]
    pd = jax.profiler.ProfileData.from_file(f)
    return [(e.name, dict(e.stats)) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


class TestProfilerSink:
    """While a JAX profiler session runs, spans also land on its
    timeline as ``repro.<track>.<name>`` annotations."""

    def test_profiler_gets_span_with_obs_off(self, tmp_path):
        import jax
        tr = Tracer(enabled=False)
        jax.profiler.start_trace(str(tmp_path))
        try:
            span = tr.span("decode", track="engine", slots=3, width=8)
            with span:
                pass
            with tr.span("bare"):
                pass
        finally:
            jax.profiler.stop_trace()
        assert span is not tr.span("decode")   # a real span while profiling
        assert len(tr) == 0                      # the ring buffer stays off
        ev = dict(_profiled_host_events(tmp_path))
        assert ev["repro.engine.decode"] == {"slots": 3, "width": 8}
        assert "repro.bare" in ev

    def test_both_sinks_with_obs_on(self, tmp_path):
        import jax
        tr = Tracer(enabled=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("step", track="engine"):
                with tr.span("sync", track="engine"):
                    pass
        finally:
            jax.profiler.stop_trace()
        assert [e["name"] for e in tr.events()] == ["sync", "step"]
        names = [n for n, _ in _profiled_host_events(tmp_path)]
        assert "repro.engine.step" in names and "repro.engine.sync" in names

    def test_noop_once_profiler_stops(self, tmp_path):
        import jax
        tr = Tracer(enabled=False)
        jax.profiler.start_trace(str(tmp_path))
        jax.profiler.stop_trace()
        assert tr.span("a") is tr.span("b", track="engine", slot=1)

    def test_span_error_still_closes_annotation(self, tmp_path):
        import jax
        tr = Tracer(enabled=False)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with pytest.raises(RuntimeError):
                with tr.span("commit", track="engine"):
                    raise RuntimeError("boom")
        finally:
            jax.profiler.stop_trace()
        assert "repro.engine.commit" in dict(_profiled_host_events(tmp_path))

    def test_profiler_names(self):
        from repro.obs.trace import profiler_name
        assert profiler_name("step", "engine") == "repro.engine.step"
        assert profiler_name("learner_step") == "repro.learner_step"

    def test_pinned_track_names_ring_events(self):
        tr = Tracer(enabled=True)
        out = {}

        def worker():
            tr.set_track("sampler-1")
            with tr.span("sampler_generate"):
                pass
            out["track"] = tr.current_track()

        t = threading.Thread(target=worker, name="t0")
        t.start()
        t.join()
        assert out["track"] == "sampler-1"
        assert tr.events()[0]["track"] == "sampler-1"
        assert tr.current_track() == threading.current_thread().name


# ---------------------------------------------------------------------------
class TestExport:
    def _traced(self, sim=False):
        tr = Tracer(enabled=True)
        if sim:
            s = _FakeSim()
            tr.use_sim(s)
            s.now = 1.0
        with tr.span("learner_step", track="learner", step=1):
            pass
        with tr.span("sampler_generate", track="sampler-0"):
            pass
        fid = tr.next_flow_id()
        tr.async_begin("chunk_transfer", fid, ts=0.1)
        tr.async_end("chunk_transfer", fid, ts=0.2)
        return tr

    def test_chrome_trace_tracks_map_to_tids(self):
        obj = chrome_trace(self._traced())
        names = {e["args"]["name"] for e in obj["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"learner", "sampler-0"} <= names
        tids = {e["tid"] for e in obj["traceEvents"] if e["ph"] != "M"}
        assert len(tids) >= 2

    def test_write_and_validate_roundtrip(self, tmp_path):
        p = str(tmp_path / "trace.json")
        n = write_chrome_trace(self._traced(), p)
        assert validate_chrome_trace(p) == n == 4

    def test_sim_clock_trace_validates_identically(self, tmp_path):
        p = str(tmp_path / "sim_trace.json")
        write_chrome_trace(self._traced(sim=True), p)
        assert validate_chrome_trace(p) == 4
        with open(p) as f:
            obj = json.load(f)
        xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
        assert all(e["ts"] == pytest.approx(1e6) for e in xs)  # sim µs

    def test_validate_rejects_malformed(self, tmp_path):
        p = str(tmp_path / "bad.json")
        with open(p, "w") as f:
            json.dump({"traceEvents": [{"ph": "X", "ts": 0.0}]}, f)
        with pytest.raises(ValueError):
            validate_chrome_trace(p)        # missing name
        with open(p, "w") as f:
            json.dump({"traceEvents": [
                {"name": "a", "ph": "X", "ts": 0.0}]}, f)
        with pytest.raises(ValueError):
            validate_chrome_trace(p)        # duration event missing dur
        with open(p, "w") as f:
            json.dump({"traceEvents": [
                {"name": "a", "ph": "b", "ts": 0.0}]}, f)
        with pytest.raises(ValueError):
            validate_chrome_trace(p)        # async event missing id


# ---------------------------------------------------------------------------
class TestSentinelMirror:
    def test_compile_events_count_into_registry(self):
        from repro import obs
        from repro.analysis import sentinel
        was = obs.metrics.enabled
        obs.metrics.enabled = True
        try:
            before = sentinel._M_COMPILES.value
            sentinel._on_event(sentinel._COMPILE_EVENT, 0.25)
            sentinel._on_event("/jax/unrelated/event", 0.25)
            assert sentinel._M_COMPILES.value == before + 1
            assert sentinel._M_COMPILE_SECONDS.value >= 0.25
        finally:
            obs.metrics.enabled = was

    def test_install_metrics_listener_idempotent(self):
        from repro.analysis.sentinel import install_metrics_listener
        install_metrics_listener()
        install_metrics_listener()          # must not double-register


# ---------------------------------------------------------------------------
def _result(i: int, ttft: float, lat: float) -> GenerationResult:
    return GenerationResult(rid=i, tokens=np.zeros(3, np.int32),
                            logps=np.zeros(3, np.float32),
                            finish_reason="eos", prompt_len=4,
                            prefix_hit_tokens=2, ttft_s=ttft, latency_s=lat)


class TestServeTelemetryBounded:
    def test_reservoirs_bound_memory(self):
        reg = MetricsRegistry(enabled=True)
        tel = ServeTelemetry(2, registry=reg, reservoir_capacity=32)
        for i in range(1000):
            tel.record(_result(i, ttft=0.01 * i, lat=0.02 * i), done_s=i)
        assert len(tel.ttfts) == 32 and len(tel.latencies) == 32
        assert tel.completed == 1000
        snap = tel.snapshot()
        assert 0.0 <= snap["ttft_p50_s"] <= 0.01 * 999
        assert snap["tokens_out"] == 3000

    def test_registry_mirror(self):
        reg = MetricsRegistry(enabled=True)
        tel = ServeTelemetry(2, registry=reg)
        tel.record(_result(0, 0.01, 0.05), done_s=0.0)
        tel.record(GenerationResult(rid=1, tokens=np.zeros(0, np.int32),
                                    logps=np.zeros(0, np.float32),
                                    finish_reason="expired", prompt_len=4))
        snap = reg.snapshot()
        assert snap["serve_requests_completed_total"] == 1
        assert snap["serve_requests_expired_total"] == 1
        assert snap["serve_ttft_seconds_count"] == 1
        assert "serve_ttft_seconds" in reg.prometheus_text()

    def test_deterministic_percentiles_same_seed(self):
        reg = MetricsRegistry(enabled=False)
        a = ServeTelemetry(1, registry=reg, reservoir_capacity=16, seed=5)
        b = ServeTelemetry(1, registry=reg, reservoir_capacity=16, seed=5)
        for i in range(500):
            a.record(_result(i, 0.001 * i, 0.002 * i))
            b.record(_result(i, 0.001 * i, 0.002 * i))
        assert a.snapshot()["ttft_p99_s"] == b.snapshot()["ttft_p99_s"]

    def test_default_capacity_matches_contract(self):
        reg = MetricsRegistry(enabled=False)
        tel = ServeTelemetry(1, registry=reg)
        assert tel.reservoir_capacity == 4096


# ---------------------------------------------------------------------------
class TestConfigure:
    def test_module_configure_flips_and_restores(self):
        from repro import obs
        assert not obs.metrics.enabled and not obs.trace.enabled
        try:
            obs.configure(True, clear=True)
            assert obs.enabled()
            with obs.trace.span("x"):
                pass
            assert len(obs.trace) == 1
            sim = _FakeSim()
            obs.configure(True, sim=sim, clear=True)
            sim.now = 3.0
            assert obs.trace.now() == 3.0
        finally:
            obs.configure(False, clear=True)
        assert not obs.enabled()
        assert obs.trace.now() != 3.0       # wall clock restored
