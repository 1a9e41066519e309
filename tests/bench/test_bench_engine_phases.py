"""The engine's host phases on the profiler's clock: a profiled tiny
rollout window carries one ``repro.engine.step`` per scheduler round with
its phases nested in it, the benchmark's own reader still keeps only its
``bench.`` spans, and the two readings give hand-computed answers on a
made-up timeline."""
import math

import pytest
from tiny import ROOT, Args, tiny_cell

from bench.lib import engine_phases as EP
from bench.lib import spec
from bench.lib import trace as T

SEED = 2 ** 31 + 4321
CELL = "gepo_rollout.qwen3-1.7b"
PHASES = {"repro.engine." + p for p in
          ("step", "admit", "prefill", "decode", "sync", "commit")}


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A tiny rollout cell's window, profiled on the CPU."""
    from bench import run as harness
    log_dir = str(tmp_path_factory.mktemp("profile"))
    env = harness.Env(log_dir)
    env.chips = 1
    cell = tiny_cell("rollout", CELL)
    out = spec.kind_module("rollout").run(cell, Args(SEED), env)
    return out, cell, T.load(log_dir), EP.load(log_dir)


def test_engine_spans_nest_in_steps(profiled):
    out, cell, tr, prog = profiled
    lo, hi = tr.window()
    prog = [p for p in prog if lo <= p[1] < hi]
    assert {n for n, _, _ in prog} == PHASES
    steps = sorted((s, s + d) for n, s, d in prog if n == EP.STEP)
    for n, s, d in prog:
        if n != EP.STEP:
            assert any(a <= s and s + d <= b for a, b in steps), n
    count = {n: sum(1 for m, _, _ in prog if m == n) for n in PHASES}
    assert count["repro.engine.admit"] == len(steps)
    assert count["repro.engine.sync"] == count["repro.engine.decode"]
    assert count["repro.engine.commit"] == count["repro.engine.decode"]
    sync_every = 8                      # ServeConfig's default decode horizon
    assert count["repro.engine.decode"] * sync_every == \
        out["record"]["decode_steps"]
    # a step opens at most 5 spans and one per prefill chunk
    for a, b in steps:
        inner = [n for n, s, _ in prog if a < s < b]
        assert len(inner) - inner.count("repro.engine.prefill") <= 4


def test_benchmark_reader_keeps_only_its_spans(profiled):
    _, _, tr, prog = profiled
    assert {n for n, _, _ in tr.host} <= {"bench.window",
                                          "bench.build_requests",
                                          "bench.generate"}
    assert prog and all("#" not in n for n, _, _ in prog)


def test_cpu_profile_has_no_device_to_read(profiled):
    _, _, tr, prog = profiled
    assert tr.devices == {}
    assert EP.host_gap_share(tr, prog) is None
    assert EP.host_ms_per_chunk(tr, prog) > 0


def made_up():
    """Window 0..100 ns. The device is busy [0,20), [30,60), [70,100):
    idle [20,30) and [60,70). Step 1 [10,50) syncs over [18,25), so the
    first gap straddles the sync's end; step 2 [55,95) syncs over
    [58,65), so the second gap straddles that sync's start. A step before
    the window does not count."""
    dev = T.Device(ops=[("%a fusion", 0, 20), ("%b fusion", 30, 30),
                        ("%c fusion", 70, 30)])
    tr = T.Trace(devices={"/device:TPU:0": dev},
                 host=[("bench.window", 0, 100), ("bench.generate", 0, 100)])
    e = "repro.engine."
    prog = [(e + "step", -40, 30), (e + "decode", -35, 5),
            (e + "step", 10, 40), (e + "admit", 10, 2),
            (e + "decode", 12, 6), (e + "sync", 18, 7),
            (e + "commit", 25, 10),
            (e + "step", 55, 40), (e + "admit", 55, 1),
            (e + "prefill", 56, 2), (e + "decode", 58, 0),
            (e + "sync", 58, 7), (e + "commit", 65, 7)]
    return tr, prog


def test_readings_by_hand():
    tr, prog = made_up()
    # idle and outside sync inside a step: [25,30) and [65,70) of 100 ns
    assert EP.host_gap_share(tr, prog) == pytest.approx(10.0)
    # (40 + 40) ns of steps less (7 + 7) ns of sync over 2 chunks
    assert EP.host_ms_per_chunk(tr, prog) == pytest.approx(33e-6)
    ms = EP.phase_ms(prog, 0, 100)
    assert ms["step"] == pytest.approx((15 + 23) * 1e-6)
    assert ms["sync"] == pytest.approx(14e-6)
    longest = EP.longest_steps(prog, 0, 100, k=1)[0]
    assert longest["ms"] == pytest.approx(40e-6)
    assert longest["at_ms"] == pytest.approx(10e-6)
    assert longest["phases"]["commit"] == pytest.approx(10e-6)


def test_readings_need_engine_spans():
    tr, _ = made_up()
    assert EP.host_gap_share(tr, []) is None
    assert EP.host_ms_per_chunk(tr, []) is None


def test_gap_share_never_exceeds_idle_share():
    tr, prog = made_up()
    idle = 100.0 * (1.0 - T.busy_share(tr))
    assert EP.host_gap_share(tr, prog) <= idle == pytest.approx(20.0)


def test_interval_arithmetic():
    assert EP.minus([(0, 10), (20, 30)], [(2, 4), (8, 22), (25, 26)]) == \
        [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert EP.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10), (20, 25)]
    assert EP.base_name("repro.engine.decode#slots=3,width=8#") == \
        "repro.engine.decode"


def test_outermost_ops_keep_the_busy_union():
    ops = [("%while.1 while", 0, 50), ("%fusion.2 fusion", 5, 10),
           ("%fusion.3 fusion", 45, 10), ("%copy.4 copy", 60, 5)]
    kept = EP.outermost(ops)
    assert [o[0] for o in kept] == ["%while.1 while", "%fusion.3 fusion",
                                    "%copy.4 copy"]
    assert T.busy_ns(T.Device(ops=kept), 0, 100) == \
        T.busy_ns(T.Device(ops=ops), 0, 100) == 60


def test_small_copy_round_trips(tmp_path):
    tr, prog = made_up()
    path = str(tmp_path / "small.json")
    got = EP.save_small(tr, prog, path, first=0, steps=2)
    tr2, prog2, saved = EP.load_small(path)
    assert saved == got == EP.readings(tr2, prog2)
    assert tr2.window() == (10, 95)
    assert all(math.isfinite(v) for v in saved.values())
    # the cut's idle time is the whole trace's idle time inside the cut
    dev, dev2 = tr.devices["/device:TPU:0"], tr2.devices["/device:TPU:0"]
    assert T.idle_gaps(dev2, 10, 95) == T.idle_gaps(dev, 10, 95)


def test_recorded_engine_trace():
    """Four decode steps of the rollout cell's window, recorded on a TPU
    v5e (``bench/phases.py --save``): the readings come out as recorded,
    finite, and the gap share is no more than the device's idle share."""
    tr, prog, saved = EP.load_small(str(ROOT / "bench" / "data"
                                        / "trace_small_engine.json"))
    got = EP.readings(tr, prog)
    assert got == pytest.approx(saved)
    assert all(math.isfinite(v) for v in got.values())
    assert 0 < got["engine_host_gap_share"] <= 100.0 * (1 - T.busy_share(tr))
    assert got["engine_host_ms_per_chunk"] > 0
    lo, hi = tr.window()
    assert {n for n, _, _ in prog} == PHASES - {"repro.engine.prefill"}
    steps = [p for p in prog if p[0] == EP.STEP]
    assert len(steps) == 4 and all(lo <= s and s + d <= hi
                                   for _, s, d in steps)
