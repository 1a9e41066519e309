"""The trace reduction: busy union, idle gaps and their host labels,
time by operation name and scope, and programs by name. Checked on a
hand-made trace with known answers, and on a small trace recorded on a
TPU v5e (``bench/data/trace_small.json``) against a plain timeline."""
import json

import numpy as np
import pytest
from tiny import ROOT

from bench.lib import trace as T

RECORDED = ROOT / "bench" / "data" / "trace_small.json"


def hand_made():
    # window 0..100 ns; ops: [10,30) and [20,40) overlap, [60,70); a
    # second device busy [0,50)
    dev0 = T.Device(ops=[("%fusion.1 fusion", 10, 20),
                         ("%fused_logprob_fwd.3 custom-call", 20, 20),
                         ("%fusion.1 fusion", 60, 10),
                         ("%while.2 while", 5, 70)],       # encloses the rest
                    modules=[("jit_step(1)", 5, 70)])
    dev1 = T.Device(ops=[("%all-reduce.4 all-reduce", 0, 50)],
                    modules=[("jit_step(1)", 0, 50)])
    host = [("bench.window", 0, 100), ("bench.dispatch", 0, 12),
            ("bench.wait", 40, 25), ("bench.build_batch", 70, 30),
            ("other", 0, 100)]
    return T.Trace(devices={"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                   host=host)


def without_while(tr):
    d = tr.devices["/device:TPU:0"]
    d.ops = [o for o in d.ops if not T.is_container(o[0])]
    return tr


def test_short_names():
    assert T.short_name("%copy.48 = bf16[64,8]{1,0:T(8,128)(2,1)} copy("
                        "bf16[64,8]{1,0} %bitcast.301)") == "%copy.48 copy"
    assert T.short_name("%fused_logprob_bwd.11 = f32[8,4]{1,0} custom-call("
                        "f32[8,4] %a), custom_call_target=\"tpu_custom_call\""
                        ) == "%fused_logprob_bwd.11 custom-call"
    assert T.is_container("%while.37 while")


def test_busy_union_and_share():
    tr = without_while(hand_made())
    assert T.busy_ns(tr.devices["/device:TPU:0"], 0, 100) == 40
    assert T.busy_ns(tr.devices["/device:TPU:0"], 25, 65) == 20   # clipped
    assert T.busy_share(tr) == pytest.approx((0.4 + 0.5) / 2)
    # an enclosing while op covers its body's gaps
    assert T.busy_ns(hand_made().devices["/device:TPU:0"], 0, 100) == 70


def test_idle_gaps_and_labels():
    tr = without_while(hand_made())
    gaps = T.idle_gaps(tr.devices["/device:TPU:0"], 0, 100)
    assert gaps == [(0, 10), (40, 60), (70, 100)]
    assert T.label(tr, 0, 10) == "dispatch"
    assert T.label(tr, 40, 60) == "wait"
    assert T.label(tr, 70, 100) == "build_batch"
    longest = T.longest_gaps(tr, k=2)
    assert [n for n, _ in longest] == ["build_batch", "wait"]
    assert [s for _, s in longest] == pytest.approx([30e-9, 20e-9])


def test_time_by_name_and_scope():
    tr = hand_made()
    ops = T.op_seconds(tr)            # averaged over 2 devices, no while
    assert set(ops) == {"%fusion.1 fusion", "%fused_logprob_fwd.3 custom-call",
                        "%all-reduce.4 all-reduce"}
    assert ops["%fusion.1 fusion"] == pytest.approx(15e-9)
    assert ops["%all-reduce.4 all-reduce"] == pytest.approx(25e-9)
    assert T.op_seconds(tr, ["fused_logprob"]) == {
        "%fused_logprob_fwd.3 custom-call": pytest.approx(10e-9)}
    assert T.top_ops(tr, 1)[0][0] == "%all-reduce.4 all-reduce"
    assert T.module_seconds(tr, "jit_step") == (pytest.approx(60e-9), 1)


def test_collective_exposed_share():
    """Window 0..100 ns. Device 0: an all-reduce [10, 50) under a fusion
    [0, 30) and a while loop [0, 100) that covers nothing: 20 ns exposed;
    an all-gather's halves [60, 62) and [80, 90) under a copy [85, 95):
    7 ns. Device 1: an all-reduce-scatter fusion [40, 120), cut at the
    window's end, none of it covered: 60 ns. Mean (27 + 60) / 2 / 100."""
    dev0 = T.Device(ops=[("%while.1 while", 0, 100),
                         ("%fusion.2 fusion", 0, 30),
                         ("%all-reduce.3 all-reduce", 10, 40),
                         ("%all-gather-start.4 all-gather-start", 60, 2),
                         ("%all-gather-done.5 all-gather-done", 80, 10),
                         ("%copy.6 copy", 85, 10)])
    dev1 = T.Device(ops=[("%fusion.7 fusion", 0, 40),
                         (T.short_name("%fusion.8 = bf16[8]{0} fusion(bf16[8]"
                                       " %a), kind=kCustom, calls="
                                       "%all-reduce-scatter.2"), 40, 80)])
    tr = T.Trace(devices={"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                 host=[("bench.window", 0, 100)])
    assert T.collective_exposed_ns(dev0, 0, 100) == 27
    assert T.collective_exposed_ns(dev1, 0, 100) == 60
    assert T.collective_exposed_share(tr) == pytest.approx(0.435)
    from bench.lib import spec
    reader = spec.metric_reader("collective_exposed_share")
    assert reader.read({"kind": "learn", "trace": tr}) == pytest.approx(43.5)
    # the other hand-made trace: device 1's all-reduce [0, 50), bare
    assert reader.read({"kind": "learn", "trace": hand_made()}) == 25.0
    # no collective in the window: nothing to read
    quiet = T.Trace(devices={"/device:TPU:0": T.Device(
        ops=[("%fusion.1 fusion", 0, 50)])}, host=[("bench.window", 0, 100)])
    assert reader.read({"kind": "learn", "trace": quiet}) is None


def test_json_round_trip(tmp_path):
    tr = hand_made()
    p = tmp_path / "t.json"
    p.write_text(json.dumps(tr.to_json()))
    back = T.Trace.from_json(json.loads(p.read_text()))
    assert T.busy_share(back) == T.busy_share(tr)
    assert T.longest_gaps(back) == T.longest_gaps(tr)


def timeline_busy(dev, lo, hi, step):
    """Busy time on a plain grid: a slot is busy if any op covers it."""
    n = int((hi - lo) // step)
    grid = np.zeros(n, bool)
    for _, s, d in dev.ops:
        a = max(int(np.ceil((s - lo) / step)), 0)
        b = min(int(np.ceil((s + d - lo) / step)), n)
        grid[a:b] = True
    return grid.sum() * step


def test_recorded_trace():
    tr = T.Trace.from_json(json.loads(RECORDED.read_text()))
    lo, hi = tr.window()
    assert hi > lo and tr.devices
    for dev in tr.devices.values():
        got = T.busy_ns(dev, lo, hi)
        want = timeline_busy(dev, lo, hi, step=(hi - lo) / 200_000)
        assert got == pytest.approx(want, rel=0.02, abs=(hi - lo) / 50_000)
        gaps = T.idle_gaps(dev, lo, hi)
        assert sum(e - s for s, e in gaps) + got == pytest.approx(hi - lo)
    share = T.busy_share(tr)
    assert 0.0 < share <= 1.0
    for name, sec in T.longest_gaps(tr):
        assert isinstance(name, str) and sec >= 0
