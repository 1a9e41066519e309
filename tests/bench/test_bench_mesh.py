"""A cell on four chips, on four virtual CPU devices: the learner reference
split over the cell's chips reads the one-device numbers to rounding and
holds no leaf whole on one device; the ``learn`` kind's run of a tiny
2x2 cell is correct, and with the exchange between chips left out
underneath (each data shard's update from its own rows alone) it is
not. One subprocess, so that the device-count flag never reaches other
tests."""
import json
import os
import subprocess
import sys
import textwrap

import pytest
from tiny import ROOT

SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/tests/bench"]
    import dataclasses, json
    import jax
    from jax.sharding import PartitionSpec as P
    from tiny import run_cell, tiny_cell
    from bench.kinds import learn
    from bench.lib import placement, program
    from bench.lib import weights as W

    SEED = 2 ** 31 + 1234
    cell = tiny_cell("learn", "gepo_learn_g4.qwen3-1.7b", tied=False)
    c = dict(cell.config)
    c["learner"] = dict(c["learner"], mesh="2x2", train=dict(
        c["learner"]["train"], logprob_impl="chunked"))
    cell = dataclasses.replace(cell, chips=4, config=c)
    t = cell.traffic
    v_pad = program.model_config(c).padded_vocab
    _, tc = learn.settings(cell, t["prompts"] * t["group_size"])
    mesh = placement.mesh(4)
    out = {{"one": learn.reference_readings(c, t, SEED, v_pad, tc),
            "four": learn.reference_readings(c, t, SEED, v_pad, tc,
                                             mesh=mesh)}}
    out["shards"] = {{
        n: [len(a.sharding.device_set), list(a.shape),
            list(a.addressable_shards[0].data.shape)]
        for n, a in W.make(c, SEED, v_pad, mesh).items()}}
    sound = run_cell(cell, SEED)
    out["sound"] = [sound["correct"], sound["checks"],
                    sound["window_compiles"]]

    import repro.training as training
    orig = training.train_step

    def no_exchange(cfg, rl, tc, state, batch, **kw):
        from repro.runtime_context import get_mesh
        m = get_mesh()
        local_tc = dataclasses.replace(
            tc, grad_accum=max(1, tc.grad_accum // m.shape["data"]))
        return jax.shard_map(
            lambda s, b: orig(cfg, rl, local_tc, s, b,
                              optimizer=kw["optimizer"]),
            mesh=m, in_specs=(P(), P("data")), out_specs=(P(), P()),
            check_vma=False)(state, batch)

    training.train_step = no_exchange
    broken = run_cell(cell, SEED)
    out["no_exchange"] = [broken["correct"], broken["checks"]]
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", SCRIPT.format(root=str(ROOT))],
                       env=env, capture_output=True, text=True, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


@pytest.mark.parametrize("reading", ["losses", "grad_norms", "change_norms"])
def test_reference_on_four_chips_reads_one_chip(result, reading):
    """To rounding: a loss is a sum of terms of order 1 over 8 rows that
    cancel to about 4e-3, so its rounding is absolute (about 1e-7); a
    norm's is relative."""
    one, four = result["one"][reading], result["four"][reading]
    if reading == "losses":
        one, four = dict(enumerate(one)), dict(enumerate(four))
    assert one.keys() == four.keys()
    for k in one:
        tol = 1e-6 if reading == "losses" else 1e-5 * abs(one[k])
        assert abs(one[k] - four[k]) <= tol, (k, one[k], four[k])


def test_reference_holds_no_leaf_whole_on_one_chip(result):
    for name, (devices, shape, shard) in result["shards"].items():
        assert devices == 4, name
        assert sum(shape) > sum(shard), (name, shape, shard)


def test_learn_cell_on_four_chips_is_correct(result):
    correct, checks, compiles = result["sound"]
    assert correct, checks
    assert compiles == 0


def test_exchange_left_out_is_not_correct(result):
    correct, checks = result["no_exchange"]
    assert not correct, checks
