"""The learner cell's runner, past its look for a chip, at a tiny size on
the CPU: a sound run is correct; with the timed path broken underneath
(a step that returns its state unchanged; half the batch left out, the
mean taken over the rest) and with the control (the reference in float8
in the program's place) the comparison comes out not correct."""
import dataclasses

import pytest
from tiny import run_cell, tiny_cell

from bench.kinds import learn

SEED = 2 ** 31 + 1234
CELL = "gepo_learn_g4.qwen3-1.7b"


@pytest.mark.parametrize("tied", [True, False])
def test_sound_run_is_correct(tied):
    out = run_cell(tiny_cell("learn", CELL, tied=tied), SEED)
    assert out["correct"], out["checks"]
    assert out["window_compiles"] == 0
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["e2e"]["learner_tokens_per_s"] > 0


@pytest.fixture
def broken(monkeypatch):
    import repro.training as training
    orig = training.train_step

    def install(kind):
        def step(cfg, rl, tc, state, batch, **kw):
            if kind == "unchanged":
                _, m = orig(cfg, rl, tc, state, batch, **kw)
                return state, m
            half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
            tc = dataclasses.replace(tc, grad_accum=max(1, tc.grad_accum // 2))
            return orig(cfg, rl, tc, state, half, **kw)
        monkeypatch.setattr(training, "train_step", step)
    return install


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(broken, fault):
    broken(fault)
    out = run_cell(tiny_cell("learn", CELL), SEED)
    assert not out["correct"], (fault, out["checks"])


def test_control_is_not_correct():
    cell = tiny_cell("learn", CELL)
    from bench.lib import program
    c, t = cell.config, cell.traffic
    cfg = program.model_config(c)
    _, tc = learn.settings(cell, t["prompts"] * t["group_size"])
    ref = learn.reference_readings(c, t, SEED, cfg.padded_vocab, tc)
    ctl = learn.reference_readings(c, t, SEED, cfg.padded_vocab, tc, mm="fp8")
    checks = learn.compare(ctl, ref)
    assert any(v > cell.limits[k] for k, v in checks.items()), checks
