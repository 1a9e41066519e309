"""The rollout cell's runner, past its look for a chip, at a tiny size on
the CPU: a sound run is correct; with a token altered where the engine
produces it, and with the control (the reference in float8 in the
program's place), the comparison comes out not correct."""
import jax.numpy as jnp
import numpy as np
from tiny import run_cell, tiny_cell

from bench.kinds import rollout

SEED = 2 ** 31 + 4321
CELL = "gepo_rollout.qwen3-1.7b"


def test_sound_run_is_correct():
    out = run_cell(tiny_cell("rollout", CELL), SEED)
    assert out["correct"], out["checks"]
    assert out["window_compiles"] == 0
    assert out["failed"] == 0 and out["attempted"] >= 8
    rec = out["record"]
    assert rec["decode_slot_steps"] == rec["generated_tokens"]


def test_altered_token_is_not_correct(monkeypatch):
    import repro.sampling.continuous as cont
    orig = cont._decode_chunk_jit

    def chunk(*a, **k):
        toks, lps, last, pool = orig(*a, **k)
        return jnp.where(toks > 3, toks + 1, toks), lps, last, pool

    monkeypatch.setattr(cont, "_decode_chunk_jit", chunk)
    out = run_cell(tiny_cell("rollout", CELL), SEED)
    assert not out["correct"], out["checks"]


def test_control_is_not_correct():
    cell = tiny_cell("rollout", CELL)
    out = run_cell(cell, SEED)
    assert out["correct"]
    # the window's served tokens, read again by the reference in float8
    from bench.lib import program
    c = cell.config
    cfg = program.model_config(c)
    served = out["served"]
    rows = rollout.check_rows(c, served, cell.traffic["serve"]["max_total_tokens"])
    ref = rollout.reference_readings(c, SEED, cfg.padded_vocab, rows)
    ctl = rollout.reference_readings(c, SEED, cfg.padded_vocab, rows, mm="fp8")
    v = rows["valid"]
    checks = {"logp_gap": float(np.max(np.abs(ctl["logp"] - ref["logp"])[v])),
              "token_gap": float(np.max(ctl["gap_of_best"][v]))}
    assert any(x > cell.limits[k] for k, x in checks.items()), checks
