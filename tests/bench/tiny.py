"""A tiny cell of each kind, for running the benchmark's cell runners on the
CPU: the published configuration's keys at toy sizes, the program's
Qwen3 entry cut to match, float32 so that the program and the reference
agree to rounding."""
from __future__ import annotations

import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.lib import spec  # noqa: E402

TINY_MODEL = {
    "name": "tiny-qwen3", "source": "test", "model_type": "qwen3",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
    "initializer_range": 0.02, "tie_word_embeddings": True,
    "torch_dtype": "float32", "reduced": [],
}
PROGRAM = {"arch": "qwen3-1.7b",
           "matches": {"hidden_size": "d_model", "intermediate_size": "d_ff",
                       "num_hidden_layers": "num_layers",
                       "num_attention_heads": "num_heads",
                       "num_key_value_heads": "num_kv_heads",
                       "head_dim": "head_dim", "vocab_size": "vocab_size",
                       "tie_word_embeddings": "tie_embeddings"}}


def tiny_config(tied: bool = True) -> dict:
    c = dict(TINY_MODEL, tie_word_embeddings=tied)
    c["program"] = dict(PROGRAM, overrides={
        "num_layers": 2, "d_model": 64, "d_ff": 128, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "vocab_size": 512,
        "tie_embeddings": tied, "dtype": "float32", "attn_chunk": 16})
    c["learner"] = {"optimizer": "adafactor", "micro_batch_rows": 4,
                    "mesh": "1x1",
                    "train": {"learning_rate": 0.001, "warmup_frac": 0.0}}
    return c


def tiny_traffic(kind: str) -> dict:
    mix = "gepo_learn_g4" if kind == "learn" else "gepo_rollout"
    t = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    t = copy.deepcopy(t)
    t.update(prompts=2, group_size=4,
             prompt_len={"low": 8, "high": 16},
             completion_len={"median": 8, "sigma": 0.5, "cap": 16})
    if kind == "learn":
        t.update(width=33, check_steps=2)
        t["rl"]["group_size"] = 4
    else:
        t.update(serve={"num_slots": 8, "max_total_tokens": 48},
                 check_requests=4)
    return t


# At this size the float32 program and the float32 reference agree to
# about 1e-6 (readings of sound runs: loss 1e-7, norms 3e-7, log-probs
# 5e-7, token gap 0); a limit of 1e-3 leaves that a factor of 1000.
TINY_LIMITS = {"loss_gap": 1e-3, "grad_gap": 1e-3, "update_gap": 1e-3,
               "logp_gap": 1e-3, "token_gap": 1e-3}


def tiny_cell(kind: str, like: str, tied: bool = True) -> spec.Cell:
    """A tiny cell that compares the numbers of the committed cell
    ``like``, each held to its tiny limit."""
    lim = json.loads((ROOT / "bench" / "limits" / f"{like}.json").read_text())
    return spec.Cell(name=f"tiny.{kind}", chips=1, config=tiny_config(tied),
                     traffic=tiny_traffic(kind),
                     limits={k: TINY_LIMITS[k] for k in lim},
                     end_to_end=[], per_layer=[])


class Args:
    def __init__(self, seed: int, seconds: float = 0.5) -> None:
        self.seed, self.seconds, self.trace = seed, seconds, 0


def run_cell(cell: spec.Cell, seed: int, seconds: float = 0.5) -> dict:
    """Drive the cell's kind as ``bench/run.py`` does, past its look for
    a chip."""
    from bench import run as harness
    env = harness.Env(None)
    env.chips = cell.chips
    kind = spec.kind_module(cell.kind)
    out = kind.run(cell, Args(seed, seconds), env)
    _, _, out["correct"] = harness.judge(out["checks"], cell.limits,
                                         env.window_compiles)
    out["window_compiles"] = env.window_compiles
    return out
