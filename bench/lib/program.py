"""The benchmark's side of the interface to the program under test: the
program's model configuration as the configuration file states it, and
the benchmark's weights handed over in the program's parameter layout.
The program's own code is imported here and in ``bench/kinds`` only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax

from bench.lib import weights as W


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for configuration file ``c``: its
    registry entry with the file's overrides, checked against every
    published number the file maps onto it."""
    from repro.configs import get_config
    prog = c["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    for key, field in prog["matches"].items():
        if getattr(cfg, field) != c[key]:
            raise ValueError(f"{c['name']}: program {field}="
                             f"{getattr(cfg, field)!r}, published {key}="
                             f"{c[key]!r}")
    if cfg.dtype != c["torch_dtype"]:
        raise ValueError(f"{c['name']}: program dtype {cfg.dtype}, "
                         f"published {c['torch_dtype']}")
    return cfg


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    """Benchmark layout -> the program's parameter tree (a renaming; the
    arrays are the same)."""
    tree = {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "blocks": {"layer_0": {
            "norm": w["attn_norm"],
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ffn_norm": w["mlp_norm"],
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }},
    }
    if "lm_head" in w:
        tree["lm_head"] = w["lm_head"]
    return tree


def program_leaf_names(tree: Dict[str, Any]) -> Dict[str, Any]:
    """Benchmark leaf name -> the same leaf of a program-layout tree."""
    b = tree["blocks"]["layer_0"]
    out = {"embed": tree["embed"], "final_norm": tree["final_norm"],
           "attn_norm": b["norm"], "mlp_norm": b["ffn_norm"],
           **b["attn"], **b["mlp"]}
    if "lm_head" in tree:
        out["lm_head"] = tree["lm_head"]
    return out


def check_layout(cfg, c: Dict[str, Any]) -> None:
    """The program's parameter tree has exactly the benchmark's leaves,
    shapes and dtype."""
    from repro.models import abstract_params
    want = abstract_params(cfg)
    have = to_program(jax.eval_shape(
        lambda: W.make(c, 0, cfg.padded_vocab)))
    ws = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), want)
    hs = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), have)
    if ws != hs:
        raise ValueError(f"{c['name']}: program parameter layout changed: "
                         f"{ws} vs the benchmark's {hs}")


def make_program_weights(cfg, c: Dict[str, Any], seed: int, plan=None):
    """The seed's weights, drawn in one program on the device(s), in the
    program's layout (sharded on ``plan`` when given)."""
    v_pad = cfg.padded_vocab
    key = W.base_key(seed)

    def make(k):
        return to_program(W.tree(k, c, v_pad,
                                 jax.numpy.dtype(c["torch_dtype"])))

    if plan is None:
        return jax.jit(make)(key)
    return jax.jit(make, out_shardings=plan.param_shardings(cfg))(key)
