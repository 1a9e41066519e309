"""The benchmark's side of the interface to the program under test: the
program's model configuration as the configuration file states it, and
the benchmark's weights handed over in the program's parameter layout
(the model family's ``to_program``). The program's own code is imported
here and in ``bench/kinds`` only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax

from bench.lib import spec
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


def check_layout(cfg, c: Dict[str, Any]) -> None:
    """The program's parameter tree has exactly the benchmark's leaves,
    shapes and dtype."""
    from repro.models import abstract_params
    want = abstract_params(cfg)
    have = spec.family(c).to_program(jax.eval_shape(
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
    to_program = spec.family(c).to_program

    def make(k):
        return to_program(W.tree(k, c, v_pad,
                                 jax.numpy.dtype(c["torch_dtype"])))

    if plan is None:
        return jax.jit(make)(key)
    return jax.jit(make, out_shardings=plan.param_shardings(cfg))(key)
