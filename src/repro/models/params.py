"""Parameter templates.

Every parameter leaf is declared once as a ``ParamTemplate`` carrying its
shape, initializer and *logical axes*. From the template tree we derive:

- ``init_params``      — materialized arrays (smoke tests / real training)
- ``abstract_params``  — ``jax.ShapeDtypeStruct`` stand-ins (multi-pod
                          dry-run: no allocation ever happens)
- sharding specs       — ``repro.parallel.axes`` maps logical axes to
                          mesh axes per execution mode (consumed through
                          ``repro.parallel.ExecutionPlan``)

Logical axis vocabulary:
  vocab, embed (d_model), ffn (d_ff), qkv (flattened heads*head_dim),
  kv (flattened kv_heads*head_dim), experts, dinner (SSM inner),
  ssm_in (SSM in-proj fan-out), conv, heads (SSM heads), state, None
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import (ATTN, CROSS, LOCAL, MAMBA, MLP, MOE, NONE,
                          ModelConfig)


@dataclasses.dataclass(frozen=True)
class ParamTemplate:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | ssm_a | ssm_dt
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _mlp_templates(cfg: ModelConfig) -> Dict[str, ParamTemplate]:
    d, f = cfg.d_model, cfg.d_ff
    out_scale = 0.02 / np.sqrt(2 * cfg.num_layers)
    return {
        "w_gate": ParamTemplate((d, f), ("embed", "ffn")),
        "w_up": ParamTemplate((d, f), ("embed", "ffn")),
        "w_down": ParamTemplate((f, d), ("ffn", "embed"), scale=out_scale),
    }


def _moe_templates(cfg: ModelConfig) -> Dict[str, Any]:
    """The router over every expert; weights of the held experts only."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_held_experts
    out_scale = 0.02 / np.sqrt(2 * cfg.num_layers)
    t = {
        "router": ParamTemplate((d, cfg.num_experts), ("embed", None)),
        "w_gate": ParamTemplate((e, d, f), ("experts", "embed", "expert_ffn")),
        "w_up": ParamTemplate((e, d, f), ("experts", "embed", "expert_ffn")),
        "w_down": ParamTemplate((e, f, d), ("experts", "expert_ffn", "embed"),
                                scale=out_scale),
    }
    if cfg.shared_expert:
        t["shared"] = _mlp_templates(cfg)
    return t


def _attn_templates(cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    d, h = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    out_scale = 0.02 / np.sqrt(2 * max(cfg.num_layers, 1))
    t = {
        "wq": ParamTemplate((d, nq * h), ("embed", "qkv")),
        "wk": ParamTemplate((d, nkv * h), ("embed", "kv")),
        "wv": ParamTemplate((d, nkv * h), ("embed", "kv")),
        "wo": ParamTemplate((nq * h, d), ("qkv", "embed"), scale=out_scale),
    }
    if cfg.qkv_bias and not cross:
        t["bq"] = ParamTemplate((nq * h,), ("qkv",), init="zeros")
        t["bk"] = ParamTemplate((nkv * h,), ("kv",), init="zeros")
        t["bv"] = ParamTemplate((nkv * h,), ("kv",), init="zeros")
    return t


def _mamba_templates(cfg: ModelConfig) -> Dict[str, ParamTemplate]:
    d = cfg.d_model
    di, N, G, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups, cfg.ssm_heads
    conv_ch = di + 2 * G * N
    fan_out = 2 * di + 2 * G * N + H      # [z, x, B, C, dt]
    out_scale = 0.02 / np.sqrt(2 * cfg.num_layers)
    return {
        "in_proj": ParamTemplate((d, fan_out), ("embed", "ssm_in")),
        "conv_w": ParamTemplate((cfg.ssm_conv, conv_ch), (None, "dinner")),
        "conv_b": ParamTemplate((conv_ch,), ("dinner",), init="zeros"),
        "A_log": ParamTemplate((H,), ("heads",), init="ssm_a"),
        "D": ParamTemplate((H,), ("heads",), init="ones"),
        "dt_bias": ParamTemplate((H,), ("heads",), init="ssm_dt"),
        "gate_norm": ParamTemplate((di,), ("dinner",), init="ones"),
        "out_proj": ParamTemplate((di, d), ("dinner", "embed"),
                                  scale=out_scale),
    }


def _layer_templates(cfg: ModelConfig, kind: str, ffn_kind: str,
                     decoder: bool = False) -> Dict[str, Any]:
    d = cfg.d_model
    t: Dict[str, Any] = {"norm": ParamTemplate((d,), ("embed",), init="ones")}
    if kind in (ATTN, LOCAL):
        t["attn"] = _attn_templates(cfg)
        if cfg.is_encdec and decoder:      # whisper decoder: +cross-attn
            t["cross_norm"] = ParamTemplate((d,), ("embed",), init="ones")
            t["cross"] = _attn_templates(cfg, cross=True)
    elif kind == CROSS:
        t["attn"] = _attn_templates(cfg, cross=True)
    elif kind == MAMBA:
        t["mamba"] = _mamba_templates(cfg)
    else:
        raise ValueError(kind)
    if ffn_kind == MLP:
        t["ffn_norm"] = ParamTemplate((d,), ("embed",), init="ones")
        t["mlp"] = _mlp_templates(cfg)
    elif ffn_kind == MOE:
        t["ffn_norm"] = ParamTemplate((d,), ("embed",), init="ones")
        t["moe"] = _moe_templates(cfg)
    elif ffn_kind == NONE:
        pass
    else:
        raise ValueError(ffn_kind)
    return t


def _stack(tree: Any, n: int) -> Any:
    """Prepend a stacking dimension of size n to every template (for the
    scanned super-blocks)."""
    def f(t: ParamTemplate) -> ParamTemplate:
        return dataclasses.replace(t, shape=(n,) + t.shape,
                                   axes=(None,) + t.axes)
    return jax.tree_util.tree_map(f, tree,
                                  is_leaf=lambda x: isinstance(x, ParamTemplate))


def param_templates(cfg: ModelConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.padded_vocab
    block = {
        f"layer_{i}": _layer_templates(cfg, kind, cfg.ffn_kind(i),
                                       decoder=True)
        for i, kind in enumerate(cfg.block_pattern)
    }
    t: Dict[str, Any] = {
        "embed": ParamTemplate((v, d), ("vocab", "embed"), scale=1.0),
        "blocks": _stack(block, cfg.num_blocks),
        "final_norm": ParamTemplate((d,), ("embed",), init="ones"),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = ParamTemplate((d, v), ("embed", "vocab"))
    if cfg.encoder_layers:
        enc_layer = _layer_templates(
            dataclasses.replace(cfg, qkv_bias=False, num_layers=cfg.encoder_layers),
            ATTN, MLP, decoder=False)
        t["encoder"] = {
            "blocks": _stack(enc_layer, cfg.encoder_layers),
            "final_norm": ParamTemplate((d,), ("embed",), init="ones"),
        }
    return t


# --------------------------------------------------------------------------
# materialization


def _is_t(x) -> bool:
    return isinstance(x, ParamTemplate)


def init_params(cfg: ModelConfig, key: jax.Array) -> Any:
    """Materialize real parameters (used for smoke-scale models and RL
    training; the full configs are only ever abstract)."""
    templates = param_templates(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(templates, is_leaf=_is_t)
    keys = jax.random.split(key, len(leaves))
    dtype = jnp.dtype(cfg.dtype)

    def mk(t: ParamTemplate, k: jax.Array) -> jax.Array:
        if t.init == "zeros":
            return jnp.zeros(t.shape, dtype)
        if t.init == "ones":
            return jnp.ones(t.shape, dtype)
        if t.init == "ssm_a":          # A in [1, 16), stored as log
            u = jax.random.uniform(k, t.shape, jnp.float32, 1.0, 16.0)
            return jnp.log(u).astype(dtype)
        if t.init == "ssm_dt":         # dt bias ~ softplus^-1(U[1e-3, 1e-1])
            u = jax.random.uniform(k, t.shape, jnp.float32, 1e-3, 1e-1)
            return jnp.log(jnp.expm1(u)).astype(dtype)
        return (t.scale * jax.random.normal(k, t.shape, jnp.float32)
                ).astype(dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [mk(t, k) for t, k in zip(leaves, keys, strict=True)])


def abstract_params(cfg: ModelConfig) -> Any:
    """ShapeDtypeStruct tree for .lower() — no device allocation."""
    dtype = jnp.dtype(cfg.dtype)
    return jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape, dtype),
        param_templates(cfg), is_leaf=_is_t)


def param_axes(cfg: ModelConfig) -> Any:
    """Tree of logical-axis tuples matching the params tree."""
    return jax.tree_util.tree_map(lambda t: t.axes, param_templates(cfg),
                                  is_leaf=_is_t)
