"""Weights from the seed, in the benchmark's own layout.

The layout is the plain one of the reference: one stacked array per kind
of layer weight, ``(layers, ...)``, plus the embedding, the final norm
and (untied models) the LM head. ``make`` draws the whole tree on the
device in one jitted program, in bfloat16; ``make_leaf`` draws one leaf
alone, bit-identical to the same leaf of ``make`` (each leaf has its own
key), so a reading can be taken against the initial weights without
holding them.

The program under test gets the same arrays through ``to_program`` in
``bench/lib/program.py``; the reference uses them as they are.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.lib.work import dims

STACKED = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
           "w_up", "w_down")


def padded_vocab(c: Dict[str, Any], multiple: int = 256) -> int:
    v = c["vocab_size"]
    return -(-v // multiple) * multiple


def leaf_shapes(c: Dict[str, Any], v_pad: int) -> Dict[str, Tuple[int, ...]]:
    k = dims(c)
    L, d, f = k["L"], k["d"], k["f"]
    q, kv = k["nq"] * k["hd"], k["nkv"] * k["hd"]
    shapes = {
        "embed": (v_pad, d), "final_norm": (d,),
        "attn_norm": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
        "wv": (L, d, kv), "wo": (L, q, d), "mlp_norm": (L, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }
    if not c["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v_pad)
    return shapes


LEAF_IDS = {n: i for i, n in enumerate(
    ("embed", "final_norm", "lm_head") + STACKED)}


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed, including those past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(c: Dict[str, Any], v_pad: int, name: str, key: jax.Array,
          dtype) -> jax.Array:
    shape = leaf_shapes(c, v_pad)[name]
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, LEAF_IDS[name])
    w = c["initializer_range"] * jax.random.normal(k, shape, jnp.float32)
    v = c["vocab_size"]
    if name == "embed":                 # padding ids have no embedding
        w = jnp.where(jnp.arange(v_pad)[:, None] < v, w, 0.0)
    elif name == "lm_head":
        w = jnp.where(jnp.arange(v_pad)[None, :] < v, w, 0.0)
    return w.astype(dtype)


def tree(key, c: Dict[str, Any], v_pad: int, dtype) -> Dict[str, jax.Array]:
    """Every leaf, traced into the caller's program."""
    return {n: _draw(c, v_pad, n, key, dtype)
            for n in leaf_shapes(c, v_pad)}


@functools.partial(jax.jit, static_argnames=("cfg_items", "v_pad", "dtype"))
def _make(key, cfg_items, v_pad, dtype):
    return tree(key, dict(cfg_items), v_pad, dtype)


@functools.partial(jax.jit, static_argnames=("cfg_items", "v_pad", "name",
                                             "dtype"))
def _make_leaf(key, cfg_items, v_pad, name, dtype):
    return _draw(dict(cfg_items), v_pad, name, key, dtype)


def config_items(c: Dict[str, Any]) -> Tuple:
    """The hashable part of a configuration that the weights depend on."""
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "initializer_range", "tie_word_embeddings")
    return tuple((k, c[k]) for k in keys)


def make(c: Dict[str, Any], seed: int, v_pad: int) -> Dict[str, jax.Array]:
    """The seed's weights in their stored type (``torch_dtype``)."""
    return _make(base_key(seed), config_items(c), v_pad,
                 jnp.dtype(c["torch_dtype"]))


def make_leaf(c: Dict[str, Any], seed: int, v_pad: int, name: str
              ) -> jax.Array:
    return _make_leaf(base_key(seed), config_items(c), v_pad, name,
                      jnp.dtype(c["torch_dtype"]))
