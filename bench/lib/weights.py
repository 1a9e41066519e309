"""Weights from the seed, in the benchmark's own layout.

The layout is the plain one of the reference: the leaves that the
configuration's model family (``bench/models/<model_type>.py``) names,
each layer weight stacked ``(layers, ...)``, plus the embedding, the
final norm and (untied models) the LM head. ``make`` draws the whole
tree on the device(s) in one jitted program, in the stored type, split
over the mesh when one is given; ``make_leaf`` draws one leaf alone,
bit-identical to the same leaf of ``make`` (each leaf has its own key),
so a reading can be taken against the initial weights without holding
them.

The program under test gets the same arrays through the family's
``to_program``; the reference uses them as they are.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bench.lib import placement, spec


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed, including those past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(c: Dict[str, Any], v_pad: int, name: str, key: jax.Array,
          dtype) -> jax.Array:
    fam = spec.family(c)
    shape = fam.leaf_shapes(c, v_pad)[name]
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, fam.LEAF_IDS[name])
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
            for n in spec.family(c).leaf_shapes(c, v_pad)}


@functools.partial(jax.jit, static_argnames=("cfg_items", "v_pad", "dtype",
                                             "mesh"))
def _make(key, cfg_items, v_pad, dtype, mesh):
    return placement.constrain(tree(key, dict(cfg_items), v_pad, dtype),
                               mesh)


@functools.partial(jax.jit, static_argnames=("cfg_items", "v_pad", "name",
                                             "dtype", "sharding"))
def _make_leaf(key, cfg_items, v_pad, name, dtype, sharding):
    w = _draw(dict(cfg_items), v_pad, name, key, dtype)
    if sharding is None:
        return w
    return jax.lax.with_sharding_constraint(w, sharding)


def config_items(c: Dict[str, Any]) -> Tuple:
    """The configuration's top-level numbers and names, hashable: the
    static part of every program the benchmark builds from it."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool, str))))


def make(c: Dict[str, Any], seed: int, v_pad: int, mesh=None
         ) -> Dict[str, jax.Array]:
    """The seed's weights in their stored type (``torch_dtype``), split
    over ``mesh`` (``bench.lib.placement``) when given."""
    return _make(base_key(seed), config_items(c), v_pad,
                 jnp.dtype(c["torch_dtype"]), mesh)


def make_leaf(c: Dict[str, Any], seed: int, v_pad: int, name: str,
              sharding: Optional[jax.sharding.Sharding] = None
              ) -> jax.Array:
    """One leaf of ``make``, laid out as ``sharding`` says when given."""
    return _make_leaf(base_key(seed), config_items(c), v_pad, name,
                      jnp.dtype(c["torch_dtype"]), sharding)
