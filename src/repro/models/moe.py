"""Mixture-of-Experts FFN: dropless top-k routing, with the experts a layer
holds computed as grouped matmuls.

The router scores all ``num_experts`` experts in float32; each token keeps
its top ``experts_per_token``, renormalised over them. A layer holds a
block of the experts (``ModelConfig.expert_block``: one chip's share under
expert parallelism, all of them by default) and computes only their part
of the result. The (token, pick) pairs whose expert it holds are sorted by
expert, and gate/up and down run as grouped matmuls
(``jax.lax.ragged_dot``) over the held experts' weights, one group of rows
per expert. The row buffer is sized for the worst case, T·min(k, held)
rows, so no pick is ever dropped and a token's output does not depend on
the rest of its batch. Compiled work grows with the held experts' rows,
not with T·E.

Aux metrics: router z-loss and load balance (returned for logging, not
folded into the RL objective).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import swiglu


def route(cfg: ModelConfig, router: jax.Array, xf: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-k routing of tokens xf (T, d) over every expert, in float32.
    Returns the router logits and softmax (T, E), and each token's gates
    and expert ids (T, k), the gates renormalised over the k picks."""
    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.experts_per_token > 1:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return logits, probs, gate, ids


def held_experts_ffn(xf: jax.Array, gate: jax.Array, ids: jax.Array,
                     p: Dict, first, count: int) -> jax.Array:
    """The part of the MoE output that experts ``first .. first+count-1``
    give, for tokens xf (T, d) routed as ``route`` returns.

    ``p`` holds those experts' ``w_gate``/``w_up`` (count, d, f) and
    ``w_down`` (count, f, d). ``first`` may be traced (an expert-parallel
    rank's block); ``count`` is static. Returns (T, d) in xf's dtype,
    accumulated over a token's picks in float32."""
    t, k = ids.shape
    local = ids.reshape(-1) - first
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count)          # other experts' pairs last
    # every held pair, grouped by expert: a token has at most min(k, count)
    order = jnp.argsort(key, stable=True)[:t * min(k, count)]
    sizes = jnp.zeros((count,), jnp.int32).at[key].add(1, mode="drop")
    tok = order // k
    xs = xf[tok]
    with jax.named_scope("expert_ffn"):
        h = (jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes))
             * jax.lax.ragged_dot(xs, p["w_up"], sizes))
        out = jax.lax.ragged_dot(h, p["w_down"], sizes)
    # rows past the held pairs belong to no group: whatever the grouped
    # matmul leaves there is not added
    part = jnp.where(mine[order][:, None],
                     out.astype(jnp.float32) * gate.reshape(-1)[order, None],
                     0.0)
    y = jnp.zeros((t, xf.shape[1]), jnp.float32).at[tok].add(part)
    return y.astype(xf.dtype)


def router_aux(logits: jax.Array, probs: jax.Array, ids: jax.Array
               ) -> Dict[str, jax.Array]:
    """Switch-style load balance over all experts, and the z-loss."""
    t, k = ids.shape
    e = probs.shape[-1]
    ce = jnp.zeros((e,)).at[ids.reshape(-1)].add(1.0) / (t * k)
    return {"moe_load_balance": e * jnp.sum(probs.mean(axis=0) * ce),
            "moe_z_loss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)}


def moe_ffn(cfg: ModelConfig, p: Dict, x: jax.Array
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (B, S, d), aux metrics."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    logits, probs, gate, ids = route(cfg, p["router"], xf)
    y = held_experts_ffn(xf, gate, ids, p, cfg.first_held_expert,
                         cfg.num_held_experts)
    if cfg.shared_expert:
        y = y + swiglu(xf, p["shared"])
    return y.reshape(b, s, d), router_aux(logits, probs, ids)
