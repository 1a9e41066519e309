"""The Qwen3 sparse-expert decoder (``model_type`` "qwen3_moe"): the dense
Qwen3 layer (``bench/models/qwen3.py``) with its MLP replaced by a
mixture of experts, at one chip's share of an expert-parallel deployment.

The router scores all ``router_experts`` experts (the published
``num_experts``) in float32; each token keeps its top
``num_experts_per_tok``, renormalised over them (``norm_topk_prob``), and
the layer's output is the gate-weighted sum of the picked experts'
SwiGLU. This chip holds ``num_experts`` of them, ids ``first_held_expert``
onwards, and computes only their part: what the absent experts would add
is left out, here as in the program. The file defines what
``bench/models/qwen3.py`` lists for a family, plus
``expert_ffn_work(c, tokens)``, the least bytes and FLOPs of the expert
FFN in one decode step.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.lib import spec
from bench.lib.reference import HI, matmul, rmsnorm

qwen3 = spec.family({"model_type": "qwen3"})

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router",
                "w_gate", "w_up", "w_down")
LEAF_IDS = dict(qwen3.LEAF_IDS, router=len(qwen3.LEAF_IDS))

# the dense family's attention half, its K/V and its attention FLOPs
attention = qwen3.attention
attn_flops = qwen3.attn_flops
kv_bytes_per_token = qwen3.kv_bytes_per_token


def _dims(c: Dict[str, Any]) -> Dict[str, int]:
    return {"d": c["hidden_size"], "f": c["moe_intermediate_size"],
            "L": c["num_hidden_layers"], "nq": c["num_attention_heads"],
            "nkv": c["num_key_value_heads"], "hd": c["head_dim"],
            "V": c["vocab_size"], "E": c["router_experts"],
            "held": c["num_experts"], "k": c["num_experts_per_tok"]}


def leaf_shapes(c: Dict[str, Any], v_pad: int) -> Dict[str, Tuple[int, ...]]:
    k = _dims(c)
    L, d, f, held = k["L"], k["d"], k["f"], k["held"]
    q, kv = k["nq"] * k["hd"], k["nkv"] * k["hd"]
    shapes = {
        "embed": (v_pad, d), "final_norm": (d,),
        "attn_norm": (L, d), "wq": (L, d, q), "wk": (L, d, kv),
        "wv": (L, d, kv), "wo": (L, q, d), "mlp_norm": (L, d),
        "router": (L, d, k["E"]), "w_gate": (L, held, d, f),
        "w_up": (L, held, d, f), "w_down": (L, held, f, d),
    }
    if not c["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v_pad)
    return shapes


# --------------------------------------------------------------------------
# the reference layer


def experts(c: Dict[str, Any], lw: Dict[str, jax.Array], h: jax.Array,
            mm: str) -> jax.Array:
    """The held experts' part of the MoE on h (R, W, d) f32: each held
    expert's SwiGLU of every token, weighted by the token's gate for it
    (zero where the token did not pick it)."""
    k = _dims(c)
    first = c["first_held_expert"]
    probs = jax.nn.softmax(matmul(h, lw["router"], mm), axis=-1)
    top, ids = jax.lax.top_k(probs, k["k"])
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    picked = ids[..., None] == first + jnp.arange(k["held"])  # (R, W, k, held)
    gate = jnp.sum(jnp.where(picked, top[..., None], 0.0), axis=-2)

    def one(wg, wu, wd):
        return matmul(jax.nn.silu(matmul(h, wg, mm)) * matmul(h, wu, mm),
                      wd, mm)

    out = jax.vmap(one)(lw["w_gate"], lw["w_up"], lw["w_down"])  # (held, R, W, d)
    return jnp.einsum("rwe,erwd->rwd", gate, out, precision=HI)


def layer(c: Dict[str, Any], lw: Dict[str, jax.Array], x: jax.Array,
          mm: str) -> jax.Array:
    """One decoder layer on x (R, W, d) f32 at positions 0..W-1."""
    x = attention(c, lw, x, mm)
    return x + experts(c, lw, rmsnorm(x, lw["mlp_norm"], c["rms_norm_eps"]),
                       mm)


# --------------------------------------------------------------------------
# the program's parameter tree

MOE_LEAVES = ("router", "w_gate", "w_up", "w_down")


def to_program(w: Dict[str, Any]) -> Dict[str, Any]:
    """Benchmark layout -> the program's parameter tree (a renaming)."""
    tree = {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "blocks": {"layer_0": {
            "norm": w["attn_norm"],
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "ffn_norm": w["mlp_norm"],
            "moe": {k: w[k] for k in MOE_LEAVES},
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
           **b["attn"], **b["moe"]}
    if "lm_head" in tree:
        out["lm_head"] = tree["lm_head"]
    return out


# --------------------------------------------------------------------------
# work counts of the configuration as held here


def _touched(k: Dict[str, int], tokens: float) -> float:
    """Held experts that ``tokens`` tokens are expected to pick, each
    token's k picks uniform over the E experts."""
    return k["held"] * (1.0 - (1.0 - k["k"] / k["E"]) ** tokens)


def _dense_params(k: Dict[str, int]) -> int:
    """Weights of a layer that every token reads: attention and router."""
    return (k["d"] * (k["nq"] + 2 * k["nkv"]) * k["hd"]
            + k["nq"] * k["hd"] * k["d"] + k["d"] * k["E"])


def layer_matmul_params(c: Dict[str, Any]) -> float:
    """Weights a token multiplies by in one layer: attention, the router,
    and the k·held/E held experts it picks on average."""
    k = _dims(c)
    return (_dense_params(k)
            + 3 * k["d"] * k["f"] * k["k"] * k["held"] / k["E"])


def matmul_params(c: Dict[str, Any]) -> float:
    """Weights every token multiplies by: the layers and the LM head."""
    k = _dims(c)
    return k["L"] * layer_matmul_params(c) + k["V"] * k["d"]


def decode_weight_bytes(c: Dict[str, Any], tokens: int,
                        dtype_bytes: int = 2) -> float:
    """Bytes of the weights a decode step serving ``tokens`` tokens must
    read: attention, router, head and RMSNorm scales whatever the tokens,
    and the held experts they are expected to touch."""
    k = _dims(c)
    norms = (2 * k["L"] + 1) * k["d"]
    experts_p = k["L"] * _touched(k, tokens) * 3 * k["d"] * k["f"]
    return (k["L"] * _dense_params(k) + k["V"] * k["d"] + norms
            + experts_p) * dtype_bytes


def expert_ffn_work(c: Dict[str, Any], tokens: int, dtype_bytes: int = 2
                    ) -> Tuple[float, float]:
    """Least bytes and FLOPs of the expert FFN, every layer, in a decode
    step of ``tokens`` tokens: the held experts they are expected to
    touch, read once, and the expected held (token, pick) pairs' rows in
    and out; 6·d·f FLOPs a pair (gate, up and down)."""
    k = _dims(c)
    pairs = tokens * k["k"] * k["held"] / k["E"]
    byts = k["L"] * (_touched(k, tokens) * 3 * k["d"] * k["f"]
                     + pairs * 2 * k["d"]) * dtype_bytes
    return byts, k["L"] * 6.0 * k["d"] * k["f"] * pairs
