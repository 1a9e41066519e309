"""The dense Qwen3 decoder (``model_type`` "qwen3"): its weights in the
benchmark's layout, the program's tree for them, its plain float32 layer
and the work it needs.

A model family is one file, ``bench/models/<model_type>.py``, found by
``bench.lib.spec.family``. The benchmark's generic code (weights drawn
from the seed, the reference's loss, head, optimizer and served-token
readings, the work counts behind the MFU and roofline readers) calls
only what a family file defines:

- ``LEAF_IDS``: every leaf the family can have, with the number its key
  is folded with (so a leaf's draw never depends on the others).
  ``embed`` (padded vocabulary, hidden), ``final_norm`` and, for an
  untied head, ``lm_head`` (hidden, padded vocabulary) are the generic
  leaves; a leaf whose name ends in ``norm`` is an RMSNorm scale and is
  drawn as ones, every other one N(0, ``initializer_range``).
- ``leaf_shapes(c, v_pad)``: the leaves of configuration ``c`` and
  their shapes, any number and rank.
- ``LAYER_LEAVES``: the per-layer leaves, each stacked ``(layers, ...)``.
- ``layer(c, lw, x, mm)``: one layer of the reference on ``x`` (R, W, d)
  in float32 at positions 0..W-1, given that layer's slice of each of
  ``LAYER_LEAVES``; matmuls through ``reference.matmul`` so that the
  float8 control reaches them.
- ``to_program(w)`` / ``program_leaf_names(tree)``: the benchmark's
  leaves as the program's parameter tree, and back.
- ``matmul_params(c)``: weights each token multiplies by;
  ``attn_flops(c, ctx)``: forward attention FLOPs of a token that
  attends ``ctx`` keys; ``kv_bytes_per_token(c, dtype_bytes)``;
  ``decode_weight_bytes(c, tokens, dtype_bytes)``: the least bytes of
  weights a decode step reads when it serves ``tokens`` tokens.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.lib.reference import HI, matmul, rmsnorm, rope

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate",
                "w_up", "w_down")
LEAF_IDS = {n: i for i, n in enumerate(
    ("embed", "final_norm", "lm_head") + LAYER_LEAVES)}


def _dims(c: Dict[str, Any]) -> Dict[str, int]:
    return {"d": c["hidden_size"], "f": c["intermediate_size"],
            "L": c["num_hidden_layers"], "nq": c["num_attention_heads"],
            "nkv": c["num_key_value_heads"], "hd": c["head_dim"],
            "V": c["vocab_size"]}


def leaf_shapes(c: Dict[str, Any], v_pad: int) -> Dict[str, Tuple[int, ...]]:
    k = _dims(c)
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


# --------------------------------------------------------------------------
# the reference layer


def attention(c: Dict[str, Any], lw: Dict[str, jax.Array], x: jax.Array,
              mm: str) -> jax.Array:
    """The attention half of a layer on x (R, W, d) f32: x plus causal
    grouped-query attention, with rotary positions, of RMSNorm(x)."""
    r, w, _ = x.shape
    nq, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps = c["rms_norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(w), (r, w))
    h = rmsnorm(x, lw["attn_norm"], eps)
    q = rope(matmul(h, lw["wq"], mm).reshape(r, w, nq, hd), pos,
             c["rope_theta"])
    k = rope(matmul(h, lw["wk"], mm).reshape(r, w, nkv, hd), pos,
             c["rope_theta"])
    v = matmul(h, lw["wv"], mm).reshape(r, w, nkv, hd)
    q = q.reshape(r, w, nkv, nq // nkv, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, precision=HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((w, w), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v, precision=HI)
    return x + matmul(o.reshape(r, w, nq * hd), lw["wo"], mm)


def layer(c: Dict[str, Any], lw: Dict[str, jax.Array], x: jax.Array,
          mm: str) -> jax.Array:
    """One decoder layer on x (R, W, d) f32 at positions 0..W-1."""
    x = attention(c, lw, x, mm)
    h = rmsnorm(x, lw["mlp_norm"], c["rms_norm_eps"])
    g = matmul(h, lw["w_gate"], mm)
    u = matmul(h, lw["w_up"], mm)
    return x + matmul(jax.nn.silu(g) * u, lw["w_down"], mm)


# --------------------------------------------------------------------------
# the program's parameter tree


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


# --------------------------------------------------------------------------
# work counts of the published model


def layer_matmul_params(c: Dict[str, Any]) -> int:
    k = _dims(c)
    attn = k["d"] * (k["nq"] + 2 * k["nkv"]) * k["hd"] + k["nq"] * k["hd"] * k["d"]
    return attn + 3 * k["d"] * k["f"]


def matmul_params(c: Dict[str, Any]) -> int:
    """Weights every token multiplies by: the layers and the LM head (the
    embedding matrix itself where the head is tied). The input embedding
    is a lookup, not a matmul."""
    k = _dims(c)
    return k["L"] * layer_matmul_params(c) + k["V"] * k["d"]


def attn_flops(c: Dict[str, Any], ctx: float) -> float:
    """Forward attention FLOPs of one token that attends ``ctx`` keys:
    scores and the weighted sum, every layer."""
    k = _dims(c)
    return 4.0 * k["L"] * k["nq"] * k["hd"] * ctx


def kv_bytes_per_token(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    k = _dims(c)
    return k["L"] * 2 * k["nkv"] * k["hd"] * dtype_bytes


def decode_weight_bytes(c: Dict[str, Any], tokens: int,
                        dtype_bytes: int = 2) -> int:
    """Bytes of the weights a decode step must read, whatever the number
    of tokens it serves: every matmul weight (the head included) and the
    RMSNorm scales."""
    k = _dims(c)
    norms = (2 * k["L"] + 1) * k["d"]
    return (matmul_params(c) + norms) * dtype_bytes
