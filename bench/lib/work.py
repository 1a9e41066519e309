"""Operations and bytes that the algorithm needs, from shapes.

Every count is of the published model (``configs/<name>.json``): matmul
FLOPs are 2 per multiply-add, attention is counted causally at each
token's own position, and nothing that an implementation recomputes
(rematerialization, padding, dead decode slots) is counted.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Sequence

Config = Dict[str, Any]


def dims(c: Config) -> Dict[str, int]:
    return {"d": c["hidden_size"], "f": c["intermediate_size"],
            "L": c["num_hidden_layers"], "nq": c["num_attention_heads"],
            "nkv": c["num_key_value_heads"], "hd": c["head_dim"],
            "V": c["vocab_size"]}


def layer_matmul_params(c: Config) -> int:
    k = dims(c)
    attn = k["d"] * (k["nq"] + 2 * k["nkv"]) * k["hd"] + k["nq"] * k["hd"] * k["d"]
    return attn + 3 * k["d"] * k["f"]


def matmul_params(c: Config) -> int:
    """Weights every token multiplies by: the layers and the LM head (the
    embedding matrix itself where the head is tied). The input embedding
    is a lookup, not a matmul."""
    k = dims(c)
    return k["L"] * layer_matmul_params(c) + k["V"] * k["d"]


def attn_flops(c: Config, ctx: float) -> float:
    """Forward attention FLOPs of one token that attends ``ctx`` keys:
    scores and the weighted sum, every layer."""
    k = dims(c)
    return 4.0 * k["L"] * k["nq"] * k["hd"] * ctx


def train_flops(c: Config, lengths: Iterable[int]) -> float:
    """Forward and backward FLOPs (3x the forward) of sequences of the
    given valid lengths: 6 per weight per token plus causal attention."""
    n = matmul_params(c)
    total = 0.0
    for t in lengths:
        # token i attends i + 1 keys: sum over i < t is t (t + 1) / 2
        total += 6.0 * n * t + 3.0 * attn_flops(c, t * (t + 1) / 2.0)
    return total


def kv_bytes_per_token(c: Config, dtype_bytes: int = 2) -> int:
    k = dims(c)
    return k["L"] * 2 * k["nkv"] * k["hd"] * dtype_bytes


def weight_bytes(c: Config, dtype_bytes: int = 2) -> int:
    """Bytes of the weights one decode step must read: every matmul
    weight (the head included) and the RMSNorm scales."""
    k = dims(c)
    norms = (2 * k["L"] + 1) * k["d"]
    return (matmul_params(c) + norms) * dtype_bytes


def decode_least_seconds(c: Config, prompt_lens: Sequence[int],
                         gen_lens: Sequence[int], peak_flops: float,
                         peak_bw: float) -> float:
    """Least device time of a closed batch's decode steps: step ``s``
    serves every request with more than ``s`` generated tokens, reads the
    weights once and each such request's cached K/V (its prompt and the
    ``s`` tokens before), and does their matmul and attention FLOPs.
    Each step costs the larger of its bytes and its FLOPs over the peaks."""
    w = weight_bytes(c)
    kvb = kv_bytes_per_token(c)
    n = matmul_params(c)
    steps = max(gen_lens, default=0)
    total = 0.0
    for s in range(steps):
        ctx = [p + s + 1 for p, g in zip(prompt_lens, gen_lens) if g > s]
        if not ctx:
            continue
        byts = w + kvb * sum(ctx)
        flops = 2.0 * n * len(ctx) + attn_flops(c, sum(ctx))
        total += max(byts / peak_bw, flops / peak_flops)
    return total


def logprob_kernel_bytes(tokens: int, vocab: int, logit_bytes: int = 4
                         ) -> Dict[str, int]:
    """Bytes the fused log-prob kernels move for flat (tokens, vocab)
    logits: the forward reads the logits once and writes three f32 values
    per token (log-prob, entropy, log-sum-exp); the backward reads the
    logits and writes their gradient."""
    per_tok = 4 * 3 + 4       # f32 outputs + the int32 target
    return {"fwd": tokens * vocab * logit_bytes + tokens * per_tok,
            "bwd": 2 * tokens * vocab * logit_bytes + tokens * (per_tok + 8)}


def logprob_kernel_flops(tokens: int, vocab: int) -> Dict[str, float]:
    """Arithmetic of the kernels: a few operations per logit (max,
    exponent, sums; the backward a softmax and a scaled difference)."""
    return {"fwd": 5.0 * tokens * vocab, "bwd": 8.0 * tokens * vocab}
