"""Operations and bytes that the algorithm needs, from shapes.

Every count is of the published model (``configs/<name>.json``), as its
model family (``bench/models/<model_type>.py``) counts it: matmul FLOPs
are 2 per multiply-add, attention is counted causally at each token's
own position, and nothing that an implementation recomputes
(rematerialization, padding, dead decode slots) is counted.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, Sequence

from bench.lib import spec

Config = Dict[str, Any]


def matmul_params(c: Config) -> int:
    """Weights every token multiplies by."""
    return spec.family(c).matmul_params(c)


def attn_flops(c: Config, ctx: float) -> float:
    """Forward attention FLOPs of one token that attends ``ctx`` keys."""
    return spec.family(c).attn_flops(c, ctx)


def kv_bytes_per_token(c: Config, dtype_bytes: int = 2) -> int:
    return spec.family(c).kv_bytes_per_token(c, dtype_bytes)


def weight_bytes(c: Config, tokens: int, dtype_bytes: int = 2) -> int:
    """The least bytes of weights one decode step reads when it serves
    ``tokens`` tokens."""
    return spec.family(c).decode_weight_bytes(c, tokens, dtype_bytes)


def train_flops(c: Config, lengths: Iterable[int]) -> float:
    """Forward and backward FLOPs (3x the forward) of sequences of the
    given valid lengths: 6 per weight per token plus causal attention."""
    n = matmul_params(c)
    total = 0.0
    for t in lengths:
        # token i attends i + 1 keys: sum over i < t is t (t + 1) / 2
        total += 6.0 * n * t + 3.0 * attn_flops(c, t * (t + 1) / 2.0)
    return total


def decode_least_seconds(c: Config, prompt_lens: Sequence[int],
                         gen_lens: Sequence[int], peak_flops: float,
                         peak_bw: float) -> float:
    """Least device time of a closed batch's decode steps: step ``s``
    serves every request with more than ``s`` generated tokens, reads the
    weights it needs once and each such request's cached K/V (its prompt
    and the ``s`` tokens before), and does their matmul and attention
    FLOPs. Each step costs the larger of its bytes and its FLOPs over the
    peaks."""
    kvb = kv_bytes_per_token(c)
    n = matmul_params(c)
    steps = max(gen_lens, default=0)
    total = 0.0
    for s in range(steps):
        ctx = [p + s + 1 for p, g in zip(prompt_lens, gen_lens) if g > s]
        if not ctx:
            continue
        byts = weight_bytes(c, len(ctx)) + kvb * sum(ctx)
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
