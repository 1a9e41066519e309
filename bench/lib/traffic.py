"""The one traffic generator: it reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws batches from the seed.

Every batch of a mix holds the same multiset of sizes, so the work per
batch does not depend on the seed: the prompt lengths are the evenly
spaced quantiles of the uniform distribution over the allowed lengths,
one per prompt, and the completion lengths the evenly spaced quantiles
of the lognormal, one per row, capped. The seed and the batch index
choose the order of those sizes, the token ids, the rewards and the
sampler log-probs.

Prompt ids are drawn over the published vocabulary, leaving out the
program's reserved ids (PAD, BOS, EOS). Each prompt is repeated
``group_size`` times: the rows of a group share it.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *stream])


def prompt_lengths(t: Dict[str, Any]) -> List[int]:
    p = t["prompt_len"]
    allowed = list(range(p["low"], p["high"] + 1, p.get("step", 1)))
    n = t["prompts"]
    return [allowed[min(int((i + 0.5) / n * len(allowed)), len(allowed) - 1)]
            for i in range(n)]


def completion_lengths(t: Dict[str, Any]) -> List[int]:
    c = t["completion_len"]
    n = t["prompts"] * t["group_size"]
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [max(1, min(c["cap"], round(c["median"] * math.exp(c["sigma"] * x))))
            for x in z]


def _ids(r: np.random.Generator, n: int, t: Dict[str, Any],
         vocab: int) -> np.ndarray:
    return r.integers(t["reserved_ids"], vocab, size=n, dtype=np.int64
                      ).astype(np.int32)


def prompts(t: Dict[str, Any], vocab: int, seed: int, batch: int
            ) -> Dict[str, Any]:
    """One batch's prompts (one per group, shuffled lengths) and each
    row's completion length (shuffled over all rows). The first id of
    prompt ``i`` of batch ``b`` is the (b * prompts + i)-th allowed id, so
    no two prompts of a run share a prefix: distinct prompts, as the
    mix's groups are, never hit the engine's prefix cache by chance."""
    r = rng(seed, batch)
    n = t["prompts"]
    plens = np.array(prompt_lengths(t))[r.permutation(n)]
    clens = np.array(completion_lengths(t))[
        r.permutation(n * t["group_size"])]
    ps = [_ids(r, int(k), t, vocab) for k in plens]
    lo = t["reserved_ids"]
    for i, p in enumerate(ps):
        p[0] = lo + (batch * n + i) % (vocab - lo)
    return {"prompts": ps, "completion_lens": clens.astype(np.int32), "rng": r}


def learn_batch(t: Dict[str, Any], vocab: int, seed: int, step: int
                ) -> Dict[str, np.ndarray]:
    """One GEPO learner batch in the program's layout: tokens (B, width)
    int32, mask (B, width-1) over completion targets, sampler_lp
    (B, width-1), rewards (B,) mixed 0/1 in every group, and each row's
    valid length (prompt + completion)."""
    g = t["group_size"]
    p = prompts(t, vocab, seed, step)
    r = p["rng"]
    b, width = t["prompts"] * g, t["width"]
    tokens = np.zeros((b, width), np.int32)
    mask = np.zeros((b, width - 1), np.float32)
    lengths = np.zeros((b,), np.int32)
    for i in range(b):
        prompt = p["prompts"][i // g]
        n_c = int(p["completion_lens"][i])
        row = np.concatenate([prompt, _ids(r, n_c, t, vocab)])
        tokens[i, :row.size] = row
        mask[i, prompt.size - 1:row.size - 1] = 1.0
        lengths[i] = row.size
    rewards = np.zeros((b,), np.float32)
    for gi in range(t["prompts"]):
        k = int(r.integers(1, g))                 # 1..g-1 correct answers
        rewards[gi * g + r.permutation(g)[:k]] = 1.0
    s = t["sampler_lp"]
    # near the random policy's log-prob of a uniformly drawn id
    base = -math.log(vocab)
    sampler_lp = (base + r.normal(0.0, s["seq_sd"], (b, 1))
                  + r.normal(0.0, s["token_sd"], (b, width - 1)))
    sampler_lp = np.minimum(sampler_lp, 0.0).astype(np.float32) * mask
    return {"tokens": tokens, "mask": mask, "sampler_lp": sampler_lp,
            "rewards": rewards, "lengths": lengths}


def learn_tokens(batch: Dict[str, np.ndarray]) -> int:
    """Non-pad tokens of a learner batch (prompt and completion)."""
    return int(np.asarray(batch["lengths"]).sum())


def rollout_requests(t: Dict[str, Any], vocab: int, seed: int, batch: int
                     ) -> List[Dict[str, Any]]:
    """One rollout batch: ``prompts`` x ``group_size`` requests, each a
    prompt and a token budget (its drawn completion length, which stands
    in for its EOS under random weights). Request ids are unique over
    the run."""
    g = t["group_size"]
    p = prompts(t, vocab, seed, batch)
    n = t["prompts"] * g
    return [{"rid": batch * n + i, "prompt": p["prompts"][i // g],
             "max_new": int(p["completion_lens"][i])} for i in range(n)]
