"""Generation engines (the sampler node's workhorse).

Two engines share one request-level contract
(:class:`repro.serving.api.Engine`):

- **static** (:class:`StaticEngine`) — prefill + one jitted ``lax.scan``
  decode loop over the whole batch. Every sequence runs the full
  ``max_new`` steps even after EOS (finished rows decode PAD into dead
  cache slots).
- **continuous** (:class:`repro.sampling.continuous.ContinuousEngine`) —
  a fixed pool of decode slots over a paged (block-table) KV cache with
  a priority request queue: finished sequences free their slot and pages
  immediately, chunked prefill for the next queued prompt interleaves
  with the jitted decode step, and shared prompt prefixes reuse KV pages
  across requests. Same tokens and log-probs as the static engine for
  identical seeds (RNG is folded per request id, never per batch
  position), but no wasted decode steps.

``build_engine`` constructs either from a ``ServeConfig`` deployment
description. The module-level ``generate(cfg, rl, params, prompts, ...)``
is the legacy batch entry point, kept as a thin shim over the engines —
new code should build an engine once and feed it
:class:`~repro.serving.api.Request` objects (see README "Serving").

Per App. B.1 the engine-side log-probs are *metadata*: the learner
recomputes them with its own forward pass by default
(``RLConfig.recompute_sampler_logps``), reproducing the paper's fix for
the vLLM/FSDP log-prob mismatch.
"""
from __future__ import annotations

import functools
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, RLConfig, ServeConfig
from repro.data.tasks import EOS, PAD
from repro.models import decode_step, forward, init_cache
from repro.parallel import plan_for_params
from repro.sampling.continuous import (ContinuousEngine, generate_continuous,
                                       rollout_from_results)
from repro.sampling.paged_cache import paged_cache_supported
from repro.sampling.sample import mask_vocab, model_logp, sample_token_rows
from repro.serving.api import GenerationResult, Request, SamplingParams

# --------------------------------------------------------------------------
# static engine: one lax.scan to max_new


@functools.partial(jax.jit, static_argnames=("cfg", "rl", "max_new",
                                             "vocab_limit", "plan"))
def _generate_jit(cfg: ModelConfig, rl: RLConfig, params, prompts, key,
                  max_new: int, vocab_limit: int,
                  memory: Optional[jax.Array] = None, plan=None,
                  rids: Optional[jax.Array] = None):
    b, tp = prompts.shape
    if plan is not None:        # tensor-parallel serve: the ExecutionPlan
        params = plan.constrain_params(cfg, params)
    cache = init_cache(cfg, params, b, tp + max_new, memory=memory)
    if plan is not None:        # KV cache placed by the same cache_specs
        cache = plan.constrain_cache(cfg, cache)
    logits, cache, _ = forward(cfg, params, prompts, cache=cache,
                               memory=memory)
    last = logits[:, -1]
    # one RNG stream per request id: draw t uses fold_in(fold_in(key, rid), t)
    # — identical draws no matter which engine/slot serves the request.
    # rid defaults to the batch row (the legacy batch entry point).
    if rids is None:
        rids = jnp.arange(b)
    row_keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(rids)

    def step(carry, t):
        cache, last, done, pos = carry
        lg = mask_vocab(last, vocab_limit)
        kt = jax.vmap(jax.random.fold_in)(row_keys, jnp.full((b,), t))
        tok, _, _ = sample_token_rows(kt, lg, temperature=rl.temperature,
                                      top_k=rl.top_k, top_p=rl.top_p)
        tok = jnp.where(done, PAD, tok)
        valid = ~done
        lp_model = jnp.where(done, 0.0, model_logp(last, tok))
        new_logits, cache = decode_step(cfg, params, cache, tok, pos,
                                        memory=memory)
        done = done | (tok == EOS)
        return (cache, new_logits, done, pos + 1), (tok, lp_model, valid)

    (_, _, done, _), (toks, lps, valid) = jax.lax.scan(
        step, (cache, last, jnp.zeros((b,), bool), jnp.int32(tp)),
        jnp.arange(max_new))
    completions = toks.T                        # (B, max_new)
    sampler_lp = lps.T
    comp_mask = valid.T.astype(jnp.float32)
    return completions, sampler_lp, comp_mask


class StaticEngine:
    """Request-level wrapper over the one-scan static path.

    The scan is rectangular, so a batch must share one prompt length
    (use the continuous engine for ragged/streaming workloads);
    ``max_new_tokens`` may vary per request — the scan runs to the batch
    max and each request is trimmed host-side, which is exact because
    draw ``t`` of request ``rid`` never depends on the scan length.
    """

    def __init__(self, cfg: ModelConfig, params, *, rl: RLConfig,
                 vocab_limit: Optional[int] = None,
                 memory: Optional[jax.Array] = None,
                 plan=None,
                 key: Optional[jax.Array] = None) -> None:
        self.cfg, self.rl, self.params = cfg, rl, params
        self.vocab_limit = vocab_limit or cfg.padded_vocab
        self.memory, self.plan = memory, plan
        self.key = key if key is not None else jax.random.PRNGKey(0)

    @property
    def profile(self) -> tuple:
        return (self.rl.temperature, self.rl.top_k, self.rl.top_p)

    def update_params(self, params: Any) -> None:
        self.params = params

    def generate(self, requests: Sequence[Request],
                 key: Optional[jax.Array] = None) -> List[GenerationResult]:
        if key is not None:
            self.key = key
        for req in requests:
            if req.params.profile != self.profile:
                raise ValueError(
                    f"request {req.rid}: sampling profile "
                    f"{req.params.profile} != engine profile {self.profile}")
        plens = {r.prompt_len for r in requests}
        if len(plens) > 1:
            raise ValueError(
                "static engine scans a rectangular batch: got prompt "
                f"lengths {sorted(plens)} — pad, or use the continuous "
                "engine for ragged prompts")
        t0 = time.perf_counter()
        prompts = jnp.asarray(np.stack([r.prompt for r in requests]))
        rids = jnp.asarray([r.rid for r in requests], jnp.int32)
        max_new = max(r.params.max_new_tokens for r in requests)
        completions, sampler_lp, comp_mask = _generate_jit(
            self.cfg, self.rl, self.params, prompts, self.key, max_new,
            self.vocab_limit, self.memory, plan=self.plan, rids=rids)
        elapsed = time.perf_counter() - t0
        # deliberate sync point: the static engine runs the whole batch to
        # completion in one executable, so the single batch-end transfer
        # is the design, not a stall in a loop
        comp_np = np.asarray(completions)   # noqa: RA003
        lp_np = np.asarray(sampler_lp)      # noqa: RA003
        mask_np = np.asarray(comp_mask)     # noqa: RA003
        out: List[GenerationResult] = []
        for i, req in enumerate(requests):
            budget = req.params.max_new_tokens
            n = int(mask_np[i, :budget].sum())
            toks = comp_np[i, :n]
            reason = "eos" if n and toks[-1] == EOS else "length"
            out.append(GenerationResult(
                rid=req.rid, tokens=toks.astype(np.int32),
                logps=lp_np[i, :n].astype(np.float32),
                finish_reason=reason, prompt_len=req.prompt_len,
                ttft_s=elapsed, latency_s=elapsed))
        return out


# --------------------------------------------------------------------------
# factory


def build_engine(cfg: ModelConfig, params, serve: ServeConfig, *,
                 rl: Optional[RLConfig] = None,
                 vocab_limit: Optional[int] = None,
                 memory: Optional[jax.Array] = None,
                 plan=None,
                 key: Optional[jax.Array] = None):
    """Construct the engine a ``ServeConfig`` describes.

    ``rl`` carries the deployment's sampling profile (every request must
    match it); ``plan`` is the resolved ExecutionPlan for ``serve.mesh``
    (callers that already placed ``params`` pass their plan). Falls back
    to the static engine — with a warning — for architectures the paged
    cache can't serve and for encoder/memory models.
    """
    rl = rl or RLConfig(engine=serve.engine)
    if serve.engine == "continuous":
        if memory is None and paged_cache_supported(cfg):
            return ContinuousEngine(
                cfg, params, rl=rl,
                max_total_tokens=serve.max_total_tokens,
                num_slots=serve.num_slots, page_size=serve.page_size,
                sync_every=serve.sync_every,
                prefill_chunk=serve.prefill_chunk or None,
                num_pages=serve.resolved_num_pages,
                vocab_limit=vocab_limit, plan=plan,
                prefix_cache=serve.prefix_cache,
                prefix_cache_entries=serve.prefix_cache_entries,
                spec_k=serve.spec_k,
                spec_ngram_max=serve.spec_ngram_max,
                spec_ngram_min=serve.spec_ngram_min, key=key)
        warnings.warn(f"{cfg.name}: continuous engine unsupported for this "
                      "architecture/memory setup; serving static",
                      stacklevel=2)
    return StaticEngine(cfg, params, rl=rl, vocab_limit=vocab_limit,
                        memory=memory, plan=plan, key=key)


# --------------------------------------------------------------------------
# legacy batch entry point (deprecated shim)


def generate(cfg: ModelConfig, rl: RLConfig, params, prompts: jax.Array,
             key: jax.Array, *, max_new: Optional[int] = None,
             vocab_limit: Optional[int] = None,
             memory: Optional[jax.Array] = None,
             engine: Optional[str] = None,
             plan=None,
             **continuous_kwargs) -> Dict[str, jax.Array]:
    """Batched generation over ``prompts`` (B, Tp). Returns a rollout dict:
    tokens (B, Tp+max_new) | completions (B, max_new) |
    sampler_lp (B, max_new) engine-side logps | comp_mask (B, max_new).

    .. deprecated::
        This is the pre-request-API surface, kept as a thin shim for the
        training loop and existing callers. New code should
        ``build_engine(cfg, params, ServeConfig(...))`` once and call
        ``engine.generate([Request(...), ...])`` — see README "Serving".

    ``engine`` (default ``rl.engine``) picks the static scan or the
    continuous-batching slot pool; architectures the paged cache can't
    serve (SSM/enc-dec/ring-KV/modality memory) fall back to static with
    a warning. Every path executes under an ``ExecutionPlan`` (``plan``;
    default: a serve-mode plan on whatever mesh ``params`` already live
    on) — on a >1-device mesh the same call runs tensor-parallel.
    """
    engine = engine or rl.engine
    plan = plan or plan_for_params(params, "serve")
    if engine not in ("static", "continuous"):
        raise ValueError(f"unknown engine {engine!r}")
    if engine == "static" and continuous_kwargs:
        # don't silently ignore num_slots=… etc. on the static path
        raise TypeError("static engine takes no continuous-engine kwargs: "
                        f"{sorted(continuous_kwargs)}")
    max_new = max_new or rl.max_new_tokens
    vocab_limit = vocab_limit or cfg.padded_vocab
    if engine == "continuous":
        if memory is None and paged_cache_supported(cfg):
            return generate_continuous(cfg, rl, params, prompts, key,
                                       max_new=max_new,
                                       vocab_limit=vocab_limit,
                                       plan=plan,
                                       **continuous_kwargs)
        dropped = (f"; ignoring {sorted(continuous_kwargs)}"
                   if continuous_kwargs else "")
        warnings.warn(f"{cfg.name}: continuous engine unsupported for this "
                      f"architecture/memory setup; falling back to "
                      f"static{dropped}", stacklevel=2)
    completions, sampler_lp, comp_mask = _generate_jit(
        cfg, rl, params, prompts, key, max_new, vocab_limit, memory,
        plan=plan)
    tokens = jnp.concatenate([prompts, completions], axis=1)
    return {"tokens": tokens, "completions": completions,
            "sampler_lp": sampler_lp, "comp_mask": comp_mask,
            "prompt_len": prompts.shape[1]}


def token_logps(cfg: ModelConfig, params, tokens: jax.Array, *,
                memory: Optional[jax.Array] = None,
                logprob_impl: Optional[str] = None) -> jax.Array:
    """Teacher-forced log p(tokens[t] | tokens[<t]) -> (B, T-1).

    This is the App. B.1 untrusted-sampler recompute — the same hot path
    as the learner's loss, so it dispatches to the fused streaming
    kernel (Pallas on TPU, chunked ``lax.map`` elsewhere) instead of
    materializing a (B·T, V) f32 log-softmax. ``logprob_impl`` takes the
    ``TrainConfig.logprob_impl`` vocabulary ("pallas" | "chunked" |
    "naive" to force a backend); None or "fused" auto-dispatches.
    """
    from repro.kernels.ops import fused_token_logprob
    logits, _, _ = forward(cfg, params, tokens[:, :-1], memory=memory)
    impl = None if logprob_impl == "fused" else logprob_impl
    lp, _ = fused_token_logprob(logits, tokens[:, 1:], impl=impl)
    return lp


__all__ = ["generate", "generate_continuous", "token_logps", "build_engine",
           "StaticEngine", "ContinuousEngine", "rollout_from_results",
           "GenerationResult", "Request", "SamplingParams"]
