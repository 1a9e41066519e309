"""Speculative decoding: draft proposal + k-token paged verification.

Raising decode tokens/s is GEPO's stability lever in the HeteroRL
setting: slow sampler nodes widen the latency window that inflates KL
divergence and importance-weight variance (PAPER.md §3), so a decode
speedup shrinks staleness directly. This module holds the *model-free*
half of the speculative pipeline — everything that does not need the
target model:

- :class:`DraftProposer` — the protocol the continuous engine drafts
  through; a small draft *model* can slot in later behind the same
  ``propose(history, k)`` surface.
- :class:`NGramDrafter` — prompt-lookup / n-gram drafting over the
  slot's own token history (prompt + committed completion): find the
  most recent earlier occurrence of the current n-gram suffix and
  propose its continuation. Zero extra FLOPs, surprisingly strong on
  templated / repetitive workloads, honest ~0 accept rate on
  incompressible ones.
- :func:`accept_drafts` — the in-jit acceptance rule, shared by the
  engine's verification executable and the tests.
- :func:`fused_rescore_diff` — the acceptance *rescore* through ONE
  ``paged_prefill_layers`` launch instead of L per-layer launches (the
  fused-layer kernels' first real consumer): replay every layer's
  window attention from the recorded per-layer queries against the
  freshly-scattered pools and report the max abs deviation from the
  in-forward outputs. Bit-exactness is the invariant (same operands,
  row-independent math); a nonzero value means the folded launch and
  the scan disagree — a kernel regression surfaced at serve time on a
  gauge instead of in a post-mortem.

Acceptance rule (exact replay)
------------------------------
The engine's RNG is counter-based: draw ``g`` of request ``rid`` is
``categorical(fold_in(req_key, g), filtered_logits)`` — a pure function
of (key, logits), independent of sampling history. Verification scores
the window ``[pending, d_1..d_k]`` in one prefill-shaped forward, so
row ``i-1`` holds the target logits *after* ``d_1..d_{i-1}``; replaying
the engine's draw at every row then gives the exact token the
sequential non-speculative engine would have emitted, and the accepted
prefix is the longest one where the drafts match those draws. This is
speculative rejection sampling with a point-mass proposal evaluated
against the engine's own uniform stream: the emitted tokens are
*literally* the target model's sequential samples, so the sampled
distribution is preserved exactly (not just in expectation), greedy
decoding stays bit-identical to the non-speculative path, and every
reported logp is the target model's logp of the emitted token — never
the drafter's (the GEPO importance-weight contract, App. B.1).
"""
from __future__ import annotations

from typing import Protocol, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import LOCAL, ModelConfig
from repro.data.tasks import EOS, PAD
from repro.sampling.sample import mask_vocab, model_logp, sample_token_rows

_EMPTY = np.zeros((0,), np.int32)


class DraftProposer(Protocol):
    """Anything that can guess the next ``k`` tokens for one slot."""

    def propose(self, history: np.ndarray, k: int) -> np.ndarray:
        """Given the slot's token history (prompt + committed completion,
        1-D int32, pending token last), return up to ``k`` proposed next
        tokens (1-D int32, possibly empty). Host-side, per slot."""
        ...


class NGramDrafter:
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the current suffix n-gram.

    Tries suffix lengths ``max_ngram`` down to ``min_ngram`` and takes
    the *most recent* prior match — recency beats frequency on the
    looping/templated outputs this drafter exists for. ``max_history``
    bounds the per-call scan so drafting stays O(history) cheap.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1,
                 max_history: int = 4096) -> None:
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(f"need 1 <= min_ngram <= max_ngram, got "
                             f"({min_ngram}, {max_ngram})")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.max_history = max_history

    def propose(self, history: np.ndarray, k: int) -> np.ndarray:
        h = np.ascontiguousarray(
            np.asarray(history, np.int32)[-self.max_history:])
        out = self._lookup(h, k)
        # chain: a match near the end of history (short loop) yields a
        # continuation shorter than k — extend it by re-proposing over
        # history + draft-so-far, so a length-c cycle still fills all k
        # slots instead of c-1. Each iteration adds >= 1 token or stops.
        while 0 < out.shape[0] < k:
            more = self._lookup(np.concatenate([h, out]), k - out.shape[0])
            if more.shape[0] == 0:
                break
            out = np.concatenate([out, more])
        return out

    def _lookup(self, h: np.ndarray, k: int) -> np.ndarray:
        n = h.shape[0]
        if k <= 0 or n < self.min_ngram + 1:
            return _EMPTY
        # byte-level rfind (C speed — this runs per slot per verify
        # round, so the python cost of a sliding-window compare would
        # land straight on the round latency): a window starting at
        # element j0 is a match at byte offset 4*j0, so unaligned hits
        # are skipped. End bound (n-1)*4 keeps the match start strictly
        # before the suffix's own start.
        hb = h.tobytes()
        for ng in range(min(self.max_ngram, n - 1), self.min_ngram - 1, -1):
            pb = h[n - ng:].tobytes()
            j = hb.rfind(pb, 0, (n - 1) * 4)
            while j > 0 and j % 4:
                j = hb.rfind(pb, 0, j + len(pb) - 1)
            if j >= 0:
                j //= 4                             # most recent match
                return h[j + ng:j + ng + k].copy()
        return _EMPTY


def accept_drafts(logits: jax.Array, window_tokens: jax.Array,
                  draft_len: jax.Array, active: jax.Array,
                  req_keys: jax.Array, gen_base: jax.Array,
                  max_new: jax.Array, *, temperature: float, top_k: int,
                  top_p: float, vocab_limit: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Longest-valid-prefix acceptance by exact replay (module doc).

    logits (B, W, V) raw f32 from the verification forward over the
    window ``[pending, d_1..d_{draft_len}, pad...]``; row ``i-1`` is the
    target distribution for emission ``i``. ``gen_base`` (B,) is the
    pending token's generation index (-1 right after prefill, when the
    pending token is the last prompt token). Returns
    ``(toks, lps, n_emit, n_acc)``: emitted tokens/logps packed into
    (B, W) (col j = emission j+1, PAD/0 past ``n_emit``), the emitted
    count, and how many emissions were accepted drafts (telemetry).
    Emission stops at the first rejection + its replacement draw, at an
    emitted EOS, and at the per-request token budget; logps are the
    *target* model's (``model_logp`` on the raw row — the decode path's
    convention), never the drafter's.
    """
    b, w, v = logits.shape
    flat = logits.reshape(b * w, v)
    gidx = (gen_base[:, None] + 1 + jnp.arange(w)[None, :]).reshape(-1)
    keys = jax.vmap(jax.random.fold_in)(jnp.repeat(req_keys, w, axis=0),
                                        gidx)
    # the exact draw the sequential engine would make at each row —
    # same per-request counter-based stream, same filtered distribution
    that, _, _ = sample_token_rows(keys, mask_vocab(flat, vocab_limit),
                                   temperature=temperature, top_k=top_k,
                                   top_p=top_p)
    lp_hat = model_logp(flat, that).reshape(b, w)
    that = that.reshape(b, w)

    drafts = window_tokens[:, 1:]                       # (B, W-1)
    cols = jnp.arange(1, w)[None, :]
    match = (drafts == that[:, :-1]) & (cols <= draft_len[:, None])
    chain = jnp.cumprod(match.astype(jnp.int32), axis=1)  # accepted prefix
    n_acc_chain = chain.sum(axis=1)

    idx = jnp.arange(1, w + 1)[None, :]                 # emission index
    can = ((idx <= n_acc_chain[:, None] + 1)            # prefix + replay draw
           & (gen_base[:, None] + idx <= max_new[:, None] - 1)
           & active[:, None])
    eos = (that == EOS) & can
    eos_before = jnp.cumsum(eos.astype(jnp.int32), axis=1) \
        - eos.astype(jnp.int32)
    emit = can & (eos_before == 0)
    toks = jnp.where(emit, that, PAD)
    lps = jnp.where(emit, lp_hat, 0.0).astype(jnp.float32)
    n_emit = emit.astype(jnp.int32).sum(axis=1)
    n_acc = (chain.astype(bool) & emit[:, :-1]).sum(axis=1)
    return toks, lps, n_emit, n_acc


def stacked_pools(cfg: ModelConfig, pool) -> Tuple[jax.Array, jax.Array]:
    """Assemble the (L, pages, Hkv, page, D) stacked-pool layout
    ``paged_*_layers`` folds, from the engine pool's scanned-block
    layout (per-pattern-position ``layer_{i}`` leaves each stacked on
    the super-block axis). Layer order is block-major — exactly the
    order ``_run_blocks`` records its q/o tapes in."""
    period = len(cfg.block_pattern)
    kp = jnp.stack([pool[f"layer_{i}"]["self"]["kp"] for i in range(period)],
                   axis=1)
    vp = jnp.stack([pool[f"layer_{i}"]["self"]["vp"] for i in range(period)],
                   axis=1)
    return (kp.reshape((-1,) + kp.shape[2:]),
            vp.reshape((-1,) + vp.shape[2:]))


def fused_rescore_diff(cfg: ModelConfig, pool, q_tape: jax.Array,
                       o_tape: jax.Array, page_table: jax.Array,
                       positions: jax.Array) -> jax.Array:
    """Rescore every layer's window attention through ONE
    ``paged_prefill_layers`` launch per mask kind (one, for uniform
    patterns) and return max |fused − in-forward| — the fused-layer
    kernels' consumer on the verification path. Layers sharing a mask
    kind fold together; mixed ATTN/LOCAL patterns take one launch per
    kind, still O(kinds) ≪ L."""
    from repro.kernels.ops import paged_prefill_layers
    kp, vp = stacked_pools(cfg, pool)
    period = len(cfg.block_pattern)
    kinds = [cfg.block_pattern[i % period] for i in range(kp.shape[0])]
    diff = jnp.float32(0.0)
    for kind in dict.fromkeys(kinds):
        idx = jnp.asarray([i for i, k in enumerate(kinds) if k == kind],
                          jnp.int32)
        o = paged_prefill_layers(
            q_tape[idx], kp[idx], vp[idx], page_table, positions,
            kind=("local" if kind == LOCAL else "causal"),
            window=cfg.sliding_window, softcap=cfg.attn_softcap,
            impl=cfg.paged_attn_impl, attn_impl=cfg.attn_impl,
            chunk=cfg.attn_chunk)
        diff = jnp.maximum(diff, jnp.max(jnp.abs(o - o_tape[idx])))
    return diff


def verify_width_buckets(spec_k: int) -> int:
    """Distinct verification-window widths the engine can hand the
    jitted verify fn for a draft cap of ``spec_k``: widths are
    max(2, min(next_pow2(1 + k), spec_k + 1)) for k in 0..spec_k — the
    pow2 bucketing that keeps verify executables O(log spec_k). The
    floor of 2 keeps the window on the prefill-shaped (query-recording)
    attention path even when nothing was drafted."""
    widths = set()
    for k in range(spec_k + 1):
        w = 1
        while w < 1 + k:
            w *= 2
        widths.add(max(2, min(w, spec_k + 1)))
    return len(widths)


__all__ = ["DraftProposer", "NGramDrafter", "accept_drafts",
           "stacked_pools", "fused_rescore_diff", "verify_width_buckets"]
