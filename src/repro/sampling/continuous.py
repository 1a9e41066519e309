"""Continuous-batching engine: persistent slot pool over a paged KV cache.

``ContinuousEngine`` is the request-level engine behind both the batch
``generate_continuous`` wrapper (one call = submit a batch, drain it)
and the asyncio serving front door (``repro.serving.server``), which
keeps one engine alive across an open-ended request stream:

- ``submit()`` queues a :class:`repro.serving.api.Request`;
- ``step()`` runs one scheduler round — admission (priority classes,
  deadlines, shared-prefix page reuse with copy-on-write), one prefill
  chunk per prefilling slot, one jitted decode chunk over every slot —
  and returns the :class:`~repro.serving.api.TokenEvent` stream that
  round produced;
- ``generate()`` is the batch convenience: submit, step until drained,
  return per-request results.

The engine owns the device state (page pools, per-slot logits, RNG
streams); the scheduler owns the host bookkeeping (block table,
allocator, prefix cache). Tokens and log-probs are bit-identical to the
static engine for the same key because RNG folds per request id, never
per slot — and bit-identical with or without prefix reuse because
cached pages hold exactly the K/V a cold prefill would write.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.config import ModelConfig, RLConfig
from repro.data.tasks import EOS, PAD
from repro.models import decode_step, forward
from repro.sampling.paged_cache import (PageAllocator, SCRATCH_PAGE,
                                        init_paged_pool,
                                        paged_cache_supported, pages_for)
from repro.sampling.prefix_cache import PrefixCache
from repro.sampling.sample import mask_vocab, model_logp, sample_token_rows
from repro.sampling.scheduler import (DECODE, PREFILL, ContinuousScheduler,
                                      GenRequest)
from repro.sampling.spec import DraftProposer, NGramDrafter, accept_drafts
from repro.serving.api import (GenerationResult, Request, SamplingParams,
                               TokenEvent)


@functools.partial(jax.jit, static_argnames=("cfg", "plan"),
                   donate_argnums=(2,))
def _prefill_chunk_jit(cfg: ModelConfig, params, pool, page_row, tokens,
                       start, plan=None):
    """One chunk of one request's prompt: tokens (1, C) at positions
    ``start + [0, C)``, K/V scattered into the request's pages. Returns
    (logits (C, V), pool)."""
    if plan is not None:
        params = plan.constrain_params(cfg, params)
        pool = plan.constrain_cache(cfg, pool)
    c = tokens.shape[1]
    positions = start + jnp.arange(c, dtype=jnp.int32)[None, :]
    logits, pool, _ = forward(cfg, params, tokens, positions=positions,
                              cache=pool, page_table=page_row)
    return logits[0], pool


@functools.partial(jax.jit, static_argnames=("cfg", "plan"),
                   donate_argnums=(2,))
def _copy_page_jit(cfg: ModelConfig, plan, pool, src, dst):
    """Copy physical page ``src`` onto ``dst`` across every layer's K/V
    pools — the copy-on-write step of shared-prefix admission (the new
    request appends into its private copy of a cached partial tail
    page)."""
    if plan is not None:
        pool = plan.constrain_cache(cfg, pool)

    def cp(leaf):                       # (nb, pages, Hkv, page, D)
        return leaf.at[:, dst].set(leaf[:, src])

    return jax.tree_util.tree_map(cp, pool)


@functools.partial(jax.jit, static_argnames=("cfg", "rl", "vocab_limit",
                                             "sync_every", "plan"),
                   donate_argnums=(3,))
def _decode_chunk_jit(cfg: ModelConfig, rl: RLConfig, params, pool,
                      page_table, last, pos, active, req_keys, gen0,
                      max_new_v, vocab_limit: int, sync_every: int,
                      plan=None):
    """``sync_every`` decode steps over every slot in one executable — the
    decode horizon that amortizes host dispatch; the scheduler regains
    control (EOS recycling, admission) only between chunks.

    Slots that finish mid-chunk (EOS / token budget) keep decoding PAD
    at a position past the block-table width, so their K/V writes hit
    the OOB-drop path instead of any physical page — with shared-prefix
    reuse a slot's own first page may be referenced by other requests,
    so a dead slot must write *nowhere*, not "harmlessly at position 0"
    as the pre-refcount engine did. Draw ``i`` of slot ``s`` uses
    fold_in(req_keys[s], gen0[s]+i): the host discards post-EOS draws,
    and earlier draws are bit-identical to the static engine's.
    """
    if plan is not None:
        params = plan.constrain_params(cfg, params)
        pool = plan.constrain_cache(cfg, pool)
    page_size = jax.tree_util.tree_leaves(pool)[0].shape[2]
    oob_pos = jnp.int32(page_table.shape[1] * page_size)

    def step(carry, i):
        pool, last, done = carry
        over = (gen0 + i) >= max_new_v              # token budget exhausted
        dead = done | over
        lg = mask_vocab(last, vocab_limit)
        kt = jax.vmap(jax.random.fold_in)(req_keys, gen0 + i)
        tok, _, _ = sample_token_rows(kt, lg, temperature=rl.temperature,
                                      top_k=rl.top_k, top_p=rl.top_p)
        lp = jnp.where(dead, 0.0, model_logp(last, tok))
        tok = jnp.where(dead, PAD, tok)
        step_pos = jnp.where(dead, oob_pos, pos + i)
        new_last, pool = decode_step(cfg, params, pool, tok, step_pos,
                                     page_table=page_table)
        done = done | (tok == EOS)
        return (pool, new_last, done), (tok, lp)

    (pool, last, _), (toks, lps) = jax.lax.scan(
        step, (pool, last, ~active), jnp.arange(sync_every))
    return toks, lps, last, pool                    # toks (K, num_slots)


@functools.partial(jax.jit, static_argnames=("cfg", "rl", "vocab_limit",
                                             "plan"),
                   donate_argnums=(3,))
def _verify_chunk_jit(cfg: ModelConfig, rl: RLConfig, params, pool,
                      page_table, packed, req_keys, max_new_v,
                      vocab_limit: int, plan=None):
    """One speculative round over every slot in one executable: score the
    per-slot window ``[pending, d_1..d_k, pad]`` in ONE prefill-shaped
    target forward through the ``paged_prefill`` dispatcher (positions
    are each slot's contiguous ``pos0 + [0, W)``), then accept the
    longest draft prefix whose tokens match the engine's replayed draws
    (``repro.sampling.spec.accept_drafts`` — distribution preserved
    exactly, greedy bit-identical to the non-speculative path).

    ``packed`` (B, W+4) int32 carries everything that changes per round
    in ONE host->device transfer — columns ``[window(W), draft_len,
    gen_base, pos0, active]`` — because this dispatch sits on the decode
    critical path and a handful of small device_puts per round was
    measurably the dominant cost. ``req_keys``/``max_new_v`` change only
    at admission and ride a cached device array.

    Every window column scatters K/V at its contiguous position —
    rejected/padded columns land on the slot's own reserved-but-unread
    page slots and are overwritten before any later query can attend
    them (the append-only rollback: rewinding positions, no page
    copies). Inactive slots run at positions ``[0, W)`` (pos0 = 0)
    against the scratch page, the prefill-shaped twin of the decode
    chunk's dead slots.

    Returns (iout (B, W+2) int32 = [toks(W), n_emit, n_acc],
    lps (B, W) f32, pool) — two device->host transfers on the result
    side for the same reason.
    """
    if plan is not None:
        params = plan.constrain_params(cfg, params)
        pool = plan.constrain_cache(cfg, pool)
    w = packed.shape[1] - 4
    window_tokens = packed[:, :w]
    draft_len, gen_base, pos0 = packed[:, w], packed[:, w + 1], \
        packed[:, w + 2]
    active = packed[:, w + 3].astype(bool)
    positions = pos0[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    logits, pool, _ = forward(cfg, params, window_tokens,
                              positions=positions, cache=pool,
                              page_table=page_table)
    toks, lps, n_emit, n_acc = accept_drafts(
        logits, window_tokens, draft_len, active, req_keys, gen_base,
        max_new_v, temperature=rl.temperature, top_k=rl.top_k,
        top_p=rl.top_p, vocab_limit=vocab_limit)
    iout = jnp.concatenate([toks, n_emit[:, None], n_acc[:, None]], axis=1)
    return iout, lps, pool


@functools.partial(jax.jit, static_argnames=("cfg", "rl", "vocab_limit",
                                             "sync_every", "plan"),
                   donate_argnums=(3,))
def _spec_decode_chunk_jit(cfg: ModelConfig, rl: RLConfig, params, pool,
                           page_table, pending, pos0, active, req_keys,
                           gen_base, max_new_v, vocab_limit: int,
                           sync_every: int, plan=None):
    """Sequential decode chunk in the *pending-token* state convention —
    the spec engine's fallback when no slot drafted anything this round
    (cold history, or acceptance-gated drafting backed off on an
    incompressible stream). The decode-chunk twin of ``_decode_chunk_jit``
    shifted by one: each step scatters the carried token's K/V and draws
    the next from the resulting logits, so no ``last``-logits state is
    needed and the final draw is left pending for the next round. Draw
    ``i`` uses ``fold_in(req_keys, gen_base + 1 + i)`` — the same
    per-request counter stream as verification, so tokens stay
    bit-identical whichever path emits them.
    """
    if plan is not None:
        params = plan.constrain_params(cfg, params)
        pool = plan.constrain_cache(cfg, pool)
    page_size = jax.tree_util.tree_leaves(pool)[0].shape[2]
    oob_pos = jnp.int32(page_table.shape[1] * page_size)

    def step(carry, i):
        pool, tok, done = carry
        gi = gen_base + 1 + i                    # gen index of this draw
        dead = done | (gi >= max_new_v)
        step_pos = jnp.where(dead, oob_pos, pos0 + i)
        logits, pool = decode_step(cfg, params, pool, tok, step_pos,
                                   page_table=page_table)
        kt = jax.vmap(jax.random.fold_in)(req_keys, gi)
        nt, _, _ = sample_token_rows(kt, mask_vocab(logits, vocab_limit),
                                     temperature=rl.temperature,
                                     top_k=rl.top_k, top_p=rl.top_p)
        lp = jnp.where(dead, 0.0, model_logp(logits, nt))
        nt = jnp.where(dead, PAD, nt)
        done = done | (nt == EOS)
        return (pool, nt, done), (nt, lp)

    (pool, _, _), (toks, lps) = jax.lax.scan(
        step, (pool, pending, ~active), jnp.arange(sync_every))
    return toks, lps, pool                       # toks (K, num_slots)


# acceptance-EMA drafting gate: below _SPEC_EMA_MIN the drafter has
# demonstrably nothing to offer this request (incompressible stream) and
# proposing more drafts only pays verification width for nothing; a
# backed-off request re-probes every _SPEC_PROBE_EVERY rounds in case
# the stream turns templated (e.g. the model falls into a cycle)
_SPEC_EMA_MIN = 0.25
_SPEC_EMA_DECAY = 0.5
_SPEC_PROBE_EVERY = 4


def _live_width(need_pages: int, cap: int) -> int:
    """Block-table width actually handed to the jitted chunk fns: the
    live-page high-water mark rounded up to a power of two (so widths
    bucket into O(log) executables), capped at ``pages_per_slot``.

    Narrowing is *bit-exact*: every page dropped is provably masked in
    attention (positions >= every slot's length), and masked entries
    contribute exact zeros to the softmax — so even the default gather
    impl stops materializing (and the kernel stops iterating) the dead
    tail of the pool."""
    w = 1
    while w < need_pages:
        w *= 2
    return min(w, cap)


def clamp_prefill_chunk(prefill_chunk: Optional[int],
                        limit: int) -> Optional[int]:
    """Clamp a configured prefill chunk width to ``limit`` tokens.

    None/0 ("prefill everything in one chunk") stays None; a configured
    width never exceeds what there is to prefill. The single definition
    of a fallback that ``ContinuousEngine.step`` (per-request remaining
    tokens) and ``generate_continuous`` (prompt width) used to each
    encode on their own.
    """
    if not prefill_chunk:
        return None
    return min(prefill_chunk, limit)


class ContinuousEngine:
    """Persistent continuous-batching engine over one model + page pool.

    One engine serves one sampling *profile* (temperature/top-k/top-p —
    the jit-static triple; ``max_new_tokens`` is per-request) and one
    page-pool capacity. Capacity knobs come from ``ServeConfig`` via
    ``repro.sampling.build_engine``; this constructor takes them raw.
    """

    def __init__(self, cfg: ModelConfig, params, *, rl: RLConfig,
                 max_total_tokens: int,
                 num_slots: int = 8,
                 page_size: int = 16,
                 sync_every: int = 8,
                 prefill_chunk: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 vocab_limit: Optional[int] = None,
                 plan=None,
                 prefix_cache: bool = True,
                 prefix_cache_entries: int = 64,
                 spec_k: int = 0,
                 drafter: Optional[DraftProposer] = None,
                 spec_ngram_max: int = 3,
                 spec_ngram_min: int = 1,
                 key: Optional[jax.Array] = None) -> None:
        if not paged_cache_supported(cfg):
            raise ValueError(f"{cfg.name}: continuous engine needs an "
                             "attention-only decode cache (no enc-dec / "
                             "ring-KV / modality memory)")
        if cfg.paged_attn_impl == "auto" and plan is not None \
                and plan.num_devices > 1:
            # a pallas_call has no GSPMD rule: sharded engines gather
            cfg = dataclasses.replace(cfg, paged_attn_impl="gather")
        self.cfg, self.rl, self.params, self.plan = cfg, rl, params, plan
        self.vocab_limit = vocab_limit or cfg.padded_vocab
        self.num_slots = num_slots
        self.page_size = page_size
        self.sync_every = sync_every
        self.prefill_chunk = prefill_chunk
        self.max_total_tokens = max_total_tokens
        self.pages_per_slot = pages_for(max_total_tokens, page_size)
        self.num_pages = num_pages or 1 + num_slots * self.pages_per_slot
        if self.num_pages < 1 + self.pages_per_slot:
            raise ValueError(
                f"num_pages={self.num_pages} cannot hold even one "
                f"max-size request ({self.pages_per_slot} pages + scratch)")
        allocator = PageAllocator(self.num_pages)
        self.prefix_cache = (PrefixCache(page_size, allocator,
                                         max_entries=prefix_cache_entries)
                             if prefix_cache else None)
        self.sched = ContinuousScheduler(num_slots, self.pages_per_slot,
                                         page_size, allocator,
                                         prefix_cache=self.prefix_cache)
        self.pool = init_paged_pool(cfg, self.num_pages, page_size)
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.spec_k = spec_k
        self.drafter: Optional[DraftProposer] = drafter
        if spec_k > 0 and self.drafter is None:
            self.drafter = NGramDrafter(max_ngram=spec_ngram_max,
                                        min_ngram=spec_ngram_min)
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self._last = jnp.zeros((num_slots, cfg.padded_vocab), jnp.float32)
        self._pos = np.zeros((num_slots,), np.int32)
        self._active = np.zeros((num_slots,), bool)
        self._gen = np.zeros((num_slots,), np.int32)
        self._max_new = np.ones((num_slots,), np.int32)
        self._req_keys = np.zeros((num_slots, 2), np.uint32)  # threefry data
        self._results: Dict[int, GenerationResult] = {}
        # unified observability (repro.obs): handles bound once — each
        # use is one enabled-check when the registry is off, and a span
        # one more check for a profiler session
        m = obs.metrics
        self._tr = obs.trace
        self._m_prefill_chunks = m.counter(
            "engine_prefill_chunks_total", "prefill chunks executed")
        self._m_prefill_tokens = m.counter(
            "engine_prefill_tokens_total", "prompt tokens prefilled")
        self._m_decode_steps = m.counter(
            "engine_decode_steps_total", "decode steps executed")
        self._m_cow = m.counter(
            "engine_cow_copies_total", "shared-prefix copy-on-write copies")
        self._g_free_pages = m.gauge(
            "engine_free_pages", "KV pages on the free list")
        self._g_queue = m.gauge(
            "engine_queue_depth", "requests queued behind admission")
        self._g_slot_util = m.gauge(
            "engine_slot_utilization", "decode-slot occupancy (instant)")
        self._g_prefix_hits = m.gauge(
            "engine_prefix_cache_hits", "shared-prefix cache hits")
        self._g_prefix_reused = m.gauge(
            "engine_prefix_tokens_reused",
            "prompt tokens served from cached prefix pages")
        self._m_spec_rounds = m.counter(
            "engine_spec_rounds_total", "speculative verification rounds")
        self._m_spec_drafted = m.counter(
            "engine_spec_drafted_total", "draft tokens proposed")
        self._m_spec_accepted = m.counter(
            "engine_spec_accepted_total", "draft tokens accepted")
        self._g_accept_rate = m.gauge(
            "engine_spec_accept_rate",
            "accepted / drafted tokens (cumulative)")
        self._g_draft_hit = m.gauge(
            "engine_spec_draft_hit_rate",
            "slot-rounds where the drafter proposed anything (cumulative)")
        # per-request acceptance EMA ([ema, rounds_since_draft]) — gates
        # drafting off on incompressible streams (periodic re-probe)
        self._spec_ema: Dict[int, List[float]] = {}
        # host-compare cache of small device-resident dispatch args
        self._dev_cache: Dict[str, tuple] = {}

    # ------------------------------------------------------------------
    @property
    def profile(self) -> tuple:
        return (self.rl.temperature, self.rl.top_k, self.rl.top_p)

    @property
    def free_pages(self) -> int:
        return self.sched.allocator.available

    @property
    def evictable_pages(self) -> int:
        """Pages a prefix-cache flush could return to the free list."""
        if self.prefix_cache is None:
            return 0
        alloc = self.sched.allocator
        return sum(1 for ent in self.prefix_cache._entries.values()
                   for pg in ent.pages if alloc.refcount(pg) == 1)

    def has_work(self) -> bool:
        return (self.sched.queue_depth > 0
                or any(r is not None for r in self.sched.slots))

    def update_params(self, params: Any) -> None:
        self.params = params
        # cached prefix pages hold KV computed under the old weights
        if self.prefix_cache is not None:
            self.prefix_cache.clear()

    def stats(self) -> Dict[str, float]:
        out: Dict[str, float] = dict(self.sched.stats)
        out["slot_utilization"] = self.sched.slot_utilization()
        out["free_pages"] = self.free_pages
        # speculative-decode surface (flows to /metrics via stats())
        out["accept_rate"] = (out["accepted_tokens_total"]
                              / max(out["drafted_tokens_total"], 1))
        out["draft_hit_rate"] = (out["draft_hits"]
                                 / max(out["spec_slot_rounds"], 1))
        if self.prefix_cache is not None:
            for k, v in self.prefix_cache.stats.items():
                out[f"prefix_cache_{k}"] = v
        return out

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue a request. Raises on profile mismatch (one sampling
        profile per engine — spin up another engine for another
        profile) and on prompts that can never fit the page budget."""
        if req.params.profile != self.profile:
            raise ValueError(
                f"request {req.rid}: sampling profile {req.params.profile} "
                f"!= engine profile {self.profile} — one profile per "
                "engine (max_new_tokens may vary per request)")
        total = req.prompt_len + req.params.max_new_tokens
        if pages_for(total, self.page_size) > self.pages_per_slot:
            raise ValueError(
                f"request {req.rid}: {total} tokens exceed the engine's "
                f"max_total_tokens={self.max_total_tokens}")
        self.sched.submit(GenRequest(
            rid=req.rid, prompt=req.prompt,
            max_new=req.params.max_new_tokens, priority=req.priority,
            deadline_s=req.deadline_s, arrival_s=req.arrival_s,
            spec_ok=req.params.spec))

    def _finish_result(self, r: GenRequest) -> GenerationResult:
        res = GenerationResult(
            rid=r.rid, tokens=np.asarray(r.tokens, np.int32),
            logps=np.asarray(r.logps, np.float32),
            finish_reason=r.finish_reason, prompt_len=r.prompt_len,
            prefix_hit_tokens=r.prefix_hit_tokens,
            ttft_s=(r.t_first_token - r.arrival_s
                    if r.t_first_token >= 0 else float("nan")),
            latency_s=r.t_done - r.arrival_s)
        self._results[r.rid] = res
        return res

    def pop_result(self, rid: int) -> Optional[GenerationResult]:
        return self._results.pop(rid, None)

    def _publish_gauges(self) -> None:
        """Page-pool / queue / prefix-cache gauges, refreshed once per
        ``step`` round. Guarded as a block so the disabled path pays one
        check instead of one per gauge."""
        if not obs.metrics.enabled:
            return
        sched = self.sched
        self._g_free_pages.set(self.free_pages)
        self._g_queue.set(sched.queue_depth)
        self._g_slot_util.set(
            sum(1 for r in sched.slots if r is not None)
            / max(self.num_slots, 1))
        if self.prefix_cache is not None:
            st = self.prefix_cache.stats
            self._g_prefix_hits.set(st.get("hits", 0))
            self._g_prefix_reused.set(st.get("tokens_reused", 0))

    # ------------------------------------------------------------------
    def step(self, now_s: Optional[float] = None) -> List[TokenEvent]:
        """One scheduler round: admit → one prefill chunk per prefilling
        slot → one decode chunk. Returns this round's token events
        (streaming order: per request, in-completion order).

        Each phase is a span on track ``engine`` (``repro.engine.*`` on
        a profiler's timeline): ``step`` holds ``admit``, one
        ``prefill`` per chunk, ``decode`` or ``verify`` (the chunk's
        inputs and dispatch), ``sync`` (the host waiting for the chunk's
        results) and ``commit`` (the per-slot token loop)."""
        now = time.perf_counter() if now_s is None else now_s
        events: List[TokenEvent] = []
        with self._tr.span("step", track="engine"):
            with self._tr.span("admit", track="engine"):
                self._admit(now, events)
            self._prefill_chunks()
            dec = self.sched.decoding()
            if not dec:
                self._publish_gauges()
            elif self.spec_k > 0:
                self._spec_round(dec, now, events)
            else:
                self._decode_chunk(dec, now, events)
        return events

    def _admit(self, now: float, events: List[TokenEvent]) -> None:
        """Admission, deadline expiry and shared-prefix copy-on-write."""
        sched = self.sched
        newly = sched.admit(now)
        for r in sched.drain_expired():
            self._finish_result(r)
            events.append(TokenEvent(rid=r.rid, token=-1, logp=0.0, index=0,
                                     finished=True, finish_reason="expired"))
        for r in newly:
            if r.cow_src >= 0:
                self.pool = _copy_page_jit(self.cfg, self.plan, self.pool,
                                           jnp.int32(r.cow_src),
                                           jnp.int32(r.cow_dst))
                sched.stats["cow_copies"] += 1
                self._m_cow.inc()
        if not newly and sched.queue_depth > 0 \
                and all(r is None for r in sched.slots):
            raise RuntimeError(
                "admission stalled with an empty slot pool: the page pool "
                f"({self.num_pages} pages) cannot fit the head request "
                "even after prefix-cache eviction")

    def _prefill_chunks(self) -> None:
        """Chunked prefill: every prefilling slot advances one chunk per
        step, interleaved with the decode chunk."""
        sched = self.sched
        for pref in [r for r in sched.slots
                     if r is not None and r.state == PREFILL]:
            c0 = pref.prefill_pos
            remaining = pref.prompt_len - c0
            cw = clamp_prefill_chunk(self.prefill_chunk,
                                     remaining) or remaining
            # only pages reachable from this chunk's max position — the
            # gather inside the paged prefill branch scales with c0 + C,
            # not pool capacity. Padded-tail writes past the narrowed
            # width hit the same OOB-drop path as past the full width.
            width = _live_width(pages_for(c0 + cw, self.page_size),
                                self.pages_per_slot)
            with self._tr.span("prefill", track="engine", rid=pref.rid,
                               slot=pref.slot, start=c0, chunk=cw,
                               width=width):
                chunk = pref.prompt[c0:c0 + cw]
                if chunk.shape[0] < cw:             # pad to fixed shape
                    chunk = np.concatenate(
                        [chunk, np.full(cw - chunk.shape[0], PAD, np.int32)])
                page_row = jnp.asarray(
                    sched.block_table[pref.slot:pref.slot + 1, :width])
                logits_c, self.pool = _prefill_chunk_jit(
                    self.cfg, self.params, self.pool, page_row,
                    jnp.asarray(chunk[None]), jnp.int32(c0), plan=self.plan)
            sched.stats["prefill_chunks"] += 1
            pref.prefill_pos = min(pref.prompt_len, c0 + cw)
            sched.stats["prefill_tokens"] += pref.prefill_pos - c0
            self._m_prefill_chunks.inc()
            self._m_prefill_tokens.inc(pref.prefill_pos - c0)
            if pref.prefill_pos >= pref.prompt_len:  # prompt fully cached
                s = pref.slot
                self._last = self._last.at[s].set(
                    logits_c[pref.prompt_len - 1 - c0])
                pref.state = DECODE
                self._active[s], self._pos[s] = True, pref.prompt_len
                self._gen[s], self._max_new[s] = 0, pref.max_new
                self._req_keys[s] = np.asarray(
                    jax.random.fold_in(self.key, pref.rid), np.uint32)
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(
                        pref.prompt,
                        pref.pages[:pages_for(pref.prompt_len,
                                              self.page_size)])

    def _decode_chunk(self, dec: List[GenRequest], now: float,
                      events: List[TokenEvent]) -> None:
        """``sync_every`` decode steps over every slot."""
        sched = self.sched
        # non-decoding slots (empty, or mid-prefill) must scatter their
        # dead PAD writes into the scratch page — NOT position 0 of pages
        # a prefilling request has already filled. The table is narrowed
        # to the live high-water mark over this decode chunk (per-slot
        # ``lengths`` = the pos vector bound the page loop inside the
        # kernel; the width bounds every impl's upper shape).
        width = _live_width(
            pages_for(int(self._pos[self._active].max()) + self.sync_every,
                      self.page_size),
            self.pages_per_slot)
        steps = np.where(self._active,
                         np.clip(self._max_new - self._gen, 0,
                                 self.sync_every), 0)
        read, table = self._count_kv_pages(self._pos, steps, width)
        with self._tr.span("decode", track="engine",
                           slots=len(dec), chunk=self.sync_every,
                           width=width, kv_pages_read=read,
                           kv_pages_table=table):
            bt = sched.block_table[:, :width].copy()
            bt[~self._active] = SCRATCH_PAGE
            toks, lps, self._last, self.pool = _decode_chunk_jit(
                self.cfg, self.rl, self.params, self.pool, jnp.asarray(bt),
                self._last, jnp.asarray(self._pos),
                jnp.asarray(self._active),
                jnp.asarray(self._req_keys), jnp.asarray(self._gen),
                jnp.asarray(self._max_new), self.vocab_limit,
                self.sync_every, plan=self.plan)
        sched.stats["decode_steps"] += self.sync_every
        self._m_decode_steps.inc(self.sync_every)
        # deliberate sync point: the scheduler needs this chunk's tokens
        # on host for EOS recycling/admission — one sync per sync_every
        # decode steps, the amortization RA003 exists to protect
        with self._tr.span("sync", track="engine"):
            tok_np, lp_np = np.asarray(toks), np.asarray(lps)  # noqa: RA003
        self._commit_chunk(dec, tok_np, lp_np, now, events)

    def _count_kv_pages(self, pos: np.ndarray, steps: np.ndarray,
                        width: int) -> Tuple[int, int]:
        """KV pages one decode chunk reads: (in place, through the
        gather view), added to the ``decode_kv_pages_read`` /
        ``decode_kv_pages_table`` counters and returned for the chunk's
        ``decode`` span.

        Slot ``s`` decodes ``steps[s]`` live steps (0 when it is not
        decoding) from position ``pos[s]``; step i attends
        ``pages_for(pos[s] + i + 1)`` pages, while the gather view reads
        the whole ``width``-page table of every slot at every step.
        Steps come from the token budgets known at dispatch, so a slot
        that ends on EOS inside the chunk counts to the chunk's end."""
        i = np.arange(self.sync_every)
        lengths = pos[:, None] + 1 + i[None, :]
        read = int(np.where(i[None, :] < steps[:, None],
                            -(-lengths // self.page_size), 0).sum())
        table = self.num_slots * width * self.sync_every
        self.sched.stats["decode_kv_pages_read"] += read
        self.sched.stats["decode_kv_pages_table"] += table
        return read, table

    def _commit_chunk(self, dec: List[GenRequest], tok_np: np.ndarray,
                      lp_np: np.ndarray, now: float,
                      events: List[TokenEvent]) -> None:
        """Commit a multi-step chunk's tokens and log-probs
        (``(sync_every, num_slots)``) to each decoding request, up to its
        EOS or token budget."""
        sched = self.sched
        with self._tr.span("commit", track="engine"):
            for r in dec:
                for i in range(self.sync_every):
                    if r.gen_count >= r.max_new:
                        break
                    t = int(tok_np[i, r.slot])
                    r.tokens.append(t)
                    r.logps.append(float(lp_np[i, r.slot]))
                    sched.stats["decode_slot_steps"] += 1
                    if r.gen_count == 1:
                        r.t_first_token = now
                    events.append(TokenEvent(rid=r.rid, token=t,
                                             logp=r.logps[-1],
                                             index=r.gen_count - 1))
                    if t == EOS:
                        break
                self._settle(r, now, events)
            self._publish_gauges()

    def _settle(self, r: GenRequest, now: float,
                events: List[TokenEvent]) -> None:
        """After a commit: the slot's position and draw counter, and the
        request's finish on EOS or its token budget."""
        self._pos[r.slot] = r.next_pos
        self._gen[r.slot] = r.gen_count
        reason = ""
        if r.tokens and r.tokens[-1] == EOS:
            reason = "eos"
        elif r.gen_count >= r.max_new:
            reason = "length"
        if reason:
            self._active[r.slot] = False
            self._spec_ema.pop(r.rid, None)
            self.sched.finish(r, reason, now)
            self._finish_result(r)
            events.append(TokenEvent(rid=r.rid, token=-1, logp=0.0,
                                     index=r.gen_count, finished=True,
                                     finish_reason=reason))

    # ------------------------------------------------------------------
    def _dev(self, name: str, arr: np.ndarray) -> jax.Array:
        """Cached device mirror of a small host array: re-upload only
        when the host copy changed. The compare costs microseconds; the
        device_puts it avoids were measurably milliseconds per verify
        round (block table, RNG keys and budgets change only at
        admission, not per round)."""
        ent = self._dev_cache.get(name)
        if ent is not None and ent[0].shape == arr.shape \
                and np.array_equal(ent[0], arr):
            return ent[1]
        dev = jnp.asarray(arr)
        self._dev_cache[name] = (arr.copy(), dev)
        return dev

    def _spec_round(self, dec: List[GenRequest], now: float,
                    events: List[TokenEvent]) -> None:
        """One speculative round replacing the decode chunk: draft on
        host (prompt-lookup over each slot's own history), verify all
        slots' windows in one prefill-shaped target forward, commit the
        accepted prefix + the replayed draw, rewind the rest by position
        (append-only pool — no page copies, no allocator traffic).

        The *pending* token (window column 0) is the last committed
        token whose K/V is not yet scattered — right after prefill that
        is the last prompt token (its rewrite is bit-identical, k/v are
        per-token functions of (token, position)), so freshly-admitted
        slots need no separate seeding dispatch and draw generation
        index 0 through the same replayed stream.

        Drafting is gated per request by an acceptance EMA: once a
        request's stream proves incompressible the drafter is switched
        off for it (with a periodic re-probe), and rounds where *no*
        slot drafts fall back to a sequential multi-step chunk
        (``_spec_fallback_chunk``) — the honest ~1x floor instead of a
        one-token-per-forward collapse.
        """
        sched = self.sched
        ns = self.num_slots
        per_slot: Dict[int, tuple] = {}
        max_k = 0
        for r in dec:
            pending = r.tokens[-1] if r.tokens else int(r.prompt[-1])
            ke = min(self.spec_k, r.max_new - r.gen_count - 1) \
                if r.spec_ok else 0
            st = self._spec_ema.setdefault(r.rid, [1.0, 0])
            if ke > 0 and st[0] < _SPEC_EMA_MIN:
                st[1] += 1
                if st[1] < _SPEC_PROBE_EVERY:
                    ke = 0                      # backed off; wait to probe
                else:
                    st[1] = 0                   # probe round: draft again
            d = np.zeros((0,), np.int32)
            if ke > 0:
                hist = np.concatenate(
                    [r.prompt, np.asarray(r.tokens, np.int32)])
                d = np.asarray(self.drafter.propose(hist, ke),
                               np.int32)[:ke]
            per_slot[r.slot] = (pending, d)
            max_k = max(max_k, len(d))
        if max_k == 0:
            # nothing drafted anywhere (cold histories, opted-out
            # requests, or EMA-gated incompressible streams): run a
            # sequential decode chunk instead of a width-2 verify that
            # would emit one token per forward
            self._spec_fallback_chunk(dec, now, events, per_slot)
            return
        # pow2-bucketed verification width (floor 2 keeps the window on
        # the prefill-shaped attention path) — O(log spec_k) executables.
        # Everything that varies per round rides ONE packed int32 array:
        # [window(W), draft_len, gen_base, pos0, active] per row.
        w = max(2, _live_width(1 + max_k, self.spec_k + 1))
        packed = np.zeros((ns, w + 4), np.int32)
        packed[:, :w] = PAD
        for r in dec:
            s = r.slot
            pending, d = per_slot[s]
            packed[s, 0] = pending
            packed[s, 1:1 + len(d)] = d
            packed[s, w] = len(d)
            packed[s, w + 1] = r.gen_count - 1           # gen_base
            packed[s, w + 2] = r.prompt_len + r.gen_count - 1   # pos0
            packed[s, w + 3] = 1                         # active
        width = _live_width(
            pages_for(int(packed[:, w + 2].max()) + w, self.page_size),
            self.pages_per_slot)
        with self._tr.span("verify", track="engine", slots=len(dec),
                           window=w, width=width):
            bt = sched.block_table[:, :width].copy()
            bt[~self._active] = SCRATCH_PAGE
            iout, lps, self.pool = _verify_chunk_jit(
                self.cfg, self.rl, self.params, self.pool,
                self._dev("bt.verify", bt), jnp.asarray(packed),
                self._dev("req_keys", self._req_keys),
                self._dev("max_new", self._max_new),
                self.vocab_limit, plan=self.plan)
        sched.stats["decode_steps"] += 1
        sched.stats["spec_rounds"] += 1
        self._m_decode_steps.inc(1)
        # two deliberate syncs per verify round (packed ints, log-probs),
        # the decode chunk's twin
        with self._tr.span("sync", track="engine"):
            io = np.asarray(iout)                          # noqa: RA003
            lp_np = np.asarray(lps)                        # noqa: RA003
        with self._tr.span("commit", track="engine"):
            self._commit_verify(dec, per_slot, io, lp_np, w, now, events)
            self._publish_gauges()

    def _commit_verify(self, dec: List[GenRequest],
                       per_slot: Dict[int, tuple], io: np.ndarray,
                       lp_np: np.ndarray, w: int, now: float,
                       events: List[TokenEvent]) -> None:
        """Commit a verify round's emitted tokens per slot and update the
        drafting gate and the acceptance counters."""
        sched = self.sched
        tok_np, ne, na = io[:, :w], io[:, w], io[:, w + 1]
        drafted = accepted = hits = 0
        for r in dec:
            s = r.slot
            dl = len(per_slot[s][1])
            drafted += dl
            accepted += int(na[s])
            hits += int(dl > 0)
            if dl > 0:
                st = self._spec_ema[r.rid]
                st[0] = (_SPEC_EMA_DECAY * st[0]
                         + (1.0 - _SPEC_EMA_DECAY) * int(na[s]) / dl)
            sched.stats["spec_slot_rounds"] += 1
            sched.stats["decode_slot_steps"] += 1
            for j in range(int(ne[s])):
                t = int(tok_np[s, j])
                r.tokens.append(t)
                r.logps.append(float(lp_np[s, j]))
                if r.gen_count == 1:
                    r.t_first_token = now
                events.append(TokenEvent(rid=r.rid, token=t,
                                         logp=r.logps[-1],
                                         index=r.gen_count - 1))
            self._settle(r, now, events)
        sched.stats["drafted_tokens_total"] += drafted
        sched.stats["accepted_tokens_total"] += accepted
        sched.stats["draft_hits"] += hits
        if obs.metrics.enabled:
            st = sched.stats
            self._m_spec_rounds.inc()
            self._m_spec_drafted.inc(drafted)
            self._m_spec_accepted.inc(accepted)
            self._g_accept_rate.set(st["accepted_tokens_total"]
                                    / max(st["drafted_tokens_total"], 1))
            self._g_draft_hit.set(st["draft_hits"]
                                  / max(st["spec_slot_rounds"], 1))

    def _spec_fallback_chunk(self, dec: List[GenRequest], now: float,
                             events: List[TokenEvent],
                             per_slot: Dict[int, tuple]) -> None:
        """Sequential multi-step chunk for no-draft rounds, in the
        pending-token convention (``_spec_decode_chunk_jit``). Tokens and
        logps are bit-identical to what the verify path would emit — the
        same per-request counter stream drives every draw and K/V lands
        at the same absolute positions — so the engine can switch between
        the two paths per round without perturbing the output stream."""
        sched = self.sched
        ns = self.num_slots
        pending = np.zeros((ns,), np.int32)
        pos0 = np.zeros((ns,), np.int32)
        gen_base = np.full((ns,), -1, np.int32)
        for r in dec:
            s = r.slot
            pending[s] = per_slot[s][0]
            pos0[s] = r.prompt_len + r.gen_count - 1
            gen_base[s] = r.gen_count - 1
        width = _live_width(
            pages_for(int(pos0.max()) + self.sync_every, self.page_size),
            self.pages_per_slot)
        steps = np.where(self._active,
                         np.clip(self._max_new - gen_base - 1, 0,
                                 self.sync_every), 0)
        read, table = self._count_kv_pages(pos0, steps, width)
        with self._tr.span("decode", track="engine", slots=len(dec),
                           chunk=self.sync_every, width=width,
                           kv_pages_read=read, kv_pages_table=table):
            bt = sched.block_table[:, :width].copy()
            bt[~self._active] = SCRATCH_PAGE
            toks, lps, self.pool = _spec_decode_chunk_jit(
                self.cfg, self.rl, self.params, self.pool,
                self._dev("bt.fallback", bt), jnp.asarray(pending),
                jnp.asarray(pos0), jnp.asarray(self._active),
                self._dev("req_keys", self._req_keys),
                jnp.asarray(gen_base), self._dev("max_new", self._max_new),
                self.vocab_limit, self.sync_every, plan=self.plan)
        sched.stats["decode_steps"] += self.sync_every
        sched.stats["spec_fallback_chunks"] += 1
        self._m_decode_steps.inc(self.sync_every)
        # one deliberate sync per chunk (the decode path's amortization)
        with self._tr.span("sync", track="engine"):
            tok_np, lp_np = np.asarray(toks), np.asarray(lps)  # noqa: RA003
        self._commit_chunk(dec, tok_np, lp_np, now, events)

    # ------------------------------------------------------------------
    def generate(self, requests: Sequence[Request],
                 key: Optional[jax.Array] = None) -> List[GenerationResult]:
        """Batch convenience: submit ``requests``, step until they all
        finish, return results in request order."""
        if key is not None:
            self.key = key
        pending = set()
        for req in requests:
            self.submit(req)
            pending.add(req.rid)
        while pending - self._results.keys():
            if not self.has_work():
                missing = sorted(pending - self._results.keys())
                raise RuntimeError(f"engine drained but requests {missing} "
                                   "never finished")
            self.step()
        return [self._results.pop(r.rid) for r in requests]


# --------------------------------------------------------------------------
# batch wrapper (the pre-request-API surface, kept exactly compatible)


def rollout_from_results(prompts: np.ndarray,
                         results: Sequence[GenerationResult],
                         max_new: int) -> Dict[str, Any]:
    """Assemble the engine-agnostic rollout dict (tokens / completions /
    sampler_lp / comp_mask) from per-request results. Row ``i`` is
    ``results[i]``; expired requests contribute all-PAD rows."""
    b, tp = prompts.shape
    completions = np.full((b, max_new), PAD, np.int32)
    sampler_lp = np.zeros((b, max_new), np.float32)
    comp_mask = np.zeros((b, max_new), np.float32)
    for i, res in enumerate(results):
        n = res.gen_count
        completions[i, :n] = res.tokens
        sampler_lp[i, :n] = res.logps
        comp_mask[i, :n] = 1.0
    tokens = np.concatenate([np.asarray(prompts), completions], axis=1)
    return {"tokens": jnp.asarray(tokens),
            "completions": jnp.asarray(completions),
            "sampler_lp": jnp.asarray(sampler_lp),
            "comp_mask": jnp.asarray(comp_mask),
            "prompt_len": tp}


def generate_continuous(cfg: ModelConfig, rl: RLConfig, params,
                        prompts: jax.Array, key: jax.Array, *,
                        max_new: Optional[int] = None,
                        vocab_limit: Optional[int] = None,
                        num_slots: Optional[int] = None,
                        page_size: int = 16,
                        prefill_chunk: Optional[int] = None,
                        prompt_lens: Optional[Sequence[int]] = None,
                        sync_every: int = 8,
                        plan=None,
                        prefix_cache: bool = False,
                        ) -> Dict[str, jax.Array]:
    """Continuous-batching generation over ``prompts`` (B, Tp).

    Drop-in for the static path: same rollout dict, same tokens/logps for
    the same ``key`` (per-request RNG streams). Extras: ``num_slots``
    decode slots are recycled as requests finish, ``prompt_lens`` admits
    per-request true prompt lengths (rows shorter than Tp),
    ``prefill_chunk`` bounds how much prompt is prefilled between decode
    chunks (defaults to the whole prompt in one chunk), ``sync_every``
    is the decode horizon, and ``prefix_cache`` turns on shared-prefix
    page reuse (bit-exact; off by default here so the legacy batch path
    keeps its exact page accounting — the serving front door defaults it
    on). ``plan`` (an ``ExecutionPlan``) makes prefill/decode run
    tensor-parallel: params and the paged KV pool are constrained by the
    plan's cache_specs.
    """
    max_new = max_new or rl.max_new_tokens
    prompts_np = np.asarray(prompts)
    b, tp = prompts_np.shape
    num_slots = min(b, num_slots or 8)
    engine = ContinuousEngine(
        cfg, params, rl=rl, max_total_tokens=tp + max_new,
        num_slots=num_slots, page_size=page_size, sync_every=sync_every,
        prefill_chunk=clamp_prefill_chunk(prefill_chunk, tp),
        vocab_limit=vocab_limit, plan=plan, prefix_cache=prefix_cache,
        key=key)
    sp = SamplingParams(temperature=rl.temperature, top_k=rl.top_k,
                        top_p=rl.top_p, max_new_tokens=max_new)
    requests = []
    for r in range(b):
        plen = int(prompt_lens[r]) if prompt_lens is not None else tp
        if not 0 < plen <= tp:
            raise ValueError(f"prompt_lens[{r}]={plen} outside (0, {tp}]")
        requests.append(Request(rid=r, prompt=prompts_np[r, :plen],
                                params=sp))
    results = engine.generate(requests)
    roll = rollout_from_results(prompts_np, results, max_new)
    roll["stats"] = engine.stats()
    return roll
