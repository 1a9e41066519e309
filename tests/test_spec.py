"""Speculative decoding: exact-replay acceptance and the drafter.

The contracts under test: greedy spec decode is bit-identical to the
non-speculative engine; every reported logp is the *target* model's
logp of the emitted token (GEPO App. B.1 — never the drafter's); and
rollback leaves the page pool balanced (append-only rewind, no
allocator traffic).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.sentinel import (spec_verify_executable_bound,
                                     spec_verify_width_buckets)
from repro.config import (ATTN, LOCAL, MLP, ModelConfig, RLConfig,
                          ServeConfig)
from repro.data.tasks import EOS, PAD
from repro.models import init_params
from repro.sampling import NGramDrafter, build_engine, filter_logits
from repro.sampling.sample import NEG_INF
from repro.sampling.spec import accept_drafts, verify_width_buckets
from repro.serving.api import Request, SamplingParams

TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                   num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=32,
                   block_pattern=(ATTN,), ffn_pattern=(MLP,),
                   dtype="float32", attn_impl="naive", remat=False,
                   rope_theta=1e4)

GQA_LOCAL = ModelConfig(name="gqa-local", family="dense", num_layers=4,
                        d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                        vocab_size=32, block_pattern=(ATTN, LOCAL),
                        ffn_pattern=(MLP,), sliding_window=8,
                        dtype="float32", attn_impl="naive", remat=False,
                        rope_theta=1e4)

GREEDY = dict(temperature=1.0, top_k=1, top_p=1.0)


def _run(cfg, params, rl, prompts, *, spec_k, key, max_new=12,
         prefix_cache=True, spec=True, sync_every=4):
    serve = ServeConfig(engine="continuous", num_slots=3, page_size=4,
                        sync_every=sync_every, prefix_cache=prefix_cache,
                        max_total_tokens=max(len(p) for p in prompts)
                        + max_new,
                        spec_k=spec_k, seed=0)
    eng = build_engine(cfg, params, serve, rl=rl,
                       vocab_limit=cfg.vocab_size, key=key)
    sp = SamplingParams.from_rl(rl)
    if not spec:
        sp = SamplingParams(temperature=rl.temperature, top_k=rl.top_k,
                            top_p=rl.top_p, max_new_tokens=rl.max_new_tokens,
                            spec=False)
    res = eng.generate([Request(rid=i, prompt=p, params=sp)
                        for i, p in enumerate(prompts)])
    return eng, res


def _prompts(rng, n=6, width=7, vocab=30):
    return [rng.integers(4, vocab, size=width).astype(np.int32)
            for _ in range(n)]


class TestNGramDrafter:
    def test_continuation_of_most_recent_match(self):
        d = NGramDrafter(max_ngram=2, min_ngram=1)
        #        match A ----v        match B (more recent) ----v
        h = np.array([5, 6, 7, 8, 1, 5, 6, 9, 2, 5, 6], np.int32)
        np.testing.assert_array_equal(d.propose(h, 2), [9, 2])

    def test_longer_ngram_beats_shorter(self):
        d = NGramDrafter(max_ngram=3, min_ngram=1)
        h = np.array([1, 2, 3, 4, 9, 6, 2, 3, 7, 1, 2, 3], np.int32)
        # trigram [1,2,3] matches at the start -> continuation 4, 9
        np.testing.assert_array_equal(d.propose(h, 2), [4, 9])

    def test_no_match_is_empty(self):
        d = NGramDrafter()
        out = d.propose(np.array([1, 2, 3, 4, 5], np.int32), 4)
        assert out.size == 0 and out.dtype == np.int32

    def test_chains_past_history_end(self):
        # a length-2 cycle has only 2 continuation tokens in history;
        # chaining re-proposes over history + draft to fill all k slots
        d = NGramDrafter(max_ngram=1)
        h = np.array([7, 3, 7], np.int32)
        np.testing.assert_array_equal(d.propose(h, 5), [3, 7, 3, 7, 3])

    def test_k_zero_and_tiny_history(self):
        d = NGramDrafter()
        assert d.propose(np.array([3], np.int32), 4).size == 0
        assert d.propose(np.array([3, 3, 3], np.int32), 0).size == 0

    def test_validation(self):
        with pytest.raises(ValueError):
            NGramDrafter(max_ngram=2, min_ngram=3)


class TestAcceptDrafts:
    """Pure-function acceptance rule, greedy profile (top_k=1 makes the
    replayed draw the argmax — fully deterministic)."""

    V = 16

    def _logits(self, argmaxes):
        lg = np.zeros((1, len(argmaxes), self.V), np.float32)
        for i, t in enumerate(argmaxes):
            lg[0, i, t] = 5.0
        return jnp.asarray(lg)

    def _accept(self, argmaxes, drafts, *, gen_base=0, max_new=100):
        w = len(argmaxes)
        window = np.full((1, w), PAD, np.int32)
        window[0, 0] = 3                      # pending token (col 0)
        window[0, 1:1 + len(drafts)] = drafts
        return accept_drafts(
            self._logits(argmaxes), jnp.asarray(window),
            jnp.asarray([len(drafts)], np.int32), jnp.asarray([True]),
            jax.random.PRNGKey(0)[None], jnp.asarray([gen_base], np.int32),
            jnp.asarray([max_new], np.int32), temperature=1.0, top_k=1,
            top_p=1.0, vocab_limit=self.V)

    def test_full_acceptance_emits_k_plus_one(self):
        # rows say 5,6,7,8; drafts 5,6,7 all match -> emit 5,6,7,8
        toks, lps, n_emit, n_acc = self._accept([5, 6, 7, 8], [5, 6, 7])
        assert int(n_emit[0]) == 4 and int(n_acc[0]) == 3
        np.testing.assert_array_equal(np.asarray(toks[0]), [5, 6, 7, 8])

    def test_first_rejection_emits_replayed_draw(self):
        # draft 9 != replay 5: emit the replay, drop the rest
        toks, _, n_emit, n_acc = self._accept([5, 6, 7, 8], [9, 6, 7])
        assert int(n_emit[0]) == 1 and int(n_acc[0]) == 0
        assert int(toks[0, 0]) == 5
        np.testing.assert_array_equal(np.asarray(toks[0, 1:]), PAD)

    def test_mid_rejection(self):
        toks, _, n_emit, n_acc = self._accept([5, 6, 7, 8], [5, 9, 7])
        assert int(n_emit[0]) == 2 and int(n_acc[0]) == 1
        np.testing.assert_array_equal(np.asarray(toks[0, :2]), [5, 6])

    def test_eos_cuts_emission(self):
        toks, _, n_emit, n_acc = self._accept([5, EOS, 7, 8], [5, EOS, 7])
        assert int(n_emit[0]) == 2
        assert int(toks[0, 1]) == EOS
        np.testing.assert_array_equal(np.asarray(toks[0, 2:]), PAD)

    def test_budget_cuts_emission(self):
        # gen_base=2 (3 tokens committed incl. pending), max_new=4:
        # room for exactly one more emission
        toks, _, n_emit, _ = self._accept([5, 6, 7, 8], [5, 6, 7],
                                          gen_base=2, max_new=4)
        assert int(n_emit[0]) == 1 and int(toks[0, 0]) == 5

    def test_inactive_row_emits_nothing(self):
        lg = self._logits([5, 6])
        toks, lps, n_emit, n_acc = accept_drafts(
            lg, jnp.full((1, 2), PAD, jnp.int32),
            jnp.asarray([0], np.int32), jnp.asarray([False]),
            jax.random.PRNGKey(0)[None], jnp.asarray([0], np.int32),
            jnp.asarray([100], np.int32), temperature=1.0, top_k=1,
            top_p=1.0, vocab_limit=self.V)
        assert int(n_emit[0]) == 0 and float(lps[0].sum()) == 0.0

    def test_logps_are_target_model_logps(self):
        """The reported logp is log_softmax(raw row)[token] — the target
        model's convention — NOT the filtered/draft distribution's."""
        rng = np.random.default_rng(0)
        lg = jnp.asarray(rng.normal(size=(1, 3, self.V)).astype(np.float32))
        am = np.asarray(jnp.argmax(lg, axis=-1))[0]
        toks, lps, n_emit, _ = accept_drafts(
            lg, jnp.asarray([[3, am[0], am[1]]], jnp.int32),
            jnp.asarray([2], np.int32), jnp.asarray([True]),
            jax.random.PRNGKey(0)[None], jnp.asarray([0], np.int32),
            jnp.asarray([100], np.int32), temperature=1.0, top_k=1,
            top_p=1.0, vocab_limit=self.V)
        ref = jax.nn.log_softmax(lg, axis=-1)
        for j in range(int(n_emit[0])):
            np.testing.assert_allclose(
                float(lps[0, j]), float(ref[0, j, int(toks[0, j])]),
                rtol=1e-6)


class TestEngineParity:
    """spec_k=4 engine vs spec-off engine: same requests, same seed."""

    @pytest.mark.parametrize("cfg", [TINY, GQA_LOCAL],
                             ids=["tiny", "gqa-local"])
    def test_greedy_bit_exact(self, cfg):
        key = jax.random.PRNGKey(0)
        params = init_params(cfg, key)
        rl = RLConfig(max_new_tokens=12, engine="continuous", **GREEDY)
        prompts = _prompts(np.random.default_rng(0))
        _, r0 = _run(cfg, params, rl, prompts, spec_k=0, key=key)
        eng, r4 = _run(cfg, params, rl, prompts, spec_k=4, key=key)
        for a, b in zip(r0, r4):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_allclose(a.logps, b.logps, rtol=2e-5,
                                       atol=1e-6)
            assert a.finish_reason == b.finish_reason
        st = eng.stats()
        # untrained greedy models loop -> the n-gram drafter locks on
        assert st["accept_rate"] > 0.3
        assert st["drafted_tokens_total"] > 0

    def test_stochastic_tokens_exact(self):
        """Exact replay reproduces the engine's counter-based draws, so
        even sampled (non-greedy) runs emit identical token streams."""
        key = jax.random.PRNGKey(1)
        params = init_params(TINY, key)
        rl = RLConfig(temperature=0.8, top_k=8, top_p=0.9,
                      max_new_tokens=10, engine="continuous")
        prompts = _prompts(np.random.default_rng(1))
        _, r0 = _run(TINY, params, rl, prompts, spec_k=0, key=key)
        _, r3 = _run(TINY, params, rl, prompts, spec_k=3, key=key)
        for a, b in zip(r0, r3):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_allclose(a.logps, b.logps, rtol=2e-5,
                                       atol=1e-6)

    def test_per_request_opt_out(self):
        """SamplingParams.spec=False rides through the spec engine with
        zero drafted tokens (all-opt-out rounds take the sequential
        fallback chunk) and stays bit-identical to the spec-off
        engine."""
        key = jax.random.PRNGKey(2)
        params = init_params(TINY, key)
        rl = RLConfig(max_new_tokens=8, engine="continuous", **GREEDY)
        prompts = _prompts(np.random.default_rng(2), n=4)
        _, r0 = _run(TINY, params, rl, prompts, spec_k=0, key=key)
        eng, r4 = _run(TINY, params, rl, prompts, spec_k=4, key=key,
                       spec=False)
        for a, b in zip(r0, r4):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        assert eng.stats()["drafted_tokens_total"] == 0

    def test_logps_are_target_model_end_to_end(self):
        """Teacher-forced recompute of the emitted sequences under the
        target params must reproduce the engine's reported logps — the
        GEPO importance-weight contract (a drafter logp leaking through
        would break the learner's ratio)."""
        from repro.sampling import rollout_from_results, token_logps
        key = jax.random.PRNGKey(3)
        params = init_params(TINY, key)
        rl = RLConfig(max_new_tokens=10, engine="continuous", **GREEDY)
        width = 7
        prompts = _prompts(np.random.default_rng(3), n=4, width=width)
        _, res = _run(TINY, params, rl, prompts, spec_k=4, key=key)
        roll = rollout_from_results(np.stack(prompts), res,
                                    rl.max_new_tokens)
        lp = token_logps(TINY, params, roll["tokens"])[:, width - 1:]
        mask = np.asarray(roll["comp_mask"])
        np.testing.assert_allclose(np.asarray(roll["sampler_lp"]) * mask,
                                   np.asarray(lp) * mask, rtol=1e-4,
                                   atol=1e-4)


class TestRollbackAndPool:
    def test_pool_balanced_after_spec_run(self):
        """Rejected drafts rewind by position only — no allocator
        traffic — so a finished spec run returns every page."""
        key = jax.random.PRNGKey(4)
        params = init_params(TINY, key)
        rl = RLConfig(max_new_tokens=12, engine="continuous", **GREEDY)
        prompts = _prompts(np.random.default_rng(4), n=8)
        eng, res = _run(TINY, params, rl, prompts, spec_k=4, key=key,
                        prefix_cache=False)
        assert len(res) == 8
        assert all(r.finish_reason in ("eos", "length") for r in res)
        assert eng.free_pages == eng.num_pages - 1   # all but scratch

    def test_pool_balanced_with_prefix_cache(self):
        key = jax.random.PRNGKey(5)
        params = init_params(TINY, key)
        rl = RLConfig(max_new_tokens=8, engine="continuous", **GREEDY)
        rng = np.random.default_rng(5)
        shared = rng.integers(4, 30, size=5).astype(np.int32)
        prompts = [np.concatenate([shared,
                                   rng.integers(4, 30, size=3)
                                   .astype(np.int32)]) for _ in range(6)]
        eng, res = _run(TINY, params, rl, prompts, spec_k=4, key=key)
        held = len({pg for ent in eng.prefix_cache._entries.values()
                    for pg in ent.pages})
        assert eng.free_pages + held == eng.num_pages - 1


class TestBucketsAndConfig:
    def test_width_buckets_match_sentinel(self):
        for k in range(0, 12):
            assert verify_width_buckets(k) == spec_verify_width_buckets(k)
        assert verify_width_buckets(4) == 3          # widths {2, 4, 5}
        assert verify_width_buckets(0) == 1          # floor width 2 only
        assert verify_width_buckets(7) == 3          # {2, 4, 8}

    def test_executable_bound(self):
        assert spec_verify_executable_bound(0, 8) == 0
        # verify widths × pow2 table widths {1,2,4,8}, plus one fallback
        # decode-chunk family over the same table widths
        assert spec_verify_executable_bound(4, 8) == \
            (spec_verify_width_buckets(4) + 1) * 4

    def test_serve_config_validation(self):
        with pytest.raises(ValueError):
            ServeConfig(engine="static", spec_k=4)
        with pytest.raises(ValueError):
            ServeConfig(engine="continuous", spec_k=-1)
        with pytest.raises(ValueError):
            ServeConfig(engine="continuous", spec_k=2, spec_ngram_min=0)


class TestFilterLogitsTopK:
    def test_lax_topk_matches_sort_reference(self):
        """Satellite: top-k threshold via lax.top_k must reproduce the
        full-sort reference exactly, ties included."""
        rng = np.random.default_rng(0)
        lg = rng.normal(size=(5, 64)).astype(np.float32)
        lg[0, :10] = 1.25                            # ties at the threshold
        lg[1] = 0.0                                  # fully degenerate
        x = jnp.asarray(lg)
        for k in (1, 3, 10, 63, 64, 0):
            got = filter_logits(x, top_k=k)
            v = x.shape[-1]
            if k and k < v:
                kth = jnp.sort(x, axis=-1)[..., v - k][..., None]
                want = jnp.where(x < kth, NEG_INF, x)
            else:
                want = x
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
