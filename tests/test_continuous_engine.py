"""Continuous-batching engine: static-engine parity (tokens + logps),
slot/page recycling, allocator invariants, and architecture fallback."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hlo import whole_pool_slices
from repro.config import ModelConfig, RLConfig, ATTN, LOCAL, MAMBA, MLP, NONE
from repro.sampling import (ContinuousScheduler, GenRequest, PageAllocator,
                            generate, generate_continuous, pages_for)
from repro.sampling import continuous as cont
from repro.sampling.paged_cache import init_paged_pool
from repro.sampling.scheduler import DONE
from repro.data.tasks import EOS
from repro.models import abstract_params, init_params

TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                   num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=32,
                   block_pattern=(ATTN,), ffn_pattern=(MLP,),
                   dtype="float32", attn_impl="naive", remat=False,
                   rope_theta=1e4)

GQA_LOCAL = ModelConfig(name="gqa-local", family="dense", num_layers=4,
                        d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                        vocab_size=32, block_pattern=(ATTN, LOCAL),
                        ffn_pattern=(MLP,), sliding_window=6,
                        dtype="float32", attn_impl="naive", remat=False,
                        rope_theta=1e4)


def _rollouts(cfg, rng, *, max_new=10, batch=6, **cont_kwargs):
    params = init_params(cfg, rng)
    prompts = jax.random.randint(rng, (batch, 5), 3, cfg.vocab_size)
    rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=max_new)
    r_static = generate(cfg, rl, params, prompts, rng, vocab_limit=20)
    r_cont = generate_continuous(cfg, rl, params, prompts, rng,
                                 vocab_limit=20, **cont_kwargs)
    return r_static, r_cont


class TestParity:
    """Acceptance: continuous engine ≡ static engine (tokens + logps)
    under identical seeds — RNG folds per request, never per slot."""

    @pytest.mark.parametrize("slots,sync_every", [(2, 1), (3, 8), (6, 4)])
    def test_tokens_logps_exact(self, rng, slots, sync_every):
        r1, r2 = _rollouts(TINY, rng, num_slots=slots, page_size=4,
                           sync_every=sync_every)
        np.testing.assert_array_equal(np.asarray(r1["completions"]),
                                      np.asarray(r2["completions"]))
        np.testing.assert_array_equal(np.asarray(r1["comp_mask"]),
                                      np.asarray(r2["comp_mask"]))
        np.testing.assert_allclose(np.asarray(r1["sampler_lp"]),
                                   np.asarray(r2["sampler_lp"]),
                                   rtol=1e-5, atol=1e-5)

    def test_parity_with_chunked_prefill_and_gqa_local(self, rng):
        """Sliding-window + GQA layers, prompt split into 2-token prefill
        chunks interleaved with decode. Tokens must still match exactly;
        logps only to float-accumulation tolerance (chunked attention
        reorders the softmax reductions)."""
        r1, r2 = _rollouts(GQA_LOCAL, rng, num_slots=2, page_size=4,
                           prefill_chunk=2, sync_every=3)
        np.testing.assert_array_equal(np.asarray(r1["completions"]),
                                      np.asarray(r2["completions"]))
        np.testing.assert_allclose(np.asarray(r1["sampler_lp"]),
                                   np.asarray(r2["sampler_lp"]),
                                   rtol=1e-3, atol=1e-3)

    def test_padded_prefill_tail_never_touches_live_pages(self, rng):
        """Long prompt + tiny max_new + big prefill chunk: the padded
        tail of the last chunk runs past the slot's logical capacity.
        Those writes must be dropped (OOB-fill page index), not clamped
        onto a live page — parity with static proves no corruption."""
        params = init_params(TINY, rng)
        prompts = jax.random.randint(rng, (4, 30), 3, TINY.vocab_size)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=2)
        r1 = generate(TINY, rl, params, prompts, rng, vocab_limit=20)
        r2 = generate_continuous(TINY, rl, params, prompts, rng,
                                 vocab_limit=20, num_slots=2, page_size=16,
                                 prefill_chunk=20, sync_every=2)
        np.testing.assert_array_equal(np.asarray(r1["completions"]),
                                      np.asarray(r2["completions"]))
        np.testing.assert_allclose(np.asarray(r1["sampler_lp"]),
                                   np.asarray(r2["sampler_lp"]),
                                   rtol=1e-5, atol=1e-5)

    def test_rlconfig_engine_switch(self, rng):
        params = init_params(TINY, rng)
        prompts = jax.random.randint(rng, (4, 5), 3, TINY.vocab_size)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0,
                      max_new_tokens=6, engine="continuous")
        roll = generate(TINY, rl, params, prompts, rng, vocab_limit=20)
        assert "stats" in roll and roll["stats"]["completed"] == 4


class TestSlotRecycling:
    def test_mixed_lengths_recycle_slots(self, rng):
        """Short + long prompts through 2 slots: every request completes,
        freed slots get re-admitted, and the engine never decodes more
        slot-steps than the static scan would."""
        params = init_params(TINY, rng)
        prompts = jax.random.randint(rng, (8, 7), 3, TINY.vocab_size)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=8)
        roll = generate_continuous(
            TINY, rl, params, prompts, rng, vocab_limit=20, num_slots=2,
            page_size=4, sync_every=2, prompt_lens=[7, 2, 5, 7, 3, 2, 6, 4])
        stats = roll["stats"]
        assert stats["submitted"] == stats["admitted"] == 8
        assert stats["completed"] == 8
        assert stats["max_active"] == 2          # never exceeds the pool
        comp = np.asarray(roll["completions"])
        mask = np.asarray(roll["comp_mask"])
        assert comp.shape == (8, 8)
        # every row produced at least one token; masked tail is PAD
        assert (mask.sum(axis=1) >= 1).all()
        for row, mrow in zip(comp, mask):
            n = int(mrow.sum())
            assert (mrow[:n] == 1.0).all() and (mrow[n:] == 0.0).all()
            if EOS in row.tolist():
                assert row.tolist().index(EOS) == n - 1

    def test_scheduler_recycles_pages_without_double_free(self):
        """Direct scheduler lifecycle: 6 requests through 2 slots with a
        pool that only fits 2 in flight; pages drain back to the
        allocator exactly once each."""
        page_size, pages_per_slot = 4, 3
        alloc = PageAllocator(1 + 2 * pages_per_slot)
        sched = ContinuousScheduler(2, pages_per_slot, page_size, alloc)
        for rid in range(6):
            sched.submit(GenRequest(rid=rid,
                                    prompt=np.full(5, 3, np.int32),
                                    max_new=7))   # 12 tokens -> 3 pages
        in_flight = sched.admit()
        assert len(in_flight) == 2 and alloc.available == 0
        assert not sched.admit()                 # pool exhausted -> defer
        sched.finish(in_flight[0], "eos")
        assert alloc.available == pages_per_slot
        assert in_flight[0].state == DONE
        again = sched.admit()                    # freed slot re-admitted
        assert len(again) == 1 and again[0].rid == 2
        assert again[0].slot == in_flight[0].slot
        # drain everything; every page must come home exactly once
        while not sched.all_done:
            for r in list(sched.slots):
                if r is not None:
                    sched.finish(r, "length")
            sched.admit()
        assert sched.stats["completed"] == 6
        assert alloc.available == 2 * pages_per_slot and alloc.in_use == 0


class TestPageAllocator:
    def test_double_free_raises(self):
        alloc = PageAllocator(8)
        pages = alloc.alloc(3)
        alloc.free(pages)
        with pytest.raises(ValueError, match="double free"):
            alloc.free(pages)

    def test_scratch_page_reserved(self):
        alloc = PageAllocator(4)
        pages = alloc.alloc(3)
        assert 0 not in pages and alloc.alloc(1) is None

    def test_exhaustion_defers(self):
        alloc = PageAllocator(4)
        assert alloc.alloc(4) is None            # only 3 usable
        first = alloc.alloc(3)
        assert alloc.alloc(1) is None
        alloc.free(first[:1])
        assert alloc.alloc(1) == first[:1]

    def test_pages_for(self):
        assert pages_for(1, 4) == 1
        assert pages_for(4, 4) == 1
        assert pages_for(5, 4) == 2


class TestFallback:
    def test_ssm_falls_back_to_static(self, rng):
        ssm = ModelConfig(name="ssm", family="ssm", num_layers=2,
                          d_model=64, num_heads=0, num_kv_heads=0, d_ff=0,
                          vocab_size=32, block_pattern=(MAMBA,),
                          ffn_pattern=(NONE,), ssm_state=16, ssm_headdim=32,
                          dtype="float32", remat=False)
        params = init_params(ssm, rng)
        prompts = jax.random.randint(rng, (2, 5), 3, 32)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=4)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            roll = generate(ssm, rl, params, prompts, rng, vocab_limit=20,
                            engine="continuous")
        assert any("falling back" in str(x.message) for x in w)
        assert np.asarray(roll["completions"]).shape == (2, 4)

    def test_continuous_refuses_unsupported(self, rng):
        ssm = ModelConfig(name="ssm2", family="ssm", num_layers=2,
                          d_model=64, num_heads=0, num_kv_heads=0, d_ff=0,
                          vocab_size=32, block_pattern=(MAMBA,),
                          ffn_pattern=(NONE,), ssm_state=16, ssm_headdim=32,
                          dtype="float32", remat=False)
        rl = RLConfig(max_new_tokens=4)
        with pytest.raises(ValueError, match="attention-only"):
            generate_continuous(ssm, rl, init_params(ssm, rng),
                                np.full((2, 5), 3), jax.random.PRNGKey(0))

    def test_unknown_engine_raises(self, rng):
        rl = RLConfig(max_new_tokens=4)
        with pytest.raises(ValueError, match="unknown engine"):
            generate(TINY, rl, init_params(TINY, rng),
                     np.full((2, 5), 3), rng, engine="turbo")

    def test_static_rejects_continuous_kwargs(self, rng):
        rl = RLConfig(max_new_tokens=4)
        with pytest.raises(TypeError, match="num_slots"):
            generate(TINY, rl, init_params(TINY, rng),
                     np.full((2, 5), 3), rng, num_slots=4)


class TestCompiledPrograms:
    """The block scan carries the stacked page pools: no compiled engine
    program slices a whole layer's pool out of them or writes one back."""

    PAGES, PAGE, SLOTS, WIDTH = 37, 4, 3, 8

    def _lower(self, program):
        cfg = GQA_LOCAL                       # 2 blocks of (ATTN, LOCAL)
        params = abstract_params(cfg)
        pool = jax.eval_shape(lambda: init_paged_pool(cfg, self.PAGES,
                                                      self.PAGE))
        sds = jax.ShapeDtypeStruct
        n = self.SLOTS
        if program == "decode":
            return cont._decode_chunk_jit.lower(
                cfg, RLConfig(), params, pool, sds((n, self.WIDTH), jnp.int32),
                sds((n, cfg.padded_vocab), jnp.float32), sds((n,), jnp.int32),
                sds((n,), jnp.bool_), sds((n, 2), jnp.uint32),
                sds((n,), jnp.int32), sds((n,), jnp.int32),
                vocab_limit=20, sync_every=2)
        return cont._prefill_chunk_jit.lower(
            cfg, params, pool, sds((1, self.WIDTH), jnp.int32),
            sds((1, 6), jnp.int32), sds((), jnp.int32))

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_no_whole_layer_pool_slices(self, program):
        hlo = self._lower(program).compile().as_text()
        layer_pool = (self.PAGES, GQA_LOCAL.num_kv_heads, self.PAGE,
                      GQA_LOCAL.head_dim)
        assert f"[{GQA_LOCAL.num_blocks},{self.PAGES}," in hlo
        assert whole_pool_slices(hlo, layer_pool) == []
