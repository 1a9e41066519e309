"""Differentiable fused-logprob: value AND gradient parity of the Pallas
kernel pair (interpret mode) and the chunked lax.map fallback against the
naive materializing oracle, including padded / non-divisible (T, V)
shapes and the out-of-range target-id contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.logprob import (token_logprob_and_entropy,
                                token_logprob_from_logits)
from repro.kernels import ops, ref
from repro.kernels.fused_logprob import chunked_logprob, fused_logprob


def _tols(dtype):
    return dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


def _naive_loss(logits, tgt, w_lp, w_ent):
    lp, ent = token_logprob_and_entropy(logits, tgt)
    return (w_lp * lp + w_ent * ent).sum()


def _mk_inputs(rng, t, v, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    logits = (4 * jax.random.normal(ks[0], (t, v))).astype(dtype)
    tgt = jax.random.randint(ks[1], (t,), 0, v)
    w_lp = jax.random.normal(ks[2], (t,))
    w_ent = jax.random.normal(ks[3], (t,))
    return logits, tgt, w_lp, w_ent


class TestGradParity:
    """jax.grad through the custom VJP == autodiff through the oracle,
    for both the logp and the entropy output."""

    @pytest.mark.parametrize("shape", [(64, 512), (128, 1024)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_pallas_interpret(self, rng, shape, dtype):
        t, v = shape
        logits, tgt, w_lp, w_ent = _mk_inputs(rng, t, v, dtype)

        def loss(x):
            lp, ent = fused_logprob(x, tgt, block_t=16, block_v=128,
                                    interpret=True)
            return (w_lp * lp + w_ent * ent).sum()

        val, grad = jax.value_and_grad(loss)(logits)
        val_e, grad_e = jax.value_and_grad(
            lambda x: _naive_loss(x, tgt, w_lp, w_ent))(logits)
        tol = _tols(dtype)
        np.testing.assert_allclose(float(val), float(val_e), rtol=1e-3)
        assert grad.dtype == logits.dtype
        np.testing.assert_allclose(np.asarray(grad, np.float32),
                                   np.asarray(grad_e, np.float32), **tol)

    @pytest.mark.parametrize("shape", [
        (100, 300, 32),          # non-divisible T and V
        (96, 257, 32),           # prime-ish vocab
        (37, 512, 64),           # T smaller than two chunks, ragged tail
        (64, 128, 64),           # exactly divisible
    ])
    def test_chunked_fallback(self, rng, shape):
        t, v, chunk = shape
        logits, tgt, w_lp, w_ent = _mk_inputs(rng, t, v)

        def loss(x):
            lp, ent = chunked_logprob(x, tgt, chunk=chunk)
            return (w_lp * lp + w_ent * ent).sum()

        val, grad = jax.value_and_grad(loss)(logits)
        val_e, grad_e = jax.value_and_grad(
            lambda x: _naive_loss(x, tgt, w_lp, w_ent))(logits)
        np.testing.assert_allclose(float(val), float(val_e), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(grad), np.asarray(grad_e),
                                   rtol=2e-4, atol=2e-4)

    def test_values_match_ref(self, rng):
        logits, tgt, _, _ = _mk_inputs(rng, 64, 384)
        lp_e, ent_e = ref.fused_logprob_ref(logits, tgt)
        for lp, ent in (chunked_logprob(logits, tgt, chunk=24),
                        fused_logprob(logits, tgt, block_t=16,
                                      block_v=128, interpret=True)):
            np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_e),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_e),
                                       rtol=1e-4, atol=1e-4)


class TestDispatcher:
    def test_auto_on_cpu_handles_any_shape(self, rng):
        # (B, S, V) with non-divisible S·B and V: auto => chunked on CPU
        ks = jax.random.split(rng, 2)
        logits = jax.random.normal(ks[0], (3, 7, 129))
        tgt = jax.random.randint(ks[1], (3, 7), 0, 129)
        lp, ent = ops.fused_token_logprob(logits, tgt)
        lp_e, ent_e = token_logprob_and_entropy(logits, tgt)
        assert lp.shape == ent.shape == (3, 7)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_e),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_e),
                                   rtol=1e-5, atol=1e-5)

    def test_pallas_ragged_falls_back(self, rng, monkeypatch):
        ks = jax.random.split(rng, 2)
        logits = jax.random.normal(ks[0], (50, 300))     # 300 % 128 != 0...
        tgt = jax.random.randint(ks[1], (50,), 0, 300)
        # ...so a forced impl="pallas" refuses the shape rather than
        # running another backend under its name
        with pytest.raises(ValueError, match="no Pallas tiling"):
            ops.fused_token_logprob(logits, tgt, impl="pallas",
                                    block_t=16, block_v=256)
        # auto on a TPU falls back to chunked, and says so
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
        assert ops.logprob_backend((40, 384)) == "pallas"
        with pytest.warns(RuntimeWarning, match="chunked jnp backend"):
            assert ops.logprob_backend(logits.shape, block_t=16,
                                       block_v=256) == "chunked"
            lp, _ = ops.fused_token_logprob(logits, tgt, block_t=16,
                                            block_v=256)
        lp_e = token_logprob_from_logits(logits, tgt)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_e),
                                   rtol=1e-5, atol=1e-5)

    def test_tile_derivation_hits_real_model_shapes(self):
        """Realistic shapes — t = B·(S−1), 256-aligned padded vocab —
        rarely divide the default blocks; the dispatcher must shrink the
        tiles rather than silently abandoning the Pallas path."""
        from repro.kernels.ops import _largest_divisor
        assert _largest_divisor(64 * 4095, 256, 8) == 240
        assert _largest_divisor(152_064, 2048, 128) == 1536  # qwen2 vocab
        assert _largest_divisor(128_256, 2048, 128) == 768   # llama3.2
        assert _largest_divisor(100, 256, 8) == 0            # no aligned tile
        assert _largest_divisor(300, 2048, 128) == 0

    def test_pallas_forced_on_unaligned_shape(self, rng):
        # t=40 (mult of 8, not of block_t=256) and v=384 (mult of 128,
        # not of 2048): previously fell back silently; now tiles shrink
        ks = jax.random.split(rng, 2)
        logits = jax.random.normal(ks[0], (5, 8, 384))
        tgt = jax.random.randint(ks[1], (5, 8), 0, 384)
        lp, ent = ops.fused_token_logprob(logits, tgt, impl="pallas")
        lp_e, ent_e = token_logprob_and_entropy(logits, tgt)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_e),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_e),
                                   rtol=1e-5, atol=1e-5)

    def test_rank1_logits(self, rng):
        logits = jax.random.normal(rng, (384,))
        tgt = jnp.asarray(7, jnp.int32)
        lp, ent = ops.fused_token_logprob(logits, tgt)
        lp_e, ent_e = token_logprob_and_entropy(logits[None], tgt[None])
        assert lp.shape == ent.shape == ()
        np.testing.assert_allclose(float(lp), float(lp_e[0]), rtol=1e-5)
        np.testing.assert_allclose(float(ent), float(ent_e[0]), rtol=1e-5)

    def test_unknown_impl_raises(self, rng):
        logits = jnp.zeros((4, 32))
        tgt = jnp.zeros((4,), jnp.int32)
        with pytest.raises(ValueError):
            ops.fused_token_logprob(logits, tgt, impl="magic")

    def test_grad_through_dispatcher(self, rng):
        logits, tgt, w_lp, w_ent = _mk_inputs(rng, 48, 160)
        g = jax.grad(lambda x: (
            w_lp * ops.fused_token_logprob(x, tgt)[0]
            + w_ent * ops.fused_token_logprob(x, tgt)[1]).sum())(logits)
        g_e = jax.grad(lambda x: _naive_loss(x, tgt, w_lp, w_ent))(logits)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_e),
                                   rtol=2e-4, atol=2e-4)


class TestTargetIdContract:
    """Masked positions may carry any id: out-of-range targets clamp to
    [0, V) instead of silently returning −lse, on every path."""

    def _dirty(self, rng, t=32, v=64):
        ks = jax.random.split(rng, 2)
        logits = jax.random.normal(ks[0], (t, v))
        tgt = jax.random.randint(ks[1], (t,), 0, v)
        dirty = tgt.at[0].set(-1).at[1].set(v).at[2].set(v + 1234)
        clean = jnp.clip(dirty, 0, v - 1)
        return logits, dirty, clean

    def test_naive_helpers_clamp(self, rng):
        logits, dirty, clean = self._dirty(rng)
        np.testing.assert_array_equal(
            np.asarray(token_logprob_from_logits(logits, dirty)),
            np.asarray(token_logprob_from_logits(logits, clean)))
        lp_d, ent_d = token_logprob_and_entropy(logits, dirty)
        lp_c, _ = token_logprob_and_entropy(logits, clean)
        np.testing.assert_array_equal(np.asarray(lp_d), np.asarray(lp_c))
        assert np.isfinite(np.asarray(lp_d)).all()
        assert np.isfinite(np.asarray(ent_d)).all()

    def test_fused_paths_match_naive_on_dirty_ids(self, rng):
        logits, dirty, _ = self._dirty(rng)
        lp_e, ent_e = token_logprob_and_entropy(logits, dirty)
        for lp, ent in (
                chunked_logprob(logits, dirty, chunk=8),
                fused_logprob(logits, dirty, block_t=8, block_v=32,
                              interpret=True),
                ops.fused_token_logprob(logits, dirty)):
            np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_e),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_e),
                                       rtol=1e-5, atol=1e-5)

    def test_ref_oracle_clamps(self, rng):
        logits, dirty, clean = self._dirty(rng)
        lp_d, _ = ref.fused_logprob_ref(logits, dirty)
        lp_c, _ = ref.fused_logprob_ref(logits, clean)
        np.testing.assert_array_equal(np.asarray(lp_d), np.asarray(lp_c))

    def test_grads_finite_on_dirty_ids(self, rng):
        logits, dirty, _ = self._dirty(rng)
        g = jax.grad(lambda x: chunked_logprob(x, dirty, chunk=8)[0].sum()
                     )(logits)
        assert np.isfinite(np.asarray(g)).all()


class TestTrainingParity:
    """The full RL loss agrees between naive and fused learner paths —
    values and parameter gradients."""

    def test_rl_loss_fused_vs_naive(self, rng):
        from repro.config import ModelConfig, RLConfig, ATTN, MLP
        from repro.models import init_params
        from repro.training import rl_loss_fn
        tiny = ModelConfig(name="tiny", family="dense", num_layers=2,
                           d_model=48, num_heads=4, num_kv_heads=2,
                           d_ff=96, vocab_size=32, block_pattern=(ATTN,),
                           ffn_pattern=(MLP,), dtype="float32",
                           attn_impl="naive", remat=False, rope_theta=1e4)
        params = init_params(tiny, rng)
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        b, s = 8, 10
        batch = {
            "tokens": jax.random.randint(ks[0], (b, s), 0, 32),
            "mask": jnp.ones((b, s - 1)),
            "sampler_lp": -jnp.abs(jax.random.normal(ks[1], (b, s - 1))),
            "rewards": (jax.random.uniform(ks[2], (b,)) > 0.5).astype(
                jnp.float32),
        }
        rl = RLConfig(loss_type="gepo", group_size=4, beta_kl=0.005)
        outs = {}
        for impl in ("naive", "fused"):
            (loss, _), grads = jax.value_and_grad(
                lambda p, i=impl: rl_loss_fn(tiny, rl, p, batch,
                                             logprob_impl=i),
                has_aux=True)(params)
            outs[impl] = (float(loss), grads)
        assert outs["naive"][0] == pytest.approx(outs["fused"][0],
                                                 rel=1e-5)
        for a, b_ in zip(jax.tree_util.tree_leaves(outs["naive"][1]),
                         jax.tree_util.tree_leaves(outs["fused"][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-6)
