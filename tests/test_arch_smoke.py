"""Per-architecture smoke tests: instantiate the reduced same-family
variant of each assigned architecture, run one forward and one RL train
step on CPU, assert output shapes and finiteness. Decode-vs-forward
consistency for every family with a decode path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import RLConfig, TrainConfig
from repro.configs import ARCHS, smoke
from repro.models import (decode_step, encode, forward, init_cache,
                          init_params)
from repro.training import init_state, rl_loss_fn, train_step

ARCH_IDS = sorted(ARCHS)


def _memory_for(cfg, params, b, key):
    if cfg.is_encdec:
        frames = jax.random.normal(key, (b, cfg.encoder_seq, cfg.d_model),
                                   jnp.float32).astype(cfg.dtype)
        return encode(cfg, params, frames)
    if cfg.memory_seq:
        return 0.02 * jax.random.normal(
            key, (b, cfg.memory_seq, cfg.d_model)).astype(cfg.dtype)
    return None


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch, rng):
    cfg = smoke(arch)
    params = init_params(cfg, rng)
    b, s = 2, 16
    toks = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    memory = _memory_for(cfg, params, b, rng)
    logits, _, aux = forward(cfg, params, toks, memory=memory)
    assert logits.shape == (b, s, cfg.padded_vocab)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all()), "NaN/Inf in logits"
    if cfg.num_experts:
        assert "moe_load_balance" in aux


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_rl_train_step(arch, rng):
    cfg = smoke(arch)
    params = init_params(cfg, rng)
    rl = RLConfig(loss_type="gepo", group_size=4, beta_kl=0.005)
    tc = TrainConfig(learning_rate=1e-3, total_steps=10)
    state = init_state(cfg, tc, params)
    b, s = 8, 12
    ks = jax.random.split(rng, 3)
    tokens = jax.random.randint(ks[0], (b, s), 0, cfg.vocab_size)
    batch = {
        "tokens": tokens,
        "mask": jnp.ones((b, s - 1)),
        "sampler_lp": -jnp.abs(jax.random.normal(ks[1], (b, s - 1))),
        "rewards": (jax.random.uniform(ks[2], (b,)) > 0.5).astype(
            jnp.float32),
    }
    memory = _memory_for(cfg, params, b, rng)
    new_state, metrics = train_step(cfg, rl, tc, state, batch,
                                    memory=memory)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # parameters actually moved
    moved = jax.tree_util.tree_map(
        lambda a, b_: float(jnp.abs(a.astype(jnp.float32)
                                    - b_.astype(jnp.float32)).max()),
        state.params, new_state.params)
    assert max(jax.tree_util.tree_leaves(moved)) > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_matches_forward(arch, rng):
    cfg = smoke(arch)
    params = init_params(cfg, rng)
    b, s = 2, 8
    toks = jax.random.randint(rng, (b, s), 0, cfg.vocab_size)
    memory = _memory_for(cfg, params, b, rng)
    full, _, _ = forward(cfg, params, toks, memory=memory)
    cache = init_cache(cfg, params, b, s, memory=memory)
    outs = []
    for t in range(s):
        lg, cache = decode_step(cfg, params, cache, toks[:, t],
                                jnp.int32(t), memory=memory)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_then_decode_continues(arch, rng):
    """Prefill fills the cache; the next decode step must match the
    forward logits of the extended sequence."""
    cfg = smoke(arch)
    params = init_params(cfg, rng)
    b, s = 2, 8
    toks = jax.random.randint(rng, (b, s + 1), 0, cfg.vocab_size)
    memory = _memory_for(cfg, params, b, rng)
    cache = init_cache(cfg, params, b, s + 1, memory=memory)
    _, cache, _ = forward(cfg, params, toks[:, :s], cache=cache,
                          memory=memory)
    lg, _ = decode_step(cfg, params, cache, toks[:, s], jnp.int32(s),
                        memory=memory)
    full, _, _ = forward(cfg, params, toks, memory=memory)
    np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(lg),
                               atol=1e-3, rtol=1e-3)
