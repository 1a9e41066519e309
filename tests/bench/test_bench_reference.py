"""The plain f32 reference matches the program at smoke size: the
forward pass (tied and untied head), and the engine's draw of a token,
which the reference replays as argmax(logits + Gumbel noise)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny import tiny_config

from bench.lib import program, reference
from bench.lib import weights as W


@pytest.mark.parametrize("tied", [True, False])
def test_forward_matches_the_program(tied):
    from repro.models import forward
    c = tiny_config(tied)
    cfg = program.model_config(c)
    program.check_layout(cfg, c)
    seed = 2 ** 31 + 11
    params = program.make_program_weights(cfg, c, seed)
    tokens = np.random.default_rng(0).integers(3, 512, (2, 24)).astype(np.int32)
    logits, _, _ = forward(cfg, params, jnp.asarray(tokens))
    wts = W.make(c, seed, cfg.padded_vocab)
    citems = tuple(sorted((k, v) for k, v in c.items()
                          if isinstance(v, (int, float, bool, str))))
    x, _ = reference.hidden(c, wts, jnp.asarray(tokens), "f32")
    h = reference.rmsnorm(x, wts["final_norm"], c["rms_norm_eps"])
    ref = jnp.matmul(h, reference.head_matrix(wts, c["vocab_size"]),
                     precision=reference.HI)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    lp = reference._block_logps(wts, jnp.asarray(tokens), citems=citems,
                                mm="f32")
    want = jax.nn.log_softmax(ref, -1)[:, :-1]
    want = np.take_along_axis(np.asarray(want), tokens[:, 1:, None], -1)[..., 0]
    np.testing.assert_allclose(np.asarray(lp), want, atol=2e-5)


def test_gumbel_replay_is_the_engines_draw():
    from repro.sampling.sample import sample_token_rows
    v_pad, v = 512, 500
    logits = jax.random.normal(jax.random.PRNGKey(3), (6, v_pad)) * 2.0
    logits = logits.at[:, v:].set(-1e30)               # masked padding ids
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(5), i))(
        jnp.arange(6))
    tok, _, _ = sample_token_rows(keys, logits, temperature=1.0, top_k=0,
                                  top_p=1.0)
    g = jax.vmap(lambda k: jax.random.gumbel(
        jax.random.wrap_key_data(k), (v_pad,), jnp.float32))(keys)
    replay = jnp.argmax(logits[:, :v] + g[:, :v], -1)
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(replay))


def test_fp8_control_rounds_both_operands():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    w = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    exact = reference.matmul(x, w, "f32")
    low = reference.matmul(x, w, "fp8")
    rel = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-3 < rel < 0.2


def test_float8_rounding_keeps_each_rows_largest_value():
    """The control's rounding: the largest value of each row comes back
    as itself (to rounding), nothing leaves the row's range, and a quotient a rounding
    step past the largest float8 value is clipped, not cast past it."""
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 512), jnp.float32)
    q = reference._q8(x, -1)
    am = jnp.argmax(jnp.abs(x), -1)[:, None]
    np.testing.assert_allclose(jnp.take_along_axis(q, am, -1),
                               jnp.take_along_axis(x, am, -1), rtol=1e-6)
    top = jnp.max(jnp.abs(x), -1, keepdims=True)
    assert bool(jnp.all(jnp.abs(q) <= top * (1 + 1e-6)))
    over = jnp.float32(reference.F8_MAX) * (1 + 2 ** -20)
    assert float(jnp.clip(over, -reference.F8_MAX, reference.F8_MAX).astype(
        reference.F8).astype(jnp.float32)) == reference.F8_MAX
