"""The MoE layer (``repro.models.moe``) drops nothing: a token's output
does not depend on the rest of its batch, for a layer that holds every
expert and for one that holds a block of them, even when every token of
the batch picks the same experts (the case a per-expert capacity drops).
Float32 on the CPU: the outputs differ by the order of summation alone."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ATTN, MOE, ModelConfig
from repro.models.moe import moe_ffn
from repro.models.params import init_params

CFG = ModelConfig(name="moe-tiny", family="moe", num_layers=1, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=32, vocab_size=256,
                  block_pattern=(ATTN,), ffn_pattern=(MOE,), num_experts=8,
                  experts_per_token=2, dtype="float32", attn_impl="naive",
                  remat=False)


@pytest.mark.parametrize("block", [None, (2, 2), (6, 2)])
def test_token_alone_equals_token_in_a_batch_of_64(block):
    cfg = dataclasses.replace(CFG, expert_block=block)
    p = jax.tree_util.tree_map(
        lambda a: a[0], init_params(cfg, jax.random.PRNGKey(0))
        ["blocks"]["layer_0"]["moe"])
    # every token near one direction: all route alike, so one expert gets
    # far more than its even share of the 64 x 2 picks
    base = jax.random.normal(jax.random.PRNGKey(1), (64,))
    x = base + 0.05 * jax.random.normal(jax.random.PRNGKey(2), (1, 64, 64))
    together, _ = moe_ffn(cfg, p, x)
    alone = jnp.concatenate([moe_ffn(cfg, p, x[:, i:i + 1])[0]
                             for i in range(64)], axis=1)
    scale = float(jnp.abs(together).max())
    assert scale > 0
    np.testing.assert_allclose(np.asarray(together), np.asarray(alone),
                               atol=1e-6 * scale, rtol=0)
