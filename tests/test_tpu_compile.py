"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Each case compiles (and runs nothing) for one chip of a described
``v5e:2x2`` topology with the TPU compiler, so a BlockSpec or layout the
chip refuses fails here instead of on the chip. Interpret-mode parity
lives in the kernels' own test files. One more case compiles the
engine's decode-chunk program at qwen3 widths and reads its structure.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU library. Keep these cases in this one file.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from _hlo import whole_pool_copies, whole_pool_slices
from repro.config import RLConfig
from repro.configs import get_config
from repro.kernels import ops
from repro.models import abstract_params
from repro.sampling import continuous as cont
from repro.sampling.paged_cache import init_paged_pool

VOCAB = 152064                   # qwen3 vocab, padded to a multiple of 256
HQ, HKV, HEAD_DIM, PAGE = 16, 8, 128, 16
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *avals) -> str:
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in avals]
    return jax.jit(fn).lower(*args).compile().as_text()


def _fused_logprob_fwd(logits, targets):
    return ops.fused_token_logprob(logits, targets, impl="pallas",
                                   interpret=False)


def _fused_logprob_grad(logits, targets):
    def total(x):
        lp, ent = ops.fused_token_logprob(x, targets, impl="pallas",
                                          interpret=False)
        return lp.sum() + ent.sum()
    return jax.grad(total)(logits)


LOGPROB_AVALS = [((1536, VOCAB), BF16), ((1536,), jnp.int32)]

# 8 slots, 32 pages of 16 tokens each, pool of 1 scratch + 8·32 pages
POOL = (1 + 8 * 32, HKV, PAGE, HEAD_DIM)
# the rollout cells' decode: 64 slots at table width 64 over 2049 pages,
# (Hkv, rep) of qwen3-1.7b and of qwen3-30b-a3b
SLOTS, WIDTH, PAGES = 64, 64, 2049


def _decode_avals(hkv, rep):
    pool = ((PAGES, hkv, PAGE, HEAD_DIM), BF16)
    return [((SLOTS, 1, hkv * rep, HEAD_DIM), BF16), pool, pool,
            ((SLOTS, WIDTH), jnp.int32), ((SLOTS,), jnp.int32)]


def _paged_decode(q, kp, vp, table, lengths):
    return ops.paged_decode(q, kp, vp, table, lengths, impl="pallas",
                            interpret=False)


def _paged_prefill(q, kp, vp, table, positions):
    return ops.paged_prefill(q, kp, vp, table, positions, impl="pallas",
                             interpret=False)


def _flash(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, interpret=False)


def _ssd_avals():
    cfg = get_config("mamba2-1.3b")
    heads = cfg.ssm_expand * cfg.d_model // cfg.ssm_headdim
    b, s, g, n = 1, 2 * cfg.ssm_chunk, cfg.ssm_ngroups, cfg.ssm_state
    return [((b, s, heads, cfg.ssm_headdim), BF16),
            ((b, s, heads), jnp.float32), ((heads,), jnp.float32),
            ((b, s, g, n), BF16), ((b, s, g, n), BF16)]


def _ssd(x, dt, a, b, c):
    return ops.ssd_scan(x, dt, a, b, c,
                        chunk=get_config("mamba2-1.3b").ssm_chunk,
                        interpret=False)


CASES = {
    "fused_logprob_fwd": (_fused_logprob_fwd, lambda: LOGPROB_AVALS),
    "fused_logprob_grad": (_fused_logprob_grad, lambda: LOGPROB_AVALS),
    "paged_decode": (_paged_decode, lambda: _decode_avals(8, 2)),
    "paged_decode_hkv4_rep8": (_paged_decode, lambda: _decode_avals(4, 8)),
    "paged_prefill": (_paged_prefill, lambda: [
        ((8, 64, HQ, HEAD_DIM), BF16), (POOL, BF16), (POOL, BF16),
        ((8, 32), jnp.int32), ((8, 64), jnp.int32)]),
    "flash_attention": (_flash, lambda: [
        ((2, 512, HQ, HEAD_DIM), BF16), ((2, 512, HKV, HEAD_DIM), BF16),
        ((2, 512, HKV, HEAD_DIM), BF16)]),
    "ssd_scan": (_ssd, _ssd_avals),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, avals = CASES[name]
    text = _compiled_text(fn, one_chip, *avals())
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel compiled"


@pytest.mark.parametrize("impl", ["gather", "auto"])
def test_decode_chunk_keeps_pools_in_place_for_v5e(one_chip, monkeypatch,
                                                   impl):
    """The rollout cell's decode chunk (64 slots, table width 64, 2049
    pages of 16, bf16, plain sampling) at qwen3-1.7b widths, cut to 2
    layers, through the gather view and through what "auto" runs on a
    TPU (the Pallas kernel): the layer scan carries the stacked pools,
    so the compiled program holds no dynamic-slice or
    dynamic-update-slice of one layer's whole pool, and no copy of a
    pool, stacked or one layer's, around the scan or the kernel."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("qwen3-1.7b"), num_layers=2,
                              paged_attn_impl=impl)
    n, width, pages = SLOTS, WIDTH, PAGES

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, pool = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        (abstract_params(cfg),
         jax.eval_shape(lambda: init_paged_pool(cfg, pages, PAGE))))
    rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0)
    hlo = cont._decode_chunk_jit.lower(
        cfg, rl, params, pool, sds((n, width), jnp.int32),
        sds((n, cfg.padded_vocab), jnp.float32), sds((n,), jnp.int32),
        sds((n,), jnp.bool_), sds((n, 2), jnp.uint32), sds((n,), jnp.int32),
        sds((n,), jnp.int32), vocab_limit=cfg.vocab_size,
        sync_every=8).compile().as_text()
    stacked = f"bf16[{cfg.num_blocks},{pages},{HKV},{PAGE},{HEAD_DIM}]"
    assert stacked in hlo
    assert whole_pool_slices(hlo, (pages, HKV, PAGE, HEAD_DIM)) == []
    assert whole_pool_copies(hlo, (pages, HKV, PAGE, HEAD_DIM)) == []
    assert ('custom_call_target="tpu_custom_call"' in hlo) == (impl == "auto")
