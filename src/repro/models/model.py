"""The unified language model: forward / encode / prefill / decode for every
supported architecture family (dense, MoE, SSM, hybrid, VLM, audio enc-dec).

Layers are applied in scanned *super-blocks* of one ``block_pattern`` period
(homogeneous across depth), keeping HLO size O(1) in depth. Activation
checkpointing (``jax.checkpoint``) wraps the block body when ``cfg.remat``.

All functions are pure; parameters come from ``repro.models.params``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ATTN, CROSS, LOCAL, MAMBA, MLP, MOE, ModelConfig
from repro.models import attention as attn_mod
from repro.models.layers import rmsnorm, rope, softcap, swiglu
from repro.models.moe import moe_ffn
from repro.models.ssm import mamba_block


# --------------------------------------------------------------------------
# sub-layer application


def _project_qkv(cfg: ModelConfig, p: Dict, xq: jax.Array,
                 xkv: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, sq, _ = xq.shape
    sk = xkv.shape[1]
    q = xq @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, sq, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, sk, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, sk, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _self_attn(cfg: ModelConfig, p: Dict, x: jax.Array, *, kind: str,
               positions: jax.Array, cache: Optional[Dict], pos,
               bidir: bool = False, page_table: Optional[jax.Array] = None,
               block=None):
    """Self-attention sub-layer body (input already normed).

    Returns (out, new_cache). In decode mode (pos is not None) x is
    (B,1,d) and the cache k/v are updated in place at ``pos``. When the
    cache is *paged* (holds "kp"/"vp" page pools and ``page_table`` maps
    (slot, logical_page) -> physical page), both chunked prefill and
    decode go through the paged scatter/gather path instead: the pools
    are the whole stacked (num_blocks, P, Hkv, page, D) pools the block
    scan carries, and this layer owns slab ``block`` of them.
    """
    q, k, v = _project_qkv(cfg, p, x, x)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    mask_kind = ("bidir" if bidir else
                 "local" if kind == LOCAL else "causal")

    if cache is not None and "kp" in cache:               # paged KV cache
        b, sq = x.shape[0], x.shape[1]
        kp, vp = cache["kp"], cache["vp"]
        page_size = kp.shape[3]                       # (nb, P, Hkv, page, D)
        page = positions // page_size                     # (B, Sq) logical
        off = positions % page_size
        # logical pages past the block-table width (only padded prefill
        # tails reach here) must gather an OOB sentinel so the scatter
        # below drops the write instead of clamping onto a live page
        phys = jnp.take_along_axis(page_table, page, axis=1, mode="fill",
                                   fill_value=jnp.iinfo(jnp.int32).min)
        # one (D,) row per (token, kv head): the written window is the
        # pool's minor axis, so XLA keeps the pools row-major in the
        # layer scan, the layout the Pallas kernels read. With a
        # (Hkv, D) window XLA stores them (P, page, Hkv, D) there and
        # relays both stacked pools around the scan and every kernel.
        head = jnp.arange(kp.shape[2])
        at = (block, phys[..., None], head, off[..., None])
        kp = kp.at[at].set(k.astype(kp.dtype))
        vp = vp.at[at].set(v.astype(vp.dtype))
        # every backend attends the layer's slab in place, through the
        # folded pools and a table offset by block·P
        from repro.kernels.ops import layer_slab, paged_decode, paged_prefill
        kpl, vpl, table = layer_slab(kp, vp, page_table, block)
        if sq == 1:                                       # decode
            # hot loop: attend the pools in place (the Pallas kernel on
            # a TPU) or via the bit-exact gather view —
            # repro.kernels.ops.paged_decode. The engine narrows
            # page_table to the live high-water mark, so every impl
            # scales with context, not pool capacity.
            o = paged_decode(q, kpl, vpl, table, positions[:, 0] + 1,
                             kind=mask_kind, window=cfg.sliding_window,
                             softcap=cfg.attn_softcap,
                             impl=cfg.paged_attn_impl)
        else:                                             # chunked prefill
            # attend the pools in place (ref/pallas) or via the dense
            # per-slot gather (what "auto" runs) —
            # repro.kernels.ops.paged_prefill. The engine narrows
            # page_table to pages_for(c0 + C), so the gather view is
            # bounded by the chunk's pow2 width bucket; the kernel/ref
            # paths never materialize it at all.
            o = paged_prefill(q, kpl, vpl, table, positions,
                              kind=mask_kind, window=cfg.sliding_window,
                              softcap=cfg.attn_softcap,
                              impl=cfg.paged_attn_impl,
                              attn_impl=cfg.attn_impl, chunk=cfg.attn_chunk)
        return o.reshape(b, sq, -1) @ p["wo"], {"kp": kp, "vp": vp}

    ring = (cfg.local_ring_kv and kind == LOCAL)
    if pos is not None:                                   # decode
        w_pos = jnp.mod(pos, cache["k"].shape[1]) if ring else pos
        kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, w_pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, w_pos, 0, 0))
        if ring:
            o = attn_mod.ring_decode_attention(
                q, kc, vc, pos=pos, window=cfg.sliding_window,
                softcap=cfg.attn_softcap)
        else:
            o = attn_mod.decode_attention(q, kc, vc, pos=pos,
                                          kind=mask_kind,
                                          window=cfg.sliding_window,
                                          softcap=cfg.attn_softcap)
        new_cache = {"k": kc, "v": vc}
    else:
        o = attn_mod.attention(q, k, v, pos_q=positions, pos_k=positions,
                               kind=mask_kind, window=cfg.sliding_window,
                               softcap=cfg.attn_softcap,
                               impl=cfg.attn_impl, chunk=cfg.attn_chunk)
        new_cache = None
        if cache is not None:                             # prefill fills cache
            if ring:
                w = cache["k"].shape[1]
                kc = attn_mod.fill_ring(k, w).astype(cache["k"].dtype)
                vc = attn_mod.fill_ring(v, w).astype(cache["v"].dtype)
            else:
                kc = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
            new_cache = {"k": kc, "v": vc}
    b, sq = x.shape[0], x.shape[1]
    return o.reshape(b, sq, -1) @ p["wo"], new_cache


def _cross_attn(cfg: ModelConfig, p: Dict, x: jax.Array, *,
                memory: Optional[jax.Array], cache: Optional[Dict]):
    """Cross-attention to a modality/encoder memory. If ``cache`` holds
    precomputed k_mem/v_mem they are used (decode); otherwise projected
    from ``memory``."""
    b, sq, _ = x.shape
    q = (x @ p["wq"]).reshape(b, sq, cfg.num_heads, cfg.head_dim)
    if cache is not None and "k_mem" in cache:
        k, v = cache["k_mem"], cache["v_mem"]
    else:
        sk = memory.shape[1]
        k = (memory @ p["wk"]).reshape(b, sk, cfg.num_kv_heads, cfg.head_dim)
        v = (memory @ p["wv"]).reshape(b, sk, cfg.num_kv_heads, cfg.head_dim)
    sk = k.shape[1]
    pos_q = jnp.zeros((b, sq), jnp.int32)
    pos_k = jnp.zeros((b, sk), jnp.int32)
    o = attn_mod.attention(q, k, v, pos_q=pos_q, pos_k=pos_k, kind="bidir",
                           impl=cfg.attn_impl, chunk=cfg.attn_chunk)
    return o.reshape(b, sq, -1) @ p["wo"]


def _ffn(cfg: ModelConfig, kind: str, p: Dict, x: jax.Array,
         aux: Dict[str, jax.Array]):
    if kind == MLP:
        return x + swiglu(rmsnorm(x, p["ffn_norm"], cfg.norm_eps), p["mlp"]), aux
    if kind == MOE:
        h_in = rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
        if cfg.moe_ep is not None:
            from repro.models.moe_ep import moe_ffn_ep
            y, a = moe_ffn_ep(cfg, p["moe"], h_in)
        else:
            y, a = moe_ffn(cfg, p["moe"], h_in)
        aux = {k: aux.get(k, 0.0) + v for k, v in a.items()}
        return x + y, aux
    return x, aux                                          # NONE


def _apply_layer(cfg: ModelConfig, idx_in_block: int, p: Dict, x: jax.Array,
                 *, positions, memory, cache, pos, aux,
                 encoder: bool = False, page_table=None, block=None):
    kind = ATTN if encoder else cfg.block_pattern[idx_in_block]
    ffn_kind = MLP if encoder else cfg.ffn_kind(idx_in_block)
    new_cache: Dict[str, Any] = {}

    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    if kind in (ATTN, LOCAL):
        o, c = _self_attn(cfg, p["attn"], h, kind=kind, positions=positions,
                          cache=None if cache is None else cache.get("self"),
                          pos=pos, bidir=encoder, page_table=page_table,
                          block=block)
        x = x + o
        if c is not None:
            new_cache["self"] = c
        if cfg.is_encdec and not encoder:                 # whisper decoder
            h2 = rmsnorm(x, p["cross_norm"], cfg.norm_eps)
            x = x + _cross_attn(cfg, p["cross"], h2, memory=memory,
                                cache=None if cache is None else cache.get("mem"))
            if cache is not None and "mem" in cache:
                new_cache["mem"] = cache["mem"]
    elif kind == CROSS:
        x = x + _cross_attn(cfg, p["attn"], h, memory=memory,
                            cache=None if cache is None else cache.get("mem"))
        if cache is not None and "mem" in cache:
            new_cache["mem"] = cache["mem"]
    elif kind == MAMBA:
        o, c = mamba_block(cfg, p["mamba"], h,
                           cache=None if cache is None else cache.get("ssm_c"),
                           decode=pos is not None)
        x = x + o
        if cache is not None:
            new_cache["ssm_c"] = c
    else:
        raise ValueError(kind)

    x, aux = _ffn(cfg, ffn_kind, p, x, aux)
    return x, new_cache, aux


# --------------------------------------------------------------------------
# block scan drivers


def _constrain(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Residual-stream sharding constraint (Megatron-SP-style sequence
    sharding between blocks) — active only when the launcher sets
    ``cfg.act_sharding`` and a mesh is in scope."""
    if cfg.act_sharding is None:
        return x
    spec = jax.sharding.PartitionSpec(*cfg.act_sharding)
    return jax.lax.with_sharding_constraint(x, spec)


def _aux_init(cfg: ModelConfig) -> Dict[str, jax.Array]:
    if MOE in cfg.ffn_pattern:
        return {"moe_load_balance": jnp.zeros(()), "moe_z_loss": jnp.zeros(())}
    return {}


def _is_paged(cache: Optional[Dict]) -> bool:
    """A paged pool (``repro.sampling.paged_cache``) holds "kp"/"vp"
    page pools where a dense cache holds per-row "k"/"v"."""
    return cache is not None and any(
        "kp" in lc.get("self", {}) for lc in cache.values())


def _run_blocks(cfg: ModelConfig, blocks: Dict, x: jax.Array, *,
                positions, memory, cache, pos, encoder=False,
                page_table=None):
    """Scan super-blocks. cache (if given) is a pytree stacked on axis 0
    matching ``blocks``; returns (x, new_cache, aux).

    A dense cache rides the scan as ``xs``/``ys``: each block reads and
    returns its own slice. A paged cache rides it whole in the carry and
    ``xs`` gives the block index: each layer scatters its K/V into, and
    attends, its slab of the stacked (nb, P, Hkv, page, D) pools in
    place, so no block copies a whole layer's pool in or out."""
    aux0 = {} if encoder else _aux_init(cfg)
    paged = _is_paged(cache)

    def body(carry, xs):
        x, aux, pools = carry
        x = _constrain(cfg, x)
        bp, bx = xs
        bc, block = (pools, bx) if paged else (bx, None)
        new_bc = {}
        for i in range(len(cfg.block_pattern)):
            key = f"layer_{i}"
            lc = None if bc is None else bc.get(key)
            x, nc, aux = _apply_layer(cfg, i, bp[key], x, positions=positions,
                                      memory=memory, cache=lc, pos=pos,
                                      aux=aux, page_table=page_table,
                                      block=block)
            if bc is not None:
                new_bc[key] = nc
        ys = 0
        if paged:
            pools = new_bc
        elif bc is not None:
            ys = new_bc
        return (x, aux, pools), ys

    if encoder:
        # encoder blocks are a single stacked layer dict
        def ebody(carry, bp):
            x, aux = carry
            x, _, aux = _apply_layer(cfg, 0, bp, x, positions=positions,
                                     memory=None, cache=None, pos=None,
                                     aux=aux, encoder=True)
            return (x, aux), 0
        fn = jax.checkpoint(ebody) if cfg.remat else ebody
        (x, aux), _ = jax.lax.scan(fn, (x, aux0), blocks)
        return x, None, aux

    fn = jax.checkpoint(body) if cfg.remat else body
    if paged:
        carry = (x, aux0, cache)
        xs = (blocks, jnp.arange(cfg.num_blocks, dtype=jnp.int32))
    else:
        carry, xs = (x, aux0, None), (blocks, cache)
    (x, aux, pools), ys = jax.lax.scan(fn, carry, xs)
    if cache is None:
        return x, None, aux
    return x, (pools if paged else ys), aux


# --------------------------------------------------------------------------
# public API


def _embed(cfg: ModelConfig, params: Dict, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _logits(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    with jax.named_scope("lm_head"):
        logits = (x @ head).astype(jnp.float32)
    if (cfg.act_sharding is not None and logits.ndim == 3
            and cfg.act_sharding[1] == "model"):
        # Megatron-SP exit: gather sequence, keep vocab sharded on model.
        dp = cfg.act_sharding[0]
        logits = jax.lax.with_sharding_constraint(
            logits, jax.sharding.PartitionSpec(dp, None, "model"))
    if cfg.final_softcap is not None:
        logits = softcap(logits, cfg.final_softcap)
    return logits


def encode(cfg: ModelConfig, params: Dict, frames: jax.Array) -> jax.Array:
    """Whisper-style encoder over stub frame embeddings (B, S_enc, d)."""
    assert cfg.is_encdec
    b, s, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, _, _ = _run_blocks(cfg, params["encoder"]["blocks"], frames,
                          positions=positions, memory=None, cache=None,
                          pos=None, encoder=True)
    return rmsnorm(x, params["encoder"]["final_norm"], cfg.norm_eps)


def forward(cfg: ModelConfig, params: Dict, tokens: jax.Array, *,
            memory: Optional[jax.Array] = None,
            positions: Optional[jax.Array] = None,
            cache: Optional[Dict] = None,
            page_table: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, Optional[Dict], Dict]:
    """Full-sequence forward (training / prefill).

    tokens (B, S) -> logits (B, S, V_padded) in f32.
    If ``cache`` is provided it is filled (prefill) and returned. A paged
    cache (page pools from ``repro.sampling.paged_cache``) additionally
    needs ``page_table`` (B, pages_per_slot) and explicit ``positions``
    for chunked prefill at an offset.
    """
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = _embed(cfg, params, tokens)
    x, new_cache, aux = _run_blocks(cfg, params["blocks"], x,
                                    positions=positions, memory=memory,
                                    cache=cache, pos=None,
                                    page_table=page_table)
    return _logits(cfg, params, x), new_cache, aux


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict,
                token: jax.Array, pos: jax.Array, *,
                memory: Optional[jax.Array] = None,
                page_table: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict]:
    """One decode step. token (B,) int32; pos scalar int32, or a (B,)
    vector when rows decode at heterogeneous positions (requires a paged
    cache + ``page_table`` — the dense cache layout assumes one shared
    write position).

    Returns (logits (B, V_padded) f32, new_cache).
    """
    b = token.shape[0]
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        positions = jnp.full((b, 1), pos, jnp.int32)
    else:
        positions = pos.astype(jnp.int32)[:, None]
    x = _embed(cfg, params, token[:, None])
    x, new_cache, _ = _run_blocks(cfg, params["blocks"], x,
                                  positions=positions, memory=memory,
                                  cache=cache, pos=pos,
                                  page_table=page_table)
    return _logits(cfg, params, x)[:, 0], new_cache


def init_cache(cfg: ModelConfig, params: Dict, batch: int, max_len: int, *,
               memory: Optional[jax.Array] = None,
               dtype: Optional[str] = None) -> Dict:
    """Decode cache pytree, stacked on the block axis.

    For CROSS / enc-dec layers the memory k/v are projected once here.
    """
    dt = jnp.dtype(dtype or cfg.dtype)
    nb = cfg.num_blocks
    cache: Dict[str, Any] = {}

    def kv(b, kind=ATTN):
        ml = max_len
        if cfg.local_ring_kv and kind == LOCAL:
            ml = min(max_len, cfg.sliding_window)
        return {"k": jnp.zeros((b, ml, cfg.num_kv_heads, cfg.head_dim), dt),
                "v": jnp.zeros((b, ml, cfg.num_kv_heads, cfg.head_dim), dt)}

    def mem_kv(i):
        """(nb, B, M, Hkv, hd) memory projections for layer slot i."""
        wk = params["blocks"][f"layer_{i}"]["cross" if cfg.is_encdec
                                            else "attn"]["wk"]
        wv = params["blocks"][f"layer_{i}"]["cross" if cfg.is_encdec
                                            else "attn"]["wv"]
        m = memory.shape[1]

        def proj(w):
            return jnp.einsum("bmd,ndh->nbmh", memory, w).reshape(
                nb, batch, m, cfg.num_kv_heads, cfg.head_dim).astype(dt)
        return {"k_mem": proj(wk), "v_mem": proj(wv)}

    for i, kind in enumerate(cfg.block_pattern):
        lc: Dict[str, Any] = {}
        if kind in (ATTN, LOCAL):
            lc["self"] = jax.tree_util.tree_map(
                lambda z: jnp.broadcast_to(z, (nb,) + z.shape).copy(),
                kv(batch, kind))
            if cfg.is_encdec:
                lc["mem"] = mem_kv(i)
        elif kind == CROSS:
            lc["mem"] = mem_kv(i)
        elif kind == MAMBA:
            conv_ch = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
            lc["ssm_c"] = {
                "conv": jnp.zeros((nb, batch, cfg.ssm_conv - 1, conv_ch), dt),
                "ssm": jnp.zeros((nb, batch, cfg.ssm_heads, cfg.ssm_headdim,
                                  cfg.ssm_state), jnp.float32),
            }
        cache[f"layer_{i}"] = lc
    return cache
