"""Fused, differentiable token-logprob (+ entropy) — the RL hot spot.

RL post-training needs log p(y_t) (and the entropy) of every sampled
token, for both the learner's loss and the App. B.1 untrusted-sampler
recompute. The naive path materializes log_softmax over the whole
vocabulary — (B·S, 152k) f32 activations (and their backward twins)
dominate HBM traffic at GEPO's training shapes. Both implementations
here stream the vocabulary instead, in the forward *and* backward pass:

- ``fused_logprob`` — Pallas TPU kernel pair under one
  ``jax.custom_vjp``. Forward: grid (n_token_blocks, n_vocab_blocks),
  vocab innermost, online logsumexp in VMEM scratch; emits (logp, ent)
  plus the O(T) residual ``lse`` (μ = E_p[x] is recovered as lse − ent,
  so the saved state per token is just two f32 scalars). Backward: a
  second kernel streams the same vocab tiles again and writes
      dlogits = g_lp·(onehot(tgt) − p) − g_ent·p·(x − μ)
  tile-by-tile (p = exp(x − lse) recomputed per tile), so neither pass
  materializes a V-sized f32 activation.

- ``chunked_logprob`` — pure-JAX fallback with the *same* custom VJP
  structure: ``lax.map`` over fixed-size token chunks, each chunk doing
  a full-vocab reduction in f32. Peak live f32 activation is
  O(chunk · V) instead of O(T · V) in both passes, works on any
  backend and any (T, V) shape (a ragged tail chunk is handled
  separately — no padded copy of the logits). Vocab reductions use the
  masked-sum gather (iota == target) so vocab-sharded logits never
  all-gather (cf. ``repro.core.logprob``).

Target-id contract (shared with ``repro.core.logprob``): ids are
clamped to [0, V) before the gather. Out-of-range ids — conventionally
parked on *masked* positions by padding — therefore return the (finite)
log-prob of a valid token instead of silently degenerating to −lse; the
loss masks them out, but diagnostics and parity tests stay finite.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Pallas forward: online logsumexp over vocab tiles


def _fwd_kernel(logits_ref, tgt_ref, logp_ref, ent_ref, lse_ref,
                m_scr, l_scr, s1_scr, tacc_scr, *, bt: int, bv: int,
                nv: int):
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        s1_scr[...] = jnp.zeros_like(s1_scr)
        tacc_scr[...] = jnp.zeros_like(tacc_scr)

    x = logits_ref[...].astype(jnp.float32)              # (bt, bv)
    tgt = tgt_ref[...]                                   # (bt, 1)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, x.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(x - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    s1_scr[...] = s1_scr[...] * alpha + (p * x).sum(axis=1, keepdims=True)
    m_scr[...] = m_new

    cols = iv * bv + jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    hit = cols == tgt
    tacc_scr[...] += jnp.where(hit, x, 0.0).sum(axis=1, keepdims=True)

    @pl.when(iv == nv - 1)
    def _finish():
        m = m_scr[...]
        l = jnp.maximum(l_scr[...], 1e-30)
        lse = m + jnp.log(l)
        logp_ref[...] = (tacc_scr[...] - lse).astype(logp_ref.dtype)
        # H = lse − E_p[x]
        ent_ref[...] = (lse - s1_scr[...] / l).astype(ent_ref.dtype)
        lse_ref[...] = lse.astype(lse_ref.dtype)


# Per-token operands travel as (T, 1) columns: a (bt, 1) block is tiled
# like the (bt, bv) logits tile's rows, where a rank-1 (bt,) block would
# have to match XLA's 1024-element tiling of 1-D arrays on TPU.


def _col(bt: int) -> pl.BlockSpec:
    return pl.BlockSpec((bt, 1), lambda it, iv: (it, 0))


def _pallas_fwd(logits, targets, block_t, block_v, interpret):
    """logits (T, V), targets (T, 1) -> (logp, ent, lse), each (T, 1)."""
    t, v = logits.shape
    bt = min(block_t, t)
    bv = min(block_v, v)
    assert t % bt == 0 and v % bv == 0, (t, v, bt, bv)
    nt, nv = t // bt, v // bv
    col = jax.ShapeDtypeStruct((t, 1), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bt=bt, bv=bv, nv=nv),
        grid=(nt, nv),
        in_specs=[pl.BlockSpec((bt, bv), lambda it, iv: (it, iv)), _col(bt)],
        out_specs=[_col(bt)] * 3,
        out_shape=[col] * 3,
        scratch_shapes=[pltpu.VMEM((bt, 1), jnp.float32)] * 4,
        interpret=interpret,
        name="fused_logprob_fwd",
    )(logits, targets)


# --------------------------------------------------------------------------
# Pallas backward: every (token, vocab) tile is independent —
# dlogits = g_lp·(onehot − p) − g_ent·p·(x − μ) with p = exp(x − lse)


def _bwd_kernel(logits_ref, tgt_ref, lse_ref, mu_ref, glp_ref, gent_ref,
                dlogits_ref, *, bt: int, bv: int):
    iv = pl.program_id(1)
    x = logits_ref[...].astype(jnp.float32)              # (bt, bv)
    p = jnp.exp(x - lse_ref[...])                        # columns (bt, 1)
    cols = iv * bv + jax.lax.broadcasted_iota(jnp.int32, (bt, bv), 1)
    hit = (cols == tgt_ref[...]).astype(jnp.float32)
    d = (glp_ref[...] * (hit - p)
         - gent_ref[...] * p * (x - mu_ref[...]))
    dlogits_ref[...] = d.astype(dlogits_ref.dtype)


def _pallas_bwd(logits, targets, lse, mu, g_lp, g_ent, block_t, block_v,
                interpret):
    """Every per-token operand is a (T, 1) column."""
    t, v = logits.shape
    bt = min(block_t, t)
    bv = min(block_v, v)
    nt, nv = t // bt, v // bv
    return pl.pallas_call(
        functools.partial(_bwd_kernel, bt=bt, bv=bv),
        grid=(nt, nv),
        in_specs=[pl.BlockSpec((bt, bv), lambda it, iv: (it, iv))]
        + [_col(bt)] * 5,
        out_specs=pl.BlockSpec((bt, bv), lambda it, iv: (it, iv)),
        out_shape=jax.ShapeDtypeStruct((t, v), logits.dtype),
        interpret=interpret,
        name="fused_logprob_bwd",
    )(logits, targets, lse, mu, g_lp, g_ent)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_logprob_vjp(logits, targets, block_t, block_v, interpret):
    logp, ent, _ = _pallas_fwd(logits, targets[:, None], block_t, block_v,
                               interpret)
    return logp[:, 0], ent[:, 0]


def _fused_fwd_rule(logits, targets, block_t, block_v, interpret):
    logp, ent, lse = _pallas_fwd(logits, targets[:, None], block_t,
                                 block_v, interpret)
    # O(T) residuals only: μ = E_p[x] = lse − H
    return (logp[:, 0], ent[:, 0]), (logits, targets, lse, lse - ent)


def _fused_bwd_rule(block_t, block_v, interpret, res, cots):
    logits, targets, lse, mu = res
    g_lp, g_ent = cots
    dlogits = _pallas_bwd(logits, targets[:, None], lse, mu, g_lp[:, None],
                          g_ent[:, None], block_t, block_v, interpret)
    return dlogits, np.zeros(targets.shape, jax.dtypes.float0)


_fused_logprob_vjp.defvjp(_fused_fwd_rule, _fused_bwd_rule)


def fused_logprob(logits: jax.Array, targets: jax.Array, *,
                  block_t: int = 256, block_v: int = 2048,
                  interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """logits (T, V); targets (T,) int -> (logp (T,), entropy (T,)), f32.

    Differentiable w.r.t. ``logits`` (custom VJP, backward is a second
    streaming Pallas kernel). T and V must divide by the (clipped) block
    sizes — the ``ops.fused_token_logprob`` dispatcher falls back to
    ``chunked_logprob`` for ragged shapes.
    """
    from repro.core.logprob import clamp_target_ids
    tgt = clamp_target_ids(targets, logits.shape[-1])
    return _fused_logprob_vjp(logits, tgt, block_t, block_v, interpret)


# --------------------------------------------------------------------------
# Chunked pure-JAX fallback: same VJP structure, bounded f32 live set


def _chunk_fwd(x: jax.Array, tgt: jax.Array):
    """One token chunk (..., c, V) -> (logp, ent, lse), each (..., c)
    f32. Delegates to the shared masked-sum math in repro.core.logprob
    (iota == target gather, so vocab-sharded logits never all-gather) —
    one source of truth for naive↔fused numerical parity."""
    from repro.core.logprob import token_logprob_entropy_lse
    return token_logprob_entropy_lse(x, tgt)


def _chunk_bwd(x, tgt, lse, mu, g_lp, g_ent):
    """dlogits for one token chunk, recomputing p = exp(x − lse)."""
    lg = x.astype(jnp.float32)
    p = jnp.exp(lg - lse[..., None])
    hit = (jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
           == tgt[..., None]).astype(jnp.float32)
    d = (g_lp[..., None] * (hit - p)
         - g_ent[..., None] * p * (lg - mu[..., None]))
    return d.astype(x.dtype)


def _chunked_fwd_pass(logits, targets, chunk: int):
    """Forward over the token axis (the second-to-last logits axis) in
    fixed ``chunk`` pieces. Chunking stays on that axis — never on a
    flattened (B·S,) — so under GSPMD the batch axes keep their data
    sharding and every slice is shard-local. The loop indexes into the
    *original* arrays with ``dynamic_slice`` (loop-invariant operands —
    no stacked (nc, ..., chunk, V) copy of the logits as a scan input),
    and only the O(tokens) outputs are stacked. A ragged tail chunk is
    handled by a direct call, so no padded copy either."""
    ax = logits.ndim - 2                       # token axis (== targets -1)
    t = logits.shape[ax]
    nc, rem = divmod(t, chunk)
    parts = []
    if nc == 1:
        parts.append(_chunk_fwd(
            jax.lax.slice_in_dim(logits, 0, chunk, axis=ax),
            jax.lax.slice_in_dim(targets, 0, chunk, axis=ax)))
    elif nc:
        def fwd_i(i):
            x = jax.lax.dynamic_slice_in_dim(logits, i * chunk, chunk,
                                             axis=ax)
            tg = jax.lax.dynamic_slice_in_dim(targets, i * chunk, chunk,
                                              axis=ax)
            return _chunk_fwd(x, tg)

        stacked = jax.lax.map(fwd_i, jnp.arange(nc))
        # (nc, ..., chunk) -> (..., nc*chunk)
        parts.append(tuple(jnp.moveaxis(s, 0, -2).reshape(
            s.shape[1:-1] + (nc * chunk,)) for s in stacked))
    if rem:
        parts.append(_chunk_fwd(
            jax.lax.slice_in_dim(logits, nc * chunk, t, axis=ax),
            jax.lax.slice_in_dim(targets, nc * chunk, t, axis=ax)))
    if len(parts) == 1:
        return parts[0]
    return tuple(jnp.concatenate(ps, axis=-1) for ps in zip(*parts, strict=True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _chunked_logprob_vjp(logits, targets, chunk):
    logp, ent, _ = _chunked_fwd_pass(logits, targets, chunk)
    return logp, ent


def _chunked_fwd_rule(logits, targets, chunk):
    logp, ent, lse = _chunked_fwd_pass(logits, targets, chunk)
    return (logp, ent), (logits, targets, lse, lse - ent)


def _chunked_bwd_rule(chunk, res, cots):
    logits, targets, lse, mu = res
    g_lp, g_ent = cots
    ax = logits.ndim - 2
    t = logits.shape[ax]
    nc, rem = divmod(t, chunk)

    def d_slice(start, size):
        x = jax.lax.dynamic_slice_in_dim(logits, start, size, axis=ax)
        args = [jax.lax.dynamic_slice_in_dim(a, start, size, axis=ax)
                for a in (targets, lse, mu, g_lp, g_ent)]
        return _chunk_bwd(x, *args)

    # one primal-shaped output buffer carried through the scan and
    # updated in place (XLA aliases while-loop carries) — never a
    # stacked (nc, ..., chunk, V) copy + concat
    dlogits = jnp.zeros(logits.shape, logits.dtype)
    if nc == 1:
        dlogits = jax.lax.dynamic_update_slice_in_dim(
            dlogits, d_slice(0, chunk), 0, axis=ax)
    elif nc:
        def body(dl, i):
            return jax.lax.dynamic_update_slice_in_dim(
                dl, d_slice(i * chunk, chunk), i * chunk, axis=ax), None

        dlogits, _ = jax.lax.scan(body, dlogits, jnp.arange(nc))
    if rem:
        dlogits = jax.lax.dynamic_update_slice_in_dim(
            dlogits, d_slice(nc * chunk, rem), nc * chunk, axis=ax)
    return dlogits, np.zeros(targets.shape, jax.dtypes.float0)


_chunked_logprob_vjp.defvjp(_chunked_fwd_rule, _chunked_bwd_rule)


def chunked_logprob(logits: jax.Array, targets: jax.Array, *,
                    chunk: int = 256) -> Tuple[jax.Array, jax.Array]:
    """Portable twin of ``fused_logprob``: logits (..., T, V), targets
    (..., T) -> (logp, entropy), f32, any backend / any shape. The token
    axis is chunked in place (leading batch axes keep their sharding);
    peak live f32 is O(batch·chunk·V) in forward *and* backward (the
    custom VJP recomputes softmax per chunk from the saved O(tokens)
    ``lse`` residual)."""
    from repro.core.logprob import clamp_target_ids
    tgt = clamp_target_ids(targets, logits.shape[-1])
    return _chunked_logprob_vjp(logits, tgt,
                                min(chunk, logits.shape[-2]))
