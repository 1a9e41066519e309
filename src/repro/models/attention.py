"""Attention: naive, chunked (flash-style online softmax in pure jnp) and
single-token decode paths. GQA is handled with grouped einsums (no kv
materialized repeats). Supports causal, sliding-window and bidirectional
masks plus Gemma-2 attention-logit softcapping.

The chunked path is the default for large shapes: it never materializes the
(Sq, Sk) score matrix, scanning kv blocks with running (m, l, acc) — the
same algorithm the Pallas `flash_attention` kernel implements on TPU (the
kernel is used on real hardware; this path is the lowering/CPU oracle).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30
PAD_POS = 2 ** 30          # sentinel position marking padded keys


def _split_heads(x: jax.Array, n: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n, -1)


def _mask(pos_q, pos_k, kind: str, window: int):
    """(..., Sq, Sk) boolean mask. kind: causal | local | bidir.
    Keys at the PAD_POS sentinel are masked in every kind."""
    valid = (pos_k < PAD_POS)[..., None, :]
    d = pos_q[..., :, None] - pos_k[..., None, :]
    if kind == "causal":
        return (d >= 0) & valid
    if kind == "local":
        return (d >= 0) & (d < window) & valid
    if kind == "bidir":
        return jnp.broadcast_to(valid, d.shape)
    raise ValueError(kind)


def _scores(q, k, cap: Optional[float]):
    """q (B,Sq,G,R,D), k (B,Sk,G,D) -> (B,G,R,Sq,Sk), pre-softmax."""
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s * (q.shape[-1] ** -0.5)
    if cap is not None:
        s = cap * jnp.tanh(s / cap)
    return s


def naive_attention(q, k, v, *, pos_q, pos_k, kind="causal", window=4096,
                    softcap=None):
    """Reference O(Sq*Sk) attention. q (B,Sq,Hq,D); k,v (B,Sk,Hkv,D)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g, r = hkv, hq // hkv
    qg = q.reshape(b, sq, g, r, d)
    s = _scores(qg, k, softcap)                              # (B,G,R,Sq,Sk)
    m = _mask(pos_q, pos_k, kind, window)[:, None, None]     # (B,1,1,Sq,Sk)
    s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, hq, d).astype(q.dtype)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, pos_q, pos_k, kind, window, softcap, q_chunk, kv_chunk):
    o, _ = _flash_fwd_impl(q, k, v, pos_q, pos_k, kind, window, softcap,
                           q_chunk, kv_chunk)
    return o


def _flash_fwd_impl(q, k, v, pos_q, pos_k, kind, window, softcap,
                    q_chunk, kv_chunk):
    o, lse = _chunked_fwd(q, k, v, pos_q=pos_q, pos_k=pos_k, kind=kind,
                          window=window, softcap=softcap, q_chunk=q_chunk,
                          kv_chunk=kv_chunk)
    return o, lse


def _flash_vjp_fwd(q, k, v, pos_q, pos_k, kind, window, softcap, q_chunk,
                   kv_chunk):
    o, lse = _flash_fwd_impl(q, k, v, pos_q, pos_k, kind, window, softcap,
                             q_chunk, kv_chunk)
    return o, (q, k, v, pos_q, pos_k, o, lse)


def _flash_vjp_bwd(kind, window, softcap, q_chunk, kv_chunk, res, do):
    q, k, v, pos_q, pos_k, o, lse = res
    dq, dk, dv = _chunked_bwd(q, k, v, pos_q, pos_k, o, lse, do,
                              kind=kind, window=window, softcap=softcap,
                              q_chunk=q_chunk, kv_chunk=kv_chunk)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _pad_blocks(q, k, v, pos_q, pos_k, q_chunk, kv_chunk):
    sq, sk = q.shape[1], k.shape[1]
    pad_q = (-sq) % q_chunk
    pad_k = (-sk) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        pos_q = jnp.pad(pos_q, ((0, 0), (0, pad_q)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        pos_k = jnp.pad(pos_k, ((0, 0), (0, pad_k)), constant_values=PAD_POS)
    return q, k, v, pos_q, pos_k


def _block_scores(qi, ki, pq, pk, kind, window, softcap):
    """Masked pre-softmax scores for one (q-block, kv-block) pair.
    qi (B,qc,G,R,D), ki (B,kc,G,D) -> (B,G,R,qc,kc)."""
    s = _scores(qi, ki, softcap)
    msk = _mask(pq, pk, kind, window)[:, None, None]
    return jnp.where(msk, s, NEG_INF), msk


def _chunked_fwd(q, k, v, *, pos_q, pos_k, kind, window, softcap,
                 q_chunk, kv_chunk):
    """Returns (o, lse) — lse (B,G,R,Sq) saved for the flash backward."""
    b, sq_orig, hq, d = q.shape
    hkv = k.shape[2]
    g, r = hkv, hq // hkv
    q, k, v, pos_q, pos_k = _pad_blocks(q, k, v, pos_q, pos_k, q_chunk,
                                        kv_chunk)
    sq, sk = q.shape[1], k.shape[1]
    nq, nk = sq // q_chunk, sk // kv_chunk

    qb = q.reshape(b, nq, q_chunk, g, r, d).transpose(1, 0, 2, 3, 4, 5)
    pqb = pos_q.reshape(b, nq, q_chunk).transpose(1, 0, 2)
    kb = k.reshape(b, nk, kv_chunk, g, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, kv_chunk, g, d).transpose(1, 0, 2, 3, 4)
    pkb = pos_k.reshape(b, nk, kv_chunk).transpose(1, 0, 2)

    def q_block(qi_pq):
        qi, pq = qi_pq

        def kv_block(carry, kv):
            m_run, l_run, acc = carry
            ki, vi, pk = kv
            s, _ = _block_scores(qi, ki, pq, pk, kind, window, softcap)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            scale = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * scale + p.sum(axis=-1)
            acc = acc * scale[..., None] + jnp.einsum(
                "bgrqk,bkgd->bgrqd", p, vi.astype(jnp.float32))
            return (m_new, l_new, acc), None

        qc = qi.shape[1]
        m0 = jnp.full((b, g, r, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, g, r, qc), jnp.float32)
        a0 = jnp.zeros((b, g, r, qc, d), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0),
                                          (kb, vb, pkb))
        l_safe = jnp.maximum(l_f, 1e-30)
        o = acc / l_safe[..., None]
        lse = m_f + jnp.log(l_safe)
        return o.transpose(0, 3, 1, 2, 4), lse               # (B,qc,G,R,D)

    o, lse = jax.lax.map(q_block, (qb, pqb))
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hq, d)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(b, g, r, sq)
    return o[:, :sq_orig].astype(q.dtype), lse[..., :sq_orig]


def _chunked_bwd(q, k, v, pos_q, pos_k, o, lse, do, *, kind, window,
                 softcap, q_chunk, kv_chunk):
    """Flash backward: recompute scores blockwise; nothing O(Sq·Sk) is ever
    materialized. Two passes: kv-major for (dk, dv), q-major for dq."""
    b, sq_orig, hq, d = q.shape
    sk_orig, hkv = k.shape[1], k.shape[2]
    g, r = hkv, hq // hkv
    q, k, v, pos_q, pos_k = _pad_blocks(q, k, v, pos_q, pos_k, q_chunk,
                                        kv_chunk)
    sq, sk = q.shape[1], k.shape[1]
    nq, nk = sq // q_chunk, sk // kv_chunk
    if sq != sq_orig:
        o = jnp.pad(o, ((0, 0), (0, sq - sq_orig), (0, 0), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, sq - sq_orig), (0, 0), (0, 0)))
        lse = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, sq - sq_orig)))

    f32 = jnp.float32
    qb = q.reshape(b, nq, q_chunk, g, r, d).transpose(1, 0, 2, 3, 4, 5)
    pqb = pos_q.reshape(b, nq, q_chunk).transpose(1, 0, 2)
    kb = k.reshape(b, nk, kv_chunk, g, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, kv_chunk, g, d).transpose(1, 0, 2, 3, 4)
    pkb = pos_k.reshape(b, nk, kv_chunk).transpose(1, 0, 2)
    dob = do.astype(f32).reshape(b, nq, q_chunk, g, r, d
                                 ).transpose(1, 0, 2, 3, 4, 5)
    lseb = lse.reshape(b, g, r, nq, q_chunk).transpose(3, 0, 1, 2, 4)
    # D_i = sum_d dO_id O_id   (nq, B, G, R, qc)
    ob = o.astype(f32).reshape(b, nq, q_chunk, g, r, d
                               ).transpose(1, 0, 2, 3, 4, 5)
    db = (dob * ob).sum(-1).transpose(0, 1, 3, 4, 2)

    scale = d ** -0.5

    def p_and_dsraw(qi, ki, pq, pk, lse_i):
        """p (B,G,R,qc,kc) and raw-score derivative chain."""
        u = jnp.einsum("bqgrd,bkgd->bgrqk", qi.astype(f32),
                       ki.astype(f32)) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(u / softcap)
            dchain = 1.0 - (s / softcap) ** 2
        else:
            s = u
            dchain = jnp.ones_like(s)
        msk = _mask(pq, pk, kind, window)[:, None, None]
        p = jnp.where(msk, jnp.exp(s - lse_i[..., None]), 0.0)
        return p, dchain

    # ---- pass 1: dq (scan kv blocks per q block) -------------------------
    def q_major(args):
        qi, pq, lse_i, do_i, d_i = args

        def kv_step(dq_acc, kv):
            ki, vi, pk = kv
            p, dchain = p_and_dsraw(qi, ki, pq, pk, lse_i)
            dp = jnp.einsum("bqgrd,bkgd->bgrqk", do_i, vi.astype(f32))
            ds = p * (dp - d_i[..., None]) * dchain
            dq_acc += jnp.einsum("bgrqk,bkgd->bqgrd", ds,
                                 ki.astype(f32)) * scale
            return dq_acc, None

        dq0 = jnp.zeros((b, q_chunk, g, r, d), f32)
        dq_i, _ = jax.lax.scan(kv_step, dq0, (kb, vb, pkb))
        return dq_i

    dq = jax.lax.map(q_major, (qb, pqb, lseb, dob, db))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hq, d)

    # ---- pass 2: dk, dv (scan q blocks per kv block) ---------------------
    def kv_major(args):
        ki, vi, pk = args

        def q_step(carry, qs):
            dk_acc, dv_acc = carry
            qi, pq, lse_i, do_i, d_i = qs
            p, dchain = p_and_dsraw(qi, ki, pq, pk, lse_i)
            dv_acc += jnp.einsum("bgrqk,bqgrd->bkgd", p, do_i)
            dp = jnp.einsum("bqgrd,bkgd->bgrqk", do_i, vi.astype(f32))
            ds = p * (dp - d_i[..., None]) * dchain
            dk_acc += jnp.einsum("bgrqk,bqgrd->bkgd", ds,
                                 qi.astype(f32)) * scale
            return (dk_acc, dv_acc), None

        z = jnp.zeros((b, kv_chunk, g, d), f32)
        (dk_i, dv_i), _ = jax.lax.scan(q_step, (z, z),
                                       (qb, pqb, lseb, dob, db))
        return dk_i, dv_i

    dk, dv = jax.lax.map(kv_major, (kb, vb, pkb))
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(b, sk, hkv, d)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(b, sk, hkv, d)
    return (dq[:, :sq_orig].astype(q.dtype),
            dk[:, :sk_orig].astype(k.dtype),
            dv[:, :sk_orig].astype(v.dtype))


def chunked_attention(q, k, v, *, pos_q, pos_k, kind="causal", window=4096,
                      softcap=None, q_chunk=512, kv_chunk=512):
    """Flash-style attention with a flash *backward* (custom VJP): neither
    direction materializes the (Sq, Sk) score matrix, and — critically for
    training memory — autodiff never sees the online-softmax scan, so no
    O(Sq·Sk) scan residuals are saved. This is the jnp twin of the Pallas
    ``flash_attention`` kernel."""
    return _flash(q, k, v, pos_q, pos_k, kind, window, softcap,
                  min(q_chunk, q.shape[1]), min(kv_chunk, k.shape[1]))


def decode_attention(q, k_cache, v_cache, *, pos, kind="causal",
                     window=4096, softcap=None, length=None):
    """Single-token attention against a (B, Smax, Hkv, D) cache.

    q: (B, 1, Hq, D); pos: current position — a scalar, or a (B,) vector
    when rows decode at heterogeneous positions (continuous batching).
    Entries > pos are masked. ``length`` (static int) is an optional
    upper bound on ``pos + 1``: entries at ``>= length`` are provably
    masked, so the cache is sliced to ``length`` and the score/mask/
    softmax work on the padded tail is skipped entirely — bit-identical
    output (masked tail entries contribute exact zeros either way).
    """
    b, _, hq, d = q.shape
    if length is not None and length < k_cache.shape[1]:
        k_cache = k_cache[:, :length]
        v_cache = v_cache[:, :length]
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    g, r = hkv, hq // hkv
    qg = q.reshape(b, 1, g, r, d)
    s = _scores(qg, k_cache, softcap)[:, :, :, 0]          # (B,G,R,Smax)
    idx = jnp.arange(smax)
    posv = jnp.reshape(jnp.asarray(pos), (-1, 1))          # (1|B, 1)
    valid = idx[None, :] <= posv
    if kind == "local":
        valid &= idx[None, :] > posv - window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, hq, d).astype(q.dtype)


def ring_decode_attention(q, k_ring, v_ring, *, pos, window,
                          softcap=None):
    """Single-token attention against a ring-buffered local-window cache.

    k_ring/v_ring: (B, W, Hkv, D) where slot s holds the key of position
    p = pos − ((pos − s) mod W) (the unique p ≡ s (mod W) in
    (pos−W, pos]); entries with p < 0 have not been written yet.
    """
    b, _, hq, d = q.shape
    w, hkv = k_ring.shape[1], k_ring.shape[2]
    g, r = hkv, hq // hkv
    qg = q.reshape(b, 1, g, r, d)
    s = _scores(qg, k_ring, softcap)[:, :, :, 0]             # (B,G,R,W)
    slot = jnp.arange(w)
    p = pos - jnp.mod(pos - slot, w)                         # slot position
    valid = p >= 0
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", prob, v_ring.astype(jnp.float32))
    return o.reshape(b, 1, hq, d).astype(q.dtype)


def fill_ring(k: jax.Array, window: int) -> jax.Array:
    """Arrange the last `window` entries of k (B,S,H,D) into ring order
    (slot s = position p with p ≡ s mod window). For S < window the tail
    slots stay zero (masked via the position-recovery rule)."""
    b, s_len = k.shape[0], k.shape[1]
    w = window
    if s_len < w:
        pad = jnp.zeros((b, w - s_len) + k.shape[2:], k.dtype)
        return jnp.concatenate([k, pad], axis=1)
    k_last = k[:, s_len - w:]
    idx = jnp.mod(jnp.arange(w) - (s_len % w), w)
    return jnp.take(k_last, idx, axis=1)


def _standard_positions(pos) -> bool:
    """Concrete positions must be the contiguous arange layout the flash
    kernel's offset-derived masks assume; traced positions cannot be
    inspected and are trusted (the documented ``impl="pallas"`` caveat —
    in-repo jit callers guarantee it, the paged-prefill offset path
    downgrades explicitly)."""
    if isinstance(pos, jax.core.Tracer):
        return True
    arr = jnp.asarray(pos)
    return bool((arr == jnp.arange(arr.shape[-1])).all())


def attention(q, k, v, *, pos_q, pos_k, kind="causal", window=4096,
              softcap=None, impl="chunked", chunk=512):
    """Full-sequence attention dispatch: naive | chunked | pallas.

    ``impl="pallas"`` routes to the Mosaic flash kernel (interpret mode
    off-TPU). The kernel derives its masks from absolute block offsets,
    so it assumes the standard contiguous layout ``pos_q = arange(Sq)``,
    ``pos_k = arange(Sk)`` with no PAD_POS sentinels — the model's
    training/prefill forward. Concrete (eager) positions are checked and
    quietly fall back to the jnp paths when they don't match; callers
    under jit with offset or padded positions (paged chunked prefill)
    must pick a jnp impl themselves.
    """
    if (impl == "pallas" and kind in ("causal", "local", "bidir")
            and _standard_positions(pos_q) and _standard_positions(pos_k)):
        from repro.kernels.ops import flash_attention
        return flash_attention(q, k, v, causal=(kind != "bidir"),
                               window=(window if kind == "local" else None),
                               softcap=softcap,
                               block_q=min(chunk, 128), block_k=min(chunk, 128))
    if impl == "naive" or q.shape[1] <= chunk:
        return naive_attention(q, k, v, pos_q=pos_q, pos_k=pos_k, kind=kind,
                               window=window, softcap=softcap)
    return chunked_attention(q, k, v, pos_q=pos_q, pos_k=pos_k, kind=kind,
                             window=window, softcap=softcap,
                             q_chunk=chunk, kv_chunk=chunk)
