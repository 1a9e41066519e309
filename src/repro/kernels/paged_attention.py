"""Paged-attention decode + chunked-prefill Pallas TPU kernels (+ twins).

One decode step of the continuous-batching engine attends a single query
token per slot against that slot's KV pages *in place* — the pools from
``repro.sampling.paged_cache`` are never regathered into a dense
``(B, pages_per_slot·page_size, Hkv, D)`` logical view (the legacy path's
O(pool) HBM traffic per token; see ``repro.kernels.ops.paged_decode``).

Pool layout: ``(num_pages, Hkv, page_size, D)``. One kv head of one page
is then a contiguous ``(page_size, D)`` tile, the block the kernels DMA —
tile-aligned on TPU, where a ``(page_size, 1, D)`` slice of a
``(page_size, Hkv, D)`` page is not.

Kernel layout:

- grid ``(slot, kv_head, logical_page)`` with the page axis innermost so
  the online-softmax accumulators (m, l, acc) live in VMEM scratch across
  page iterations — the flash-attention recurrence over pages;
- the block table and per-slot ``lengths`` ride in as scalar-prefetch
  operands (``pltpu.PrefetchScalarGridSpec``), so the kv BlockSpec index
  map resolves ``table[slot, j]`` to a physical page id before each grid
  step issues its DMA;
- pages at or past ``ceil(lengths[slot]/page_size)`` are *dead*: their
  index map re-points at the slot's last live page (same block index ⇒
  Pallas skips the copy — no DMA, and ``pl.when`` skips the compute), so
  bytes and FLOPs scale with the slot's true context length, not the
  allocator's ``pages_per_slot`` capacity;
- GQA is resolved in the index maps: all ``rep = Hq // Hkv`` query heads
  of one kv head run in a single kernel instance against one page fetch;
- masking matches ``repro.models.attention.decode_attention``: key
  positions ``idx <= pos`` (with ``pos = lengths - 1``), plus the
  sliding-window band and attention-logit softcap. Masked positions are
  zeroed in ``v`` (not just NEG_INF'd in the scores) so garbage in dead
  page tails — scratch-page contents included, even NaNs — can never
  reach a live slot's output.

``paged_decode_ref`` is the jnp twin (``lax.fori_loop`` over live pages
with running (m, l, acc)): the CPU oracle and the lowering path, the same
pairing as ``chunked_attention`` ↔ ``flash_attention``. Its loop bound is
the *batch-max* live page count, so its bytes also scale with occupancy
rather than pool capacity.

``paged_prefill`` extends the same layout to a whole prefill *chunk*: a
(B, C, Hq, D) block of queries per slot starting at per-slot offset
``c0 = starts[slot]`` (query row i sits at absolute position c0 + i and
attends kv positions ≤ c0 + i). Grid ``(slot, q_tile, kv_head, page)``;
the block table plus per-slot ``lengths`` *and* ``starts`` ride in as
scalar-prefetch operands so the kv index map can clamp the logical page
to the tile's causal reach — pages past ``(c0 + tile_end) // page_size``
(and, with a sliding window, before the tile's window floor) re-point at
the nearest reachable page, so bytes/chunk scale with
``pages_for(c0 + C)`` rather than the table width the caller padded to.
``paged_prefill_ref`` is its ``fori_loop`` jnp twin, same contract.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask_scores_and_values(s, v, j, page_size, length, window):
    """Apply the decode validity band to one page block.

    s (R, page) scores, v (page, D) values; returns masked (s, v) where
    invalid key positions are NEG_INF in s and *zero* in v — the zeroing
    is what keeps NaN/garbage in unwritten page tails out of ``p @ v``.
    """
    def band(col):
        ok = col < length
        if window is not None:
            ok &= col > length - 1 - window
        return ok

    cols_s = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    cols_v = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (page_size, 1), 0)
    s = jnp.where(band(cols_s), s, NEG_INF)
    v = jnp.where(band(cols_v), v, 0.0)
    return s, v


def _kernel(table_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, window: Optional[int],
            softcap: Optional[float], page_size: int, npages: int):
    s_id = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[s_id]
    live = j * page_size < length

    @pl.when(live)
    def _body():
        q = q_ref[...].astype(jnp.float32)                # (rep, D)
        k = k_ref[...].astype(jnp.float32)                # (page, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                     # (rep, page)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s, v = _mask_scores_and_values(s, v, j, page_size, length, window)

        m_prev = m_scr[...]                               # (rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(j == npages - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                    page_table: jax.Array, lengths: jax.Array, *,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """Decode-step attention against paged KV pools, in place.

    q (B, Hq, D) single query token per slot; kp/vp
    (num_pages, Hkv, page_size, D) page pools; page_table (B, npages)
    int32 slot→physical-page map; lengths (B,) int32 valid tokens per
    slot (``pos + 1`` — the current token's k/v must already be
    scattered into the pools). Returns (B, Hq, D) in q.dtype.
    """
    b, hq, d = q.shape
    num_pages, hkv, page_size, dk = kp.shape
    assert d == dk and hq % hkv == 0, (q.shape, kp.shape)
    rep = hq // hkv
    npages = page_table.shape[1]
    qr = q.reshape(b, hkv, rep, d)

    def q_map(s, h, j, table_ref, lengths_ref):
        del table_ref, lengths_ref, j
        return (s, h, 0, 0)

    def kv_map(s, h, j, table_ref, lengths_ref):
        # dead pages re-point at the slot's last live page: identical
        # consecutive block indices make Pallas skip the DMA, and the
        # body's pl.when(live) skips the compute.
        length = lengths_ref[s]
        last_live = jnp.maximum(pl.cdiv(length, page_size) - 1, 0)
        jj = jnp.minimum(j, last_live)
        return (table_ref[s, jj], h, 0, 0)

    q_block = pl.BlockSpec((None, None, rep, d), q_map)
    kv_block = pl.BlockSpec((None, None, page_size, d), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, npages),
        in_specs=[q_block, kv_block, kv_block],
        out_specs=q_block,
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=d ** -0.5, window=window,
                          softcap=softcap, page_size=page_size,
                          npages=npages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), qr, kp, vp)
    return out.reshape(b, hq, d)


def paged_decode_ref(q: jax.Array, kp: jax.Array, vp: jax.Array,
                     page_table: jax.Array, lengths: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None) -> jax.Array:
    """Pure-JAX twin of ``paged_attention``: ``fori_loop`` over logical
    pages with running (m, l, acc), bounded by the batch-max live page
    count so work scales with occupancy. Same shapes/semantics as the
    kernel; this is the CPU oracle and the GSPMD-native lowering path
    (the per-page gather partitions cleanly with kv-heads on 'model')."""
    b, hq, d = q.shape
    hkv, page_size = kp.shape[1], kp.shape[2]
    rep = hq // hkv
    npages = page_table.shape[1]
    scale = d ** -0.5
    # keep every pool-sized operand in the pool dtype and upcast inside
    # the dots (preferred_element_type): an explicit kp.astype(f32) is
    # loop-invariant, so XLA hoists it and converts the *entire pool*
    # once — the O(pool) temp buffer this path exists to avoid.
    qg = q.reshape(b, hkv, rep, d).astype(kp.dtype)
    table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def body(j, carry):
        m_run, l_run, acc = carry
        phys = jax.lax.dynamic_slice_in_dim(table, j, 1, axis=1)[:, 0]
        k = kp[phys]                                      # (B, Hkv, page, D)
        v = vp[phys]
        s = jnp.einsum("bgrd,bgpd->bgrp", qg, k,
                       preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        idx = j * page_size + jnp.arange(page_size)
        valid = idx[None, :] < lengths[:, None]           # (B, page)
        if window is not None:
            valid &= idx[None, :] > lengths[:, None] - 1 - window
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        # zero masked values so garbage/NaN in dead tails (scratch page
        # included) can never reach a live slot through 0 * NaN
        v = jnp.where(valid[:, None, :, None], v, jnp.zeros((), v.dtype))
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrp,bgpd->bgrd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((b, hkv, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep), jnp.float32)
    a0 = jnp.zeros((b, hkv, rep, d), jnp.float32)
    n_live = jnp.clip(-(-jnp.max(lengths) // page_size), 0, npages)
    _, l_f, acc = jax.lax.fori_loop(0, n_live, body, (m0, l0, a0))
    o = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return o.reshape(b, hq, d).astype(q.dtype)


def _prefill_kernel(table_ref, lengths_ref, starts_ref, q_ref, k_ref, v_ref,
                    o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                    window: Optional[int], softcap: Optional[float],
                    page_size: int, npages: int, bq: int, rep: int):
    s_id = pl.program_id(0)
    iq = pl.program_id(1)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[s_id]
    q0 = starts_ref[s_id] + iq * bq                  # tile row 0, absolute
    last_live = jnp.maximum(pl.cdiv(length, page_size) - 1, 0)
    last_reach = jnp.minimum(last_live, (q0 + bq - 1) // page_size)
    if window is not None:
        first_reach = jnp.maximum((q0 - window + 1) // page_size, 0)
    else:
        first_reach = 0
    live = (j >= first_reach) & (j <= last_reach)

    @pl.when(live)
    def _body():
        rows = bq * rep
        q = q_ref[...].astype(jnp.float32)                # (rows, D)
        k = k_ref[...].astype(jnp.float32)                # (page, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                     # (rows, page)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        # row r of the flattened tile is query position q0 + r // rep
        # (the rep grouped heads of one query token are adjacent rows)
        pos_q = q0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // rep
        col = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        ok = col <= pos_q
        if window is not None:
            ok &= col > pos_q - window
        s = jnp.where(ok, s, NEG_INF)
        # zero v past the slot's length so NaN/garbage in the unwritten
        # tail of the last live page can never reach the output via 0·NaN
        col_v = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)
        v = jnp.where(col_v < length, v, 0.0)

        m_prev = m_scr[...]                               # (rows, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(j == npages - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_prefill(q: jax.Array, kp: jax.Array, vp: jax.Array,
                  page_table: jax.Array, lengths: jax.Array,
                  starts: jax.Array, *, window: Optional[int] = None,
                  softcap: Optional[float] = None, block_q: int = 128,
                  interpret: bool = False) -> jax.Array:
    """One chunked-prefill step against paged KV pools, in place.

    q (B, C, Hq, D): a C-token query chunk per slot whose row i sits at
    absolute position ``starts[slot] + i``; kp/vp
    (num_pages, Hkv, page_size, D) page pools with the chunk's k/v
    already scattered in; page_table (B, npages) int32; lengths (B,)
    int32 total valid tokens per slot (``starts + C`` for a full chunk);
    starts (B,) int32 chunk offsets. Returns (B, C, Hq, D) in q.dtype.

    Causality alone keeps padded table width harmless: every query row's
    reach is clamped to its own position, so unreachable pages re-point
    at the nearest reachable one (no DMA) and ``pl.when`` skips their
    compute — bytes scale with ``pages_for(starts + C)``.
    """
    b, c, hq, d = q.shape
    num_pages, hkv, page_size, dk = kp.shape
    assert d == dk and hq % hkv == 0, (q.shape, kp.shape)
    rep = hq // hkv
    npages = page_table.shape[1]
    from repro.kernels.flash_attention import _fit_block
    bq = _fit_block(c, block_q)
    nq = c // bq
    # kv-head-major rows: one grid step's (bq query tokens × rep grouped
    # heads) are bq·rep contiguous rows of a (rows, D) tile
    qr = (q.reshape(b, c, hkv, rep, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, hkv, c * rep, d))

    def q_map(s, iq, h, j, table_ref, lengths_ref, starts_ref):
        del table_ref, lengths_ref, starts_ref, j
        return (s, h, iq, 0)

    def kv_map(s, iq, h, j, table_ref, lengths_ref, starts_ref):
        # clamp the logical page into the tile's causal/window reach:
        # repeated block indices ⇒ Pallas skips the DMA, pl.when skips
        # the compute, so dead/unreachable pages cost nothing.
        length = lengths_ref[s]
        q0 = starts_ref[s] + iq * bq
        last_live = jnp.maximum(pl.cdiv(length, page_size) - 1, 0)
        last = jnp.minimum(last_live, (q0 + bq - 1) // page_size)
        first = jnp.zeros((), jnp.int32)
        if window is not None:
            first = jnp.clip((q0 - window + 1) // page_size, 0, last)
        jj = jnp.clip(j, first, last)
        return (table_ref[s, jj], h, 0, 0)

    q_block = pl.BlockSpec((None, None, bq * rep, d), q_map)
    kv_block = pl.BlockSpec((None, None, page_size, d), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nq, hkv, npages),
        in_specs=[q_block, kv_block, kv_block],
        out_specs=q_block,
        scratch_shapes=[
            pltpu.VMEM((bq * rep, 1), jnp.float32),
            pltpu.VMEM((bq * rep, 1), jnp.float32),
            pltpu.VMEM((bq * rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=d ** -0.5, window=window,
                          softcap=softcap, page_size=page_size,
                          npages=npages, bq=bq, rep=rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, c * rep, d), q.dtype),
        interpret=interpret,
        name="paged_prefill",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      starts.astype(jnp.int32), qr, kp, vp)
    return (out.reshape(b, hkv, c, rep, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, c, hq, d))


def paged_prefill_ref(q: jax.Array, kp: jax.Array, vp: jax.Array,
                      page_table: jax.Array, lengths: jax.Array,
                      starts: jax.Array, *, window: Optional[int] = None,
                      softcap: Optional[float] = None) -> jax.Array:
    """Pure-JAX twin of ``paged_prefill``: ``fori_loop`` over logical
    pages with running (m, l, acc) per query row, bounded by the
    batch-max live page count — no dense (B, npages·page_size, Hkv, D)
    view is ever materialized, so temp bytes scale with live pages."""
    b, c, hq, d = q.shape
    hkv, page_size = kp.shape[1], kp.shape[2]
    rep = hq // hkv
    npages = page_table.shape[1]
    scale = d ** -0.5
    # pool-dtype operands + preferred_element_type dots: an explicit
    # .astype(f32) on kp/vp would be loop-invariant and XLA would hoist
    # a full-pool f32 copy — the exact temp buffer this path avoids.
    qg = q.reshape(b, c, hkv, rep, d).astype(kp.dtype)
    table = page_table.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    pos_q = starts.astype(jnp.int32)[:, None] + jnp.arange(c)     # (B, C)

    def body(j, carry):
        m_run, l_run, acc = carry
        phys = jax.lax.dynamic_slice_in_dim(table, j, 1, axis=1)[:, 0]
        k = kp[phys]                                      # (B, Hkv, page, D)
        v = vp[phys]
        s = jnp.einsum("bcgrd,bgpd->bgrcp", qg, k,
                       preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        idx = j * page_size + jnp.arange(page_size)
        ok = idx[None, None, :] <= pos_q[:, :, None]      # (B, C, page)
        if window is not None:
            ok &= idx[None, None, :] > pos_q[:, :, None] - window
        s = jnp.where(ok[:, None, None], s, NEG_INF)      # (B,g,r,C,page)
        valid = idx[None, :] < lengths[:, None]           # (B, page)
        v = jnp.where(valid[:, None, :, None], v, jnp.zeros((), v.dtype))
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bgrcp,bgpd->bgrcd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((b, hkv, rep, c), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, c), jnp.float32)
    a0 = jnp.zeros((b, hkv, rep, c, d), jnp.float32)
    n_live = jnp.clip(-(-jnp.max(lengths) // page_size), 0, npages)
    _, l_f, acc = jax.lax.fori_loop(0, n_live, body, (m0, l0, a0))
    o = acc / jnp.maximum(l_f, 1e-30)[..., None]          # (B,g,r,C,D)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, c, hq, d).astype(q.dtype)
