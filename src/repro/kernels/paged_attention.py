"""Paged-attention decode + chunked-prefill Pallas TPU kernels (+ twins).

One decode step of the continuous-batching engine attends a single query
token per slot against that slot's KV pages *in place* — the pools from
``repro.sampling.paged_cache`` are never regathered into a dense
``(B, pages_per_slot·page_size, Hkv, D)`` logical view (the legacy path's
O(pool) HBM traffic per token; see ``repro.kernels.ops.paged_decode``).

Pool layout: ``(num_pages, Hkv, page_size, D)``. One page of all kv
heads is then one contiguous block (the decode kernel's DMA), and one kv
head of one page a contiguous ``(page_size, D)`` tile (the prefill
kernel's block) — tile-aligned on TPU, where a ``(page_size, 1, D)``
slice of a ``(page_size, Hkv, D)`` page is not.

Decode kernel layout (``paged_attention``):

- grid ``(slot,)``, run in order: one instance handles all Hkv heads and
  their ``rep`` grouped query heads against one copy of each page;
- the pools stay in HBM (``memory_space=pl.ANY``). A slot's pages are
  copied ``PAGES_PER_BLOCK`` at a time, one page of all kv heads per
  DMA, into a double-buffered VMEM scratch: block i + 1 is in flight
  while block i is computed, and a slot's last block starts the next
  live slot's first;
- the flattened block table, per-slot live lengths and each slot's
  next live slot ride in as scalar-prefetch operands
  (``pltpu.PrefetchScalarGridSpec``); the block loop runs over the
  slot's live blocks only, so bytes and FLOPs scale with the slot's
  true context length, not the table width. A dead slot (length 0, or
  past the table's reach, where the engine parks a finished slot)
  costs one empty grid step and returns zeros;
- masking matches ``repro.models.attention.decode_attention``: key
  positions ``idx <= pos`` (with ``pos = lengths - 1``), plus the
  sliding-window band (blocks wholly before it are skipped) and
  attention-logit softcap. In the block holding the slot's last token
  masked positions are also zeroed in ``v`` (not just NEG_INF'd in the
  scores), so garbage in dead page tails or stale scratch rows — even
  NaNs — can never reach a live slot's output.

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
# pages the decode kernel copies per block: 128 tokens of every kv head
PAGES_PER_BLOCK = 8


def _live_slots(lengths, width: int, page_size: int):
    """The decode kernel's per-slot operands: each slot's live length
    (0 for the dead-slot marker, a length past the table's reach
    ``width·page_size``) and ``nxt`` (B + 1,): ``nxt[0]`` is the first
    slot with a live token and ``nxt[s + 1]`` the next one after slot
    ``s`` (B when there is none)."""
    b = lengths.shape[0]
    live = jnp.where(lengths > width * page_size, 0, lengths)
    has = jnp.where(live > 0, jnp.arange(b, dtype=jnp.int32), b)
    after = jax.lax.cummin(has, reverse=True)            # min over s' >= s
    return live, jnp.concatenate([after, jnp.full((1,), b, jnp.int32)])


def _decode_kernel(table_ref, lengths_ref, nxt_ref, q_ref, kp_ref, vp_ref,
                   o_ref, kbuf, vbuf, sems, buf_ref,
                   m_scr, l_scr, acc_scr, *, scale: float,
                   window: Optional[int], softcap: Optional[float],
                   page_size: int, width: int, ppb: int, num_slots: int):
    s_id = pl.program_id(0)
    hkv = kbuf.shape[2]
    tokens = ppb * page_size

    def first_block(slot):
        """The first block a slot's band reaches (its window's floor)."""
        if window is None:
            return 0
        return jnp.maximum(lengths_ref[slot] - window, 0) // tokens

    def block_copies(slot, blk, buf):
        """(live?, K copy, V copy) of each page of ``slot``'s block
        ``blk`` into buffer ``buf``. Pages past the slot's length are not
        copied: their buffer rows keep whatever they held, and the tail
        block masks them."""
        n_pages = (lengths_ref[slot] + page_size - 1) // page_size
        out = []
        for i in range(ppb):
            j = blk * ppb + i
            page = table_ref[slot * width + jnp.minimum(j, width - 1)]
            out.append((j < n_pages,
                        pltpu.make_async_copy(kp_ref.at[page], kbuf.at[buf, i],
                                              sems.at[0, buf]),
                        pltpu.make_async_copy(vp_ref.at[page], vbuf.at[buf, i],
                                              sems.at[1, buf])))
        return out

    def start(slot, blk, buf):
        for live, kc, vc in block_copies(slot, blk, buf):
            @pl.when(live)
            def _():
                kc.start()
                vc.start()

    def wait(slot, blk, buf):
        for live, kc, vc in block_copies(slot, blk, buf):
            @pl.when(live)
            def _():
                kc.wait()
                vc.wait()

    # the first live slot's first block; every later slot's first block
    # is started by the previous live slot's last block (below)
    @pl.when(s_id == 0)
    def _prologue():
        buf_ref[0] = 0
        first_slot = nxt_ref[0]

        @pl.when(first_slot < num_slots)
        def _():
            start(first_slot, first_block(first_slot), 0)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    length = lengths_ref[s_id]
    n_blk = (length + tokens - 1) // tokens
    nxt = nxt_ref[s_id + 1]

    def attend(blk, tail: bool):
        """Online softmax over block ``blk`` for every kv head, with the
        slot's next block (or, from its tail block, the next live slot's
        first block) in flight meanwhile. Only the tail block reaches
        positions >= length: there masked scores are NEG_INF and masked
        values zero, so stale or garbage rows (even NaN) never reach the
        output. A block before it needs a mask only for the window."""
        buf = buf_ref[0]
        if not tail:
            start(s_id, blk + 1, 1 - buf)
        else:
            @pl.when(nxt < num_slots)
            def _():
                start(nxt, first_block(nxt), 1 - buf)

        wait(s_id, blk, buf)
        masked = tail or window is not None
        if masked:
            cols = blk * tokens + jax.lax.broadcasted_iota(
                jnp.int32, (1, tokens), 1)
            ok = cols < length if tail else cols > length - 1 - window
            if tail and window is not None:
                ok &= cols > length - 1 - window
        for h in range(hkv):
            q = q_ref[h]                                  # (rep, D)
            k = kbuf[buf, :, h].reshape(tokens, -1)       # (T, D)
            v = vbuf[buf, :, h].reshape(tokens, -1)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale                                 # (rep, T)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            if masked:
                s = jnp.where(ok, s, NEG_INF)
            if tail:
                rows = blk * tokens + jax.lax.broadcasted_iota(
                    jnp.int32, (tokens, 1), 0)
                v = jnp.where(rows < length, v, jnp.zeros((), v.dtype))
            m_prev = m_scr[h]                             # (rep, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_scr[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new
        buf_ref[0] = 1 - buf

    def body(blk, carry):
        attend(blk, tail=False)
        return carry

    first = first_block(s_id)
    jax.lax.fori_loop(first, jnp.maximum(n_blk - 1, first), body, 0)

    @pl.when(n_blk > first)
    def _tail():
        attend(n_blk - 1, tail=True)

    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q: jax.Array, kp: jax.Array, vp: jax.Array,
                    page_table: jax.Array, lengths: jax.Array, *,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """Decode-step attention against paged KV pools, in place.

    q (B, Hq, D) single query token per slot; kp/vp
    (num_pages, Hkv, page_size, D) page pools; page_table (B, W) int32
    slot→physical-page map; lengths (B,) int32 valid tokens per slot
    (``pos + 1`` — the current token's k/v must already be scattered
    into the pools). A length past the table's reach (``> W·page_size``,
    where the engine parks a finished slot) marks a dead slot: it reads
    no page and returns zeros, as does a length of 0. Returns
    (B, Hq, D) in q.dtype.

    One grid step per slot handles all Hkv heads and their ``rep``
    query heads. The pools stay in HBM (``memory_space=pl.ANY``); the
    slot's live pages are copied ``PAGES_PER_BLOCK`` at a time into a
    double-buffered VMEM scratch, block i + 1 in flight while block i is
    computed, and the next live slot's first block in flight during
    this slot's last. The block loop runs over the slot's live blocks
    only, so a dead slot costs one empty grid step.
    """
    b, hq, d = q.shape
    num_pages, hkv, page_size, dk = kp.shape
    assert d == dk and hq % hkv == 0, (q.shape, kp.shape)
    rep = hq // hkv
    width = page_table.shape[1]
    ppb = min(PAGES_PER_BLOCK, width)
    live, nxt = _live_slots(lengths.astype(jnp.int32), width, page_size)
    q_block = pl.BlockSpec((None, hkv, rep, d), lambda s, *_: (s, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[q_block, pool, pool],
        out_specs=q_block,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, hkv, page_size, d), kp.dtype),
            pltpu.VMEM((2, ppb, hkv, page_size, d), vp.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hkv, rep, 1), jnp.float32),
            pltpu.VMEM((hkv, rep, 1), jnp.float32),
            pltpu.VMEM((hkv, rep, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=d ** -0.5, window=window,
                          softcap=softcap, page_size=page_size, width=width,
                          ppb=ppb, num_slots=b),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        # a slot's last block starts the next slot's first: the grid
        # runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode",
    )(page_table.astype(jnp.int32).reshape(-1), live, nxt,
      q.reshape(b, hkv, rep, d).astype(kp.dtype), kp, vp)
    return out.reshape(b, hq, d)


def paged_decode_ref(q: jax.Array, kp: jax.Array, vp: jax.Array,
                     page_table: jax.Array, lengths: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None) -> jax.Array:
    """Pure-JAX twin of ``paged_attention``: ``fori_loop`` over logical
    pages with running (m, l, acc), bounded by the batch-max live page
    count so work scales with occupancy. Same shapes/semantics as the
    kernel, dead-slot marker included (zeros); this is the CPU oracle
    and the GSPMD-native lowering path (the per-page gather partitions
    cleanly with kv-heads on 'model')."""
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
    # the kernel's dead-slot marker: a length past the table reads nothing
    lengths = jnp.where(lengths > npages * page_size, 0, lengths)

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
