"""Jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile via Mosaic; elsewhere they execute in
interpret mode for correctness validation. ``on_tpu()`` decides which
backend the "auto" dispatchers pick.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_logprob import (chunked_logprob as _chunked_logprob,
                                         fused_logprob as _fused_logprob)
from repro.kernels.paged_attention import (paged_attention as _paged,
                                           paged_decode_ref as _paged_ref,
                                           paged_prefill as _paged_prefill,
                                           paged_prefill_ref as
                                           _paged_prefill_ref)
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    interp = (not on_tpu()) if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  block_q=block_q, block_k=block_k, interpret=interp)


PAGED_IMPLS = ("auto", "pallas", "ref", "gather")


def _logical_view(kp, vp, page_table):
    """Each slot's dense (B, npages·page_size, Hkv, D) k/v view, gathered
    from (num_pages, Hkv, page_size, D) pools through its block table."""
    b, npages = page_table.shape
    hkv, page_size, d = kp.shape[1:]

    def view(pool):
        return (pool[page_table].transpose(0, 1, 3, 2, 4)
                .reshape(b, npages * page_size, hkv, d))
    return view(kp), view(vp)


@functools.partial(jax.jit, static_argnames=("kind", "window", "softcap",
                                             "impl", "interpret"))
def paged_decode(q, kp, vp, page_table, lengths, *, kind: str = "causal",
                 window: Optional[int] = None,
                 softcap: Optional[float] = None,
                 impl: Optional[str] = None,
                 interpret: Optional[bool] = None):
    """Decode-step attention against paged KV pools — the serving hot loop.

    q (B, 1, Hq, D) one query token per slot (``decode_attention``'s
    layout); kp/vp (num_pages, Hkv, page_size, D) page pools; page_table
    (B, npages); lengths (B,) valid tokens per slot (current token's k/v
    already scattered). Returns (B, 1, Hq, D). From the model's block
    scan, kp/vp are every layer's stacked pools folded to (L·P, ...) and
    page_table points into the layer's slab (``layer_slab``).

    ``impl`` selects the backend (``ModelConfig.paged_attn_impl``):
      - "gather": materialize the logical (B, npages·page_size, Hkv, D)
        view and run ``decode_attention`` over it — bit-identical to the
        dense decode path (the static ≡ continuous engine parity
        contract), O(slots · npages) bytes/token. The engine narrows
        ``page_table`` to the live high-water mark before calling.
      - "ref": ``paged_decode_ref`` — per-page online softmax, no
        materialized view, GSPMD-native (kv-heads shard over 'model').
      - "pallas": the Mosaic kernel: each slot's live pages DMA'd in
        place, nothing read for a dead slot (length 0, or past the
        table's reach). pallas_call has no GSPMD partitioning rules: on
        a multi-device mesh call it under shard_map with kv-heads (and
        the grouped q heads) split over 'model' — same caveat as
        ``fused_token_logprob``; the engine keeps "auto" off it there.
      - None / "auto" (the ModelConfig default): pallas on a TPU,
        gather elsewhere.

    ``kind``/``window`` follow ``decode_attention``: the sliding-window
    band applies only when kind == "local".
    """
    if impl not in PAGED_IMPLS + (None,):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    if impl in (None, "auto"):
        impl = "pallas" if on_tpu() else "gather"
    if kind not in ("causal", "local"):
        raise ValueError(f"paged decode is causal-only, got kind={kind!r}")
    eff_window = window if kind == "local" else None
    if impl == "gather":
        from repro.models.attention import decode_attention
        kc, vc = _logical_view(kp, vp, page_table)
        return decode_attention(q, kc, vc, pos=lengths - 1, kind=kind,
                                window=window, softcap=softcap)
    if impl == "ref":
        o = _paged_ref(q[:, 0], kp, vp, page_table, lengths,
                       window=eff_window, softcap=softcap)
    else:
        interp = (not on_tpu()) if interpret is None else interpret
        o = _paged(q[:, 0], kp, vp, page_table, lengths,
                   window=eff_window, softcap=softcap, interpret=interp)
    return o[:, None]


@functools.partial(jax.jit, static_argnames=("kind", "window", "softcap",
                                             "impl", "attn_impl", "chunk",
                                             "interpret"))
def paged_prefill(q, kp, vp, page_table, positions, *, kind: str = "causal",
                  window: Optional[int] = None,
                  softcap: Optional[float] = None,
                  impl: Optional[str] = None, attn_impl: str = "chunked",
                  chunk: int = 512, interpret: Optional[bool] = None):
    """Chunked-prefill attention against paged KV pools — long-prompt
    admission's hot loop.

    q (B, C, Hq, D) one C-token query chunk per slot; kp/vp
    (num_pages, Hkv, page_size, D) page pools (the chunk's k/v already
    scattered in; from the block scan, the folded stacked pools of
    ``layer_slab``); page_table (B, npages); positions (B, C) absolute
    query positions, ``starts[slot] + arange(C)`` — contiguous per slot.
    Returns (B, C, Hq, D).

    ``impl`` selects the backend (``ModelConfig.paged_attn_impl``):
      - "gather": materialize the logical (B, npages·page_size, Hkv, D)
        view and run dense ``attention`` over it — bit-identical to the
        pre-kernel chunked-prefill branch of ``models/model.py`` (the
        static ≡ continuous parity contract), O(table width)
        bytes/chunk. ``attn_impl``/``chunk`` feed through to that dense
        attention (the flash kernel assumes pos_q = arange(Sq), so
        "pallas" downgrades to "chunked").
      - "ref": ``paged_prefill_ref`` — per-page online softmax, no dense
        view, bytes scale with the batch-max live page count.
      - "pallas": the Mosaic kernel; unreachable pages re-point in the
        index map, so bytes scale with ``pages_for(starts + C)``. Like
        ``paged_decode``, wrap in shard_map to split kv heads on a mesh.
      - None / "auto": gather on every backend (the kernel is untimed on
        the chip; the view is bounded by the chunk's width bucket).
    """
    if impl not in PAGED_IMPLS + (None,):
        raise ValueError(f"unknown paged-attention impl {impl!r}")
    if impl in (None, "auto"):
        impl = "gather"
    if kind not in ("causal", "local"):
        raise ValueError(f"paged prefill is causal-only, got kind={kind!r}")
    eff_window = window if kind == "local" else None
    if impl == "gather":
        from repro.models.attention import attention
        kc, vc = _logical_view(kp, vp, page_table)
        b, lview = kc.shape[:2]
        pos_k = jnp.broadcast_to(jnp.arange(lview), (b, lview))
        # the Pallas flash kernel assumes pos_q = arange(Sq): chunked
        # prefill runs at an offset, so it drops to the jnp twin
        a_impl = "chunked" if attn_impl == "pallas" else attn_impl
        return attention(q, kc, vc, pos_q=positions, pos_k=pos_k,
                         kind=kind, window=window, softcap=softcap,
                         impl=a_impl, chunk=chunk)
    starts = positions[:, 0].astype(jnp.int32)
    lengths = (positions[:, -1] + 1).astype(jnp.int32)
    if impl == "ref":
        return _paged_prefill_ref(q, kp, vp, page_table, lengths, starts,
                                  window=eff_window, softcap=softcap)
    interp = (not on_tpu()) if interpret is None else interpret
    return _paged_prefill(q, kp, vp, page_table, lengths, starts,
                          window=eff_window, softcap=softcap,
                          interpret=interp)


def layer_slab(kp, vp, page_table, layer):
    """Address layer ``layer`` of stacked pools in place.

    kp/vp (L, P, Hkv, page, D) fold to (L·P, Hkv, page, D), a reshape
    that copies nothing, and the table's physical pages offset by
    layer·P so they index the layer's slab. Every paged backend then
    reads exactly the pages it would read from that layer's own pool.
    ``layer`` is an int32 scalar, or an array that broadcasts against
    ``page_table``. Returns (kp, vp, table).
    """
    lyr, pool_pages = kp.shape[:2]

    def fold(pool):
        return pool.reshape((lyr * pool_pages,) + pool.shape[2:])
    return (fold(kp), fold(vp),
            page_table.astype(jnp.int32) + layer * pool_pages)


def _fold_layers(q, kp, vp, page_table, lengths):
    """Fold a leading layer axis into the slot axis so ONE kernel launch
    serves every layer's pools.

    q (L, B, ...), kp/vp (L, P, Hkv, page, D), page_table (B, W),
    lengths (B,) → per-layer operands stacked along slots: the pools
    concatenate to (L·P, ...), and layer l's table rows offset by l·P so
    they index the l-th pool slab (``layer_slab``). Slots never mix
    across grid steps, so the folded launch is bit-exact vs L per-layer
    launches — it just amortizes one grid setup and one scalar-prefetch
    DMA over all layers instead of paying them L times.
    """
    lyr, b = q.shape[0], q.shape[1]
    layers = jnp.arange(lyr, dtype=jnp.int32)[:, None, None]
    kpf, vpf, tablef = layer_slab(kp, vp, page_table, layers)
    tablef = tablef.reshape(lyr * b, -1)
    lengthsf = jnp.broadcast_to(lengths, (lyr,) + lengths.shape
                                ).reshape(lyr * b)
    qf = q.reshape((lyr * b,) + q.shape[2:])
    return qf, kpf, vpf, tablef, lengthsf


@functools.partial(jax.jit, static_argnames=("kind", "window", "softcap",
                                             "impl", "interpret"))
def paged_decode_layers(q, kp, vp, page_table, lengths, *,
                        kind: str = "causal", window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        impl: Optional[str] = None,
                        interpret: Optional[bool] = None):
    """``paged_decode`` over all layers' pools in ONE launch.

    q (L, B, 1, Hq, D) per-layer queries; kp/vp (L, P, Hkv, page, D)
    stacked pools (the scanned-block layout of ``init_paged_cache``);
    page_table (B, W) and lengths (B,) shared by every layer. Returns
    (L, B, 1, Hq, D), bit-exact vs L separate ``paged_decode`` calls.

    Inside the model's forward pass layer l's *query* depends on layer
    l-1's output, so the block scan cannot use this; it serves callers
    that already hold all layers' queries (speculative scoring, KV-pool
    maintenance sweeps) and pins the launch-count/bit-exactness claim
    the benchmarks measure.
    """
    lyr, b = q.shape[0], q.shape[1]
    qf, kpf, vpf, tablef, lengthsf = _fold_layers(q, kp, vp, page_table,
                                                  lengths)
    o = paged_decode(qf, kpf, vpf, tablef, lengthsf, kind=kind,
                     window=window, softcap=softcap, impl=impl,
                     interpret=interpret)
    return o.reshape((lyr, b) + o.shape[1:])


@functools.partial(jax.jit, static_argnames=("kind", "window", "softcap",
                                             "impl", "attn_impl", "chunk",
                                             "interpret"))
def paged_prefill_layers(q, kp, vp, page_table, positions, *,
                         kind: str = "causal", window: Optional[int] = None,
                         softcap: Optional[float] = None,
                         impl: Optional[str] = None,
                         attn_impl: str = "chunked", chunk: int = 512,
                         interpret: Optional[bool] = None):
    """``paged_prefill`` over all layers' pools in ONE launch: q
    (L, B, C, Hq, D), kp/vp (L, P, Hkv, page, D), positions (B, C)
    shared across layers. Returns (L, B, C, Hq, D), bit-exact vs L
    separate calls — same layer-folding as ``paged_decode_layers``."""
    lyr, b = q.shape[0], q.shape[1]
    lengths = (positions[:, -1] + 1).astype(jnp.int32)
    qf, kpf, vpf, tablef, _ = _fold_layers(q, kp, vp, page_table, lengths)
    posf = jnp.broadcast_to(positions, (lyr,) + positions.shape
                            ).reshape((lyr * b,) + positions.shape[1:])
    o = paged_prefill(qf, kpf, vpf, tablef, posf, kind=kind, window=window,
                      softcap=softcap, impl=impl, attn_impl=attn_impl,
                      chunk=chunk, interpret=interpret)
    return o.reshape((lyr, b) + o.shape[1:])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log_neg, b, c, *, chunk: int = 128,
             interpret: Optional[bool] = None):
    interp = (not on_tpu()) if interpret is None else interpret
    return _ssd_scan(x, dt, a_log_neg, b, c, chunk=chunk, interpret=interp)


@functools.partial(jax.jit, static_argnames=("block_t", "block_v",
                                             "interpret"))
def fused_logprob(logits, targets, *, block_t: int = 256,
                  block_v: int = 2048, interpret: Optional[bool] = None):
    interp = (not on_tpu()) if interpret is None else interpret
    return _fused_logprob(logits, targets, block_t=block_t, block_v=block_v,
                          interpret=interp)


def _largest_divisor(n: int, cap: int, mult: int) -> int:
    """Largest d ≤ cap with n % d == 0 and d % mult == 0 (0 if none) —
    picks a Pallas tile size that exactly divides real model shapes
    (padded vocabs are 256-aligned, not block_v-aligned; token counts
    are B·(S−1))."""
    for d in range(min(cap, n) - min(cap, n) % mult, 0, -mult):
        if n % d == 0:
            return d
    return 0


LOGPROB_IMPLS = ("pallas", "chunked", "naive")


def logprob_tiles(t: int, v: int, block_t: int = 256,
                  block_v: int = 2048) -> Tuple[int, int]:
    """(token tile, vocab tile) of the Pallas kernel for flat (t, v)
    logits: the largest hardware-aligned divisors of the actual shape
    (t = B·(S−1) and 256-aligned padded vocabs rarely divide the default
    blocks). (0, 0) when no aligned tile divides."""
    bt = _largest_divisor(t, block_t, 8) or (t if t < 8 else 0)
    bv = _largest_divisor(v, block_v, 128) or (v if v < 128 else 0)
    return (bt, bv) if bt and bv else (0, 0)


def logprob_backend(shape: Tuple[int, ...], impl: Optional[str] = None,
                    block_t: int = 256, block_v: int = 2048) -> str:
    """The backend ``fused_token_logprob`` runs for logits of ``shape``:
    "pallas", "chunked" or "naive".

    ``impl`` None (auto) means Pallas on TPU and chunked elsewhere; on
    TPU a shape the kernel cannot tile runs chunked with a warning.
    A forced "pallas" raises for such a shape, on any backend."""
    if impl not in LOGPROB_IMPLS + (None,):
        raise ValueError(f"unknown logprob impl {impl!r}")
    if impl is not None:
        want = impl
    else:
        want = "pallas" if on_tpu() else "chunked"
    if want != "pallas":
        return want
    t, v = int(np.prod(shape[:-1])), shape[-1]
    if logprob_tiles(t, v, block_t, block_v) != (0, 0):
        return "pallas"
    msg = (f"fused log-prob: no Pallas tiling of ({t} tokens, vocab {v}) "
           f"with token tiles of 8·k <= {block_t} and vocab tiles of "
           f"128·k <= {block_v}")
    if impl == "pallas":
        raise ValueError(msg)
    warnings.warn(msg + "; running the chunked jnp backend instead",
                  RuntimeWarning, stacklevel=3)
    return "chunked"


@functools.partial(jax.jit, static_argnames=("impl", "block_t", "block_v",
                                             "chunk", "interpret"))
def fused_token_logprob(logits, targets, *, impl: Optional[str] = None,
                        block_t: int = 256, block_v: int = 2048,
                        chunk: int = 256,
                        interpret: Optional[bool] = None):
    """Training-stack entry for memory-bounded token log-probs.

    logits (..., V) [any float dtype], targets (...,) int ->
    (logp (...,), entropy (...,)), both f32 — differentiable w.r.t.
    ``logits`` with a streaming backward (no V-sized f32 activation in
    either pass; see ``repro.kernels.fused_logprob``).

    ``impl`` selects the backend (``logprob_backend`` resolves it):
      - None (default): Pallas on TPU, chunked pure-JAX elsewhere; a
        shape the kernel cannot tile runs chunked with a warning;
      - "pallas" / "chunked": forced ("pallas" raises when T or V has
        no aligned tile that divides it);
      - "naive": the materializing log-softmax reference
        (``repro.core.logprob``) — for A/B benchmarks and debugging.

    Out-of-range target ids are clamped to [0, V) (masked positions may
    carry any id — the padding contract of ``repro.core.logprob``).
    """
    from repro.core.logprob import token_logprob_and_entropy
    if logits.ndim == 1:                       # single token, no batch dim
        lp, ent = fused_token_logprob(
            logits[None], targets.reshape((1,)), impl=impl,
            block_t=block_t, block_v=block_v, chunk=chunk,
            interpret=interpret)
        return lp.reshape(targets.shape), ent.reshape(targets.shape)
    backend = logprob_backend(logits.shape, impl, block_t, block_v)
    if backend == "naive":
        return token_logprob_and_entropy(logits, targets)
    lead, v = logits.shape[:-1], logits.shape[-1]
    if backend == "pallas":
        # the kernel takes flat (T, V). NOTE pallas_call has no GSPMD
        # partitioning rules: on a multi-device mesh, call this under
        # shard_map so the kernel sees per-device (T, V) shards — under
        # plain GSPMD the flatten below would merge a data-sharded batch
        # axis into the token axis and replicate the logits. The chunked
        # branch is GSPMD-native (shard-local token-axis slices) and is
        # what the CPU dry-run grid lowers.
        bt, bv = logprob_tiles(int(np.prod(lead)), v, block_t, block_v)
        interp = (not on_tpu()) if interpret is None else interpret
        lp, ent = _fused_logprob(logits.reshape((-1, v)),
                                 targets.reshape((-1,)),
                                 block_t=bt, block_v=bv, interpret=interp)
        return lp.reshape(lead), ent.reshape(lead)
    # chunked keeps the (..., T, V) layout: the token axis is chunked in
    # place so data-sharded batch axes never get flattened into the
    # sliced axis (GSPMD would otherwise replicate the whole logits)
    return _chunked_logprob(logits, targets, chunk=chunk)
