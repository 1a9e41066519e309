"""Paged (block-table) KV cache for the continuous-batching engine.

The dense decode cache in ``models/model.py::init_cache`` allocates
``batch × max_len`` KV rows up front and ties a sequence to its row for
the whole generation. Here the sequence axis is instead carved into
fixed-size *pages* owned by a global pool:

- **page pools** — per attention layer, ``kp``/``vp`` of shape
  ``(num_blocks, num_pages, Hkv, page_size, head_dim)``, stacked on the
  scanned super-block axis like the dense cache. The model's block scan
  carries them whole and each block scatters into and attends its own
  slab in place (``models/model.py::_run_blocks``);
- **block table** — ``(num_slots, pages_per_slot)`` int32 mapping a decode
  slot's logical page to a physical page. Logical position ``p`` of slot
  ``s`` lives at ``pool[table[s, p // page_size], p % page_size]``;
- **allocator** — a host-side free list with a double-free guard. Page 0
  is reserved as a *scratch sink*: unassigned block-table entries point at
  it, so idle slots (and chunk padding) scatter harmlessly into garbage
  that is never causally visible.

When a sequence hits EOS its pages return to the pool immediately and the
slot can be re-admitted — the whole point of continuous batching.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.config import ATTN, LOCAL, ModelConfig

SCRATCH_PAGE = 0


def pages_for(total_len: int, page_size: int) -> int:
    """Pages needed to hold ``total_len`` tokens."""
    return -(-total_len // page_size)


class PageAllocator:
    """Refcounted free-list page allocator. Page 0 (scratch) is never
    handed out.

    ``alloc`` hands out pages at refcount 1; ``retain``/``release`` move
    the count up and down, and a page returns to the free list only when
    its count hits zero. This is what lets N requests share the KV pages
    of a common prompt prefix: each sharer (and the prefix cache itself)
    holds one reference, and the physical page outlives any individual
    request. ``free`` is the legacy single-owner spelling of ``release``
    — releasing a page that is not live still raises, preserving the old
    double-free guard.
    """

    def __init__(self, num_pages: int) -> None:
        if num_pages < 2:
            raise ValueError("need at least one scratch + one usable page")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._refs)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` pages at refcount 1, or None if the pool can't
        satisfy the request (the caller defers admission — or evicts
        prefix-cache entries — until pages free up)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for pg in pages:
            self._refs[pg] = 1
        return pages

    def retain(self, pages: List[int]) -> None:
        """Add one reference to each live page (shared-prefix admission)."""
        for pg in pages:
            if pg not in self._refs:
                raise ValueError(f"retain of dead / foreign page {pg}")
            self._refs[pg] += 1

    def release(self, pages: List[int]) -> List[int]:
        """Drop one reference per page; pages whose count hits zero go
        back on the free list (returned, in order)."""
        freed: List[int] = []
        for pg in pages:
            if pg not in self._refs:
                raise ValueError(f"double free / foreign page {pg}")
            self._refs[pg] -= 1
            if self._refs[pg] == 0:
                del self._refs[pg]
                self._free.append(pg)
                freed.append(pg)
        return freed

    # legacy single-owner alias (pre-refcount callers and tests)
    free = release


def init_paged_pool(cfg: ModelConfig, num_pages: int, page_size: int, *,
                    dtype: Optional[str] = None) -> Dict:
    """Page-pool pytree matching the model's per-block cache structure.

    Only attention-family layers are supported — SSM/cross-attention
    state is per-slot constant-size and doesn't page; the engine falls
    back to the static path for those architectures.
    """
    dt = jnp.dtype(dtype or cfg.dtype)
    nb = cfg.num_blocks
    pool: Dict[str, Dict] = {}
    for i, kind in enumerate(cfg.block_pattern):
        if kind not in (ATTN, LOCAL):
            raise ValueError(
                f"paged cache supports attention layers only, got {kind!r}")
        shape = (nb, num_pages, cfg.num_kv_heads, page_size, cfg.head_dim)
        pool[f"layer_{i}"] = {"self": {"kp": jnp.zeros(shape, dt),
                                       "vp": jnp.zeros(shape, dt)}}
    return pool


def paged_cache_supported(cfg: ModelConfig) -> bool:
    """True when the continuous engine's paged cache can serve ``cfg``."""
    return (all(k in (ATTN, LOCAL) for k in cfg.block_pattern)
            and not cfg.is_encdec
            and not cfg.local_ring_kv
            and cfg.memory_seq == 0)


def new_block_table(num_slots: int, pages_per_slot: int) -> np.ndarray:
    """Host-side block table, all entries parked on the scratch page."""
    return np.full((num_slots, pages_per_slot), SCRATCH_PAGE, np.int32)
