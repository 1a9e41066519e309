"""Paged-attention hot-loop microbenchmark: gather-legacy vs ref vs
pallas, decode steps *and* chunked-prefill chunks.

One decode step of the continuous engine runs ``paged_decode`` per layer
and every admitted prompt runs ``paged_prefill`` per chunk per layer —
the two hottest loops in the serving path. This bench times exactly
those ops across context lengths × pool occupancy and reports XLA's
``temp_size_in_bytes`` for the compiled executable as a peak-HBM-traffic
proxy (the ``logprob_bench`` convention):

  - gather   — the legacy path: materialize the whole
               (B, pages_per_slot·page_size, Hkv, D) logical view, then
               dense attention over it. O(pool) bytes regardless of
               context.
  - ref      — ``paged_decode_ref`` / ``paged_prefill_ref``: per-page
               online softmax bounded by the live high-water mark.
               O(ceil(len/page)) bytes.
  - pallas   — the Mosaic kernels in interpret mode on CPU (compiled on
               a real TPU); benched at a reduced size — interpret mode
               pays a large python constant per grid step, but the
               memory story matches ref.

The prefill sweep varies the chunk's start offset ``c0`` (prompt already
cached) against a fixed-width table: the gather path's dense view pays
for the full table width while ref/pallas touch only
``pages_for(c0 + C)`` pages. A fused-layers section times L per-layer
launches against ONE layer-folded launch (``paged_decode_layers``).

  PYTHONPATH=src python -m benchmarks.decode_bench [--smoke]

Output: CSV rows ``decode,<impl>,ctx<L>of<pool>,<ms>,<temp MiB>`` /
``prefill,<impl>,c0<c0>+<C>of<pool>,...`` plus a ``BENCH_decode.json``
artifact (path: $BENCH_DECODE_JSON) — the serving-path perf trajectory.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import (paged_decode, paged_decode_layers,
                               paged_prefill)

SMOKE_ENV = os.environ.get("BENCH_SMOKE", "0") == "1"
JSON_PATH = os.environ.get("BENCH_DECODE_JSON", "BENCH_decode.json")


def _make_case(b, hkv, rep, d, page, pages_per_slot, ctx, seed=0,
               dtype=jnp.float32):
    """Engine-shaped operands: every slot holds ``ctx`` live tokens of a
    pool provisioned for ``pages_per_slot`` pages per slot.

    f32 pools so the temp proxy compares layouts, not dtype lowering:
    XLA:CPU has no native bf16 dot, and the resulting upcast is
    loop-invariant for the page-loop impls — it would charge *only*
    them a pool-sized f32 conversion that a real TPU never pays."""
    hq = hkv * rep
    pool = 1 + b * pages_per_slot
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d), dtype)
    kp = jax.random.normal(ks[1], (pool, hkv, page, d), dtype)
    vp = jax.random.normal(ks[2], (pool, hkv, page, d), dtype)
    host = np.random.default_rng(seed)
    perm = host.permutation(np.arange(1, pool))
    table = perm[:b * pages_per_slot].reshape(b, pages_per_slot)
    lengths = host.integers(max(1, ctx // 2), ctx + 1, size=b)
    return (q, kp, vp, jnp.asarray(table.astype(np.int32)),
            jnp.asarray(lengths.astype(np.int32)))


def _make_prefill_case(b, hkv, rep, d, page, pages_per_slot, c0, chunk,
                       seed=0, dtype=jnp.float32):
    """A prefill chunk mid-prompt: C queries at offset c0, every slot's
    table at the full provisioned width (the worst pow2 bucket — what a
    long prompt's tail chunks see)."""
    hq = hkv * rep
    pool = 1 + b * pages_per_slot
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, chunk, hq, d), dtype)
    kp = jax.random.normal(ks[1], (pool, hkv, page, d), dtype)
    vp = jax.random.normal(ks[2], (pool, hkv, page, d), dtype)
    host = np.random.default_rng(seed)
    perm = host.permutation(np.arange(1, pool))
    table = perm[:b * pages_per_slot].reshape(b, pages_per_slot)
    positions = c0 + np.arange(chunk, dtype=np.int32)[None]
    positions = np.broadcast_to(positions, (b, chunk))
    return (q, kp, vp, jnp.asarray(table.astype(np.int32)),
            jnp.asarray(positions))


def _temp_bytes(fn, args, **kw) -> Optional[int]:
    try:
        mem = fn.lower(*args, **kw).compile().memory_analysis()
        return int(mem.temp_size_in_bytes) if mem is not None else None
    except Exception:
        return None


def _bench_fn(fn, impl: str, args, *, reps: int, interpret=None):
    kw: Dict = {"impl": impl}
    if interpret is not None:
        kw["interpret"] = interpret
    tmp = _temp_bytes(fn, args, **kw)
    out = fn(*args, **kw)                            # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / reps * 1e3
    return ms, tmp


def _bench(impl: str, args, *, reps: int, interpret=None):
    return _bench_fn(paged_decode, impl, args, reps=reps,
                     interpret=interpret)


def run_bench(smoke: bool) -> List[str]:
    # decode-shaped: GQA 4:1. The pool is provisioned for the
    # longest request (prompt + max_new); the sweep holds the pool fixed
    # and varies the live context, i.e. pool-over-context ratio — the
    # regime where the legacy gather pays for capacity it never reads.
    if smoke:
        b, hkv, rep, d, page = 4, 2, 4, 64, 8
        pages_per_slot, ctxs, reps = 64, (32, 128, 512), 2
        pallas_ctx = 32
    else:
        b, hkv, rep, d, page = 8, 4, 4, 128, 16
        pages_per_slot, ctxs, reps = 128, (256, 512, 2048), 3
        pallas_ctx = 64
    pool_tokens = pages_per_slot * page

    rows: List[str] = []
    records: List[Dict] = []
    temps: Dict = {}
    for ctx in ctxs:
        args = _make_case(b, hkv, rep, d, page, pages_per_slot, ctx)
        for impl in ("gather", "ref"):
            ms, tmp = _bench(impl, args, reps=reps)
            temps[(impl, ctx)] = tmp
            mib = f"{tmp / 2**20:.1f}" if tmp is not None else "n/a"
            rows.append(f"decode,{impl},ctx{ctx}of{pool_tokens},"
                        f"{ms:.1f},{mib}")
            records.append({"impl": impl, "ctx": ctx,
                            "pool_tokens": pool_tokens,
                            "batch": b, "kv_heads": hkv, "rep": rep,
                            "head_dim": d, "page_size": page,
                            "ms": round(ms, 2), "temp_bytes": tmp})
    # pallas in interpret mode: one small shape, memory story == ref.
    # The table is narrowed to the live high-water mark exactly like the
    # engine does before dispatch — the interpreter walks every grid
    # step in python, so the dead-page DMA skip doesn't save it time.
    q, kp, vp, table, lengths = _make_case(b, hkv, rep, d, page,
                                           pages_per_slot, pallas_ctx)
    args = (q, kp, vp, table[:, :max(1, -(-pallas_ctx // page))], lengths)
    ms, tmp = _bench("pallas", args, reps=1, interpret=True)
    mib = f"{tmp / 2**20:.1f}" if tmp is not None else "n/a"
    rows.append(f"decode,pallas,ctx{pallas_ctx}of{pool_tokens},"
                f"{ms:.1f},{mib} (interpret)")
    records.append({"impl": "pallas-interpret", "ctx": pallas_ctx,
                    "pool_tokens": pool_tokens, "ms": round(ms, 2),
                    "temp_bytes": tmp})

    # the headline: at >=4x pool-over-context, the in-place path must
    # beat the legacy gather on the temp-bytes proxy
    ratios = {}
    for ctx in ctxs:
        tg, tr = temps.get(("gather", ctx)), temps.get(("ref", ctx))
        if tg and tr:
            ratios[str(ctx)] = round(tg / tr, 2)
            rows.append(f"# ctx={ctx} (pool/ctx={pool_tokens/ctx:.0f}x): "
                        f"gather temp = {tg / tr:.2f}x ref temp")

    # ---- chunked prefill: chunk offset (cached prompt) sweep ----------
    # full-width tables throughout — the regime where the gather path's
    # dense view pays for table width while ref touches pages_for(c0+C)
    chunk = 16 if smoke else 64
    c0s = ((0, 64, pool_tokens - chunk) if smoke
           else (0, 512, pool_tokens - chunk))
    ptemps: Dict = {}
    for c0 in c0s:
        pargs = _make_prefill_case(b, hkv, rep, d, page, pages_per_slot,
                                   c0, chunk)
        for impl in ("gather", "ref"):
            ms, tmp = _bench_fn(paged_prefill, impl, pargs, reps=reps)
            ptemps[(impl, c0)] = tmp
            mib = f"{tmp / 2**20:.1f}" if tmp is not None else "n/a"
            rows.append(f"prefill,{impl},c0{c0}+{chunk}of{pool_tokens},"
                        f"{ms:.1f},{mib}")
            records.append({"phase": "prefill", "impl": impl, "c0": c0,
                            "chunk": chunk, "pool_tokens": pool_tokens,
                            "batch": b, "kv_heads": hkv, "rep": rep,
                            "head_dim": d, "page_size": page,
                            "ms": round(ms, 2), "temp_bytes": tmp})
    # pallas prefill in interpret mode: one small shape, memory == ref
    pc0 = c0s[0]
    pargs = _make_prefill_case(b, hkv, rep, d, page,
                               8 if smoke else 16, pc0, chunk)
    ms, tmp = _bench_fn(paged_prefill, "pallas", pargs, reps=1,
                        interpret=True)
    mib = f"{tmp / 2**20:.1f}" if tmp is not None else "n/a"
    rows.append(f"prefill,pallas,c0{pc0}+{chunk},{ms:.1f},{mib} "
                "(interpret)")
    records.append({"phase": "prefill", "impl": "pallas-interpret",
                    "c0": pc0, "chunk": chunk, "ms": round(ms, 2),
                    "temp_bytes": tmp})

    pratios = {}
    for c0 in c0s:
        tg, tr = ptemps.get(("gather", c0)), ptemps.get(("ref", c0))
        if tg and tr:
            pratios[str(c0)] = round(tg / tr, 2)
            live = c0 + chunk
            rows.append(f"# prefill c0={c0} "
                        f"(pool/live={pool_tokens/live:.0f}x): "
                        f"gather temp = {tg / tr:.2f}x ref temp")

    # ---- fused multi-layer launch: L calls vs one folded call ---------
    lyr = 2 if smoke else 4
    fb, fpps = (2, 16) if smoke else (4, 32)
    base = [_make_case(fb, hkv, rep, d, page, fpps, fpps * page // 2,
                       seed=s) for s in range(lyr)]
    qs = jnp.stack([c[0] for c in base])
    kps = jnp.stack([c[1] for c in base])
    vps = jnp.stack([c[2] for c in base])
    table_f, lengths_f = base[0][3], base[0][4]

    def looped():
        return [paged_decode(qs[l], kps[l], vps[l], table_f, lengths_f,
                             impl="ref") for l in range(lyr)]

    jax.block_until_ready(looped())
    t0 = time.perf_counter()
    for _ in range(reps):
        outs = looped()
    jax.block_until_ready(outs)
    ms_loop = (time.perf_counter() - t0) / reps * 1e3
    fargs = (qs, kps, vps, table_f, lengths_f)
    ms_fused, _ = _bench_fn(paged_decode_layers, "ref", fargs, reps=reps)
    rows.append(f"decode,ref-L{lyr}-looped,b{fb},{ms_loop:.1f},n/a")
    rows.append(f"decode,ref-L{lyr}-fused,b{fb},{ms_fused:.1f},n/a "
                f"(one launch for {lyr} layers)")
    records.append({"phase": "fused", "impl": "ref-looped", "layers": lyr,
                    "batch": fb, "ms": round(ms_loop, 2)})
    records.append({"phase": "fused", "impl": "ref-fused", "layers": lyr,
                    "batch": fb, "ms": round(ms_fused, 2)})

    out = {"bench": "decode", "unit": "ms/step+temp_bytes",
           "workload": {"batch": b, "kv_heads": hkv, "rep": rep,
                        "head_dim": d, "page_size": page,
                        "pages_per_slot": pages_per_slot,
                        "prefill_chunk": chunk,
                        "dtype": "float32", "smoke": smoke},
           "rows": records, "gather_over_ref_temp": ratios,
           "prefill_gather_over_ref_temp": pratios}
    try:
        with open(JSON_PATH, "w") as f:
            json.dump(out, f, indent=1)
        rows.append(f"# wrote {JSON_PATH}")
    except OSError:
        rows.append(f"# could not write {JSON_PATH}")
    return rows


def run() -> List[str]:
    """benchmarks.run entrypoint."""
    return run_bench(SMOKE_ENV)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI workload (<30 s CPU)")
    args = ap.parse_args()
    print("table,impl,shape,step_ms,temp_mib")
    for r in run_bench(args.smoke or SMOKE_ENV):
        print(r)


if __name__ == "__main__":
    main()
