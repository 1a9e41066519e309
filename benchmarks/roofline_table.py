"""Deliverable (g): aggregate the dry-run JSON records into the roofline
table (per arch × shape × mesh: three terms, bottleneck, useful-FLOPs
fraction, HBM fit), plus the analytic paged-decode bytes-per-token rows
(gather-legacy O(pool) vs in-place kernel O(len) KV traffic)."""
from __future__ import annotations

import glob
import json
import os

RESULTS = os.environ.get("DRYRUN_RESULTS", "results/dryrun")


def paged_decode_rows() -> list:
    """Bytes-per-token model of the serving decode hot loop: the legacy
    gather reads (and re-materializes) every slot's full page allotment
    each step, the in-place kernel reads only the live pages."""
    from repro.config import DECODE_32K
    from repro.configs import get_config

    cfg = get_config("qwen2-7b")
    page_size = 16
    pool_len = DECODE_32K.seq_len                 # pages_per_slot * page
    kv_bytes = 2 * cfg.num_kv_heads * cfg.head_dim * 2      # k+v, bf16
    rows = ["roofline,paged-decode,arch,ctx,pool,kv_GiB_per_tok_gather,"
            "kv_GiB_per_tok_kernel,ratio"]
    for ctx in (2048, 8192, pool_len):
        gather = cfg.num_layers * pool_len * kv_bytes        # O(pool)
        live = -(-ctx // page_size) * page_size
        kernel = cfg.num_layers * live * kv_bytes            # O(len)
        rows.append(
            f"roofline,paged-decode,{cfg.name},{ctx},{pool_len},"
            f"{gather/2**30:.3f},{kernel/2**30:.3f},"
            f"{gather/kernel:.1f}x")
    return rows


def paged_prefill_rows() -> list:
    """Bytes-per-chunk model of chunked-prefill admission: the gather
    path materializes the narrowed table's dense view — the pow2 width
    bucket for ``pages_for(c0 + C)`` — per layer per chunk, while the
    in-place kernel streams exactly the reachable pages. The gap is the
    pow2 rounding (≤2x) *plus* the materialization itself: gather pays
    its bytes twice (read pool, write view, read view), the kernel
    once."""
    from repro.config import DECODE_32K
    from repro.configs import get_config

    cfg = get_config("qwen2-7b")
    page_size = 16
    chunk = 512
    pool_len = DECODE_32K.seq_len
    pool_pages = pool_len // page_size
    kv_bytes = 2 * cfg.num_kv_heads * cfg.head_dim * 2      # k+v, bf16
    rows = ["roofline,paged-prefill,arch,c0,chunk,pool,"
            "kv_GiB_per_chunk_gather,kv_GiB_per_chunk_kernel,ratio"]
    for c0 in (2048, 8192, pool_len - chunk):
        live_pages = -(-(c0 + chunk) // page_size)
        width = 1
        while width < live_pages:
            width *= 2
        width = min(width, pool_pages)
        # dense view: read the pages + write/read the materialized copy
        gather = cfg.num_layers * 2 * width * page_size * kv_bytes
        kernel = cfg.num_layers * live_pages * page_size * kv_bytes
        rows.append(
            f"roofline,paged-prefill,{cfg.name},{c0},{chunk},{pool_len},"
            f"{gather/2**30:.3f},{kernel/2**30:.3f},"
            f"{gather/kernel:.1f}x")
    return rows


def run() -> list:
    files = sorted(glob.glob(os.path.join(RESULTS, "*.json")))
    rows = ["roofline,arch,shape,mesh,compute_ms,memory_ms,collective_ms,"
            "bottleneck,useful_flops_frac,args_GiB,temp_GiB,fit16G"]
    if not files:
        rows.append("roofline,NO_RESULTS,run `python -m repro.launch."
                    "dryrun` first,,,,,,,,,")
        return rows + paged_decode_rows() + paged_prefill_rows()
    for fn in files:
        with open(fn) as f:
            r = json.load(f)
        t = r["roofline"]
        ma = r.get("memory_analysis", {})
        rows.append(
            f"roofline,{r['arch']},{r['shape']},{r['mesh']},"
            f"{t['compute_s']*1e3:.2f},{t['memory_s']*1e3:.2f},"
            f"{t['collective_s']*1e3:.2f},{t['bottleneck']},"
            f"{(r.get('useful_flops_frac') or 0):.3f},"
            f"{r.get('entry_arg_bytes_per_dev', 0)/2**30:.2f},"
            f"{ma.get('temp_size_in_bytes', 0)/2**30:.2f},"
            f"{r.get('hbm_fit_16g')}")
    return rows + paged_decode_rows() + paged_prefill_rows()
