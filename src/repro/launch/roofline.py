"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds-per-step
(per-device program):

  compute    = HLO_FLOPs / peak_bf16_flops
  memory     = HLO_bytes_accessed / HBM_bw
  collective = Σ collective output bytes / ICI_bw

Collective bytes are parsed from the post-SPMD optimized HLO
(``compiled.as_text()``) — they are not part of ``cost_analysis``.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

from repro.config import ModelConfig, ShapeConfig
from repro.parallel.mesh import peak_rates

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def normalize_cost_analysis(cost) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` returns a per-device list on
    jax<=0.4.x and a flat dict on newer releases; normalize to a dict.
    (Lives here, not in dryrun.py — importing dryrun mutates XLA_FLAGS.)"""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost or {}

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*?\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo_text: str) -> Dict[str, str]:
    """Map computation name -> body text from an HLO dump."""
    comps: Dict[str, str] = {}
    cur_name = None
    cur_lines = []
    for line in hlo_text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?\s*"
                     r"\([^)]*\)? ?.*-> .*\{\s*$", line)
        if not line.startswith(" ") and "{" in line and "->" in line:
            m2 = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)", line)
            if m2:
                if cur_name is not None:
                    comps[cur_name] = "\n".join(cur_lines)
                cur_name = m2.group(1)
                cur_lines = []
                if "ENTRY" in line:
                    comps["__entry__"] = cur_name
                continue
        if cur_name is not None:
            if line.startswith("}"):
                comps[cur_name] = "\n".join(cur_lines)
                cur_name = None
                cur_lines = []
            else:
                cur_lines.append(line)
    if cur_name is not None:
        comps[cur_name] = "\n".join(cur_lines)
    return comps


_WHILE_RE = re.compile(
    r"while\([^)]*\),\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CALL_RE = re.compile(r"(?:call|conditional)\([^)]*\).*?"
                      r"(?:to_apply|branch_computations)=\{?%?([\w.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def _trip_count(cond_text: str) -> int:
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    return max(consts) if consts else 1


def parse_collectives_loop_aware(hlo_text: str) -> Dict[str, int]:
    """Collective result bytes, multiplying ops inside ``while`` bodies by
    their trip count (scan-over-blocks would otherwise be counted once).
    Trip counts are read from the loop-condition constant."""
    comps = _split_computations(hlo_text)
    entry = comps.get("__entry__")
    memo: Dict[str, Dict[str, int]] = {}

    def direct(text: str) -> Dict[str, int]:
        out = {k: 0 for k in COLLECTIVES}
        for m in _OP_RE.finditer(text):
            if "-done(" in m.group(0):
                continue
            out[m.group(2)] += _shape_bytes(m.group(1))
        return out

    def total(name: str, seen=()) -> Dict[str, int]:
        if name in memo:
            return memo[name]
        if name in seen or name not in comps or name == "__entry__":
            return {k: 0 for k in COLLECTIVES}
        text = comps[name]
        out = direct(text)
        for wm in _WHILE_RE.finditer(text):
            cond, body = wm.group(1), wm.group(2)
            trips = _trip_count(comps.get(cond, ""))
            sub = total(body, seen + (name,))
            for k in out:
                out[k] += trips * sub[k]
        for cm in _CALL_RE.finditer(text):
            sub = total(cm.group(1), seen + (name,))
            for k in out:
                out[k] += sub[k]
        memo[name] = out
        return out

    if entry is None:
        return direct(hlo_text)
    return total(entry)


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum of result bytes per collective kind (``-start`` ops only are
    counted once; ``-done`` carries no new transfer)."""
    out: Dict[str, int] = {k: 0 for k in COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        if "-done(" in m.group(0):
            continue
        out[kind] += _shape_bytes(type_str)
    # avoid double counting async pairs: the regex above already skips
    # -done; -start results include both operand+result aliased buffers,
    # which we accept as the transfer upper bound.
    return out


def entry_io_bytes(hlo_text: str) -> Tuple[int, int]:
    """Per-device (argument, result) bytes from the SPMD ENTRY signature —
    the authoritative post-partitioning shapes."""
    m = re.search(r"ENTRY %?[\w.\-]+ \((.*?)\) -> (.+?) \{", hlo_text, re.S)
    if not m:
        return 0, 0
    return _shape_bytes(m.group(1)), _shape_bytes(m.group(2))


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Ideal algorithmic FLOPs per step, global: 6·N·D (train, fwd+bwd) or
    2·N·D (inference fwd), N = *active* params."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    tokens = shape.global_batch                # one token per sequence
    return 2.0 * n_active * tokens


def roofline(flops_per_dev: float, bytes_per_dev: float,
             coll_bytes_per_dev: float, device_kind: str) -> Dict[str, float]:
    peak = peak_rates(device_kind)
    t_c = flops_per_dev / peak["bf16_flops"]
    t_m = bytes_per_dev / peak["hbm_bw"]
    t_n = coll_bytes_per_dev / peak["ici_bw"]
    dom = max((t_c, "compute"), (t_m, "memory"), (t_n, "collective"))
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
            "bottleneck": dom[1]}


def fmt_row(name: str, terms: Dict[str, float]) -> str:
    return (f"{name:55s} comp={terms['compute_s']*1e3:9.3f}ms "
            f"mem={terms['memory_s']*1e3:9.3f}ms "
            f"coll={terms['collective_s']*1e3:9.3f}ms "
            f"-> {terms['bottleneck']}")
