"""Logical-axis → mesh-axis resolution per execution mode.

This is the single source of placement rules for the whole stack: the
``ExecutionPlan`` (repro.parallel.plan) turns these specs into fitted
``NamedSharding`` trees consumed by the real train/sample steps, and the
multi-pod dry-run lowers against the same trees.

Modes:
  train        FSDP(+pod) on d_model rows × tensor parallel on heavy dims,
               Megatron-SP residual sharding (batch→dp, seq→model).
  serve        tensor parallel weights (replicated over data), batch→dp;
               expert FFN additionally sharded over data (big-MoE serving).
  long         context-parallel decode (batch=1): weight heavy dims over
               (data×model) [(pod×data×model) multi-pod], KV-cache sequence
               over data(+pod), heads over model.

Anything GSPMD cannot divide evenly it pads — acceptable for lowering and
flagged by the roofline analysis; runtime jit boundaries instead use
``fit_spec`` to prune non-dividing axes.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ModelConfig
from repro.models.params import param_axes
from repro.optim import AdafactorState, AdamWState
from repro.parallel.mesh import data_axes

MODES = ("train", "train_fsdp", "serve", "long")


def _rules(mode: str, mesh: jax.sharding.Mesh) -> Dict[str, Any]:
    dp = data_axes(mesh)                     # ("pod","data") or ("data",)
    dm = dp[:-1] + ("data", "model") if "pod" in mesh.axis_names \
        else ("data", "model")               # full fold for long mode
    if mode == "train":
        return {"vocab": "model", "embed": dp, "ffn": "model",
                "qkv": "model", "kv": "model", "experts": "model",
                "expert_ffn": None, "ssm_in": "model", "dinner": "model",
                "heads": "model", None: None}
    if mode == "train_fsdp":
        # §Perf H-A3: pure ZeRO-3 — every weight sharded on exactly one
        # fan-out dim over the WHOLE mesh, batch data-parallel over the
        # whole mesh, no tensor parallelism (no per-layer activation
        # collectives; params are all-gathered per layer instead).
        return {"vocab": dm, "embed": None, "ffn": dm, "qkv": dm,
                "kv": dm, "experts": "model", "expert_ffn": dp[-1],
                "ssm_in": dm, "dinner": dm, "heads": None, None: None}
    if mode == "serve":
        return {"vocab": "model", "embed": None, "ffn": "model",
                "qkv": "model", "kv": "model", "experts": "model",
                "expert_ffn": "data", "ssm_in": "model", "dinner": "model",
                "heads": "model", None: None}
    if mode == "long":
        return {"vocab": dm, "embed": None, "ffn": dm, "qkv": dm,
                "kv": dm, "experts": "model", "expert_ffn": "data",
                "ssm_in": dm, "dinner": dm, "heads": dm, None: None}
    raise ValueError(mode)


def resolve_spec(axes: Tuple[Optional[str], ...], mode: str,
                 mesh: jax.sharding.Mesh) -> P:
    rules = _rules(mode, mesh)
    return P(*[rules.get(a) for a in axes])


def param_specs(cfg: ModelConfig, mode: str, mesh: jax.sharding.Mesh):
    return jax.tree_util.tree_map(
        lambda axes: resolve_spec(axes, mode, mesh), param_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple))


def opt_specs(pspecs: Any, optimizer: str):
    """Optimizer-state specs derived from the parameter specs."""
    if optimizer == "adamw":
        return AdamWState(step=P(), m=pspecs, v=pspecs)

    def row(spec: P) -> P:
        return P(*spec[:-1]) if len(spec) >= 2 else spec

    def col(spec: P) -> P:
        return P(*(spec[:-2] + spec[-1:])) if len(spec) >= 2 else P(None)

    return AdafactorState(
        step=P(),
        vr=jax.tree_util.tree_map(row, pspecs,
                                  is_leaf=lambda x: isinstance(x, P)),
        vc=jax.tree_util.tree_map(col, pspecs,
                                  is_leaf=lambda x: isinstance(x, P)))


def batch_specs(cfg: ModelConfig, mesh: jax.sharding.Mesh) -> Dict[str, P]:
    dp = data_axes(mesh)
    out = {"tokens": P(dp, None), "mask": P(dp, None),
           "sampler_lp": P(dp, None), "rewards": P(dp)}
    if cfg.is_encdec:
        out["frames"] = P(dp, None, None)
    elif cfg.memory_seq:
        out["image_embeds"] = P(dp, None, None)
    return out


def cache_specs(cfg: ModelConfig, cache: Any, mode: str,
                mesh: jax.sharding.Mesh):
    """Specs for a decode-cache pytree built by ``init_cache`` (or its
    abstract twin), or a paged page-pool built by ``init_paged_pool``.
    Leaf roles are recognized by path name.

    The dense KV-cache *sequence* dim is sharded over 'model' (serve) or
    the whole mesh (long): GQA kv-head counts (4–8) cannot shard 16-way,
    and at 32k–500k contexts the cache dominates HBM — context-parallel
    decode (partial-softmax flash-decode, inserted by GSPMD) is the only
    layout that fits. Per-device cache = total / (dp × model).

    Paged pools (``kp``/``vp``, shape (nb, pages, Hkv, page, hd)) instead
    shard kv-heads over 'model' — pages are the unit of allocator locality,
    so splitting inside a page would defeat the block table; ``fit_spec``
    falls back to replication when Hkv doesn't divide. The paged-decode
    backends compose with this layout: gather/ref partition natively
    under GSPMD, the Pallas kernel dispatches per-shard via shard_map
    (grid over local kv-heads; see tests/test_paged_attention.py)."""
    dp = data_axes(mesh)
    long = mode == "long"
    if long and "pod" in mesh.axis_names:
        seq_axes = ("pod", "data", "model")
    elif long:
        seq_axes = ("data", "model")
    else:
        seq_axes = "model"
    batch_axes = (None if long else dp)

    def spec_for(path, leaf) -> P:
        names = [str(getattr(p, "key", "")) for p in path]
        if "kp" in names or "vp" in names:      # (nb, pages, Hkv, page, hd)
            return P(None, None, "model", None, None)
        if "k" in names or "v" in names or "k_mem" in names or "v_mem" in names:
            # (nb, B, S, Hkv, hd)
            return P(None, batch_axes, seq_axes, None, None)
        if "conv" in names:                     # (nb, B, K-1, conv_ch)
            return P(None, batch_axes, None,
                     seq_axes if long else "model")
        if "ssm" in names:                      # (nb, B, H, P, N)
            return P(None, batch_axes,
                     seq_axes if long else "model", None, None)
        raise ValueError(f"unknown cache leaf {names}")

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(p, l) for p, l in flat])


def act_sharding_for(mode: str, mesh: jax.sharding.Mesh
                     ) -> Optional[Tuple]:
    """Residual-stream constraint handed to the model config."""
    dp = data_axes(mesh)
    if mode == "train":
        return (dp, "model", None)             # batch→dp, seq→model (SP)
    if mode == "train_fsdp":
        return (dp + ("model",), None, None)   # batch over the whole mesh
    return None


def to_named(mesh: jax.sharding.Mesh, spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def fit_spec(mesh: jax.sharding.Mesh, spec: P, shape: Tuple[int, ...]) -> P:
    """Prune mesh axes that do not evenly divide the dimension (jit
    in/out_shardings demand exact divisibility — e.g. 8 kv heads cannot
    shard over model=16; GQA heads then stay partially sharded)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries, strict=True):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        prod = 1
        for ax in axes:
            n = mesh.shape[ax]
            if dim % (prod * n) == 0:
                keep.append(ax)
                prod *= n
            else:
                break
        out.append(tuple(keep) if len(keep) > 1 else
                   (keep[0] if keep else None))
    return P(*out)


def to_named_fit(mesh: jax.sharding.Mesh, spec_tree: Any,
                 aval_tree: Any) -> Any:
    """NamedShardings with divisibility-fitted specs (shapes taken from the
    matching ShapeDtypeStruct tree)."""
    return jax.tree_util.tree_map(
        lambda s, a: NamedSharding(mesh, fit_spec(mesh, s, a.shape)),
        spec_tree, aval_tree,
        is_leaf=lambda x: isinstance(x, P))
