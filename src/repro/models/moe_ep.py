"""Expert-parallel MoE via ``shard_map`` (§Perf optimization, beyond the
GSPMD baseline in ``moe.py``).

Why: under pure GSPMD the sort/scatter dispatch is a *global* token
permutation — the partitioner replicates the full (T·k, d) token buffer in
f32 on every device (measured: 64 GiB per buffer at jamba-prefill shapes).

Layout:
- tokens stay sharded over the data axes, replicated over 'model';
- experts are sharded over 'model'; expert weights may additionally be
  sharded over a data axis (mode-dependent) and are all-gathered *inside*
  the shard to full (E_loc, d, f) — a per-layer weight AG instead of a
  per-token data AG;
- each model rank selects + computes its own experts' tokens from its
  local replica (pure local gather), then one ``psum`` over 'model'
  combines expert outputs — a Megatron row-parallel all-reduce.

Per-device working set: (E_loc, C_loc, d) with C_loc = T_loc·k/E·cap —
independent of the global token count.

Enabled by the launcher via ``cfg.moe_ep`` = "train" | "serve" (weights
FSDP-sharded on d_model vs f) + ``cfg.ep_dp_axes``.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import swiglu


def moe_ffn_ep(cfg: ModelConfig, p: Dict, x: jax.Array
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B,S,d) -> (B,S,d). Requires a mesh context (inside jit under
    ``with mesh:``) and cfg.moe_ep/'ep_dp_axes' set by the launcher."""
    from repro.runtime_context import get_mesh
    e, k = cfg.num_experts, cfg.experts_per_token
    mode = cfg.moe_ep
    dp = tuple(cfg.ep_dp_axes or ())
    tp = "model"
    mesh = get_mesh()
    tp_size = mesh.shape[tp]
    assert e % tp_size == 0, (e, tp_size)
    e_loc = e // tp_size
    # long-context decode has batch=1: tokens replicate over the data axes
    dp_prod = 1
    for ax in dp:
        dp_prod *= mesh.shape[ax]
    if x.shape[0] % max(dp_prod, 1):
        dp = ()

    # FSDP axis of the expert weights to re-gather inside the shard:
    #  train: (E, d, f) sharded P(model, dp[-1], None) — gather dim 1
    #  serve: (E, d, f) sharded P(model, None, 'data') — gather dim 2
    if mode == "train":
        wg_axis, g_dim_up, g_dim_down = dp[-1], 1, 2
        w_up_spec = P(tp, wg_axis, None)
        w_dn_spec = P(tp, None, wg_axis)
    else:
        wg_axis, g_dim_up, g_dim_down = "data", 2, 1
        w_up_spec = P(tp, None, wg_axis)
        w_dn_spec = P(tp, wg_axis, None)

    def gather(w, dim):
        return jax.lax.all_gather(w, wg_axis, axis=dim, tiled=True)

    x_spec = P(dp if dp else None, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(x_spec, P(None, None),
                  w_up_spec, w_up_spec, w_dn_spec),
        out_specs=(x_spec, P(), P(), P()),
        check_vma=False)
    def inner(x_loc, router, w_gate, w_up, w_down):
        b_loc, s, d = x_loc.shape
        t_loc = b_loc * s
        xf = x_loc.reshape(t_loc, d)
        w_gate = gather(w_gate, g_dim_up)                # (E_loc, d, f)
        w_up = gather(w_up, g_dim_up)
        w_down = gather(w_down, g_dim_down)              # (E_loc, f, d)

        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)          # (T_loc, E)
        gate, ids = jax.lax.top_k(probs, k)
        if k > 1:
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        rank_id = jax.lax.axis_index(tp)
        cap = max(int(t_loc * k / e * cfg.capacity_factor), 4)
        # accumulate/psum in the model dtype: the f32 (T_loc, d) combine
        # buffers were the residual memory peak (measured 2 GiB/layer)
        y = jnp.zeros((t_loc, d), x_loc.dtype)
        drop = jnp.zeros((), jnp.float32)
        for el in range(e_loc):
            ge = rank_id * e_loc + el
            sel = (ids == ge)                            # (T_loc, k)
            tok_gate = (gate * sel).sum(-1)
            routed = sel.any(-1)
            order = jnp.argsort(~routed)                 # routed first
            idx = order[:cap]
            valid = routed[idx]
            xe = xf[idx] * valid[:, None].astype(xf.dtype)
            h = jax.nn.silu(xe @ w_gate[el]) * (xe @ w_up[el])
            out = h @ w_down[el]
            out = out * (tok_gate[idx] * valid)[:, None].astype(out.dtype)
            y = y.at[idx].add(out.astype(y.dtype), mode="drop")
            drop += routed.sum().astype(jnp.float32) \
                - valid.sum().astype(jnp.float32)

        y = jax.lax.psum(y, tp)                          # combine experts

        me = probs.mean(axis=0)
        ce = jnp.zeros((e,)).at[ids.reshape(-1)].add(1.0) / (t_loc * k)
        lb = jax.lax.pmean(e * jnp.sum(me * ce), tp)
        zl = jax.lax.pmean(jnp.mean(jax.nn.logsumexp(logits, -1) ** 2), tp)
        df = jax.lax.pmean(drop / (t_loc * k), tp)
        for ax in dp:
            lb = jax.lax.pmean(lb, ax)
            zl = jax.lax.pmean(zl, ax)
            df = jax.lax.pmean(df, ax)
        return (y.reshape(b_loc, s, d), lb, zl, df)

    y, lb, zl, df = inner(x, p["router"], p["w_gate"], p["w_up"],
                          p["w_down"])
    if cfg.shared_expert:
        y = y + swiglu(x, p["shared"])
    return y, {"moe_load_balance": lb, "moe_z_loss": zl,
               "moe_drop_frac": df}
