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
- each model rank computes its own block of experts for its local
  replica of the tokens, as a layer that holds that block
  (``moe.held_experts_ffn``: dropless, grouped matmuls), then one
  ``psum`` over 'model' combines expert outputs — a Megatron
  row-parallel all-reduce.

Per-device working set: T_loc·min(k, E_loc) rows of the held pairs —
independent of the global token count.

Enabled by the launcher via ``cfg.moe_ep`` = "train" | "serve" (weights
FSDP-sharded on d_model vs f) + ``cfg.ep_dp_axes``.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import swiglu
from repro.models.moe import held_experts_ffn, route, router_aux


def moe_ffn_ep(cfg: ModelConfig, p: Dict, x: jax.Array
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x (B,S,d) -> (B,S,d). Requires a mesh context (inside jit under
    ``with mesh:``) and cfg.moe_ep/'ep_dp_axes' set by the launcher."""
    from repro.runtime_context import get_mesh
    held = cfg.num_held_experts
    mode = cfg.moe_ep
    dp = tuple(cfg.ep_dp_axes or ())
    tp = "model"
    mesh = get_mesh()
    tp_size = mesh.shape[tp]
    assert held % tp_size == 0, (held, tp_size)
    e_loc = held // tp_size
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
        out_specs=(x_spec, P(), P()),
        check_vma=False)
    def inner(x_loc, router, w_gate, w_up, w_down):
        b_loc, s, d = x_loc.shape
        xf = x_loc.reshape(b_loc * s, d)
        w = {"w_gate": gather(w_gate, g_dim_up),         # (E_loc, d, f)
             "w_up": gather(w_up, g_dim_up),
             "w_down": gather(w_down, g_dim_down)}       # (E_loc, f, d)
        logits, probs, gate, ids = route(cfg, router, xf)
        # this rank's block of experts, computed as a layer that holds it
        first = cfg.first_held_expert + jax.lax.axis_index(tp) * e_loc
        y = held_experts_ffn(xf, gate, ids, w, first, e_loc)
        y = jax.lax.psum(y, tp)                          # combine experts

        aux = router_aux(logits, probs, ids)
        for ax in (tp,) + dp:
            aux = {n: jax.lax.pmean(v, ax) for n, v in aux.items()}
        return (y.reshape(b_loc, s, d), aux["moe_load_balance"],
                aux["moe_z_loss"])

    y, lb, zl = inner(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    if cfg.shared_expert:
        y = y + swiglu(x, p["shared"])
    return y, {"moe_load_balance": lb, "moe_z_loss": zl}
