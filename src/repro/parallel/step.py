"""Jitted sharded step builders.

``make_sharded_train_step`` is the only way the repo builds a runnable
train step: explicit ``in_shardings``/``out_shardings`` from the
``ExecutionPlan`` and a **donated** ``TrainState`` (params + optimizer
buffers are consumed in place — no 2× param footprint inside the step).
On the default 1×1 plan this degenerates to single-device execution with
the exact same code path.

Donation contract: the state passed in is dead after the call. Nodes that
keep a replica of the learner's params (samplers) must hold their own
copies (``ExecutionPlan.device_put_params(copy=True)``).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, RLConfig, TrainConfig
from repro.parallel.plan import ExecutionPlan
from repro.runtime_context import mesh_context


def _sig(tree: Dict[str, Any]) -> Tuple:
    """Hashable (key, shape, dtype) signature of a dict batch — retrace
    key for the shape-specialized executables below."""
    return tuple(sorted((k, tuple(v.shape), jnp.dtype(v.dtype).name)
                        for k, v in tree.items()))


def make_sharded_train_step(cfg: ModelConfig, rl: RLConfig, tc: TrainConfig,
                            plan: ExecutionPlan, *,
                            optimizer: str = "adamw",
                            donate: bool = True) -> Callable:
    """(state, batch) -> (state, metrics), jitted against the plan.

    Batch shardings are fitted per batch shape (cached), state shardings
    once per (cfg, optimizer). Grad-accum microbatch slicing is pinned
    shard-local via ``plan.constrain_microbatches``.
    """
    from repro.training import train_step
    state_sh = plan.state_shardings(cfg, optimizer)
    mb_con = plan.microbatch_constraint(cfg, tc.grad_accum)

    def step(state, batch):
        return train_step(cfg, rl, tc, state, batch, optimizer=optimizer,
                          mb_constraint=mb_con)

    @functools.lru_cache(maxsize=16)
    def build(sig):
        batch_sh = plan.batch_shardings(cfg, {
            k: jax.ShapeDtypeStruct(shape, jnp.dtype(dt))
            for k, shape, dt in sig})
        return jax.jit(step, in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,) if donate else ())

    def step_fn(state, batch):
        with mesh_context(plan.mesh):
            return build(_sig(batch))(state, batch)

    step_fn.plan = plan
    return step_fn


def make_sharded_sft_step(cfg: ModelConfig, tc: TrainConfig,
                          plan: ExecutionPlan, *,
                          optimizer: str = "adamw",
                          donate: bool = True) -> Callable:
    """(state, tokens, mask) -> (state, loss) with plan shardings and a
    donated ``TrainState`` — the SFT warm-start twin of the RL step."""
    from repro.training import apply_update, sft_loss_fn
    state_sh = plan.state_shardings(cfg, optimizer)

    def step(state, tokens, mask):
        loss, grads = jax.value_and_grad(
            lambda p: sft_loss_fn(cfg, p, tokens, mask,
                                  logprob_impl=tc.logprob_impl))(
            state.params)
        new_state, _, _ = apply_update(tc, optimizer, state, grads)
        return new_state, loss

    @functools.lru_cache(maxsize=8)
    def build(tok_shape, mask_shape):
        sh = plan.batch_shardings(cfg, {
            "tokens": jax.ShapeDtypeStruct(tok_shape, jnp.int32),
            "mask": jax.ShapeDtypeStruct(mask_shape, jnp.float32)})
        in_sh = (state_sh, sh["tokens"], sh["mask"])
        return jax.jit(step, in_shardings=in_sh,
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,) if donate else ())

    def step_fn(state, tokens, mask):
        with mesh_context(plan.mesh):
            return build(tuple(tokens.shape), tuple(mask.shape))(
                state, tokens, mask)

    step_fn.plan = plan
    return step_fn
