"""The RL train step: forward → policy loss → grads → clipped optimizer
update. This single function is shared by

- the HeteroRL learner node (tiny models, real training on CPU),
- the production launcher (``repro.launch.train``) and the multi-pod
  dry-run, where it is lowered/compiled against the assigned architecture
  × input-shape grid with GSPMD sharding.

Batch layout (targets are tokens shifted by one):
  tokens (B, T) int32 | mask (B, T-1) f32 over target positions |
  sampler_lp (B, T-1) f32 | rewards (B,) f32, group-contiguous.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, RLConfig, TrainConfig
from repro.core import group_advantages, policy_loss
from repro.core.logprob import token_logprob_from_logits
from repro.kernels.ops import fused_token_logprob
from repro.models import forward
from repro.optim import (AdafactorState, AdamWState, adafactor_init,
                         adafactor_update, adamw_init, adamw_update,
                         clip_by_global_norm, warmup_schedule)

# Metrics that aggregate across grad-accum microbatches with `max` rather
# than a mean — averaging per-microbatch maxima would understate e.g. the
# worst importance weight of the step (the Fig. 4 stability signal).
MAX_METRICS = frozenset({"iw_max"})


def _token_lp_ent(logits: jax.Array, targets: jax.Array, impl: str):
    """(logp, entropy) per target token under the configured
    ``TrainConfig.logprob_impl``; entropy is None on the naive path (it
    would cost an extra full-vocab sweep there)."""
    if impl == "naive":
        return token_logprob_from_logits(logits, targets), None
    fused_impl = None if impl == "fused" else impl
    lp, ent = fused_token_logprob(logits, targets, impl=fused_impl)
    return lp, ent


class TrainState(NamedTuple):
    params: Any
    opt: Any
    step: jax.Array


def optimizer_of(state: TrainState) -> str:
    """The optimizer a ``TrainState`` was built for, read off its opt
    buffers — the step, its shardings and its update rule follow it."""
    if isinstance(state.opt, AdamWState):
        return "adamw"
    if isinstance(state.opt, AdafactorState):
        return "adafactor"
    raise TypeError(f"unknown optimizer state {type(state.opt).__name__}")


def init_state(cfg: ModelConfig, tc: TrainConfig, params,
               optimizer: str = "adamw", plan=None) -> TrainState:
    """Fresh optimizer state; with an ``ExecutionPlan`` the whole
    ``TrainState`` is ``device_put`` onto the plan's shardings so the
    first sharded step pays no resharding copy."""
    init = adamw_init if optimizer == "adamw" else adafactor_init
    state = TrainState(params=params, opt=init(params),
                       step=jnp.zeros((), jnp.int32))
    if plan is not None:
        state = plan.device_put_state(cfg, state, optimizer)
    return state


def rl_loss_fn(cfg: ModelConfig, rl: RLConfig, params,
               batch: Dict[str, jax.Array],
               memory: Optional[jax.Array] = None,
               logprob_impl: str = "fused"
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    # modality stubs ride in the batch so they micro-batch with it
    if memory is None and "frames" in batch:
        from repro.models import encode as _encode
        memory = _encode(cfg, params, batch["frames"])
    elif memory is None and "image_embeds" in batch:
        memory = batch["image_embeds"]
    tokens = batch["tokens"]
    # named scopes thread phase names into the HLO metadata, so XLA /
    # jax.profiler traces of the jitted step carry rl/forward,
    # rl/logprob, rl/loss instead of one opaque jit_train_step blob
    with jax.named_scope("rl_forward"):
        logits, _, aux = forward(cfg, params, tokens[:, :-1], memory=memory)
    with jax.named_scope("rl_logprob"):
        learner_lp, learner_ent = _token_lp_ent(logits, tokens[:, 1:],
                                                logprob_impl)

    sampler_lp = batch["sampler_lp"]
    if not rl.recompute_sampler_logps:
        # trust engine-side logps verbatim (paper shows this is unstable)
        sampler_lp = jax.lax.stop_gradient(sampler_lp)

    with jax.named_scope("rl_loss"):
        adv = group_advantages(
            batch["rewards"], rl.group_size,
            normalize=rl.adv_normalize,
            kind=rl.loss_type if rl.loss_type in ("bnpo", "dr_grpo")
            else "grpo")
        loss, metrics = policy_loss(rl, learner_lp, sampler_lp,
                                    batch["mask"], adv, entropy=learner_ent)
    for k, v in aux.items():                      # MoE router diagnostics
        metrics[k] = v / max(cfg.num_blocks, 1)
    metrics["reward_mean"] = batch["rewards"].mean()
    return loss, metrics


def train_step(cfg: ModelConfig, rl: RLConfig, tc: TrainConfig,
               state: TrainState, batch: Dict[str, jax.Array], *,
               optimizer: str = "adamw",
               memory: Optional[jax.Array] = None,
               mb_constraint: Optional[Any] = None
               ) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """One (optionally micro-batched) RL update. ``mb_constraint`` (set by
    the sharded step builder) re-pins the reshaped (accum, mb, ...) batch
    so microbatch slicing stays shard-local under GSPMD."""
    def loss_fn(params, mb):
        return rl_loss_fn(cfg, rl, params, mb, memory=memory,
                          logprob_impl=tc.logprob_impl)

    if tc.grad_accum > 1:
        def mb_grads(carry, mb):
            g_acc, m_acc = carry
            (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, mb)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
            m_acc = {k: (jnp.maximum(m_acc[k], v) if k in MAX_METRICS
                         else m_acc[k] + v) for k, v in m.items()}
            return (g_acc, m_acc), None

        mbs = jax.tree_util.tree_map(
            lambda x: x.reshape((tc.grad_accum, -1) + x.shape[1:]), batch)
        if mb_constraint is not None:
            mbs = mb_constraint(mbs)
        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        # metrics pytree structure only — jax.eval_shape performs no
        # FLOPs, so the step runs exactly grad_accum loss evaluations
        m_avals = jax.eval_shape(
            lambda p, mb: loss_fn(p, mb)[1], state.params,
            jax.tree_util.tree_map(lambda x: x[0], mbs))
        m0 = {k: (jnp.full(s.shape, -jnp.inf, s.dtype) if k in MAX_METRICS
                  else jnp.zeros(s.shape, s.dtype))
              for k, s in m_avals.items()}
        (grads, msum), _ = jax.lax.scan(mb_grads, (g0, m0), mbs)
        grads = jax.tree_util.tree_map(lambda g: g / tc.grad_accum, grads)
        metrics = {k: (v if k in MAX_METRICS else v / tc.grad_accum)
                   for k, v in msum.items()}
    else:
        (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch)

    with jax.named_scope("optim_update"):
        new_state, gnorm, lr = apply_update(tc, optimizer, state, grads)
    metrics["grad_norm"] = gnorm
    metrics["lr"] = lr
    return new_state, metrics


def apply_update(tc: TrainConfig, optimizer: str, state: TrainState,
                 grads: Any) -> Tuple[TrainState, jax.Array, jax.Array]:
    """Clip, then one ``optimizer`` step at the warmup schedule's rate:
    (new state, pre-clip grad norm, learning rate)."""
    grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
    lr = warmup_schedule(tc, state.step)
    update = adamw_update if optimizer == "adamw" else adafactor_update
    new_params, new_opt = update(tc, grads, state.opt, state.params, lr)
    return TrainState(new_params, new_opt, state.step + 1), gnorm, lr


def jit_train_step(cfg: ModelConfig, rl: RLConfig, tc: TrainConfig,
                   optimizer: str = "adamw", plan=None):
    """Jitted train step through the unified execution layer: explicit
    in/out shardings from the ``ExecutionPlan`` (default: the 1×1 local
    plan) and a **donated** ``TrainState`` — callers must treat the input
    state as consumed (keep copies of params you hand to other nodes).
    With ``plan=None`` the ``TrainConfig.mesh`` knob decides (default the
    1×1 local plan)."""
    from repro.parallel import make_sharded_train_step, plan_from_flag
    plan = plan or plan_from_flag(tc.mesh, "train")
    return make_sharded_train_step(cfg, rl, tc, plan, optimizer=optimizer)


# --------------------------------------------------------------------------
# Supervised warm-start. The paper RL-tunes a *pretrained* model
# (Qwen3-1.7B/8B); our CPU-scale experiments mirror that by SFT-ing the
# tiny model on (prompt, answer) pairs until it emits well-formed answers,
# then handing it to RL.


def sft_loss_fn(cfg: ModelConfig, params, tokens: jax.Array,
                mask: jax.Array, logprob_impl: str = "fused") -> jax.Array:
    logits, _, _ = forward(cfg, params, tokens[:, :-1])
    lp, _ = _token_lp_ent(logits, tokens[:, 1:], logprob_impl)
    nll = -lp
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def jit_sft_step(cfg: ModelConfig, tc: TrainConfig, plan=None,
                 optimizer: str = "adamw"):
    """Jitted SFT step through the same execution layer as the RL step
    (plan shardings + donated state; ``TrainConfig.mesh`` decides when no
    plan is passed)."""
    from repro.parallel import make_sharded_sft_step, plan_from_flag
    return make_sharded_sft_step(cfg, tc, plan or plan_from_flag(tc.mesh,
                                                                 "train"),
                                 optimizer=optimizer)
