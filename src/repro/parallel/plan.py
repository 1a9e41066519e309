"""ExecutionPlan: the one object that owns placement for a running system.

A plan is (mesh, mode) — hashable, so it rides through ``jax.jit`` as a
static argument and keys executable caches. From it every layer derives
its fitted ``NamedSharding`` trees (params, optimizer state, batch, KV
cache) out of the logical-axis rules in ``repro.parallel.axes``:

- the learner jits its train step with explicit in/out shardings and
  donated ``TrainState`` buffers (``repro.parallel.step``),
- sampler engines constrain params and the (paged) KV cache inside their
  prefill/decode executables,
- checkpoint round-trips ``device_put`` onto the plan on fetch and
  host-gather on publish,
- the multi-pod dry-run lowers against the same trees instead of
  duplicating resolution.

``local_plan`` (a 1×1 mesh) backs single-device execution so there is one
code path regardless of scale; multi-device CPU testing forces host
devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import ModelConfig
from repro.models import abstract_params
from repro.optim import adafactor_init, adamw_init
from repro.parallel import axes
from repro.parallel.mesh import data_axes, local_mesh, mesh_from_flag


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Mesh + parameter-sharding mode. Frozen/hashable: equal plans mean
    equal placement, so jit caches and ``lru_cache`` key on it directly."""
    mesh: jax.sharding.Mesh
    mode: str = "train"            # train | train_fsdp | serve | long

    def __post_init__(self):
        if self.mode not in axes.MODES:
            raise ValueError(f"mode {self.mode!r} not in {axes.MODES}")

    # ---- descriptive ----------------------------------------------------
    @property
    def num_devices(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return data_axes(self.mesh)

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def describe(self) -> str:
        shape = "x".join(f"{self.mesh.shape[a]}{a[0]}"
                         for a in self.mesh.axis_names)
        return (f"ExecutionPlan(mode={self.mode}, mesh={shape}, "
                f"devices={self.num_devices})")

    # ---- fitted NamedSharding trees -------------------------------------
    def _fit(self, spec: P, shape: Tuple[int, ...]) -> NamedSharding:
        return NamedSharding(self.mesh, axes.fit_spec(self.mesh, spec,
                                                      tuple(shape)))

    def param_shardings(self, cfg: ModelConfig) -> Any:
        return _param_shardings(self, cfg)

    def state_shardings(self, cfg: ModelConfig,
                        optimizer: str = "adamw") -> Any:
        """``TrainState``-shaped tree of fitted shardings (params + opt
        buffers + step). Opt-state avals come from ``jax.eval_shape`` of
        the real optimizer init, so they can never drift from it."""
        return _state_shardings(self, cfg, optimizer)

    def batch_shardings(self, cfg: ModelConfig,
                        batch: Dict[str, Any]) -> Dict[str, NamedSharding]:
        """Fitted shardings for the keys present in ``batch`` (arrays or
        avals). Unknown keys are an error — placement must be total."""
        specs = axes.batch_specs(cfg, self.mesh)
        unknown = sorted(set(batch) - set(specs))
        if unknown:
            raise ValueError(f"no batch sharding rule for keys {unknown}")
        return {k: self._fit(specs[k], v.shape) for k, v in batch.items()}

    def cache_shardings(self, cfg: ModelConfig, cache: Any) -> Any:
        cspecs = axes.cache_specs(cfg, cache, self.mode, self.mesh)
        return axes.to_named_fit(self.mesh, cspecs, cache)

    # ---- in-trace constraints -------------------------------------------
    def constrain_params(self, cfg: ModelConfig, params: Any) -> Any:
        specs = axes.param_specs(cfg, self.mode, self.mesh)
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, self._fit(s, x.shape)),
            params, specs, is_leaf=lambda x: isinstance(x, P))

    def constrain_cache(self, cfg: ModelConfig, cache: Any) -> Any:
        specs = axes.cache_specs(cfg, cache, self.mode, self.mesh)
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, self._fit(s, x.shape)),
            cache, specs, is_leaf=lambda x: isinstance(x, P))

    def microbatch_constraint(self, cfg: ModelConfig,
                              grad_accum: int) -> Optional[Any]:
        """The ``mb_constraint`` hook for ``train_step`` — one shared
        construction site so the runtime step and the dry-run lowering
        can never disagree about grad-accum sharding."""
        if grad_accum <= 1:
            return None
        return functools.partial(self.constrain_microbatches, cfg)

    def constrain_microbatches(self, cfg: ModelConfig,
                               mbs: Dict[str, Any]) -> Dict[str, Any]:
        """Pin the reshaped grad-accum tree (accum, mb, ...) so each
        microbatch stays data-sharded on its own axis. Without this GSPMD
        propagates the global-batch sharding onto the scanned *accum* axis
        and replicates every microbatch slice (the PR-2 lesson: reshapes
        across the data axis must be re-constrained shard-local)."""
        specs = axes.batch_specs(cfg, self.mesh)
        return {k: jax.lax.with_sharding_constraint(
                    v, self._fit(P(None, *specs[k]), v.shape))
                for k, v in mbs.items()}

    # ---- placement / gather ---------------------------------------------
    def init_params(self, cfg: ModelConfig, key: jax.Array) -> Any:
        """Random-init params (``repro.models.init_params``'s values) born
        on the plan's shardings, in one program: no leaf ever sits whole
        on one device, and no per-leaf f32 temporaries pile up behind
        asynchronous eager dispatch."""
        return _init_fn(self, cfg)(key)

    def device_put_params(self, cfg: ModelConfig, params: Any, *,
                          copy: bool = False) -> Any:
        """Place a param tree onto the plan. ``copy=True`` forces fresh
        buffers (via host) — required when the source tree belongs to a
        node whose step donates its buffers (e.g. a sampler keeping its
        own replica of learner params)."""
        sh = self.param_shardings(cfg)
        src = (jax.tree_util.tree_map(np.asarray, params) if copy
               else params)
        return jax.tree_util.tree_map(jax.device_put, src, sh)

    def device_put_state(self, cfg: ModelConfig, state: Any,
                         optimizer: str = "adamw", *,
                         copy: bool = False) -> Any:
        """Place a ``TrainState`` onto the plan. ``copy=True`` gives the
        caller-owned buffers a fresh on-device twin first (``jnp.copy``)
        — required by nodes whose train step donates the state while the
        source (e.g. a shared warm start) stays live elsewhere."""
        sh = self.state_shardings(cfg, optimizer)
        src = jax.tree_util.tree_map(jnp.copy, state) if copy else state
        return jax.tree_util.tree_map(jax.device_put, src, sh)

    def device_put_batch(self, cfg: ModelConfig,
                         batch: Dict[str, Any]) -> Dict[str, Any]:
        sh = self.batch_shardings(cfg, batch)
        return {k: jax.device_put(v, sh[k]) for k, v in batch.items()}

    @staticmethod
    def host_gather(tree: Any) -> Any:
        """Gather a (possibly sharded) pytree to host numpy arrays — the
        publish half of the checkpoint round-trip."""
        return jax.tree_util.tree_map(np.asarray, tree)


# Fitted-tree builders are pure in (plan, cfg[, optimizer]) — all
# hashable — and O(param leaves) of host-side spec resolution, so they
# are memoized here (device_put_params runs once per run_online step).
@functools.lru_cache(maxsize=64)
def _param_shardings(plan: ExecutionPlan, cfg: ModelConfig) -> Any:
    return axes.to_named_fit(plan.mesh,
                             axes.param_specs(cfg, plan.mode, plan.mesh),
                             abstract_params(cfg))


@functools.lru_cache(maxsize=8)
def _init_fn(plan: ExecutionPlan, cfg: ModelConfig):
    from repro.models import init_params
    return jax.jit(functools.partial(init_params, cfg),
                   out_shardings=_param_shardings(plan, cfg))


@functools.lru_cache(maxsize=64)
def _state_shardings(plan: ExecutionPlan, cfg: ModelConfig,
                     optimizer: str) -> Any:
    from repro.training import TrainState
    p_avals = abstract_params(cfg)
    init = adamw_init if optimizer == "adamw" else adafactor_init
    opt_avals = jax.eval_shape(init, p_avals)
    avals = TrainState(params=p_avals, opt=opt_avals,
                       step=jax.ShapeDtypeStruct((), jnp.int32))
    pspecs = axes.param_specs(cfg, plan.mode, plan.mesh)
    specs = TrainState(params=pspecs,
                       opt=axes.opt_specs(pspecs, optimizer),
                       step=P())
    return axes.to_named_fit(plan.mesh, specs, avals)


def make_plan(mesh: Optional[jax.sharding.Mesh] = None,
              mode: str = "train") -> ExecutionPlan:
    return ExecutionPlan(mesh=mesh if mesh is not None else local_mesh(),
                         mode=mode)


@functools.lru_cache(maxsize=8)
def local_plan(mode: str = "train") -> ExecutionPlan:
    """Single-device (1×1 mesh) plan — the default execution path."""
    return ExecutionPlan(mesh=local_mesh(), mode=mode)


@functools.lru_cache(maxsize=32)
def plan_from_flag(spec: Optional[str], mode: str) -> ExecutionPlan:
    """Plan from a ``--mesh``/config knob ("DxM" or "PxDxM"); None or
    "1x1" gives the local plan."""
    if spec is None or spec in ("", "1x1"):
        return local_plan(mode)
    return ExecutionPlan(mesh=mesh_from_flag(spec), mode=mode)


def plan_for_params(params: Any, mode: str = "serve") -> ExecutionPlan:
    """Plan matching the mesh a param tree already lives on — the default
    for callers (eval, ad-hoc generation) that receive placed params
    rather than a plan. Falls back to the local plan for single-device
    arrays."""
    leaves = jax.tree_util.tree_leaves(params)
    mesh = getattr(getattr(leaves[0], "sharding", None), "mesh", None) \
        if leaves else None
    if isinstance(mesh, jax.sharding.Mesh):
        return ExecutionPlan(mesh=mesh, mode=mode)
    return local_plan(mode)
