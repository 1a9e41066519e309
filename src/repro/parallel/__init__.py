"""Unified sharded execution layer.

One mesh/sharding path for train, sample, and dry-run: logical-axis rules
(``axes``) + meshes (``mesh``) feed an ``ExecutionPlan`` (``plan``) that
every executing surface — learner train step, sampler engines, checkpoint
round-trips, the lowering-only dry-run — consumes for placement.
"""
from repro.parallel.mesh import (PEAKS, data_axes, local_mesh,
                                 make_debug_mesh, make_mesh,
                                 make_production_mesh, mesh_from_flag,
                                 peak_rates)
from repro.parallel.plan import (ExecutionPlan, local_plan, make_plan,
                                 plan_for_params, plan_from_flag)
from repro.parallel.step import make_sharded_sft_step, make_sharded_train_step

__all__ = [
    "ExecutionPlan", "make_plan", "local_plan", "plan_from_flag",
    "plan_for_params",
    "make_sharded_train_step", "make_sharded_sft_step",
    "make_mesh", "make_production_mesh", "make_debug_mesh", "local_mesh",
    "mesh_from_flag", "data_axes", "PEAKS", "peak_rates",
]
