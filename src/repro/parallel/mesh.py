"""Device meshes for every execution scale.

Production target is TPU v5e: a single pod is 256 chips as
(data=16, model=16); multi-pod is 2 pods × 256 chips as
(pod=2, data=16, model=16) — the ``pod`` axis is the slow inter-pod
(DCN/WAN) dimension; HeteroRL's design keeps cross-pod traffic to
checkpoint broadcast + rollout streaming, but the dry-run also proves the
*learner step itself* shards across pods.

``local_mesh`` is the degenerate (data=1, model=1) mesh every runtime path
uses when no parallelism is requested — one code path for 1 and N devices.
``mesh_from_flag`` parses the ``--mesh DxM`` / ``PxDxM`` CLI form; host
testing at D·M > 1 needs ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
exported before the first jax import.

Defined as functions (never module-level constants) so importing this
module does not touch jax device state.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
from jax.sharding import AxisType


# Published per-chip peaks keyed by ``Device.device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
# ICI over 4 links). A device missing here is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# The production mesh below is a v5e pod; the dry-run models that chip.
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def peak_rates(device_kind: str) -> Dict[str, float]:
    """Per-chip peak FLOP/s and bytes/s of ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}") from None


def make_mesh(shape: Tuple[int, ...],
              names: Tuple[str, ...]) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: placement is propagated
    by GSPMD from the plan's in/out shardings and in-trace constraints,
    which ``Explicit`` axes (the ``jax.make_mesh`` default) reject."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *,
                    multi_pod: bool = False) -> jax.sharding.Mesh:
    """Small mesh for CI-scale dry-run tests (requires
    --xla_force_host_platform_device_count >= product)."""
    if multi_pod:
        return make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return make_mesh((n_data, n_model), ("data", "model"))


@functools.lru_cache(maxsize=1)
def local_mesh() -> jax.sharding.Mesh:
    """The (data=1, model=1) mesh backing single-device execution plans.
    Cached so every caller sees the same Mesh object (stable jit keys)."""
    return make_mesh((1, 1), ("data", "model"))


def mesh_from_flag(spec: str) -> jax.sharding.Mesh:
    """Parse a ``DxM`` (or ``PxDxM`` multi-pod) mesh spec, e.g. "1x1",
    "2x4", "2x2x2". Validates against the visible device count with the
    host-device-count recipe in the error."""
    try:
        dims = tuple(int(p) for p in spec.lower().split("x"))
    except ValueError:
        dims = ()
    if len(dims) not in (2, 3) or any(d < 1 for d in dims):
        raise ValueError(f"mesh spec {spec!r}: expected DxM or PxDxM "
                         "positive integers, e.g. '2x2' or '2x2x2'")
    need = 1
    for d in dims:
        need *= d
    have = len(jax.devices())
    if need > have:
        raise RuntimeError(
            f"mesh {spec} needs {need} devices but only {have} visible — "
            "on CPU export XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={need} before the first jax import")
    if len(dims) == 2:
        if dims == (1, 1):
            return local_mesh()
        return make_mesh(dims, ("data", "model"))
    return make_mesh(dims, ("pod", "data", "model"))


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return (("pod", "data") if "pod" in mesh.axis_names else ("data",))
