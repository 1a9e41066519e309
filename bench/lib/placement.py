"""Where the benchmark's own arrays live: the reference's weights, its
float32 gradient and optimizer statistics, and the initial weights drawn
again to measure a change.

On one chip there is no mesh: arrays stay on the default device and
every program is the one-device program. On more, a mesh of one axis
over the cell's chips, with each leaf split on its largest axis that the
number of chips divides (the first such, where two are as large), so
that a layer's matmuls split as in tensor parallelism; a leaf with none
is held whole on every chip. The embedding and the LM head (and their
statistics) are split on their smallest such axis, the hidden one: the
reference reads them over the published vocabulary, a slice of the
padded one whose pieces would not line up with the padded one's. GSPMD
places the rest.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

AXIS = "chips"
VOCAB_LEAVES = ("embed", "lm_head")


def mesh(chips: int, devices: Optional[Sequence[Any]] = None
         ) -> Optional[Mesh]:
    """The mesh over the first ``chips`` devices (of ``devices``, by
    default this host's); None for one chip."""
    if chips == 1:
        return None
    devs = list(jax.devices() if devices is None else devices)[:chips]
    if len(devs) < chips:
        raise ValueError(f"{chips} chips asked for, {len(devs)} found")
    return Mesh(np.array(devs), (AXIS,), axis_types=(AxisType.Auto,))


def leaf_sharding(m: Mesh, name: str, shape: Tuple[int, ...]
                  ) -> NamedSharding:
    """The split of leaf ``name`` (or of its optimizer statistic)."""
    n = m.devices.size
    axes = [i for i, s in enumerate(shape) if s % n == 0]
    spec = [None] * len(shape)
    if axes:
        pick = min if name in VOCAB_LEAVES else max
        spec[pick(axes, key=lambda i: shape[i])] = AXIS
    return NamedSharding(m, PartitionSpec(*spec))


def constrain(tree: Any, m: Optional[Mesh]) -> Any:
    """Inside a jitted function: pin each leaf of ``tree``, a dict by
    leaf name or a tuple of such, to its split (nothing on one chip)."""
    if m is None:
        return tree
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jax.lax.with_sharding_constraint(
            a, leaf_sharding(m, path[-1].key, a.shape)), tree)


def zeros(name: str, shape: Tuple[int, ...], m: Optional[Mesh]
          ) -> jax.Array:
    """float32 zeros, split as leaf ``name`` of that shape is."""
    if m is None:
        return jnp.zeros(shape, jnp.float32)
    return jnp.zeros(shape, jnp.float32,
                     device=leaf_sharding(m, name, shape))


def host(x: Any, m: Optional[Mesh]) -> jax.Array:
    """Host data for the benchmark's programs: on the default device, or
    whole on every chip of the mesh."""
    if m is None:
        return jnp.asarray(x)
    return jax.device_put(np.asarray(x), NamedSharding(m, PartitionSpec()))


def spread(a: jax.Array) -> Optional[jax.sharding.Sharding]:
    """An array's sharding where it spans more than one device, else None
    (one device: the one-device program)."""
    return a.sharding if len(a.sharding.device_set) > 1 else None
