"""Back-compat shim: meshes now live in ``repro.parallel.mesh`` (the
unified execution layer owns placement for train, sample, and dry-run)."""
from repro.parallel.mesh import (data_axes, local_mesh,  # noqa: F401
                                 make_debug_mesh, make_production_mesh,
                                 mesh_from_flag)

__all__ = ["make_production_mesh", "make_debug_mesh", "local_mesh",
           "mesh_from_flag", "data_axes"]
