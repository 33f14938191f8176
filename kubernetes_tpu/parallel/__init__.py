"""Device meshes for the scheduling tensors (SURVEY §2.8 / §5.7)."""

from kubernetes_tpu.parallel.mesh import (
    NODES_AXIS,
    SLICE_AXIS,
    build_mesh,
    build_multislice_mesh,
)

__all__ = ["NODES_AXIS", "SLICE_AXIS", "build_mesh", "build_multislice_mesh"]
