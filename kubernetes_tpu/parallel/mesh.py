"""Device-mesh construction for the scheduling tensors.

SURVEY §2.8/§5.7: the reference scales the Filter/Score fan-out with 16
goroutines over the node list (framework/parallelize) and samples nodes
(`percentageOfNodesToScore`) when clusters get big. The TPU design instead
shards the node axis of the scheduling tensors over a `jax.sharding.Mesh`
(`TPUBackend(mesh=...)`: `NamedSharding` on every node-axis array, the one
fused program partitioned by XLA, which inserts the cross-shard reductions
of the per-step argmax):

- **nodes axis** across chips within a slice (ICI);
- multi-slice DCN adds an outer **slice axis** to the same specs (the
  50k-node config #5 path); the code below is mesh-size-agnostic — 1 chip
  is just a (1,)-shaped mesh (SURVEY §7 hard-part #6).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

NODES_AXIS = "nodes"
SLICE_AXIS = "slice"


def build_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the node axis (the solver's axis)."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"requested {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (NODES_AXIS,))


def build_multislice_mesh(n_slices: int,
                          chips_per_slice: int | None = None) -> Mesh:
    """(slice × nodes) mesh — BASELINE config #5's 50k-node shape.

    The outer `slice` axis maps to DCN (cross-slice traffic), the inner
    `nodes` axis to ICI within a slice; the cluster's node dimension is
    sharded over BOTH (flattened slice-major) and XLA's partitioner
    places the cross-shard reductions. Under the real multi-slice
    runtime `jax.devices()` orders devices slice-major so rows land on
    physical slices; on the virtual CPU mesh the grouping is positional
    (what the dryrun proves)."""
    devs = jax.devices()
    if chips_per_slice is None:
        if len(devs) % n_slices:
            raise ValueError(
                f"{len(devs)} devices don't divide into {n_slices} slices")
        chips_per_slice = len(devs) // n_slices
    total = n_slices * chips_per_slice
    if total > len(devs):
        raise ValueError(f"requested {total} devices, have {len(devs)}")
    # The backend pads the node axis to multiples of NODE_PAD (256); a
    # shard count that doesn't divide it fails deep inside XLA sharding —
    # surface it here instead.
    from kubernetes_tpu.ops.tensorize import NODE_PAD
    if NODE_PAD % total:
        raise ValueError(
            f"{n_slices}x{chips_per_slice}={total} shards must divide "
            f"NODE_PAD={NODE_PAD} (use a power-of-two shard count)")
    arr = np.array(devs[:total]).reshape(n_slices, chips_per_slice)
    return Mesh(arr, (SLICE_AXIS, NODES_AXIS))
