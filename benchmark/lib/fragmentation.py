"""Quantities and the packing arithmetic, in the benchmark's own code.

`fragmentation_occupied_pct` is the benchmark's copy of
`PerfRunner._fragmentation_occupied` (perf/scheduler_perf.py): the mean,
over nodes that hold at least one pod, of the free share of allocatable
averaged over the node's resources (the pod count is not a resource
there, and is not one here). It works from bindings the client saw, not
from the scheduler's cache.
"""

from __future__ import annotations

import re
from typing import Mapping

import numpy as np

_QTY = re.compile(r"^([+-]?[0-9.]+(?:[eE][+-]?[0-9]+)?)([A-Za-z]*)$")
_SUFFIX = {"": 1.0, "m": 1e-3, "k": 1e3, "M": 1e6, "G": 1e9, "T": 1e12,
           "Ki": 2.0 ** 10, "Mi": 2.0 ** 20, "Gi": 2.0 ** 30,
           "Ti": 2.0 ** 40}


def milli(quantity) -> int:
    """A Kubernetes quantity ("100m", "250Mi", "8", 2) in integer
    thousandths of its unit."""
    if isinstance(quantity, bool) or quantity is None:
        raise ValueError(f"not a quantity: {quantity!r}")
    if isinstance(quantity, (int, float)):
        return round(quantity * 1000)
    m = _QTY.match(str(quantity))
    if not m or m.group(2) not in _SUFFIX:
        raise ValueError(f"not a quantity: {quantity!r}")
    return round(float(m.group(1)) * _SUFFIX[m.group(2)] * 1000)


def resource_vector(resources: Mapping, names: list[str]) -> np.ndarray:
    """`resources` ({"cpu": "8", ...}) as int64 thousandths in the order
    of `names`; a resource that is not named is 0."""
    return np.array([milli(resources[n]) if n in resources else 0
                     for n in names], dtype=np.int64)


def fragmentation_occupied_pct(alloc: np.ndarray, used: np.ndarray,
                               pods_on_node: np.ndarray) -> float:
    """alloc, used: [nodes, resources] in the same units; pods_on_node:
    [nodes]. Empty cluster → 0.0."""
    occupied = pods_on_node > 0
    if not occupied.any():
        return 0.0
    a = alloc[occupied].astype(np.float64)
    u = used[occupied].astype(np.float64)
    has = a > 0
    free = np.where(has, np.maximum(0.0, (a - u) / np.where(has, a, 1.0)),
                    0.0)
    n_res = has.sum(axis=1)
    per_node = np.where(n_res > 0, free.sum(axis=1) / np.maximum(n_res, 1),
                        1.0)
    # sorted, so that the same multiset of nodes sums to the same digits
    return float(100.0 * np.sort(per_node).mean())
