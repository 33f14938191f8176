"""The least work of a chunk solve whose InterPodAffinity score moves with
its own placements (the carried scan), as a function of the problem only.

On top of the chunk's least work (lib/work_model.py, unchanged), every
pod whose score the chunk carries is one serial step that has at least
to read its raw InterPodAffinity weights over the N nodes and the N
counts the chunk's earlier placements left, write the counts back, and
per node add the two, leave the infeasible nodes out of the maximum and
the minimum, take both, subtract, divide and accumulate the weighted
result into the node's score: CARRY_OPS_PER_NODE operations. A solve
that touches fewer than N nodes a step would beat this model.
"""

from __future__ import annotations

from benchmark.lib import work_model

#: add, mask, max, min, subtract, divide, accumulate
CARRY_OPS_PER_NODE = 7
#: values of N a carried step reads (weights, counts) and writes (counts)
CARRY_PLANES = 3


def carried_solve_work(nodes: int, resources: int, pods: int, classes: int,
                       chunks: int, steps: int) -> tuple[float, float]:
    """(operations, bytes) of `chunks` solves placing `pods` pods, of
    which `steps` pods had their InterPodAffinity score carried."""
    ops, bytes_ = work_model.solve_work(
        nodes=nodes, resources=resources, pods=pods, classes=classes,
        chunks=chunks)
    ops += float(steps) * nodes * CARRY_OPS_PER_NODE
    bytes_ += float(steps) * nodes * CARRY_PLANES \
        * work_model.BYTES_PER_VALUE
    return ops, bytes_
