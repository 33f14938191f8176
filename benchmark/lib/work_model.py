"""The least work a batch solve needs, as a function of the PROBLEM only.

A solve places P pending pods of C distinct classes (a class = pods with
the same requests and constraints) on N nodes with R resources each. Any
implementation — scans, Sinkhorn, a Pallas kernel, a shortlist, a block
index — has at least to, once per chunk it is handed:

- read the node-state planes (allocatable and used: 2 x N x R values)
  and write the used plane back (N x R);
- read the C class request rows (C x R) and write P results;
- decide feasibility and score of every (class, node, resource) cell:
  one compare, one subtract, one divide and one accumulate, C x N x R
  times.

Values are 4 bytes. Nothing here depends on the route taken or on a
shape the program chooses (shortlist width, block width, wave width,
Sinkhorn iterations, padding): the same problem reads the same work. A
solve that keeps state on the device so that a chunk touches fewer than
N nodes would beat this model; re-basing it is a `benchmark` issue's.
"""

from __future__ import annotations

BYTES_PER_VALUE = 4
OPS_PER_CELL = 4


def solve_work(nodes: int, resources: int, pods: int, classes: int,
               chunks: int) -> tuple[float, float]:
    """(operations, bytes) of `chunks` solves that place `pods` pods in
    all."""
    plane = nodes * resources
    values = chunks * (3 * plane + classes * resources) + pods
    ops = chunks * classes * plane * OPS_PER_CELL
    return float(ops), float(values * BYTES_PER_VALUE)


def least_seconds(ops: float, bytes_: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_ops = ops / peak["flops_per_s"]
    by_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops > by_bytes \
        else (by_bytes, "bytes")
