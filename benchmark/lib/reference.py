"""The plain reference: a cluster in numpy, the default scheduler's
policy in a loop, and the comparison that decides `correct`.

It imports nothing of the program and takes nothing the program made:
the cluster is rebuilt from the configuration file, and the bindings it
judges are the ones the benchmark's own watch saw over the wire.

Guarantees held (the configuration files state them):

- every acknowledged create ends bound, exactly once (`unbound`,
  `bound_twice`);
- no node is bound past its allocatable in any resource or in pods, and
  every binding names a node of the cluster (`nodes_over_allocatable`,
  `unknown_node`);
- the client reads back through the wire the binding its watch showed
  (`readback_mismatch`);
- placements are the device path's (`not_device_placed`).

Which feasible node a pod got is not compared here: with one pod
template on identical nodes, occupied-node fragmentation depends only on
how many nodes are occupied, and the default scheduler below occupies as
many as there are — no feasible placement packs worse than it. Packing
is held by the end-to-end metric `frag_occupied_pct` and its bound.

`Placer` is kube-scheduler's documented default score set for
these pods — NodeResourcesFit/LeastAllocated plus
NodeResourcesBalancedAllocation, equal weights, over cpu and memory —
one pod at a time, highest score wins, lowest node index on ties. It is
the plain statement of the semantics, and with `stale_chunk` the
control that has to fail (tests/benchmark, benchmark/control.py).
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.fragmentation import (
    fragmentation_occupied_pct,
    resource_vector,
)

#: resources the default score set weighs, and fragmentation averages
SCORED = ["cpu", "memory"]


class ClusterModel:
    """The deployment a configuration states: identical nodes named
    `node-<i>`, one pod template."""

    def __init__(self, config: dict):
        self.n_nodes = int(config["nodes"])
        alloc = config["node_template"]["allocatable"]
        self.resources = sorted(k for k in alloc if k != "pods")
        self.alloc = resource_vector(alloc, self.resources)
        self.alloc_pods = int(alloc["pods"])
        self.request = resource_vector(
            config["pod_template"]["requests"], self.resources)
        self._scored = [self.resources.index(r) for r in SCORED
                        if r in self.resources]

    def node_index(self, name: str) -> int:
        """-1 for a name that is no node of this cluster."""
        head, _, tail = name.rpartition("-")
        if head != "node" or not tail.isdigit():
            return -1
        i = int(tail)
        return i if i < self.n_nodes else -1

    def occupancy(self, node_indexes: np.ndarray):
        """(used [nodes, resources], pods [nodes]) after these bindings."""
        pods = np.bincount(node_indexes, minlength=self.n_nodes)
        used = pods[:, None].astype(np.int64) * self.request[None, :]
        return used, pods

    def fragmentation(self, node_indexes: np.ndarray) -> float:
        used, pods = self.occupancy(node_indexes)
        alloc = np.broadcast_to(self.alloc, used.shape)
        return fragmentation_occupied_pct(
            alloc[:, self._scored], used[:, self._scored], pods)

    # -- the default scheduler, one pod at a time --------------------------

    def _score(self, pods_on: np.ndarray) -> np.ndarray:
        """Score of placing one more pod on each node; -inf where it
        does not fit."""
        after = (pods_on[:, None] + 1) * self.request[None, :]
        fits = (after <= self.alloc[None, :]).all(axis=1) \
            & (pods_on + 1 <= self.alloc_pods)
        frac = after[:, self._scored] / self.alloc[self._scored]
        least = 100.0 * (1.0 - frac).mean(axis=1)
        balanced = 100.0 * (1.0 - frac.std(axis=1))
        return np.where(fits, least + balanced, -np.inf)

    def placer(self, stale_chunk: int = 1) -> "Placer":
        return Placer(self, stale_chunk)


class Placer:
    """The default scheduler, one pod at a time: `place()` gives the node
    index of the next pod (-1: none fits). `stale_chunk` > 1 is the
    CONTROL: it looks at the cluster only once every that many pods and
    places the whole chunk by that one look — the fault of a solve that
    does not carry its own placements forward."""

    def __init__(self, model: ClusterModel, stale_chunk: int = 1):
        self.model = model
        self.stale_chunk = int(stale_chunk)
        self.pods_on = np.zeros(model.n_nodes, dtype=np.int64)
        self.score = model._score(self.pods_on)
        self.placed = 0

    def place(self) -> int:
        stale = self.stale_chunk > 1
        if stale and self.placed % self.stale_chunk == 0:
            self.score = self.model._score(self.pods_on)
        best = int(np.argmax(self.score))
        if self.score[best] == -np.inf:
            return -1
        self.pods_on[best] += 1
        self.placed += 1
        if not stale:
            self.score[best] = self.model._score(
                self.pods_on[best:best + 1])[0]
        return best


def check(model: ClusterModel, *, created: list[str], bound: dict[str, str],
          rebound: list[str], readback: dict[str, str | None],
          not_device_placed: int) -> dict:
    """Every number compared, each beside its limit:
    {name: {"value": v, "limit": l}}. `created` are the pod keys whose
    create was acknowledged; `bound` what the watch saw; `readback` what
    a GET through the wire returned for a sample of them."""
    idx = np.array([model.node_index(n) for n in bound.values()],
                   dtype=np.int64)
    known = idx[idx >= 0]
    used, pods = model.occupancy(known)
    over = ((used > model.alloc[None, :]).any(axis=1)
            | (pods > model.alloc_pods))
    numbers = {
        "unbound": sum(1 for k in created if k not in bound),
        "bound_twice": len(rebound),
        "unknown_node": int((idx < 0).sum()),
        "nodes_over_allocatable": int(over.sum()),
        "readback_mismatch": sum(
            1 for k, node in readback.items() if bound.get(k) != node),
        "not_device_placed": int(not_device_placed),
    }
    out = {k: {"value": v, "limit": 0} for k, v in numbers.items()}
    return out


def is_correct(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())
