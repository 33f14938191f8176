"""Label-set interning: the dense bridge for irregular label algebra.

SURVEY §7 hard-part #2: label-selector matching is set algebra over
irregular data. The observation that makes it dense: resident pods come from
templates, so the number of DISTINCT (namespace, label-dict) signatures is
tiny (tens) even in 150k-pod clusters. Interning signatures turns
"pods × selector" matching into:

    node_sig_count (N × U)   — how many resident pods of signature u on node n
    match_vec      (U,)      — does signature u match this selector (host,
                               U evaluations of the exact host Selector)
    counts (N,) = node_sig_count @ match_vec      — MXU-shaped

Topology domains intern the same way: `domain_ids (N,)` for a topology key
maps nodes to dense domain indices, so per-domain aggregation is a
segment-sum and per-node lookup is a gather — the affinity kernels
(ops/affinity.py) are built entirely from these three primitives.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from kubernetes_tpu.api.labels import from_label_selector, ns_contains
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo, Snapshot


def _sig(pi: PodInfo) -> tuple:
    return (pi.namespace, tuple(sorted(pi.labels.items())))


def came_and_gone(seen: list[PodInfo], now: list[PodInfo]):
    """The pods that came to one of a node's pod lists and the ones that
    went since it read `seen` — THE rule of every table advanced by the
    changed-node log. A pod is its PodInfo object: an update replaces it
    and counts as one of each. The cache replaces a changed node's clone,
    so a list read once is never written again."""
    k = len(seen)
    if len(now) >= k and now[:k] == seen:
        return now[k:], ()              # nothing left: the new tail
    had, have = set(seen), set(now)
    return ([pi for pi in now if pi not in had],
            [pi for pi in seen if pi not in have])


class LabelSigTable:
    """Unique (namespace, labels) signatures of resident pods and how many
    pods of each sit on each node: `node_sig_count` (n_pad, U).

    Built by one walk over every resident pod, then ADVANCED: `recount`
    rewrites only the rows of the nodes a later snapshot names as changed
    (the scheduler cache's changed-node log) by the pods that came to or
    left each, so keeping the table current costs one signature per pod
    that moved, not one per resident pod. A new signature appends a column
    (capacity doubles, so appends amortise); a signature whose last pod
    left keeps its all-zero column. Counts are small integers in float32
    and matching sums them per selector, so neither column order nor zero
    columns move `node_sig_count @ match_vec` by a bit against a freshly
    built table."""

    def __init__(self, snapshot: Snapshot, n_pad: int):
        self.sigs: dict[tuple, int] = {}
        self.sig_examples: list[PodInfo] = []   # one pod per signature
        #: (n_pad, capacity ≥ U) backing store; `node_sig_count` is its
        #: first U columns.
        self._buf = np.zeros((n_pad, 4), dtype=np.float32)
        #: per node, the pod list its row was last counted from (the
        #: cache REPLACES a changed node's clone: a list counted once is
        #: never written again)
        self._counted: list[list[PodInfo]] = [[]] * len(snapshot.nodes)
        #: selector-signature -> (U,) match vector cache
        self._match_cache: dict[str, np.ndarray] = {}
        self.recount(snapshot.nodes, range(len(snapshot.nodes)))

    @property
    def node_sig_count(self) -> np.ndarray:
        return self._buf[:, :max(1, len(self.sig_examples))]

    def recount(self, nodes: Sequence[NodeInfo], rows) -> None:
        """Bring the rows of node indices `rows` up to `nodes[i].pods`: add
        the pods that came since the row was last counted, take off the
        ones that went (`came_and_gone`)."""
        at_n: list[int] = []
        at_u: list[int] = []
        by: list[float] = []
        for n in rows:
            pods = nodes[n].pods
            came, gone = came_and_gone(self._counted[n], pods)
            self._counted[n] = pods
            for moved, step in ((came, 1.0), (gone, -1.0)):
                for pi in moved:
                    at_n.append(n)
                    at_u.append(self._intern(pi))
                    by.append(step)
        if at_n:
            np.add.at(self._buf, (at_n, at_u), by)

    def _intern(self, pi: PodInfo) -> int:
        s = _sig(pi)
        u = self.sigs.get(s)
        if u is None:
            u = self.sigs[s] = len(self.sig_examples)
            self.sig_examples.append(pi)
            if u >= self._buf.shape[1]:
                grown = np.zeros((self._buf.shape[0], 2 * u),
                                 dtype=np.float32)
                grown[:, :u] = self._buf
                self._buf = grown
            self._match_cache.clear()  # cached vectors are one short
        return u

    def match_vec(self, label_selector: Mapping | None,
                  namespaces: Sequence[str]) -> np.ndarray:
        """(U,) float32: 1.0 where the signature's namespace ∈ namespaces and
        its labels match the selector — the exact host Selector semantics.
        `namespaces` may be labels.ALL_NAMESPACES ("*",) = every namespace."""
        key = repr((label_selector, tuple(namespaces)))
        vec = self._match_cache.get(key)
        if vec is None:
            sel = from_label_selector(label_selector)
            nset = set(namespaces)
            vec = np.zeros((max(1, len(self.sig_examples)),), dtype=np.float32)
            for u, pi in enumerate(self.sig_examples):
                if ns_contains(nset, pi.namespace) and sel.matches(pi.labels):
                    vec[u] = 1.0
            self._match_cache[key] = vec
        return vec


class TopologyTable:
    """Per-topology-key dense domain ids (lazily built, cached). They read
    node labels alone, so they outlive every snapshot of one node set whose
    node objects did not change; `nodes` is then pointed at the newest."""

    def __init__(self, nodes: Sequence[NodeInfo], n_pad: int):
        self.nodes = nodes
        self._n_pad = n_pad
        self._cache: dict[str, tuple[np.ndarray, int]] = {}

    def domains(self, topology_key: str) -> tuple[np.ndarray, int]:
        """(domain_ids (n_pad,) int32, num_domains). Nodes WITHOUT the key
        get the reserved domain 0 ("no domain" — always treated separately
        via the has_key mask); real domains start at 1."""
        got = self._cache.get(topology_key)
        if got is None:
            ids = np.zeros((self._n_pad,), dtype=np.int32)
            interned: dict[str, int] = {}
            for n, ni in enumerate(self.nodes):
                v = ni.labels.get(topology_key)
                if v is None:
                    continue
                d = interned.get(v)
                if d is None:
                    d = interned[v] = len(interned) + 1
                ids[n] = d
            got = (ids, len(interned) + 1)
            self._cache[topology_key] = got
        return got

    def has_key(self, topology_key: str) -> np.ndarray:
        return self.domains(topology_key)[0] > 0
