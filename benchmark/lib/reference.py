"""The plain reference: a cluster in numpy, the default scheduler's
policy in a loop, and the comparison that decides `correct`.

It imports nothing of the program and takes nothing the program made:
the cluster is rebuilt from the configuration file, the pods are the
arguments the benchmark's own generator created them from, and the
bindings it judges are the ones the benchmark's own watch saw over the
wire.

`ClusterModel` is a DEPLOYMENT: what a configuration file means. As it
stands it is the default one, which a configuration that names no
`deployment` gets — `nodes` identical nodes named `node-<i>` from
`node_template`, `pod_template` for every pod of every phase. A
configuration that names one gets the `Deployment` class of
`benchmark/deployments/<name>.py` (lib/manifest.py finds it by name), a
subclass that overrides what differs:

    nodes()            the cluster to stage: (name, make_node arguments)
    pods(phase, names) the make_pod arguments of each pod of a phase
                       (`init`, `warm`, `burst`, `measured`)
    own_numbers(...)   the deployment's own guarantees, each a number
                       beside its limit, after the generic six
    placer(sound)      the reference as a live scheduler (the control)
    problem()          what a reader may ask: nodes, resources, classes

Guarantees held for every deployment, limit 0, under these names:

- every acknowledged create ends bound, exactly once (`unbound`,
  `bound_twice`);
- no node is bound past its allocatable in any resource or in pods —
  summed from what EACH bound pod requested — and every binding names a
  node of the cluster (`nodes_over_allocatable`, `unknown_node`);
- the client reads back through the wire the binding its watch showed
  (`readback_mismatch`);
- placements are the device path's (`not_device_placed`).

Which feasible node a pod got is not compared by the default: with one
pod template on identical nodes, occupied-node fragmentation depends
only on how many nodes are occupied, and the default scheduler below
occupies as many as there are — no feasible placement packs worse than
it. Packing is held by the end-to-end metric `frag_occupied_pct` and
its bound. A deployment whose pods carry a constraint states what the
constraint forbids in `own_numbers`.

`Placer` is kube-scheduler's documented default score set —
NodeResourcesFit/LeastAllocated plus NodeResourcesBalancedAllocation,
equal weights, over cpu and memory — one pod at a time, highest score
wins, lowest node index on ties. It is the plain statement of the
semantics, and with `stale_chunk` the default control that has to fail
(tests/benchmark, benchmark/control.py).
"""

from __future__ import annotations

import numpy as np

from benchmark.lib.fragmentation import (
    fragmentation_occupied_pct,
    resource_vector,
)

#: resources the default score set weighs, and fragmentation averages
SCORED = ["cpu", "memory"]


def pod_key(pod: dict) -> str:
    """`namespace/name` of a pod object, as the store keys it."""
    meta = pod["metadata"]
    return f"{meta.get('namespace', 'default')}/{meta['name']}"


def pod_requests(pod: dict) -> dict:
    """What a pod object asks of a node: the requests of the one
    container `make_pod` gives it."""
    container, = pod["spec"]["containers"]
    return (container.get("resources") or {}).get("requests") or {}


def _by_identity(items: list) -> tuple[list, np.ndarray]:
    """(distinct, which): the DISTINCT objects of `items` (the same
    template object serves thousands of pods or nodes) and, for each
    item, its place among them."""
    slot: dict[int, int] = {}
    distinct: list = []
    which = np.empty(len(items), dtype=np.int64)
    for j, item in enumerate(items):
        s = slot.get(id(item))
        if s is None:
            s = slot[id(item)] = len(distinct)
            distinct.append(item)
        which[j] = s
    return distinct, which


class ClusterModel:
    """The default deployment, and the base of every other (see the
    module's docstring)."""

    #: pods the default control places by one look at the cluster: the
    #: program's chunk width
    stale_chunk = 1024

    def __init__(self, config: dict):
        self.config = config
        staged = self.nodes()
        self.node_names = [name for name, _ in staged]
        self._index = {name: i for i, name in enumerate(self.node_names)}
        self.n_nodes = len(staged)
        allocs = [kw["allocatable"] for _, kw in staged]
        distinct, which = _by_identity(allocs)
        self.resources = sorted({r for a in distinct for r in a
                                 if r != "pods"})
        self.alloc = np.array(
            [resource_vector(a, self.resources) for a in distinct])[which]
        self.alloc_pods = np.array(
            [int(a["pods"]) for a in distinct], dtype=np.int64)[which]
        self._scored = [self.resources.index(r) for r in SCORED
                        if r in self.resources]

    # -- what the generator stages and creates ------------------------------

    def nodes(self) -> list[tuple[str, dict]]:
        """The cluster: a name and `make_node` arguments for each node
        (with `allocatable`). Shared argument objects are never written
        to: the generator copies them for each object it makes."""
        template = self.config["node_template"]
        return [(f"node-{i}", template)
                for i in range(int(self.config["nodes"]))]

    def pods(self, phase: str, names: list[str]) -> list[dict]:
        """`make_pod` arguments for each of `names`, pods of one phase
        (`init`, `warm`, `burst`, `measured`). Where they do not vary,
        the same object for every pod: the generator shares the event
        loop with the control plane."""
        return [self.config["pod_template"]] * len(names)

    def problem(self) -> dict:
        """The problem a solve is handed, for a reader's work model:
        nodes, resource planes (the pod count is one), distinct pod
        classes (same requests and constraints) in the traffic."""
        return {"nodes": self.n_nodes, "resources": len(self.resources) + 1,
                "classes": 1}

    # -- the arithmetic of bindings -----------------------------------------

    def node_index(self, name) -> int:
        """-1 for a name that is no node of this cluster."""
        return self._index.get(name, -1)

    def request_rows(self, specs: list[dict]) -> np.ndarray:
        """[pods, resources]: what each pod asked, from the arguments it
        was created with."""
        distinct, which = _by_identity(specs)
        table = np.array([resource_vector(kw.get("requests") or {},
                                          self.resources)
                          for kw in distinct], dtype=np.int64)
        return table.reshape(-1, len(self.resources))[which]

    def placed(self, created: list[str], specs: list[dict],
               bound: dict[str, str]) -> tuple[np.ndarray, np.ndarray]:
        """(node index, request row) of every created pod that is bound
        to a node of the cluster."""
        at = np.array([self.node_index(bound.get(k)) for k in created],
                      dtype=np.int64)
        keep = at >= 0
        return at[keep], self.request_rows(specs)[keep]

    def occupancy(self, at: np.ndarray, rows: np.ndarray):
        """(used [nodes, resources], pods [nodes]) after these bindings."""
        used = np.zeros((self.n_nodes, len(self.resources)), dtype=np.int64)
        np.add.at(used, at, rows)
        return used, np.bincount(at, minlength=self.n_nodes)

    def fragmentation(self, at: np.ndarray, rows: np.ndarray) -> float:
        used, pods = self.occupancy(at, rows)
        return fragmentation_occupied_pct(
            self.alloc[:, self._scored], used[:, self._scored], pods)

    # -- the comparison -------------------------------------------------------

    def check(self, *, created: list[str], specs: list[dict],
              bound: dict[str, str], rebound: list[str],
              readback: dict[str, str | None], not_device_placed: int,
              settled: list[int]) -> dict:
        """Every number compared, each beside its limit:
        {name: {"value": v, "limit": l}}. `created` are the pod keys
        whose create was acknowledged, in order, and `specs` the
        arguments each was created from; `bound` what the watch saw;
        `readback` what a GET through the wire returned for a sample;
        `settled` the lengths of `created` at which the client had seen
        every pod created so far bound (the end of each wave)."""
        used, pods = self.occupancy(*self.placed(created, specs, bound))
        over = ((used > self.alloc).any(axis=1) | (pods > self.alloc_pods))
        numbers = {
            "unbound": sum(1 for k in created if k not in bound),
            "bound_twice": len(rebound),
            "unknown_node": sum(1 for n in bound.values()
                                if self.node_index(n) < 0),
            "nodes_over_allocatable": int(over.sum()),
            "readback_mismatch": sum(
                1 for k, node in readback.items() if bound.get(k) != node),
            "not_device_placed": int(not_device_placed),
        }
        out = {k: {"value": v, "limit": 0} for k, v in numbers.items()}
        out.update(self.own_numbers(
            created=created, specs=specs, bound=bound, settled=settled))
        return out

    def own_numbers(self, *, created: list[str], specs: list[dict],
                    bound: dict[str, str], settled: list[int]) -> dict:
        """The deployment's own guarantees, {name: {"value", "limit"}}.
        The default states none beyond the generic six."""
        return {}

    # -- the default scheduler, one pod at a time --------------------------

    def score(self, used: np.ndarray, pods_on: np.ndarray, row: np.ndarray,
              nodes=slice(None)) -> np.ndarray:
        """Score of placing one more pod that asks `row` on each of
        `nodes` (`used`, `pods_on`: theirs); -inf where it does not fit."""
        alloc = self.alloc[nodes]
        after = used + row[None, :]
        fits = (after <= alloc).all(axis=1) \
            & (pods_on + 1 <= self.alloc_pods[nodes])
        frac = after[:, self._scored] / alloc[:, self._scored]
        least = 100.0 * (1.0 - frac).mean(axis=1)
        balanced = 100.0 * (1.0 - frac.std(axis=1))
        return np.where(fits, least + balanced, -np.inf)

    def placer(self, sound: bool) -> "Placer":
        """The reference as a live scheduler: sound, or with this
        deployment's guarantee broken (the control, which `correct` has
        to fail by one of this deployment's numbers)."""
        return Placer(self, 1 if sound else self.stale_chunk)


class Placer:
    """The default scheduler, one pod at a time: `place(pod)` gives the
    node index of the next pod (-1: none fits). `stale_chunk` > 1 is the
    default CONTROL: it looks at the cluster only once every that many
    pods and places the whole chunk by that one look — the fault of a
    solve that does not carry its own placements forward.

    A deployment's placer narrows the choice with `allowed(pod)` (a mask
    over nodes, None for all) and keeps its own state in `note`."""

    def __init__(self, model: ClusterModel, stale_chunk: int = 1):
        self.model = model
        self.stale_chunk = int(stale_chunk)
        self.used = np.zeros((model.n_nodes, len(model.resources)),
                             dtype=np.int64)
        self.pods_on = np.zeros(model.n_nodes, dtype=np.int64)
        self.placed = 0
        #: request class -> (row, score of one more such pod per node)
        self._scores: dict[tuple, tuple[np.ndarray, np.ndarray]] = {}

    def allowed(self, pod: dict) -> np.ndarray | None:
        return None

    def note(self, pod: dict, node: int) -> None:
        pass

    def _class(self, pod: dict) -> tuple[np.ndarray, np.ndarray]:
        asked = pod_requests(pod)
        key = tuple(sorted(asked.items()))
        if key not in self._scores:
            row = resource_vector(asked, self.model.resources)
            self._scores[key] = (row, self.model.score(
                self.used, self.pods_on, row))
        return self._scores[key]

    def place(self, pod: dict) -> int:
        stale = self.stale_chunk > 1
        if stale and self.placed % self.stale_chunk == 0:
            self._scores.clear()
        row, score = self._class(pod)
        mask = self.allowed(pod)
        if mask is not None:
            score = np.where(mask, score, -np.inf)
        best = int(np.argmax(score))
        if score[best] == -np.inf:
            return -1
        self.used[best] += row
        self.pods_on[best] += 1
        self.placed += 1
        self.note(pod, best)
        if not stale:
            one = slice(best, best + 1)
            for asked, kept in self._scores.values():
                kept[best] = self.model.score(
                    self.used[one], self.pods_on[one], asked, one)[0]
        return best


def is_correct(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())
