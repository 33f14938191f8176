"""Upstream scheduler_perf's SchedulingPreferredPodAffinity deployment:
identical nodes (`make_node` labels each `kubernetes.io/hostname`), and
every pod — init pods, warm-up waves, warm bursts and measured waves
alike — pod-with-preferred-pod-affinity.yaml: labelled `foo: ""`, and
carrying one `preferredDuringSchedulingIgnoredDuringExecution` pod
affinity term of weight 1 whose selector matches that label,
`topologyKey: kubernetes.io/hostname`. "Co-locate my replicas".

The default profile weighs InterPodAffinity at 2. A pod's raw score on a
node counts the pods there its term selects, plus the pods there whose
term selects it (the symmetric half): here both count the same pods.
Min-max normalised over the feasible nodes, that is 200 points on the
fullest node that still fits against the ~100 that LeastAllocated and
BalancedAllocation give back, so one pod at a time the default scheduler
fills a node to its 80-pod CPU limit before it opens the next.

Its guarantee, beside the generic six: the nodes that hold a bound pod
are no more than the nodes the default scheduler occupies when it places
the same created pods (`occupied_nodes_over_reference`, limit 0). With
one template the reference's count does not depend on the order of
placement, and a program that scores every placement with the weights
as they stand after every earlier one makes the same choices.

The plain reference (`placer(sound=True)`) is that default scheduler one
pod at a time in numpy — LeastAllocated + BalancedAllocation
(`ClusterModel.score`) + 2 x the normalised InterPodAffinity score, both
halves, lowest node index on ties — with nothing of the program in it.
The control (`sound=False`) looks at the InterPodAffinity weights once
every 1,024 pods (the program's chunk width) and scores the whole chunk
by that look, resources kept live: the fault of a solve that scores with
a chunk-start row. The first chunk sees every weight equal and spreads,
the next ones stack on the nodes the last look saw, and hundreds of
nodes are occupied where the reference fills a few dozen.
"""

import numpy as np

from benchmark.lib.fragmentation import resource_vector
from benchmark.lib.reference import ClusterModel, Placer, pod_requests

#: InterPodAffinity's weight in the default profile
IPA_WEIGHT = 2.0
MAX_NODE_SCORE = 100.0


class Deployment(ClusterModel):
    def __init__(self, config: dict):
        term = config["preferred_affinity"]
        self.label_key = term["label_key"]
        self.label_value = term["label_value"]
        self.weight = float(term["weight"])
        labels = {self.label_key: self.label_value}
        #: the one argument object every pod of every phase is made from
        self.pod_args = dict(
            config["pod_template"], labels=labels,
            affinity={"podAffinity": {
                "preferredDuringSchedulingIgnoredDuringExecution": [{
                    "weight": int(term["weight"]),
                    "podAffinityTerm": {
                        "labelSelector": {"matchLabels": dict(labels)},
                        "topologyKey": term["topology_key"]}}]}})
        super().__init__(config)

    def pods(self, phase, names):
        return [self.pod_args] * len(names)

    def problem(self):
        """One pod class, and every pod's InterPodAffinity score moves
        with the placements of its own chunk: each pod is one step of
        the carried scan (benchmark/lib/affinity_work.py)."""
        return dict(super().problem(), classes=1)

    def _traits(self, labels, affinity) -> tuple[bool, bool]:
        """(the term selects the pod, the pod carries the term)."""
        selected = (labels or {}).get(self.label_key) == self.label_value
        carries = bool(((affinity or {}).get("podAffinity") or {}).get(
            "preferredDuringSchedulingIgnoredDuringExecution"))
        return selected, carries

    def own_numbers(self, *, created, specs, bound, settled):
        """Nodes holding a bound pod, less the nodes the reference
        occupies placing every created pod in the order created."""
        at = np.array([self.node_index(bound.get(k)) for k in created],
                      dtype=np.int64)
        program = int(np.unique(at[at >= 0]).size)
        placer = PreferredPlacer(self)
        rows = self.request_rows(specs)
        traits: dict[int, tuple[bool, bool]] = {}
        reference = np.zeros((self.n_nodes,), dtype=bool)
        for j, kw in enumerate(specs):
            t = traits.get(id(kw))
            if t is None:
                t = traits[id(kw)] = self._traits(
                    kw.get("labels"), kw.get("affinity"))
            node = placer.place_row(rows[j], *t)
            if node >= 0:
                reference[node] = True
        return {"occupied_nodes_over_reference": {
            "value": program - int(reference.sum()), "limit": 0}}

    def placer(self, sound: bool) -> Placer:
        return PreferredPlacer(self, 1 if sound else self.stale_chunk)


class PreferredPlacer(Placer):
    """The default scheduler with this deployment's InterPodAffinity
    score: resources exactly as `Placer` (kept live, node by node), and
    per node the pods the term selects (`selected`) and the pods that
    carry the term (`carriers`). A pod that carries the term weighs the
    selected pods on a node; a pod the term selects weighs the carriers
    (the symmetric half). The hostname key makes each node its own
    domain, and a node that does not fit is left out of the min-max.

    `stale_chunk` > 1 is the CONTROL: the InterPodAffinity counts are
    looked at once every that many pods, resources stay live."""

    def __init__(self, model: Deployment, stale_chunk: int = 1):
        super().__init__(model, 1)
        self.ipa_every = int(stale_chunk)
        self.selected = np.zeros((model.n_nodes,), dtype=np.float64)
        self.carriers = np.zeros((model.n_nodes,), dtype=np.float64)
        self._seen = (self.selected, self.carriers)

    def place(self, pod: dict) -> int:
        model = self.model
        meta = pod["metadata"]
        return self.place_row(
            np.asarray(resource_vector(pod_requests(pod), model.resources)),
            *model._traits(meta.get("labels"),
                           (pod.get("spec") or {}).get("affinity")))

    def place_row(self, row: np.ndarray, selected: bool,
                  carries: bool) -> int:
        model = self.model
        key = tuple(row)
        if key not in self._scores:
            self._scores[key] = (row, model.score(
                self.used, self.pods_on, row))
        _, score = self._scores[key]
        if self.ipa_every == 1:
            self._seen = (self.selected, self.carriers)
        elif self.placed % self.ipa_every == 0:
            self._seen = (self.selected.copy(), self.carriers.copy())
        fits = np.isfinite(score)
        if not fits.any():
            return -1
        seen_selected, seen_carriers = self._seen
        raw = np.zeros((model.n_nodes,), dtype=np.float64)
        if carries:
            raw += model.weight * seen_selected
        if selected:
            raw += model.weight * seen_carriers
        hi, lo = raw[fits].max(), raw[fits].min()
        total = score
        if hi > lo:
            total = score + IPA_WEIGHT * MAX_NODE_SCORE * (raw - lo) \
                / (hi - lo)
        best = int(np.argmax(total))
        self.used[best] += row
        self.pods_on[best] += 1
        self.placed += 1
        self.selected[best] += selected
        self.carriers[best] += carries
        one = slice(best, best + 1)
        for asked, kept in self._scores.values():
            kept[best] = model.score(
                self.used[one], self.pods_on[one], asked, one)[0]
        return best
