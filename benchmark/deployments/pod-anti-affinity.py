"""Upstream scheduler_perf's SchedulingPodAntiAffinity deployment:
identical nodes (`make_node` labels each `kubernetes.io/hostname`), and
every pod — init pods, warm-up waves, warm bursts and measured waves
alike — pod-with-pod-anti-affinity.yaml: labelled, and carrying one
`requiredDuringSchedulingIgnoredDuringExecution` pod anti-affinity term
whose selector matches the pod's own label, `topologyKey:
kubernetes.io/hostname`. At most one pod of a group per node.

The one departure from upstream (the configuration's `assumed` states
it in full): a GROUP IS ONE WAVE. Upstream's run ends after one measured
phase; on a standing cluster one label would fill its 5,000 hosts after
5,000 pods. So the label's value ends in the pod's group — its name up
to the last `-` (`init`, `warm0`, `burst7`, `s7fffffff-w3`; a name with
no `-` is its own group) — and the term selects that value: many
Deployments, each with "one replica per node" against itself.

Its guarantee, beside the generic six: no node holds two pods of one
group (`hosts_sharing_a_group`, limit 0), counted over every pod
created, set-up included. Unlike a skew it shows in the final state: no
order of placement is needed.

The plain reference (`placer(sound=True)`) is the default scheduler one
pod at a time behind that filter, in numpy, with nothing of the program
in it. The control (`sound=False`) is the fault the other cells' control
has: one look at the cluster for a whole chunk of pods, so the chunk
lands on one node — past its allocatable, and a group sharing a host.
"""

import numpy as np

from benchmark.lib.reference import ClusterModel, Placer


def group_of(name: str) -> str:
    """A pod's group: its name up to the last `-`; a name with none is
    its own group."""
    return name.rsplit("-", 1)[0] if "-" in name else name


class Deployment(ClusterModel):
    def __init__(self, config: dict):
        term = config["anti_affinity"]
        self.topology_key = term["topology_key"]
        self.label_key = term["label_key"]
        self.label_value = term["label_value"]
        #: group -> the one argument object its pods share
        self._group_args: dict[str, dict] = {}
        super().__init__(config)

    def pods(self, phase, names):
        """Every phase's pods carry the term; one argument object per
        group, shared by the group's pods."""
        return [self._args(group_of(name)) for name in names]

    def _args(self, group: str) -> dict:
        args = self._group_args.get(group)
        if args is None:
            labels = {self.label_key: f"{self.label_value}-{group}"}
            args = self._group_args[group] = dict(
                self.config["pod_template"], labels=labels,
                affinity={"podAntiAffinity": {
                    "requiredDuringSchedulingIgnoredDuringExecution": [{
                        "labelSelector": {"matchLabels": dict(labels)},
                        "topologyKey": self.topology_key}]}})
        return args

    def problem(self):
        """A closed wave is one group and starts when the last is bound,
        so a chunk carries one class: one request, one mask row."""
        return dict(super().problem(), classes=1)

    def own_numbers(self, *, created, specs, bound, settled):
        """(node, group) pairs that hold two or more bound pods, over
        every pod created."""
        at = np.array([self.node_index(bound.get(k)) for k in created],
                      dtype=np.int64)
        # a group's pods share one argument object: its label is read once
        number: dict[str, int] = {}
        of_args: dict[int, int] = {}
        group = np.empty(len(specs), dtype=np.int64)
        for j, kw in enumerate(specs):
            g = of_args.get(id(kw))
            if g is None:
                value = (kw.get("labels") or {}).get(self.label_key)
                g = of_args[id(kw)] = -1 if value is None \
                    else number.setdefault(value, len(number))
            group[j] = g
        keep = (at >= 0) & (group >= 0)
        pair = at[keep] * max(len(number), 1) + group[keep]
        _, count = np.unique(pair, return_counts=True)
        return {"hosts_sharing_a_group": {
            "value": int((count >= 2).sum()), "limit": 0}}

    def placer(self, sound: bool) -> Placer:
        return AntiAffinityPlacer(self) if sound \
            else Placer(self, self.stale_chunk)


class AntiAffinityPlacer(Placer):
    """The default scheduler behind the InterPodAffinity filter of this
    deployment: first resources and the pod count
    (`ClusterModel.score`), then every node that holds a pod of the
    incoming pod's group is closed. The incoming pod's own term (no
    resident it selects on the node) and the symmetry rule (no resident
    whose term selects the incoming pod) coincide here, because every
    member of a group carries the same term selecting exactly the group:
    one set of closed hosts per group states both."""

    def __init__(self, model: Deployment):
        super().__init__(model)
        #: group label value -> which nodes hold a pod of the group
        self.hosts: dict[str, np.ndarray] = {}

    def _group(self, pod: dict):
        return (pod["metadata"].get("labels") or {}).get(
            self.model.label_key)

    def allowed(self, pod):
        held = self.hosts.get(self._group(pod))
        return None if held is None else ~held

    def note(self, pod, node):
        group = self._group(pod)
        if group is None:
            return
        held = self.hosts.get(group)
        if held is None:
            held = self.hosts[group] = np.zeros(
                self.model.n_nodes, dtype=bool)
        held[node] = True
