"""Upstream scheduler_perf's TopologySpreading deployment: identical
nodes labelled with a zone round robin (labelNodePrepareStrategy), plain
init pods (pod-default.yaml), and every later pod — warm-up waves, warm
bursts and measured waves alike — pod-with-topology-spreading.yaml:
labelled, and held by one zone `topologySpreadConstraints` entry
(`DoNotSchedule`) whose selector matches the pod's own label.

Its guarantee, beside the generic six: whenever the client has seen
every pod bound (the end of each wave), no zone holds more matching pods
than the emptiest zone plus `maxSkew` (`zones_over_max_skew`, limit 0).
The filter admits a placement only while that holds, so it holds after
every placement, and the ends of the waves are where the benchmark's
own watch can say which pods the cluster held.

The plain reference (`placer(sound=True)`) is the default scheduler one
pod at a time behind the PodTopologySpread filter, in numpy, with
nothing of the program in it. The control (`sound=False`) is the fault
the other cells' control has: one look at the cluster for a whole chunk
of pods, so the chunk lands on one node — past its allocatable, and in
one zone.
"""

import numpy as np

from benchmark.lib.reference import ClusterModel, Placer

ZONE_LABEL = "topology.kubernetes.io/zone"


def _matches(labels: dict | None, selector: dict) -> bool:
    return all((labels or {}).get(k) == v for k, v in selector.items())


class Deployment(ClusterModel):
    def __init__(self, config: dict):
        self.zones = list(config["zones"])
        spread = config["spread"]
        self.max_skew = int(spread["max_skew"])
        self.selector = dict(spread["match_labels"])
        self.spread_pod = dict(
            config["pod_template"], labels=self.selector,
            topology_spread_constraints=[{
                "maxSkew": self.max_skew, "topologyKey": ZONE_LABEL,
                "whenUnsatisfiable": "DoNotSchedule",
                "labelSelector": {"matchLabels": self.selector}}])
        super().__init__(config)
        self.zone_of = np.arange(self.n_nodes) % len(self.zones)

    def nodes(self):
        """Upstream's nodes do not differ in size: one template, one
        node argument object per zone."""
        per_zone = [dict(self.config["node_template"],
                         labels={ZONE_LABEL: zone}) for zone in self.zones]
        return [(f"node-{i}", per_zone[i % len(per_zone)])
                for i in range(int(self.config["nodes"]))]

    def pods(self, phase, names):
        plain = phase == "init"
        return [self.config["pod_template"] if plain else self.spread_pod] \
            * len(names)

    def own_numbers(self, *, created, specs, bound, settled):
        """Zones ahead of the emptiest by more than maxSkew, summed over
        the ends of the waves."""
        at = np.array([self.node_index(bound.get(k)) for k in created],
                      dtype=np.int64)
        held = np.array([_matches(kw.get("labels"), self.selector)
                         for kw in specs], dtype=bool)
        zone = np.where(held & (at >= 0), self.zone_of[at], -1)
        over = 0
        for n in settled:
            seen = zone[:n]
            count = np.bincount(seen[seen >= 0], minlength=len(self.zones))
            over += int((count - count.min() > self.max_skew).sum())
        return {"zones_over_max_skew": {"value": over, "limit": 0}}

    def placer(self, sound: bool) -> Placer:
        return SpreadPlacer(self) if sound \
            else Placer(self, self.stale_chunk)


class SpreadPlacer(Placer):
    """The default scheduler behind the PodTopologySpread filter: first
    resources and the pod count (`ClusterModel.score`), then, for a pod
    the selector matches, a zone is closed while one more pod there
    would put it more than maxSkew ahead of the emptiest zone."""

    def __init__(self, model: Deployment):
        super().__init__(model)
        self.count = np.zeros(len(model.zones), dtype=np.int64)

    def _held(self, pod: dict) -> bool:
        return _matches(pod["metadata"].get("labels"), self.model.selector)

    def open_zones(self) -> np.ndarray:
        return self.count + 1 - self.count.min() <= self.model.max_skew

    def allowed(self, pod):
        if not self._held(pod):
            return None
        return self.open_zones()[self.model.zone_of]

    def note(self, pod, node):
        if self._held(pod):
            self.count[self.model.zone_of[node]] += 1
