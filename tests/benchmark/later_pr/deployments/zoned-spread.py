"""A constraint deployment, as a later PR would bring it: nodes in zones
(upstream scheduler_perf's labelNodePrepareStrategy: the zone label
round robin), the first zone's nodes twice the size, plain init pods,
and every later pod labelled and held by a zone
`topologySpreadConstraints` (`DoNotSchedule`).

Its guarantee, beside the generic six: whenever the client has seen
every pod bound (the end of each wave), no zone holds more matching pods
than the emptiest zone plus `maxSkew` (`zones_over_max_skew`, limit 0).
Its control is a scheduler that places by resources only: the big nodes
draw twice the pods, and their zone runs ahead.
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
        small, big = self.config["node_template"], self.config["big_node"]
        return [(f"node-{i}", dict(
            big if i % len(self.zones) == 0 else small,
            labels={ZONE_LABEL: self.zones[i % len(self.zones)]}))
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
        return _SpreadPlacer(self) if sound else Placer(self)


class _SpreadPlacer(Placer):
    """The default scheduler behind the PodTopologySpread filter: a zone
    is closed to a matching pod while one more there would put it more
    than maxSkew ahead of the emptiest."""

    def __init__(self, model: Deployment):
        super().__init__(model)
        self.count = np.zeros(len(model.zones), dtype=np.int64)

    def _held(self, pod: dict) -> bool:
        return _matches(pod["metadata"].get("labels"), self.model.selector)

    def allowed(self, pod):
        if not self._held(pod):
            return None
        open_ = self.count + 1 - self.count.min() <= self.model.max_skew
        return open_[self.model.zone_of]

    def note(self, pod, node):
        if self._held(pod):
            self.count[self.model.zone_of[node]] += 1
