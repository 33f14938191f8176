"""Hostname anti-affinity through `TPUBackend.assign`, against the plain
reference of the benchmark's deployment (benchmark/deployments/
pod-anti-affinity.py), on UNEQUAL nodes where the gate binds: a few big
nodes that resources alone would fill with every pod, and more pods of
a group than big nodes. Held where the Sinkhorn route is asked for (a
chunk whose pods carry the term never takes it: a transport plan sends
a whole group to a handful of nodes), for the greedy wave scan at W 1
and W 32, a batch of one and two chunks of one assign():
never two of a group on a host; a pod the solve could not keep (two of
a group picked one host inside a chunk — the solve does not know they
exclude each other, the host verify does) is handed back with a reason
and placed by a later assign(); a group larger than the cluster leaves
exactly its excess unschedulable, by InterPodAffinity. And the span and
the counters the cell's metrics read.
"""

import copy
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.lib.fragmentation import resource_vector  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402
from benchmark.lib.reference import pod_requests  # noqa: E402

CELL = "sched-perf-antiaffinity-5k.drain"
BIG, SMALL = 8, 16


def _deployment():
    """24 nodes under the cell's own deployment: node-0..7 with 8 CPUs,
    node-8..23 with 1 (ten pods of 100m each)."""
    manifest = Manifest()
    config = copy.deepcopy(manifest.config(manifest.cell(CELL)))
    config.update(nodes=BIG + SMALL, init_pods=0, wave_pods=0)
    small = {"allocatable": dict(
        config["node_template"]["allocatable"], cpu="1")}

    class Unequal(type(manifest.deployment(config))):
        def nodes(self):
            return [(name, kw if i < BIG else small)
                    for i, (name, kw) in enumerate(super().nodes())]
    return Unequal(config)


class _Cluster:
    """The same cluster twice: the program's cache and framework, and
    the deployment's sound placer told of every placement."""

    def __init__(self, model, residents=()):
        from kubernetes_tpu.api.types import make_node
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        from kubernetes_tpu.scheduler.framework import Framework
        from kubernetes_tpu.scheduler.plugins.registry import (
            DEFAULT_SCORE_WEIGHTS,
            build_plugins,
        )
        self.model = model
        self.cache = SchedulerCache()
        for name, kw in model.nodes():
            self.cache.add_node(make_node(name, **copy.deepcopy(kw)))
        self.placer = model.placer(sound=True)
        self.fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
        self.at: dict[str, int] = {}
        self.gate_bound = False
        for j, (group, node) in enumerate(residents):
            self.bind(self.pod(f"{group}-r{j}"), node)

    def pod(self, name: str) -> dict:
        from kubernetes_tpu.api.types import make_pod
        args, = self.model.pods("measured", [name])
        return make_pod(name, uid=name, **copy.deepcopy(args))

    def allowed(self, pod) -> np.ndarray:
        """The nodes the plain reference's filters admit for the pod
        now: resources and the pod count, then the group's hosts."""
        model, placer = self.model, self.placer
        row = resource_vector(pod_requests(pod), model.resources)
        score = model.score(placer.used, placer.pods_on, row)
        gate = placer.allowed(pod)
        if gate is not None and not gate[int(np.argmax(score))]:
            self.gate_bound = True     # resources alone chose a closed host
        fits = np.isfinite(score)
        return fits if gate is None else fits & gate

    def bind(self, pod, node: int) -> None:
        from kubernetes_tpu.scheduler.types import PodInfo
        model, placer = self.model, self.placer
        pod["spec"]["nodeName"] = model.node_names[node]
        self.cache.add_pod(PodInfo(pod))
        placer.used[node] += resource_vector(
            pod_requests(pod), model.resources)
        placer.pods_on[node] += 1
        placer.note(pod, node)
        self.at[pod["metadata"]["name"]] = node

    def assign(self, backend, pods):
        """One assign(); every placement checked against the reference
        at that point and bound. Returns the pods handed back and the
        diagnostics."""
        from kubernetes_tpu.scheduler.types import PodInfo
        assignments, diagnostics = backend.assign(
            [PodInfo(p) for p in pods], self.cache.update_snapshot(),
            self.fwk)
        back = []
        for pod in pods:
            key = f"default/{pod['metadata']['name']}"
            name = assignments[key]
            if name is None:
                assert key in diagnostics, f"{key} lost: no reason given"
                back.append(pod)
                continue
            node = self.model.node_index(name)
            assert self.allowed(pod)[node], (
                f"{key} on {name}, which holds "
                f"{[n for n, at in self.at.items() if at == node]}")
            self.bind(pod, node)
        return back, diagnostics

    def drain(self, backend, pods, rounds=40):
        """assign() until a round places nothing: what a queue's requeue
        does. Returns what is left and the last diagnostics."""
        diagnostics = {}
        for _ in range(rounds):
            if not pods:
                break
            left, diagnostics = self.assign(backend, pods)
            if len(left) == len(pods):
                break
            pods = left
        return pods, diagnostics


def _backend(max_batch=64, tracer=False):
    from kubernetes_tpu.metrics.registry import SchedulerMetrics
    from kubernetes_tpu.ops import TPUBackend
    from kubernetes_tpu.utils.tracing import Tracer
    backend = TPUBackend(max_batch=max_batch, mesh=None)
    backend.metrics = SchedulerMetrics()
    if tracer:
        backend.tracer = Tracer(enabled=True)
    return backend


def _rejects(backend, plugin="InterPodAffinity") -> float:
    return backend.metrics.verify_rejects.value(plugin=plugin)


RESIDENTS = [("ga", 0), ("ga", 1), ("ga", 2),
             ("gb", 8), ("gb", 9), ("gb", 10), ("gb", 11)]

ROUTES = [
    pytest.param({"KTPU_SOLVE_MODE": "optimal"}, 64, id="optimal-asked"),
    pytest.param({"KTPU_SOLVE_MODE": "greedy", "KTPU_WAVEFRONT": "0"}, 64,
                 id="greedy-W1"),
    pytest.param({"KTPU_SOLVE_MODE": "greedy", "KTPU_WAVE_WIDTH": "32"}, 64,
                 id="greedy-W32"),
    pytest.param({}, 16, id="two-chunks"),
]


def _mixed_batch(cluster, seed):
    names = [f"ga-{j}" for j in range(18)] + [f"gb-{j}" for j in range(12)] \
        + [f"gc-{j}" for j in range(10)]
    np.random.default_rng(seed).shuffle(names)
    return [cluster.pod(name) for name in names]


@pytest.mark.parametrize("env,max_batch", ROUTES)
@pytest.mark.parametrize("seed", [3, 11])
def test_never_two_of_a_group_on_a_host_and_no_pod_is_lost(
        monkeypatch, env, max_batch, seed):
    """Forty pods of three groups, two of them with residents: all can
    be placed (21 and 20 open hosts for 18 and 12 pods), but only by
    leaving the eight big nodes that resources prefer."""
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    cluster = _Cluster(_deployment(), RESIDENTS)
    backend = _backend(max_batch)
    left, _ = cluster.drain(backend, _mixed_batch(cluster, seed))
    assert not left, [p["metadata"]["name"] for p in left]
    assert cluster.gate_bound
    # the final state, read apart from the replay: one of a group a host
    groups: dict[tuple, int] = {}
    for name, node in cluster.at.items():
        key = (name.rsplit("-", 1)[0], node)
        groups[key] = groups.get(key, 0) + 1
    assert max(groups.values()) == 1
    assert len(cluster.at) == len(RESIDENTS) + 40
    deg = backend.metrics.backend_degradations
    assert deg.value(kind="host_fallback") == 0
    assert _rejects(backend, "NodeResourcesFit") == 0
    # no chunk of these pods was rounded from a transport plan
    assert backend.metrics.solver_optimal_solves.value() == 0
    if env.get("KTPU_SOLVE_MODE") == "optimal":
        assert backend.metrics.solver_optimal_fallbacks.value() >= 1


@pytest.mark.parametrize("mode", ["auto", "optimal"])
def test_a_chunk_whose_pods_may_exclude_each_other_keeps_the_greedy_scan(
        mode):
    """The policy row: drain-scale chunks go to the Sinkhorn plan, but
    not one in which a pod carries a required anti-affinity term — it
    degrades structurally, and the fallback is recorded."""
    from kubernetes_tpu.ops.backend import AdaptiveTuner
    from kubernetes_tpu.utils import flags
    tuner = AdaptiveTuner()
    with flags.scoped_set("KTPU_SOLVE_MODE", mode):
        assert tuner.solve_mode(512, has_gang=False, spread=False,
                                class_mode=True) == ("optimal", False)
        assert tuner.solve_mode(512, has_gang=False, spread=False,
                                class_mode=True, exclusive=True) == (
            "greedy", True)


def test_five_hundred_of_a_group_on_identical_nodes_land_in_one_assign():
    """The cell's shape in small: a drain-scale chunk of one group on
    identical nodes, a fifth of them holding another group. Every pod
    is placed by the first assign(), each on a host of its own."""
    manifest = Manifest()
    config = copy.deepcopy(manifest.config(manifest.cell(CELL)))
    config.update(nodes=400, init_pods=0, wave_pods=0)
    cluster = _Cluster(manifest.deployment(config),
                       [("init", i) for i in range(80)])
    backend = _backend(max_batch=1024)
    left, _ = cluster.assign(
        backend, [cluster.pod(f"w0-{j}") for j in range(390)])
    assert not left and _rejects(backend) == 0
    assert backend.metrics.solver_optimal_solves.value() == 0


@pytest.mark.parametrize("env,max_batch", ROUTES)
def test_what_the_solve_could_not_keep_is_handed_back_with_the_reason(
        monkeypatch, env, max_batch):
    """Twelve pods of one new group in one assign(), eight big nodes
    open: the solve's picks coincide on them; the host verify keeps one
    a host and hands the rest back as InterPodAffinity conflicts,
    counted; later assign()s see the group's hosts closed and place
    them."""
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    cluster = _Cluster(_deployment())
    backend = _backend(max_batch)
    pods = [cluster.pod(f"gd-{j}") for j in range(12)]
    back, diagnostics = cluster.assign(backend, pods)
    assert _rejects(backend) == len(back)
    for pod in back:
        status, = diagnostics[f"default/{pod['metadata']['name']}"].values()
        assert status.plugin == "InterPodAffinity"
    left, _ = cluster.drain(backend, back)
    assert not left
    assert sorted(cluster.at.values()) == sorted(set(cluster.at.values()))


@pytest.mark.parametrize("env,max_batch", ROUTES)
def test_a_group_larger_than_the_cluster_leaves_exactly_its_excess(
        monkeypatch, env, max_batch):
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    cluster = _Cluster(_deployment())
    backend = _backend(max_batch)
    pods = [cluster.pod(f"ge-{j}") for j in range(BIG + SMALL + 5)]
    left, diagnostics = cluster.drain(backend, pods)
    assert len(left) == 5
    assert len(set(cluster.at.values())) == BIG + SMALL
    for pod in left:
        assert not cluster.allowed(pod).any()
        reasons = diagnostics[f"default/{pod['metadata']['name']}"]
        assert {st.plugin for st in reasons.values()} == {"InterPodAffinity"}
        assert len(reasons) == BIG + SMALL          # every host, by name


@pytest.mark.parametrize("group,expected", [("ga", BIG), ("gz", 0)])
def test_a_batch_of_one_stays_off_its_groups_hosts(group, expected):
    """The warm burst of 1: a lone constrained pod rides the backend as
    a batch of one and is gated like any other. Every big node holds a
    pod of `ga`: resources alone send any pod to big node 0, and a pod
    of `ga` has to take the first small node."""
    cluster = _Cluster(_deployment(), [("ga", i) for i in range(BIG)])
    backend = _backend()
    left, _ = cluster.assign(backend, [cluster.pod(f"{group}-lone")])
    assert not left
    assert cluster.at[f"{group}-lone"] == expected
    assert cluster.gate_bound == (group == "ga")


def _affinity_spans(backend):
    return [s for s in backend.tracer.spans
            if s.name == "solver.affinity_rows"
            and getattr(s, "span_id", None)]


def test_the_span_says_how_the_compiler_reached_the_snapshot():
    """`solver.affinity_rows` (layer `attempt`), one per chunk with a
    gated pod: the first of an assign() builds (looking at every resident
    carrier) or advances (looking at the carriers of the nodes that
    changed, and moving the ones that came and went), the rest are
    `kept`; the histogram is observed once per span and the counters grow
    by what was looked at and what moved."""
    from kubernetes_tpu.utils.tracing import layer_of
    cluster = _Cluster(_deployment(), RESIDENTS)
    backend = _backend(max_batch=16, tracer=True)
    try:
        pods = _mixed_batch(cluster, 5)
        left, _ = cluster.assign(backend, pods[:24])      # two chunks
        first, second = _affinity_spans(backend)
        # each resident is on both of its node's lists: looked at twice,
        # and to a compiler built anew every one of them came
        assert first.attrs == {"build": "full", "terms": 2, "rows": 3,
                               "carriers": 2 * len(RESIDENTS),
                               "came": 2 * len(RESIDENTS), "gone": 0}
        assert second.attrs["build"] == "kept"
        assert (second.attrs["carriers"], second.attrs["came"],
                second.attrs["gone"]) == (0, 0, 0)
        assert layer_of("solver.affinity_rows") == "attempt"
        metrics = backend.metrics
        assert metrics.affinity_carriers_walked.value() == 2 * len(RESIDENTS)
        moved = metrics.affinity_carriers_moved
        assert (moved.value(dir="came"), moved.value(dir="gone")) \
            == (2 * len(RESIDENTS), 0)
        resident = len(cluster.at)
        bound = resident - len(RESIDENTS)
        # the nodes the first assign() bound a pod to, and all they hold
        changed = set(list(cluster.at.values())[len(RESIDENTS):])
        reread = sum(node in changed for node in cluster.at.values())
        cluster.assign(backend, left + pods[24:])
        third = _affinity_spans(backend)[2]
        assert third.attrs["build"] == "delta"
        # a delta reads the two lists of the changed nodes, not every
        # resident, and moves the pods that were bound since
        assert third.attrs["carriers"] == 2 * reread
        assert (third.attrs["came"], third.attrs["gone"]) == (2 * bound, 0)
        assert third.attrs["terms"] == 3                  # gc is resident now
        assert metrics.affinity_carriers_walked.value() == \
            2 * len(RESIDENTS) + 2 * reread
        assert (moved.value(dir="came"), moved.value(dir="gone")) \
            == (2 * resident, 0)
        spans = _affinity_spans(backend)
        _, total = metrics.affinity_rows_duration.snapshot()
        assert total == len(spans)
        rendered = metrics.registry.render()
        wall = sum(s.end - s.start for s in spans)
        line = next(ln for ln in rendered.splitlines() if ln.startswith(
            "scheduler_tpu_affinity_rows_seconds_sum"))
        assert float(line.split()[-1]) == pytest.approx(wall, rel=1e-9)
    finally:
        backend.tracer.enabled = False


def test_the_histogram_is_observed_with_tracing_off_too():
    cluster = _Cluster(_deployment(), RESIDENTS)
    backend = _backend(max_batch=16)
    cluster.assign(backend, _mixed_batch(cluster, 7)[:20])   # two chunks
    _, total = backend.metrics.affinity_rows_duration.snapshot()
    assert total == 2


def test_a_chunk_without_a_gated_pod_opens_no_span():
    """Plain pods on a cluster where no resident carries a term: the
    gate is false for every pod and the compiler is not reached."""
    from kubernetes_tpu.api.types import make_pod
    cluster = _Cluster(_deployment())
    backend = _backend(tracer=True)
    try:
        plain = [make_pod(f"plain-{j}", uid=f"plain-{j}",
                          requests={"cpu": "100m", "memory": "250Mi"})
                 for j in range(6)]
        from kubernetes_tpu.scheduler.types import PodInfo
        backend.assign([PodInfo(p) for p in plain],
                       cluster.cache.update_snapshot(), cluster.fwk)
        assert not _affinity_spans(backend)
        assert backend.metrics.affinity_rows_duration.snapshot()[1] == 0
        assert backend.metrics.affinity_carriers_walked.value() == 0
    finally:
        backend.tracer.enabled = False


@pytest.mark.parametrize("plugin", [
    "NodeResourcesFit", "NodePorts", "InterPodAffinity", "other"])
def test_the_reject_series_exist_at_zero_from_registration(plugin):
    from kubernetes_tpu.metrics.registry import SchedulerMetrics
    rendered = SchedulerMetrics().registry.render()
    assert f'scheduler_tpu_verify_rejects_total{{plugin="{plugin}"}} 0' \
        in rendered
