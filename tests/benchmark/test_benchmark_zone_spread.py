"""The zone-spread deployment (benchmark/deployments/zone-spread.py,
benchmark/configs/sched-perf-spread-5k.json), on the CPU at a size of
tens: the real files run by the unchanged harness with the real `drain`
mix (its warm burst of 1 is the lone constrained pod), the deployment's
own number planted and read, the control, and the system against the
plain reference where the gate binds.
"""

import copy
import io
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib.control import control_cluster  # noqa: E402
from benchmark.lib.fragmentation import resource_vector  # noqa: E402
from benchmark.lib.harness import run_cell  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402
from benchmark.lib.reference import pod_requests  # noqa: E402

CELL = "sched-perf-spread-5k.drain"
ZONE = "topology.kubernetes.io/zone"
GENERIC_SIX = ["unbound", "bound_twice", "unknown_node",
               "nodes_over_allocatable", "readback_mismatch",
               "not_device_placed"]


def _small_tree(**sizes) -> Manifest:
    """The committed BENCHMARK.json and benchmark/, with the cell's
    configuration cut to tens of nodes and pods (and the mix's waits to
    a test's patience); nothing else differs from what the chip runs."""
    tree = Manifest()
    config = dict(tree.config(tree.cell(CELL)),
                  **(sizes or {"nodes": 60, "init_pods": 30,
                               "wave_pods": 120}))
    tree.config = lambda cell: config
    mix = dict(tree.traffic(tree.cell(CELL)), barrier_seconds=30,
               trace_seconds=1.0, warm_min_chunks=2)
    tree.traffic = lambda cell: mix
    return tree


def _run(tree, trace=False, seconds=1.5, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(CELL, 2**31 + 2828, seconds, trace, manifest=tree,
                  require_chip=False, stdout=out, stderr=err, **kw)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


def _lost(err: str) -> dict:
    line = next(ln for ln in err.splitlines() if ln.startswith("bench: lost"))
    return json.loads(line[len("bench: lost "):])


@pytest.fixture(scope="module")
def tree():
    return _small_tree()


@pytest.fixture(scope="module")
def model(tree):
    return tree.deployment(tree.config(tree.cell(CELL)))


@pytest.fixture(scope="module")
def timed(tree):
    """(rc, result, stderr, the program's degradation counts at the end
    of the run: set-up, with the mix's warm bursts, included)."""
    from benchmark.lib.cluster import Cluster
    seen = {}

    class Watched(Cluster):
        async def stop(self):
            deg = self.metrics.backend_degradations
            seen.update({kind: deg.value(kind=kind)
                         for kind in ("lone_batch", "host_path")})
            await super().stop()
    return *_run(tree, cluster_factory=Watched), seen


@pytest.fixture(scope="module")
def traced(tree):
    return _run(tree, trace=True)


# -- the files, as the harness finds them ----------------------------------

def test_the_committed_files_state_upstreams_deployment():
    manifest = Manifest()
    cell = manifest.cell(CELL)
    config = manifest.config(cell)
    basic = manifest.config({"config": "sched-perf-5k"})
    assert cell["chips"] == 1 and cell["traffic"] == "drain"
    assert (config["nodes"], config["init_pods"], config["wave_pods"]) == (
        5000, 5000, 2000)
    assert config["reduced"] == [] and config["deployment"] == "zone-spread"
    # the two 5k drains differ by the constraint and the wave size alone
    assert config["node_template"] == basic["node_template"]
    assert config["pod_template"] == basic["pod_template"]
    assert set(basic["guarantees"]) | {"max_skew"} == set(config["guarantees"])
    model = manifest.deployment(config)
    assert model.problem() == {"nodes": 5000, "resources": 3, "classes": 1}
    nodes = model.nodes()
    assert [kw["labels"][ZONE] for _, kw in nodes[:4]] == [
        "moon-1", "moon-2", "moon-3", "moon-1"]
    assert len({id(kw) for _, kw in nodes}) == 3    # shared, one per zone
    assert (model.alloc == model.alloc[0]).all()    # no big node
    init, = model.pods("init", ["a"])
    assert "topology_spread_constraints" not in init and "labels" not in init
    for phase in ("warm", "burst", "measured"):
        pod, = model.pods(phase, ["b"])
        constraint, = pod["topology_spread_constraints"]
        assert constraint == {
            "maxSkew": 5, "topologyKey": ZONE,
            "whenUnsatisfiable": "DoNotSchedule",
            "labelSelector": {"matchLabels": {"color": "blue"}}}
        assert pod["labels"] == {"color": "blue"}
        assert pod["requests"] == basic["pod_template"]["requests"]


def test_both_new_cells_are_cells_and_join_the_drain_metrics():
    """Membership only, so that a later PR appends its cells and metrics
    without an edit here. The nine `.drain` metrics that read the
    tracer's ledger do not list the new cells: their lists are pinned by
    test_benchmark_host_metrics.py, a `benchmark` PR's to relax
    (PERF.md section 7)."""
    manifest = Manifest()
    doc = manifest.doc
    cells = {w["name"]: w for w in doc["workloads"]}
    assert cells[CELL]["chips"] == 1
    four = cells["kwok-50k.drain4"]
    assert (four["config"], four["chips"]) == ("kwok-50k", 4)
    # the same mix as the one-chip cell's, whatever file name carries it
    # (a pair of configuration and traffic appears once in BENCHMARK.json)
    assert manifest.traffic(four) == manifest.traffic(cells["kwok-50k.drain"])
    listing = {m["name"]: m.get("workloads", [])
               for m in doc["end_to_end"] + doc["per_layer"]}
    for name in ("pods_bound_per_s", "frag_occupied_pct",
                 "device_busy_ms_per_kpod.drain", "device_idle_pct.drain",
                 "mask_solve_update_roofline.drain", "peak_hbm_mb.drain",
                 "prep_ms_per_kpod.drain", "solve_wait_ms_per_chunk.drain",
                 "compiles_in_window.drain", "trace_lower_s_in_window.drain",
                 "create_ack_p50_ms.drain"):
        assert {CELL, "kwok-50k.drain4"} <= set(listing[name]), name
    for name in ("spread_replayed_pods_per_kpod.drain",
                 "spread_poisoned_pods.drain", "lone_batch_pods.drain"):
        assert CELL in listing[name], name
        assert manifest.metric_file(name)["reader"] == "counter_ratio"


# -- the cell, run by the unchanged harness --------------------------------

def test_the_cell_runs_correct_with_the_lone_pod_on_the_device_path(timed):
    rc, result, err, _ = timed
    assert rc == 0 and result["correct"] is True, err[-3000:]
    assert result["attempted"] >= 120 and result["failed"] == 0
    assert list(result["compared"]) == GENERIC_SIX + ["zones_over_max_skew"]
    assert all(n == {"value": 0, "limit": 0}
               for n in result["compared"].values())
    assert all(v == 0 for v in _lost(err).values()), _lost(err)
    assert set(result["metrics"]) == {
        "pods_bound_per_s", "frag_occupied_pct", "setup_s"}


def test_the_traced_run_reads_the_new_metrics(traced):
    """Host spans and counters read on any platform; the device's
    metrics are absent here, never zero."""
    rc, result, err = traced
    assert rc == 0 and result["correct"] is True, err[-3000:]
    metrics = result["metrics"]
    # the warm burst of 1 was the lone pod; none was popped alone in
    # the window's back-to-back waves, and none missed the table
    assert metrics["spread_poisoned_pods.drain"]["value"] == 0
    assert metrics["lone_batch_pods.drain"]["value"] >= 0
    assert metrics["spread_replayed_pods_per_kpod.drain"]["value"] >= 0
    assert "device_busy_ms_per_kpod.drain" not in metrics


def test_the_lone_pod_of_the_warm_bursts_rode_a_batch_of_one(timed):
    """`drain.json`'s burst of 1: a zone-spread pod popped alone. It is
    counted on the batch path, and nowhere on the host scheduler."""
    *_, seen = timed
    assert seen["lone_batch"] >= 1 and seen["host_path"] == 0


# -- the deployment's own number ---------------------------------------------

def _planted(model, zone_of_pod):
    """`check` over bindings planted by the test: pod j on some node of
    zone zone_of_pod[j]."""
    n = len(zone_of_pod)
    created = [f"default/p{j}" for j in range(n)]
    specs = model.pods("measured", created)
    per_zone = [np.flatnonzero(model.zone_of == z)
                for z in range(len(model.zones))]
    bound = {k: model.node_names[per_zone[z][j % len(per_zone[z])]]
             for j, (k, z) in enumerate(zip(created, zone_of_pod))}
    return model.check(created=created, specs=specs, bound=bound,
                       rebound=[], readback={}, not_device_placed=0,
                       settled=[n])


@pytest.mark.parametrize("zones,over", [
    ([0, 1, 2] * 10, 0),                      # level
    ([0] * 5 + [1, 2] * 0, 0),                # five ahead: still allowed
    ([0] * 6, 1),                             # six ahead of two empty zones
    ([0] * 20 + [1] * 20 + [2] * 3, 2),       # two zones run ahead
])
def test_bindings_planted_into_one_zone_read_over_max_skew(
        model, zones, over):
    numbers = _planted(model, zones)
    assert numbers["zones_over_max_skew"] == {"value": over, "limit": 0}
    assert all(numbers[k]["value"] == 0 for k in GENERIC_SIX)


def test_plain_pods_are_not_counted_by_the_constraint(model):
    created = [f"default/i{j}" for j in range(12)]
    numbers = model.check(
        created=created, specs=model.pods("init", created),
        bound={k: "node-0" for k in created}, rebound=[], readback={},
        not_device_placed=0, settled=[12])
    assert numbers["zones_over_max_skew"]["value"] == 0


# -- the control ---------------------------------------------------------------

def _control(tree, sound):
    model = tree.deployment(tree.config(tree.cell(CELL)))
    return _run(tree, seconds=0.2,
                cluster_factory=control_cluster(model, sound))


def test_the_control_is_not_correct_and_the_sound_reference_is():
    """One look at the cluster per 128 pods (a node holds 80; the cells'
    control looks once per 1,024): the chunk lands on one node, past its
    allocatable and in one zone. Sound, the same reference is correct."""
    tree = _small_tree(nodes=30, init_pods=0, wave_pods=300)
    real = tree.deployment

    def deployment(config):
        model = real(config)
        model.stale_chunk = 128
        return model
    tree.deployment = deployment
    _, sound, err = _control(tree, True)
    assert sound["correct"] is True, err[-2000:]
    assert sound["compared"]["zones_over_max_skew"] == {
        "value": 0, "limit": 0}
    _, broken, _ = _control(tree, False)
    assert broken["correct"] is False
    failing = {k for k, n in broken["compared"].items()
               if n["value"] > n["limit"]}
    assert failing == {"nodes_over_allocatable", "zones_over_max_skew"}


# -- the system against the plain reference, where the gate binds -------------

def _seeded_problem(seed):
    """Unequal zones: twelve nodes in moon-1, five in moon-2, three in
    moon-3 — so resources alone would draw pods to moon-1 — with
    matching residents that start the zones apart. Returns the
    deployment over those nodes and the resident (node, pod) pairs."""
    rng = np.random.default_rng(seed)
    manifest = Manifest()
    config = copy.deepcopy(manifest.config(manifest.cell(CELL)))
    config.update(nodes=20, init_pods=0, wave_pods=0)
    config["spread"]["max_skew"] = int(rng.integers(1, 4))
    config["node_template"]["allocatable"].update(cpu="2", pods="12")
    model = manifest.deployment(config)
    model.zone_of = np.array([0] * 12 + [1] * 5 + [2] * 3)
    rng.shuffle(model.zone_of)
    start = rng.permutation(3) * config["spread"]["max_skew"]
    residents = []
    for z, count in enumerate(start):
        nodes = np.flatnonzero(model.zone_of == z)
        residents += [int(nodes[j % len(nodes)]) for j in range(count)]
    return model, residents


def _system_and_reference(model, residents):
    """The same cluster twice: the program's cache and framework, and
    the deployment's sound placer told of the same residents."""
    from kubernetes_tpu.api.types import make_node, make_pod
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    from kubernetes_tpu.scheduler.types import PodInfo
    cache = SchedulerCache()
    for i, (name, kw) in enumerate(model.nodes()):
        kw = dict(copy.deepcopy(kw),
                  labels={ZONE: model.zones[model.zone_of[i]]})
        cache.add_node(make_node(name, **kw))
    placer = model.placer(sound=True)
    for j, node in enumerate(residents):
        pod = make_pod(f"res-{j}", uid=f"res-{j}",
                       node_name=model.node_names[node],
                       **copy.deepcopy(model.spread_pod))
        cache.add_pod(PodInfo(pod))
        _follow(model, placer, pod, node)
    return cache, placer


def _follow(model, placer, pod, node):
    """Tell the reference of a placement somebody else made."""
    placer.used[node] += resource_vector(pod_requests(pod), model.resources)
    placer.pods_on[node] += 1
    placer.note(pod, node)


def _allowed_now(model, placer, pod) -> np.ndarray:
    """The nodes the plain reference's filters admit for this pod now:
    resources, the pod count, then the zone gate."""
    row = resource_vector(pod_requests(pod), model.resources)
    fits = np.isfinite(model.score(placer.used, placer.pods_on, row))
    gate = placer.allowed(pod)
    return fits if gate is None else fits & gate


@pytest.mark.parametrize("wave,pods", [
    pytest.param("1", 40, id="W1"),
    pytest.param("32", 40, id="W32"),
    pytest.param("32", 1, id="batch-of-one"),
])
@pytest.mark.parametrize("seed", [3, 11])
def test_every_placement_lies_in_what_the_references_filter_allows(
        monkeypatch, wave, pods, seed):
    """TPUBackend.assign over a batch of spread pods, then the batch
    replayed in its order against the reference: each pod's node is one
    the PodTopologySpread gate (and the resources) admitted at that
    point, for the serial scan, the wavefront scan and a batch of one."""
    from kubernetes_tpu.api.types import make_pod
    from kubernetes_tpu.metrics.registry import SchedulerMetrics
    from kubernetes_tpu.ops import TPUBackend
    from kubernetes_tpu.scheduler.framework import Framework
    from kubernetes_tpu.scheduler.plugins.registry import (
        DEFAULT_SCORE_WEIGHTS,
        build_plugins,
    )
    from kubernetes_tpu.scheduler.types import PodInfo
    if wave == "1":
        monkeypatch.setenv("KTPU_WAVEFRONT", "0")
    else:
        monkeypatch.setenv("KTPU_WAVE_WIDTH", wave)
    model, residents = _seeded_problem(seed)
    cache, placer = _system_and_reference(model, residents)
    batch = [make_pod(f"p{j}", uid=f"p{j}", **copy.deepcopy(model.spread_pod))
             for j in range(pods)]
    backend = TPUBackend(max_batch=64, mesh=None)
    backend.metrics = SchedulerMetrics()
    fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
    assignments, _ = backend.assign(
        [PodInfo(p) for p in batch], cache.update_snapshot(), fwk)
    closed_once = False
    for pod in batch:
        name = assignments[f"default/{pod['metadata']['name']}"]
        ok = _allowed_now(model, placer, pod)
        closed_once |= not placer.open_zones().all()
        if name is None:
            assert not ok.any(), f"{pod['metadata']['name']} left out"
            continue
        node = model.node_index(name)
        assert ok[node], (
            f"{pod['metadata']['name']} on {name}, zone "
            f"{model.zones[model.zone_of[node]]}; counts {placer.count}")
        _follow(model, placer, pod, node)
    assert closed_once            # the gate did bind in this sequence
    deg = backend.metrics.backend_degradations
    assert deg.value(kind="spread_poisoned") == 0
    assert deg.value(kind="host_fallback") == 0


def test_the_table_build_has_a_span_that_says_what_it_built():
    """`solver.spread_table` (layer `attempt`) around the union table's
    build, once per assign(), with the counts of what it compiled; the
    poisoned series exists at 0 from registration on."""
    from kubernetes_tpu.api.types import make_pod
    from kubernetes_tpu.metrics.registry import SchedulerMetrics
    from kubernetes_tpu.ops import TPUBackend
    from kubernetes_tpu.scheduler.framework import Framework
    from kubernetes_tpu.scheduler.plugins.registry import (
        DEFAULT_SCORE_WEIGHTS,
        build_plugins,
    )
    from kubernetes_tpu.scheduler.types import PodInfo
    from kubernetes_tpu.utils.tracing import Tracer, layer_of
    model, residents = _seeded_problem(5)
    cache, _ = _system_and_reference(model, residents)
    backend = TPUBackend(max_batch=16, mesh=None)
    backend.metrics = SchedulerMetrics()
    backend.tracer = Tracer(enabled=True)
    try:
        fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
        batch = [PodInfo(make_pod(f"p{j}", uid=f"p{j}",
                                  **copy.deepcopy(model.spread_pod)))
                 for j in range(20)]                    # two chunks
        backend.assign(batch, cache.update_snapshot(), fwk)
        built = [s for s in backend.tracer.spans
                 if s.name == "solver.spread_table"
                 and getattr(s, "span_id", None)]
        assert len(built) == 1                          # once per assign()
        assert built[0].attrs == {
            "templates": 1, "constraints": 1, "domains": 3}
        assert layer_of("solver.spread_table") == "attempt"
        assert 'kind="spread_poisoned"} 0' in \
            backend.metrics.registry.render()
    finally:
        backend.tracer.enabled = False
