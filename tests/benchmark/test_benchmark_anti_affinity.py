"""The pod-anti-affinity deployment (benchmark/deployments/
pod-anti-affinity.py, benchmark/configs/sched-perf-antiaffinity-5k.json),
on the CPU at a size of tens: the committed files, the groups a pod's
name puts it in, the plain reference's filter, the deployment's own
number planted and read, the control, and the real files run by the
unchanged harness with the real `drain` mix.
"""

import io
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib.control import control_cluster  # noqa: E402
from benchmark.lib.harness import run_cell  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402

CELL = "sched-perf-antiaffinity-5k.drain"
HOSTNAME = "kubernetes.io/hostname"
GENERIC_SIX = ["unbound", "bound_twice", "unknown_node",
               "nodes_over_allocatable", "readback_mismatch",
               "not_device_placed"]
NEW_METRICS = {
    "affinity_rows_ms_per_kpod.drain": ("program_span", "ms/kpod"),
    "affinity_carriers_walked_per_pod.drain": ("program_counter", "pods/pod"),
    "affinity_verify_rejects_per_kpod.drain": (
        "program_counter", "pods/kpod"),
}


def _small_tree(**sizes) -> Manifest:
    """The committed BENCHMARK.json and benchmark/, with the cell's
    configuration cut to tens of nodes and pods (and the mix's waits to
    a test's patience); nothing else differs from what the chip runs."""
    tree = Manifest()
    config = dict(tree.config(tree.cell(CELL)),
                  **(sizes or {"nodes": 60, "init_pods": 30,
                               "wave_pods": 40}))
    tree.config = lambda cell: config
    mix = dict(tree.traffic(tree.cell(CELL)), barrier_seconds=30,
               trace_seconds=1.0, warm_min_chunks=2)
    tree.traffic = lambda cell: mix
    return tree


def _run(tree, trace=False, seconds=1.0, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(CELL, 2**31 + 3232, seconds, trace, manifest=tree,
                  require_chip=False, stdout=out, stderr=err, **kw)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


@pytest.fixture(scope="module")
def model():
    tree = _small_tree(nodes=12, init_pods=0, wave_pods=0)
    return tree.deployment(tree.config(tree.cell(CELL)))


# -- the files, as the harness finds them ----------------------------------

def test_the_committed_files_state_upstreams_deployment():
    manifest = Manifest()
    cell = manifest.cell(CELL)
    config = manifest.config(cell)
    basic = manifest.config({"config": "sched-perf-5k"})
    entry = next(c for c in manifest.doc["configs"]
                 if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "drain"
    assert (config["nodes"], config["init_pods"], config["wave_pods"]) == (
        5000, 1000, 1000)
    assert config["reduced"] == [] == entry["reduced"]
    assert config["deployment"] == "pod-anti-affinity"
    assert config["architecture"] is None
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "SchedulingPodAntiAffinity" in entry["source"]
    # the two 5k drains differ by the term, the init pods and the wave size
    assert config["node_template"] == basic["node_template"]
    assert config["pod_template"] == basic["pod_template"]
    assert set(basic["guarantees"]) | {"one_per_host"} \
        == set(config["guarantees"])
    assert config["anti_affinity"] == {
        "topology_key": HOSTNAME, "label_key": "color",
        "label_value": "green"}
    assert any("a group is one wave" in line for line in config["assumed"])
    # the same mix as the plain drain's, byte for byte: the same file
    assert manifest.traffic(cell) == manifest.traffic(
        manifest.cell("sched-perf-5k.drain"))
    model = manifest.deployment(config)
    assert model.problem() == {"nodes": 5000, "resources": 3, "classes": 1}
    assert len({id(kw) for _, kw in model.nodes()}) == 1
    assert (model.alloc == model.alloc[0]).all()


def test_the_cell_joins_the_drain_metrics_and_brings_three():
    """Membership only, so that a later PR appends its cells and metrics
    without an edit here."""
    manifest = Manifest()
    doc = manifest.doc
    listing = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for name in ("pods_bound_per_s", "frag_occupied_pct",
                 "create_ack_p50_ms.drain", "prep_ms_per_kpod.drain",
                 "solve_wait_ms_per_chunk.drain",
                 "device_busy_ms_per_kpod.drain", "device_idle_pct.drain",
                 "peak_hbm_mb.drain", "compiles_in_window.drain",
                 "trace_lower_s_in_window.drain",
                 "mask_solve_update_roofline.drain", "lone_batch_pods.drain"):
        assert CELL in listing[name]["workloads"], name
    for name, (source, unit) in NEW_METRICS.items():
        m = listing[name]
        assert m["workloads"] == [CELL]
        assert (m["layer"], m["moves"], m["better"]) == (
            "attempt", "pods_bound_per_s", "lower")
        assert (m["source"], m["unit"]) == (source, unit)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_file_resolves_and_stays_off_the_ledger(name):
    """Data over the reader that exists; each reads a `scheduler_tpu_`
    series, none a `ktpu_` family of the tracer's ledger
    (test_benchmark_host_metrics.py pins the set of metrics that do)."""
    manifest = Manifest()
    spec = manifest.metric_file(name)
    assert spec["reader"] == "counter_ratio" and spec["what"]
    assert callable(manifest.reader(spec["reader"]))
    args = spec["args"]
    assert args["numerator"]["name"].startswith("scheduler_tpu_")
    assert args["denominator"] in ("pods", "kpods")


# -- groups -------------------------------------------------------------------

@pytest.mark.parametrize("phase,name,group", [
    ("init", "init-5", "init"),
    ("warm", "warm0-17", "warm0"),
    ("warm", "warm3-0", "warm3"),
    ("burst", "burst7-3", "burst7"),
    ("burst", "burst40-39", "burst40"),
    ("measured", "s7fffffff-w3-999", "s7fffffff-w3"),
    ("measured", "s80000c9a-w12-0", "s80000c9a-w12"),
    ("measured", "a", "a"),
])
def test_a_pods_group_is_its_names_prefix(model, phase, name, group):
    args, = model.pods(phase, [name])
    assert args["labels"] == {"color": f"green-{group}"}
    term, = args["affinity"]["podAntiAffinity"][
        "requiredDuringSchedulingIgnoredDuringExecution"]
    assert term == {"labelSelector": {"matchLabels": args["labels"]},
                    "topologyKey": HOSTNAME}
    assert args["requests"] == model.config["pod_template"]["requests"]
    assert set(args) == {"requests", "labels", "affinity"}


def test_a_groups_pods_share_one_argument_object(model):
    names = [f"s1-w0-{i}" for i in range(5)] + ["s1-w1-0", "b", "s1-w0-9"]
    specs = model.pods("measured", names)
    assert len({id(kw) for kw in specs}) == 3
    assert specs[0] is specs[4] is specs[7]
    assert specs[0] is model.pods("warm", ["s1-w0-77"])[0]   # by name alone
    assert specs[5] is not specs[0] and specs[6] is not specs[0]


def test_the_pod_made_from_the_arguments_carries_label_and_term(model):
    from kubernetes_tpu.api.types import make_pod
    from kubernetes_tpu.scheduler.types import PodInfo
    args, = model.pods("measured", ["s1-w2-0"])
    pi = PodInfo(make_pod("s1-w2-0", **args))
    assert pi.labels == {"color": "green-s1-w2"}
    term, = pi.required_anti_affinity_terms
    assert term["topologyKey"] == HOSTNAME
    assert not pi.required_affinity_terms


# -- the plain reference --------------------------------------------------------

def _pod(model, name):
    from kubernetes_tpu.api.types import make_pod
    args, = model.pods("measured", [name])
    return make_pod(name, **args)


def test_the_reference_closes_exactly_the_hosts_of_the_group(model):
    placer = model.placer(sound=True)
    assert placer.allowed(_pod(model, "ga-0")) is None       # none yet
    for j, node in enumerate([2, 5, 7]):
        placer.note(_pod(model, f"ga-{j}"), node)
    placer.note(_pod(model, "gb-0"), 5)
    open_a = placer.allowed(_pod(model, "ga-9"))
    assert sorted(np.flatnonzero(~open_a)) == [2, 5, 7]
    open_b = placer.allowed(_pod(model, "gb-9"))
    assert sorted(np.flatnonzero(~open_b)) == [5]
    assert placer.allowed(_pod(model, "gc-0")) is None


def test_the_reference_fills_a_group_one_a_host_then_places_none(model):
    placer = model.placer(sound=True)
    got = [placer.place(_pod(model, f"ga-{j}"))
           for j in range(model.n_nodes + 3)]
    assert sorted(got[:model.n_nodes]) == list(range(model.n_nodes))
    assert got[model.n_nodes:] == [-1, -1, -1]
    # another group starts over on the same hosts
    assert placer.place(_pod(model, "gb-0")) == 0


# -- the deployment's own number -------------------------------------------------

def _check(model, placement):
    """`check` over bindings planted by the test: (pod name, node)."""
    created = [f"default/{name}" for name, _ in placement]
    specs = model.pods("measured", [name for name, _ in placement])
    bound = {k: model.node_names[node]
             for k, (_, node) in zip(created, placement) if node is not None}
    return model.check(created=created, specs=specs, bound=bound,
                       rebound=[], readback={}, not_device_placed=0,
                       settled=[len(created)])


@pytest.mark.parametrize("placement,sharing", [
    ([("ga-0", 0), ("ga-1", 1), ("gb-0", 0), ("gb-1", 1)], 0),
    ([("ga-0", 3), ("ga-1", 3)], 1),
    ([("ga-0", 3), ("ga-1", 3), ("ga-2", 3)], 1),          # one pair of
    ([("ga-0", 3), ("ga-1", 3), ("gb-0", 3), ("gb-1", 3)], 2),
    ([("ga-0", 1), ("ga-1", 1), ("ga-2", 2), ("ga-3", 2), ("gb-0", 2)], 2),
    ([("init-0", 4), ("init-1", 4), ("x", 4), ("y", 4)], 1),
])
def test_bindings_planted_on_one_host_read_as_sharing(
        model, placement, sharing):
    numbers = _check(model, placement)
    assert list(numbers) == GENERIC_SIX + ["hosts_sharing_a_group"]
    assert numbers["hosts_sharing_a_group"] == {
        "value": sharing, "limit": 0}
    assert all(numbers[k]["value"] == 0 for k in GENERIC_SIX)


def test_an_unbound_pod_shares_nothing(model):
    numbers = _check(model, [("ga-0", 3), ("ga-1", None)])
    assert numbers["hosts_sharing_a_group"]["value"] == 0
    assert numbers["unbound"]["value"] == 1


# -- the control -------------------------------------------------------------------

def test_the_control_is_not_correct_and_the_sound_reference_is():
    """One look at the cluster per 128 pods (a node holds 80; the cells'
    control looks once per 1,024): the chunk lands on one node, past its
    allocatable and a group sharing it. Sound, the same reference is
    correct."""
    tree = _small_tree(nodes=45, init_pods=0, wave_pods=24)
    real = tree.deployment

    def deployment(config):
        model = real(config)
        model.stale_chunk = 128
        return model
    tree.deployment = deployment
    model = tree.deployment(tree.config(tree.cell(CELL)))
    _, sound, err = _run(tree, seconds=0.2,
                         cluster_factory=control_cluster(model, True))
    assert sound["correct"] is True, err[-2000:]
    assert sound["compared"]["hosts_sharing_a_group"] == {
        "value": 0, "limit": 0}
    _, broken, _ = _run(tree, seconds=0.2,
                        cluster_factory=control_cluster(model, False))
    assert broken["correct"] is False
    failing = {k for k, n in broken["compared"].items()
               if n["value"] > n["limit"]}
    assert failing == {"nodes_over_allocatable", "hosts_sharing_a_group"}


# -- the cell, run by the unchanged harness ------------------------------------------

@pytest.fixture(scope="module")
def timed():
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
    return *_run(_small_tree(), cluster_factory=Watched), seen


def test_the_cell_runs_correct_with_the_lone_pod_on_the_device_path(timed):
    rc, result, err, seen = timed
    assert rc == 0 and result["correct"] is True, err[-3000:]
    assert result["attempted"] >= 40 and result["failed"] == 0
    assert list(result["compared"]) == GENERIC_SIX + [
        "hosts_sharing_a_group"]
    assert all(n == {"value": 0, "limit": 0}
               for n in result["compared"].values())
    assert set(result["metrics"]) == {
        "pods_bound_per_s", "frag_occupied_pct", "setup_s"}
    # `drain.json`'s burst of 1: a constrained pod popped alone rode a
    # batch of one, and nothing took the host scheduler
    assert seen["lone_batch"] >= 1 and seen["host_path"] == 0


def test_the_traced_run_reads_the_new_metrics():
    """Host spans and counters read on any platform; the device's
    metrics are absent here, never zero."""
    rc, result, err = _run(_small_tree(), trace=True)
    assert rc == 0 and result["correct"] is True, err[-3000:]
    metrics = result["metrics"]
    assert metrics["affinity_rows_ms_per_kpod.drain"]["value"] > 0
    # every wave is one batch here, and every resident is walked twice
    assert metrics["affinity_carriers_walked_per_pod.drain"]["value"] > 2
    assert metrics["affinity_verify_rejects_per_kpod.drain"]["value"] >= 0
    assert metrics["lone_batch_pods.drain"]["value"] >= 0
    assert "device_busy_ms_per_kpod.drain" not in metrics
