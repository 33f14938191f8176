"""The preferred-pod-affinity deployment (benchmark/deployments/
preferred-pod-affinity.py, benchmark/configs/
sched-perf-preferred-affinity-5k.json), on the CPU at a size of tens:
the committed files as the harness finds them, the plain reference
against the host scheduler's plugins one pod at a time, the two controls
failing by the deployment's own number, the real files run by the
unchanged harness, and the new roofline's work model.
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

from benchmark.lib import affinity_work, work_model  # noqa: E402
from benchmark.lib.control import control_cluster  # noqa: E402
from benchmark.lib.harness import run_cell  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402
from benchmark.lib.reference import Placer  # noqa: E402

CELL = "sched-perf-preferred-affinity-5k.drain"
OWN = "occupied_nodes_over_reference"
GENERIC_SIX = ["unbound", "bound_twice", "unknown_node",
               "nodes_over_allocatable", "readback_mismatch",
               "not_device_placed"]
NEW_METRICS = {
    "affinity_score_ms_per_kpod.drain": (
        "program_span", "ms/kpod", "lower", "attempt"),
    "affinity_score_carried_pct.drain": (
        "program_counter", "%", "higher", "attempt"),
    "affinity_scan_roofline.drain": (
        "device_trace", "%", "higher", "kernels"),
}


def _small_tree(**sizes) -> Manifest:
    """The committed BENCHMARK.json and benchmark/, with the cell's
    configuration cut to tens of nodes and pods (and the mix's waits to
    a test's patience); nothing else differs from what the chip runs."""
    tree = Manifest()
    config = dict(tree.config(tree.cell(CELL)),
                  **(sizes or {"nodes": 40, "init_pods": 60,
                               "wave_pods": 50}))
    tree.config = lambda cell: config
    mix = dict(tree.traffic(tree.cell(CELL)), barrier_seconds=30,
               trace_seconds=1.0, warm_min_chunks=2)
    tree.traffic = lambda cell: mix
    return tree


def _run(tree, trace=False, seconds=1.0, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(CELL, 2**31 + 4141, seconds, trace, manifest=tree,
                  require_chip=False, stdout=out, stderr=err, **kw)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


# -- the files, as the harness finds them ------------------------------------

def test_the_committed_files_state_upstreams_deployment():
    manifest = Manifest()
    cell = manifest.cell(CELL)
    config = manifest.config(cell)
    basic = manifest.config({"config": "sched-perf-5k"})
    entry = next(c for c in manifest.doc["configs"]
                 if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "drain"
    assert (config["nodes"], config["init_pods"], config["wave_pods"]) == (
        5000, 5000, 1000)
    assert config["reduced"] == [] == entry["reduced"]
    assert config["deployment"] == "preferred-pod-affinity"
    assert config["architecture"] is None
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "SchedulingPreferredPodAffinity" in entry["source"]
    assert config["node_template"] == basic["node_template"]
    assert config["pod_template"] == basic["pod_template"]
    assert set(basic["guarantees"]) | {"packs_with_siblings"} \
        == set(config["guarantees"])
    assert config["preferred_affinity"] == {
        "label_key": "foo", "label_value": "", "weight": 1,
        "topology_key": "kubernetes.io/hostname"}
    assert manifest.traffic(cell) == manifest.traffic(
        manifest.cell("sched-perf-5k.drain"))
    model = manifest.deployment(config)
    assert model.problem() == {"nodes": 5000, "resources": 3, "classes": 1}
    # one argument object for every pod of every phase, term and label on it
    specs = [kw for phase in ("init", "warm", "burst", "measured")
             for kw in model.pods(phase, ["a", "b"])]
    assert len({id(kw) for kw in specs}) == 1
    term, = specs[0]["affinity"]["podAffinity"][
        "preferredDuringSchedulingIgnoredDuringExecution"]
    assert term["weight"] == 1 and specs[0]["labels"] == {"foo": ""}
    assert term["podAffinityTerm"] == {
        "labelSelector": {"matchLabels": {"foo": ""}},
        "topologyKey": "kubernetes.io/hostname"}


def test_the_cell_joins_the_drain_metrics_and_brings_three():
    """Membership only, so that a later PR appends its cells and metrics
    without an edit here."""
    doc = Manifest().doc
    listing = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for name in ("pods_bound_per_s", "frag_occupied_pct",
                 "create_ack_p50_ms.drain", "prep_ms_per_kpod.drain",
                 "solve_wait_ms_per_chunk.drain",
                 "device_busy_ms_per_kpod.drain", "device_idle_pct.drain",
                 "peak_hbm_mb.drain", "compiles_in_window.drain",
                 "trace_lower_s_in_window.drain",
                 "mask_solve_update_roofline.drain",
                 "tensors_ms_per_kpod.drain", "lone_batch_pods.drain"):
        assert CELL in listing[name]["workloads"], name
    for name, (source, unit, better, layer) in NEW_METRICS.items():
        m = listing[name]
        assert CELL in m["workloads"]
        assert (m["layer"], m["moves"], m["better"]) == (
            layer, "pods_bound_per_s", better)
        assert (m["source"], m["unit"]) == (source, unit)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_file_resolves(name):
    manifest = Manifest()
    spec = manifest.metric_file(name)
    assert spec["what"] and callable(manifest.reader(spec["reader"]))
    if spec["reader"] == "counter_ratio":
        assert spec["args"]["numerator"]["name"].startswith(
            "scheduler_tpu_affinity_score_")
    else:
        assert spec["args"] == {"program": "jit__mask_solve_update"}


# -- the plain reference against the host scheduler ----------------------------

def _model(nodes: int):
    tree = _small_tree(nodes=nodes, init_pods=0, wave_pods=0)
    return tree.deployment(tree.config(tree.cell(CELL)))


@pytest.mark.parametrize("mix", ["all", "mixed"])
def test_the_reference_agrees_with_the_host_plugins(mix):
    """One pod at a time, plugin by plugin (the default profile: every
    filter, every score, its weights), highest total wins and the
    lowest node index on ties: the same node as the reference's, for
    every pod. "mixed" interleaves pods without the term and pods with
    the label alone, so that each half of the score is read apart."""
    from kubernetes_tpu.api.types import make_node, make_pod
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    from kubernetes_tpu.scheduler.framework import CycleState, Framework
    from kubernetes_tpu.scheduler.plugins.registry import (
        DEFAULT_SCORE_WEIGHTS,
        build_plugins,
    )
    from kubernetes_tpu.scheduler.types import PodInfo
    model = _model(8)
    cache = SchedulerCache()
    for name, kw in model.nodes():
        cache.add_node(make_node(name, **copy.deepcopy(kw)))
    fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
    placer = model.placer(sound=True)
    plain = model.config["pod_template"]
    labelled = dict(plain, labels={"foo": ""})
    kinds = [model.pod_args] if mix == "all" \
        else [model.pod_args, plain, model.pod_args, labelled]
    for j in range(230):
        pod = make_pod(f"p{j}", uid=f"p{j}",
                       **copy.deepcopy(kinds[j % len(kinds)]))
        want = placer.place(copy.deepcopy(pod))
        pi = PodInfo(pod)
        snapshot = cache.update_snapshot()
        state = CycleState()
        fwk.run_pre_filter(state, pi, snapshot)
        feasible = [ni for ni in snapshot.nodes
                    if fwk.run_filters(state, pi, ni).is_success()]
        fwk.run_pre_score(state, pi, feasible)
        scores = fwk.run_scores(state, pi, feasible)
        best = max(scores.values())
        got = next(i for i, ni in enumerate(snapshot.nodes)
                   if scores.get(ni.name) == best)
        assert got == want, (j, scores)
        pod["spec"]["nodeName"] = model.node_names[got]
        cache.add_pod(PodInfo(pod))
    occupied = int((placer.pods_on > 0).sum())
    assert occupied == (3 if mix == "all" else 8)


def test_the_reference_fills_node_after_node():
    model = _model(200)
    specs = model.pods("measured", ["x"] * 1000)
    created = [f"default/x-{j}" for j in range(1000)]
    numbers = model.own_numbers(created=created, specs=specs, bound={},
                                settled=[])
    # nothing bound: the program occupies 0 nodes, the reference 13
    assert numbers == {OWN: {"value": -13, "limit": 0}}
    bound = {k: model.node_names[j // 80] for j, k in enumerate(created)}
    assert model.own_numbers(created=created, specs=specs, bound=bound,
                             settled=[])[OWN]["value"] == 0
    bound[created[0]] = model.node_names[150]
    assert model.own_numbers(created=created, specs=specs, bound=bound,
                             settled=[])[OWN]["value"] == 1


# -- the controls ----------------------------------------------------------------

def _blind(model):
    """The InterPodAffinity-blind control: resources sound, no score for
    the term at all."""
    class Blind(type(model)):
        def placer(self, sound):
            return Placer(self, 1)
    return Blind(model.config)


@pytest.mark.parametrize("which", ["sound", "stale", "blind"])
def test_the_controls_fail_by_the_deployments_own_number(which):
    """The stale chunk (the InterPodAffinity weights looked at once per
    1,024 pods) and the blind control place every pod within allocatable
    and still occupy many nodes where the reference fills one after the
    other: `correct` is false by `occupied_nodes_over_reference` alone.
    Sound, the same reference is correct."""
    tree = _small_tree(nodes=40, init_pods=60, wave_pods=50)
    model = tree.deployment(tree.config(tree.cell(CELL)))
    if which == "blind":
        model = _blind(model)
    _, result, err = _run(tree, seconds=0.3, cluster_factory=control_cluster(
        model, which == "sound"))
    failing = {k for k, n in result["compared"].items()
               if n["value"] > n["limit"]}
    if which == "sound":
        assert result["correct"] is True, err[-2000:]
        assert result["compared"][OWN]["value"] <= 0
    else:
        assert result["correct"] is False
        assert failing == {OWN}
        assert result["compared"][OWN]["value"] >= 20


# -- the cell, run by the unchanged harness ---------------------------------------

@pytest.fixture(scope="module")
def timed():
    from benchmark.lib.cluster import Cluster
    seen = {}

    class Watched(Cluster):
        async def stop(self):
            deg = self.metrics.backend_degradations
            seen.update({kind: deg.value(kind=kind)
                         for kind in ("lone_batch", "host_path")})
            seen["carried"] = self.metrics.affinity_score_classes.value(
                kind="carried")
            await super().stop()
    return *_run(_small_tree(), cluster_factory=Watched), seen


def test_the_cell_runs_correct_with_every_pod_carried(timed):
    rc, result, err, seen = timed
    assert rc == 0 and result["correct"] is True, err[-3000:]
    assert result["attempted"] >= 50 and result["failed"] == 0
    assert list(result["compared"]) == GENERIC_SIX + [OWN]
    assert all(n["value"] <= n["limit"]
               for n in result["compared"].values())
    assert set(result["metrics"]) == {
        "pods_bound_per_s", "frag_occupied_pct", "setup_s"}
    assert seen["lone_batch"] >= 1 and seen["host_path"] == 0
    assert seen["carried"] >= 1


def test_the_traced_run_reads_the_new_metrics():
    """Host spans and counters read on any platform; the device's
    metrics, the new roofline among them, are absent here, never zero."""
    rc, result, err = _run(_small_tree(), trace=True)
    assert rc == 0 and result["correct"] is True, err[-3000:]
    metrics = result["metrics"]
    assert metrics["affinity_score_ms_per_kpod.drain"]["value"] > 0
    assert metrics["affinity_score_carried_pct.drain"]["value"] == 100.0
    assert "affinity_scan_roofline.drain" not in metrics
    assert "device_busy_ms_per_kpod.drain" not in metrics


# -- the roofline's work model ------------------------------------------------------

def test_the_carried_work_grows_with_steps_and_nodes():
    base = dict(resources=3, pods=1000, classes=1, chunks=2)
    plain = work_model.solve_work(nodes=5000, **base)
    none = affinity_work.carried_solve_work(nodes=5000, steps=0, **base)
    assert none == plain
    ops1, bytes1 = affinity_work.carried_solve_work(
        nodes=5000, steps=1000, **base)
    ops2, bytes2 = affinity_work.carried_solve_work(
        nodes=5000, steps=2000, **base)
    ops3, bytes3 = affinity_work.carried_solve_work(
        nodes=10000, steps=1000, **base)
    assert ops2 - ops1 == pytest.approx(ops1 - plain[0])
    assert bytes2 - bytes1 == pytest.approx(bytes1 - plain[1])
    # a step reads and writes three values of N, 4 bytes each
    assert bytes1 - plain[1] == 1000 * 5000 * 3 * 4
    assert ops3 > ops1 and bytes3 > bytes1


def test_the_roofline_reader_reads_a_recorded_program():
    from benchmark.lib.harness import Context
    manifest = Manifest()
    read = manifest.reader("affinity_scan_roofline")
    ctx = Context()
    ctx.model = _model(5000)
    ctx.device_kind = "TPU v5 lite"
    assert read(ctx, program="jit__mask_solve_update") is None
    ctx.traced_pods = 1000
    ctx.trace = {"programs": {"jit__mask_solve_update": {
        "runs": 2, "seconds": 0.025}}}
    got = read(ctx, program="jit__mask_solve_update")
    ops, bytes_ = affinity_work.carried_solve_work(
        nodes=5000, resources=3, pods=1000, classes=1, chunks=2,
        steps=1000)
    assert got == pytest.approx(100.0 * bytes_ / 819e9 / 0.025)
    assert 0 < got < 100
    assert np.isfinite(got)
