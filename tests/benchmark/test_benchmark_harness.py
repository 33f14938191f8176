"""The harness end to end, on the CPU at a tiny size.

A cell is refused without a TPU. A configuration, a traffic mix and a
per-layer metric added as FILES AND ENTRIES ONLY — to a copy of
BENCHMARK.json and benchmark/, as a later PR's tree would hold them, no
file that is there edited — are found and run by the unchanged code
(device metrics absent, never zero). So is CODE: a constraint deployment
(its zoned nodes, its pods, its plain reference with a number of its
own, its control) and a traffic kind, the files of `later_pr/` here. The
controls and the planted faults come out as not correct, and a cell runs
on exactly its `chips`.
"""

import asyncio
import copy

import io
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib.cluster import Cluster  # noqa: E402
from benchmark.lib.control import control_cluster  # noqa: E402
from benchmark.lib.harness import Refused, run_cell  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402
from benchmark.lib.reference import ClusterModel  # noqa: E402
from benchmark.lib.traffic import Generator  # noqa: E402

LATER_PR = Path(__file__).resolve().parent / "later_pr"
GENERIC_SIX = ["unbound", "bound_twice", "unknown_node",
               "nodes_over_allocatable", "readback_mismatch",
               "not_device_placed"]

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}

REHEARSAL_CONFIG = {
    "name": "rehearsal", "source": "tests/benchmark: a toy cluster",
    "nodes": 60, "init_pods": 20, "wave_pods": 120,
    "node_template": {"allocatable": {
        "cpu": "8", "memory": "32Gi", "pods": "110"}},
    "pod_template": {"requests": {"cpu": "100m", "memory": "250Mi"}},
    "chips": 1, "guarantees": {}, "assumed": [], "reduced": [],
}


@pytest.fixture(scope="module")
def later_tree(tmp_path_factory):
    """A later PR's tree: the benchmark as committed, plus files and
    entries for two configurations (one names a deployment of its own),
    three traffic mixes (one of a kind of its own) and one metric."""
    root = tmp_path_factory.mktemp("later_pr")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = root / "benchmark"
    (bench / "configs" / "rehearsal.json").write_text(
        json.dumps(REHEARSAL_CONFIG))
    waves = json.loads((bench / "traffic" / "drain.json").read_text())
    waves.update(trace_seconds=1.0, barrier_seconds=20)
    (bench / "traffic" / "toy-waves.json").write_text(json.dumps(waves))
    loop = json.loads((bench / "traffic" / "trickle.json").read_text())
    loop.update(rate=150, warm_quiet_seconds=0.5, warm_cap_seconds=3,
                trace_seconds=1.0, barrier_seconds=20)
    (bench / "traffic" / "toy-loop.json").write_text(json.dumps(loop))
    (bench / "metrics" / "bound_events.toy.json").write_text(json.dumps({
        "what": "scheduled attempts per pod bound: a counter the program "
                "already has, read by a reader that is already there",
        "reader": "counter_ratio",
        "args": {"numerator": {"name": "scheduler_schedule_attempts_total",
                               "match": {"result": "scheduled"}},
                 "denominator": "pods"}}))
    # code as files: a deployment, its configuration, a traffic kind
    shutil.copytree(LATER_PR, bench, dirs_exist_ok=True)
    zoned = json.loads((bench / "configs" / "zoned.json").read_text())
    for cfg in (REHEARSAL_CONFIG, zoned):
        doc["configs"].append({
            "name": cfg["name"], "source": cfg["source"],
            "file": f"benchmark/configs/{cfg['name']}.json", "reduced": [],
            "why": "toy"})
    cells = ["rehearsal.toy-waves", "rehearsal.toy-loop"]
    waves = [cells[0], "zoned.zoned-waves", "rehearsal.one-wave",
             "rehearsal4.toy-waves"]
    doc["configs"].append(dict(
        doc["configs"][-2], name="rehearsal4",
        file="benchmark/configs/rehearsal4.json"))
    (bench / "configs" / "rehearsal4.json").write_text(
        json.dumps(dict(REHEARSAL_CONFIG, name="rehearsal4")))
    for name in waves + cells[1:]:
        config, traffic = name.split(".")
        doc["workloads"].append({
            "name": name, "config": config, "traffic": traffic,
            "chips": 4 if config == "rehearsal4" else 1, "why": "toy"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] += waves \
                if "sched-perf-5k.drain" in m["workloads"] else cells[1:]
    doc["per_layer"].append({
        "name": "bound_events.toy", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "queue and serving tier",
        "moves": "pods_bound_per_s", "workloads": [cells[0]]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    after = {p: p.read_bytes() for p in before}
    assert after == before          # nothing that was there was edited
    return Manifest(root, bench)


def _run(manifest, cell, trace, seconds=1.5, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(cell, 2**31 + 12345, seconds, trace, manifest=manifest,
                  require_chip=False, stdout=out, stderr=err, **kw)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), err.getvalue()


@pytest.fixture(scope="module")
def waves_timed(later_tree):
    return _run(later_tree, "rehearsal.toy-waves", False)


@pytest.fixture(scope="module")
def waves_traced(later_tree):
    return _run(later_tree, "rehearsal.toy-waves", True)


@pytest.fixture(scope="module")
def loop_timed(later_tree):
    return _run(later_tree, "rehearsal.toy-loop", False)


def test_a_cell_is_refused_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "sched-perf-5k.drain", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "refused" in p.stderr and "TPU" in p.stderr


def test_an_unknown_cell_is_an_error(later_tree):
    with pytest.raises(KeyError):
        run_cell("no-such.cell", 1, 1.0, False, manifest=later_tree,
                 require_chip=False)


def test_last_line_has_the_contracts_keys(waves_timed):
    rc, result, err = waves_timed
    assert rc == 0
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "compared"      # the numbers come last
    assert set(result["device"]) == DEVICE_KEYS
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, err[-2000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_closed_waves_report_rate_packing_and_set_up(waves_timed):
    _, result, err = waves_timed
    assert set(result["metrics"]) == {
        "pods_bound_per_s", "frag_occupied_pct", "setup_s"}
    assert result["attempted"] % 120 == 0 or result["attempted"] > 120
    window = json.loads(
        [ln for ln in err.splitlines()
         if ln.startswith("bench: window ")][-1][len("bench: window "):])
    assert len(window["wave_rates"]) == len(window["waves"]) >= 1
    assert {"compiles_in_window", "gc_in_window"} <= set(window)
    # every number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-6:]
    assert all(ln.startswith("bench: compared ") and "(limit 0)" in ln
               for ln in tail)


def test_open_loop_reports_the_median_and_the_tail(loop_timed):
    rc, result, err = loop_timed
    assert rc == 0 and result["correct"] is True, err[-2000:]
    assert set(result["metrics"]) == {
        "sched_latency_p50_ms", "sched_latency_p95_ms", "setup_s"}
    m = result["metrics"]
    assert 0 < m["sched_latency_p50_ms"]["value"] \
        <= m["sched_latency_p95_ms"]["value"]


def test_every_seed_sends_the_same_gaps_in_another_order(later_tree):
    mix = later_tree.traffic(later_tree.cell("rehearsal.toy-loop"))
    model = ClusterModel(REHEARSAL_CONFIG)
    a, b = (Generator(None, model, mix, seed)._gaps(4.0, seed)
            for seed in (1, 2**31 + 7))
    assert a != b and sorted(a) == sorted(b) and len(a) > 100


def test_traced_run_reads_per_layer_metrics_and_no_device_number(
        waves_traced, later_tree):
    rc, result, err = waves_traced
    assert rc == 0 and result["correct"] is True, err[-2000:]
    names = set(result["metrics"])
    # the metric added as a file, read from the program's counter
    assert result["metrics"]["bound_events.toy"]["value"] >= 1.0
    assert {"create_ack_p50_ms.drain", "compiles_in_window.drain",
            "trace_lower_s_in_window.drain"} <= names
    # no chip, no trace of one: device metrics are absent, not zero
    assert not names & {
        "device_busy_ms_per_kpod.drain", "device_idle_pct.drain",
        "mask_solve_update_roofline.drain", "peak_hbm_mb.drain"}
    assert "busy_s" not in result["device"]
    assert "breakdown" not in result
    assert not (later_tree.root / ".bench_scratch" / "trace").exists()


def _control(tree, cell, sound, **kw):
    """benchmark/control.py's run of a cell: the deployment's reference
    in the program's place."""
    model = tree.deployment(tree.config(tree.cell(cell)))
    return _run(tree, cell, False, seconds=0.2,
                cluster_factory=control_cluster(model, sound), **kw)


def _default_control_tree(later_tree):
    """One look at the cluster per 128 pods (a node holds 80) where the
    cells' control has 1,024: the toy's nodes go past allocatable."""
    config = dict(REHEARSAL_CONFIG, nodes=40, init_pods=0, wave_pods=300)
    tree = Manifest(later_tree.root, later_tree.bench_dir)
    tree.config = lambda cell: config

    def deployment(config):
        model = ClusterModel(config)
        model.stale_chunk = 128
        return model
    tree.deployment = deployment
    return tree


@pytest.mark.parametrize("cell,number", [
    ("rehearsal.toy-waves", "nodes_over_allocatable"),
    ("zoned.zoned-waves", "zones_over_max_skew")])
def test_the_control_comes_out_as_not_correct(later_tree, cell, number):
    """The deployment's reference in the program's place: sound, it is
    correct; with the deployment's guarantee broken, `correct` is false
    by the deployment's number. (The zoned control places by resources
    only, and the generic six read 0.)"""
    tree = later_tree if cell.startswith("zoned") \
        else _default_control_tree(later_tree)
    _, sound, err = _control(tree, cell, True)
    assert sound["correct"] is True, err[-2000:]
    assert sound["compared"][number] == {"value": 0, "limit": 0}
    _, broken, _ = _control(tree, cell, False)
    assert broken["correct"] is False
    failing = [k for k, n in broken["compared"].items()
               if n["value"] > n["limit"]]
    assert failing == [number]
    assert list(broken["compared"])[:6] == GENERIC_SIX


class _AlteredBind(Cluster):
    """The timed path with an answer altered where it is produced: the
    scheduler's Bind step writes node-0 whatever the solve chose."""

    def build_scheduler(self):
        sched = super().build_scheduler()
        for fwk in sched.profiles.values():
            real = fwk.run_bind

            async def run_bind(state, pi, node_name, _real=real):
                return await _real(state, pi, "node-0")
            fwk.run_bind = run_bind
        return sched


class _DroppedBind(Cluster):
    """...and with every fifth answer never delivered."""

    def build_scheduler(self):
        from kubernetes_tpu.scheduler.framework import Status
        sched = super().build_scheduler()
        count = [0]
        for fwk in sched.profiles.values():
            real = fwk.run_bind

            async def run_bind(state, pi, node_name, _real=real):
                count[0] += 1
                if count[0] % 5 == 0:
                    return Status.success()     # claims it, writes nothing
                return await _real(state, pi, node_name)
            fwk.run_bind = run_bind
        return sched


def test_an_altered_answer_comes_out_as_not_correct(later_tree):
    _, result, _ = _run(later_tree, "rehearsal.toy-waves", False,
                        cluster_factory=_AlteredBind)
    assert result["correct"] is False
    assert result["compared"]["nodes_over_allocatable"]["value"] >= 1


def test_a_dropped_answer_comes_out_as_not_correct(later_tree):
    tree = Manifest(later_tree.root, later_tree.bench_dir)
    real = tree.traffic
    tree.traffic = lambda cell: dict(real(cell), barrier_seconds=2)
    with pytest.raises(RuntimeError, match="unbound"):
        # set-up itself refuses to go on with pods that never bind ...
        _run(tree, "rehearsal.toy-waves", False,
             cluster_factory=_DroppedBind)


def test_a_host_scheduler_run_does_not_pass_as_a_device_run(later_tree):
    class _NoBackend(Cluster):
        def build_scheduler(self):
            sched = super().build_scheduler()
            sched.backend = None            # what an open circuit leaves
            return sched
    _, result, _ = _run(later_tree, "rehearsal.toy-waves", False,
                        cluster_factory=_NoBackend)
    assert result["correct"] is False
    assert result["compared"]["not_device_placed"]["value"] >= 1


# -- code found by name: a deployment, a traffic kind -----------------------

@pytest.fixture(scope="module")
def zoned_timed(later_tree):
    return _run(later_tree, "zoned.zoned-waves", False)


def test_a_deployment_added_as_files_is_found_and_held_to_its_number(
        zoned_timed):
    """60 nodes in three zones, plain init pods, measured pods under a
    zone spread constraint: run by the unchanged code, with the
    deployment's number printed beside its limit after the six."""
    rc, result, err = zoned_timed
    assert rc == 0 and result["correct"] is True, err[-3000:]
    assert result["attempted"] >= 120 and result["failed"] == 0
    assert list(result["compared"]) == GENERIC_SIX + ["zones_over_max_skew"]
    assert all(n == {"value": 0, "limit": 0}
               for n in result["compared"].values())
    assert err.strip().splitlines()[-1] == \
        "bench: compared zones_over_max_skew = 0 (limit 0)"
    assert set(result["metrics"]) == {
        "pods_bound_per_s", "frag_occupied_pct", "setup_s"}


def test_the_deployments_objects_are_what_its_file_says(later_tree):
    model = later_tree.deployment(
        later_tree.config(later_tree.cell("zoned.zoned-waves")))
    nodes = dict(model.nodes())
    assert len(nodes) == 60 and model.n_nodes == 60
    zone = "topology.kubernetes.io/zone"
    assert [nodes[f"node-{i}"]["labels"][zone] for i in range(4)] == [
        "moon-1", "moon-2", "moon-3", "moon-1"]
    assert model.alloc[0, 0] == 2 * model.alloc[1, 0]    # mixed node sizes
    init, = model.pods("init", ["a"])
    measured, = model.pods("measured", ["b"])
    assert "topology_spread_constraints" not in init
    constraint, = measured["topology_spread_constraints"]
    assert constraint["maxSkew"] == 1
    assert constraint["whenUnsatisfiable"] == "DoNotSchedule"
    assert measured["labels"] == {"app": "spread"}
    assert model.problem() == {"nodes": 60, "resources": 3, "classes": 1}


def test_a_traffic_kind_added_as_a_file_resolves_and_runs(later_tree):
    mix = later_tree.traffic(later_tree.cell("rehearsal.one-wave"))
    assert not Generator.defines(mix["kind"])
    assert callable(later_tree.kind(mix["kind"]).window)
    rc, result, err = _run(later_tree, "rehearsal.one-wave", False)
    assert rc == 0 and result["correct"] is True, err[-2000:]
    assert result["attempted"] == 120 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "pods_bound_per_s", "frag_occupied_pct", "setup_s"}


@pytest.mark.parametrize("what,call", [
    ("deployments", lambda m: m.deployment({"deployment": "no-such"})),
    ("kinds", lambda m: m.kind("no-such")),
    ("readers", lambda m: m.reader("no-such"))])
def test_a_name_with_no_file_is_an_error_that_names_the_path(
        later_tree, what, call):
    with pytest.raises(FileNotFoundError) as e:
        call(later_tree)
    assert str(later_tree.bench_dir / what / "no-such.py") in str(e.value)


class _Recorder:
    """A client and a watch that take everything and bind at once."""

    def __init__(self):
        self.made: list[tuple[str, dict]] = []
        self.client = self
        self.bound: dict = {}
        self.bound_at: dict = {}

    async def create(self, resource, obj):
        self.made.append((resource, obj))

    async def wait_bound(self, keys, deadline):
        self.bound.update((k, "node-0") for k in keys)
        return True


@pytest.mark.parametrize("config", ["sched-perf-5k", "kwok-50k"])
def test_no_deployment_named_builds_the_parents_objects(config):
    """A configuration that names no deployment stages byte for byte
    what the generator built before deployments were: `nodes` x
    `node_template` named node-<i>, `pod_template` for every pod, keys
    from its namespace (uids are random: left out)."""
    from kubernetes_tpu.api.types import make_node, make_pod
    manifest = Manifest()
    cfg = dict(manifest.config({"config": config}), nodes=700, init_pods=600)
    assert "deployment" not in cfg
    rec = _Recorder()
    gen = Generator(rec, manifest.deployment(cfg), {"kind": "closed_waves"},
                    7)
    asyncio.run(gen.stage())

    def same(a, b):
        a, b = copy.deepcopy(a), copy.deepcopy(b)
        a["metadata"].pop("uid"), b["metadata"].pop("uid")
        return a == b and json.dumps(a) == json.dumps(b)
    nodes = [o for r, o in rec.made if r == "nodes"]
    pods = [o for r, o in rec.made if r == "pods"]
    assert len(nodes) == 700 and len(pods) == 600
    assert all(same(o, make_node(f"node-{i}", **copy.deepcopy(
        cfg["node_template"]))) for i, o in enumerate(nodes))
    assert all(same(o, make_pod(f"init-{i}", **copy.deepcopy(
        cfg["pod_template"]))) for i, o in enumerate(pods))
    namespace = cfg["pod_template"].get("namespace", "default")
    assert gen.all_created == [f"{namespace}/init-{i}" for i in range(600)]
    assert gen.all_specs == [cfg["pod_template"]] * 600
    assert gen.settled == [600]


# -- a cell runs on exactly its chips ----------------------------------------

def test_a_one_chip_cell_builds_no_mesh_whatever_the_machine_holds(
        waves_timed):
    """The virtual CPU has eight devices; left to itself the backend
    would shard over all of them and the line would still say one."""
    import jax
    assert len(jax.devices()) >= 4
    _, result, _ = waves_timed
    assert result["device"]["count"] == 1


class _Chips(Cluster):
    seen: list = []

    def build_scheduler(self):
        sched = super().build_scheduler()
        self.seen.append((self.chips, self.backend.mesh))
        return sched


class _AutoMesh(Cluster):
    """What the harness did before: the backend takes every device."""

    def build_scheduler(self):
        from kubernetes_tpu.ops import TPUBackend
        sched = super().build_scheduler()
        self.backend = sched.backend = TPUBackend(max_batch=None)
        return sched


@pytest.mark.parametrize("cell,chips", [
    ("rehearsal.toy-waves", 1), ("rehearsal4.toy-waves", 4)])
def test_the_backend_spans_exactly_the_cells_chips(later_tree, cell, chips):
    _Chips.seen.clear()
    rc, result, err = _run(later_tree, cell, False, seconds=0.5,
                           cluster_factory=_Chips)
    assert rc == 0 and result["correct"] is True, err[-2000:]
    assert result["device"]["count"] == chips
    (asked, mesh), = _Chips.seen
    assert asked == chips
    assert mesh is None if chips == 1 else mesh.devices.size == 4
    # a backend on any other number of chips: the run is refused
    with pytest.raises(Refused, match=f"runs on {chips} chip"):
        _run(later_tree, cell, False, seconds=0.5, cluster_factory=_AutoMesh)


def test_a_cell_is_refused_on_fewer_devices_than_its_chips(later_tree,
                                                            monkeypatch):
    import jax
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    with pytest.raises(Refused, match="needs 4 TPU chip"):
        _run(later_tree, "rehearsal4.toy-waves", False)
