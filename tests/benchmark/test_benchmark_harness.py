"""The harness end to end, on the CPU at a tiny size.

A cell is refused without a TPU. A configuration, a traffic mix and a
per-layer metric added as FILES AND ENTRIES ONLY — to a copy of
BENCHMARK.json and benchmark/, as a later PR's tree would hold them, no
file that is there edited — are found and run by the unchanged code
(device metrics absent, never zero). The control and the planted faults
come out as not correct.
"""

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
from benchmark.lib.harness import run_cell  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402

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
    entries for one configuration, two traffic mixes and one metric."""
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
    doc["configs"].append({
        "name": "rehearsal", "source": REHEARSAL_CONFIG["source"],
        "file": "benchmark/configs/rehearsal.json", "reduced": [],
        "why": "toy"})
    cells = ["rehearsal.toy-waves", "rehearsal.toy-loop"]
    for name in cells:
        doc["workloads"].append({
            "name": name, "config": "rehearsal",
            "traffic": name.split(".")[1], "chips": 1, "why": "toy"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            kind = "drain" if "sched-perf-5k.drain" in m["workloads"] \
                else "trickle"
            m["workloads"].append(cells[0] if kind == "drain" else cells[1])
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
    from benchmark.lib.traffic import Generator
    mix = later_tree.traffic(later_tree.cell("rehearsal.toy-loop"))
    a, b = (Generator(None, REHEARSAL_CONFIG, mix, seed)._gaps(4.0, seed)
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


def test_the_control_comes_out_as_not_correct(later_tree):
    """The reference in the program's place: sound, it is correct; with
    one look at the cluster per 128 pods (a node holds 80), nodes go
    past allocatable."""
    config = dict(REHEARSAL_CONFIG, nodes=40, init_pods=0, wave_pods=300)
    tree = Manifest(later_tree.root, later_tree.bench_dir)
    tree.config = lambda cell: config
    _, sound, err = _run(tree, "rehearsal.toy-waves", False, seconds=0.2,
                         cluster_factory=control_cluster(config, 1))
    assert sound["correct"] is True, err[-2000:]
    assert sound["compared"]["nodes_over_allocatable"]["value"] == 0
    _, broken, _ = _run(tree, "rehearsal.toy-waves", False, seconds=0.2,
                        cluster_factory=control_cluster(config, 128))
    assert broken["correct"] is False
    assert broken["compared"]["nodes_over_allocatable"]["value"] > 0


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
