"""The host per-layer metrics that read the tracer's self-time ledger
(`ktpu_host_self_seconds_total` and its sibling families): every metric
file resolves, and a short traced run of the public classes, on the CPU
at a toy size, gives each of them a finite reading and leaves every
series they name in `Registry.render()`.
"""

import io
import json
import math
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import counters  # noqa: E402
from benchmark.lib.cluster import Cluster  # noqa: E402
from benchmark.lib.harness import run_cell  # noqa: E402
from benchmark.lib.manifest import Manifest  # noqa: E402

LEDGER_FAMILIES = (
    "ktpu_host_self_seconds_total", "ktpu_span_wall_seconds_total",
    "ktpu_span_total", "ktpu_loop_wall_seconds_total",
    "ktpu_loop_busy_seconds_total", "ktpu_trace_spans_dropped_total")

DRAIN = [f"host_{layer}_ms_per_kpod.drain" for layer in (
    "wire", "store", "informer", "attempt", "bind", "events", "gc")] + [
    "loop_idle_pct.drain", "host_unattributed_pct.drain"]
TRICKLE = ["loop_idle_pct.trickle", "host_unattributed_pct.trickle",
           "host_attempt_ms_per_kpod.trickle", "queue_wait_ms_mean.trickle",
           "bind_wall_ms_mean.trickle"]

TOY_CONFIG = {
    "name": "toy-host", "source": "tests/benchmark: a toy cluster",
    "nodes": 60, "init_pods": 20, "wave_pods": 120,
    "node_template": {"allocatable": {
        "cpu": "8", "memory": "32Gi", "pods": "110"}},
    "pod_template": {"requests": {"cpu": "100m", "memory": "250Mi"}},
    "chips": 1, "guarantees": {}, "assumed": [], "reduced": [],
}


def _ledger_metrics(doc) -> list[dict]:
    """The per-layer entries whose files read the ledger's families."""
    manifest = Manifest()
    out = []
    for m in doc["per_layer"]:
        args = manifest.metric_file(m["name"]).get("args", {})
        sides = [args.get("numerator"), args.get("denominator")]
        if any(isinstance(s, dict) and s["name"] in LEDGER_FAMILIES
               for s in sides):
            out.append(m)
    return out


def test_the_fourteen_are_in_the_manifest():
    doc = Manifest().doc
    names = [m["name"] for m in _ledger_metrics(doc)]
    assert sorted(names) == sorted(DRAIN + TRICKLE)
    for m in _ledger_metrics(doc):
        assert m["source"] == "program_span"
        drain = m["name"].endswith(".drain")
        assert m["moves"] == ("pods_bound_per_s" if drain
                              else "sched_latency_p50_ms")
        assert m["workloads"] == (
            ["sched-perf-5k.drain", "kwok-50k.drain"] if drain
            else ["sched-perf-5k.trickle"])
        assert m["better"] == ("higher" if m["name"].startswith(
            "loop_idle_pct") else "lower")


@pytest.mark.parametrize("name", DRAIN + TRICKLE)
def test_each_metric_file_resolves_to_the_ratio_reader(name):
    manifest = Manifest()
    spec = manifest.metric_file(name)
    assert spec["reader"] == "counter_ratio" and spec["what"]
    assert callable(manifest.reader(spec["reader"]))
    for side in ("numerator", "denominator"):
        assert spec["args"][side]["name"] in LEDGER_FAMILIES
    assert spec["args"]["scale"] in (100.0, 1e3, 1e6)


@pytest.fixture(scope="module")
def toy_tree(tmp_path_factory):
    """The benchmark as committed plus a toy configuration and two toy
    mixes that the ledger's metrics list, as files and entries only."""
    root = tmp_path_factory.mktemp("host_metrics")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "benchmark"
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    (bench / "configs" / "toy-host.json").write_text(json.dumps(TOY_CONFIG))
    waves = json.loads((bench / "traffic" / "drain.json").read_text())
    waves.update(trace_seconds=30, barrier_seconds=30)
    (bench / "traffic" / "toy-waves.json").write_text(json.dumps(waves))
    loop = json.loads((bench / "traffic" / "trickle.json").read_text())
    loop.update(rate=150, warm_quiet_seconds=0.5, warm_cap_seconds=3,
                trace_seconds=30, barrier_seconds=30)
    (bench / "traffic" / "toy-loop.json").write_text(json.dumps(loop))
    doc["configs"].append({
        "name": "toy-host", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/toy-host.json", "reduced": [],
        "why": "toy"})
    cells = {"drain": "toy-host.toy-waves", "trickle": "toy-host.toy-loop"}
    for name in cells.values():
        doc["workloads"].append({
            "name": name, "config": "toy-host",
            "traffic": name.split(".")[1], "chips": 1, "why": "toy"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            kind = "drain" if "sched-perf-5k.drain" in m["workloads"] \
                else "trickle"
            m["workloads"].append(cells[kind])
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return Manifest(root, bench)


def _traced(manifest, cell):
    made = []

    def factory():
        made.append(Cluster())
        return made[-1]

    out, err = io.StringIO(), io.StringIO()
    # the whole window is traced: on a loaded test machine the first
    # bindings can be a second late
    rc = run_cell(cell, 2**31 + 4242, 4.0, True, manifest=manifest,
                  require_chip=False, cluster_factory=factory,
                  stdout=out, stderr=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True, err.getvalue()[-2000:]
    return result, counters.snapshot(made[0].metrics.registry)


@pytest.fixture(scope="module")
def waves(toy_tree):
    return _traced(toy_tree, "toy-host.toy-waves")


@pytest.fixture(scope="module")
def loop(toy_tree):
    return _traced(toy_tree, "toy-host.toy-loop")


@pytest.mark.parametrize("name", DRAIN)
def test_a_traced_drain_reads_each_drain_metric(waves, name):
    result, _ = waves
    value = result["metrics"][name]["value"]
    assert math.isfinite(value) and value >= 0.0
    if name.endswith("_pct.drain"):
        assert value <= 100.0


@pytest.mark.parametrize("name", TRICKLE)
def test_a_traced_trickle_reads_each_trickle_metric(loop, name):
    result, _ = loop
    value = result["metrics"][name]["value"]
    assert math.isfinite(value) and value >= 0.0
    if "_pct." in name:
        assert value <= 100.0


@pytest.mark.parametrize("name", DRAIN + TRICKLE)
def test_every_series_a_metric_names_is_in_the_exposition(
        waves, loop, name):
    _, snap = waves if name.endswith(".drain") else loop
    args = Manifest().metric_file(name)["args"]
    for side in ("numerator", "denominator"):
        got = counters.total(snap, args[side]["name"],
                             args[side].get("match"))
        assert got is not None, (name, side, args[side])


def test_the_layers_account_for_the_loop_and_little_is_unnamed(waves):
    """Closure through the exposition: the loop thread's self-times sum
    to the ledger's wall, nothing was dropped, and the program's own
    callbacks are named (what is left is the benchmark's client)."""
    result, snap = waves
    selfs = sum(v for (n, labels), v in snap.items()
                if n == "ktpu_host_self_seconds_total"
                and 'thread="loop"' in labels)
    wall = counters.total(snap, "ktpu_loop_wall_seconds_total")
    assert wall > 1.0
    assert selfs == pytest.approx(wall, rel=0.02)
    assert counters.total(snap, "ktpu_trace_spans_dropped_total") == 0
    assert result["metrics"]["host_unattributed_pct.drain"]["value"] < 60.0
