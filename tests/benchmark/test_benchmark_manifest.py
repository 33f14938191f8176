"""BENCHMARK.json keeps to the contract's shape, and every name in it
resolves to the files the harness looks for: data, and the code a
configuration's deployment and a mix's kind name."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib.manifest import Manifest  # noqa: E402
from benchmark.lib.traffic import Generator  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return Manifest()


@pytest.fixture(scope="module")
def doc(manifest):
    return manifest.doc


def _all_metrics(doc):
    return doc["end_to_end"] + doc["per_layer"]


def test_top_level_keys(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51 and isinstance(doc["run_seconds"], int)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines(doc):
    names = [m["name"] for m in _all_metrics(doc)]
    assert len(names) == len(set(names))
    for entry in doc["configs"] + doc["workloads"] + _all_metrics(doc):
        assert NAME.match(entry["name"]), entry["name"]
    for m in _all_metrics(doc):
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in doc["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for text in ([w["why"] for w in doc["workloads"]]
                 + [c["why"] for c in doc["configs"]]
                 + [c["source"] for c in doc["configs"]]
                 + [m["layer"] for m in doc["per_layer"]]
                 + doc["command"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_entries_have_exactly_the_contracts_keys(doc):
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}


def test_paths_hold_the_benchmark_and_the_command_stays_inside(doc):
    assert 1 <= len(doc["paths"]) <= 16
    for p in doc["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
        for f in (ROOT / p).rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                assert PATH.match(str(f.relative_to(ROOT))), f
    assert len(doc["command"]) <= 32
    assert any(doc["command"][1].startswith(p + "/") for p in doc["paths"])


def test_every_config_has_a_cell_and_a_file_of_its_own(doc, manifest):
    used = {w["config"] for w in doc["workloads"]}
    files = [c["file"] for c in doc["configs"]]
    assert len(files) == len(set(files))
    for c in doc["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in doc["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
        for key in ("init_pods", "guarantees", "assumed"):
            assert key in cfg, (c["name"], key)
        # the rest is what its deployment needs: the default one, which a
        # configuration that names none gets, reads these three
        if "deployment" not in cfg:
            assert {"nodes", "node_template", "pod_template"} <= set(cfg)
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(doc["workloads"]) // 2)


def test_every_cell_resolves_to_its_files(doc, manifest):
    for w in doc["workloads"]:
        model = manifest.deployment(manifest.config(w))
        assert model.n_nodes == len(model.nodes()) > 0
        assert all("allocatable" in kw for _, kw in model.nodes()[:3])
        for phase in ("init", "warm", "burst", "measured"):
            specs = model.pods(phase, ["a", "b"])
            assert len(specs) == 2 and all("requests" in kw for kw in specs)
        mix = manifest.traffic(w)
        assert Generator.defines(mix["kind"]) \
            or callable(manifest.kind(mix["kind"]).window)
        e2e = [m["name"] for m in manifest.end_to_end(w)]
        assert "setup_s" in e2e and len(e2e) >= 2
        # every end-to-end metric of the cell is a quantity of its mix
        assert set(e2e) - {"setup_s"} == set(mix["end_to_end"])
        assert manifest.per_layer(w), w["name"]


def test_per_layer_metrics_move_what_their_cells_report(doc, manifest):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    cells = {w["name"]: w for w in doc["workloads"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in doc["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for cell in m.get("workloads", cells):
            reported = [x["name"] for x in manifest.end_to_end(cells[cell])]
            assert m["moves"] in reported, (m["name"], cell)


def test_every_per_layer_metric_has_a_file_and_a_reader(doc, manifest):
    for m in doc["per_layer"]:
        spec = manifest.metric_file(m["name"])
        assert callable(manifest.reader(spec["reader"]))
        assert isinstance(spec.get("args", {}), dict)
    # a layer is spelled one way
    layers = {m["layer"] for m in doc["per_layer"]}
    assert len({layer.lower() for layer in layers}) == len(layers)


@pytest.mark.parametrize("config,nodes", [
    ("sched-perf-5k", 5000), ("kwok-50k", 50000)])
def test_the_problem_a_reader_asks_for_is_what_the_parent_read(
        manifest, config, nodes):
    """readers/roofline.py took the nodes from the configuration, counted
    the keys of `node_template.allocatable` (the pod count is a plane)
    and one class: the deployment states the same problem."""
    cfg = manifest.config({"config": config})
    assert manifest.deployment(cfg).problem() == {
        "nodes": nodes, "resources": len(cfg["node_template"]["allocatable"]),
        "classes": 1}


def test_rooflines_are_named_and_united_as_shares(doc):
    for m in doc["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
