"""BENCHMARK.json and the files it names. A cell resolves, by the names
in its entry, to `configs/<config>.json`'s file and
`traffic/<traffic>.json`; a per-layer metric to `metrics/<name>.json`,
which names a reader in `readers/`. Code is found the same way: a
configuration's `deployment` in `deployments/<name>.py` (its nodes, its
pods, its plain reference and its control: lib/reference.py says what
one states), and a mix's `kind` that lib/traffic.py does not define in
`kinds/<kind>.py`."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH_DIR):
        self.root = Path(root)
        self.bench_dir = Path(bench_dir)
        self.doc = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                return load_json(self.root / c["file"])
        raise KeyError(f"no config {cell['config']!r} in BENCHMARK.json")

    def traffic(self, cell: dict) -> dict:
        return load_json(self.bench_dir / "traffic" / f"{cell['traffic']}.json")

    def reports(self, metric: dict, cell: dict, end_to_end: list[str]) -> bool:
        """Does this cell report this metric? By its `workloads` key, or
        without one by whether the cell reports what the metric moves."""
        if "workloads" in metric:
            return cell["name"] in metric["workloads"]
        return metric["moves"] in end_to_end

    def end_to_end(self, cell: dict) -> list[dict]:
        return [m for m in self.doc["end_to_end"]
                if "workloads" not in m or cell["name"] in m["workloads"]]

    def per_layer(self, cell: dict) -> list[dict]:
        e2e = [m["name"] for m in self.end_to_end(cell)]
        return [m for m in self.doc["per_layer"]
                if self.reports(m, cell, e2e)]

    def metric_file(self, name: str) -> dict:
        return load_json(self.bench_dir / "metrics" / f"{name}.json")

    def _module(self, directory: str, name: str):
        """`<directory>/<name>.py` of the benchmark, loaded by name."""
        path = self.bench_dir / directory / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(
                f"BENCHMARK.json's files name {name!r}, and there is no "
                f"{path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{directory}_{name}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def reader(self, name: str):
        """The `read(ctx, **args)` function of readers/<name>.py."""
        return self._module("readers", name).read

    def deployment(self, config: dict):
        """What this configuration means, built: the `Deployment` of
        deployments/<config["deployment"]>.py, or the default one
        (`reference.ClusterModel`) where it names none."""
        from benchmark.lib.reference import ClusterModel
        name = config.get("deployment")
        if name is None:
            return ClusterModel(config)
        return self._module("deployments", name).Deployment(config)

    def kind(self, name: str):
        """kinds/<name>.py: `warm(gen)` and `window(gen, seconds,
        on_start)` over lib/traffic.py's Generator."""
        return self._module("kinds", name)
