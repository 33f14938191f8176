"""Shared by tests/test_mesh_parity_{plain,modes,spread}.py.

One question — is the sharded solve bit-identical to one device? — asked
of the program the four-chip cell runs: `TPUBackend(mesh=M).assign(...)`
(node axis under `NamedSharding`, the one fused `_mask_solve_update`
partitioned by XLA) against `TPUBackend(mesh=None).assign(...)` on the
same snapshot and pods. Each ROUTE is a (workload, overrides) pair that
forces one branch of the fused program onto small CPU shapes with the
overrides the single-device suites already use; the statics every chunk
actually ran with are recorded at the dispatch seam, so a route that
silently degraded to another fails here instead of passing vacuously.
"""

import dataclasses
import random
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Callable

import jax
import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops import backend as backend_mod
from kubernetes_tpu.ops.backend import TPUBackend
from kubernetes_tpu.parallel import build_mesh, build_multislice_mesh
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.framework import Framework
from kubernetes_tpu.scheduler.plugins.coscheduling import (
    POD_GROUP_LABEL,
    Coscheduling,
    make_pod_group,
)
from kubernetes_tpu.scheduler.plugins.registry import (
    DEFAULT_PLUGINS,
    DEFAULT_SCORE_WEIGHTS,
    build_plugins,
)
from kubernetes_tpu.scheduler.types import PodInfo
from test_tpu_backend import default_fwk, random_cluster, random_pending

#: name -> (devices needed, builder). Built inside the test: a suite
#: pointed at a machine with fewer devices skips instead of failing.
MESHES = {
    "mesh1": (1, lambda: build_mesh(1)),
    "mesh4": (4, lambda: build_mesh(4)),
    "mesh8": (8, lambda: build_mesh(8)),
    "slice2x4": (8, lambda: build_multislice_mesh(2, 4)),
}
MESHES_1D = ("mesh1", "mesh4", "mesh8")
#: the meshes of the ragged rows (`ragged` below): 1, 2 and 4 devices.
RAGGED_MESHES = {"mesh1": MESHES["mesh1"],
                 "mesh2": (2, lambda: build_mesh(2)),
                 "mesh4": MESHES["mesh4"]}

#: the fused program's five tail counters, as the backend accounts them.
TAIL_COUNTERS = ("solver_shortlist_fallbacks", "solver_wave_commits",
                 "solver_wave_replays", "solver_blocks_scanned",
                 "solver_blocks_pruned")


def mesh_of(name: str):
    need, build = {**MESHES, **RAGGED_MESHES}[name]
    if len(jax.devices()) < need:
        pytest.skip(f"{name} needs {need} devices")
    return build()


# -- workloads --------------------------------------------------------------

def uniform_cluster(n_nodes: int, zones: int = 0, cpu: str = "8"):
    cache = SchedulerCache()
    for i in range(n_nodes):
        labels = {"zone": f"z{i % zones}"} if zones else None
        cache.add_node(make_node(
            f"n{i}", labels=labels,
            allocatable={"cpu": cpu, "memory": "32Gi", "pods": "110"}))
    return cache.update_snapshot()


def template_pods(n: int, seed: int):
    """Two request templates, interleaved by the seed."""
    rng = random.Random(seed)
    shapes = ({"cpu": "500m", "memory": "512Mi"},
              {"cpu": "1", "memory": "2Gi"})
    return [PodInfo(make_pod(f"pend-{i}", requests=rng.choice(shapes),
                             uid=f"uid-{i}")) for i in range(n)]


def hetero(seed: int, n_nodes: int = 96, n_pods: int = 40):
    rng = random.Random(seed)
    return random_cluster(rng, n_nodes), random_pending(rng, n_pods)


def spread_pods(n: int, seed: int, max_skew: int = 1):
    """Zone-spread pods (DoNotSchedule) with plain pods the selector
    also counts interleaved, so gated and contribute-only chunks both
    run."""
    rng = random.Random(seed)
    cons = [{"maxSkew": max_skew, "topologyKey": "zone",
             "whenUnsatisfiable": "DoNotSchedule",
             "labelSelector": {"matchLabels": {"app": "spread"}}}]
    pods = []
    for i in range(n):
        kw = dict(requests={"cpu": "250m", "memory": "256Mi"},
                  labels={"app": "spread"}, uid=f"uid-{i}")
        if rng.random() < 0.75:
            kw["topology_spread_constraints"] = cons
        pods.append(PodInfo(make_pod(f"sp-{i}", **kw)))
    return pods


def gang_fwk(groups: dict[str, int]):
    """The default framework plus Coscheduling over a fixed set of
    PodGroups {name: minMember} (a stub indexer: no store, no loop)."""
    plugins = build_plugins(DEFAULT_PLUGINS + ["Coscheduling"])
    cosched = next(p for p in plugins if isinstance(p, Coscheduling))
    cosched.pg_informer = SimpleNamespace(indexer={
        f"default/{name}": make_pod_group(name, min_member=mm)
        for name, mm in groups.items()})
    return Framework(plugins, DEFAULT_SCORE_WEIGHTS)


def gang_pods(group: str, cpus: list[str]):
    """One member of `group` per entry of `cpus`."""
    return [PodInfo(make_pod(
        f"{group}-{i}", labels={POD_GROUP_LABEL: group},
        requests={"cpu": cpu}, uid=f"{group}-{i}"))
        for i, cpu in enumerate(cpus)]


# -- the comparison ---------------------------------------------------------

GREEDY = {"KTPU_SOLVE_MODE": "greedy"}


def ran(**want) -> Callable[[list], bool]:
    """An `expect`: every dispatched chunk ran with these statics."""
    return lambda statics: all(
        s[k] == v for s in statics for k, v in want.items())


@dataclass
class Case:
    """One route's workload, overrides and what its chunks must have run.

    `expect(statics)` sees one dict per dispatched chunk — `use_spread`,
    `shortlist_k`, `wave_w`, `solve_mode`, `block_w`, `class_mode`,
    `gang` — read at the dispatch seam, and says whether the route was
    reached; `check(case, got, metrics)` adds what else the route must
    show (a counter that moved, a pin that landed)."""

    snap: object
    pods: list
    expect: Callable[[list], bool]
    env: dict = field(default_factory=dict)
    chunk: int = 16
    fwk: Framework = field(default_factory=default_fwk)
    large_n: int | None = None
    check: Callable | None = None


def ragged(case: Case) -> Case:
    """`case` cut to one full chunk and six pods of the next (fewer
    where the case has fewer): the second chunk is mostly padding, which
    the scans skip since PR 31 — the trip count is a replicated scalar
    on a mesh. The route's own `check` counts what all of its pods did
    and stays with the uncut row."""
    return dataclasses.replace(
        case, pods=case.pods[:case.chunk + 6], check=None)


def run_case(case: Case, mesh, monkeypatch, seen=None):
    """(placements, the backend's metrics, the statics of each chunk);
    `seen(backend, out, ctx)`, if given, looks at each dispatched chunk."""
    statics = []
    inner = TPUBackend._dispatch_chunk_jit

    def recording(self, prep, ctx):
        out = inner(self, prep, ctx)
        if seen is not None:
            seen(self, out, ctx)
        statics.append({
            "use_spread": out["spread_used"],
            "shortlist_k": out["shortlist_k"], "wave_w": out["wave_w"],
            "solve_mode": out["solve_mode"], "block_w": out["block_w"],
            "class_mode": out["class_mode"],
            "gang": out["gang_onehot"] is not None})
        return out

    with monkeypatch.context() as mp:
        for k in ("KTPU_WAVEFRONT", "KTPU_WAVE_WIDTH", "KTPU_SHORTLIST_K",
                  "KTPU_BLOCK_WIDTH", "KTPU_SOLVE_MODE", "KTPU_CLASS_PAD"):
            mp.delenv(k, raising=False)
        for k, v in case.env.items():
            mp.setenv(k, v)
        if case.large_n is not None:
            mp.setattr(backend_mod.AdaptiveTuner, "LARGE_N", case.large_n)
        mp.setattr(TPUBackend, "_dispatch_chunk_jit", recording)
        b = TPUBackend(max_batch=case.chunk, mesh=mesh)
        b.metrics = SchedulerMetrics()
        got, _ = b.assign(case.pods, case.snap, case.fwk)
    return got, b.metrics, statics


def assert_within_allocatable(snap, pods, got) -> None:
    by_key = {p.key: p for p in pods}
    extra: dict[str, list] = {}
    for key, node in got.items():
        if node:
            extra.setdefault(node, []).append(by_key[key])
    for node, placed in extra.items():
        ni = snap.get(node)
        assert ni is not None, node
        assert ni.requested.pods + len(placed) <= ni.allocatable.pods, node
        for res in ("cpu", "memory"):
            want = ni.requested.get(res) + sum(
                p.requests.get(res, 0) for p in placed)
            assert want <= ni.allocatable.get(res), (node, res)


_BASELINES: dict = {}


def check_parity(case_name: str, case: Case, mesh_name: str,
                 monkeypatch) -> None:
    """`case` on `mesh_name` against the same case on one device."""
    if case_name not in _BASELINES:
        _BASELINES[case_name] = run_case(case, None, monkeypatch)
    base, base_m, base_statics = _BASELINES[case_name]
    got, m, statics = run_case(case, mesh_of(mesh_name), monkeypatch)

    assert base_statics and case.expect(base_statics), base_statics
    assert statics == base_statics
    assert got == base
    for name in TAIL_COUNTERS:
        assert getattr(m, name).value() == getattr(base_m, name).value(), \
            name
    assert_within_allocatable(case.snap, case.pods, got)
    assert m.backend_degradations.value(kind="host_fallback") == 0
    assert any(got.values()), "nothing was placed: the case compares nothing"
    if case.check is not None:
        case.check(case, got, m)
