"""Mesh parity, what is not a plain greedy scan over class planes: the
Sinkhorn `optimal` mode, per-pod planes (class overflow), pods pinned to
one node, and a gang that must be dropped whole — each on every mesh
against one device (tests/mesh_parity.py says how)."""

import pytest

from kubernetes_tpu.api.types import make_pod
from kubernetes_tpu.scheduler.types import PodInfo
from mesh_parity import (
    GREEDY,
    MESHES,
    RAGGED_MESHES,
    Case,
    check_parity,
    gang_fwk,
    gang_pods,
    hetero,
    ragged,
    ran,
    template_pods,
    uniform_cluster,
)


def optimal():
    snap, pods = hetero(17)

    def solved(case, got, metrics):
        assert metrics.solver_optimal_solves.value() == 3

    return Case(
        snap, pods, env={"KTPU_SOLVE_MODE": "optimal"},
        expect=ran(solve_mode="optimal", shortlist_k=0,
                   wave_w=0, class_mode=True),
        check=solved)


def per_pod_planes():
    """Every pod its own request shape against a class cap of 4: each
    chunk overflows to per-pod planes (C == P, identity index)."""
    snap, pods = hetero(19)

    def fell_back(case, got, metrics):
        assert metrics.class_split_fallbacks.value() == len(case.pods)

    return Case(
        snap, pods,
        env={**GREEDY, "KTPU_CLASS_PAD": "4"},
        expect=ran(class_mode=False, shortlist_k=0), check=fell_back)


#: pinned nodes in the first, a middle and the last shard of every mesh
#: (250 nodes pad to 256 columns: 32 a shard at 8, 64 at 4).
_PINS = ("n3", "n40", "n100", "n130", "n200", "n249")


def _pinned_case(seed: int, env: dict, **want):
    """The exception column is a GLOBAL node coordinate: a pod pinned to
    a node of any shard lands there, as on one device."""
    snap = uniform_cluster(250)
    pods = template_pods(36, seed)
    for j, node in enumerate(_PINS):
        pods.insert(5 * j + 2, PodInfo(make_pod(
            f"pin-{j}", requests={"cpu": "500m", "memory": "512Mi"},
            node_name=node, uid=f"pin-{j}")))

    def landed(case, got, metrics):
        for j, node in enumerate(_PINS):
            assert got[f"default/pin-{j}"] == node
        assert metrics.plane_classes.value() <= 2  # pins split no class

    return Case(snap, pods, env={**GREEDY, **env},
                expect=ran(class_mode=True, **want), check=landed)


def pinned():
    return _pinned_case(23, {"KTPU_SHORTLIST_K": "0",
                             "KTPU_WAVE_WIDTH": "4"},
                        shortlist_k=0, wave_w=4)


def pinned_shortlist():
    """Under the shortlist a pinned column that misses its class's
    candidates resolves through the exactness fallback."""
    return _pinned_case(29, {"KTPU_SHORTLIST_K": "16",
                             "KTPU_WAVEFRONT": "0"},
                        shortlist_k=16, wave_w=0)


def gang_dropped_whole():
    """Gang `fits` (4 × 1 cpu, minMember 4) binds every member; gang
    `bent` needs 5 and two of its members fit nowhere (16 cpu on 8-cpu
    nodes): the three that found a node are dropped with them."""
    snap = uniform_cluster(200)
    pods = template_pods(6, 31) + gang_pods("fits", ["1"] * 4) \
        + template_pods(2, 37) + gang_pods("bent", ["1", "1", "1", "16", "16"])

    def all_or_nothing(case, got, metrics):
        assert all(got[p.key] for p in case.pods
                   if p.name.startswith("fits-"))
        assert not any(got[p.key] for p in case.pods
                       if p.name.startswith("bent-"))

    return Case(snap, pods, fwk=gang_fwk({"fits": 4, "bent": 5}),
                expect=ran(gang=True), check=all_or_nothing)


ROUTES = {f.__name__: f for f in (
    optimal, per_pod_planes, pinned, pinned_shortlist, gang_dropped_whole)}


@pytest.mark.parametrize("mesh", list(MESHES))
@pytest.mark.parametrize("route", list(ROUTES))
def test_mesh_matches_one_device(route, mesh, monkeypatch):
    check_parity(route, ROUTES[route](), mesh, monkeypatch)


@pytest.mark.parametrize("mesh", list(RAGGED_MESHES))
@pytest.mark.parametrize("route", list(ROUTES))
def test_ragged_chunk_matches_one_device(route, mesh, monkeypatch):
    check_parity("ragged:" + route, ragged(ROUTES[route]()), mesh,
                 monkeypatch)
