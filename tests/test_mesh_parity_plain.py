"""Mesh parity, the plain scans: the serial scan, the wave scan, the
shortlist-pruned scans and the block-index prefilter, each on every mesh
against one device (tests/mesh_parity.py says how)."""

import random

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.types import PodInfo
from mesh_parity import (
    GREEDY,
    MESHES,
    RAGGED_MESHES,
    Case,
    check_parity,
    hetero,
    ragged,
    ran,
    template_pods,
)
from test_tpu_backend import TOL_POOL, random_cluster


def serial():
    snap, pods = hetero(3)
    return Case(
        snap, pods,
        env={**GREEDY, "KTPU_WAVEFRONT": "0", "KTPU_SHORTLIST_K": "0"},
        expect=ran(use_spread=False, shortlist_k=0,
                   wave_w=0, block_w=0, solve_mode="greedy",
                   class_mode=True))


def wave():
    snap, pods = hetero(5)
    return Case(
        snap, pods,
        env={**GREEDY, "KTPU_WAVE_WIDTH": "4", "KTPU_SHORTLIST_K": "0"},
        expect=ran(use_spread=False, shortlist_k=0,
                   wave_w=4, solve_mode="greedy"))


def shortlist():
    """Two templates, one tolerating the fleet's taints: on this fleet
    the bound check sends a chunk's pods to the full row (fallbacks > 0)."""
    snap = random_cluster(random.Random(9), 160)
    pods = [PodInfo(make_pod(
        f"pend-{i}", uid=f"uid-{i}",
        requests={"cpu": "500m", "memory": "512Mi"} if i % 2
        else {"cpu": "1", "memory": "2Gi"},
        tolerations=TOL_POOL if i % 2 else None)) for i in range(48)]

    def fell_back(case, got, metrics):
        assert metrics.solver_shortlist_fallbacks.value() > 0

    return Case(
        snap, pods,
        env={**GREEDY, "KTPU_WAVEFRONT": "0", "KTPU_SHORTLIST_K": "16"},
        expect=ran(use_spread=False, shortlist_k=16,
                   wave_w=0, block_w=0, class_mode=True),
        check=fell_back)


def shortlist_wave():
    snap, pods = hetero(11, n_nodes=120)
    return Case(
        snap, pods,
        env={**GREEDY, "KTPU_WAVE_WIDTH": "4", "KTPU_SHORTLIST_K": "16"},
        expect=ran(use_spread=False, shortlist_k=16, wave_w=4, block_w=0))


def shortlist_block():
    """The block index forced on as tests/test_block_index_solver.py
    does (LARGE_N patched down, width 16: B = 16 blocks of the 256
    padded columns, M + 1 = 5 ≤ B) on a fleet whose head is empty and
    whose tail carries residents, so whole blocks are dominated and
    pruned — two blocks a shard at 8 shards."""
    cache = SchedulerCache()
    for i in range(240):
        cache.add_node(make_node(
            f"n{i}", allocatable={"cpu": "8", "memory": "32Gi",
                                  "pods": "110"}))
        if i >= 48:
            cache.add_pod(PodInfo(make_pod(
                f"res-{i}", node_name=f"n{i}",
                requests={"cpu": f"{1000 + 13 * (i % 40)}m",
                          "memory": "4Gi"})))

    def pruned(case, got, metrics):
        assert metrics.solver_blocks_pruned.value() > 0

    return Case(
        cache.update_snapshot(), template_pods(48, 13), large_n=1,
        env={**GREEDY, "KTPU_WAVEFRONT": "0", "KTPU_SHORTLIST_K": "16",
             "KTPU_BLOCK_WIDTH": "16"},
        expect=ran(use_spread=False, shortlist_k=16, block_w=16),
        check=pruned)


ROUTES = {f.__name__: f for f in (
    serial, wave, shortlist, shortlist_wave, shortlist_block)}


@pytest.mark.parametrize("mesh", list(MESHES))
@pytest.mark.parametrize("route", list(ROUTES))
def test_mesh_matches_one_device(route, mesh, monkeypatch):
    check_parity(route, ROUTES[route](), mesh, monkeypatch)


@pytest.mark.parametrize("mesh", list(RAGGED_MESHES))
@pytest.mark.parametrize("route", list(ROUTES))
def test_ragged_chunk_matches_one_device(route, mesh, monkeypatch):
    check_parity("ragged:" + route, ragged(ROUTES[route]()), mesh,
                 monkeypatch)
