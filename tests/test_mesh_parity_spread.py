"""Mesh parity, the spread scans (serial, wave, shortlist) on every mesh
against one device (tests/mesh_parity.py says how), and beside them what
else holds only across chips: no overcommit under contention, the
50,000-node width on the (slice × nodes) mesh, and the resident pack's
row scatter on a sharded pack."""

import numpy as np
import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.ops.backend import TPUBackend
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.types import PodInfo
from kubernetes_tpu.serving.resident import resident_row_scatter
from mesh_parity import (
    GREEDY,
    MESHES,
    MESHES_1D,
    RAGGED_MESHES,
    Case,
    assert_within_allocatable,
    check_parity,
    mesh_of,
    ragged,
    ran,
    spread_pods,
    uniform_cluster,
)
from test_tpu_backend import default_fwk


def spread_serial():
    return Case(
        uniform_cluster(96, zones=3), spread_pods(40, 41),
        env={**GREEDY, "KTPU_WAVEFRONT": "0", "KTPU_SHORTLIST_K": "0"},
        expect=ran(use_spread=True, shortlist_k=0, wave_w=0))


def spread_wave():
    return Case(
        uniform_cluster(96, zones=3), spread_pods(40, 43, max_skew=2),
        env={**GREEDY, "KTPU_WAVE_WIDTH": "4", "KTPU_SHORTLIST_K": "0"},
        expect=ran(use_spread=True, shortlist_k=0, wave_w=4))


def spread_shortlist():
    """spread ∩ shortlist keeps its W = 1 scan: the dispatch pins the
    wave width to 0 whatever the tuner said."""
    return Case(
        uniform_cluster(120, zones=4), spread_pods(40, 47),
        env={**GREEDY, "KTPU_SHORTLIST_K": "16"},
        expect=ran(use_spread=True, shortlist_k=16, wave_w=0))


ROUTES = {f.__name__: f for f in (
    spread_serial, spread_wave, spread_shortlist)}


@pytest.mark.parametrize("mesh", list(MESHES))
@pytest.mark.parametrize("route", list(ROUTES))
def test_mesh_matches_one_device(route, mesh, monkeypatch):
    check_parity(route, ROUTES[route](), mesh, monkeypatch)


@pytest.mark.parametrize("mesh", list(RAGGED_MESHES))
@pytest.mark.parametrize("route", list(ROUTES))
def test_ragged_chunk_matches_one_device(route, mesh, monkeypatch):
    check_parity("ragged:" + route, ragged(ROUTES[route]()), mesh,
                 monkeypatch)


@pytest.mark.parametrize("mesh", MESHES_1D)
def test_contention_never_overcommits(mesh):
    """256 pods of 3 cores onto 200 nodes with 4 cores each, the nodes
    spread over every shard: one pod a node, the rest unassigned."""
    snap = uniform_cluster(200, cpu="4")
    pods = [PodInfo(make_pod(f"big-{i}", requests={"cpu": "3"},
                             uid=f"u{i}")) for i in range(256)]
    got, _ = TPUBackend(max_batch=64, mesh=mesh_of(mesh)).assign(
        pods, snap, default_fwk())
    placed = [n for n in got.values() if n]
    assert len(placed) == 200 and len(set(placed)) == 200
    assert_within_allocatable(snap, pods, got)


def test_50k_node_width_on_multislice_mesh(monkeypatch):
    """The 50,000-node problem width (BASELINE config #5) through the
    backend on the (2 × 4) mesh, flagless: across LARGE_N it is the
    route `kwok-50k.drain4` runs — greedy wave scan, shortlist, block
    index at the tuner's own widths — and it equals one device."""
    cache = SchedulerCache()
    for i in range(51_200):
        cache.add_node(make_node(
            f"n{i}", allocatable={"cpu": str(4 + i % 5), "memory": "32Gi",
                                  "pods": "110"}))
    pods = [PodInfo(make_pod(
        f"p{i}", requests={"cpu": "500m" if i % 2 else "2",
                           "memory": "1Gi"}, uid=f"u{i}"))
        for i in range(32)]

    def all_placed(case, got, metrics):
        assert all(got.values())  # plenty of room at this width

    check_parity("width_50k", Case(
        cache.update_snapshot(), pods, chunk=32,
        expect=ran(solve_mode="greedy", shortlist_k=32,
                   wave_w=32, block_w=128),
        check=all_placed), "slice2x4", monkeypatch)


@pytest.mark.parametrize("mesh", MESHES_1D)
def test_resident_row_scatter_on_sharded_pack(mesh):
    """The one jitted body the batch path runs outside the fused
    program: rows replicated, the (N, 2R+1) pack sharded over the node
    axis — equal to a numpy scatter, and still sharded afterwards."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh_of(mesh), PartitionSpec("nodes", None))
    rng = np.random.default_rng(53)
    pack = rng.integers(0, 1 << 20, size=(256, 5)).astype(np.int32)
    rows = np.array([0, 31, 32, 127, 128, 200, 255, 0], np.int32)
    vals = rng.integers(0, 1 << 20, size=(8, 5)).astype(np.int32)
    vals[7] = vals[0]  # the bucket pad repeats the first row
    out = resident_row_scatter(sharding)(
        jax.device_put(pack, sharding), rows, vals)
    want = pack.copy()
    want[rows] = vals
    np.testing.assert_array_equal(np.asarray(out), want)
    assert out.sharding.is_equivalent_to(sharding, 2)
