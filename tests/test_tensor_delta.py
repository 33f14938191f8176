"""ClusterTensors' delta build stays O(changed): the topology planes ride
the snapshot's epoch handles (set_epoch / spec_seq) and the mesh flag,
and no walk of the node list proves them unchanged. A full build keys
taints and planes on one per-node fingerprint. The backend counts which
path each build took and times it, with tracing off.
"""

import re

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops import TPUBackend
from kubernetes_tpu.ops.tensorize import ClusterTensors
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.types import PodInfo
from kubernetes_tpu.topology.mesh import MESH_COORD_LABEL
from kubernetes_tpu.topology import planes


class _NoWalk(list):
    """A snapshot's node list that refuses to be iterated: indexing and
    len() work, a walk over every node raises."""

    def __iter__(self):
        raise AssertionError("the delta build walked the node list")


def _cache(n=8, labels=None):
    cache = SchedulerCache()
    for i in range(n):
        cache.add_node(make_node(
            f"node-{i}", labels=(labels or {}).get(i),
            allocatable={"cpu": "8", "memory": "32Gi", "pods": "110"}))
    return cache


def _assume(cache, name, node):
    cache.assume_pod(PodInfo(make_pod(
        name, uid=name, requests={"cpu": "500m", "memory": "1Gi"})), node)


def _unwalkable(snapshot):
    snapshot.nodes = _NoWalk(snapshot.nodes)
    return snapshot


class TestDeltaKeepsThePlanes:
    def test_kept_by_identity_without_a_walk(self):
        cache = _cache()
        first = ClusterTensors(cache.update_snapshot())
        assert first.build_kind == "full" and first.topology.rebuilt
        _assume(cache, "p0", "node-3")
        snap = _unwalkable(cache.update_snapshot())
        ct = ClusterTensors(snap, prev=first)
        assert ct.build_kind == "delta"
        assert ct.topology is first.topology
        assert not ct.topology.rebuilt
        # the changed row moved, the others are prev's
        assert ct.used_pods[3] == 1 and ct.used_pods.sum() == 1
        assert ct.node_gens[3] == snap.nodes[3].generation

    def test_a_chain_of_deltas_keeps_one_set_of_planes(self):
        cache = _cache()
        ct = ClusterTensors(cache.update_snapshot())
        topo = ct.topology
        for k in range(5):
            _assume(cache, f"p{k}", f"node-{k}")
            ct = ClusterTensors(_unwalkable(cache.update_snapshot()),
                                prev=ct)
            assert ct.build_kind == "delta" and ct.topology is topo
        assert ct.used_pods[:5].tolist() == [1] * 5

    def test_label_move_rebuilds_with_the_new_coordinate(self, monkeypatch):
        monkeypatch.setenv("KTPU_MESH_SHAPE", "2x2")
        coords = {0: "0,0", 1: "0,1", 2: "1,0"}
        cache = _cache(3, labels={i: {MESH_COORD_LABEL: c}
                                  for i, c in coords.items()})
        first = ClusterTensors(cache.update_snapshot())
        assert first.topology.coords[1].tolist() == [0, 1, 0]
        assert first.topology.node_of_cell[3] == -1  # (1,1) is a hole
        cache.update_node(make_node(
            "node-1", labels={MESH_COORD_LABEL: "1,1"},
            allocatable={"cpu": "8", "memory": "32Gi", "pods": "110"}))
        ct = ClusterTensors(cache.update_snapshot(), prev=first)
        assert ct.build_kind == "full"  # spec_seq moved
        assert ct.topology is not first.topology and ct.topology.rebuilt
        assert ct.topology.coords[1].tolist() == [1, 1, 0]
        assert ct.topology.node_of_cell[3] == 1
        assert ct.topology.node_of_cell[1] == -1

    def test_live_mesh_flag_change_between_deltas_rebuilds(
            self, monkeypatch):
        cache = _cache()
        first = ClusterTensors(cache.update_snapshot())
        assert first.topology.spec.dims[:2] != (2, 4)
        _assume(cache, "p0", "node-0")
        monkeypatch.setenv("KTPU_MESH_SHAPE", "2x4")
        ct = ClusterTensors(cache.update_snapshot(), prev=first)
        assert ct.build_kind == "delta"
        assert ct.topology is not first.topology and ct.topology.rebuilt
        assert ct.topology.spec.dims == (2, 4, 1)
        assert ct.topology.on_mesh == 8
        # and the next delta under the same flag keeps the new planes
        _assume(cache, "p1", "node-1")
        again = ClusterTensors(_unwalkable(cache.update_snapshot()), prev=ct)
        assert again.build_kind == "delta"
        assert again.topology is ct.topology and not again.topology.rebuilt

    def test_switch_turned_on_live_builds_the_planes(self, monkeypatch):
        monkeypatch.setenv("KTPU_TOPOLOGY", "0")
        cache = _cache()
        first = ClusterTensors(cache.update_snapshot())
        assert first.topology is None
        monkeypatch.setenv("KTPU_TOPOLOGY", "1")
        _assume(cache, "p0", "node-0")
        ct = ClusterTensors(cache.update_snapshot(), prev=first)
        assert ct.build_kind == "delta"
        assert ct.topology is not None and ct.topology.rebuilt
        assert ct.topology.on_mesh == 8

    def test_kill_switch_leaves_no_planes_on_either_path(self, monkeypatch):
        monkeypatch.setenv("KTPU_TOPOLOGY", "0")
        cache = _cache()
        first = ClusterTensors(cache.update_snapshot())
        _assume(cache, "p0", "node-2")
        ct = ClusterTensors(_unwalkable(cache.update_snapshot()),
                            prev=first)
        assert first.build_kind == "full" and first.topology is None
        assert ct.build_kind == "delta" and ct.topology is None

    def test_delta_planes_equal_a_full_builds(self):
        """The kept planes are the ones a fresh full build derives."""
        cache = _cache(12)
        ct = ClusterTensors(cache.update_snapshot())
        for k in range(3):
            _assume(cache, f"p{k}", f"node-{2 * k}")
            ct = ClusterTensors(cache.update_snapshot(), prev=ct)
        fresh = ClusterTensors(cache.update_snapshot())
        assert ct.build_kind == "delta" and fresh.build_kind == "full"
        for field in ("cell_of_node", "node_of_cell", "coords"):
            assert (getattr(ct.topology, field)
                    == getattr(fresh.topology, field)).all(), field
        assert (ct.used_q == fresh.used_q).all()
        assert (ct.used_pods == fresh.used_pods).all()


class TestFullPathFingerprint:
    def test_built_once_and_shared_with_the_planes(self, monkeypatch):
        made = []
        real = planes.build_topology_planes

        def spy(nodes, n_pad, prev, fingerprint):
            made.append(fingerprint)
            return real(nodes, n_pad, prev, fingerprint)

        monkeypatch.setattr(
            "kubernetes_tpu.ops.tensorize.build_topology_planes", spy)
        ct = ClusterTensors(_cache().update_snapshot())
        assert made == [ct._static_fp]
        assert made[0] is ct._static_fp
        assert ct.topology.fingerprint[2] is ct._static_fp

    def test_full_rebuild_on_an_unchanged_node_set_keeps_the_planes(self):
        """A full build (no epoch handles) still reuses equal planes by
        the per-node fingerprint, as before."""
        cache = _cache()
        snap = cache.update_snapshot()
        first = ClusterTensors(snap)
        snap.set_epoch = -1  # unknown handles: the full walk
        again = ClusterTensors(snap, prev=first)
        assert again.build_kind == "full"
        assert again.topology is first.topology
        assert not again.topology.rebuilt


class TestBackendCountsBuilds:
    @pytest.fixture
    def backend(self):
        backend = TPUBackend(max_batch=8)
        backend.metrics = SchedulerMetrics()
        return backend

    def test_kinds_counted_and_wall_observed_with_tracing_off(
            self, backend):
        m = backend.metrics
        assert m.cluster_tensor_builds.value(kind="delta") == 0
        assert m.cluster_tensor_builds.value(kind="full") == 0
        cache = _cache()
        backend._tensors(cache.update_snapshot())
        _assume(cache, "p0", "node-1")
        snap = cache.update_snapshot()
        backend._tensors(snap)
        backend._tensors(snap)  # same generation: no build
        _assume(cache, "p1", "node-2")
        backend._tensors(cache.update_snapshot())
        assert m.cluster_tensor_builds.value(kind="full") == 1
        assert m.cluster_tensor_builds.value(kind="delta") == 2
        assert m.tensors_duration.count() == 3
        assert m.tensors_duration.sum() > 0.0
        assert m.topology_plane_rebuilds.value() == 1

    def test_series_in_the_exposition(self, backend):
        backend._tensors(_cache().update_snapshot())
        text = backend.metrics.registry.render()
        assert 'scheduler_tpu_cluster_tensor_builds_total{kind="delta"} 0' \
            in text
        assert 'scheduler_tpu_cluster_tensor_builds_total{kind="full"} 1' \
            in text
        assert "scheduler_tpu_tensors_seconds_sum" in text
        assert re.search(r"^scheduler_tpu_tensors_seconds_count(\{\})? 1$",
                         text, re.M)


@pytest.mark.parametrize("name", [
    "tensors_ms_per_kpod.trickle", "tensors_ms_per_kpod.drain",
    "tensor_delta_builds_pct.trickle"])
def test_the_benchmark_reads_these_series(name):
    """Each per-layer metric over the build counters resolves to the
    ratio reader and names series the scheduler's registry exposes."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmark.lib import counters
    from benchmark.lib.manifest import Manifest

    manifest = Manifest()
    entry = next(m for m in manifest.doc["per_layer"] if m["name"] == name)
    assert entry["layer"] == "attempt"
    spec = manifest.metric_file(name)
    assert spec["reader"] == "counter_ratio"
    metrics = SchedulerMetrics()
    metrics.tensors_duration.observe(0.0)  # a histogram shows once observed
    snap = counters.snapshot(metrics.registry)
    for side in ("numerator", "denominator"):
        arg = spec["args"][side]
        if isinstance(arg, dict):
            assert counters.total(snap, arg["name"], arg.get("match")) \
                is not None, (name, side)
