"""Tier-1 smoke for topology-aware slice placement (kubernetes_tpu/
topology — ISSUE 19).

Pins: (a) the subsystem is ACTIVE BY DEFAULT — KTPU_TOPOLOGY defaults
on and ClusterTensors carries coordinate planes, rebuilt only when the
mesh flags or node set move; (b) the KTPU_TOPOLOGY=0 kill switch
degrades STRUCTURALLY — no topology planes, TopologySlice skips — and
topology-free workloads assign BIT-IDENTICALLY with the flag on or
off (the flat-capacity call graph is untouched); (c) slice-shaped
gangs bind ALL-OR-NOTHING onto one contiguous sub-mesh, at device
shard counts {1, 4, 8}, counted by scheduler_slice_gangs_bound_total;
(d) a shape with no feasible placement leaves the whole gang pending;
(e) the ChurnDay SlicePacking family (KTPU_MESH_SHAPE=auto staging,
gangArrival/sliceDeath timeline) stays schema-valid and deterministic.
"""

import asyncio
import random

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.client import InformerFactory
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops import TPUBackend
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.framework import Framework
from kubernetes_tpu.scheduler.plugins.coscheduling import (
    POD_GROUP_LABEL,
    make_pod_group,
)
from kubernetes_tpu.scheduler.plugins.registry import (
    DEFAULT_PLUGINS,
    DEFAULT_SCORE_WEIGHTS,
    build_plugins,
)
from kubernetes_tpu.store import install_core_validation, new_cluster_store
from kubernetes_tpu.topology import MeshSpec, is_contiguous_slice, node_cell
from kubernetes_tpu.utils import flags
from test_tpu_backend import default_fwk, random_cluster, random_pending


def run(coro):
    return asyncio.run(coro)


class TestActiveByDefault:
    def test_flags_default_on(self):
        assert flags.get("KTPU_TOPOLOGY") is True
        assert flags.get("KTPU_MESH_SHAPE") == "auto"

    def test_cluster_tensors_carry_planes(self):
        from kubernetes_tpu.ops.tensorize import ClusterTensors
        cache = SchedulerCache()
        for i in range(8):
            cache.add_node(make_node(f"node-{i}"))
        ct = ClusterTensors(cache.update_snapshot())
        assert ct.topology is not None
        assert ct.topology.on_mesh == 8
        assert ct.topology.rebuilt

    def test_planes_reused_for_stable_node_set(self):
        from kubernetes_tpu.topology.planes import build_topology_planes
        cache = SchedulerCache()
        for i in range(4):
            cache.add_node(make_node(f"node-{i}"))
        nodes = cache.update_snapshot().nodes
        fp = tuple((ni.name, ni.spec_epoch) for ni in nodes)
        first = build_topology_planes(nodes, 8, None, fp)
        again = build_topology_planes(nodes, 8, first, fp)
        assert again is first and not again.rebuilt


class TestKillSwitch:
    def test_structural_degrade(self, monkeypatch):
        monkeypatch.setenv("KTPU_TOPOLOGY", "0")
        from kubernetes_tpu.ops.tensorize import ClusterTensors
        cache = SchedulerCache()
        for i in range(4):
            cache.add_node(make_node(f"node-{i}"))
        assert ClusterTensors(cache.update_snapshot()).topology is None

    def test_topology_free_assignments_bit_identical(self, monkeypatch):
        """The flat-capacity call graph with the flag OFF must place a
        topology-free workload exactly like the flag-ON default."""
        rng = random.Random(19)
        snapshot = random_cluster(rng, 24)
        pods = random_pending(rng, 12)
        on, _ = TPUBackend(max_batch=8).assign(
            pods, snapshot, default_fwk())
        monkeypatch.setenv("KTPU_TOPOLOGY", "0")
        off, _ = TPUBackend(max_batch=8).assign(
            pods, snapshot, default_fwk())
        assert on == off

    def test_gang_plugin_skips_when_off(self, monkeypatch):
        """With the switch off a slice-shaped gang still gang-schedules
        (count-only Permit), but TopologySlice never activates."""
        from kubernetes_tpu.scheduler.plugins.topologyslice import (
            TopologySlice,
        )
        monkeypatch.setenv("KTPU_TOPOLOGY", "0")
        plugin = TopologySlice()
        assert not plugin.active_for(object())


async def _gang_sched(store, shards):
    plugins = build_plugins(
        DEFAULT_PLUGINS + ["Coscheduling", "TopologySlice"],
        {"TopologySlice": {"shards": shards}}, store=store)
    fwk = Framework(plugins, DEFAULT_SCORE_WEIGHTS,
                    metrics=SchedulerMetrics())
    sched = Scheduler(store, profiles={"default-scheduler": fwk},
                      seed=7, backend=TPUBackend(max_batch=8))
    factory = InformerFactory(store)
    await sched.setup_informers(factory)
    factory.start()
    await factory.wait_for_sync()
    return sched, factory


async def _bound_map(store):
    return {p["metadata"]["name"]: p["spec"]["nodeName"]
            for p in (await store.list("pods")).items
            if p["spec"].get("nodeName")}


def _slice_pod(name, group):
    return make_pod(name, labels={POD_GROUP_LABEL: group},
                    requests={"cpu": "500m"}, uid=name)


class TestShapedGangs:
    @pytest.mark.parametrize("shards", [1, 4, 8])
    def test_all_or_nothing_contiguous_bind(self, shards):
        """A 2x2 slice gang on a 4x4 auto torus: nothing binds until
        the LAST member arrives, then all four land on nodes forming
        one contiguous sub-mesh."""
        async def body():
            store = new_cluster_store()
            install_core_validation(store)
            # node-i name fallback maps the fleet onto the auto mesh.
            for i in range(16):
                await store.create("nodes", make_node(f"node-{i}"))
            await store.create("podgroups", make_pod_group(
                "tile", min_member=4, schedule_timeout_seconds=5.0,
                slice_shape=(2, 2)))
            sched, factory = await _gang_sched(store, shards)
            task = asyncio.ensure_future(sched.run(batch_size=8))
            try:
                for i in range(3):
                    await store.create("pods", _slice_pod(f"t-{i}", "tile"))
                await asyncio.sleep(0.4)
                assert await _bound_map(store) == {}

                await store.create("pods", _slice_pod("t-3", "tile"))
                for _ in range(200):
                    if len(await _bound_map(store)) == 4:
                        break
                    await asyncio.sleep(0.05)
                bound = await _bound_map(store)
                assert set(bound) == {"t-0", "t-1", "t-2", "t-3"}

                # The four nodes form one contiguous 2x2 sub-mesh.
                spec = MeshSpec((4, 4, 1), True)
                cells = [node_cell(n, {}, spec) for n in bound.values()]
                assert None not in cells
                assert is_contiguous_slice(cells, spec, (2, 2))
                assert sched.metrics.slice_gangs_bound.value() == 1
            finally:
                await sched.stop()
                task.cancel()
                factory.stop()
                store.stop()
        run(body())

    def test_impossible_shape_leaves_gang_pending(self):
        """No orientation of the shape fits the mesh: the whole gang
        stays pending — no partial binds, ever."""
        async def body():
            store = new_cluster_store()
            install_core_validation(store)
            for i in range(4):   # auto mesh: 2x2 — a 1x3 can't fit
                await store.create("nodes", make_node(f"node-{i}"))
            await store.create("podgroups", make_pod_group(
                "bar", min_member=3, schedule_timeout_seconds=0.5,
                slice_shape=(1, 3)))
            sched, factory = await _gang_sched(store, shards=1)
            task = asyncio.ensure_future(sched.run(batch_size=8))
            try:
                for i in range(3):
                    await store.create("pods", _slice_pod(f"b-{i}", "bar"))
                await asyncio.sleep(0.8)
                assert await _bound_map(store) == {}
                assert sched.metrics.slice_gangs_bound.value() == 0
            finally:
                await sched.stop()
                task.cancel()
                factory.stop()
                store.stop()
        run(body())


class TestChurnFamilySchema:
    def test_slice_packing_family_wellformed(self):
        import os

        import yaml

        from kubernetes_tpu.config.scheduler import ProfileConfig
        from kubernetes_tpu.perf.churn.faults import build_fault_timeline
        path = os.path.join(
            os.path.dirname(__file__), "..", "kubernetes_tpu", "perf",
            "config", "performance-config.yaml")
        with open(path) as f:
            families = yaml.safe_load(f)
        fam = next(c for c in families
                   if c["name"] == "ChurnSlicePacking")
        # The profile enables the gang pair at every extension point.
        prof = ProfileConfig(fam["schedulerConfig"]["profiles"][0])
        assert "Coscheduling" in prof.active["Permit"]
        assert "TopologySlice" in prof.active["PreFilter"]
        assert "TopologySlice" in prof.active["Filter"]
        churn = next(op for op in fam["workloadTemplate"]
                     if op["opcode"] == "churnOpenLoop")
        kinds = [f["kind"] for f in churn["faults"]]
        assert kinds == ["gangArrival", "sliceDeath"]
        for wl in fam["workloads"]:
            params = wl["params"]
            specs = [{k: (params[v[1:]] if isinstance(v, str)
                          and v.startswith("$") else v)
                      for k, v in f.items()} for f in churn["faults"]]
            t1 = build_fault_timeline(specs, seed=17,
                                      node_names=["node-0"])
            t2 = build_fault_timeline(specs, seed=17,
                                      node_names=["node-0"])
            assert [e.signature() for e in t1] \
                == [e.signature() for e in t2]
            # the re-coalesce fault targets the arrival's group
            death = next(e for e in t1 if e.kind == "sliceDeath")
            arrive = next(e for e in t1 if e.kind == "gangArrival")
            assert death.params["group"] == \
                f"slice-{round(arrive.at * 1e3)}"
