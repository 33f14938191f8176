"""Differential tests: TPU batch backend vs the host (CPU oracle) path.

SURVEY §7 phase 5: "Differential test: TPU vs CPU oracle on randomized
clusters". Kernels are checked one-for-one against the host plugins they
tensorize; the backend is checked end-to-end for (a) soundness — it never
assigns an infeasible placement, including under intra-batch contention —
and (b) score parity — single-pod batches pick a host-argmax node.
"""

import asyncio
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.client import InformerFactory
from kubernetes_tpu.ops import TPUBackend
from kubernetes_tpu.ops import kernels
from kubernetes_tpu.ops.tensorize import ClusterTensors, PodBatch
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.framework import CycleState, Framework
from kubernetes_tpu.scheduler.plugins.nodeaffinity import TaintToleration
from kubernetes_tpu.scheduler.plugins.noderesources import (
    BalancedAllocation,
    NodeResourcesFit,
    insufficient_resources,
)
from kubernetes_tpu.scheduler.plugins.registry import (
    DEFAULT_SCORE_WEIGHTS,
    build_plugins,
)
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo
from kubernetes_tpu.store import install_core_validation, new_cluster_store


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# fixtures: randomized clusters
# ---------------------------------------------------------------------------

TAINT_POOL = [
    {"key": "dedicated", "value": "infra", "effect": "NoSchedule"},
    {"key": "gpu", "value": "true", "effect": "NoSchedule"},
    {"key": "flaky", "value": "", "effect": "PreferNoSchedule"},
    {"key": "old", "value": "", "effect": "PreferNoSchedule"},
]
TOL_POOL = [
    {"key": "dedicated", "operator": "Equal", "value": "infra",
     "effect": "NoSchedule"},
    {"key": "gpu", "operator": "Exists"},
    {"key": "flaky", "operator": "Exists"},
]


def random_cluster(rng: random.Random, n_nodes: int, resident_per_node: int = 3):
    """Build a snapshot via the real cache so NodeInfo aggregates are honest."""
    cache = SchedulerCache()
    for i in range(n_nodes):
        taints = [t for t in TAINT_POOL if rng.random() < 0.25]
        node = make_node(
            f"n{i}",
            allocatable={
                "cpu": f"{rng.choice([2, 4, 8, 16])}",
                "memory": f"{rng.choice([4, 16, 64, 256])}Gi",
                "pods": str(rng.choice([10, 110])),
            },
            taints=taints or None,
        )
        cache.add_node(node)
        for j in range(rng.randrange(resident_per_node + 1)):
            pod = make_pod(
                f"resident-{i}-{j}", node_name=f"n{i}",
                requests={"cpu": f"{rng.randrange(100, 2000)}m",
                          "memory": f"{rng.randrange(64, 2048)}Mi"},
                tolerations=TOL_POOL,
            )
            cache.add_pod(PodInfo(pod))
    return cache.update_snapshot()


def random_pending(rng: random.Random, n: int):
    pods = []
    for i in range(n):
        tols = [t for t in TOL_POOL if rng.random() < 0.4]
        pods.append(PodInfo(make_pod(
            f"pend-{i}",
            requests={"cpu": f"{rng.randrange(100, 4000)}m",
                      "memory": f"{rng.randrange(64, 8192)}Mi"},
            tolerations=tols or None,
            uid=f"uid-{i}",
        )))
    return pods


def default_fwk():
    return Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)


# ---------------------------------------------------------------------------
# kernel-level differential
# ---------------------------------------------------------------------------

class TestScoreWire:
    def test_f16_within_band_f32_beyond(self):
        """Dirty score planes ship f16 only inside its faithful range;
        oversized plugin weights (sums >1024) must fall back to f32 and
        never reach the device as inf (ADVICE r3)."""
        from kubernetes_tpu.ops.backend import compress_score_wire
        small = np.full((4, 8), 600.0, dtype=np.float32)
        assert compress_score_wire(small).dtype == np.float16
        big = np.full((4, 8), 700.0 * 100, dtype=np.float32)  # weight 700
        wire = compress_score_wire(big)
        assert wire.dtype == np.float32
        assert np.isfinite(wire).all()
        assert compress_score_wire(np.zeros((0, 0), np.float32)).dtype \
            == np.float16


class TestKernelsVsHost:
    def setup_method(self):
        self.rng = random.Random(7)
        self.snapshot = random_cluster(self.rng, 40)
        self.pods = random_pending(self.rng, 16)
        self.ct = ClusterTensors(self.snapshot)
        self.batch = PodBatch(self.pods, self.ct, 16)

    def test_fit_mask_matches_insufficient_resources(self):
        mask = np.asarray(kernels.fit_filter_mask(
            jnp.asarray(self.ct.alloc_q), jnp.asarray(self.ct.used_q),
            jnp.asarray(self.ct.used_pods), jnp.asarray(self.ct.alloc_pods),
            jnp.asarray(self.batch.req_q)))
        for i, pi in enumerate(self.pods):
            for j, ni in enumerate(self.snapshot.nodes):
                host_fits = not insufficient_resources(pi, ni)
                # Soundness: device-feasible ⇒ host-feasible (quantization
                # may only reject, never admit).
                if mask[i, j]:
                    assert host_fits, (pi.key, ni.name)
                # Tightness on this value range (quanta are ≤ memory/2^20):
                if not mask[i, j]:
                    assert not host_fits, (pi.key, ni.name)

    def test_taint_mask_matches_host_filter(self):
        plug = TaintToleration()
        mask = np.asarray(kernels.taint_filter_mask(
            jnp.asarray(self.ct.taint_filter_mat),
            jnp.asarray(self.batch.untol_filter)))
        state = CycleState()
        for i, pi in enumerate(self.pods):
            for j, ni in enumerate(self.snapshot.nodes):
                assert mask[i, j] == plug.filter(state, pi, ni).is_success()

    def test_fit_score_matches_host(self):
        plug = NodeResourcesFit()
        col_w = np.zeros(len(self.ct.resources), np.float32)
        for spec in plug.score_resources:
            col_w[self.ct.r_index[spec["name"]]] = spec.get("weight", 1)
        scores = np.asarray(kernels.fit_score(
            jnp.asarray(self.ct.alloc_q), jnp.asarray(self.ct.used_nz_q),
            jnp.asarray(self.batch.req_nz_q), jnp.asarray(col_w),
            "LeastAllocated"))
        state = CycleState()
        for i, pi in enumerate(self.pods):
            for j, ni in enumerate(self.snapshot.nodes):
                host = plug.score(state, pi, ni)
                assert scores[i, j] == pytest.approx(host, abs=0.05), \
                    (pi.key, ni.name)

    def test_balanced_score_matches_host(self):
        plug = BalancedAllocation()
        col_mask = np.zeros(len(self.ct.resources), np.bool_)
        for r in plug.resources:
            col_mask[self.ct.r_index[r]] = True
        scores = np.asarray(kernels.balanced_allocation_score(
            jnp.asarray(self.ct.alloc_q), jnp.asarray(self.ct.used_nz_q),
            jnp.asarray(self.batch.req_nz_q), jnp.asarray(col_mask)))
        state = CycleState()
        for i, pi in enumerate(self.pods):
            for j, ni in enumerate(self.snapshot.nodes):
                host = plug.score(state, pi, ni)
                assert scores[i, j] == pytest.approx(host, abs=0.05)

    def test_taint_score_matches_host_normalized(self):
        plug = TaintToleration()
        feasible = np.ones((16, self.ct.n_pad), np.bool_)
        feasible[:, self.ct.n_real:] = False
        scores = np.asarray(kernels.taint_toleration_score(
            jnp.asarray(self.ct.taint_prefer_mat),
            jnp.asarray(self.batch.untol_prefer), jnp.asarray(feasible)))
        state = CycleState()
        for i, pi in enumerate(self.pods):
            raw = {ni.name: plug.score(state, pi, ni)
                   for ni in self.snapshot.nodes}
            plug.normalize_scores(state, pi, raw)
            for j, ni in enumerate(self.snapshot.nodes):
                assert scores[i, j] == pytest.approx(raw[ni.name], abs=0.05)


# ---------------------------------------------------------------------------
# backend-level differential
# ---------------------------------------------------------------------------

class TestBackendVsOracle:
    def test_mesh_path_is_active_and_matches_single_device(self):
        """The 8-virtual-device conftest must put the backend on its
        node-axis mesh (the production multi-chip path), and the sharded
        program must produce the same assignments as mesh=None."""
        if len(jax.devices()) < 2:
            pytest.skip("not enough devices")
        rng = random.Random(7)
        snapshot = random_cluster(rng, 30)
        pods = random_pending(rng, 16)
        fwk = default_fwk()
        sharded = TPUBackend(max_batch=8)
        assert sharded.mesh is not None, \
            "expected auto mesh on the 8-device test platform"
        single = TPUBackend(max_batch=8, mesh=None)
        a_sh, _ = sharded.assign(pods, snapshot, fwk)
        a_si, _ = single.assign(pods, snapshot, fwk)
        assert a_sh == a_si

    def test_chunked_pipeline_matches_one_chunk(self):
        """Internal chunking (device-chained used-state) must agree with a
        single-chunk solve of the same batch."""
        rng = random.Random(31)
        snapshot = random_cluster(rng, 30)
        pods = random_pending(rng, 24)
        fwk = default_fwk()
        chunked, _ = TPUBackend(max_batch=8).assign(pods, snapshot, fwk)
        whole, _ = TPUBackend(max_batch=24).assign(pods, snapshot, fwk)
        assert chunked == whole

    def test_single_pod_picks_host_argmax(self):
        rng = random.Random(11)
        for trial in range(5):
            snapshot = random_cluster(rng, 25)
            [pod] = random_pending(rng, 1)
            fwk = default_fwk()
            backend = TPUBackend(max_batch=8)
            assignments, diags = backend.assign([pod], snapshot, fwk)
            chosen = assignments[pod.key]

            # Host oracle: feasible set + combined scores.
            state = CycleState()
            fwk.run_pre_filter(state, pod, snapshot)
            feasible = [ni for ni in snapshot.nodes
                        if fwk.run_filters(state, pod, ni).is_success()]
            if not feasible:
                assert chosen is None
                continue
            assert chosen is not None, f"trial {trial}: host found {len(feasible)} nodes"
            assert chosen in {ni.name for ni in feasible}
            fwk.run_pre_score(state, pod, feasible)
            host_scores = fwk.run_scores(state, pod, feasible)
            best = max(host_scores.values())
            assert host_scores[chosen] == pytest.approx(best, abs=0.1), \
                f"trial {trial}: {host_scores[chosen]} vs max {best}"

    def test_batch_assignments_are_sequentially_feasible(self):
        rng = random.Random(23)
        for trial in range(3):
            snapshot = random_cluster(rng, 20, resident_per_node=2)
            pods = random_pending(rng, 30)
            fwk = default_fwk()
            backend = TPUBackend(max_batch=32)
            assignments, _ = backend.assign(pods, snapshot, fwk)

            # Replay on a fresh working copy with the host plugins.
            working = {ni.name: ni.clone() for ni in snapshot.nodes}
            for pi in pods:
                node = assignments.get(pi.key)
                if node is None:
                    continue
                ni = working[node]
                assert not insufficient_resources(pi, ni), \
                    f"trial {trial}: {pi.key} infeasible on {node}"
                state = CycleState()
                assert fwk.run_filters(state, pi, ni).is_success()
                ni.add_pod(pi)

    def test_unschedulable_diagnostics_name_the_resource(self):
        snapshot = random_cluster(random.Random(3), 5)
        huge = PodInfo(make_pod("huge", requests={"cpu": "4000"}))
        fwk = default_fwk()
        backend = TPUBackend(max_batch=4)
        assignments, diags = backend.assign([huge], snapshot, fwk)
        assert assignments[huge.key] is None
        statuses = diags[huge.key]
        assert statuses, "expected per-node failure reasons"
        reasons = {r for st in statuses.values() for r in st.reasons}
        assert any("Insufficient cpu" in r for r in reasons)

    def test_batch_contention_never_overcommits(self):
        """8 pods of 3 cores into nodes with 4 cores free: at most one per
        node; leftovers come back unassigned, never overpacked."""
        cache = SchedulerCache()
        for i in range(4):
            cache.add_node(make_node(f"n{i}", allocatable={
                "cpu": "4", "memory": "16Gi", "pods": "110"}))
        snapshot = cache.update_snapshot()
        pods = [PodInfo(make_pod(f"big-{i}", requests={"cpu": "3"},
                                 uid=f"u{i}")) for i in range(8)]
        fwk = default_fwk()
        backend = TPUBackend(max_batch=8)
        assignments, _ = backend.assign(pods, snapshot, fwk)
        per_node: dict[str, int] = {}
        for pi in pods:
            n = assignments.get(pi.key)
            if n:
                per_node[n] = per_node.get(n, 0) + 1
        assert sum(per_node.values()) == 4
        assert all(v == 1 for v in per_node.values())

    def test_taints_respected_in_batch(self):
        cache = SchedulerCache()
        cache.add_node(make_node("tainted", taints=[
            {"key": "dedicated", "value": "infra", "effect": "NoSchedule"}]))
        cache.add_node(make_node("open"))
        snapshot = cache.update_snapshot()
        plain = PodInfo(make_pod("plain", requests={"cpu": "1"}, uid="u1"))
        tolerant = PodInfo(make_pod(
            "tolerant", requests={"cpu": "1"}, uid="u2",
            tolerations=[{"key": "dedicated", "operator": "Equal",
                          "value": "infra", "effect": "NoSchedule"}]))
        fwk = default_fwk()
        backend = TPUBackend(max_batch=4)
        assignments, _ = backend.assign([plain, tolerant], snapshot, fwk)
        assert assignments[plain.key] == "open"
        assert assignments[tolerant.key] in ("open", "tainted")

    def test_anti_affinity_symmetry_within_batch(self):
        """Pod A has anti-affinity against app=web; pod B (app=web, no
        constraints of its own) must not verify onto A's node."""
        cache = SchedulerCache()
        cache.add_node(make_node("n0", labels={"zone": "z1"}))
        snapshot = cache.update_snapshot()
        anti = {
            "podAntiAffinity": {
                "requiredDuringSchedulingIgnoredDuringExecution": [{
                    "labelSelector": {"matchLabels": {"app": "web"}},
                    "topologyKey": "kubernetes.io/hostname",
                }]}}
        a = PodInfo(make_pod("a", labels={"app": "db"}, affinity=anti,
                             requests={"cpu": "1"}, uid="ua"))
        b = PodInfo(make_pod("b", labels={"app": "web"},
                             requests={"cpu": "1"}, uid="ub"))
        fwk = default_fwk()
        backend = TPUBackend(max_batch=4)
        assignments, _ = backend.assign([a, b], snapshot, fwk)
        assert assignments[a.key] == "n0"
        # b would violate a's anti-affinity on the only node → unassigned.
        assert assignments[b.key] is None


# ---------------------------------------------------------------------------
# end-to-end through the Scheduler batched loop
# ---------------------------------------------------------------------------

class TestSchedulerWithBackend:
    def test_batched_e2e_binds_all(self):
        async def body():
            store = new_cluster_store()
            install_core_validation(store)
            for i in range(10):
                await store.create("nodes", make_node(f"node-{i}"))
            sched = Scheduler(store, seed=1, backend=TPUBackend(max_batch=32))
            factory = InformerFactory(store)
            await sched.setup_informers(factory)
            factory.start()
            await factory.wait_for_sync()
            for i in range(60):
                await store.create("pods", make_pod(
                    f"p{i}", requests={"cpu": "200m", "memory": "256Mi"}))
            task = asyncio.ensure_future(sched.run(batch_size=32))
            for _ in range(100):
                pods = (await store.list("pods")).items
                bound = [p for p in pods if p["spec"].get("nodeName")]
                if len(bound) >= 60:
                    break
                await asyncio.sleep(0.05)
            await sched.stop()
            task.cancel()
            assert len(bound) == 60
            spread = {p["spec"]["nodeName"] for p in bound}
            assert len(spread) == 10  # LeastAllocated balances
        run(body())
