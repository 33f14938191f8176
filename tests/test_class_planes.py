"""Randomized differential parity: class-dictionary device planes vs the
per-pod plane fallback (ISSUE r14 acceptance: bit-identical assignments).

The class format reorganizes WHAT the solve pipeline ships and computes
— (C, N) equivalence-class planes + a (P,) index + a sparse exception
column instead of per-pod (P, N) planes — but must not move a single
assignment: the class rows carry exactly the rows every member pod would
have carried, exceptions intersect exactly the single-column host rows
they replace, and the shortlist's exactness bound covers the pinned-pod
corner (a pin outside its class shortlist falls back to the full row).
These tests run the same randomized workloads through both formats
(KTPU_CLASS_PAD=0 is the structural per-pod degrade) and require the
assignment maps to be EQUAL, including the None (unschedulable) entries,
across tight-capacity contention, affinity/score families, hard spread,
the shortlist regime, control-plane shards {1, 4, 8}, and the two
adversarial extremes (every pod its own class; one class for all).
"""

from __future__ import annotations

import asyncio
import random
import time

import pytest

from kubernetes_tpu.api.meta import namespaced_name
from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.client import InformerFactory, ResourceEventHandler
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops import TPUBackend
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler.types import PodInfo
from kubernetes_tpu.store import install_core_validation, new_cluster_store

from test_tpu_backend import default_fwk, random_cluster, random_pending

ZONES = ("a", "b", "c")


def _class_env(monkeypatch, on: bool, pad: int | None = None) -> None:
    if not on:
        monkeypatch.setenv("KTPU_CLASS_PAD", "0")
    elif pad is None:
        monkeypatch.delenv("KTPU_CLASS_PAD", raising=False)
    else:
        monkeypatch.setenv("KTPU_CLASS_PAD", str(pad))


def _assign(pods, snap, fwk, monkeypatch, on: bool, pad=None, chunk=32):
    _class_env(monkeypatch, on, pad)
    b = TPUBackend(max_batch=chunk, mesh=None)
    b.metrics = SchedulerMetrics()
    assignments, _diags = b.assign(pods, snap, fwk)
    return assignments, b.metrics


def _parity(pods, snap, monkeypatch, chunk=32, pad=None):
    fwk = default_fwk()
    dense, _ = _assign(pods, snap, fwk, monkeypatch, on=False, chunk=chunk)
    got, m = _assign(pods, snap, fwk, monkeypatch, on=True, pad=pad,
                     chunk=chunk)
    assert got == dense, {
        k: (got[k], dense[k]) for k in got if got[k] != dense[k]}
    return dense, m


def _labeled_cluster(seed: int, n_nodes: int = 40):
    """Zone-labeled nodes via the real cache (honest aggregates)."""
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    rng = random.Random(seed)
    cache = SchedulerCache()
    for i in range(n_nodes):
        cache.add_node(make_node(
            f"n{i}",
            allocatable={"cpu": str(rng.choice((4, 8, 16))),
                         "memory": rng.choice(("16Gi", "64Gi")),
                         "pods": "110"},
            labels={"zone": rng.choice(ZONES), "disk": "ssd"}))
    return cache.update_snapshot()


class TestBackendParity:
    @pytest.mark.parametrize("seed", [3, 17])
    def test_tight_capacity_contention(self, seed, monkeypatch):
        rng = random.Random(seed)
        snap = random_cluster(rng, 32, resident_per_node=4)
        pods = random_pending(rng, 96)
        _parity(pods, snap, monkeypatch, chunk=32)

    def test_affinity_and_score_rows(self, monkeypatch):
        snap = _labeled_cluster(7)
        rng = random.Random(7)
        pods = []
        for i in range(48):
            kw = dict(requests={"cpu": "250m", "memory": "256Mi"},
                      labels={"app": rng.choice(("web", "db"))},
                      uid=f"uid-{i}")
            roll = rng.random()
            if roll < 0.3:
                kw["node_selector"] = {"zone": rng.choice(ZONES)}
            elif roll < 0.6:
                kw["affinity"] = {"podAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [{
                        "weight": 50,
                        "podAffinityTerm": {
                            "topologyKey": "zone",
                            "labelSelector": {"matchLabels": {
                                "app": kw["labels"]["app"]}}}}]}}
            elif roll < 0.8:
                kw["affinity"] = {"nodeAffinity": {
                    "preferredDuringSchedulingIgnoredDuringExecution": [{
                        "weight": 10,
                        "preference": {"matchExpressions": [{
                            "key": "zone", "operator": "In",
                            "values": [rng.choice(ZONES)]}]}}]}}
            pods.append(PodInfo(make_pod(f"pend-{i}", **kw)))
        dense, m = _parity(pods, snap, monkeypatch, chunk=16)
        assert any(v is not None for v in dense.values())
        # The run really exercised multi-class dirty planes.
        assert m.plane_classes.value() >= 2
        assert m.plane_bytes.value() > 0

    def test_hard_spread(self, monkeypatch):
        snap = _labeled_cluster(11, n_nodes=24)
        cons = [{"maxSkew": 1, "topologyKey": "zone",
                 "whenUnsatisfiable": "DoNotSchedule",
                 "labelSelector": {"matchLabels": {"app": "spread"}}}]
        pods = [PodInfo(make_pod(
            f"sp-{i}", requests={"cpu": "100m", "memory": "128Mi"},
            labels={"app": "spread"}, topology_spread_constraints=cons,
            uid=f"su-{i}")) for i in range(30)]
        # Interleave unconstrained pods so contribute-only chunks and the
        # spread scan both run under class planes.
        pods += [PodInfo(make_pod(
            f"pl-{i}", requests={"cpu": "200m", "memory": "128Mi"},
            labels={"app": "spread"}, uid=f"pu-{i}")) for i in range(10)]
        _parity(pods, snap, monkeypatch, chunk=16)

    def test_shortlist_regime(self, monkeypatch):
        """Above the activation threshold the class path prunes (dense
        fallback keeps the full scan) — assignments still identical."""
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        cache = SchedulerCache()
        for i in range(160):
            cache.add_node(make_node(
                f"n{i}", allocatable={"cpu": "8", "memory": "32Gi",
                                      "pods": "110"}))
        snap = cache.update_snapshot()
        pods = [PodInfo(make_pod(
            f"pend-{i}", requests={"cpu": "500m", "memory": "512Mi"},
            uid=f"uid-{i}")) for i in range(40)]
        fwk = default_fwk()
        dense, md = _assign(pods, snap, fwk, monkeypatch, on=False,
                            chunk=16)
        got, mc = _assign(pods, snap, fwk, monkeypatch, on=True, chunk=16)
        assert got == dense
        assert mc.solver_shortlist_pods.value() == len(pods)
        assert md.solver_shortlist_pods.value() == 0

    def test_all_pods_distinct_c_equals_p(self, monkeypatch):
        """Adversarial extreme: every pod a distinct request shape. With
        a big pad the class build carries C == P real classes; past the
        pad it falls back per-pod — all three agree."""
        rng = random.Random(29)
        snap = random_cluster(rng, 24, resident_per_node=2)
        pods = [PodInfo(make_pod(
            f"pend-{i}", requests={"cpu": f"{100 + 7 * i}m",
                                   "memory": f"{64 + 3 * i}Mi"},
            uid=f"uid-{i}")) for i in range(40)]
        fwk = default_fwk()
        dense, _ = _assign(pods, snap, fwk, monkeypatch, on=False, chunk=64)
        wide, mw = _assign(pods, snap, fwk, monkeypatch, on=True, pad=64,
                           chunk=64)
        over, mo = _assign(pods, snap, fwk, monkeypatch, on=True, pad=8,
                           chunk=64)
        assert wide == dense and over == dense
        assert mw.plane_classes.value() == len(pods)          # C == P
        assert mo.class_split_fallbacks.value() == len(pods)  # overflow

    def test_single_class_c_equals_1(self, monkeypatch):
        rng = random.Random(31)
        snap = random_cluster(rng, 24, resident_per_node=2)
        pods = [PodInfo(make_pod(
            f"pend-{i}", requests={"cpu": "300m", "memory": "256Mi"},
            uid=f"uid-{i}")) for i in range(48)]
        _, m = _parity(pods, snap, monkeypatch, chunk=16)
        assert m.plane_classes.value() == 1

    def test_pinned_pods_with_scores_share_class(self, monkeypatch):
        """Pins × score plugins: a pinned pod's normalized score row is
        computed over its pin-restricted feasible set (per-pod unique),
        but a single-column argmax is score-invariant — so its parts
        are dropped from the class key and pinned pods coalesce into
        ONE scoreless class per template instead of one class per pin
        (no overflow fallback), still bit-identical to per-pod planes."""
        snap = _labeled_cluster(19, n_nodes=36)
        pods = []
        for i in range(36):
            kw = dict(requests={"cpu": "250m", "memory": "256Mi"},
                      uid=f"uid-{i}",
                      affinity={"nodeAffinity": {
                          "preferredDuringSchedulingIgnoredDuringExecution":
                          [{"weight": 10,
                            "preference": {"matchExpressions": [{
                                "key": "zone", "operator": "In",
                                "values": ["a"]}]}}]}})
            if i % 3 == 0:
                kw["node_name"] = f"n{i}"
            pods.append(PodInfo(make_pod(f"pend-{i}", **kw)))
        dense, m = _parity(pods, snap, monkeypatch, chunk=36)
        # One scored class + one pinned scoreless class, NOT 12 pin
        # classes and NOT a per-pod fallback.
        assert m.plane_classes.value() == 2
        assert m.class_split_fallbacks.value() == 0
        for i in range(0, 36, 3):
            assert dense[pods[i].key] == f"n{i}"

    def test_exception_pins_share_class(self, monkeypatch):
        """NodeName single-column rows ride the exception vector: pinned
        pods keep their template's class (C stays 1), land exactly on
        the named node, and match the per-pod fallback bit for bit."""
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        cache = SchedulerCache()
        for i in range(160):
            cache.add_node(make_node(
                f"n{i}", allocatable={"cpu": "8", "memory": "32Gi",
                                      "pods": "110"}))
        snap = cache.update_snapshot()
        pods = []
        for i in range(32):
            kw = dict(requests={"cpu": "500m", "memory": "512Mi"},
                      uid=f"uid-{i}")
            if i % 4 == 0:
                kw["node_name"] = f"n{100 + i}"
            pods.append(PodInfo(make_pod(f"pend-{i}", **kw)))
        dense, m = _parity(pods, snap, monkeypatch, chunk=16)
        assert m.plane_classes.value() == 1  # pins did NOT split classes
        for i in range(0, 32, 4):
            assert dense[pods[i].key] == f"n{100 + i}"


async def _schedule_e2e(store, nodes, pods, batch: int = 64) -> dict:
    """End-to-end through store + informers + scheduler (the
    test_sharded_parity driver): returns {pod key: node name}."""
    install_core_validation(store)
    for spec in nodes:
        await store.create("nodes", make_node(**spec))
    sched = Scheduler(store, seed=42, backend=TPUBackend(max_batch=batch),
                      metrics=SchedulerMetrics())
    factory = InformerFactory(store)
    await sched.setup_informers(factory)
    bound: dict[str, str] = {}

    def track(obj):
        node = obj.get("spec", {}).get("nodeName")
        if node:
            bound[namespaced_name(obj)] = node

    factory.informer("pods").add_event_handler(ResourceEventHandler(
        on_add=track, on_update=lambda old, new: track(new)))
    factory.start()
    await factory.wait_for_sync()
    run_task = asyncio.ensure_future(sched.run(batch_size=batch))
    try:
        for spec in pods:
            await store.create("pods", make_pod(**spec))
        deadline = time.monotonic() + 60
        while len(bound) < len(pods):
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"only {len(bound)}/{len(pods)} pods bound")
            await asyncio.sleep(0.01)
    finally:
        await sched.stop()
        run_task.cancel()
        factory.stop()
        store.stop()
    return dict(bound)


def _sharded_workload(seed: int, n_nodes: int = 48, n_pods: int = 96):
    rng = random.Random(seed)
    nodes = [dict(
        name=f"n-{i:03d}",
        allocatable={"cpu": str(rng.choice((4, 8, 16))),
                     "memory": rng.choice(("16Gi", "32Gi", "64Gi")),
                     "pods": "110"},
        labels={"zone": rng.choice(ZONES)}) for i in range(n_nodes)]
    pods = []
    for i in range(n_pods):
        spec = dict(
            name=f"p-{i:03d}",
            requests={"cpu": f"{rng.choice((100, 250, 500))}m",
                      "memory": rng.choice(("128Mi", "256Mi", "512Mi"))})
        if rng.random() < 0.3:
            spec["node_selector"] = {"zone": rng.choice(ZONES)}
        pods.append(spec)
    return nodes, pods


def test_sharded_control_plane_parity(monkeypatch):
    """Class planes vs per-pod planes, end to end through the sharded
    control plane at shard counts {1, 4, 8}: every configuration must
    produce the SAME assignment map as the unsharded per-pod reference."""
    async def go():
        nodes, pods = _sharded_workload(13)
        _class_env(monkeypatch, on=False)
        reference = await _schedule_e2e(new_cluster_store(), nodes, pods)
        assert len(reference) == len(pods)
        _class_env(monkeypatch, on=True)
        for shards in (1, 4, 8):
            got = await _schedule_e2e(
                new_cluster_store(shards=shards), nodes, pods)
            assert got == reference, (
                f"shards={shards}: "
                f"{sum(1 for k in got if got[k] != reference.get(k))} "
                f"assignments diverged")
    asyncio.run(go())
