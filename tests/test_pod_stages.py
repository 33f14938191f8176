"""A pod's stages with tracing off, through a real KTPU wire.

The store stamps each commit, the wire's `ev` frame carries the stamp,
and the scheduler turns it into per-pod series on its own registry:
`scheduler_pod_stage_duration_seconds{stage}` (delivery, queue, attempt,
binding), `informer_watch_delay_seconds{resource,type}`, the scheduling
SLI and `scheduler_queue_incoming_pods_total`. Also the per-cycle
sampling of `scheduler_plugin_execution_duration_seconds`.
"""

import asyncio
import dataclasses
import random
import time

import msgpack
import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.apiserver import wire as wire_mod
from kubernetes_tpu.apiserver.wire import WireServer, WireStore
from kubernetes_tpu.client import InformerFactory
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.scheduler import framework as framework_mod
from kubernetes_tpu.scheduler.framework import (
    CycleState,
    Framework,
    Plugin,
    Status,
)
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo
from kubernetes_tpu.store import install_core_validation, new_cluster_store
from kubernetes_tpu.store.mvcc import Event
from kubernetes_tpu.utils.tracing import DEFAULT_TRACER

STAGES = ("delivery", "queue", "attempt", "binding")


@pytest.fixture(autouse=True)
def _tracing_off():
    was = DEFAULT_TRACER.enabled
    DEFAULT_TRACER.enabled = False
    yield
    DEFAULT_TRACER.enabled = was


class _Unstamped:
    """The backing store as a server that sends no commit stamp sees
    it: every watch event arrives without `committed`."""

    def __init__(self, store):
        self._store = store

    def __getattr__(self, name):
        return getattr(self._store, name)

    async def watch(self, *args, **kwargs):
        inner = await self._store.watch(*args, **kwargs)

        async def gen():
            async for ev in inner:
                yield dataclasses.replace(ev, committed=None)
        return gen()


class Plane:
    """Backing store, KTPU wire server, and a scheduler on its own wire
    connection with its own informers (no device backend unless one is
    passed: the host path)."""

    def __init__(self, enc="msgpack", stamped=True, batch_size=1,
                 **sched_kw):
        self.backing = new_cluster_store()
        install_core_validation(self.backing)
        served = self.backing if stamped else _Unstamped(self.backing)
        self.server = WireServer(served, host="unix:")
        self.enc = enc
        self.batch_size = batch_size
        self.sched_kw = sched_kw
        self.metrics = SchedulerMetrics()

    async def __aenter__(self):
        await self.server.start()
        self.sched_store = WireStore(self.server.target, enc=self.enc)
        self.client = WireStore(self.server.target, enc=self.enc)
        self.sched = Scheduler(self.sched_store, seed=7,
                               metrics=self.metrics, **self.sched_kw)
        self.factory = InformerFactory(self.sched_store)
        await self.sched.setup_informers(self.factory)
        self.factory.start()
        await self.factory.wait_for_sync()
        self.task = asyncio.ensure_future(
            self.sched.run(batch_size=self.batch_size))
        return self

    async def __aexit__(self, *exc):
        await self.sched.stop()
        self.task.cancel()
        await asyncio.gather(self.task, return_exceptions=True)
        self.factory.stop()
        await self.sched_store.close()
        await self.client.close()
        await self.server.stop()
        self.backing.stop()

    async def add_nodes(self, n, prefix="n"):
        for i in range(n):
            await self.client.create("nodes", make_node(
                f"{prefix}{i}", allocatable={"cpu": "8", "memory": "32Gi",
                                             "pods": "110"}))

    async def bind_pods(self, names, cpu="100m"):
        for name in names:
            await self.client.create("pods", make_pod(
                name, requests={"cpu": cpu, "memory": "100Mi"}))
        await self.until(
            lambda: self.count("binding") >= self._bound + len(names))
        self._bound = self.count("binding")

    _bound = 0

    async def until(self, cond, timeout=20.0):
        deadline = time.monotonic() + timeout
        while not cond():
            assert time.monotonic() < deadline, "timed out"
            await asyncio.sleep(0.01)

    def count(self, stage):
        return self.metrics.pod_stage_duration.count(stage=stage)

    def total(self, stage):
        return self.metrics.pod_stage_duration.sum(stage=stage)

    def delay_count(self, ev_type, resource="pods"):
        return self.metrics.watch_delay.count(resource=resource, type=ev_type)


@pytest.mark.parametrize("enc", ["msgpack", "json"])
def test_four_stages_tile_first_attempt_pods(enc):
    async def body():
        async with Plane(enc) as p:
            await p.add_nodes(2)
            names = [f"p{i}" for i in range(6)]
            await p.bind_pods(names)
            for stage in STAGES:
                assert p.count(stage) == len(names), stage
            assert p.total("delivery") > 0
            # the SLI starts at the first queue add, which is the first
            # activeQ entry: queue + attempt + binding, pod by pod
            sli = p.metrics.e2e_sli_duration
            assert sli.count(attempts="1") == len(names)
            tiled = sum(p.total(s) for s in ("queue", "attempt", "binding"))
            assert abs(sli.sum(attempts="1") - tiled) < 1e-6
            assert p.metrics.queue_incoming.value(
                event="PodAdd", queue="active") == len(names)
            # the creates and the bindings reached the scheduler's
            # informer stamped; so did the nodes made after its sync
            await p.until(lambda: p.delay_count("MODIFIED") >= len(names))
            assert p.delay_count("ADDED") >= len(names)
            assert p.delay_count("ADDED", "nodes") == 2
            assert p.metrics.watch_delay.sum(resource="pods",
                                             type="ADDED") > 0
    asyncio.run(body())


@pytest.mark.parametrize("route", ["fast_path", "batch"])
def test_stages_on_the_device_routes(route, monkeypatch):
    """The same stages on the device backend's two routes: lone pods on
    the single-pod fast path, a burst through batch solves (the serving
    tier off, so that no pod of the burst is drained one by one)."""
    from kubernetes_tpu.ops.backend import TPUBackend
    if route == "batch":
        monkeypatch.setenv("KTPU_SERVING", "0")

    async def body():
        backend = TPUBackend(max_batch=16, mesh=None)
        async with Plane(backend=backend,
                         batch_size=1 if route == "fast_path" else 64) as p:
            await p.add_nodes(4)
            if route == "fast_path":
                await p.bind_pods([f"f{i}" for i in range(5)])
                n = 5
                assert p.metrics.serving_fast_path_pods.value() == n
            else:
                n = 40
                await asyncio.gather(*(p.client.create("pods", make_pod(
                    f"b{i}", requests={"cpu": "100m", "memory": "100Mi"}))
                    for i in range(n)))
                await p.until(lambda: p.count("binding") >= n, timeout=120)
                assert p.metrics.solve_duration.count() > 0
                assert p.metrics.serving_fast_path_pods.value() == 0
            for stage in STAGES:
                assert p.count(stage) == n, stage
            tiled = sum(p.total(s) for s in ("queue", "attempt", "binding"))
            sli = p.metrics.e2e_sli_duration
            assert sli.count(attempts="1") == n
            assert abs(sli.sum(attempts="1") - tiled) < 1e-6
    asyncio.run(body())


def test_counts_only_rise():
    async def body():
        async with Plane() as p:
            await p.add_nodes(2)
            await p.bind_pods(["a0", "a1", "a2"])
            before = {s: p.count(s) for s in STAGES}
            sli = p.metrics.e2e_sli_duration.count(attempts="1")
            delay = p.delay_count("ADDED")
            await p.bind_pods(["b0", "b1"])
            for s in STAGES:
                assert p.count(s) == before[s] + 2
            assert p.metrics.e2e_sli_duration.count(attempts="1") == sli + 2
            assert p.delay_count("ADDED") >= delay + 2
    asyncio.run(body())


def test_retried_pod_has_two_queue_entries_and_one_delivery():
    async def body():
        async with Plane(pod_initial_backoff=0.01,
                         pod_max_backoff=0.05) as p:
            await p.add_nodes(1)
            # 10 CPUs fit no 8-CPU node: the first attempt fails
            await p.client.create("pods", make_pod(
                "big", requests={"cpu": "10", "memory": "100Mi"}))
            await p.until(lambda: p.sched.queue.stats()["unschedulable"] == 1)
            await p.client.create("nodes", make_node(
                "huge", allocatable={"cpu": "16", "memory": "64Gi",
                                     "pods": "110"}))
            await p.until(lambda: p.count("binding") == 1)
            assert p.count("delivery") == 1
            assert p.count("queue") == 2
            assert p.count("attempt") == 1
            assert p.metrics.e2e_sli_duration.count(attempts="2") == 1
            assert p.metrics.queue_incoming.value(
                event="ScheduleAttemptFailure", queue="unschedulable") == 1
    asyncio.run(body())


def test_frame_without_stamp_decodes_and_is_not_observed():
    async def body():
        async with Plane(stamped=False) as p:
            await p.add_nodes(2)
            await p.bind_pods(["u0", "u1", "u2"])
            bound = await p.client.get("pods", "default/u1")
            assert bound["spec"]["nodeName"]
            # bound through unstamped frames: no delivery, no watch age
            assert p.count("delivery") == 0
            assert p.count("queue") == 3 and p.count("binding") == 3
            assert p.metrics.watch_delay.count(
                resource="pods", type="ADDED") == 0
            assert p.metrics.watch_delay.count(
                resource="pods", type="MODIFIED") == 0
    asyncio.run(body())


def test_received_stamp_becomes_a_local_commit_time():
    now = time.monotonic()
    assert wire_mod._received_commit(["w1", "ev", "ADDED", {}]) is None
    wall = time.time() - 0.25
    local = wire_mod._received_commit(["w1", "ev", "ADDED", {}, wall])
    assert 0.2 < now - local < 0.3 + (time.monotonic() - now)


def test_tail_is_packed_once_per_event_and_shared_by_twins():
    obj = {"metadata": {"name": "x", "resourceVersion": "5"}}
    ev = Event("MODIFIED", obj, 5, committed=time.monotonic())
    twin = Event("ADDED", obj, 5, committed=ev.committed)
    twin._wire_src = ev
    first = wire_mod._event_tail(ev, True)
    assert wire_mod._event_tail(ev, True) is first
    assert wire_mod._event_tail(twin, True) is first
    frame = msgpack.unpackb(b"\x95" + msgpack.packb("w") + b"\xa2ev"
                            + msgpack.packb("MODIFIED") + first)
    assert frame[3] == obj
    assert abs(frame[4] - time.time()) < 5.0
    plain = Event("ADDED", obj, 5)
    assert wire_mod._event_tail(plain, True) == msgpack.packb(obj)


class _Fits(Plugin):
    NAME = "Fits"
    EXTENSION_POINTS = ("Filter",)

    def filter(self, state, pod, node):
        return Status.success()


def test_plugin_metrics_sampled_on_a_tenth_of_cycles():
    metrics = SchedulerMetrics()
    fwk = Framework([_Fits()], metrics=metrics)
    fwk.plugin_metrics_sampler = random.Random(2024)
    pod = PodInfo(make_pod("s"))
    node = NodeInfo(make_node("n", allocatable={"cpu": "1", "memory": "1Gi",
                                                "pods": "10"}))
    cycles = 5000
    for _ in range(cycles):
        state = fwk.new_cycle_state()
        fwk.run_filters(state, pod, node)
        fwk.run_filters(state, pod, node)   # one decision per cycle
    timed = metrics.plugin_duration.count(plugin="Fits",
                                          extension_point="Filter")
    assert timed % 2 == 0
    share = timed / 2 / cycles
    assert abs(share - framework_mod.PLUGIN_METRICS_SAMPLE_PERCENT / 100) \
        < 0.01, share
    # a clone keeps its cycle's decision; a bare state times nothing
    assert CycleState(True).clone().record_plugin_metrics is True
    fwk.run_filters(CycleState(), pod, node)
    assert metrics.plugin_duration.count(
        plugin="Fits", extension_point="Filter") == timed
