"""The single-pod fast path's device round trip leaves the event loop free.

`ServingTier._fast_cycle` dispatches the solve, yields the loop until
the index is ready (`is_ready()`), reads it (`SinglePodFastPath.fetch`)
and only then verifies, against the cache as it stands after the wait.
Pinned here: (a) the loop runs other callbacks while the index is on its
way; (b) a node filled, removed or respecced during the wait sends the
pod to the batch path with nothing assumed past allocatable; a pod
deleted during the wait ends unbound and forgotten, as when its chunk
solves; (d) `scheduler_tpu_fast_fetch_seconds` observes one wait per
fast solve, tracing on or off, and the span `solver.fast_fetch` names
the read; (e) one fast solve is in flight at a time: no attempt, assume
or resident refresh starts while an index is pending. The
yielding-vs-blocking placement differential is in
tests/test_serving_smoke.py.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.ops.backend import TPUBackend
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.types import PodInfo
from kubernetes_tpu.serving.fastpath import SinglePodFastPath
from kubernetes_tpu.serving.loop import ServingTier
from kubernetes_tpu.serving.resident import ResidentPlanes
from kubernetes_tpu.store import install_core_validation, new_cluster_store
from kubernetes_tpu.utils.tracing import Tracer
from test_serving_smoke import FastCycleScheduler

#: a wait that only a blocked loop exhausts
_WAIT_S = 10.0


def _cache(n, cpu="8"):
    cache = SchedulerCache()
    for i in range(n):
        cache.add_node(make_node(
            f"n{i}", allocatable={"cpu": cpu, "memory": "32Gi",
                                  "pods": "110"}))
    return cache


def _lone(name, cpu="100m", **kw):
    return PodInfo(make_pod(name, uid=f"u-{name}",
                            requests={"cpu": cpu, "memory": "128Mi"},
                            **kw))


def _within_allocatable(cache):
    return all(ni.requested.get("cpu") <= ni.allocatable.get("cpu")
               and ni.requested.pods <= ni.allocatable.pods
               for ni in cache.nodes.values())


class _HeldIndex:
    """A dispatched index that reads as ready only once `ready()` says
    so; reading it before then waits for `released` (an event a loop
    callback sets), so a read that blocks the loop fails on a timeout
    instead of hanging."""

    def __init__(self, arr, ready, released=None):
        self._arr, self._ready, self._released = arr, ready, released

    def is_ready(self):
        return self._ready()

    def __array__(self, dtype=None, copy=None):
        if self._released is not None \
                and not self._released.wait(_WAIT_S):
            raise AssertionError("the index was read before the loop ran")
        return np.asarray(self._arr, dtype=dtype)


def _hold_dispatch(monkeypatch, make_ready, released=None):
    """Patch SinglePodFastPath.dispatch so that each pending index is a
    _HeldIndex; `make_ready(pending)` returns its `is_ready`."""
    orig = SinglePodFastPath.dispatch

    def dispatch(self, *a, **kw):
        pending = orig(self, *a, **kw)
        if pending is None:
            return None
        return pending._replace(idx_d=_HeldIndex(
            pending.idx_d, make_ready(pending), released))

    monkeypatch.setattr(SinglePodFastPath, "dispatch", dispatch)


def test_the_loop_runs_while_the_index_is_fetched(monkeypatch):
    """The index is ready only after a loop callback ran: a cycle that
    read it without yielding the loop would wait out its timeout and
    fail."""
    sched = FastCycleScheduler(_cache(8))
    tier = ServingTier(sched)
    released = threading.Event()
    _hold_dispatch(monkeypatch, lambda pending: released.is_set, released)

    async def body():
        asyncio.get_running_loop().call_soon(released.set)
        return await tier._try_fast_path(_lone("a"))

    assert asyncio.run(body())
    assert sched.placed == [("default/a", sched.placed[0][1])]
    assert sched.metrics.serving_fast_path_failures.value() == 0


def _live_scheduler(store, nodes, cpu):
    """A running scheduler with the serving tier over `nodes` nodes of
    `cpu` CPUs; returns (sched, factory, run task)."""
    from conftest import start_scheduler

    async def start():
        for i in range(nodes):
            await store.create("nodes", make_node(
                f"n{i}", allocatable={"cpu": cpu, "memory": "16Gi",
                                      "pods": "32"}))
        sched, factory = await start_scheduler(
            store, backend=TPUBackend(max_batch=16, mesh=None))
        return sched, factory, asyncio.ensure_future(
            sched.run(batch_size=64))
    return start()


async def _bound_node(store, key, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = await store.get("pods", key)
        if got["spec"].get("nodeName"):
            return got["spec"]["nodeName"]
        await asyncio.sleep(0.02)
    raise AssertionError(f"{key} never bound")


@pytest.mark.parametrize("change", ["filled", "removed", "tainted"])
def test_a_node_changed_during_the_wait_sends_the_pod_to_the_batch_path(
        monkeypatch, change):
    """The index names a node; before it reaches the host, that node is
    filled (another pod assumed there), removed, or tainted NoSchedule.
    The verify reads the live cache, declines, and the pod binds
    elsewhere as a batch of one; no node holds more than its
    allocatable."""
    hit: list[str] = []

    async def body():
        store = new_cluster_store()
        install_core_validation(store)
        sched, factory, run = await _live_scheduler(store, 3, "1")
        loop = asyncio.get_running_loop()
        changed = threading.Event()

        def change_node(name):
            if change == "removed":
                sched.cache.remove_node(name)
            elif change == "filled":
                sched.cache.assume_pod(_lone("filler", cpu="800m"), name)
            else:
                sched.cache.update_node(make_node(
                    name, allocatable={"cpu": "1", "memory": "16Gi",
                                       "pods": "32"},
                    taints=[{"key": "drain", "effect": "NoSchedule"}]))
            changed.set()

        def make_ready(pending):
            def ready():
                if not hit:
                    hit.append(pending.node_names[
                        int(np.asarray(pending.idx_d))])
                    loop.call_soon(change_node, hit[0])
                return changed.is_set()
            return ready

        _hold_dispatch(monkeypatch, make_ready)
        try:
            await store.create("pods", make_pod(
                "p", requests={"cpu": "600m", "memory": "128Mi"}))
            node = await _bound_node(store, "default/p")
            m = sched.metrics
            assert hit and node != hit[0]
            assert m.serving_fast_path_pods.value() == 0
            assert m.backend_degradations.value(kind="lone_batch") == 1
            assert m.serving_fast_path_failures.value() == 0
            assert _within_allocatable(sched.cache)
        finally:
            await sched.stop()
            run.cancel()
            factory.stop()
            store.stop()

    asyncio.run(body())


def test_a_pod_deleted_during_the_wait_ends_unbound_and_forgotten(
        monkeypatch):
    """As when a pod is deleted while its chunk solves: the placement
    is assumed, its binding fails, and the cache forgets it."""

    async def body():
        store = new_cluster_store()
        install_core_validation(store)
        sched, factory, run = await _live_scheduler(store, 3, "4")
        deleted = asyncio.Event()
        started: list = []

        async def delete():
            await store.delete("pods", "default/gone")
            deleted.set()

        def make_ready(pending):
            def ready():
                if not started:
                    started.append(asyncio.ensure_future(delete()))
                return deleted.is_set()
            return ready

        _hold_dispatch(monkeypatch, make_ready)
        try:
            await store.create("pods", make_pod(
                "gone", requests={"cpu": "100m", "memory": "128Mi"}))
            for _ in range(500):
                if deleted.is_set() and not sched._binding_tasks:
                    break
                await asyncio.sleep(0.02)
            assert deleted.is_set()
            assert sched.metrics.serving_fast_path_pods.value() == 1
            assert not sched._binding_tasks
            assert not sched.cache.is_assumed("default/gone")
            assert _within_allocatable(sched.cache)
            assert all(not ni.pods for ni in sched.cache.nodes.values())
            assert sched.metrics.serving_fast_path_failures.value() == 0
        finally:
            await sched.stop()
            run.cancel()
            factory.stop()
            store.stop()

    asyncio.run(body())


@pytest.mark.parametrize("traced", [False, True])
def test_fast_fetch_histogram_counts_each_fast_solve(traced):
    """One observation per fast solve, whether tracing is on or off;
    an ineligible pod dispatches nothing. The blocking try_schedule
    reads through the same fetch, so it counts too."""
    tracer = Tracer(enabled=traced)
    try:
        sched = FastCycleScheduler(_cache(6), tracer=tracer)
        tier = ServingTier(sched)
        pods = [_lone(f"f{i}") for i in range(5)]
        pods.insert(2, _lone("ports", host_ports=[8080]))

        async def body():
            return [await tier._try_fast_path(pi) for pi in pods]

        took = asyncio.run(body())
    finally:
        tracer.enabled = False
    assert took == [True, True, False, True, True, True]
    h = sched.metrics.fast_fetch_duration
    assert h.count() == 5
    assert h.sum() > 0.0
    fetch_spans = [s for s in tracer.spans
                   if getattr(s, "name", None) == "solver.fast_fetch"]
    assert len(fetch_spans) == (5 if traced else 0)
    tier.fastpath.try_schedule(_lone("blocking"),
                               sched.cache.update_snapshot(), sched.fwk)
    assert h.count() == 6


def test_no_second_attempt_starts_while_a_fetch_is_pending(monkeypatch):
    """A drain of several pods: while any index is pending, no fast
    dispatch, batch dispatch, assume or resident refresh begins, though
    the loop is free for 30 ms a pod."""
    pending = [False]
    violations: list[str] = []
    fetches = [0]
    orig_fetch = SinglePodFastPath.fetch

    def make_ready(p):
        pending[0] = True
        t_ready = time.monotonic() + 0.03
        return lambda: time.monotonic() >= t_ready

    def fetch(self, p, *a):
        pending[0] = False
        fetches[0] += 1
        return orig_fetch(self, p, *a)

    def guard(cls, name):
        orig = getattr(cls, name)

        def wrapped(*a, **kw):
            if pending[0]:
                violations.append(name)
            return orig(*a, **kw)
        monkeypatch.setattr(cls, name, wrapped)

    guard(SinglePodFastPath, "dispatch")
    _hold_dispatch(monkeypatch, make_ready)
    monkeypatch.setattr(SinglePodFastPath, "fetch", fetch)
    guard(SchedulerCache, "assume_pod")
    guard(ResidentPlanes, "refresh")

    async def body():
        from kubernetes_tpu.scheduler import Scheduler
        store = new_cluster_store()
        install_core_validation(store)
        sched, factory, run = await _live_scheduler(store, 4, "8")
        orig_batch = Scheduler._schedule_pods

        async def batch(self, pods):
            if pending[0]:
                violations.append("_schedule_pods")
            return await orig_batch(self, pods)
        monkeypatch.setattr(Scheduler, "_schedule_pods", batch)
        try:
            names = [f"d{i}" for i in range(8)]
            for name in names:
                await store.create("pods", make_pod(
                    name, requests={"cpu": "100m", "memory": "128Mi"}))
            for name in names:
                await _bound_node(store, f"default/{name}")
            return sched.metrics.serving_fast_path_pods.value()
        finally:
            await sched.stop()
            run.cancel()
            factory.stop()
            store.stop()

    fast = asyncio.run(body())
    assert violations == []
    assert fast >= 1 and fetches[0] >= fast
