"""`Scheduler.hold()` / `release()`: the scheduler stands by while pods
become pending, as a standby replica does before it wins the lease.
While held, `run()` pops nothing and starts no attempt — also when the
hold finds it parked in an empty queue's pop — and informers, queue adds
and cycles in flight go on; a release hands the whole backlog to one
pop; `stop()` ends a held run. For the plain loop and the serving tier.
"""

import asyncio

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.store import install_core_validation, new_cluster_store
from tests.conftest import start_scheduler

REQ = {"cpu": "100m", "memory": "128Mi"}


async def _cluster(nodes=4):
    store = new_cluster_store()
    install_core_validation(store)
    for i in range(nodes):
        await store.create("nodes", make_node(f"node-{i}"))
    return store


async def _bound(store) -> int:
    return sum(1 for p in (await store.list("pods")).items
               if p["spec"].get("nodeName"))


async def _bound_is(store, n) -> bool:
    return await _bound(store) == n


async def _until(cond, timeout=5.0):
    for _ in range(int(timeout / 0.02)):
        got = cond()
        if asyncio.iscoroutine(got):
            got = await got
        if got:
            return True
        await asyncio.sleep(0.02)
    return False


def _backend(kind):
    if kind == "plain":
        return None
    from kubernetes_tpu.ops import TPUBackend
    return TPUBackend(max_batch=64, mesh=None)


@pytest.mark.parametrize("loop_kind", ["plain", "serving"])
@pytest.mark.parametrize("parked", [True, False], ids=["parked", "fresh"])
def test_pods_created_while_held_stay_pending_and_leave_as_one_pop(
        loop_kind, parked):
    """`parked`: the hold comes while run() already waits in the pop of
    an empty queue (where a standing scheduler is between waves);
    `fresh`: it comes before run() starts."""
    async def body():
        store = await _cluster()
        sched, factory = await start_scheduler(
            store, backend=_backend(loop_kind))
        pops = []
        drain = sched.queue._drain_locked

        def counted(max_pods):
            out = drain(max_pods)
            pops.append(len(out))
            return out
        sched.queue._drain_locked = counted
        if parked:
            task = asyncio.ensure_future(sched.run(batch_size=256))
            await asyncio.sleep(0.05)
            await sched.hold()
        else:
            await sched.hold()
            task = asyncio.ensure_future(sched.run(batch_size=256))
        for i in range(30):
            await store.create("pods", make_pod(f"p{i}", requests=REQ))
        assert await _until(lambda: sched.queue.stats()["active"] == 30)
        await asyncio.sleep(0.2)
        assert await _bound(store) == 0 and pops == []
        assert sched.queue.stats()["in_flight"] == 0
        await sched.release()
        assert await _until(
            lambda: _bound_is(store, 30), 10.0), await _bound(store)
        assert pops[0] == 30                  # the backlog left as one pop
        await sched.stop()
        task.cancel()
        factory.stop()
        store.stop()
    asyncio.run(body())


def test_a_cycle_in_flight_finishes_under_a_hold():
    """The hold stops pops, not what was popped: pods dispatched before
    it are bound while it lasts."""
    async def body():
        store = await _cluster()
        sched, factory = await start_scheduler(store)
        for i in range(6):
            await store.create("pods", make_pod(f"a{i}", requests=REQ))
        assert await _until(lambda: sched.queue.stats()["active"] == 6)
        pods = await sched.queue.pop_batch(16)       # the loop's own pop
        await sched.hold()
        await sched._schedule_pods(pods)
        assert await _until(lambda: _bound(store), 5.0)
        assert await _until(
            lambda: sched.queue.stats()["in_flight"] == 0, 5.0)
        assert await _bound(store) == 6
        await sched.stop()
        factory.stop()
        store.stop()
    asyncio.run(body())


@pytest.mark.parametrize("pending", [0, 5])
def test_stop_while_held_returns_and_ends_the_run(pending):
    async def body():
        store = await _cluster()
        sched, factory = await start_scheduler(store)
        task = asyncio.ensure_future(sched.run(batch_size=16))
        await asyncio.sleep(0.05)
        await sched.hold()
        for i in range(pending):
            await store.create("pods", make_pod(f"p{i}", requests=REQ))
        assert await _until(
            lambda: sched.queue.stats()["active"] == pending)
        await asyncio.wait_for(sched.stop(), 2.0)
        await asyncio.wait_for(task, 2.0)            # run() came back
        assert await _bound(store) == 0              # and started nothing
        factory.stop()
        store.stop()
    asyncio.run(body())


def test_release_without_a_hold_and_a_second_hold():
    async def body():
        store = await _cluster()
        sched, factory = await start_scheduler(store)
        task = asyncio.ensure_future(sched.run(batch_size=16))
        await sched.release()                        # nothing to undo
        for round_ in range(2):
            await sched.hold()
            for i in range(4):
                await store.create("pods", make_pod(
                    f"r{round_}-{i}", requests=REQ))
            assert await _until(
                lambda: sched.queue.stats()["active"] == 4)
            assert await _bound(store) == 4 * round_
            await sched.release()
            assert await _until(
                lambda r=round_: _bound_is(store, 4 * (r + 1)), 5.0)
        await sched.stop()
        task.cancel()
        factory.stop()
        store.stop()
    asyncio.run(body())
