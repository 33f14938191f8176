"""The store's two event windows at and past capacity (store/mvcc.py
`push_window`): the mvcc replay log (`MVCCStore._events`, floor
`_first_retained_rv`) and a watch-cache ring (`_ResourceCache.ring`,
floor `ring_floor`).

Each case runs twice: `log` — the ring is capped by the store's event
window, so both windows fill together; `cache` — the ring's own capacity
binds under a log four times its size, so the ring fills alone and its
misses fall back to the log. The oracle is the windows' old algorithm:
a list trimmed from the front once past capacity. The same writes must
leave the same entries and the same floors.
"""

import asyncio

import pytest

from kubernetes_tpu.store.cacher import Cacher
from kubernetes_tpu.store.mvcc import Expired, MVCCStore

CAP = 8


def run(coro):
    return asyncio.run(coro)


class FrontTrimmed:
    """The reference window: append, then `del entries[:drop]` past
    capacity, the floor at the last entry dropped."""

    def __init__(self, cap: int, floor: int):
        self.cap, self.entries, self.floor = cap, [], floor

    def append(self, rv: int) -> None:
        self.entries.append(rv)
        if len(self.entries) > self.cap:
            drop = len(self.entries) - self.cap
            self.floor = self.entries[drop - 1]
            del self.entries[:drop]


class Windows:
    """A store whose `pods` ring is live from rv 0, both windows shadowed
    by the reference, and the pods state at every rv for `_at`."""

    def __init__(self, which: str):
        if which == "log":
            self.store = MVCCStore(event_window=CAP)
        else:
            self.store = MVCCStore(event_window=4 * CAP)
            self.store.cacher = Cacher(self.store, ring_capacity=CAP)
        # rv 0: every later pods event lands in the ring.
        self.ring = self.store.cacher._cache("pods").ring
        self.ref_log = FrontTrimmed(self.store._event_window, 0)
        self.ref_ring = FrontTrimmed(
            min(CAP, self.store._event_window), 0)
        self.pods_at = {0: {}}
        self.pod_events = 0

    @property
    def cache(self):
        return self.store.cacher._caches["pods"]

    def log_rvs(self) -> list[int]:
        return [ev.rv for _res, ev in self.store._events]

    def ring_rvs(self) -> list[int]:
        return [e[0] for e in self.cache.ring]

    def check_against_reference(self) -> None:
        assert self.log_rvs() == self.ref_log.entries
        assert self.store._first_retained_rv == self.ref_log.floor + 1
        assert self.ring_rvs() == self.ref_ring.entries
        assert self.cache.ring_floor == self.ref_ring.floor

    async def write(self, n: int) -> None:
        """`n` writes: pod creates, label updates, deletes, and node
        creates between them (events of another resource in the log)."""
        s = self.store
        for _ in range(n):
            i = s.resource_version
            live = [f"default/{n}" for n in sorted(self.pods_at[i])]
            if i % 4 == 3:
                await s.create("nodes", {"metadata": {"name": f"n{i}"}})
                self.ref_log.append(s.resource_version)
                self.pods_at[s.resource_version] = self.pods_at[i]
                continue
            if i % 5 == 4 and live:
                await s.delete("pods", live[0])
            elif i % 3 == 2 and live:
                def relabel(obj, i=i):
                    obj = dict(obj)
                    obj["metadata"] = dict(obj["metadata"], labels={
                        "at": str(i)})
                    return obj
                await s.guaranteed_update("pods", live[-1], relabel)
            else:
                await s.create("pods", {"metadata": {
                    "name": f"p{i}", "namespace": "default"}, "spec": {}})
            rv = s.resource_version
            self.ref_log.append(rv)
            self.ref_ring.append(rv)
            self.pod_events += 1
            got = await s.list("pods")
            self.pods_at[rv] = {p["metadata"]["name"]: p for p in got.items}
            self.check_against_reference()


async def replay(store: MVCCStore, rv: int, *, direct: bool) -> list[int]:
    watch = await (store.watch_direct if direct else store.watch)(
        "pods", resource_version=rv, bookmarks=False)
    want = sum(1 for res, ev in store._events
               if res == "pods" and ev.rv > rv)
    got = []
    while len(got) < want:
        ev = await asyncio.wait_for(watch.__anext__(), 2.0)
        got.append(ev.rv)
    await watch.aclose()
    return got


WINDOWS = pytest.mark.parametrize("which", ["log", "cache"])


@WINDOWS
def test_three_capacities_retain_exactly_the_last_capacity(which):
    async def body():
        w = Windows(which)
        await w.write(3 * CAP * 2)  # ~3 × CAP pod events and more
        assert w.pod_events >= 3 * CAP
        log, ring = w.log_rvs(), w.ring_rvs()
        assert len(log) == w.store._event_window
        assert w.store._first_retained_rv == log[0]
        assert len(ring) == min(CAP, w.store._event_window)
        assert w.cache.ring_floor + 1 == ring[0]
        w.store.stop()
    run(body())


@WINDOWS
def test_a_watch_from_the_floor_replays_exactly_the_retained(which):
    async def body():
        w = Windows(which)
        await w.write(3 * CAP * 2)
        s = w.store
        if which == "log":
            floor = s._first_retained_rv - 1
            want = [ev.rv for res, ev in s._events if res == "pods"]
            assert await replay(s, floor, direct=True) == want
        else:
            floor = w.cache.ring_floor
            hits = s.cacher.metrics.hits.value()
            assert await replay(s, floor, direct=False) == w.ring_rvs()
            assert s.cacher.metrics.hits.value() == hits + 1
        s.stop()
    run(body())


@WINDOWS
def test_a_watch_from_below_the_floor(which):
    """The log raises Expired (410); the cache hands the request to the
    log, which still holds it (a miss, not an error)."""
    async def body():
        w = Windows(which)
        await w.write(3 * CAP * 2)
        s = w.store
        if which == "log":
            with pytest.raises(Expired):
                await s.watch_direct(
                    "pods", resource_version=s._first_retained_rv - 2)
            with pytest.raises(Expired):
                await s.watch("pods", resource_version=w.cache.ring_floor - 1)
        else:
            below = w.cache.ring_floor - 1
            assert below + 1 >= s._first_retained_rv  # the log has it
            misses = s.cacher.metrics.misses.value()
            want = [ev.rv for res, ev in s._events
                    if res == "pods" and ev.rv > below]
            assert await replay(s, below, direct=False) == want
            assert s.cacher.metrics.misses.value() == misses + 1
            assert len(want) > len(w.ring_rvs())
        s.stop()
    run(body())


@WINDOWS
def test_list_at_an_rv_rolls_back_across_a_full_ring(which):
    async def body():
        w = Windows(which)
        await w.write(3 * CAP * 2)
        s = w.store
        assert len(w.ring_rvs()) == w.ring.maxlen
        for rv in range(w.cache.ring_floor, s.resource_version + 1):
            got = await s.list("pods", resource_version=rv,
                               resource_version_match="Exact")
            assert got.resource_version == rv
            assert {p["metadata"]["name"]: p for p in got.items} == \
                w.pods_at[rv]
        with pytest.raises(Expired):
            await s.list("pods", resource_version=w.cache.ring_floor - 1,
                         resource_version_match="Exact")
        s.stop()
    run(body())


@WINDOWS
def test_shrinking_the_window_on_a_live_store_trims_both(which):
    async def body():
        w = Windows(which)
        await w.write(3 * CAP * 2)
        s = w.store
        before_log, before_ring = w.log_rvs(), w.ring_rvs()
        s._event_window = CAP // 2
        assert w.log_rvs() == before_log[-(CAP // 2):]
        assert s._first_retained_rv == w.log_rvs()[0]
        assert w.ring_rvs() == before_ring[-(CAP // 2):]
        assert w.cache.ring_floor == before_ring[-(CAP // 2) - 1]
        assert w.cache.ring_floor + 1 == w.ring_rvs()[0]
        # Later writes keep the new size: the same as the reference's.
        w.ref_log = FrontTrimmed(CAP // 2, s._first_retained_rv - 1)
        w.ref_log.entries = w.log_rvs()
        w.ref_ring = FrontTrimmed(CAP // 2, w.cache.ring_floor)
        w.ref_ring.entries = w.ring_rvs()
        await w.write(CAP)
        assert len(w.log_rvs()) == len(w.ring_rvs()) == CAP // 2
        s.stop()
    run(body())


@WINDOWS
def test_evictions_count_the_writes_past_capacity(which):
    async def body():
        w = Windows(which)
        evictions = w.store.watch_metrics.window_evictions

        def count(window):
            return sum(v for key, v in evictions._values.items()
                       if key[0] == window)

        log_cap = w.store._event_window
        ring_cap = w.ring.maxlen
        await w.write(ring_cap)  # every window still has room
        assert count("log") == count("cache") == 0
        await w.write(3 * CAP * 2 - ring_cap)
        assert evictions.value(window="cache", resource="pods") == \
            w.pod_events - ring_cap > 0
        # Every write is one log event, from rv 1.
        assert count("log") == w.store.resource_version - log_cap > 0
        assert evictions.value(window="log", resource="pods") + \
            evictions.value(window="log", resource="nodes") == count("log")
        w.store.stop()
    run(body())


@WINDOWS
def test_evictions_are_served_at_the_apiservers_metrics(which):
    async def body():
        import aiohttp

        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.metrics.registry import Registry
        w = Windows(which)
        api = APIServer(w.store, metrics_registry=Registry())
        await api.start()
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.get(api.url + "/metrics") as r:
                    text = await r.text()
            assert "# TYPE store_window_evictions_total counter" in text
            assert "store_window_evictions_total{" not in text
            await w.write(3 * CAP * 2)
            n = w.store.watch_metrics.window_evictions.value(
                window=which, resource="pods")
            async with aiohttp.ClientSession() as sess:
                async with sess.get(api.url + "/metrics") as r:
                    text = await r.text()
            assert n > 0
            assert (f'store_window_evictions_total{{window="{which}",'
                    f'resource="pods"}} {n}') in text, text
        finally:
            await api.stop()
            w.store.stop()
    run(body())
