"""Store durability: WAL + snapshot + crash recovery (SURVEY §5.4).

The contract proved here: a killed-and-restarted control plane resumes
with resourceVersion continuity, watches resume across the restart for
rvs newer than the last snapshot, and older rvs get 410 Expired (the
informer relist signal).
"""

import asyncio
import json
import os
import tempfile
import unittest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.client import InformerFactory
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.store import (
    DurabilityManager,
    Expired,
    MVCCStore,
    install_core_validation,
    new_cluster_store,
    recover_store,
)


def run(coro):
    return asyncio.run(coro)


class TestWALRecovery(unittest.TestCase):
    def test_crash_recovery_rv_continuity_and_watch_resume(self):
        async def body():
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            install_core_validation(store)
            mgr = DurabilityManager(store, d, fsync="always",
                                    snapshot_interval_s=3600)
            await store.create("nodes", make_node("n0"))
            for i in range(5):
                await store.create("pods", make_pod(f"p{i}"))
            snap_rv = mgr.wal.snapshot()          # checkpoint mid-history
            created = await store.create("pods", make_pod("after-snap"))
            rv_before_crash = int(created["metadata"]["resourceVersion"])
            await store.create("pods", make_pod("last"))
            uid_last = (await store.get("pods", "default/last"))[
                "metadata"]["uid"]
            final_rv = store.resource_version
            # CRASH: no clean close, no final snapshot — the WAL alone
            # must carry the post-snapshot writes (fsync="always").
            del store, mgr

            re_store = recover_store(d)
            install_core_validation(re_store)
            # state + rv continuity
            self.assertEqual(re_store.resource_version, final_rv)
            pods = (await re_store.list("pods")).items
            self.assertEqual(len(pods), 7)
            self.assertEqual(
                (await re_store.get("pods", "default/last"))[
                    "metadata"]["uid"], uid_last)
            fresh = await re_store.create("pods", make_pod("post-restart"))
            self.assertEqual(int(fresh["metadata"]["resourceVersion"]),
                             final_rv + 1)
            # watch resumes exactly where the crashed watcher stopped
            watch = await re_store.watch(
                "pods", resource_version=rv_before_crash)
            got = []
            async for ev in watch:
                if ev.type == "BOOKMARK":
                    continue
                got.append((ev.type, ev.object["metadata"]["name"]))
                if len(got) == 2:
                    break
            self.assertEqual(got, [("ADDED", "last"),
                                   ("ADDED", "post-restart")])
            # pre-snapshot rvs are compacted -> 410 Expired (relist)
            with self.assertRaises(Expired):
                await re_store.watch("pods", resource_version=snap_rv - 3)
            re_store.stop()
        run(body())

    def test_deletes_and_updates_survive(self):
        async def body():
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            install_core_validation(store)
            DurabilityManager(store, d, fsync="always",
                              snapshot_interval_s=3600)
            await store.create("pods", make_pod("keep"))
            await store.create("pods", make_pod("gone"))
            await store.delete("pods", "default/gone")

            def label(obj):
                obj["metadata"].setdefault("labels", {})["x"] = "1"
                return obj
            await store.guaranteed_update("pods", "default/keep", label)
            del store

            re_store = recover_store(d)
            pods = (await re_store.list("pods")).items
            self.assertEqual([p["metadata"]["name"] for p in pods],
                             ["keep"])
            self.assertEqual(pods[0]["metadata"]["labels"]["x"], "1")
            re_store.stop()
        run(body())

    def test_replay_longer_than_the_window_keeps_its_tail(self):
        """A WAL tail longer than the recovered store's event window
        enters it through the commit's append: the window holds the last
        `event_window` events and the floor names the first of them —
        never a floor that claims events the window dropped."""
        async def body():
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            DurabilityManager(store, d, fsync="always",
                              snapshot_interval_s=3600)
            for i in range(12):
                await store.create("pods", make_pod(f"p{i}"))
            written = [int(p["metadata"]["resourceVersion"])
                       for p in (await store.list("pods")).items]
            del store

            re_store = recover_store(d, factory=lambda: MVCCStore(
                event_window=5))
            kept = [ev.rv for _res, ev in re_store._events]
            self.assertEqual(kept, sorted(written)[-5:])
            self.assertEqual(re_store._first_retained_rv, kept[0])
            with self.assertRaises(Expired):
                await re_store.watch("pods", resource_version=kept[0] - 2)
            watch = await re_store.watch("pods",
                                         resource_version=kept[0] - 1)
            got = []
            async for ev in watch:
                if ev.type != "BOOKMARK":
                    got.append(ev.rv)
                if len(got) == 5:
                    break
            self.assertEqual(got, kept)
            re_store.stop()
        run(body())

    def test_torn_tail_truncates_not_corrupts(self):
        async def body():
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            DurabilityManager(store, d, fsync="always",
                              snapshot_interval_s=3600)
            await store.create("pods", make_pod("a"))
            await store.create("pods", make_pod("b"))
            # simulate a torn write at the tail
            wal = [f for f in os.listdir(d) if f.startswith("wal-")][0]
            with open(os.path.join(d, wal), "a") as f:
                f.write('[9999,"ADDED","po')  # no newline, truncated JSON
            del store
            re_store = recover_store(d)
            names = sorted(p["metadata"]["name"]
                           for p in (await re_store.list("pods")).items)
            self.assertEqual(names, ["a", "b"])
            self.assertLess(re_store.resource_version, 9999)
            re_store.stop()
        run(body())

    def test_periodic_snapshot_compacts_and_recovers(self):
        async def body():
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            mgr = DurabilityManager(store, d, fsync="batch",
                                    flush_interval_s=0.01,
                                    snapshot_interval_s=0.05)
            mgr.start()
            for i in range(30):
                await store.create("pods", make_pod(f"p{i}"))
                await asyncio.sleep(0.005)
            await asyncio.sleep(0.1)  # let a snapshot land
            snaps = [f for f in os.listdir(d) if f.startswith("snapshot-")]
            self.assertTrue(snaps, "no periodic snapshot written")
            await mgr.stop()
            del store
            re_store = recover_store(d)
            self.assertEqual(
                len((await re_store.list("pods")).items), 30)
            re_store.stop()
        run(body())

    def test_selector_watch_transition_survives_restart(self):
        """prev_labels ride the WAL: a selector watcher resuming across
        the restart sees the synthesized DELETED for a label transition
        that happened while it was down (cacher prevObject semantics)."""
        async def body():
            import tempfile
            from kubernetes_tpu.api.labels import parse_selector
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            install_core_validation(store)
            DurabilityManager(store, d, fsync="always",
                              snapshot_interval_s=3600)
            created = await store.create(
                "pods", make_pod("a", labels={"app": "web"}))
            rv0 = int(created["metadata"]["resourceVersion"])

            def drop(obj):
                obj["metadata"]["labels"] = {}
                return obj
            await store.guaranteed_update("pods", "default/a", drop)
            del store  # crash

            re_store = recover_store(d)
            watch = await re_store.watch(
                "pods", resource_version=rv0,
                selector=parse_selector("app=web"))
            async for ev in watch:
                if ev.type == "BOOKMARK":
                    continue
                self.assertEqual(ev.type, "DELETED")
                self.assertEqual(ev.object["metadata"]["name"], "a")
                break
            re_store.stop()
        run(body())

    def test_control_plane_restart_e2e(self):
        """Full loop: scheduler binds pods, the process 'dies', a new
        control plane recovers the store and keeps scheduling — bound
        pods stay bound, pending pods get scheduled."""
        async def body():
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            install_core_validation(store)
            DurabilityManager(store, d, fsync="always",
                              snapshot_interval_s=3600)
            for i in range(3):
                await store.create("nodes", make_node(f"n{i}"))
            sched = Scheduler(store, seed=1)
            factory = InformerFactory(store)
            await sched.setup_informers(factory)
            factory.start()
            await factory.wait_for_sync()
            loop = asyncio.ensure_future(sched.run(batch_size=8))
            for i in range(4):
                await store.create("pods", make_pod(f"p{i}"))
            for _ in range(200):
                pods = (await store.list("pods")).items
                if sum(1 for p in pods
                       if p["spec"].get("nodeName")) == 4:
                    break
                await asyncio.sleep(0.02)
            await sched.stop()
            loop.cancel()
            factory.stop()
            # crash + restart
            del store
            re_store = recover_store(d)
            install_core_validation(re_store)
            pods = (await re_store.list("pods")).items
            bound = {p["metadata"]["name"]: p["spec"].get("nodeName")
                     for p in pods}
            self.assertEqual(sum(1 for v in bound.values() if v), 4)
            sched2 = Scheduler(re_store, seed=2)
            factory2 = InformerFactory(re_store)
            await sched2.setup_informers(factory2)
            factory2.start()
            await factory2.wait_for_sync()
            loop2 = asyncio.ensure_future(sched2.run(batch_size=8))
            await re_store.create("pods", make_pod("new-after-restart"))
            ok = False
            for _ in range(200):
                p = await re_store.get("pods", "default/new-after-restart")
                if p["spec"].get("nodeName"):
                    ok = True
                    break
                await asyncio.sleep(0.02)
            self.assertTrue(ok, "recovered control plane failed to bind")
            # bindings persisted before the crash are untouched
            for name, node in bound.items():
                cur = await re_store.get("pods", f"default/{name}")
                self.assertEqual(cur["spec"].get("nodeName"), node)
            await sched2.stop()
            loop2.cancel()
            factory2.stop()
            re_store.stop()
        run(body())


class TestSnapshotCrashAtomicity(unittest.TestCase):
    """ISSUE r22 satellite: snapshot writes are crash-atomic — written
    to `snapshot-<rv>.json.tmp`, fsynced, then `os.replace`d — so a
    crash mid-snapshot can never leave a half-written file that
    recovery would load as truth."""

    def test_no_tmp_after_snapshot_and_orphan_ignored(self):
        async def body():
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            install_core_validation(store)
            mgr = DurabilityManager(store, d, fsync="always",
                                    snapshot_interval_s=3600)
            for i in range(4):
                await store.create("pods", make_pod(f"p{i}"))
            mgr.wal.snapshot()
            self.assertFalse(
                [f for f in os.listdir(d) if f.endswith(".tmp")],
                "normal snapshot left a .tmp behind")
            # A crash between the tmp write and os.replace leaves an
            # orphan — even one claiming a FUTURE rv with garbage in it.
            orphan = os.path.join(d, "snapshot-999999.json.tmp")
            with open(orphan, "w") as f:
                f.write('{"rv": 999999, "tables": {"pods"')
            await store.create("pods", make_pod("after"))
            final_rv = store.resource_version
            del store, mgr  # crash

            re_store = recover_store(d)
            self.assertEqual(re_store.resource_version, final_rv)
            self.assertEqual(
                len((await re_store.list("pods")).items), 5)
            # the next snapshot's GC reclaims the orphan
            mgr2 = DurabilityManager(re_store, d, fsync="always",
                                     snapshot_interval_s=3600)
            mgr2.wal.snapshot()
            self.assertFalse(os.path.exists(orphan),
                             "snapshot GC left the .tmp orphan")
            await mgr2.stop()
            re_store.stop()
        run(body())

    def test_crash_between_rotate_and_snapshot_write(self):
        """Phase A (capture + segment rotation) landed, phase B (the
        disk write) never did: recovery must fall back to the OLD
        snapshot and replay BOTH WAL segments — no committed write
        lost."""
        async def body():
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            install_core_validation(store)
            mgr = DurabilityManager(store, d, fsync="always",
                                    snapshot_interval_s=3600)
            for i in range(3):
                await store.create("pods", make_pod(f"p{i}"))
            mgr.wal.snapshot()
            await store.create("pods", make_pod("in-old-segment"))
            # crash window: rotate happens, write_snapshot never runs
            mgr.wal.begin_snapshot()
            await store.create("pods", make_pod("in-new-segment"))
            final_rv = store.resource_version
            del store, mgr  # crash

            re_store = recover_store(d)
            names = sorted(p["metadata"]["name"]
                           for p in (await re_store.list("pods")).items)
            self.assertEqual(names, sorted(
                ["p0", "p1", "p2", "in-old-segment", "in-new-segment"]))
            self.assertEqual(re_store.resource_version, final_rv)
            re_store.stop()
        run(body())

    def test_stop_serializes_with_inflight_background_snapshot(self):
        """stop() awaits the background write_snapshot worker thread
        before taking its own final snapshot — two writers interleaving
        segment rotation + GC was the corruption window."""
        async def body():
            import time as _time
            d = tempfile.mkdtemp()
            store = new_cluster_store()
            install_core_validation(store)
            mgr = DurabilityManager(store, d, fsync="batch",
                                    flush_interval_s=0.01,
                                    snapshot_interval_s=0.05)
            orig = mgr.wal.write_snapshot

            def slow_write(data, rv):
                _time.sleep(0.3)   # widen the in-flight window
                orig(data, rv)
            mgr.wal.write_snapshot = slow_write
            mgr.start()
            for i in range(10):
                await store.create("pods", make_pod(f"p{i}"))
            for _ in range(400):   # wait for a background snapshot
                if mgr._snap_inflight is not None:
                    break
                await asyncio.sleep(0.01)
            self.assertIsNotNone(mgr._snap_inflight)
            await mgr.stop(final_snapshot=True)  # races the worker

            self.assertFalse(
                [f for f in os.listdir(d) if f.endswith(".tmp")])
            final_rv = store.resource_version
            del store, mgr
            re_store = recover_store(d)
            self.assertEqual(re_store.resource_version, final_rv)
            self.assertEqual(
                len((await re_store.list("pods")).items), 10)
            re_store.stop()
        run(body())

    def test_wal_kill_switch_snapshot_only(self):
        """KTPU_WAL=0 degrades to snapshot-only durability (the r16
        shape): writes after the last snapshot are legitimately lost on
        crash, and the log file stays empty. KTPU_WAL_FSYNC routes the
        fsync policy when no explicit argument is given."""
        async def body():
            from kubernetes_tpu.utils import flags
            d = tempfile.mkdtemp()
            with flags.scoped_set("KTPU_WAL", False), \
                    flags.scoped_set("KTPU_WAL_FSYNC", "always"):
                store = new_cluster_store()
                mgr = DurabilityManager(store, d,
                                        snapshot_interval_s=3600)
                self.assertEqual(mgr.wal.fsync, "always")
                self.assertFalse(mgr.wal.enabled)
                await store.create("pods", make_pod("durable"))
                mgr.wal.snapshot()
                await store.create("pods", make_pod("volatile"))
                del store, mgr  # crash: post-snapshot write unlogged
            wals = [f for f in os.listdir(d) if f.startswith("wal-")]
            self.assertTrue(all(
                os.path.getsize(os.path.join(d, f)) == 0 for f in wals))
            re_store = recover_store(d)
            names = [p["metadata"]["name"]
                     for p in (await re_store.list("pods")).items]
            self.assertEqual(names, ["durable"])
            re_store.stop()
        run(body())


class TestWALReplayDifferential(unittest.TestCase):
    """ISSUE r22 satellite: randomized differential — a seeded random
    create/update/delete stream with snapshots interleaved, crash,
    recover, then compare the FULL recovered dump (every table, every
    object, the rv counter) against the live store's final dump."""

    def test_randomized_stream_parity(self):
        async def body():
            import random
            for seed in (7, 23, 101):
                rng = random.Random(seed)
                d = tempfile.mkdtemp()
                store = new_cluster_store()
                install_core_validation(store)
                mgr = DurabilityManager(store, d, fsync="always",
                                        snapshot_interval_s=3600)
                alive = {"pods": [], "nodes": []}
                serial = 0
                for _ in range(120):
                    resource = rng.choice(("pods", "nodes"))
                    roll = rng.random()
                    if roll < 0.5 or not alive[resource]:
                        serial += 1
                        name = f"s{seed}-{resource[:-1]}-{serial}"
                        obj = (make_pod(name) if resource == "pods"
                               else make_node(name))
                        await store.create(resource, obj)
                        ns = obj["metadata"].get("namespace", "")
                        alive[resource].append(
                            f"{ns}/{name}" if ns else name)
                    elif roll < 0.8:
                        key = rng.choice(alive[resource])
                        stamp = str(rng.randrange(10_000))

                        def label(obj, stamp=stamp):
                            obj["metadata"].setdefault(
                                "labels", {})["stamp"] = stamp
                            return obj
                        await store.guaranteed_update(
                            resource, key, label)
                    else:
                        key = rng.choice(alive[resource])
                        alive[resource].remove(key)
                        await store.delete(resource, key)
                    if rng.random() < 0.05:
                        mgr.wal.snapshot()  # checkpoint mid-stream
                live = json.loads(store.dump())
                del store, mgr  # crash

                re_store = recover_store(d)
                recovered = json.loads(re_store.dump())
                self.assertEqual(recovered, live,
                                 f"replay diverged for seed {seed}")
                re_store.stop()
        run(body())


class TestServerDurabilityBootstrap(unittest.TestCase):
    """The KTPU_DATA_DIR / data_dir bootstrap (ISSUE 12 satellite):
    persistence reachable END TO END through the server, not just from
    tests — APIServer(data_dir=...) recovers on construction, runs the
    background snapshotter for its lifetime, and a restarted server
    serves the previous run's objects over the wire."""

    def test_server_data_dir_recover_on_restart(self):
        async def body():
            from kubernetes_tpu.apiserver import APIServer, RemoteStore
            d = tempfile.mkdtemp()
            srv = APIServer(data_dir=d, fsync="always")
            await srv.start()
            rs = RemoteStore(srv.url)
            await rs.create("nodes", make_node("dur-n0"))
            await rs.create("pods", make_pod("dur-p0"))
            rv_before = srv.store.resource_version
            await rs.close()
            await srv.stop()  # final snapshot on clean shutdown
            snaps = [f for f in os.listdir(d) if f.startswith("snapshot-")]
            self.assertTrue(snaps, "clean stop left no snapshot")

            srv2 = APIServer(data_dir=d)
            await srv2.start()
            self.assertGreaterEqual(srv2.store.resource_version, rv_before)
            rs2 = RemoteStore(srv2.url)
            pods = (await rs2.list("pods")).items
            self.assertEqual([p["metadata"]["name"] for p in pods],
                             ["dur-p0"])
            nodes = (await rs2.list("nodes")).items
            self.assertEqual([n["metadata"]["name"] for n in nodes],
                             ["dur-n0"])
            # RV continuity: the next write rides the recovered counter,
            # and the recovered server keeps committing to the WAL.
            created = await rs2.create("pods", make_pod("dur-p1"))
            self.assertGreater(
                int(created["metadata"]["resourceVersion"]), rv_before)
            await rs2.close()
            await srv2.stop()
        run(body())

    def test_env_bootstrap(self):
        async def body():
            from kubernetes_tpu.apiserver import APIServer
            d = tempfile.mkdtemp()
            os.environ["KTPU_DATA_DIR"] = d
            try:
                srv = APIServer()
                await srv.start()
                self.assertIsNotNone(srv.durability)
                await srv.store.create("pods", make_pod("env-p0"))
                await srv.stop()
            finally:
                os.environ.pop("KTPU_DATA_DIR", None)
            re_store = recover_store(d)
            self.assertEqual(
                (await re_store.get("pods", "default/env-p0"))[
                    "metadata"]["name"], "env-p0")
            # No store, no dir → explicit error, not a silent
            # in-memory server masquerading as durable.
            with self.assertRaises(ValueError):
                APIServer()
        run(body())


if __name__ == "__main__":
    unittest.main()
