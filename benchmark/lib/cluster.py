"""The system under test, built from the program's public classes.

The same single-process tree `PerfRunner._run_inner` builds for
`bench.py --through-apiserver --transport wire` (backing store with core
validation, admission + audit on the servers, APIServer + WireServer on
a unix socket, the scheduler on its own WireStore connection with
`Scheduler(store, seed=42, backend=TPUBackend())`, serving tier on,
flagless routing) — but standing: nothing here tears it down at the end
of a template, so a window can run wave after wave on it.

The benchmark is a CLIENT of that tree. It has a wire connection of its
own (creates, reads) and a pod informer of its own on that connection:
what it knows of a binding is what the apiserver sent it over the wire,
never a LIST and never the scheduler's cache.
"""

from __future__ import annotations

import asyncio
import gc
import os
import sys
import tempfile
import time


class Cluster:
    """The control plane, the scheduler and the benchmark's client."""

    #: False: no device backend (the control, which is no device run)
    device = True

    def __init__(self):
        #: the cell's `chips`: the devices the backend may span
        self.chips = 1
        self.backing = None
        self.api = None
        self.wire = None
        self.sched_store = None
        self.client = None
        self.metrics = None
        self.backend = None
        self.sched = None
        self.sched_factory = None
        self.client_factory = None
        self._run_task = None
        #: what the client's watch has seen: pod key -> node name
        self.bound: dict[str, str] = {}
        #: pod key -> time.monotonic() when the client first saw it bound
        self.bound_at: dict[str, float] = {}
        #: pod keys the watch showed on a SECOND node (must stay empty)
        self.rebound: list[str] = []
        self._waiting: set[str] = set()

    async def start(self, batch_size: int = 16384, chips: int = 1) -> None:
        from kubernetes_tpu.apiserver.admission import WebhookAdmission
        from kubernetes_tpu.apiserver.server import APIServer
        from kubernetes_tpu.apiserver.wire import WireServer, WireStore
        from kubernetes_tpu.client import InformerFactory, ResourceEventHandler
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        from kubernetes_tpu.policy import (
            AuditPipeline,
            AuditPolicy,
            PolicyEngine,
        )
        from kubernetes_tpu.store import (
            install_core_validation,
            new_cluster_store,
        )

        self.chips = int(chips)
        # bench.prepare's collector setting: the entry a user runs has it.
        gc.set_threshold(100_000, 50, 50)
        self.backing = new_cluster_store()
        install_core_validation(self.backing)
        self.api = APIServer(
            self.backing,
            admission=WebhookAdmission(
                self.backing, policy_engine=PolicyEngine(self.backing)),
            audit=AuditPipeline(AuditPolicy([])))
        await self.api.start()
        self.wire = WireServer.for_apiserver(self.api, host=_socket_target())
        await self.wire.start()
        self.sched_store = WireStore(self.wire.target)
        self.client = WireStore(self.wire.target)

        self.metrics = SchedulerMetrics()
        self.sched = self.build_scheduler()
        self.sched_factory = InformerFactory(self.sched_store)
        await self.sched.setup_informers(self.sched_factory)

        self.client_factory = InformerFactory(self.client)
        self.client_factory.informer("pods").add_event_handler(
            ResourceEventHandler(
                on_add=self._saw, on_update=lambda old, new: self._saw(new)))
        self.sched_factory.start()
        self.client_factory.start()
        await self.sched_factory.wait_for_sync()
        await self.client_factory.wait_for_sync()
        self._run_task = asyncio.ensure_future(
            self.sched.run(batch_size=batch_size if self.device else 1))

    def build_scheduler(self):
        """The program's scheduler on its own wire connection. (The
        control and the fault tests put something else in its place.)"""
        from kubernetes_tpu.scheduler import Scheduler
        if self.device:
            from kubernetes_tpu.ops import TPUBackend
            # exactly the cell's chips, whatever the machine holds: left
            # to itself (mesh="auto") the backend shards the node axis
            # over every device it can see
            mesh = None
            if self.chips > 1:
                from kubernetes_tpu.parallel import build_mesh
                mesh = build_mesh(self.chips)
            self.backend = TPUBackend(max_batch=None, mesh=mesh)
        return Scheduler(self.sched_store, seed=42, backend=self.backend,
                         metrics=self.metrics)

    def _saw(self, pod: dict) -> None:
        node = pod.get("spec", {}).get("nodeName")
        if not node:
            return
        meta = pod["metadata"]
        key = f"{meta.get('namespace', 'default')}/{meta['name']}"
        before = self.bound.get(key)
        if before is None:
            self.bound[key] = node
            self.bound_at[key] = time.monotonic()
            self._waiting.discard(key)
        elif before != node:
            self.rebound.append(key)

    async def wait_bound(self, keys, deadline: float) -> bool:
        """Until the client's watch has shown every key bound, or the
        deadline (time.monotonic()) passes: True when all are. One
        waiter at a time."""
        self._waiting = {k for k in keys if k not in self.bound}
        while self._waiting:
            if time.monotonic() >= deadline:
                return False
            await asyncio.sleep(0.005)
        return True

    def chips_used(self) -> int | None:
        """Devices the backend's mesh spans (no mesh: one); None where
        there is no device backend."""
        if self.backend is None:
            return None
        mesh = self.backend.mesh
        return 1 if mesh is None else int(mesh.devices.size)

    def backend_attached(self) -> bool | None:
        """None: no device backend was asked for."""
        if not self.device:
            return None
        return self.sched.backend is not None

    async def stop(self) -> None:
        """Stop every task and free the program's state."""
        if self.sched is not None:
            await self.sched.stop()
        if self._run_task is not None:
            self._run_task.cancel()
            await asyncio.gather(self._run_task, return_exceptions=True)
        for factory in (self.sched_factory, self.client_factory):
            if factory is not None:
                factory.stop()
        for store in (self.client, self.sched_store):
            if store is not None:
                await store.close()
        if self.wire is not None:
            await self.wire.stop()
        if self.api is not None:
            await self.api.stop()
        if self.backing is not None:
            self.backing.stop()
        self.sched = self.backend = self.backing = None
        self.sched_factory = self.client_factory = None


def _socket_target() -> str:
    """A unix socket under TMPDIR (never a fixed /tmp name); a path too
    long for a unix socket falls back to loopback TCP."""
    path = os.path.join(tempfile.gettempdir(),
                        f"ktpu-bench-{os.getpid()}.sock")
    if len(path.encode()) > 100:
        print(f"bench: {path!r} is too long for a unix socket; the wire "
              "runs on loopback TCP", file=sys.stderr)
        return "127.0.0.1"
    if os.path.exists(path):
        os.unlink(path)
    return f"unix:{path}"
