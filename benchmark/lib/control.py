"""The control: the plain reference put in the program's place, with one
guarantee broken. `correct` has to come out false.

`ReferenceScheduler` is the default scheduler of lib/reference.py driven
as a live component: it watches pending pods on its own wire connection
and binds them through the pods/binding subresource, one look at the
cluster per pod. With `stale_chunk` > 1 it looks once every that many
pods and places the whole chunk by that look — a solve that does not
carry its own placements forward, the fault a faster pipeline would
tempt — and nodes go past their allocatable.
"""

from __future__ import annotations

import asyncio

from benchmark.lib.cluster import Cluster
from benchmark.lib.reference import ClusterModel


class ReferenceScheduler:
    def __init__(self, store, model: ClusterModel, stale_chunk: int = 1):
        self.store = store
        self.model = model
        self.stale_chunk = int(stale_chunk)
        self.backend = None
        self._pending: asyncio.Queue = asyncio.Queue()
        self._stop = False

    async def setup_informers(self, factory) -> None:
        from kubernetes_tpu.client import ResourceEventHandler

        def on_add(pod: dict) -> None:
            if not (pod.get("spec") or {}).get("nodeName"):
                self._pending.put_nowait(pod)
        factory.informer("pods").add_event_handler(
            ResourceEventHandler(on_add=on_add))

    async def run(self, batch_size: int = 1) -> None:
        from kubernetes_tpu.api.types import make_binding
        placer = self.model.placer(self.stale_chunk)
        while not self._stop:
            pod = await self._pending.get()
            best = placer.place()
            if best < 0:
                continue
            meta = pod["metadata"]
            key = f"{meta.get('namespace', 'default')}/{meta['name']}"
            await self.store.subresource(
                "pods", key, "binding", make_binding(pod, f"node-{best}"))

    async def stop(self) -> None:
        self._stop = True


def control_cluster(config: dict, stale_chunk: int):
    """A Cluster factory whose scheduler is the reference (sound when
    `stale_chunk` is 1, the control when it is larger)."""
    class ControlCluster(Cluster):
        device = False

        def build_scheduler(self):
            return ReferenceScheduler(
                self.sched_store, ClusterModel(config), stale_chunk)
    return ControlCluster
