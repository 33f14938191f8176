"""The control: the plain reference put in the program's place, with one
guarantee broken. `correct` has to come out false.

`ReferenceScheduler` is the deployment's placer (lib/reference.py:
`model.placer(sound)`) driven as a live component: it watches pending
pods on its own wire connection and binds them through the pods/binding
subresource. Sound, it looks at the cluster once per pod and keeps what
the deployment guarantees. Broken, it is whatever the deployment says
breaks ITS guarantee; the default deployment's looks once every
`stale_chunk` pods and places the whole chunk by that look — a solve
that does not carry its own placements forward, the fault a faster
pipeline would tempt — and nodes go past their allocatable.
"""

from __future__ import annotations

import asyncio

from benchmark.lib.cluster import Cluster
from benchmark.lib.reference import ClusterModel, pod_key


class ReferenceScheduler:
    def __init__(self, store, model: ClusterModel, sound: bool):
        self.store = store
        self.model = model
        self.sound = sound
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
        placer = self.model.placer(self.sound)
        while not self._stop:
            pod = await self._pending.get()
            best = placer.place(pod)
            if best < 0:
                continue
            await self.store.subresource(
                "pods", pod_key(pod), "binding",
                make_binding(pod, self.model.node_names[best]))

    async def stop(self) -> None:
        self._stop = True


def control_cluster(model: ClusterModel, sound: bool):
    """A Cluster factory whose scheduler is the deployment's reference:
    sound, or with the deployment's guarantee broken (the control)."""
    class ControlCluster(Cluster):
        device = False

        def build_scheduler(self):
            return ReferenceScheduler(self.sched_store, model, sound)
    return ControlCluster
