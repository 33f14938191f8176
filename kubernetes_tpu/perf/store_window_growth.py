"""Store cost per pod as its two event windows fill — a CPU microbenchmark,
never a device number.

`new_cluster_store()` with 5,000 nodes, then rounds of 10,000 pods: each
pod's create, then each pod's binding (the `pods/binding` subresource)
and its `Scheduled` Event, the three writes a drained pod costs the store.
One line per round: µs a pod of the creates and of binding + Event, the
two windows' fill (`_events`, the pods ring) and, where the store counts
them, `store_window_evictions_total` so far. The pods ring fills at
50,000 pods (100,000 pod events, two a pod) and the log at 65,000
(200,000 events: the nodes and three a pod): a store whose full window
shifts every entry behind it on each write steps up there.

Runs against whichever `kubernetes_tpu` is first on the path, so one
script measures any commit:

    PYTHONPATH=. python kubernetes_tpu/perf/store_window_growth.py
    PYTHONPATH=<parent checkout> python kubernetes_tpu/perf/store_window_growth.py
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import time

from kubernetes_tpu.api.meta import new_object
from kubernetes_tpu.api.types import make_binding, make_node, make_pod
from kubernetes_tpu.store import new_cluster_store


def _evictions(store) -> dict[str, float]:
    counter = getattr(store.watch_metrics, "window_evictions", None)
    if counter is None:  # a store from before the counter
        return {}
    out: dict[str, float] = {}
    for (window, _resource), v in counter._values.items():
        out[window] = out.get(window, 0.0) + v
    return out


async def measure(nodes: int, pods: int, step: int) -> list[dict]:
    store = new_cluster_store()
    for i in range(nodes):
        await store.create("nodes", make_node(f"node-{i}"), _owned=True,
                           return_copy=False)
    rows = []
    for lo in range(0, pods, step):
        batch = [make_pod(f"pod-{i}") for i in range(lo, lo + step)]
        bindings = [(f"default/pod-{lo + j}",
                     make_binding(p, f"node-{(lo + j) % nodes}"))
                    for j, p in enumerate(batch)]
        events = [new_object(
            "Event", f"pod-{lo + j}.{lo + j:x}", "default",
            involvedObject={"kind": "Pod", "name": f"pod-{lo + j}",
                            "namespace": "default"},
            type="Normal", reason="Scheduled", message="bound",
            source={"component": "default-scheduler"}, count=1)
            for j in range(step)]
        t0 = time.perf_counter()
        for p in batch:
            await store.create("pods", p, _owned=True, return_copy=False)
        t1 = time.perf_counter()
        for (key, b), ev in zip(bindings, events):
            await store.subresource("pods", key, "binding", b)
            await store.create("events", ev, _owned=True, return_copy=False)
        t2 = time.perf_counter()
        ring = store.cacher._caches["pods"].ring if store.cacher else ()
        rows.append({
            "pods": lo + step,
            "create_us_per_pod": round((t1 - t0) / step * 1e6, 1),
            "bind_event_us_per_pod": round((t2 - t1) / step * 1e6, 1),
            "log_len": len(store._events),
            "pods_ring_len": len(ring),
            "evictions": _evictions(store),
        })
        print(json.dumps(rows[-1]), flush=True)
    store.stop()
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--nodes", type=int, default=5_000)
    ap.add_argument("--pods", type=int, default=90_000)
    ap.add_argument("--step", type=int, default=10_000)
    args = ap.parse_args()
    # The collector's pauses grow with the heap and are not the store's.
    gc.disable()
    asyncio.run(measure(args.nodes, args.pods, args.step))


if __name__ == "__main__":
    main()
