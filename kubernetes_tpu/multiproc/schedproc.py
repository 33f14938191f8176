"""Scheduler process: leader-elected active/standby pair.

`sched_main` is the spawn-child entrypoint for one scheduler replica.
Each replica builds a `ProcessShardedStore` over the shard sockets
and blocks in `LeaderElector.run` on the `ktpu-scheduler` Lease
(client/leaderelection.py — lease CAS, KTPU_LEASE_DURATION clock).
Only the LEADER constructs the Scheduler and its device backend,
rebuilds its assume-cache from fresh informer LISTs (the reference's
behavior: scheduler cache state is never replicated, it is REBUILT on
failover), and schedules; the standby holds no informers and no
backend — a chip belongs to one process, so a standby that initialised
JAX before the election would take it from the leader — and costs
nothing until the lease frees.

Measurement rides the store, not a side channel: the parent writes a
marker ConfigMap (`kube-system/ktpu-measure`, `{id, op}`) and the
leader's status loop answers on `kube-system/ktpu-sched-status` with
the acked marker id, its scheduled count, and — after an `end`
marker — exact attempt percentiles over the marked window (the r11
WindowedLatencyRecorder, same recorder the in-process harness
reads). After a failover the new leader marks from ITS window start,
so percentiles cover the post-failover tail — honest, and visible in
the detail JSON via `leader_elections_total` > 1. The same status row
carries the leader's device-loss counters and solve provenance: the
parent of a device run never touches JAX, so it learns from here which
device scheduled and whether it was kept.

A replica imports jax only after it holds the lease, and only when the
parent requested a device backend — a host-path scheduler pair boots
in interpreter time.
"""

from __future__ import annotations

import asyncio
import os
import signal

MARKER_KEY = "kube-system/ktpu-measure"
STATUS_KEY = "kube-system/ktpu-sched-status"
STATUS_PERIOD_S = 0.1


def sched_main(identity: str, targets: list, env: dict,
               backend_spec: dict | None = None, batch_size: int = 1,
               scheduler_kwargs: dict | None = None) -> None:
    """Process target (module-level for spawn pickling). Blocks until
    SIGTERM/SIGINT; the active replica additionally dies with the
    whole process on kill_leader() — that is the point."""
    os.environ.update(env)
    asyncio.run(_replica(identity, list(targets), backend_spec,
                         batch_size, dict(scheduler_kwargs or {})))


async def _replica(identity: str, targets: list,
                   backend_spec: dict | None, batch_size: int,
                   scheduler_kwargs: dict) -> None:
    from kubernetes_tpu.client.leaderelection import LeaderElector
    from kubernetes_tpu.multiproc.client import ProcessShardedStore

    store = ProcessShardedStore(targets)
    elector = LeaderElector(store, "ktpu-scheduler", identity)
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        loop.add_signal_handler(sig, stop.set)

    async def lead() -> None:
        await _lead(store, identity, backend_spec, batch_size,
                    scheduler_kwargs, elector)

    run_task = asyncio.ensure_future(elector.run(lead))
    stop_task = asyncio.ensure_future(stop.wait())
    await asyncio.wait({run_task, stop_task},
                       return_when=asyncio.FIRST_COMPLETED)
    run_task.cancel()
    await asyncio.gather(run_task, return_exceptions=True)
    stop_task.cancel()
    await store.close()


def _device_backend(chunk: int | None):
    from kubernetes_tpu.ops import TPUBackend
    from kubernetes_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    return TPUBackend(max_batch=chunk)


async def _lead(store, identity: str, backend_spec: dict | None,
                batch_size: int, scheduler_kwargs: dict, elector) -> None:
    """The leader payload: the device backend (built only now that the
    lease is held), assume-cache rebuild (fresh informers), the
    scheduling loop, and the status/marker responder."""
    from kubernetes_tpu.client.informer import InformerFactory
    from kubernetes_tpu.metrics.registry import SchedulerMetrics
    from kubernetes_tpu.scheduler.scheduler import Scheduler

    backend = None
    if backend_spec and backend_spec.get("kind") == "tpu":
        # In a worker thread: importing jax and reaching the chip takes
        # ~10 s (more on a busy host), and the elector renews the lease
        # on THIS loop. Built inline, a slow start outlived the 15 s
        # lease on the v5e (PR 21): the standby took it, and then needed
        # the chip this process still held.
        backend = await asyncio.to_thread(
            _device_backend, backend_spec.get("chunk"))

    metrics = SchedulerMetrics()
    metrics.registry._metrics.setdefault(
        "leader_elections_total", elector.metrics.elections)
    metrics.registry._metrics.setdefault(
        "scheduler_is_leader", elector.metrics.is_leader)
    sched = Scheduler(store, seed=42, backend=backend, metrics=metrics,
                      **scheduler_kwargs)
    factory = InformerFactory(store)
    await sched.setup_informers(factory)
    factory.start()
    # The stretch presets put ~1M objects behind this sync: LIST +
    # decode over the wire is minutes, not seconds, on a narrow box. A
    # tight timeout here turns "slow sync" into a leader crash-loop
    # (payload dies -> lease expires -> standby dies the same way), so
    # the deadline only guards against a truly wedged apiserver.
    await factory.wait_for_sync(timeout=900.0)
    status = asyncio.ensure_future(
        _status_loop(store, identity, metrics, elector,
                     sched if backend is not None else None))
    try:
        await sched.run(batch_size=batch_size)
    finally:
        status.cancel()
        await asyncio.gather(status, return_exceptions=True)
        await sched.stop()
        factory.stop()


async def _status_loop(store, identity: str, metrics, elector,
                       device_sched=None) -> None:
    """Answer measure markers and publish leader status via ConfigMaps.
    Store writes ride the meta shard like any client's — no side
    channel to keep alive across failover. `device_sched` is the
    Scheduler when it was given a device backend: its device-loss
    counters and solve provenance ride the row (see the module doc)."""
    import json

    from kubernetes_tpu.api.meta import new_object
    from kubernetes_tpu.store.mvcc import NotFound, StoreError

    provenance = ""
    if device_sched is not None:
        from kubernetes_tpu.ops.backend import solve_provenance
        provenance = json.dumps(solve_provenance())

    win = metrics.attempt_window()
    mark: int | None = None
    acked = ""
    pcts: dict | None = None
    while True:
        try:
            try:
                marker = (await store.get(
                    "configmaps", MARKER_KEY)).get("data") or {}
            except NotFound:
                marker = {}
            mid = str(marker.get("id", ""))
            if mid and mid != acked:
                if marker.get("op") == "begin":
                    mark = win.mark()
                    pcts = None
                elif mark is not None:
                    pcts = win.percentiles_since(
                        mark, (0.50, 0.90, 0.99, 0.999))
                acked = mid
            data = {
                "identity": identity,
                "ackId": acked,
                "isLeader": "1" if elector.is_leader else "0",
                "elections": str(int(elector.metrics.elections.value())),
                "scheduledTotal": str(int(metrics.schedule_attempts.value(
                    result="scheduled", profile="default-scheduler"))),
            }
            if pcts is not None:
                for q, label in ((0.50, "p50"), (0.90, "p90"),
                                 (0.99, "p99"), (0.999, "p999")):
                    data[label] = repr(pcts[q])
            if device_sched is not None:
                data.update(
                    {k: str(v)
                     for k, v in metrics.device_loss_counts().items()},
                    provenance=provenance,
                    backendAttached=(
                        "1" if device_sched.backend is not None else "0"),
                    deviceSolves=str(
                        int(metrics.solve_duration.count())
                        + int(metrics.serving_fast_path_pods.value())))

            def put(obj):
                obj["data"] = data
                return obj

            try:
                await store.guaranteed_update("configmaps", STATUS_KEY, put)
            except NotFound:
                cm = new_object("ConfigMap", "ktpu-sched-status",
                                "kube-system")
                cm["data"] = data
                await store.create("configmaps", cm)
        except asyncio.CancelledError:
            raise
        except StoreError:
            pass  # transient (shard restarting): retry next tick
        await asyncio.sleep(STATUS_PERIOD_S)
