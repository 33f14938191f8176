"""End-to-end attempt tracing (SURVEY §5.1): traceparent propagation
across all three wires, threshold-triggered span-tree dumps, and the
Chrome/Perfetto export nesting device-solve chunks under the attempt.
"""

import asyncio
import json
import logging

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.store import install_core_validation, new_cluster_store
from kubernetes_tpu.utils.tracing import (
    DEFAULT_TRACER,
    TRACEPARENT_ANNOTATION,
    Tracer,
    traceparent_of,
)


def run(coro):
    return asyncio.run(coro)


@pytest.fixture
def tracer():
    DEFAULT_TRACER.enabled = True
    DEFAULT_TRACER.clear()
    yield DEFAULT_TRACER
    DEFAULT_TRACER.enabled = False
    DEFAULT_TRACER.clear()


def _span(tracer, name):
    # spans only: the ring also holds the ledger's leaf records (one per
    # executed stretch, same names, no ids)
    matches = [s for s in tracer.spans if s.name == name and s.span_id]
    assert matches, ([s.name for s in tracer.spans], name)
    return matches[-1]


class TestTraceparentPropagation:
    """(a) one traceparent survives each wire's round-trip: the server's
    request span joins the client's trace instead of opening a new one."""

    def test_http_roundtrip(self, tracer):
        async def body():
            from kubernetes_tpu.apiserver import APIServer, RemoteStore
            backing = new_cluster_store()
            install_core_validation(backing)
            srv = APIServer(backing)
            await srv.start()
            rs = RemoteStore(srv.url)
            try:
                with tracer.span("client.create") as root:
                    created = await rs.create("pods", make_pod("p-http"))
            finally:
                await rs.close()
                await srv.stop()
                backing.stop()
            server_span = _span(tracer, "apiserver.create.pods")
            assert server_span.trace_id == root.trace_id
            assert server_span.parent_id == root.span_id
            # the stored pod carries the request's traceparent for the
            # scheduler to parent to (same trace id)
            tp = traceparent_of(created)
            assert tp and root.trace_id in tp
        run(body())

    def test_wire_roundtrip(self, tracer):
        async def body():
            from kubernetes_tpu.apiserver import APIServer
            from kubernetes_tpu.apiserver.wire import WireServer, WireStore
            backing = new_cluster_store()
            install_core_validation(backing)
            api = APIServer(backing)
            await api.start()
            wire = WireServer.for_apiserver(api, host="unix:")
            await wire.start()
            ws = WireStore(wire.target)
            try:
                with tracer.span("client.create") as root:
                    created = await ws.create("pods", make_pod("p-wire"))
            finally:
                await ws.close()
                await wire.stop()
                await api.stop()
                backing.stop()
            server_span = _span(tracer, "wire.create.pods")
            assert server_span.trace_id == root.trace_id
            assert server_span.parent_id == root.span_id
            tp = traceparent_of(created)
            assert tp and root.trace_id in tp
        run(body())

    def test_wire_multi_members_each_join_the_trace(self, tracer):
        """Ops coalesced into one multi frame are still N requests: each
        member's server span parents to ITS caller's span."""
        async def body():
            from kubernetes_tpu.apiserver import APIServer
            from kubernetes_tpu.apiserver.wire import WireServer, WireStore
            backing = new_cluster_store()
            install_core_validation(backing)
            api = APIServer(backing)
            await api.start()
            wire = WireServer.for_apiserver(api, host="unix:")
            await wire.start()
            ws = WireStore(wire.target)
            try:
                await ws.create("nodes", make_node("warm"))  # connect
                with tracer.span("client.batch") as root:
                    # same-tick gather coalesces into one multi frame
                    await asyncio.gather(
                        ws.create("pods", make_pod("m-0")),
                        ws.create("pods", make_pod("m-1")))
            finally:
                await ws.close()
                await wire.stop()
                await api.stop()
                backing.stop()
            members = [s for s in tracer.spans
                       if s.name == "wire.create.pods"
                       and s.trace_id == root.trace_id]
            assert len(members) == 2, [
                (s.name, s.trace_id) for s in tracer.spans]
        run(body())

    def test_malformed_traced_frame_still_gets_a_reply(self, tracer):
        """A traced wrapper carrying a non-string traceparent must
        degrade to an untraced op, not crash span creation outside the
        error-reply path (which would hang the caller's future)."""
        async def body():
            from kubernetes_tpu.apiserver import APIServer
            from kubernetes_tpu.apiserver.wire import WireServer, WireStore
            from kubernetes_tpu.store.mvcc import NotFound
            backing = new_cluster_store()
            install_core_validation(backing)
            api = APIServer(backing)
            await api.start()
            wire = WireServer.for_apiserver(api, host="unix:")
            await wire.start()
            ws = WireStore(wire.target)
            try:
                await ws.create("nodes", make_node("warm"))  # connect
                fut = asyncio.get_event_loop().create_future()
                ws._pending["rx"] = fut
                ws._send(["rx", "traced", 123, "get", "pods",
                          "default/missing"])
                with pytest.raises(NotFound):  # a real reply, not a hang
                    await asyncio.wait_for(fut, 5.0)
            finally:
                await ws.close()
                await wire.stop()
                await api.stop()
                backing.stop()
        run(body())

    def test_grpc_roundtrip(self, tracer):
        async def body():
            from kubernetes_tpu.apiserver.grpc_server import (
                GRPCAPIServer,
                GRPCRemoteStore,
            )
            backing = new_cluster_store()
            install_core_validation(backing)
            srv = GRPCAPIServer(backing)
            await srv.start()
            client = GRPCRemoteStore(srv.target)
            try:
                with tracer.span("client.create") as root:
                    created = await client.create(
                        "pods", make_pod("p-grpc"))
            finally:
                await client.close()
                await srv.stop()
                backing.stop()
            server_span = _span(tracer, "grpc.create.pods")
            assert server_span.trace_id == root.trace_id
            assert server_span.parent_id == root.span_id
            tp = traceparent_of(created)
            assert tp and root.trace_id in tp
        run(body())

    def test_wire_create_parents_scheduler_attempt(self, tracer):
        """The full journey: a create through the KTPU wire parents the
        scheduler's attempt span (via the stamped annotation), which in
        turn holds the queue-wait and extension-point children; the wire
        span is joinable by audit ID."""
        async def body():
            from kubernetes_tpu.apiserver import APIServer
            from kubernetes_tpu.apiserver.wire import WireServer, WireStore
            from kubernetes_tpu.client import InformerFactory
            from kubernetes_tpu.policy import AuditPipeline, AuditPolicy
            from kubernetes_tpu.scheduler import Scheduler
            backing = new_cluster_store()
            install_core_validation(backing)
            audit = AuditPipeline(AuditPolicy.metadata_for_all())
            api = APIServer(backing, audit=audit)
            await api.start()
            wire = WireServer.for_apiserver(api, host="unix:")
            await wire.start()
            ws = WireStore(wire.target)
            sched = Scheduler(ws, seed=3)
            factory = InformerFactory(ws)
            await sched.setup_informers(factory)
            factory.start()
            await factory.wait_for_sync()
            run_task = asyncio.ensure_future(sched.run(batch_size=1))
            try:
                await ws.create("nodes", make_node("n0"))
                with tracer.span("kubectl.create") as root:
                    await ws.create("pods", make_pod("journey"))
                for _ in range(300):
                    p = await ws.get("pods", "default/journey")
                    if p["spec"].get("nodeName"):
                        break
                    await asyncio.sleep(0.02)
                assert p["spec"].get("nodeName") == "n0"
            finally:
                await sched.stop()
                run_task.cancel()
                factory.stop()
                await ws.close()
                await wire.stop()
                await api.stop()
                await audit.close()
                backing.stop()
            wire_span = next(
                s for s in tracer.spans if s.name == "wire.create.pods"
                and s.trace_id == root.trace_id)
            attempt = next(
                s for s in tracer.spans if s.name == "scheduler.attempt"
                and s.attrs.get("pod") == "default/journey")
            # ONE trace: client span → wire request span → attempt span
            assert attempt.trace_id == root.trace_id
            assert attempt.parent_id == wire_span.span_id
            # queue wait + extension points nest under the attempt
            kids = {s.name for s in tracer.spans
                    if s.parent_id == attempt.span_id}
            assert "scheduler.queue.wait" in kids, kids
            assert "framework.PreFilter" in kids, kids
            assert "framework.Filter" in kids, kids
            # audit ↔ trace join: the wire span carries the auditID and
            # the audit event carries the span's traceparent
            audit_id = wire_span.attrs.get("audit_id")
            assert audit_id
            entry = next(e for e in audit.sink.entries
                         if e["auditID"] == audit_id
                         and e["stage"] == "ResponseComplete")
            assert wire_span.trace_id in \
                entry["annotations"]["traceparent"]
        run(body())


class TestThresholdTreeDump:
    """(b) utiltrace semantics for span trees: only roots slower than the
    threshold log their breakdown."""

    def test_fires_above_threshold(self, caplog):
        t = Tracer(enabled=True, threshold_ms=0.0)
        with caplog.at_level(logging.INFO,
                             logger="kubernetes_tpu.utils.tracing"):
            with t.span("attempt", pod="default/p"):
                with t.span("solve"):
                    pass
        t.enabled = False
        assert len(caplog.records) == 1
        msg = caplog.records[0].message
        assert "Span[attempt{pod=default/p}]" in msg
        assert "solve" in msg

    def test_silent_below_threshold(self, caplog):
        t = Tracer(enabled=True, threshold_ms=10_000.0)
        with caplog.at_level(logging.INFO,
                             logger="kubernetes_tpu.utils.tracing"):
            with t.span("attempt"):
                with t.span("solve"):
                    pass
        t.enabled = False
        assert not caplog.records

    def test_child_spans_never_dump(self, caplog):
        """Only ROOTS trigger the dump — a slow child logs once via its
        root, not once per nesting level."""
        t = Tracer(enabled=True, threshold_ms=0.0)
        with caplog.at_level(logging.INFO,
                             logger="kubernetes_tpu.utils.tracing"):
            with t.span("root"):
                with t.span("mid"):
                    with t.span("leaf"):
                        pass
        t.enabled = False
        assert len(caplog.records) == 1

    def test_env_var_default(self, monkeypatch):
        monkeypatch.setenv("KTPU_TRACE_THRESHOLD_MS", "250")
        assert Tracer().threshold_ms == 250.0
        monkeypatch.delenv("KTPU_TRACE_THRESHOLD_MS")
        assert Tracer().threshold_ms is None


class TestPerfettoExport:
    """(c) schema-valid Chrome trace JSON with device-solve chunks nested
    under the scheduling attempt."""

    def test_solve_spans_nest_under_attempt(self, tracer, monkeypatch):
        # This test pins the CHUNKED solve's span nesting
        # (solver.dispatch/solve under the batch attempt); the serving
        # tier would legitimately fast-drain a 4-pod batch through the
        # pinned single-pod solve (which has no chunk spans) — pin it
        # off for the chunk-path assertion.
        monkeypatch.setenv("KTPU_SERVING", "0")

        async def body():
            from kubernetes_tpu.client import InformerFactory
            from kubernetes_tpu.ops import TPUBackend
            from kubernetes_tpu.scheduler import Scheduler
            store = new_cluster_store()
            install_core_validation(store)
            for i in range(2):
                await store.create("nodes", make_node(f"n{i}"))
            # Pods staged BEFORE the loop starts so one pop drains a
            # multi-pod batch through the device backend.
            for i in range(4):
                await store.create("pods", make_pod(f"p{i}"))
            sched = Scheduler(store, seed=7,
                              backend=TPUBackend(max_batch=8))
            factory = InformerFactory(store)
            await sched.setup_informers(factory)
            factory.start()
            await factory.wait_for_sync()
            run_task = asyncio.ensure_future(sched.run(batch_size=8))
            try:
                for _ in range(600):
                    pods = (await store.list("pods")).items
                    if sum(1 for p in pods
                           if p["spec"].get("nodeName")) == 4:
                        break
                    await asyncio.sleep(0.02)
                assert sum(1 for p in pods
                           if p["spec"].get("nodeName")) == 4
            finally:
                await sched.stop()
                run_task.cancel()
                factory.stop()
                store.stop()

            doc = json.loads(tracer.to_perfetto())
            evs = doc["traceEvents"]
            assert evs
            for e in evs:  # Chrome trace-event schema (complete events)
                assert e["ph"] == "X"
                for field in ("name", "pid", "tid", "ts", "dur", "args"):
                    assert field in e, (field, e)
            # pid 1 holds the spans, pid 2 the ledger's leaf records
            # (one track per real thread, no ids)
            assert {e["pid"] for e in evs} == {1, 2}
            leaf_tids = {e["tid"] for e in evs if e["pid"] == 2}
            assert leaf_tids == {int(e["args"]["tid"]) for e in evs
                                 if e["pid"] == 2}
            evs = [e for e in evs if e["pid"] == 1]
            by_span = {e["args"]["span_id"]: e for e in evs}
            solve = next(e for e in evs if e["name"] == "solver.solve")
            # walk the parent chain: the solve chunk must nest under a
            # scheduler.attempt span
            seen = set()
            cur = solve
            while cur is not None and cur["name"] != "scheduler.attempt":
                pid = cur["args"].get("parent_id")
                assert pid and pid not in seen, \
                    (solve, [e["name"] for e in evs])
                seen.add(pid)
                cur = by_span.get(pid)
            assert cur is not None and cur["name"] == "scheduler.attempt"
            # dispatch span rides the same tree
            assert any(e["name"] == "solver.dispatch" for e in evs)
            # binds happened and are attributed to pods for trace_for
            assert any(e["name"] == "scheduler.bind" for e in evs)
        run(body())

    def test_queue_wait_covers_only_current_attempt(self, tracer):
        """A retried pod's queue.wait span starts at its LATEST activeQ
        entry, not first-enqueue — prior cycles and backoff windows must
        not inflate the wait."""
        async def body():
            from kubernetes_tpu.scheduler.framework import Framework
            from kubernetes_tpu.scheduler.queue import SchedulingQueue
            from kubernetes_tpu.scheduler.types import PodInfo
            now = [100.0]
            q = SchedulingQueue(Framework([]), initial_backoff=0.0,
                                clock=lambda: now[0])
            pi = PodInfo(make_pod("retry"))
            await q.add(pi)
            assert pi.enqueued_at == 100.0
            now[0] = 101.0
            (popped,) = await q.pop_batch(1)
            assert popped.dequeued_at == 101.0
            now[0] = 150.0  # a long failed cycle...
            await q.move_to_backoff(pi)
            async with q._cond:
                q._flush_backoff_locked()  # ...then re-activation
            assert pi.enqueued_at == 150.0  # re-stamped, not 100.0
            now[0] = 150.5
            (popped,) = await q.pop_batch(1)
            assert popped.dequeued_at - popped.enqueued_at == 0.5
            await q.close()
        run(body())

    def test_retroactive_record_parents_to_current(self, tracer):
        with tracer.span("attempt") as sp:
            tracer.record("queue.wait", 1.0, 2.0, pod="default/x")
        rec = _span(tracer, "queue.wait")
        assert rec.parent_id == sp.span_id
        assert rec.trace_id == sp.trace_id
        assert abs(rec.duration_ms - 1000.0) < 1e-6
        doc = json.loads(tracer.to_perfetto())
        assert any(e["name"] == "queue.wait" for e in doc["traceEvents"])


class TestDisabledOverhead:
    """Tracing off (the default) must leave no trace artifacts anywhere
    on the path — the <2% bench headline guard's functional half."""

    def test_no_annotation_stamped_when_disabled(self):
        async def body():
            from kubernetes_tpu.apiserver import APIServer, RemoteStore
            backing = new_cluster_store()
            install_core_validation(backing)
            srv = APIServer(backing)
            await srv.start()
            rs = RemoteStore(srv.url)
            try:
                created = await rs.create("pods", make_pod("plain"))
            finally:
                await rs.close()
                await srv.stop()
                backing.stop()
            ann = (created["metadata"].get("annotations") or {})
            assert TRACEPARENT_ANNOTATION not in ann
            assert len(DEFAULT_TRACER.spans) == 0
        assert not DEFAULT_TRACER.enabled
        run(body())


# ---------------------------------------------------------------------------
# the self-time ledger (ISSUE 25)
# ---------------------------------------------------------------------------

import asyncio.events  # noqa: E402
import gc  # noqa: E402
import time  # noqa: E402
import zlib  # noqa: E402
from collections import deque  # noqa: E402

from kubernetes_tpu.metrics.registry import Registry  # noqa: E402
from kubernetes_tpu.utils.tracing import (  # noqa: E402
    GC,
    IDLE,
    OTHER,
    ambient,
    layer_of,
)

_STDLIB_RUN = asyncio.events.Handle._run


def _spin(seconds: float) -> None:
    """Keep the thread busy (not asleep) for `seconds`."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _self(t: Tracer, name: str, thread: str = "loop") -> float:
    return t._families()["ktpu_host_self_seconds_total"].get(
        (layer_of(name), name, thread), 0.0)


def _wall(t: Tracer, name: str) -> float:
    return t._families()["ktpu_span_wall_seconds_total"].get(
        (layer_of(name), name), 0.0)


def _closes(t: Tracer, name: str) -> float:
    return t._families()["ktpu_span_total"].get((layer_of(name), name), 0.0)


def _loop_totals(t: Tracer) -> tuple[float, float]:
    fam = t._families()
    return (sum(v for k, v in fam["ktpu_host_self_seconds_total"].items()
                if k[2] == "loop"),
            fam["ktpu_loop_wall_seconds_total"][()])


@pytest.fixture
def ledger():
    """A tracer of its own, big ring, switched off whatever happens."""
    t = Tracer(max_spans=1 << 16)
    yield t
    t.enabled = False
    assert asyncio.events.Handle._run is _STDLIB_RUN


async def _three_tasks(t: Tracer) -> None:
    async def a():
        with t.span("toy.a"):
            _spin(0.03)
            await asyncio.sleep(0.08)

    async def b():
        with t.span("toy.b"):
            _spin(0.02)
            with t.span("toy.b.inner"):
                _spin(0.01)
            await asyncio.sleep(0.02)
            _spin(0.01)

    async def waiter():
        with t.span("toy.waiter"):
            await asyncio.sleep(0.1)

    await asyncio.gather(a(), b(), waiter())


class TestSelfTimeLedger:
    def test_interleaved_tasks_get_their_own_self_time(self, ledger):
        ledger.enabled = True
        run(_three_tasks(ledger))
        ledger.enabled = False
        # each span is charged what ITS task ran, however they interleave
        assert 0.028 <= _self(ledger, "toy.a") <= 0.06
        assert 0.028 <= _self(ledger, "toy.b") <= 0.06
        # the nested sync span takes its stretch out of its parent's
        assert 0.009 <= _self(ledger, "toy.b.inner") <= 0.03
        # wall runs across the awaits
        assert _wall(ledger, "toy.a") >= 0.10
        assert _closes(ledger, "toy.a") == 1

    def test_an_awaiting_task_is_charged_nothing(self, ledger):
        ledger.enabled = True
        run(_three_tasks(ledger))
        ledger.enabled = False
        assert _wall(ledger, "toy.waiter") >= 0.099
        assert _self(ledger, "toy.waiter") < 0.01

    def test_closure_self_times_sum_to_the_loops_wall(self, ledger):
        ledger.enabled = True
        t0 = time.monotonic()
        run(_three_tasks(ledger))
        ledger.enabled = False
        elapsed = time.monotonic() - t0
        total, wall = _loop_totals(ledger)
        assert wall == pytest.approx(elapsed, rel=0.02)
        assert total == pytest.approx(wall, rel=0.02)
        # asleep between callbacks, and asyncio.run's own set-up and
        # tear-down callbacks, are named too
        assert _self(ledger, IDLE) > 0.02
        assert _self(ledger, OTHER) > 0.0
        busy = ledger._families()["ktpu_loop_busy_seconds_total"][()]
        assert busy == pytest.approx(wall - _self(ledger, IDLE), abs=1e-9)

    def test_the_hook_is_there_only_while_tracing_is_on(self, ledger):
        assert asyncio.events.Handle._run is _STDLIB_RUN
        ledger.enabled = True
        assert asyncio.events.Handle._run is not _STDLIB_RUN
        ledger.enabled = False
        assert asyncio.events.Handle._run is _STDLIB_RUN

    def test_the_hook_is_removed_after_an_exception_in_the_window(
            self, ledger):
        async def boom():
            loop = asyncio.get_running_loop()
            loop.set_exception_handler(lambda *_: None)
            loop.call_soon(lambda: 1 / 0)     # a callback that raises
            await asyncio.sleep(0.01)
            with ledger.span("toy.boom"):
                raise RuntimeError("inside a span")

        with pytest.raises(RuntimeError):
            try:
                ledger.enabled = True
                run(boom())
            finally:
                ledger.enabled = False
        assert asyncio.events.Handle._run is _STDLIB_RUN
        assert ledger._on_gc not in gc.callbacks
        # the span that the exception left is closed and counted
        assert _closes(ledger, "toy.boom") == 1
        total, wall = _loop_totals(ledger)
        assert total == pytest.approx(wall, rel=0.02)

    def test_a_worker_threads_span_is_charged_to_the_worker(self, ledger):
        def fetch():
            with ledger.span("toy.fetch"):
                _spin(0.08)

        async def body():
            with ledger.span("toy.caller"):
                await asyncio.to_thread(fetch)

        ledger.enabled = True
        run(body())
        ledger.enabled = False
        assert 0.079 <= _self(ledger, "toy.fetch", "worker") <= 0.12
        assert _self(ledger, "toy.fetch", "loop") == 0.0
        # the caller awaited: the worker's 80 ms are not the loop's (what
        # it is charged is starting the worker thread)
        assert _self(ledger, "toy.caller", "loop") < 0.04
        # and the worker is charged nothing once its span is left, though
        # its copied context still names the caller's open span
        assert _self(ledger, "toy.caller", "worker") == 0.0

    def test_the_collector_has_its_own_name(self, ledger):
        async def body():
            junk = [[i] for i in range(50_000)]
            with ledger.span("toy.collect"):
                gc.collect()
            return len(junk)

        ledger.enabled = True
        run(body())
        ledger.enabled = False
        assert _self(ledger, GC) > 0.0
        assert layer_of(GC) == "gc"
        total, wall = _loop_totals(ledger)
        assert total == pytest.approx(wall, rel=0.02)

    def test_the_harness_sequence_yields_leaves_that_tile_the_stretch(
            self, ledger):
        """benchmark/lib/harness._Profile, step for step: a new ring by
        assignment, on, work, off, read (name, start, end), clear — all
        from inside the running loop."""
        got = {}

        async def body():
            await asyncio.sleep(0.01)              # untraced prelude
            ledger.spans = deque(maxlen=1 << 16)
            ledger.enabled = True
            got["on"] = time.monotonic()
            await _three_tasks(ledger)
            ledger.enabled = False
            got["off"] = time.monotonic()
            got["records"] = [(s.name, s.start, s.end, s.attrs)
                              for s in ledger.spans if s.end is not None]
            ledger.spans.clear()

        run(body())
        leaves = sorted((s, e, n) for n, s, e, attrs in got["records"]
                        if attrs.get("thread") == "loop")
        assert leaves
        assert leaves[0][0] == pytest.approx(got["on"], abs=2e-3)
        assert leaves[-1][1] == pytest.approx(got["off"], abs=2e-3)
        for (_, end, _), (start, _, _) in zip(leaves, leaves[1:]):
            assert start == end          # no hole and no overlap
        covered = sum(e - s for s, e, _ in leaves)
        assert covered == pytest.approx(got["off"] - got["on"], rel=0.02)
        # the benchmark's unchanged rule (each instant to the latest-started
        # record still open) over the whole stretch as one idle gap now
        # says what the THREAD ran: it agrees with the ledger's self-times
        # (leaves are coarse below 10 us), and the task that only waited
        # gets nothing though its span was open all along
        from benchmark.lib.trace_reduce import attribute_gaps
        ruled = attribute_gaps(
            [(leaves[0][0], leaves[-1][1])],
            [(n, s, e) for n, s, e, _ in got["records"]])
        for name in ("toy.a", "toy.b", "toy.b.inner", IDLE):
            assert ruled[name] == pytest.approx(
                _self(ledger, name), abs=1e-3), name
        assert ruled[IDLE] > 0.02 and ruled["toy.a"] > 0.025
        assert ruled.get("toy.waiter", 0.0) < 0.01
        assert "host.other" not in ruled

    def test_a_section_is_charged_but_leaves_no_record(self, ledger):
        async def body():
            with ledger.span("toy.outer"):
                _spin(0.005)
                with ledger.section("toy.hot"):
                    _spin(0.01)
                _spin(0.005)

        ledger.enabled = True
        run(body())
        ledger.enabled = False
        assert 0.0095 <= _self(ledger, "toy.hot") <= 0.03
        assert _closes(ledger, "toy.hot") == 1
        assert _wall(ledger, "toy.hot") == pytest.approx(
            _self(ledger, "toy.hot"), abs=1e-4)
        # its stretch came out of the enclosing span's self-time
        assert 0.0095 <= _self(ledger, "toy.outer") <= 0.03
        assert not [s for s in ledger.spans if s.name == "toy.hot"]
        assert ledger.section("toy.hot") is ledger.span("toy.outer")  # off

    def test_nothing_is_appended_once_tracing_is_off(self, ledger):
        """The harness switches tracing off and walks the ring while a
        worker thread still sits inside `solver.solve`: the worker's late
        exit must settle its time without touching the ring."""
        import threading
        inside, release = threading.Event(), threading.Event()

        def fetch():
            with ledger.span("toy.fetch"):
                inside.set()
                release.wait(5)

        async def body():
            fut = asyncio.ensure_future(asyncio.to_thread(fetch))
            while not inside.is_set():
                await asyncio.sleep(0.001)
            ledger.enabled = False
            ring = ledger.spans
            before = list(ring)
            release.set()
            await fut
            assert list(ring) == before

        ledger.enabled = True
        run(body())
        # the worker's stretch up to the switch-off is charged all the same
        assert _self(ledger, "toy.fetch", "worker") > 0.0
        assert _closes(ledger, "toy.fetch") == 1

    def test_a_cut_keeps_a_retroactive_record_from_beating_the_leaf(
            self, ledger):
        """A record whose start lies in the past began inside some leaf;
        the reader's rule would give it the rest of that leaf. Whoever
        stamps such a start (the scheduling queue) cuts the leaf there."""
        got = {}

        async def body():
            _spin(0.002)
            stamped = time.monotonic()      # e.g. enqueued_at
            ledger.cut()
            _spin(0.01)                     # same callback, same name
            await asyncio.sleep(0.003)
            ledger.record("toy.wait", stamped, time.monotonic())
            got["lo"] = stamped

        ledger.enabled = True
        run(body())
        ledger.enabled = False
        from benchmark.lib.trace_reduce import attribute_gaps
        records = [(s.name, s.start, s.end) for s in ledger.spans
                   if s.end is not None]
        ruled = attribute_gaps([(got["lo"], got["lo"] + 0.012)], records)
        assert ruled.get("toy.wait", 0.0) < 1e-4, dict(ruled)
        assert _wall(ledger, "toy.wait") > 0.012

    def test_what_the_ring_drops_is_counted(self):
        t = Tracer(max_spans=8)
        t.enabled = True
        for _ in range(20):
            with t.span("toy.x"):
                pass
        t.enabled = False
        dropped = t._families()["ktpu_trace_spans_dropped_total"][()]
        assert len(t.spans) == 8
        assert dropped >= 20 - 8

    def test_a_disabled_span_is_the_shared_no_op(self):
        t = Tracer()
        assert t.span("a") is t.span("b", pod="x")
        with t.span("a") as sp:
            assert sp is None
        assert len(t.spans) == 0

    def test_off_means_off(self):
        t = Tracer()
        reg = Registry()
        t.register_into(reg)
        t.enabled = True
        run(_three_tasks(t))
        t.enabled = False
        before = reg.render()
        assert 'ktpu_span_total{layer="toy",span="toy.a"} 1' in before
        assert asyncio.events.Handle._run is _STDLIB_RUN
        assert t._on_gc not in gc.callbacks
        run(_three_tasks(t))        # the same work, tracing off
        gc.collect()
        assert reg.render() == before
        assert len([s for s in t.spans if s.end is None]) == 0

    def test_a_retroactive_record_has_wall_and_count_but_no_self(
            self, ledger):
        ledger.enabled = True
        now = time.monotonic()
        ledger.record("toy.wait", now - 1.0, now)
        ledger.enabled = False
        assert _closes(ledger, "toy.wait") == 1
        assert _wall(ledger, "toy.wait") == pytest.approx(1.0)
        assert _self(ledger, "toy.wait") == 0.0
        assert _self(ledger, "toy.wait", "worker") == 0.0

    def test_ambient_names_a_task_that_started_before_tracing(self, ledger):
        async def reflector(stop):
            while not stop.is_set():
                _spin(0.002)
                await asyncio.sleep(0.002)

        async def nameless(stop):
            while not stop.is_set():
                _spin(0.001)
                await asyncio.sleep(0.002)

        async def body():
            stop = asyncio.Event()
            with ambient("informer.toys"):
                named = asyncio.ensure_future(reflector(stop))
            loose = asyncio.ensure_future(nameless(stop))
            await asyncio.sleep(0.01)
            ledger.enabled = True           # both tasks are already running
            await asyncio.sleep(0.08)
            ledger.enabled = False
            stop.set()
            await asyncio.gather(named, loose)

        run(body())
        assert _self(ledger, "informer.toys") > 0.02
        assert layer_of("informer.toys") == "informer"
        # the other task's time is unattributed, and listed by what ran
        assert _self(ledger, OTHER) > 0.01
        top = dict(ledger.unattributed())
        assert any("nameless" in name for name in top), top
        assert not any("reflector" in name for name in top), top

    def test_perfetto_tracks_do_not_depend_on_the_hash_seed(self, ledger):
        ledger.enabled = True
        run(_three_tasks(ledger))
        ledger.enabled = False
        spans = {s.span_id: s for s in ledger.spans if s.span_id}
        evs = json.loads(ledger.to_perfetto())["traceEvents"]
        for e in evs:
            if e["pid"] == 1:
                trace_id = spans[e["args"]["span_id"]].trace_id
                assert e["tid"] == zlib.crc32(trace_id.encode()) % 100_000
            else:
                assert e["args"]["thread"] in ("loop", "worker")
                assert "span_id" not in e["args"]

    def test_the_last_tracer_switched_on_sees_the_loop(self):
        first, second = Tracer(), Tracer()
        first.enabled = True
        second.enabled = True
        run(_three_tasks(second))
        second.enabled = False
        assert asyncio.events.Handle._run is _STDLIB_RUN
        first.enabled = False
        assert asyncio.events.Handle._run is _STDLIB_RUN
        assert _self(second, IDLE) > 0.0 and _self(first, IDLE) == 0.0


@pytest.mark.parametrize("name,layer", [
    ("wire.create.pods", "wire"), ("wire.multi", "wire"),
    ("wire.decode", "wire"), ("wire.client.recv", "wire"),
    ("wire.watch.pods", "wire"), ("admission.admit", "wire"),
    ("apiserver.create.pods", "wire"), ("grpc.create.pods", "wire"),
    ("wire.create.events", "events"), ("store.create.events", "events"),
    ("store.commit.events", "events"), ("events.record", "events"),
    ("events.flush", "events"),
    ("store.create.pods", "store"), ("store.update.pods", "store"),
    ("store.commit.pods", "store"), ("store.cacher.pods", "store"),
    ("store.fanout.pods", "store"), ("store.subresource.binding", "store"),
    ("informer.pods", "informer"), ("informer.nodes", "informer"),
    ("scheduler.queue.wait", "queue"), ("scheduler.loop", "queue"),
    ("scheduler.attempt", "attempt"), ("scheduler.snapshot", "attempt"),
    ("scheduler.assume", "attempt"), ("framework.Reserve", "attempt"),
    ("framework.Permit", "attempt"), ("solver.tensors", "attempt"),
    ("solver.prep", "attempt"), ("solver.dispatch", "attempt"),
    ("solver.solve", "attempt"), ("solver.verify", "attempt"),
    ("solver.fast", "attempt"),
    ("scheduler.bind", "bind"), ("framework.PreBind", "bind"),
    ("framework.Bind", "bind"), ("framework.PostBind", "bind"),
    ("host.gc", "gc"), ("loop.idle", "idle"), ("loop.other", "other"),
    ("agent.mark_running", "agent"), ("kubectl.create", "kubectl"),
])
def test_one_table_decides_a_spans_layer(name, layer):
    assert layer_of(name) == layer
