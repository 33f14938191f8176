"""Prometheus exposition lint (tier-1): the full default registry must
render valid text format line-by-line — HELP/TYPE pairing, label
escaping, sample-name/metric-name agreement, no duplicate registration
across the WatchMetrics/SchedulerMetrics/APIServerMetrics/audit/policy
register_into paths — plus the Gauge TYPE-line regression and the exact
windowed-percentile recorder.
"""

import asyncio
import math
import re

from kubernetes_tpu.metrics.registry import (
    APIServerMetrics,
    Counter,
    Gauge,
    Histogram,
    Registry,
    SchedulerMetrics,
    WatchMetrics,
    WindowedLatencyRecorder,
)

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_HELP_RE = re.compile(rf"^# HELP ({_NAME})(?: (.*))?$")
_TYPE_RE = re.compile(rf"^# TYPE ({_NAME}) (counter|gauge|histogram|summary)$")
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(?:\{{(.*)\}})? (-?[0-9.e+-]+|NaN|[+-]Inf)$")
_LABEL_RE = re.compile(rf'({_NAME})="((?:[^"\\\n]|\\\\|\\"|\\n)*)"')


def validate_exposition(text: str) -> list[str]:
    """Line-by-line Prometheus text-format check. Returns the metric
    names seen (in order), raising AssertionError with the offending
    line on any violation."""
    seen_types: dict[str, str] = {}
    current: str | None = None
    pending_help: str | None = None
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line:
            continue
        m = _HELP_RE.match(line)
        if m:
            assert pending_help is None, \
                f"line {lineno}: HELP {m.group(1)} follows unpaired HELP"
            pending_help = m.group(1)
            continue
        m = _TYPE_RE.match(line)
        if m:
            name = m.group(1)
            # HELP must immediately precede TYPE for the same metric
            assert pending_help == name, \
                f"line {lineno}: TYPE {name} not preceded by its HELP " \
                f"(got {pending_help!r})"
            pending_help = None
            assert name not in seen_types, \
                f"line {lineno}: duplicate TYPE for {name} " \
                "(double registration)"
            seen_types[name] = m.group(2)
            current = name
            continue
        assert pending_help is None, \
            f"line {lineno}: HELP {pending_help} not followed by TYPE"
        m = _SAMPLE_RE.match(line)
        assert m, f"line {lineno}: unparseable sample line {line!r}"
        sample, labels = m.group(1), m.group(2)
        assert current is not None, \
            f"line {lineno}: sample before any TYPE"
        allowed = {current}
        if seen_types[current] == "histogram":
            allowed = {f"{current}_bucket", f"{current}_sum",
                       f"{current}_count"}
        assert sample in allowed, \
            f"line {lineno}: sample {sample!r} under metric {current!r}"
        if labels:
            # the whole label body must be well-formed pairs (catches
            # unescaped quotes/newlines/backslashes)
            stripped = _LABEL_RE.sub("", labels).replace(",", "")
            assert stripped == "", \
                f"line {lineno}: malformed labels {labels!r}"
    assert pending_help is None, f"dangling HELP {pending_help}"
    return list(seen_types)


class TestGaugeRender:
    def test_type_line_is_gauge_even_when_help_mentions_counter(self):
        """Regression: the old render derived TYPE by replacing the first
        'counter' substring — corrupting the HELP line whenever the help
        text itself contained the word."""
        g = Gauge("queue_depth", "a counter of queued items")
        g.set(3.0)
        out = g.render()
        assert "# HELP queue_depth a counter of queued items" in out
        assert "# TYPE queue_depth gauge" in out
        assert "counter" not in out.splitlines()[1]

    def test_plain_gauge(self):
        g = Gauge("g", "help", labels=("k",))
        g.set(1.5, k="v")
        validate_exposition(g.render())


class TestLabelEscaping:
    def test_quotes_backslashes_newlines_escape(self):
        c = Counter("c_total", "help", labels=("sel",))
        c.inc(sel='app="x",\\tier\nblue')
        out = c.render()
        validate_exposition(out)
        line = out.splitlines()[-1]
        assert '\\"x\\"' in line and "\\\\tier" in line and "\\n" in line
        assert "\n" not in line

    def test_help_newline_escapes(self):
        c = Counter("c_total", "line one\nline two")
        out = c.render()
        assert out.splitlines()[0] == "# HELP c_total line one\\nline two"
        validate_exposition(out)

    def test_histogram_label_escaping(self):
        h = Histogram("h_seconds", "help", labels=("who",))
        h.observe(0.01, who='say "hi"')
        validate_exposition(h.render())


class TestExpositionLint:
    def _full_registry(self) -> Registry:
        """Every register_into path the servers actually compose onto one
        /metrics endpoint."""
        from kubernetes_tpu.policy.audit import AuditSink
        from kubernetes_tpu.policy.vap import PolicyEngine
        from kubernetes_tpu.store import new_cluster_store
        r = Registry()
        sm = SchedulerMetrics(r)
        sm.observe_attempt("scheduled", "default-scheduler", 0.004)
        sm.observe_plugin("NodeResourcesFit", "Filter", 0.0001)
        sm.set_pending({"active": 1, "backoff": 0})
        sm.solve_duration.observe(0.002)
        wm = WatchMetrics()
        wm.events_dispatched.inc()
        wm.register_into(r)
        am = APIServerMetrics()
        am.observe("create", "pods", 201, 0.001)
        am.inc_inflight("create")
        am.dec_inflight("create")
        am.register_into(r)
        sink = AuditSink()
        sink.events_total.inc(stage="ResponseComplete")
        sink.register_into(r)
        store = new_cluster_store()
        engine = PolicyEngine(store)
        engine.register_into(r)
        store.stop()
        # the tracer's self-time ledger (the Scheduler registers it)
        from kubernetes_tpu.utils.tracing import Tracer
        tracer = Tracer(enabled=True)
        with tracer.span("wire.create.pods"):
            pass
        tracer.enabled = False
        tracer.register_into(r)
        return r

    def test_full_default_registry_renders_clean(self):
        names = validate_exposition(self._full_registry().render())
        # the families this PR's contract names must all be present
        for want in ("scheduler_scheduling_attempt_duration_seconds",
                     "scheduler_tpu_solve_seconds",
                     "watch_events_dispatched_total",
                     "apiserver_request_duration_seconds",
                     "apiserver_current_inflight_requests",
                     "audit_events_total",
                     "policy_evaluations_total",
                     "ktpu_host_self_seconds_total",
                     "ktpu_span_wall_seconds_total",
                     "ktpu_span_total",
                     "ktpu_loop_wall_seconds_total",
                     "ktpu_loop_busy_seconds_total",
                     "ktpu_trace_spans_dropped_total"):
            assert want in names, (want, names)

    def test_register_into_is_idempotent(self):
        """Registering the same family twice (both wires share one
        registry) must not duplicate HELP/TYPE blocks."""
        r = self._full_registry()
        WatchMetrics().register_into(r)  # same names, different objects
        am = APIServerMetrics()
        am.register_into(r)
        validate_exposition(r.render())  # duplicate TYPE would assert

    def test_apiserver_metrics_on_both_wires(self):
        """The request-duration family observes from the HTTP middleware
        AND the KTPU wire into one shared instance at /metrics."""
        from kubernetes_tpu.api.types import make_pod
        from kubernetes_tpu.apiserver import APIServer, RemoteStore
        from kubernetes_tpu.apiserver.wire import WireServer, WireStore
        from kubernetes_tpu.store import (
            install_core_validation,
            new_cluster_store,
        )

        async def body():
            backing = new_cluster_store()
            install_core_validation(backing)
            registry = Registry()
            api = APIServer(backing, metrics_registry=registry)
            await api.start()
            wire = WireServer.for_apiserver(api, host="unix:")
            await wire.start()
            rs = RemoteStore(api.url)
            ws = WireStore(wire.target)
            try:
                await rs.create("pods", make_pod("via-http"))
                await ws.create("pods", make_pod("via-wire"))
                await ws.get("pods", "default/via-wire")
                # rendered through the server's /metrics endpoint
                import aiohttp
                async with aiohttp.ClientSession() as s:
                    async with s.get(f"{api.url}/metrics") as resp:
                        text = await resp.text()
            finally:
                await rs.close()
                await ws.close()
                await wire.stop()
                await api.stop()
                backing.stop()
            validate_exposition(text)
            m = api.request_metrics
            assert m.request_duration.count(
                verb="create", resource="pods", code="201") == 2
            assert m.request_duration.count(
                verb="get", resource="pods", code="200") == 1
            # inflight settles back to zero on both kinds
            assert m.inflight.value(request_kind="mutating") == 0
            assert ('apiserver_request_duration_seconds_bucket'
                    in text)
            assert 'apiserver_current_inflight_requests' in text
        asyncio.run(body())


class TestWindowedLatencyRecorder:
    def test_exact_percentiles(self):
        w = WindowedLatencyRecorder(capacity=4096)
        mark = w.mark()
        for i in range(1, 1001):  # 1..1000 ms
            w.observe(i / 1000.0)
        got = w.percentiles_since(mark, (0.50, 0.99, 0.999))
        assert got[0.50] == 0.500   # exact, not a bucket edge
        assert got[0.99] == 0.990
        assert got[0.999] == 0.999

    def test_window_isolation(self):
        """Observations before the mark never leak into the window."""
        w = WindowedLatencyRecorder(capacity=64)
        for _ in range(10):
            w.observe(100.0)  # warmup junk
        mark = w.mark()
        for v in (1.0, 2.0, 3.0):
            w.observe(v)
        got = w.percentiles_since(mark, (0.5, 1.0))
        assert got[0.5] == 2.0
        assert got[1.0] == 3.0

    def test_empty_window_is_nan(self):
        w = WindowedLatencyRecorder()
        got = w.percentiles_since(w.mark(), (0.5, 0.999))
        assert math.isnan(got[0.5]) and math.isnan(got[0.999])

    def test_overflow_keeps_newest_tail(self):
        w = WindowedLatencyRecorder(capacity=8)
        mark = w.mark()
        for i in range(100):
            w.observe(float(i))
        got = w.percentiles_since(mark, (0.0, 1.0))
        # window larger than capacity degrades to the newest 8 values
        assert got[0.0] == 92.0
        assert got[1.0] == 99.0

    def test_rides_observe_attempt(self):
        sm = SchedulerMetrics()
        mark = sm.attempt_window().mark()
        for ms in (1, 2, 3, 4, 5):
            sm.observe_attempt("scheduled", "default-scheduler",
                               ms / 1000.0)
        sm.observe_attempt("unschedulable", "default-scheduler", 9.0)
        got = sm.attempt_window().percentiles_since(mark, (1.0,))
        assert got[1.0] == 0.005  # failures ride their own window
        assert sm.attempt_window("unschedulable").count_since(0) == 1
