"""The scheduling framework: extension points, Status codes, CycleState.

Parity target: pkg/scheduler/framework/interface.go (`Plugin`,
`PreEnqueuePlugin`, `QueueSortPlugin`, `PreFilterPlugin`, `FilterPlugin`,
`PostFilterPlugin`, `PreScorePlugin`, `ScorePlugin` + `ScoreExtensions`,
`ReservePlugin`, `PermitPlugin`, `PreBindPlugin`, `BindPlugin`,
`PostBindPlugin`; `Status`/`Code`) and framework/runtime/framework.go
(`frameworkImpl.RunFilterPlugins` / `RunScorePlugins` / ... with per-plugin
duration metrics).

The state machine per scheduling attempt (schedule_one.go):

    PreEnqueue -> [queue] -> PreFilter -> Filter -> (PostFilter on failure)
      -> PreScore -> Score -> NormalizeScore -> Reserve -> Permit
      -> [async] WaitOnPermit -> PreBind -> Bind -> PostBind

TPU-first deviation: plugins additionally may expose **batch kernels**
(`filter_batch` / `score_batch`) that compute a whole (P pods × N nodes) mask
or score tensor at once; the TPU backend (ops/solver.py) composes those instead
of the per-(pod,node) methods. A plugin without a batch kernel falls back to
the host path for that extension point — the per-extension-point backend
selection the north star's feature gate demands.
"""

from __future__ import annotations

import asyncio
import contextlib
import random
import time
from typing import Any, Callable, Iterable, Mapping

from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo, Snapshot

#: shared no-op context manager (stateless, safe to re-enter): the
#: disabled-tracer fast path of ep_span costs one attribute check + this.
_NULL_CM = contextlib.nullcontext()

#: share of scheduling cycles whose plugin calls are timed into
#: scheduler_plugin_execution_duration_seconds (upstream's
#: pluginMetricsSamplePercent); the others read no clock
PLUGIN_METRICS_SAMPLE_PERCENT = 10

# --- Status codes (framework.Code) -----------------------------------------

SUCCESS = 0
ERROR = 1
UNSCHEDULABLE = 2
UNSCHEDULABLE_AND_UNRESOLVABLE = 3  # preemption won't help
WAIT = 4   # Permit parked the pod (gang scheduling)
SKIP = 5

MAX_NODE_SCORE = 100
MIN_NODE_SCORE = 0


class Status:
    __slots__ = ("code", "reasons", "plugin")

    def __init__(self, code: int = SUCCESS, reasons: Iterable[str] = (), plugin: str = ""):
        self.code = code
        self.reasons = list(reasons)
        self.plugin = plugin

    @classmethod
    def success(cls) -> "Status":
        return cls(SUCCESS)

    @classmethod
    def unschedulable(cls, *reasons: str, resolvable: bool = True) -> "Status":
        return cls(UNSCHEDULABLE if resolvable else UNSCHEDULABLE_AND_UNRESOLVABLE, reasons)

    @classmethod
    def error(cls, *reasons: str) -> "Status":
        return cls(ERROR, reasons)

    @classmethod
    def skip(cls) -> "Status":
        return cls(SKIP)

    @classmethod
    def wait(cls) -> "Status":
        return cls(WAIT)

    def is_success(self) -> bool:
        return self.code == SUCCESS

    def is_skip(self) -> bool:
        return self.code == SKIP

    def is_wait(self) -> bool:
        return self.code == WAIT

    def is_unschedulable(self) -> bool:
        return self.code in (UNSCHEDULABLE, UNSCHEDULABLE_AND_UNRESOLVABLE)

    def message(self) -> str:
        return "; ".join(self.reasons)

    def with_plugin(self, name: str) -> "Status":
        self.plugin = self.plugin or name
        return self

    def __repr__(self) -> str:
        names = {0: "Success", 1: "Error", 2: "Unschedulable",
                 3: "UnschedulableAndUnresolvable", 4: "Wait", 5: "Skip"}
        return f"Status({names[self.code]}, {self.reasons!r}, plugin={self.plugin!r})"


class CycleState:
    """Per-attempt scratch space (framework/cycle_state.go): plugins stash
    PreFilter/PreScore precomputation under their own keys.

    Only a cycle whose state has `record_plugin_metrics` times its
    plugin calls: `Framework.new_cycle_state` decides it once a cycle,
    as upstream's schedulingCycle does."""

    def __init__(self, record_plugin_metrics: bool = False):
        self._data: dict[str, Any] = {}
        self.skip_filter_plugins: set[str] = set()
        self.skip_score_plugins: set[str] = set()
        self.record_plugin_metrics = record_plugin_metrics

    def write(self, key: str, value: Any) -> None:
        self._data[key] = value

    def read(self, key: str) -> Any:
        return self._data.get(key)

    def clone(self) -> "CycleState":
        cs = CycleState(self.record_plugin_metrics)
        cs._data = dict(self._data)
        cs.skip_filter_plugins = set(self.skip_filter_plugins)
        cs.skip_score_plugins = set(self.skip_score_plugins)
        return cs


# --- Plugin base -----------------------------------------------------------

class Plugin:
    """Base plugin. Subclasses override the extension points they implement
    and declare them in EXTENSION_POINTS. Args come from the per-plugin
    config (KubeSchedulerConfiguration pluginConfig)."""

    NAME = "Plugin"
    EXTENSION_POINTS: tuple[str, ...] = ()

    def __init__(self, args: Mapping | None = None):
        self.args = dict(args or {})

    # PreEnqueue: gate pods out of the active queue entirely.
    def pre_enqueue(self, pod: PodInfo) -> Status:
        return Status.success()

    # QueueSort: less(a, b) ordering for the active queue.
    def less(self, a: PodInfo, b: PodInfo) -> bool:
        raise NotImplementedError

    # PreFilter: per-pod precompute; may narrow candidate nodes or Skip.
    def pre_filter(self, state: CycleState, pod: PodInfo,
                   snapshot: Snapshot) -> Status:
        return Status.success()

    # Filter: feasibility of pod on one node.
    def filter(self, state: CycleState, pod: PodInfo, node: NodeInfo) -> Status:
        return Status.success()

    # PostFilter: runs when no node passed Filter (preemption lives here).
    def post_filter(self, state: CycleState, pod: PodInfo, snapshot: Snapshot,
                    filtered_status: Mapping[str, Status]) -> tuple[str, Status]:
        return "", Status.unschedulable()

    # PreScore
    def pre_score(self, state: CycleState, pod: PodInfo,
                  nodes: list[NodeInfo]) -> Status:
        return Status.success()

    # Score: 0..100 per node.
    def score(self, state: CycleState, pod: PodInfo, node: NodeInfo) -> float:
        return 0.0

    # NormalizeScore (ScoreExtensions): rescale this plugin's raw scores.
    def normalize_scores(self, state: CycleState, pod: PodInfo,
                         scores: dict[str, float]) -> None:
        return None

    # Reserve / Unreserve
    def reserve(self, state: CycleState, pod: PodInfo, node_name: str) -> Status:
        return Status.success()

    def unreserve(self, state: CycleState, pod: PodInfo, node_name: str) -> None:
        return None

    # Permit: may return Wait (gang scheduling parks here) with a timeout.
    def permit(self, state: CycleState, pod: PodInfo,
               node_name: str) -> tuple[Status, float]:
        return Status.success(), 0.0

    # PreBind / Bind / PostBind
    async def pre_bind(self, state: CycleState, pod: PodInfo, node_name: str) -> Status:
        return Status.success()

    async def bind(self, state: CycleState, pod: PodInfo, node_name: str) -> Status:
        return Status.skip()

    def post_bind(self, state: CycleState, pod: PodInfo, node_name: str) -> None:
        return None

    # --- batch kernels (TPU path) -----------------------------------------
    # Implemented by tensorizable plugins; see ops/plugins_tpu.py. Returning
    # NotImplemented routes this plugin through the host path.

    def filter_batch(self, tensors, pods):  # -> (P,N) bool mask or NotImplemented
        return NotImplemented

    def score_batch(self, tensors, pods):  # -> (P,N) float scores or NotImplemented
        return NotImplemented


class EnqueueExtensions:
    """Which cluster events may make a pod schedulable again
    (framework.EnqueueExtensions.EventsToRegister → QueueingHint).
    Event strings: "Node/Add", "Node/Update", "Pod/Delete", "Pod/Add", ..."""

    @staticmethod
    def events_for(plugin: Plugin) -> list[str]:
        return getattr(plugin, "EVENTS", ["Node/Add", "Node/Update", "Pod/Delete"])


# --- Framework runner ------------------------------------------------------

class Framework:
    """frameworkImpl: a configured set of plugins per profile, with
    per-plugin timing on sampled cycles recorded for metrics parity."""

    def __init__(
        self,
        plugins: list[Plugin],
        score_weights: Mapping[str, int] | None = None,
        profile_name: str = "default-scheduler",
        metrics=None,
        disabled: Mapping[str, Iterable[str]] | None = None,
    ):
        self.profile_name = profile_name
        self.plugins = plugins
        self.score_weights = dict(score_weights or {})
        self.metrics = metrics
        #: utils/tracing.Tracer injected by the Scheduler (like metrics):
        #: each extension-point run_* becomes a child span of the attempt
        #: when tracing is on; a None/disabled tracer costs one check.
        self.tracer = None
        #: the draws behind PLUGIN_METRICS_SAMPLE_PERCENT
        self.plugin_metrics_sampler = random.Random(0)
        disabled = {k: set(v) for k, v in (disabled or {}).items()}

        def enabled(point: str) -> list[Plugin]:
            off = disabled.get(point, set()) | disabled.get("*", set())
            return [p for p in plugins
                    if point in p.EXTENSION_POINTS and p.NAME not in off]

        self.pre_enqueue_plugins = enabled("PreEnqueue")
        self.queue_sort_plugins = enabled("QueueSort")
        self.pre_filter_plugins = enabled("PreFilter")
        self.filter_plugins = enabled("Filter")
        self.post_filter_plugins = enabled("PostFilter")
        self.pre_score_plugins = enabled("PreScore")
        self.score_plugins = enabled("Score")
        self.reserve_plugins = enabled("Reserve")
        self.permit_plugins = enabled("Permit")
        self.pre_bind_plugins = enabled("PreBind")
        self.bind_plugins = enabled("Bind")
        self.post_bind_plugins = enabled("PostBind")

    def ep_span(self, point: str):
        """Context manager for one extension point's span (a no-op unless
        the injected tracer is enabled) — the utiltrace step analog at
        span granularity; per-plugin timing stays on the metrics path."""
        t = self.tracer
        if t is not None and t.enabled:
            return t.span(f"framework.{point}", profile=self.profile_name)
        return _NULL_CM

    def _sample(self) -> bool:
        return self.plugin_metrics_sampler.random() * 100 \
            < PLUGIN_METRICS_SAMPLE_PERCENT

    def new_cycle_state(self) -> CycleState:
        """A scheduling cycle's state, its plugin-metrics sampling drawn:
        the plugin calls of an unsampled cycle read no clock."""
        return CycleState(self._sample())

    def _timed(self, record: bool, plugin: Plugin, point: str,
               fn: Callable, *args):
        """fn(*args), timed into the plugin histogram when `record` (the
        cycle was sampled)."""
        if not record or self.metrics is None:
            return fn(*args)
        t0 = time.perf_counter()
        try:
            return fn(*args)
        finally:
            self.metrics.observe_plugin(plugin.NAME, point,
                                        time.perf_counter() - t0)

    # -- queue hooks --

    def run_pre_enqueue(self, pod: PodInfo) -> Status:
        # no cycle yet: one draw per add, as upstream's queue does
        record = bool(self.pre_enqueue_plugins) and self._sample()
        for p in self.pre_enqueue_plugins:
            st = self._timed(record, p, "PreEnqueue", p.pre_enqueue, pod)
            if not st.is_success():
                return st.with_plugin(p.NAME)
        return Status.success()

    def less(self, a: PodInfo, b: PodInfo) -> bool:
        for p in self.queue_sort_plugins:
            return p.less(a, b)
        return a.queued_at < b.queued_at

    # -- scheduling cycle --

    def run_pre_filter(self, state: CycleState, pod: PodInfo,
                       snapshot: Snapshot) -> Status:
        with self.ep_span("PreFilter"):
            for p in self.pre_filter_plugins:
                st = self._timed(state.record_plugin_metrics, p,
                                 "PreFilter", p.pre_filter, state, pod,
                                 snapshot)
                if st.is_skip():
                    state.skip_filter_plugins.add(p.NAME)
                    continue
                if not st.is_success():
                    return st.with_plugin(p.NAME)
            return Status.success()

    def run_filters(self, state: CycleState, pod: PodInfo,
                    node: NodeInfo) -> Status:
        for p in self.filter_plugins:
            if p.NAME in state.skip_filter_plugins:
                continue
            st = self._timed(state.record_plugin_metrics, p, "Filter",
                             p.filter, state, pod, node)
            if not st.is_success():
                return st.with_plugin(p.NAME)
        return Status.success()

    def run_post_filters(self, state: CycleState, pod: PodInfo,
                         snapshot: Snapshot,
                         statuses: Mapping[str, Status]) -> tuple[str, Status]:
        with self.ep_span("PostFilter"):
            for p in self.post_filter_plugins:
                nominated, st = self._timed(
                    state.record_plugin_metrics, p, "PostFilter",
                    p.post_filter, state, pod, snapshot, statuses)
                if st.is_success() or not st.is_unschedulable():
                    return nominated, st.with_plugin(p.NAME)
            return "", Status.unschedulable()

    def run_pre_score(self, state: CycleState, pod: PodInfo,
                      nodes: list[NodeInfo]) -> Status:
        with self.ep_span("PreScore"):
            for p in self.pre_score_plugins:
                st = self._timed(state.record_plugin_metrics, p,
                                 "PreScore", p.pre_score, state, pod, nodes)
                if st.is_skip():
                    state.skip_score_plugins.add(p.NAME)
                    continue
                if not st.is_success():
                    return st.with_plugin(p.NAME)
            return Status.success()

    def run_scores(self, state: CycleState, pod: PodInfo,
                   nodes: list[NodeInfo]) -> dict[str, float]:
        """Weighted sum over score plugins (RunScorePlugins + NormalizeScore +
        plugin weight application)."""
        record = state.record_plugin_metrics
        with self.ep_span("Score"):
            totals = {n.name: 0.0 for n in nodes}
            for p in self.score_plugins:
                if p.NAME in state.skip_score_plugins:
                    continue
                raw = {}
                for n in nodes:
                    raw[n.name] = self._timed(record, p, "Score", p.score,
                                              state, pod, n)
                self._timed(record, p, "NormalizeScore", p.normalize_scores,
                            state, pod, raw)
                w = self.score_weights.get(p.NAME, 1)
                for name, s in raw.items():
                    totals[name] += w * s
            return totals

    # -- reserve / permit / bind --

    def run_reserve(self, state: CycleState, pod: PodInfo, node_name: str) -> Status:
        with self.ep_span("Reserve"):
            done: list[Plugin] = []
            for p in self.reserve_plugins:
                st = self._timed(state.record_plugin_metrics, p,
                                 "Reserve", p.reserve, state, pod, node_name)
                if not st.is_success():
                    for q in done:
                        q.unreserve(state, pod, node_name)
                    return st.with_plugin(p.NAME)
                done.append(p)
            return Status.success()

    def run_unreserve(self, state: CycleState, pod: PodInfo, node_name: str) -> None:
        for p in reversed(self.reserve_plugins):
            self._timed(state.record_plugin_metrics, p, "Unreserve",
                        p.unreserve, state, pod, node_name)

    def run_permit(self, state: CycleState, pod: PodInfo,
                   node_name: str) -> tuple[Status, float]:
        with self.ep_span("Permit"):
            max_timeout = 0.0
            waiting = False
            for p in self.permit_plugins:
                st, timeout = self._timed(
                    state.record_plugin_metrics, p, "Permit", p.permit,
                    state, pod, node_name)
                if st.is_wait():
                    waiting = True
                    max_timeout = max(max_timeout, timeout)
                elif not st.is_success():
                    return st.with_plugin(p.NAME), 0.0
            return (Status.wait(), max_timeout) if waiting \
                else (Status.success(), 0.0)

    async def run_pre_bind(self, state: CycleState, pod: PodInfo,
                           node_name: str) -> Status:
        record = state.record_plugin_metrics and self.metrics is not None
        with self.ep_span("PreBind"):
            for p in self.pre_bind_plugins:
                t0 = time.perf_counter() if record else 0.0
                st = await p.pre_bind(state, pod, node_name)
                if record:
                    self.metrics.observe_plugin(p.NAME, "PreBind",
                                                time.perf_counter() - t0)
                if not st.is_success():
                    return st.with_plugin(p.NAME)
            return Status.success()

    async def run_bind(self, state: CycleState, pod: PodInfo,
                       node_name: str) -> Status:
        record = state.record_plugin_metrics and self.metrics is not None
        with self.ep_span("Bind"):
            for p in self.bind_plugins:
                t0 = time.perf_counter() if record else 0.0
                st = await p.bind(state, pod, node_name)
                if record:
                    self.metrics.observe_plugin(p.NAME, "Bind",
                                                time.perf_counter() - t0)
                if st.is_skip():
                    continue
                return st.with_plugin(p.NAME)
            return Status.error("no bind plugin handled the pod")

    def run_post_bind(self, state: CycleState, pod: PodInfo, node_name: str) -> None:
        with self.ep_span("PostBind"):
            for p in self.post_bind_plugins:
                self._timed(state.record_plugin_metrics, p, "PostBind",
                            p.post_bind, state, pod, node_name)
