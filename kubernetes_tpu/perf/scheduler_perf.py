"""scheduler_perf: the reference's scale benchmark harness, YAML-compatible.

Parity target: test/integration/scheduler_perf/ (scheduler_perf.go,
config/performance-config.yaml — SURVEY §3.5). Same trick: in-process
control plane, **no kubelets** — Node objects are data, pods "run" because
nothing contradicts Bind. Same workload YAML shape:

    - name: SchedulingBasic
      workloadTemplate:
      - opcode: createNodes
        countParam: $initNodes
        nodeTemplate: {...}            # inline instead of nodeTemplatePath
      - opcode: createPods
        countParam: $initPods
        podTemplate: {...}
      - opcode: createPods
        countParam: $measurePods
        collectMetrics: true           # the measured phase
      - opcode: barrier                # wait until all created pods scheduled
      workloads:
      - name: 100Nodes
        params: {initNodes: 100, initPods: 500, measurePods: 1000}

Opcodes: createNodes, createPods, barrier, sleep, churn (delete/recreate a
slice of pods for queue pressure), startAgents (N in-process NodeAgents —
hollow kubelets with field-selector pod watches — register their own
Nodes in place of kwok-style data staging, so the run carries the
control-plane cost of N watch consumers + mark-Running writes + lease
heartbeats), relistStorm (every started agent tears down its watch and
cold-start relists AT ONCE — the watch-cache tier's measured scenario:
N reads of one shared snapshot instead of N store scans), churnOpenLoop
(the ChurnDay battery, perf/churn: a TIMED open-loop arrival window —
seeded Poisson/burst/ramp pod arrivals on an absolute clock with an
optional deterministic fault timeline injected mid-wave; saturation
shows up as queue growth, the exact p50/p99/p999 attempt percentiles
are the headline, and disruptive faults report time-to-recovery).
Metrics collected over the measured phase:
SchedulingThroughput (pods/s), scheduling_attempt_duration percentiles
(p50/p90/p99 from the scheduler's own histogram — SURVEY §5.5 names),
node fragmentation % (mean free-capacity fraction; the bin-packing
quality metric BASELINE tracks), and the backend's device-residency
counters (host_fallback_pods / spread_poisoned_pods).
"""

from __future__ import annotations

import asyncio
import copy
import json
import sys
import time
from typing import Any, Mapping

from kubernetes_tpu.api.meta import namespaced_name
from kubernetes_tpu.api.types import (make_node, make_pod,
                                      split_node_topology)
from kubernetes_tpu.client import InformerFactory
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.store import install_core_validation, new_cluster_store


def _subst(value: Any, params: Mapping[str, Any]) -> Any:
    """$param substitution (countParam etc.)."""
    if isinstance(value, str) and value.startswith("$"):
        return params[value[1:]]
    return value


def _resolve_count(op: Mapping, params: Mapping[str, Any]) -> int:
    if "countParam" in op:
        return int(_subst(op["countParam"], params))
    return int(op.get("count", 0))


class WorkloadResult:
    def __init__(self):
        self.throughput = 0.0          # pods/s over the measured phase
        self.measured_pods = 0
        #: time.monotonic() at the start of the measured window (for
        #: callers that place their own events inside or outside it).
        self.measured_start = 0.0
        self.measured_seconds = 0.0
        self.attempt_p50 = 0.0
        self.attempt_p90 = 0.0
        self.attempt_p99 = 0.0
        #: exact-only (the 16-bucket histogram cannot resolve it): true
        #: p999 attempt latency over the measured window — the ROADMAP #3
        #: churn-battery headline percentile.
        self.attempt_p999 = float("nan")
        #: True when p50/p90/p99/p999 came from the exact windowed
        #: recorder (raw order statistics) rather than bucket edges.
        self.attempt_percentiles_exact = False
        self.fragmentation_pct = 0.0
        self.scheduled_total = 0
        self.unschedulable_total = 0
        #: DropIfChannelFull accounting (bounded event broadcaster): a
        #: burst silently shedding most of its "Scheduled" events is a
        #: result property, not stderr noise.
        self.events_emitted_total = 0
        self.events_dropped_total = 0
        #: Device-residency accounting over the measured phase (TPU
        #: backend degradation counters): pods that took per-pod host
        #: plugin rows, and spread pods that missed the union scan table.
        #: A residency regression shows up HERE per run, not just in a
        #: stderr warning.
        self.host_fallback_pods = 0
        self.spread_poisoned_pods = 0
        #: Watch-dispatch efficiency over the measured phase (the store's
        #: interned selector index — metrics/registry.py WatchMetrics):
        #: deliveries vs predicate evaluations. checks staying O(events)
        #: while watcher count grows is the index working; a regression
        #: to O(events × watchers) shows up here as data.
        self.watch_events_dispatched_total = 0
        self.watch_predicate_checks_total = 0
        #: Watch-cache serving-tier accounting over the measured phase
        #: (store/cacher.py): LIST/watch-establishment requests served
        #: from the RV-snapshotted cache vs handed to the mvcc core. A
        #: relist storm that stays all-hits is the tier working.
        self.watch_cache_hits_total = 0
        self.watch_cache_misses_total = 0
        #: relistStorm opcode results: wall time for every agent to tear
        #: down its watch, LIST (off the shared snapshot) and re-watch at
        #: once, plus the storm's own cache hit/miss deltas.
        self.relist_storm_agents = 0
        self.relist_storm_seconds = 0.0
        self.relist_storm_cache_hits = 0
        self.relist_storm_cache_misses = 0
        #: Policy-chain accounting over the measured phase
        #: (policy/vap.py + policy/audit.py): expression evaluations and
        #: audit stage events. A policy-chain regression (policies
        #: silently not evaluating, audit silently shedding) is DATA in
        #: the detail JSON, not stderr noise. The index triple is the
        #: O(matching) dispatch witness: hits = candidates served from
        #: the (resource, operation) exact map, residue = wildcard
        #: entries still scanned linearly, rebuilds = invalidations that
        #: actually cost a rebuild. Audit drops ride the same drop
        #: accounting the event recorder reports.
        self.policy_evaluations_total = 0
        self.audit_events_total = 0
        self.audit_events_dropped_total = 0
        self.policy_index_hits_total = 0
        self.policy_index_residue_scans_total = 0
        self.policy_index_rebuilds_total = 0
        #: Solve-side accounting over the measured phase (the r8 50k
        #: profile's 98%-idle blind spot made data): chunk count and
        #: total device-solve wall (the fused solve as the consumer sees
        #: it — scheduler_tpu_solve_seconds), the per-step scan width of
        #: the last chunk (K + P when the shortlist prunes, N when not),
        #: and the shortlist's exactness-fallback counters.
        self.solver_solve_chunks = 0
        self.solver_solve_seconds_total = 0.0
        self.solver_scan_width = 0
        self.solver_shortlist_pods_total = 0
        self.solver_shortlist_fallbacks_total = 0
        #: Block-index accounting (ISSUE 20): (class, block) pairs the
        #: bound scan walked vs proved losers over the measured phase —
        #: the prune rate is the sublinearity witness the 200k/1m rows
        #: report next to solver_solve_seconds_total.
        self.solver_blocks_scanned_total = 0
        self.solver_blocks_pruned_total = 0
        #: Wavefront-solve accounting over the measured phase (r18): the
        #: wave width of the latest chunk and the speculative-commit vs
        #: serial-replay split — the replay fraction the AdaptiveTuner's
        #: width policy keys on is recorded per run, not inferred.
        self.solver_wave_width = 0
        self.solver_wave_commits_total = 0
        self.solver_wave_replays_total = 0
        #: The solve-backend provenance row (jax platform, device count,
        #: carry donation) stamped per family so a CPU pre-flight row is
        #: never mistaken for a chip row.
        self.solve_provenance: dict = {}
        #: Device-loss accounting over the WHOLE run (warm-up included —
        #: a backend that failed there and recovered still failed):
        #: batches the backend raised on and the host path scheduled
        #: (schedule_attempts{result="backend_fallback"}), fast-path
        #: solves/warm-ups that raised, and whether the backend was
        #: still attached at the end (False = the circuit opened; None =
        #: the run never had one). `device_run_failures` reads these.
        #: host_path_pods: pods a scheduler WITH a backend still placed
        #: plugin by plugin (backend_degradations{kind="host_path"}) —
        #: by design for a lone pod the fast path cannot take, so
        #: reported, and only chip_smoke.py's accounting gates on it.
        #: All three come from SchedulerMetrics.device_loss_counts.
        self.host_path_pods = 0
        self.backend_fallback_total = 0
        self.fast_path_failures_total = 0
        self.backend_attached: bool | None = None
        #: Class-dictionary device-plane accounting over the measured
        #: phase (r14): host-side chunk-prep wall (the prep-vs-solve
        #: split per family), equivalence classes behind the latest
        #: chunk's planes, plane payload bytes actually uploaded, and
        #: pods that rode a per-pod fallback after class overflow.
        self.prep_seconds_total = 0.0
        self.plane_classes_per_chunk = 0
        self.plane_bytes_uploaded_total = 0
        self.class_split_fallback_pods = 0
        #: Sharded-control-plane accounting (ROADMAP #5): the run's
        #: shard count (1 = classic single store), per-shard host-prep
        #: rebuilds over the measured phase (the incremental path keeps
        #: this at dirty-shards-only), the solve wall attributed to the
        #: sharded path, and the top-level cross-shard argmax steps.
        self.shard_count = 1
        self.shard_tensor_rebuilds_total = 0
        self.shard_solve_seconds = 0.0
        self.cross_shard_reductions_total = 0
        #: Multi-process control plane accounting (r22 tentpole): OS
        #: processes behind the run (1 = the classic in-process tree —
        #: the structural-degrade witness), WAL appends / replayed
        #: entries / fsync wall summed across the shard apiserver
        #: processes, and scheduler leader elections observed (1 = the
        #: initial acquisition; >1 means a failover happened mid-run).
        self.process_count = 1
        self.wal_appends_total = 0
        self.wal_replay_entries_total = 0
        self.wal_fsync_seconds_total = 0.0
        self.leader_elections_total = 0
        #: Serving-tier accounting over the measured phase
        #: (kubernetes_tpu/serving, ROADMAP #3): lone pods placed
        #: through the pinned C=1 fast path, dispatches whose admission
        #: window merged extra pods, resident device-plane refreshes
        #: (count + wall of the O(changed) scatter), and the admission
        #: window the tier last applied. Zeros under KTPU_SERVING=0 —
        #: the structural-degrade witness.
        self.serving_fast_path_pods_total = 0
        self.serving_coalesced_batches_total = 0
        self.resident_plane_refreshes_total = 0
        self.resident_plane_refresh_seconds_total = 0.0
        self.admission_window_ms = 0.0
        #: startAgents opcode wall (the cold-start fleet boot measured
        #: by the agent-batching satellite; 0.0 when no agents started).
        self.agent_start_seconds = 0.0
        #: createNodes opcode wall — data staging for the node objects
        #: (plus their topology/DRA satellites). Staged in concurrent
        #: 512-wide windows like createPods; at the 1m preset the old
        #: serial awaits were a double-digit-minute pre-measurement
        #: wall the detail JSON never showed.
        self.staging_seconds = 0.0
        #: ChurnDay open-loop battery (perf/churn): the measured phase
        #: is a TIMED arrival-process window, not a drained bulk —
        #: offered vs achieved rate proves the loop stayed open,
        #: backlog growth is the saturation witness (the knee signal),
        #: and the exact attempt percentiles above are the headline.
        self.churn_offered_rate = 0.0
        self.churn_achieved_rate = 0.0
        self.churn_arrival_model = ""
        self.churn_arrivals_total = 0
        self.churn_duration_s = 0.0
        self.churn_backlog_peak = 0
        self.churn_backlog_final = 0
        self.churn_pending_final: dict[str, int] = {}
        #: None = no churn phase ran; else the is_saturated verdict.
        self.churn_saturated: bool | None = None
        #: open-loop honesty counters: arrivals fired >50ms late, and
        #: creates the transport backstop forced to serialize.
        self.churn_late_arrivals = 0
        self.churn_throttled_creates = 0
        self.churn_create_errors = 0
        self.churn_create_drain_s = 0.0
        #: fault-injection records (timeline order) + per-kind counts +
        #: the worst measured time-to-recovery.
        self.churn_faults: list[dict] = []
        self.churn_faults_injected: dict[str, int] = {}
        self.churn_recovery_seconds_max: float | None = None
        #: r20 global-assignment accounting: OCCUPIED-node fragmentation
        #: (the optimizable packing metric — the all-nodes figure above
        #: is placement-invariant once every pod places), optimal-mode
        #: solve vs greedy-degrade chunk counts over the measured phase,
        #: and the ChurnDay rebalance family's outputs — a
        #: [t_s, frag_pct, frag_occupied_pct] curve sampled through the
        #: churn window, descheduler evict-and-replace moves, and the
        #: post-churn backlog-drain recovery wall (descheduler runs
        #: only).
        self.fragmentation_occupied_pct = 0.0
        self.solver_optimal_solves_total = 0
        self.solver_optimal_fallbacks_total = 0
        self.churn_fragmentation_curve: list[list[float]] = []
        self.churn_descheduler_evictions = 0
        self.churn_rebalance_recovery_s: float | None = None
        #: Topology-slice accounting (topology/): slice-shaped gangs
        #: Permit released as one contiguous sub-mesh over the measured
        #: phase, the slice-fragmentation gauge after the last plan
        #: (free cells covered by NO feasible placement of that shape),
        #: and coordinate-plane rebuilds (reuse does not count — a
        #: stable node set should rebuild once, not per chunk).
        self.slice_gangs_bound_total = 0
        self.slice_fragmentation_pct = 0.0
        self.topology_plane_rebuilds_total = 0

    def as_dict(self) -> dict:
        import math

        def ms(v: float):
            return None if math.isnan(v) else round(v * 1e3, 3)

        return {
            "throughput_pods_per_sec": round(self.throughput, 2),
            "measured_pods": self.measured_pods,
            "measured_seconds": round(self.measured_seconds, 3),
            "attempt_p50_ms": ms(self.attempt_p50),
            "attempt_p90_ms": ms(self.attempt_p90),
            "attempt_p99_ms": ms(self.attempt_p99),
            "attempt_p999_ms": ms(self.attempt_p999),
            "attempt_percentiles_exact": self.attempt_percentiles_exact,
            "fragmentation_pct": round(self.fragmentation_pct, 2),
            "fragmentation_occupied_pct": round(
                self.fragmentation_occupied_pct, 2),
            "scheduled_total": self.scheduled_total,
            "unschedulable_total": self.unschedulable_total,
            "events_dropped_total": self.events_dropped_total,
            "events_dropped_pct": round(
                100.0 * self.events_dropped_total
                / self.events_emitted_total, 2)
            if self.events_emitted_total else 0.0,
            "host_fallback_pods": self.host_fallback_pods,
            "spread_poisoned_pods": self.spread_poisoned_pods,
            "watch_events_dispatched_total":
                self.watch_events_dispatched_total,
            "watch_predicate_checks_total":
                self.watch_predicate_checks_total,
            "watch_cache_hits_total": self.watch_cache_hits_total,
            "watch_cache_misses_total": self.watch_cache_misses_total,
            "relist_storm_agents": self.relist_storm_agents,
            "relist_storm_seconds": round(self.relist_storm_seconds, 3),
            "relist_storm_cache_hits": self.relist_storm_cache_hits,
            "relist_storm_cache_misses": self.relist_storm_cache_misses,
            "policy_evaluations_total": self.policy_evaluations_total,
            "audit_events_total": self.audit_events_total,
            "audit_events_dropped_total":
                self.audit_events_dropped_total,
            "policy_index_hits_total": self.policy_index_hits_total,
            "policy_index_residue_scans_total":
                self.policy_index_residue_scans_total,
            "policy_index_rebuilds_total":
                self.policy_index_rebuilds_total,
            "solver_solve_chunks": self.solver_solve_chunks,
            "solver_solve_seconds_total": round(
                self.solver_solve_seconds_total, 3),
            "solver_scan_width": self.solver_scan_width,
            "solver_shortlist_fallbacks_total":
                self.solver_shortlist_fallbacks_total,
            "solver_blocks_scanned_total":
                self.solver_blocks_scanned_total,
            "solver_blocks_pruned_total":
                self.solver_blocks_pruned_total,
            "solver_shortlist_hit_pct": round(
                100.0 * (1.0 - self.solver_shortlist_fallbacks_total
                         / self.solver_shortlist_pods_total), 2)
            if self.solver_shortlist_pods_total else None,
            "solver_wave_width": self.solver_wave_width,
            "solver_wave_commits_total": self.solver_wave_commits_total,
            "solver_wave_replays_total": self.solver_wave_replays_total,
            "solver_wave_replay_pct": round(
                100.0 * self.solver_wave_replays_total
                / (self.solver_wave_commits_total
                   + self.solver_wave_replays_total), 2)
            if (self.solver_wave_commits_total
                + self.solver_wave_replays_total) else None,
            "solve_provenance": self.solve_provenance,
            "host_path_pods": self.host_path_pods,
            "backend_fallback_total": self.backend_fallback_total,
            "fast_path_failures_total": self.fast_path_failures_total,
            "backend_attached": self.backend_attached,
            "solver_optimal_solves_total": self.solver_optimal_solves_total,
            "solver_optimal_fallbacks_total":
                self.solver_optimal_fallbacks_total,
            "prep_seconds_total": round(self.prep_seconds_total, 3),
            "plane_classes_per_chunk": self.plane_classes_per_chunk,
            "plane_bytes_uploaded_total": self.plane_bytes_uploaded_total,
            "class_split_fallback_pods": self.class_split_fallback_pods,
            "shard_count": self.shard_count,
            "shard_tensor_rebuilds_total": self.shard_tensor_rebuilds_total,
            # 6 decimals: the wavefront solve put small-chunk walls into
            # the sub-millisecond range, which 3-decimal rounding
            # reported as a (false) zero.
            "shard_solve_seconds": round(self.shard_solve_seconds, 6),
            "cross_shard_reductions_total": self.cross_shard_reductions_total,
            "process_count": self.process_count,
            "wal_appends_total": self.wal_appends_total,
            "wal_replay_entries_total": self.wal_replay_entries_total,
            "wal_fsync_seconds_total": round(
                self.wal_fsync_seconds_total, 4),
            "leader_elections_total": self.leader_elections_total,
            "serving_fast_path_pods_total": self.serving_fast_path_pods_total,
            "serving_coalesced_batches_total":
                self.serving_coalesced_batches_total,
            "resident_plane_refreshes_total":
                self.resident_plane_refreshes_total,
            "resident_plane_refresh_seconds_total": round(
                self.resident_plane_refresh_seconds_total, 4),
            "admission_window_ms": self.admission_window_ms,
            "agent_start_seconds": round(self.agent_start_seconds, 3),
            "staging_seconds": round(self.staging_seconds, 3),
            "churn_offered_rate": round(self.churn_offered_rate, 2),
            "churn_achieved_rate": round(self.churn_achieved_rate, 2),
            "churn_arrival_model": self.churn_arrival_model,
            "churn_arrivals_total": self.churn_arrivals_total,
            "churn_duration_s": round(self.churn_duration_s, 3),
            "churn_backlog_peak": self.churn_backlog_peak,
            "churn_backlog_final": self.churn_backlog_final,
            "churn_pending_final": dict(self.churn_pending_final),
            "churn_saturated": self.churn_saturated,
            "churn_late_arrivals": self.churn_late_arrivals,
            "churn_throttled_creates": self.churn_throttled_creates,
            "churn_create_errors": self.churn_create_errors,
            "churn_create_drain_s": round(self.churn_create_drain_s, 3),
            "churn_faults": list(self.churn_faults),
            "churn_faults_injected": dict(self.churn_faults_injected),
            "churn_recovery_seconds_max": self.churn_recovery_seconds_max,
            "churn_fragmentation_curve": [
                list(s) for s in self.churn_fragmentation_curve],
            "churn_descheduler_evictions": self.churn_descheduler_evictions,
            "churn_rebalance_recovery_s": self.churn_rebalance_recovery_s,
            "slice_gangs_bound_total": self.slice_gangs_bound_total,
            "slice_fragmentation_pct": round(
                self.slice_fragmentation_pct, 2),
            "topology_plane_rebuilds_total":
                self.topology_plane_rebuilds_total,
        }


def resolve_processes(processes: int | None = None) -> int:
    """The control plane's OS-process count: the explicit request, else
    KTPU_PROCESSES, else 1. Every entry point resolves it HERE, before
    it constructs anything — with N >= 2 the chip is the leader
    replica's, and a parent that has built a backend holds it."""
    from kubernetes_tpu.utils import flags
    if processes is None:
        processes = flags.get("KTPU_PROCESSES") or 1
    return int(processes)


def device_backend(chunk: int | None, processes: int):
    """(backend, backend_spec) for a PerfRunner that was asked for the
    device: in one process a `TPUBackend`; with `processes` >= 2 only
    the SPEC the elected leader builds its own from — this process then
    never imports jax (importing it here is what the caller avoids)."""
    if processes > 1:
        return None, {"kind": "tpu", "chunk": chunk}
    from kubernetes_tpu.ops import TPUBackend
    return TPUBackend(max_batch=chunk), None


def device_run_failures(detail: Mapping[str, Any]) -> list[str]:
    """Why a run that WAS ASKED FOR THE DEVICE counts as failed, from
    its `WorkloadResult.as_dict()` ([] = it kept the device throughout).
    The circuit breaker and the fast-path reroute keep pods scheduling
    through a device fault — a product behaviour — but the numbers such
    a run prints are the host scheduler's. A run nobody attached a
    backend to (`backend_attached` None) is the same thing from the
    start. Call it only for runs asked for the device; host runs have
    nothing to lose."""
    out = [f"{k}={detail[k]}"
           for k in ("backend_fallback_total", "fast_path_failures_total")
           if detail[k]]
    if detail["backend_attached"] is None:
        out.append("backend_attached=null (no device backend was ever "
                   "attached: the host path scheduled this run)")
    elif not detail["backend_attached"]:
        out.append("backend_attached=false (the circuit opened, or no "
                   "replica vouched for a device solve)")
    return out


def device_exit(details: list[Mapping[str, Any]], processes: int) -> int:
    """Exit code of a finished run that was asked for the device — the
    one rule behind `bench.py --backend tpu` (all modes) and this
    module's `--backend tpu`: non-zero when any detail row lost the
    device, or when the parent of a multi-process run imported jax (the
    chip is the leader replica's)."""
    lost = [why for d in details if (why := device_run_failures(d))]
    if processes > 1 and "jax" in sys.modules:
        lost.append(["this parent process imported jax; the chip is the "
                     "leader replica's"])
    if lost:
        print(f"FAILED: the device was lost: {json.dumps(lost)}",
              file=sys.stderr)
        return 1
    return 0


DEFAULT_NODE_TEMPLATE = {
    "allocatable": {"cpu": "8", "memory": "32Gi", "pods": "110"}}
DEFAULT_POD_TEMPLATE = {
    "requests": {"cpu": "100m", "memory": "250Mi"}}


class _ServerPair:
    """The apiserver processes backing a boundary-crossing run: the HTTP
    server (policy owner) and, in wire mode, the framed-wire listener."""

    def __init__(self, api, wire):
        self.api = api
        self.wire = wire

    async def stop(self) -> None:
        if self.wire is not None:
            await self.wire.stop()
        await self.api.stop()


class _SchedulerProxy:
    """Stands in for the in-process Scheduler when scheduling happens
    in child processes (--processes >= 2): the harness keeps reading
    the same seams — queue depth, event-recorder counters, the cache
    snapshot — but the answers come from the parent's own pod informer
    (backlog = pods without a nodeName) or are structurally empty (the
    assume-cache lives in the leader replica; fragmentation over it is
    reported as 0 here and the exact attempt percentiles come over the
    measure-marker protocol instead)."""

    class _Recorder:
        emitted = 0
        dropped = 0

    class _Cache:
        @staticmethod
        def update_snapshot() -> list:
            return []

    def __init__(self):
        self.queue = self
        self.recorder = self._Recorder()
        self.cache = self._Cache()
        self._unbound: set[str] = set()

    async def setup_informers(self, factory) -> None:
        from kubernetes_tpu.client import ResourceEventHandler

        def _upd(obj):
            key = namespaced_name(obj)
            if obj.get("spec", {}).get("nodeName"):
                self._unbound.discard(key)
            else:
                self._unbound.add(key)

        factory.informer("pods").add_event_handler(ResourceEventHandler(
            on_add=_upd, on_update=lambda old, new: _upd(new),
            on_delete=lambda obj: self._unbound.discard(
                namespaced_name(obj))))

    # -- queue surface (self.queue is self) --------------------------------

    def stats(self) -> dict:
        return {"active": len(self._unbound), "backoff": 0,
                "unschedulable": 0, "gated": 0, "in_flight": 0}

    def backlog_depth(self) -> int:
        return len(self._unbound)

    # -- lifecycle ---------------------------------------------------------

    async def run(self, batch_size: int = 1) -> None:
        # The replicas schedule; the proxy just holds the task slot the
        # harness cancels on teardown.
        await asyncio.Event().wait()

    async def stop(self) -> None:
        pass


class PerfRunner:
    """Executes one workload (template ops + params) against an in-process
    store + scheduler, mirroring mustSetupCluster → runWorkload."""

    def __init__(self, backend=None, batch_size: int = 1,
                 backend_spec: Mapping | None = None,
                 scheduler_kwargs: Mapping | None = None,
                 scheduler_config: Mapping | None = None,
                 through_apiserver: bool = False,
                 profile_dir: str | None = None,
                 policy_count: int = 0,
                 policy_tenants: int = 0,
                 audit_rules: list | None = None,
                 shards: int | None = None,
                 processes: int | None = None,
                 data_dir: str | None = None):
        self.backend = backend
        #: what the leader replica of a multi-process run builds ITS
        #: backend from ({"kind": "tpu", "chunk": N|None}). The chip
        #: belongs to that one process: the parent passes this spec,
        #: never a backend, and so never touches JAX.
        self.backend_spec = dict(backend_spec) if backend_spec else None
        self.batch_size = batch_size
        self.scheduler_kwargs = dict(scheduler_kwargs or {})
        #: control-plane shard count for the backing store (>1 builds a
        #: ShardedNodeStore; None resolves KTPU_SHARDS, default 1).
        self.shards = shards
        #: OS-process count for the control plane (r22 tentpole). >1
        #: spawns one apiserver process per shard plus a leader-elected
        #: scheduler pair and drives them through the cross-process
        #: facade; None resolves KTPU_PROCESSES; <=1 builds today's
        #: in-process tree exactly (nothing multiproc is constructed).
        self.processes = processes
        #: KTPU_DATA_DIR override for the shard processes (per-shard
        #: snapshot + WAL directories live under it).
        self.data_dir = data_dir
        #: measure-marker protocol client, live only during a
        #: multi-process run (see multiproc/controlplane.py).
        self._mp = None
        self._cp = None
        #: ValidatingAdmissionPolicies (+bindings) installed before the
        #: run — the policy-chain overhead knob (BASELINE r9: headline
        #: with a 10-policy set vs disabled). Only meaningful with
        #: through_apiserver (the policy chain lives on the servers).
        self.policy_count = policy_count
        #: >0 shards the policy set across N tenant namespaces with
        #: per-namespace selectors and disjoint resourceRules so only
        #: ~1% of stored policies match any given request — the
        #: realistic multi-tenant shape the O(matching) index targets
        #: (the 1k-policy headline row uses 1000/100). 0 keeps the
        #: legacy uniform all-matching set (the r9 comparison row).
        self.policy_tenants = policy_tenants
        #: audit policy rules for the run's AuditPipeline ([] = level
        #: None for everything: stage events cost nothing).
        self.audit_rules = list(audit_rules or [])
        self._policy_engine = None
        self._audit = None
        #: Optional inline KubeSchedulerConfiguration (a workload family may
        #: enable non-default plugins, e.g. NodeResourceTopologyMatch).
        self.scheduler_config = scheduler_config
        #: Cross the process boundary like the reference's scheduler_perf
        #: (in-process apiserver + REAL wire): all traffic — workload
        #: writes, the scheduler's informers, and binding POSTs — goes over
        #: the apiserver instead of direct store calls. True/"http" = the
        #: HTTP/1.1+JSON wire; "wire" = the KTPU multiplexed framed wire
        #: (the HTTP/2 analog core components use — apiserver/wire.py).
        self.through_apiserver = through_apiserver
        #: jax.profiler trace of the MEASURED phase only (not warmup/jit
        #: compile) when the backend supports it.
        self.profile_dir = profile_dir

    async def run(self, template_ops: list, params: Mapping[str, Any],
                  timeout: float = 600.0) -> WorkloadResult:
        from kubernetes_tpu.utils import flags
        if self.shards is None:
            return await self._run_inner(template_ops, params, timeout)
        # The host prep's per-shard accounting resolves the same
        # flagless policy (control_plane_shards); an explicit shard
        # request must reach it too — scoped to this run (save/restore
        # so overlapping runs can't cross-restore each other's value).
        with flags.scoped_set("KTPU_SHARDS", self.shards):
            return await self._run_inner(template_ops, params, timeout)

    async def _run_inner(self, template_ops: list,
                         params: Mapping[str, Any],
                         timeout: float = 600.0) -> WorkloadResult:
        from kubernetes_tpu.utils import flags
        nproc = resolve_processes(self.processes)
        if nproc > 1 and self.backend is not None:
            # Whoever built that backend touched JAX and holds the chip
            # the leader replica needs; and no child would schedule
            # through it (children build their own from backend_spec),
            # so the run would be the host path under a device's name.
            raise ValueError(
                f"a {nproc}-process run takes backend_spec, not a "
                "backend: resolve the process count first "
                "(resolve_processes / device_backend)")
        cp = None
        self._mp = None
        self._cp = None
        server = None
        client = None
        if nproc > 1:
            # r22 tentpole topology: one apiserver OS process per shard
            # plus a leader-elected scheduler pair; the parent only
            # stages the workload and reads results through the
            # cross-process facade. N<=1 takes the else branch and
            # builds today's in-process tree exactly as before.
            from kubernetes_tpu.multiproc import (
                MeasureProtocol,
                MultiProcessControlPlane,
            )
            cp = MultiProcessControlPlane(
                nproc,
                data_dir=self.data_dir or flags.get("KTPU_DATA_DIR"),
                backend_spec=self.backend_spec,
                batch_size=self.batch_size,
                scheduler_kwargs=self.scheduler_kwargs)
            try:
                await cp.start()
                await cp.start_schedulers(2)
                store = backing = cp.client()
                metrics = SchedulerMetrics()
                sched = _SchedulerProxy()
                factory = InformerFactory(store)
                await sched.setup_informers(factory)
                self._mp = MeasureProtocol(store)
                self._cp = cp
            except BaseException:
                await cp.stop()
                raise
            return await self._drive(template_ops, params, timeout,
                                     backing, store, metrics, sched,
                                     factory, server, client, cp)
        backing = new_cluster_store(shards=self.shards)
        install_core_validation(backing)
        try:
            api_kw = {}
            if self.through_apiserver:
                # The policy chain rides the servers: admission
                # (webhooks + expression policies) and the audit
                # pipeline are ALWAYS constructed for boundary-crossing
                # runs, so the detail JSON's policy/audit counters are
                # real measurements (zero when no policies/rules exist).
                from kubernetes_tpu.apiserver.admission import (
                    WebhookAdmission,
                )
                from kubernetes_tpu.policy import (
                    AuditPipeline,
                    AuditPolicy,
                    PolicyEngine,
                )
                self._policy_engine = PolicyEngine(backing)
                self._audit = AuditPipeline(
                    AuditPolicy(self.audit_rules))
                api_kw = {"admission": WebhookAdmission(
                    backing, policy_engine=self._policy_engine),
                    "audit": self._audit}
                await self._install_policies(backing)
            if self.through_apiserver == "wire":
                # The core-component transport: HTTP server up (policy
                # lives there), store traffic over the multiplexed wire.
                from kubernetes_tpu.apiserver.server import APIServer
                from kubernetes_tpu.apiserver.wire import (
                    WireServer,
                    WireStore,
                )
                server = _ServerPair(APIServer(backing, **api_kw), None)
                await server.api.start()
                server.wire = WireServer.for_apiserver(
                    server.api, host="unix:")
                await server.wire.start()
                client = WireStore(server.wire.target)
                store = client
            elif self.through_apiserver:
                from kubernetes_tpu.apiserver.client import RemoteStore
                from kubernetes_tpu.apiserver.server import APIServer
                server = _ServerPair(APIServer(backing, **api_kw), None)
                await server.api.start()
                client = RemoteStore(server.api.url)
                store = client
            else:
                store = backing
            metrics = SchedulerMetrics()
            profiles = None
            if self.scheduler_config is not None:
                from kubernetes_tpu.config.scheduler import load_config
                cfg = load_config(self.scheduler_config)
                profiles = {p.scheduler_name: p.build_framework(
                    store=store, metrics=metrics) for p in cfg.profiles}
            sched = Scheduler(store, seed=42, backend=self.backend,
                              metrics=metrics, profiles=profiles,
                              **self.scheduler_kwargs)
            factory = InformerFactory(store)
            await sched.setup_informers(factory)
        except BaseException:
            # Setup failed after the server/client came up — don't leak
            # the bound socket or background tasks.
            if client is not None:
                await client.close()
            if server is not None:
                await server.stop()
            backing.stop()
            raise
        return await self._drive(template_ops, params, timeout, backing,
                                 store, metrics, sched, factory, server,
                                 client, None)

    async def _drive(self, template_ops: list, params: Mapping[str, Any],
                     timeout: float, backing, store, metrics, sched,
                     factory, server, client, cp) -> WorkloadResult:
        """The opcode loop, shared by both construction paths (`cp` is
        the MultiProcessControlPlane for --processes >= 2, else None)."""
        # Bound-pod accounting via watch events, not store LISTs: a LIST
        # deep-copies every object and was the harness's own hot spot.
        bound_keys: set[str] = set()

        def _track(obj):
            if obj.get("spec", {}).get("nodeName"):
                bound_keys.add(namespaced_name(obj))

        from kubernetes_tpu.client import ResourceEventHandler
        factory.informer("pods").add_event_handler(ResourceEventHandler(
            on_add=_track, on_update=lambda old, new: _track(new),
            on_delete=lambda obj: bound_keys.discard(namespaced_name(obj))))

        factory.start()
        await factory.wait_for_sync()
        run_task = asyncio.ensure_future(sched.run(batch_size=self.batch_size))

        result = WorkloadResult()
        node_count = 0
        pod_seq = 0
        created_total = 0
        agents: list = []
        agent_dir: str | None = None
        deadline = time.monotonic() + timeout
        completed = False
        try:
            for op in template_ops:
                opcode = op["opcode"]
                if opcode == "startAgents":
                    # Agent-backed staging: N hollow-kubelet NodeAgents
                    # (kubernetes_tpu/agent) register their own Nodes and
                    # consume field-selector-filtered pod watches — the
                    # kubelet topology — instead of createNodes' bare
                    # data staging. Their mark-Running writes and lease
                    # renewals ride the same store/wire as the workload.
                    import tempfile

                    from kubernetes_tpu.agent import NodeAgent
                    count = _resolve_count(op, params)
                    tmpl = {**DEFAULT_NODE_TEMPLATE,
                            **(op.get("nodeTemplate") or {})}
                    if agent_dir is None:
                        agent_dir = tempfile.mkdtemp(prefix="ktpu-agents-")
                    new_agents = [
                        NodeAgent(store, f"node-{node_count + i}",
                                  checkpoint_dir=agent_dir,
                                  node_template=copy.deepcopy(tmpl),
                                  lease_period=float(_subst(
                                      op.get("leasePeriod", 5.0),
                                      params)))
                        for i in range(count)]
                    # Track BEFORE starting so a mid-boot failure still
                    # stops every booted agent in the finally block
                    # (stop() on a never-started agent is a no-op).
                    # Batched fleet boot (NodeAgent.start_many): wide
                    # registration windows first, then wide watch
                    # establishment — per-agent serialized handshakes
                    # were the r12-identified 50k-agent headroom.
                    agents.extend(new_agents)
                    t0 = time.monotonic()
                    from kubernetes_tpu.agent.agent import NodeAgent as _NA
                    await _NA.start_many(new_agents)
                    result.agent_start_seconds += time.monotonic() - t0
                    node_count += count

                elif opcode == "createNodes":
                    count = _resolve_count(op, params)
                    tmpl = {**DEFAULT_NODE_TEMPLATE,
                            **(op.get("nodeTemplate") or {})}
                    # Optional NUMA topology (BASELINE config #4): create a
                    # NodeResourceTopology per node, splitting allocatable
                    # across zones the way a device-manager agent reports.
                    topo = op.get("topologyTemplate")
                    # Optional DRA inventory (SURVEY §2.3 dynamicresources):
                    # one ResourceSlice per node listing devices with NUMA
                    # attributes, plus the DeviceClass selecting them.
                    dra = op.get("draTemplate")
                    t0 = time.monotonic()
                    if dra:
                        from kubernetes_tpu.api.types import (
                            make_device_class,
                            make_resource_slice,
                        )
                        cls = dra.get("className", "tpu")
                        try:
                            await store.create(
                                "deviceclasses",
                                make_device_class(cls, {"type": cls}))
                        except Exception:
                            pass  # already created by an earlier op

                    # Staging writes go out in concurrent 512-wide
                    # windows, same shape as createPods: each window
                    # coalesces into one multiplexed wire frame, where
                    # per-node serial awaits paid a full RTT apiece —
                    # at the 1m preset that serial loop alone was a
                    # double-digit-minute wall before any measurement.
                    async def stage_node(i):
                        name = f"node-{node_count + i}"
                        await store.create("nodes", make_node(
                            name, **copy.deepcopy(tmpl)))
                        if topo:
                            await store.create(
                                "noderesourcetopologies",
                                split_node_topology(
                                    name, tmpl.get("allocatable") or {},
                                    num_zones=int(topo.get("zones", 2)),
                                    devices=topo.get("devices")))
                        if dra:
                            zones = int(dra.get("zones", 2))
                            per = int(dra.get("devicesPerZone", 4))
                            devices = [
                                {"name": f"dev-{z}-{k}",
                                 "attributes": {"type": cls,
                                                "numa": str(z)}}
                                for z in range(zones) for k in range(per)]
                            await store.create(
                                "resourceslices",
                                make_resource_slice(
                                    name, dra.get("driver", "dra.ktpu"),
                                    devices))

                    for lo in range(0, count, 512):
                        await asyncio.gather(*(
                            stage_node(i)
                            for i in range(lo, min(lo + 512, count))))
                    result.staging_seconds += time.monotonic() - t0
                    node_count += count

                elif opcode == "createPods":
                    count = _resolve_count(op, params)
                    tmpl = {**DEFAULT_POD_TEMPLATE,
                            **(op.get("podTemplate") or {})}
                    # DRA pods: podTemplate.claim stamps one ResourceClaim
                    # per pod (the resourceclaim controller's output shape)
                    # referenced via spec.resourceClaims.
                    claim_tmpl = tmpl.pop("claim", None)
                    measured = bool(op.get("collectMetrics"))
                    if measured:
                        # Metric window starts now: percentiles and
                        # throughput cover only the measured phase (warmup
                        # attempts — including jit compile — are excluded).
                        window = self._begin_measure(metrics, backing)
                        await self._mp_begin()
                        if self.profile_dir and hasattr(
                                self.backend, "start_profile"):
                            self.backend.start_profile(self.profile_dir)
                    names = [f"pod-{pod_seq + i}" for i in range(count)]
                    # Writes go out in concurrent windows (the reference
                    # harness drives the apiserver with multi-goroutine
                    # client QPS; serial awaits would make the HTTP
                    # boundary the benchmark). 512-wide windows let the
                    # wire transport coalesce a whole window into one
                    # multiplexed frame.
                    if claim_tmpl:
                        from kubernetes_tpu.api.types import (
                            make_resource_claim,
                        )

                        async def create_claimed(name):
                            await store.create(
                                "resourceclaims", make_resource_claim(
                                    f"{name}-c0",
                                    requests=copy.deepcopy(
                                        claim_tmpl.get("requests") or []),
                                    constraints=copy.deepcopy(
                                        claim_tmpl.get("constraints")
                                        or [])))
                            await store.create("pods", make_pod(
                                name, resource_claims=[{
                                    "name": "c0",
                                    "resourceClaimName": f"{name}-c0"}],
                                **copy.deepcopy(tmpl)))

                        for lo in range(0, count, 512):
                            await asyncio.gather(*(
                                create_claimed(name)
                                for name in names[lo:lo + 512]))
                    else:
                        for lo in range(0, count, 512):
                            await asyncio.gather(*(
                                store.create("pods", make_pod(
                                    name, **copy.deepcopy(tmpl)))
                                for name in names[lo:lo + 512]))
                    pod_seq += count
                    created_total += count
                    if op.get("scopedBarrier") and not measured:
                        # Wait for THIS op's pods only (reference barriers
                        # take a labelSelector): lets a warmup op complete
                        # even when it deletes other pods (a preemption
                        # warmup shrinks the global bound count, so a
                        # global barrier would never pass).
                        pod_ns = tmpl.get("namespace", "default")
                        want = {f"{pod_ns}/{n}" for n in names}
                        await self._wait_keys(bound_keys, want, deadline)
                    if measured:
                        # Scoped to THIS op's pods (reference barriers take
                        # a labelSelector for the same reason): preemption
                        # deletes victims, so the global count can shrink.
                        pod_ns = tmpl.get("namespace", "default")
                        want = {f"{pod_ns}/{n}" for n in names}
                        await self._wait_keys(bound_keys, want, deadline)
                        self._end_measure(result, metrics, backing,
                                          window, count)
                        await self._mp_end(result)
                        if self.profile_dir and hasattr(
                                self.backend, "stop_profile"):
                            self.backend.stop_profile()

                elif opcode == "ungatePods":
                    # Strip schedulingGates from every gated pod (the
                    # reference's gated-pods workload: a controller lifts
                    # the gate; PreEnqueue re-admits). Measured variant
                    # times gate-removal → all bound.
                    measured = bool(op.get("collectMetrics"))
                    if measured:
                        window = self._begin_measure(metrics, backing)
                        await self._mp_begin()
                    gated = [p for p in (await store.list("pods")).items
                             if p["spec"].get("schedulingGates")]

                    def strip(obj):
                        obj["spec"].pop("schedulingGates", None)
                        return obj
                    for p in gated:
                        await store.guaranteed_update(
                            "pods", namespaced_name(p), strip)
                    if measured:
                        await self._wait_bound(bound_keys, created_total,
                                               deadline)
                        self._end_measure(result, metrics, backing,
                                          window, len(gated))
                        await self._mp_end(result)

                elif opcode == "relistStorm":
                    # Every agent reconnects AT ONCE: tear down its
                    # watch, full LIST, re-watch (agent.force_relist) —
                    # the cold-start storm ROADMAP #2 names. With the
                    # watch cache active the N LISTs are reads of one
                    # shared snapshot (hit/miss deltas recorded); the
                    # direct-mvcc path pays N table scans.
                    h0, m0 = self._cache_totals(backing)
                    t0 = time.monotonic()
                    await asyncio.gather(
                        *(a.force_relist() for a in agents))
                    result.relist_storm_seconds = time.monotonic() - t0
                    result.relist_storm_agents = len(agents)
                    h1, m1 = self._cache_totals(backing)
                    result.relist_storm_cache_hits = int(h1 - h0)
                    result.relist_storm_cache_misses = int(m1 - m0)

                elif opcode == "churnOpenLoop":
                    # ChurnDay (perf/churn): a TIMED open-loop arrival
                    # window — pods enqueue at the process's rate on an
                    # absolute clock whatever the scheduler does, with
                    # an optional deterministic fault timeline injected
                    # mid-wave. No trailing barrier belongs after this
                    # op: a saturated run deliberately ends with unbound
                    # pods (that backlog IS the measurement).
                    created_total += await self._run_churn_phase(
                        op, params, result, metrics, backing, store,
                        sched, factory, agents, bound_keys, pod_seq)
                    pod_seq += result.churn_arrivals_total

                elif opcode == "barrier":
                    await self._wait_bound(bound_keys, created_total, deadline)

                elif opcode == "sleep":
                    await asyncio.sleep(float(
                        _subst(op.get("duration", 0), params)))

                elif opcode == "churn":
                    # Delete + recreate a slice of bound pods: queue pressure
                    # and cache-update load (reference churnOp).
                    count = _resolve_count(op, params)
                    pods = (await store.list("pods")).items[:count]
                    for p in pods:
                        await store.delete("pods", namespaced_name(p))
                    created_total -= len(pods)
                    # Wait for the deletions to reach the informer before
                    # recreating, or the next barrier reads stale bound keys.
                    while len(bound_keys) > created_total \
                            and time.monotonic() < deadline:
                        await asyncio.sleep(0.01)
                    tmpl = {**DEFAULT_POD_TEMPLATE,
                            **(op.get("podTemplate") or {})}
                    for i in range(len(pods)):
                        await store.create("pods", make_pod(
                            f"pod-{pod_seq + i}", **copy.deepcopy(tmpl)))
                    pod_seq += len(pods)
                    created_total += len(pods)

                else:
                    raise ValueError(f"unknown opcode {opcode!r}")
            completed = True
        finally:
            if agents:
                await asyncio.gather(
                    *(a.stop() for a in agents), return_exceptions=True)
            if agent_dir is not None:
                import shutil
                shutil.rmtree(agent_dir, ignore_errors=True)
            await sched.stop()
            run_task.cancel()
            factory.stop()
            finalize_error = None
            if cp is not None:
                # WAL/HA and device counters live in the children: pull
                # them while the shard sockets still answer. A failure
                # here waits until the children are stopped (the leader
                # holds the chip the next run's leader needs), and is
                # dropped only when another exception is already on its
                # way out — the primary failure must surface.
                try:
                    await self._finalize_multiproc(result, backing)
                except Exception as e:
                    finalize_error = e
            try:
                if client is not None:
                    await client.close()
                if server is not None:
                    await server.stop()
                backing.stop()
            finally:
                if cp is not None:
                    await cp.stop()
                    self._cp = None
                    self._mp = None
            if completed and finalize_error is not None:
                raise finalize_error

        # Percentiles were captured over the measured window above
        # (scheduler_scheduling_attempt_duration_seconds — SURVEY §5.5);
        # fall back to whole-run percentiles when no phase was measured.
        if cp is None:
            if result.measured_pods == 0:
                h = metrics.attempt_duration
                labels = {"result": "scheduled",
                          "profile": "default-scheduler"}
                result.attempt_p50 = h.percentile(0.50, **labels)
                result.attempt_p90 = h.percentile(0.90, **labels)
                result.attempt_p99 = h.percentile(0.99, **labels)
            result.scheduled_total = _result_count(metrics, "scheduled")
            result.unschedulable_total = _result_count(
                metrics, "unschedulable")
            for k, v in metrics.device_loss_counts().items():
                setattr(result, k, v)
            if self.backend is not None:
                result.backend_attached = sched.backend is not None
        result.shard_count = int(getattr(backing, "node_shards", 1))
        result.fragmentation_pct = self._fragmentation(sched)
        result.fragmentation_occupied_pct = \
            self._fragmentation_occupied(sched)
        metrics.fragmentation_pct.set(result.fragmentation_occupied_pct)
        result.events_emitted_total = sched.recorder.emitted
        result.events_dropped_total = sched.recorder.dropped
        return result

    async def _run_churn_phase(self, op: Mapping, params: Mapping[str, Any],
                               result: WorkloadResult, metrics, backing,
                               store, sched, factory, agents: list,
                               bound_keys: set, pod_seq: int) -> int:
        """Execute one churnOpenLoop op; returns the net pod-count delta
        (arrivals + fault creates − fault deletes) for created_total."""
        from kubernetes_tpu.metrics.registry import ChurnMetrics
        from kubernetes_tpu.perf.churn import (
            ChurnDriver,
            FaultInjector,
            build_fault_timeline,
            is_saturated,
            make_arrival_process,
        )
        duration = float(_subst(op.get("duration", 5.0), params))
        seed = int(_subst(op.get("seed", 0), params))
        arrival = {k: _subst(v, params)
                   for k, v in (op.get("arrival")
                                or {"model": "poisson", "rate": 100}).items()}
        process = make_arrival_process(arrival, seed=seed)
        churn_metrics = ChurnMetrics(metrics.registry)
        measured = bool(op.get("collectMetrics"))
        tmpl = {**DEFAULT_POD_TEMPLATE, **(op.get("podTemplate") or {})}
        pod_ns = tmpl.get("namespace", "default")

        async def create_arrival(name: str, template: dict | None = None):
            await store.create("pods", make_pod(
                name, **(template if template is not None
                         else copy.deepcopy(tmpl))))

        driver = ChurnDriver(
            process, duration,
            create_pod=create_arrival,
            backlog_stats=sched.queue.stats,
            # Keep scheduler_pending_pods{queue} fresh under saturation
            # (the scheduler only refreshes it per popped batch).
            on_backlog=metrics.set_pending,
            metrics=churn_metrics,
            name_prefix=f"churn{pod_seq}")

        injector = None
        timeline = []
        nlc = None
        fault_specs = op.get("faults") or []
        if fault_specs:
            timeline = build_fault_timeline(
                [{k: _subst(v, params) for k, v in f.items()}
                 for f in fault_specs],
                seed=seed,
                node_names=[a.node_name for a in agents])
            injector = FaultInjector(
                store=store, agents=agents, bound_keys=bound_keys,
                create_pod=create_arrival,
                backlog_fn=sched.queue.backlog_depth,
                control_plane=self._cp,
                metrics=churn_metrics, pod_template=tmpl,
                recovery_threshold=int(_subst(
                    op.get("recoveryThreshold", 10), params)),
                recovery_timeout=float(_subst(
                    op.get("recoveryTimeout", 60.0), params)),
                namespace=pod_ns)
            if any(ev.kind == "nodeDeath" for ev in timeline):
                # Node death needs the lease-expiry machinery live: a
                # killed agent's Lease goes stale, the controller
                # taints unreachable after the grace period, and the
                # NoExecute manager evicts (SURVEY §5.3).
                from kubernetes_tpu.controllers.nodelifecycle import (
                    NodeLifecycleController,
                )
                tol = float(_subst(op.get("tolerationSeconds", 0.25),
                                   params))
                nlc = NodeLifecycleController(
                    store,
                    node_monitor_period=0.1,
                    node_monitor_grace_period=float(_subst(
                        op.get("nodeGracePeriod", 1.0), params)),
                    default_toleration_seconds=tol,
                    # The admission default stamps 300s on every pod;
                    # the scenario's toleration knob caps it so the
                    # eviction clock runs at bench speed.
                    toleration_seconds_cap=tol)
                nlc.setup(factory)
                factory.informer("leases").start()
                await factory.informer("leases").wait_for_sync()
                nlc.start()

        # Rebalance family (r20): an optional descheduler closes the
        # consolidation loop DURING the churn window, and a fragmentation
        # sampler records the over-time curve the on/off pair compares.
        # `descheduler: {enabled, period, budget, threshold}` on the op
        # pins it per workload; absent, the KTPU_DESCHEDULER flag rules.
        desch = None
        dcfg = op.get("descheduler")
        if dcfg is None:
            from kubernetes_tpu.utils import flags as _flags
            d_on = bool(_flags.get("KTPU_DESCHEDULER"))
            dcfg = {}
        else:
            dcfg = {k: _subst(v, params) for k, v in dcfg.items()}
            d_on = bool(dcfg.get("enabled", True))
        if d_on:
            from kubernetes_tpu.controllers.descheduler import (
                DeschedulerController,
            )
            desch = DeschedulerController(
                store,
                period=float(dcfg.get("period", 0.25)),
                budget=int(dcfg["budget"]) if "budget" in dcfg else None,
                threshold=float(dcfg.get("threshold", 0.5)))
            desch.setup(factory)
            for res in ("pods", "nodes"):
                factory.informer(res).start()
                await factory.informer(res).wait_for_sync()
            desch.start()

        curve: list[list[float]] = []
        sample_every = float(_subst(op.get("sampleInterval", 0.0), params))

        async def _sample(t0: float) -> None:
            while True:
                curve.append([
                    round(time.monotonic() - t0, 3),
                    round(self._fragmentation(sched), 2),
                    round(self._fragmentation_occupied(sched), 2)])
                await asyncio.sleep(sample_every)

        window = self._begin_measure(metrics, backing) if measured else None
        if measured:
            await self._mp_begin()
        sampler = None
        try:
            t0 = time.monotonic()
            if sample_every > 0:
                sampler = asyncio.ensure_future(_sample(t0))
            inj_task = None
            if injector is not None:
                inj_task = asyncio.ensure_future(
                    injector.run(timeline, t0))
            phase = await driver.run(t0)
            if inj_task is not None:
                await inj_task
                await injector.drain()
            if desch is not None:
                # Recovery: stop proposing moves, then the bounded wait
                # for the backlog (evicted replacements included) to
                # drain back under the threshold.
                await desch.stop()
                r0 = time.monotonic()
                r_deadline = r0 + float(_subst(
                    op.get("recoveryTimeout", 30.0), params))
                thresh = int(_subst(op.get("recoveryThreshold", 10),
                                    params))
                while time.monotonic() < r_deadline \
                        and sched.queue.backlog_depth() > thresh:
                    await asyncio.sleep(0.05)
                result.churn_rebalance_recovery_s = round(
                    time.monotonic() - r0, 3)
        finally:
            if sampler is not None:
                sampler.cancel()
                # one last point so the curve shows the recovered state
                curve.append([
                    round(time.monotonic() - t0, 3),
                    round(self._fragmentation(sched), 2),
                    round(self._fragmentation_occupied(sched), 2)])
            if desch is not None:
                if not desch._stopped:
                    await desch.stop()
                result.churn_descheduler_evictions = desch.evictions
            if nlc is not None:
                await nlc.stop()
        result.churn_fragmentation_curve = curve
        if measured:
            self._end_measure(result, metrics, backing, window,
                              phase.arrivals_total)
            await self._mp_end(result)
        result.churn_offered_rate = phase.offered_rate
        result.churn_achieved_rate = phase.achieved_rate
        result.churn_arrival_model = phase.arrival_model
        result.churn_arrivals_total = phase.arrivals_total
        result.churn_duration_s = phase.duration
        result.churn_backlog_peak = phase.backlog_peak
        result.churn_backlog_final = phase.backlog_final
        result.churn_pending_final = dict(phase.pending_final)
        result.churn_saturated = is_saturated(
            phase.arrivals_total, phase.backlog_final,
            float(_subst(op.get("saturationFrac", 0.2), params)),
            offered_rate=phase.offered_rate,
            achieved_rate=phase.achieved_rate)
        result.churn_late_arrivals = phase.late_arrivals
        result.churn_throttled_creates = phase.throttled_creates
        result.churn_create_errors = phase.create_errors
        result.churn_create_drain_s = phase.create_drain_s
        net = phase.arrivals_total
        if injector is not None:
            result.churn_faults = list(injector.results)
            counts: dict[str, int] = {}
            for rec in injector.results:
                counts[rec["kind"]] = counts.get(rec["kind"], 0) + 1
            result.churn_faults_injected = counts
            recoveries = [rec["recovery_s"] for rec in injector.results
                          if rec.get("recovery_s") is not None]
            if recoveries:
                result.churn_recovery_seconds_max = max(recoveries)
            net += injector.net_created
        return net

    async def _install_policies(self, backing) -> None:
        """The overhead knob: N pass-through pod policies + bindings
        (BASELINE r9 measures the headline with 10 vs 0). With
        policy_tenants > 0 the set is tenant-sharded instead —
        realistic multi-tenant matching for the O(matching) index."""
        if not self.policy_count:
            return
        if self.policy_tenants:
            await self._install_tenant_policies(backing)
            return
        from kubernetes_tpu.api.types import (
            make_validating_admission_policy,
            make_vap_binding,
        )
        for i in range(self.policy_count):
            name = f"bench-policy-{i}"
            await backing.create(
                "validatingadmissionpolicies",
                make_validating_admission_policy(name, [
                    {"expression": "size(object.spec.containers) >= 1"
                                   " and not has(object.spec.paused)",
                     "message": "bench policy"}],
                    match_constraints={"resourceRules": [
                        {"resources": ["pods"],
                         "operations": ["CREATE"]}]}))
            await backing.create("validatingadmissionpolicybindings",
                                 make_vap_binding(f"{name}-b", name))

    async def _install_tenant_policies(self, backing) -> None:
        """Realistic tenant shards (ISSUE 15 headline shape): N policies
        across T tenant namespaces — 4 of 5 are pod-CREATE policies
        scoped by a per-tenant namespaceSelector (the bench's pods land
        in "default", labeled tenant t0, so only ~N·0.8/T of them
        match: ~1% at 1000/100), 1 of 5 carries disjoint non-pod
        resourceRules the exact-key index never surfaces for a pod
        create. A ~1% slice of pod policies (stride 97, coprime with
        the tenant stride so breadth never correlates with one tenant's
        whole shard) adds a matchConditions prefilter + a variables
        entry (the breadth surface rides the measured path) and a
        second, paramRef-carrying binding against a shared per-tenant
        ConfigMap (prebuilt param closures exercised)."""
        from kubernetes_tpu.api.types import (
            make_config_map,
            make_namespace,
            make_validating_admission_policy,
            make_vap_binding,
        )
        from kubernetes_tpu.store.mvcc import AlreadyExists
        tenants = self.policy_tenants
        other_rules = ["configmaps", "secrets", "services",
                       "deployments", "leases", "replicasets",
                       "statefulsets", "daemonsets"]
        for t in range(tenants):
            ns = make_namespace(f"tenant-{t}")
            ns["metadata"]["labels"] = {"ktpu.io/tenant": f"t{t}"}
            await backing.create("namespaces", ns)
        # The measured pods ride the "default" namespace: label it as
        # tenant t0 so exactly that tenant's shard applies.
        default_ns = make_namespace("default")
        default_ns["metadata"]["labels"] = {"ktpu.io/tenant": "t0"}
        try:
            await backing.create("namespaces", default_ns)
        except AlreadyExists:
            cur = await backing.get("namespaces", "default")
            cur.setdefault("metadata", {})["labels"] = {
                "ktpu.io/tenant": "t0"}
            await backing.update("namespaces", cur)
        for t in range(tenants):
            await backing.create(
                "configmaps",
                make_config_map(f"tenant-caps-{t}",
                                data={"maxPriority": "1000000"}))
        for i in range(self.policy_count):
            t = i % tenants
            name = f"tenant-policy-{i}"
            if i % 5 == 4:
                # Disjoint non-pod rules: a pod CREATE never surfaces
                # these from the exact-key index (and the linear scan
                # pays for skipping them — the comparison's point).
                constraints = {"resourceRules": [
                    {"resources": [other_rules[i % len(other_rules)]],
                     "operations": ["CREATE", "UPDATE"]}]}
            else:
                constraints = {
                    "resourceRules": [{"resources": ["pods"],
                                       "operations": ["CREATE"]}],
                    "namespaceSelector": {
                        "matchLabels": {"ktpu.io/tenant": f"t{t}"}},
                }
            kwargs = {}
            validations = [
                {"expression": "size(object.spec.containers) >= 1"
                               " and not has(object.spec.paused)",
                 "message": f"tenant t{t} policy"}]
            spec_extra = {}
            if i % 97 == 0 and i % 5 != 4:
                spec_extra = {
                    "matchConditions": [
                        {"name": "has-spec",
                         "expression": "has(object.spec)"}],
                    "variables": [
                        {"name": "cset",
                         "expression": "object.spec.containers"}],
                }
                validations = [
                    {"expression": "size(variables.cset) >= 1",
                     "message": f"tenant t{t} policy"}]
                kwargs["param_kind"] = "ConfigMap"
            policy = make_validating_admission_policy(
                name, validations, match_constraints=constraints,
                **kwargs)
            policy["spec"].update(spec_extra)
            await backing.create("validatingadmissionpolicies", policy)
            await backing.create("validatingadmissionpolicybindings",
                                 make_vap_binding(f"{name}-b", name))
            if kwargs:
                await backing.create(
                    "validatingadmissionpolicybindings",
                    make_vap_binding(f"{name}-pb", name, param_ref={
                        "name": f"tenant-caps-{t}",
                        "namespace": "default"}))

    def _policy_totals(self) -> tuple[float, ...]:
        """(evals, index hits, residue scans, rebuilds, audit events,
        audit drops) — the policy/audit counter snapshot the measured
        window differences."""
        evals = hits = residue = rebuilds = audits = dropped = 0.0
        if self._policy_engine is not None:
            eng = self._policy_engine
            evals = sum(eng.evaluations._values.values())
            hits = eng.index_hits.value()
            residue = eng.index_residue_scans.value()
            rebuilds = eng.index_rebuilds.value()
        if self._audit is not None:
            audits = sum(
                self._audit.sink.events_total._values.values())
            dropped = self._audit.sink.events_dropped.value()
        return evals, hits, residue, rebuilds, audits, dropped

    @staticmethod
    def _cache_totals(backing) -> tuple[float, float]:
        """(hits, misses) of the store's watch-cache tier (0s when the
        KTPU_WATCH_CACHE=0 kill switch disabled it)."""
        cacher = getattr(backing, "cacher", None)
        if cacher is None:
            return 0.0, 0.0
        return cacher.metrics.hits.value(), cacher.metrics.misses.value()

    async def _mp_begin(self) -> None:
        """Open the child-side measured window (multi-process runs
        only): the leader marks its exact attempt recorder."""
        if self._mp is not None:
            await self._mp.begin()

    async def _mp_end(self, result: WorkloadResult) -> None:
        """Close the child-side window: the leader's exact attempt
        percentiles override the parent's recorder (which never saw an
        attempt — scheduling happened in another process). A failover
        mid-window can eat the marker; the parent-side wall-clock
        throughput from _end_measure then stands alone."""
        if self._mp is None:
            return
        row = await self._mp.end()
        import math
        try:
            pcts = {q: float(row[k]) for q, k in (
                (0.50, "p50"), (0.90, "p90"),
                (0.99, "p99"), (0.999, "p999"))}
        except (KeyError, TypeError, ValueError):
            return
        if math.isnan(pcts[0.50]):
            return
        result.attempt_p50 = pcts[0.50]
        result.attempt_p90 = pcts[0.90]
        result.attempt_p99 = pcts[0.99]
        result.attempt_p999 = pcts[0.999]
        result.attempt_percentiles_exact = True

    async def _finalize_multiproc(self, result: WorkloadResult,
                                  backing) -> None:
        """Pull the run's child-process counters (leader status row +
        per-shard WAL stats) — must run BEFORE the control plane stops:
        the sums live in the children, not the parent."""

        def _i(v) -> int:
            try:
                return int(v)
            except (TypeError, ValueError):
                return 0

        row = await self._mp.status()
        result.process_count = int(backing.node_shards)
        result.scheduled_total = _i(row.get("scheduledTotal"))
        result.leader_elections_total = _i(row.get("elections"))
        if self.backend_spec is not None:
            # The leader's word on the device (multiproc/schedproc.py):
            # a row that cannot vouch for it — no leader answered, or
            # no device solve ever ran — counts as the device lost.
            for k in ("backend_fallback_total", "fast_path_failures_total",
                      "host_path_pods"):
                setattr(result, k, _i(row.get(k)))
            result.backend_attached = row.get("backendAttached") == "1" \
                and _i(row.get("deviceSolves")) > 0
            result.solve_provenance = json.loads(
                row.get("provenance") or "{}")
        total = (await backing.control_stats()).get("total") or {}
        result.wal_appends_total = _i(total.get("walAppends"))
        result.wal_replay_entries_total = _i(total.get("walReplayed"))
        result.wal_fsync_seconds_total = float(
            total.get("walFsyncSeconds") or 0.0)

    def _begin_measure(self, metrics: SchedulerMetrics, backing) -> tuple:
        deg = metrics.backend_degradations
        wm = backing.watch_metrics
        return (metrics.attempt_duration.snapshot(
            result="scheduled", profile="default-scheduler"),
            time.monotonic(),
            deg.value(kind="host_fallback"),
            deg.value(kind="spread_poisoned"),
            wm.events_dispatched.value(),
            wm.predicate_checks.value(),
            *self._cache_totals(backing),
            *self._policy_totals(),
            metrics.solve_duration.count(),
            metrics.solve_duration.sum(),
            metrics.solver_shortlist_pods.value(),
            metrics.solver_shortlist_fallbacks.value(),
            metrics.solver_blocks_scanned.value(),
            metrics.solver_blocks_pruned.value(),
            metrics.solver_wave_commits.value(),
            metrics.solver_wave_replays.value(),
            metrics.prep_duration.sum(),
            metrics.plane_bytes.value(),
            metrics.class_split_fallbacks.value(),
            sum(metrics.shard_tensor_rebuilds._values.values()),
            sum(metrics.shard_solve_seconds._values.values()),
            metrics.cross_shard_reductions.value(),
            metrics.serving_fast_path_pods.value(),
            metrics.serving_coalesced_batches.value(),
            metrics.resident_plane_refreshes.value(),
            metrics.resident_plane_refresh.sum(),
            metrics.solver_optimal_solves.value(),
            metrics.solver_optimal_fallbacks.value(),
            metrics.slice_gangs_bound.value(),
            metrics.topology_plane_rebuilds.value(),
            metrics.attempt_window().mark())

    def _end_measure(self, result: WorkloadResult,
                     metrics: SchedulerMetrics,
                     backing, window: tuple, count: int) -> None:
        (hist_base, t0, fallback_base, poisoned_base,
         dispatched_base, checks_base, cache_hits_base, cache_miss_base,
         evals_base, idx_hits_base, idx_res_base, idx_rb_base,
         audits_base, audit_drop_base,
         solve_chunks_base, solve_s_base, sl_pods_base,
         sl_fall_base, blk_scan_base, blk_prune_base,
         wave_com_base, wave_rep_base,
         prep_s_base, plane_b_base, class_fb_base,
         shard_rb_base, shard_s_base, xshard_base,
         fast_base, coalesced_base, refresh_base, refresh_s_base,
         opt_base, opt_fb_base,
         slice_gangs_base, topo_rb_base,
         window_mark) = window
        dt = time.monotonic() - t0
        result.measured_pods = count
        result.measured_start = t0
        result.measured_seconds = dt
        result.throughput = count / dt if dt > 0 else 0.0
        h = metrics.attempt_duration
        labels = {"result": "scheduled", "profile": "default-scheduler"}
        result.attempt_p50 = h.percentile_since(0.50, hist_base, **labels)
        result.attempt_p90 = h.percentile_since(0.90, hist_base, **labels)
        result.attempt_p99 = h.percentile_since(0.99, hist_base, **labels)
        # TRUE order-statistic percentiles over the measured window (the
        # exact recorder riding attempt_duration's observe path); the
        # bucket-edge values above remain only as the fallback when no
        # scheduled attempt landed in the window.
        win = metrics.attempt_window()
        exact = win.percentiles_since(window_mark,
                                      (0.50, 0.90, 0.99, 0.999))
        import math
        if not math.isnan(exact[0.50]):
            result.attempt_p50 = exact[0.50]
            result.attempt_p90 = exact[0.90]
            result.attempt_p99 = exact[0.99]
            result.attempt_p999 = exact[0.999]
            result.attempt_percentiles_exact = True
        deg = metrics.backend_degradations
        result.host_fallback_pods = int(
            deg.value(kind="host_fallback") - fallback_base)
        result.spread_poisoned_pods = int(
            deg.value(kind="spread_poisoned") - poisoned_base)
        wm = backing.watch_metrics
        result.watch_events_dispatched_total = int(
            wm.events_dispatched.value() - dispatched_base)
        result.watch_predicate_checks_total = int(
            wm.predicate_checks.value() - checks_base)
        hits, misses = self._cache_totals(backing)
        result.watch_cache_hits_total = int(hits - cache_hits_base)
        result.watch_cache_misses_total = int(misses - cache_miss_base)
        (evals, idx_hits, idx_res, idx_rb,
         audits, audit_drops) = self._policy_totals()
        result.policy_evaluations_total = int(evals - evals_base)
        result.policy_index_hits_total = int(idx_hits - idx_hits_base)
        result.policy_index_residue_scans_total = int(
            idx_res - idx_res_base)
        result.policy_index_rebuilds_total = int(idx_rb - idx_rb_base)
        result.audit_events_total = int(audits - audits_base)
        result.audit_events_dropped_total = int(
            audit_drops - audit_drop_base)
        result.solver_solve_chunks = int(
            metrics.solve_duration.count() - solve_chunks_base)
        result.solver_solve_seconds_total = \
            metrics.solve_duration.sum() - solve_s_base
        result.solver_scan_width = int(metrics.solver_scan_width.value())
        result.solver_shortlist_pods_total = int(
            metrics.solver_shortlist_pods.value() - sl_pods_base)
        result.solver_shortlist_fallbacks_total = int(
            metrics.solver_shortlist_fallbacks.value() - sl_fall_base)
        result.solver_blocks_scanned_total = int(
            metrics.solver_blocks_scanned.value() - blk_scan_base)
        result.solver_blocks_pruned_total = int(
            metrics.solver_blocks_pruned.value() - blk_prune_base)
        result.solver_wave_width = int(metrics.solver_wave_width.value())
        result.solver_wave_commits_total = int(
            metrics.solver_wave_commits.value() - wave_com_base)
        result.solver_wave_replays_total = int(
            metrics.solver_wave_replays.value() - wave_rep_base)
        if self.backend is not None:
            from kubernetes_tpu.ops.backend import solve_provenance
            result.solve_provenance = solve_provenance()
        result.prep_seconds_total = \
            metrics.prep_duration.sum() - prep_s_base
        result.plane_classes_per_chunk = int(
            metrics.plane_classes.value())
        result.plane_bytes_uploaded_total = int(
            metrics.plane_bytes.value() - plane_b_base)
        result.class_split_fallback_pods = int(
            metrics.class_split_fallbacks.value() - class_fb_base)
        result.shard_count = int(getattr(backing, "node_shards", 1))
        result.shard_tensor_rebuilds_total = int(
            sum(metrics.shard_tensor_rebuilds._values.values())
            - shard_rb_base)
        result.shard_solve_seconds = \
            sum(metrics.shard_solve_seconds._values.values()) - shard_s_base
        result.cross_shard_reductions_total = int(
            metrics.cross_shard_reductions.value() - xshard_base)
        result.serving_fast_path_pods_total = int(
            metrics.serving_fast_path_pods.value() - fast_base)
        result.serving_coalesced_batches_total = int(
            metrics.serving_coalesced_batches.value() - coalesced_base)
        result.resident_plane_refreshes_total = int(
            metrics.resident_plane_refreshes.value() - refresh_base)
        result.resident_plane_refresh_seconds_total = \
            metrics.resident_plane_refresh.sum() - refresh_s_base
        result.solver_optimal_solves_total = int(
            metrics.solver_optimal_solves.value() - opt_base)
        result.solver_optimal_fallbacks_total = int(
            metrics.solver_optimal_fallbacks.value() - opt_fb_base)
        result.slice_gangs_bound_total = int(
            metrics.slice_gangs_bound.value() - slice_gangs_base)
        result.topology_plane_rebuilds_total = int(
            metrics.topology_plane_rebuilds.value() - topo_rb_base)
        result.slice_fragmentation_pct = \
            metrics.slice_fragmentation_pct.value()
        # Gauge is base-unit seconds now (metrics lint); the detail JSON
        # field keeps its ms name for report continuity.
        result.admission_window_ms = 1e3 * metrics.admission_window.value()

    async def _wait_bound(self, bound_keys: set, want: int,
                          deadline: float) -> None:
        """barrierOp: block until every created pod has a nodeName."""
        while time.monotonic() < deadline:
            if len(bound_keys) >= want:
                return
            await asyncio.sleep(0.01)
        raise TimeoutError(
            f"barrier: {len(bound_keys)}/{want} pods bound at timeout")

    @staticmethod
    async def _wait_keys(bound_keys: set, want: set,
                         deadline: float) -> None:
        """Scoped barrier: block until a specific key set is bound."""
        while time.monotonic() < deadline:
            if want <= bound_keys:
                return
            await asyncio.sleep(0.01)
        missing = len(want - bound_keys)
        raise TimeoutError(f"scoped barrier: {missing} pods unbound at timeout")

    @staticmethod
    def _fragmentation(sched: Scheduler) -> float:
        """Mean free-capacity fraction across nodes (%, lower = tighter)."""
        snapshot = sched.cache.update_snapshot()
        if not len(snapshot):
            return 0.0
        total = 0.0
        for ni in snapshot:
            fracs = []
            for r, alloc in ni.allocatable.res.items():
                if alloc > 0:
                    fracs.append(
                        max(0.0, (alloc - ni.requested.get(r)) / alloc))
            total += sum(fracs) / len(fracs) if fracs else 1.0
        return 100.0 * total / len(snapshot)

    @staticmethod
    def _fragmentation_occupied(sched: Scheduler) -> float:
        """Mean free-capacity fraction across OCCUPIED nodes (%, the r20
        packing metric — ops/solver.fragmentation_occupied's host twin):
        the all-nodes figure is placement-invariant once every pod
        places; this one drops when the same pods pack fewer, fuller
        nodes. Empty cluster → 0.0."""
        snapshot = sched.cache.update_snapshot()
        total = 0.0
        occupied = 0
        for ni in snapshot:
            if not ni.pods:
                continue
            occupied += 1
            fracs = []
            for r, alloc in ni.allocatable.res.items():
                if alloc > 0:
                    fracs.append(
                        max(0.0, (alloc - ni.requested.get(r)) / alloc))
            total += sum(fracs) / len(fracs) if fracs else 1.0
        return 100.0 * total / occupied if occupied else 0.0


def _result_count(metrics: SchedulerMetrics, result: str) -> int:
    return int(metrics.schedule_attempts.value(
        result=result, profile="default-scheduler"))


def load_config(path: str) -> list[dict]:
    import yaml
    with open(path) as f:
        return yaml.safe_load(f)


def run_suite(config: list[dict], backend_factory=None, batch_size: int = 1,
              filter_name: str | None = None, timeout: float = 600.0,
              through_apiserver=False) -> dict[str, dict]:
    """Run every (testcase × workload) pair, like BenchmarkPerfScheduling.
    `backend_factory()` -> (backend, backend_spec), fresh per workload
    (see `device_backend`); None runs the host scheduler."""
    out: dict[str, dict] = {}
    for case in config:
        for wl in case.get("workloads") or [{"name": "default", "params": {}}]:
            full = f"{case['name']}/{wl['name']}"
            if filter_name and filter_name not in full:
                continue
            backend, spec = backend_factory() if backend_factory \
                else (None, None)
            # Per-family runner settings: a family may pin the apiserver
            # boundary and a policy/audit load (PolicyScale carries the
            # 1k-tenant set) so headline rows are reproducible from
            # config alone.
            runner = PerfRunner(backend=backend, backend_spec=spec,
                                batch_size=batch_size,
                                scheduler_config=case.get("schedulerConfig"),
                                through_apiserver=case.get(
                                    "throughApiserver", through_apiserver),
                                policy_count=case.get("policyCount", 0),
                                policy_tenants=case.get(
                                    "policyTenants", 0),
                                audit_rules=[{"level": case["auditLevel"]}]
                                if case.get("auditLevel") else None)
            res = asyncio.run(runner.run(
                case["workloadTemplate"], wl.get("params") or {},
                timeout=timeout))
            out[full] = res.as_dict()
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("config", help="workload YAML")
    ap.add_argument("--backend", choices=["host", "tpu"], default="host")
    ap.add_argument("--batch-size", type=int, default=1)
    ap.add_argument("--chunk", type=int, default=None,
                    help="OVERRIDE the backend solve chunk (jit batch "
                         "signature); default 1024")
    ap.add_argument("--filter", default=None)
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-workload deadline in seconds (the 20k-agent "
                         "family boots longer than the 600s default)")
    ap.add_argument("--through-apiserver", choices=["", "http", "wire"],
                    default="",
                    help="cross the process boundary: all traffic (agent "
                         "watches included) rides the chosen apiserver "
                         "wire instead of direct store calls")
    args = ap.parse_args(argv)

    factory = None
    batch = args.batch_size
    # Before anything is constructed: with KTPU_PROCESSES >= 2 this
    # process hands the leader replica a spec and stays off JAX.
    nproc = resolve_processes()
    if args.backend == "tpu":
        batch = max(batch, 128)
        chunk = None if args.chunk is None \
            else max(min(args.chunk, batch), 2)
        if nproc <= 1:  # the leader enables its own (schedproc.py)
            from kubernetes_tpu.utils.compile_cache import (
                enable_compile_cache,
            )
            enable_compile_cache()
        factory = lambda: device_backend(chunk, nproc)  # noqa: E731
    boundary = {"": False, "http": True, "wire": "wire"}[
        args.through_apiserver]
    results = run_suite(load_config(args.config), backend_factory=factory,
                        batch_size=batch, filter_name=args.filter,
                        timeout=args.timeout, through_apiserver=boundary)
    print(json.dumps(results, indent=2))
    if args.backend == "tpu":
        return device_exit(list(results.values()), nproc)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
