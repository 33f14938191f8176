"""TPUBackend: the batched scheduling backend behind `Scheduler(backend=...)`.

North-star seam (BASELINE.json): the reference's per-pod
`findNodesThatFitPod` / `prioritizeNodes` 16-goroutine fan-out
(pkg/scheduler/framework/parallelize/parallelism.go, schedule_one.go) becomes
one XLA program over `(C classes × N nodes)` class-dictionary mask/score
planes (pods dedupe into C equivalence classes; a `(P,)` index maps pods to
rows) plus a batched assignment solve (ops/solver.py). The plugin contract
is preserved:

- Plugins with device kernels (ops/kernels.py) — NodeResourcesFit,
  NodeResourcesBalancedAllocation, TaintToleration — run fully on device.
- Static node-predicate plugins (NodeAffinity, NodeName, NodeUnschedulable,
  ImageLocality) run host-side ONCE per distinct pod spec signature per
  node-set epoch and are cached as dense rows (template-derived workloads
  have a handful of signatures). Semantics are *exactly* the host plugin's —
  the cached row is produced by calling its `filter()`/`score()`.
- The constraint families are DEVICE-RESIDENT end to end: InterPodAffinity
  compiles every term shape (namespaceSelector included — resolved to
  namespace sets at table-build time) into dense rows over interned label
  signatures, and PodTopologySpread rides the union scan table
  (heterogeneous templates, minDomains, restricted node eligibility,
  non-self-matching selectors).
- Device planes are CLASS-DICTIONARY native: pods dedupe into
  equivalence classes keyed by (request row, toleration row, host
  filter-row signatures, score-row signatures), and the wire ships one
  (C, N/8) bit-packed mask plane + one (C, N) float16 static-score
  plane + a (P,) int32 class index — never a per-pod (P, N) plane, on
  host OR device (the fused program computes fit/taint/score planes at
  class level and every solver scan gathers `class_idx[pod]` per step).
  Template batches have a handful of classes, so per-chunk plane work
  is O(C·N) ≈ chunk/C smaller than the per-pod format this replaces
  (and the r7 row-dictionary score wire is subsumed by it). Single-
  allowed-column host rows (NodeName, DRA allocated-claim pins) ride a
  sparse per-pod exception column instead of splitting a class. A chunk
  with more classes than KTPU_CLASS_PAD — or KTPU_CLASS_PAD=0 —
  degrades structurally to per-pod planes (C == P, identity index),
  counted as class_split_fallbacks.
- The remaining per-pod host rows (NodePorts conflicts, volume plugins,
  DRA shapes the tensors can't answer) are Skip-gated per pod and COUNTED
  (kind="host_fallback"; bench detail `host_fallback_pods`) — residency
  regressions are data, not stderr noise.

Per-plugin unsat masks are kept (not fused away) so FailedScheduling events
retain per-plugin reasons (SURVEY §5.5 explainability requirement); they are
materialized host-side lazily, only for pods that end the cycle unassigned.

After the solve, assignments are **verified** host-side against a working
snapshot (exact integer arithmetic + full plugin re-check for pods with
stateful constraints); violators are returned unassigned and requeue — the
"solve, round, verify, re-queue" loop SURVEY §7 hard-part #1 prescribes.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from functools import partial
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from kubernetes_tpu.api.labels import ns_contains
from kubernetes_tpu.utils import flags
from kubernetes_tpu.utils.locking import check_dispatch_seam
from kubernetes_tpu.utils.jax_platform import cpu_requested
from kubernetes_tpu.ops import kernels, solver
from kubernetes_tpu.ops.tensorize import ClusterTensors, PodBatch
from kubernetes_tpu.scheduler.framework import (
    MAX_NODE_SCORE,
    CycleState,
    Framework,
    Status,
    UNSCHEDULABLE_AND_UNRESOLVABLE,
)
from kubernetes_tpu.scheduler.plugins.noderesources import (
    insufficient_resources,
)
from kubernetes_tpu.scheduler.types import NodeInfo, PodInfo, Snapshot

logger = logging.getLogger(__name__)

# jax.profiler host annotations (SURVEY §5.1): bracket the solve
# dispatch/fetch so device-solve chunks appear in the SAME jax-profiler
# timeline as the host-side work when a --profile-dir trace is taken.
# TraceMe-backed — near-free when no trace is active.
_TRACE_ANNOTATION = jax.profiler.TraceAnnotation
#: what _span returns with tracing off: stateless, safe to re-enter.
_NULL_CM = contextlib.nullcontext()
_STEP_ANNOTATION = jax.profiler.StepTraceAnnotation

#: Plugins with full device kernels.
DEVICE_FILTER_PLUGINS = {"NodeResourcesFit", "TaintToleration"}
DEVICE_SCORE_PLUGINS = {
    "NodeResourcesFit", "NodeResourcesBalancedAllocation", "TaintToleration"}

#: Pipeline-depth OVERRIDE (sweeps/debugging). Unset = the AdaptiveTuner's
#: table picks the depth; see its policy docstring. Read LIVE per use —
#: the old import-time read forced callers (bench.py) to export the
#: env var before this module imported, an ordering footgun the flag
#: lint (analysis/flags_pass.py) now rejects.
def _pipeline_depth_override() -> int | None:
    return flags.get("KTPU_PIPELINE_DEPTH")


#: Solve chunk, before and after the tuner has decided (see its table).
_DEFAULT_CHUNK = 1024


def _shortlist_k_override() -> int | None:
    """Shortlist OVERRIDE (sweeps/differential tests): an integer K forces
    the shortlist width regardless of the tuner's policy, 0 disables
    pruning entirely. Unset = flagless — the AdaptiveTuner derives K from
    the chunk width and the observed fallback rate (see its shortlist_k
    policy). Live read, like the pipeline depth."""
    return flags.get("KTPU_SHORTLIST_K")

#: Class-dictionary plane cap: the maximum number of REAL pod
#: equivalence classes per chunk (plane row 0 is reserved for the empty
#: class — padding pods, unknown-resource pods, conflicting pins — so
#: plane rows ≤ KTPU_CLASS_PAD + 1, bucketed to the next power of two
#: for a stable jit signature). Pods share a class when they share
#: (request row, toleration row, host filter-row set, score-row parts);
#: template batches have a handful, so the planes are (C, N) with
#: C ≪ chunk — a 1024-pod chunk at 50k nodes ships ~2 class rows
#: (~25 KB) where the per-pod format shipped a 6.4 MB packed mask and
#: materialized a 100+ MB score plane on device. A chunk with more
#: classes than this cap — or KTPU_CLASS_PAD=0 — falls back to
#: per-pod planes (C == P, identity index): structurally the pre-class
#: dense format, bit-identical assignments, counted per pod as
#: class_split_fallbacks.
DEFAULT_CLASS_PAD = 31


def class_pad() -> int:
    """Effective class cap: 0 = class planes off (per-pod fallback).
    Read per assign() so tests/bench can flip the env knobs live."""
    return max(0, flags.get("KTPU_CLASS_PAD"))


def _pow2(n: int) -> int:
    """The least power of two ≥ n (≥ 1): carry shapes repeat across
    chunks, so their programs do."""
    out = 1
    while out < n:
        out <<= 1
    return out


def _class_rows_bucket(n_classes: int) -> int:
    """Plane row count for n_classes real classes + the reserved empty
    row 0, bucketed to a power of two (≥ 2) so jit signatures repeat."""
    rows = 2
    while rows < n_classes + 1:
        rows <<= 1
    return rows


class AdaptiveTuner:
    """Flagless solve routing: pipeline depth, shortlist, wavefront,
    solve mode and the serving tier's policy rows. `--chunk`
    and KTPU_PIPELINE_DEPTH are overrides.

    The device is locally attached: `probe()` — the median wall of three
    tiny put+fetch round trips — read 1.1 ms on the TPU v5e (PR 21, chip
    run; 0.9 ms median over 50), so no row keys on transfer latency and
    the value is only reported (chip_smoke.py). What is left of the
    chunk/depth table (tests/test_shortlist_smoke.py pins it):

    | when                                         | chunk | depth |
    |----------------------------------------------|-------|-------|
    | before WARMUP_CHUNKS chunks, N < 32768       | 1024  | 4     |
    | after them, or N ≥ 32768 from the 1st assign | 1024  | 2     |

    The chunk of 1024 and the depths were chosen on a CPU container and
    have not been swept on the chip (ROADMAP A4): against 2048 or 512,
    not measured. What the chip has shown (PERF.md, PR 26, TPU v5 lite,
    `kwok-50k.drain`): a 1,024-pod chunk at 50,000 nodes — shortlist
    K = 1024, W = 64, four orders — is 8.8 ms of device now that the
    wave scan looks each candidate up once (725 ms while every wave
    member gathered its own (K+P)-wide candidate row), so the chunk's
    fixed host cost (prep ~14 ms, `solver.tensors` ~27 ms a batch), not
    its scan, is what a wider chunk would amortize. Node count is
    STRUCTURAL (known at the first assign), so the large-N row applies
    without waiting out the warmup window. The pre-warm-up depth of 4
    is the constructor's default, and is what a drain's first
    super-batch runs.

    **Shortlist width** (the r10 pruned solve): K = chunk × boost, active
    only while the node count dwarfs the scan width (N ≥ 4·(K + chunk) —
    below that the narrow scan plus prefilter costs more than it saves;
    the 5k preset measured ~10% behind its full scan at factor 2).
    K defaults to the chunk width because the sequential-equivalent scan
    can visit one fresh node per pod: a round-robin workload (uniform
    nodes — the 50k preset) needs the whole chunk's winners inside one
    shortlist or every pod past the K-th pays the N-wide fallback. The
    boost doubles (to ×8 max) at assign() boundaries whenever the
    observed fallback rate crosses 25% — fallbacks are exact but O(N), so
    a persistently-missing shortlist must widen or it silently degrades
    to the unpruned solve plus overhead.
    """

    WARMUP_CHUNKS = 8
    #: node count from which the large-N chunk row applies.
    LARGE_N = 32768
    #: shortlist activates when n_real ≥ FACTOR × (K + chunk). Measured
    #: on the CPU container (r10): at N=5k / chunk 1024 the pruned width
    #: (2048) plus the per-chunk prefilter/top-k ran ~10% BEHIND the
    #: r9-tuned full scan, while at N=50k it is a 3–6× win — the factor
    #: is set so the 5k headline keeps its full scan and activation
    #: starts where the width ratio pays (≥4×).
    SHORTLIST_FACTOR = 4
    SHORTLIST_MAX_BOOST = 8
    SHORTLIST_FALLBACK_RATIO = 0.25
    #: minimum solved pods before the fallback rate is trusted.
    SHORTLIST_MIN_SAMPLE = 512
    #: Block-index width (the two-pass block-sparse prefilter — see
    #: block_width()): node columns per aggregate block. 128 keeps the
    #: bound scan O(C·N/128) while M = 2·ceil((K+1)/128) selected
    #: blocks re-gather ~2K+ columns — comfortably inside the regime
    #: where the full (C,N) chunk-start pass is the measured wall
    #: (N ≥ LARGE_N with shortlist active).
    BLOCK_WIDTH = 128
    #: Wavefront policy rows (the r18 speculative solve): W pods per
    #: scan step. The two widths were picked from a sweep on a CPU
    #: container; on the chip no other width has been tried: not
    #: measured (ROADMAP A4). At W=64 the 50k cell commits every wave
    #: speculatively (replays 0, shortlist fallbacks 0: ledger, PR 24
    #: on) and its 16 wave steps a chunk take 7.5 ms of device together
    #: (PERF.md, PR 26). Node count is STRUCTURAL, so like the large-N
    #: chunk row the tier applies from the first assign.
    #: Conflict rate is WORKLOAD-dependent (packing strategies re-pick
    #: debited nodes; contested spread domains force replays), so the
    #: width halves at decide() boundaries whenever the measured replay
    #: fraction crosses the ratio — replays are exact but serial, so a
    #: persistently-conflicting wave must narrow or the speculation
    #: overhead is pure waste (the shortlist boost rule, mirrored).
    WAVE_WIDTH_SMALL = 32
    WAVE_WIDTH_LARGE = 64
    WAVE_REPLAY_RATIO = 0.25
    WAVE_MIN_SAMPLE = 512
    #: Admission-window policy row (the serving tier, ROADMAP #3 — see
    #: serving/admission.py for the state machine that consults it).
    #: Thresholds are seeded from the r15 churn knee sweep (BASELINE
    #: r15, 5k nodes): the knee sat at 1000/s and the 250/s trickle row
    #: was the p999 pathology — at or below the idle threshold (set
    #: just ABOVE the trickle row, so rate-estimate jitter around
    #: exactly 250/s can't flap it into coalescing) every pod
    #: dispatches IMMEDIATELY (the fast path is sub-ms; holding a lone
    #: pod buys nothing), above it the window is sized to coalesce
    #: ~ADMISSION_TARGET_PODS at the estimated offered rate, capped so
    #: no pod ever waits past the cap (the cap IS the p50 budget).
    ADMISSION_IDLE_RATE = 300.0
    ADMISSION_TARGET_PODS = 8.0
    ADMISSION_MAX_WINDOW_S = 4e-3
    #: Fast-path dispatch cap: the largest popped dispatch worth
    #: draining pod-by-pod through the pinned C=1 solve instead of one
    #: padded chunk. The crossover is the measured ratio — a chunk's
    #: wall is fixed (scan over the padded width; ~0.35 s at 5k on the
    #: CPU container, BASELINE r15/r16) while the fast path pays
    #: ~1–2 ms per pod, so anything under chunk/fast pods is faster
    #: serially AND keeps the queue in the lone-pod regime instead of
    #: locking into batch-every-chunk-wall (the r15 trickle pathology:
    #: arrivals accumulating during one chunk guarantee the next pop is
    #: another chunk). Seeds cover the pre-measurement window; the
    #: serving tier feeds both EWMAs from its own dispatches.
    FAST_PATH_SEED_CHUNK_S = 0.25
    #: pre-measurement fast-wall seed: deliberately OPTIMISTIC (1 ms —
    #: the measured 5k wall is ~0.6 ms) so the seeded rate limit
    #: (0.5/1 ms = 500/s) clears the 250/s trickle with margin; a
    #: too-conservative seed suppressed the fast path before any
    #: sample could land and the suppression was self-sustaining.
    FAST_PATH_SEED_SOLVE_S = 1e-3
    #: node count the 1 ms solve seed was measured at; an unmeasured
    #: fast wall seeds at SEED_SOLVE_S x (n / CALIB_N) because solve_one
    #: is a full-N scan (see _fast_wall_seed).
    FAST_PATH_SEED_CALIB_N = 5000
    FAST_PATH_CAP_MIN = 8
    FAST_PATH_CAP_MAX = 512
    #: Batch-optimal (Sinkhorn) routing policy row (r20): `auto`
    #: engages only where the latency budget allows — drain/rollout-
    #: scale chunks and gang placement. The plan is a fixed per-chunk
    #: device cost (KTPU_SINKHORN_ITERS dense (C,N) passes), so a chunk
    #: below this many real pods keeps the greedy scan — the iteration
    #: cost would dominate what the rounding saves. Serving single-pod
    #: traffic never reaches this policy at all (solve_one is a separate
    #: pinned program), and gang chunks route optimal at ANY width: all-
    #: or-nothing placement is exactly where greedy's myopia strands
    #: feasible gangs.
    OPTIMAL_MIN_PODS = 64
    #: Serial fast-drain is only right while the OFFERED rate is within
    #: its capacity (1/fast_wall) with headroom: above this utilization
    #: the pipelined batch path must take over or the serial drain
    #: itself becomes the bottleneck — a sustained drain through a
    #: shared-loop wire self-throttles its own creates to the drain
    #: rate, so backlog alone never reveals the pressure.
    FAST_PATH_UTILIZATION = 0.5

    def __init__(self):
        self.latency_s: float | None = None
        self.total_chunks = 0
        #: node count of the latest assign() — structural signal for the
        #: large-N row and the shortlist policy (set by the backend).
        self.n_nodes = 0
        self.shortlist_boost = 1
        self.solve_pods = 0
        self.solve_fallbacks = 0
        #: wavefront feedback state: the policy W divides by wave_shrink
        #: (replay-fraction feedback can only NARROW the wave; the
        #: override pins it).
        self.wave_shrink = 1
        self.wave_commits = 0
        self.wave_replays = 0

    def probe(self) -> float:
        """Median tiny put+fetch round trip (no jit, pure transfer).
        Reported, not acted on — see the class docstring."""
        if self.latency_s is None:
            samples = []
            probe = np.zeros((64,), dtype=np.int32)
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(jax.device_put(probe))
                samples.append(time.perf_counter() - t0)
            self.latency_s = sorted(samples)[1]
        return self.latency_s

    def observe_chunk(self) -> None:
        self.total_chunks += 1

    def observe_solve(self, pods: int, fallbacks: int) -> None:
        """Shortlist hit-rate sample from one finalized chunk."""
        self.solve_pods += pods
        self.solve_fallbacks += fallbacks

    def observe_wave(self, commits: int, replays: int) -> None:
        """Wavefront commit/replay sample from one finalized chunk."""
        self.wave_commits += commits
        self.wave_replays += replays

    def solve_mode(self, p_real: int, has_gang: bool, spread: bool,
                   class_mode: bool, exclusive: bool = False,
                   carried: bool = False) -> tuple[str, bool]:
        """('greedy' | 'optimal', structural_fallback) for one chunk —
        the KTPU_SOLVE_MODE policy row. 'greedy' pins the r18 scan call
        graph (the kill switch). Optimal requires class planes (the
        (C,N) cost matrix IS the class dictionary), a non-spread chunk
        (the spread scan's non-monotone domain gating has no transport
        relaxation), a chunk that is not `exclusive` (a pod of it
        carries a required anti-affinity term, so pods of the chunk may
        exclude each other, which no column capacity states: a plan
        sends a whole hostname group to a handful of nodes, the host
        verify keeps one pod on each and requeues the rest; the greedy
        scan debits a node as it takes it and spreads the group) and a
        chunk that is not `carried` (its own placements move a pod's
        InterPodAffinity score — "co-locate my replicas" — which a plan
        over chunk-start scores cannot state: the scan recomputes and
        renormalises that score at every step from counts it carries);
        an ineligible chunk degrades structurally to greedy with the
        fallback bit set so solver_optimal_fallbacks_total records it.
        Under 'auto' the
        optimal mode engages for gang chunks and for chunks of at least
        OPTIMAL_MIN_PODS real pods (drain/rollout waves) — EXCEPT at
        the structural large-N row (n_nodes >= LARGE_N, the same signal
        as the chunk/W/block-width rows), where non-gang chunks keep
        the greedy scan: the Sinkhorn plan is a fixed
        KTPU_SINKHORN_ITERS dense (C,N) passes per chunk, so above
        LARGE_N the plan itself is the linear-in-N solve wall the block
        index removes (measured @ 200k: ~20 s/chunk optimal vs < 1 s
        greedy with the block-sparse prefilter) — the latency-budget
        rationale that routes drains optimal inverts. Gang chunks
        still route optimal at ANY node count (all-or-nothing
        placement is where greedy's myopia strands feasible gangs),
        and KTPU_SOLVE_MODE=optimal still pins every eligible chunk
        (the policy row only shapes 'auto')."""
        raw = flags.get("KTPU_SOLVE_MODE")
        if raw == "greedy":
            return "greedy", False
        eligible = class_mode and not spread and not exclusive \
            and not carried
        if raw == "optimal":
            return ("optimal", False) if eligible else ("greedy", True)
        if not (has_gang or p_real >= self.OPTIMAL_MIN_PODS):
            return "greedy", False
        if not has_gang and self.n_nodes >= self.LARGE_N:
            return "greedy", False
        return ("optimal", False) if eligible else ("greedy", True)

    def wave_width(self, chunk: int) -> int:
        """Wavefront width for a chunk; 1 = degenerate one-member waves.
        The KTPU_WAVEFRONT kill switch is routed by the backend (it
        selects the W=1 scan FUNCTIONS, not a one-member wave), so this
        is pure width policy: the override, else the swept node-count
        tier narrowed by the replay-fraction feedback."""
        override = flags.get("KTPU_WAVE_WIDTH")
        if override is not None:
            return max(1, min(override, chunk))
        w = self.WAVE_WIDTH_LARGE if self.n_nodes >= self.LARGE_N \
            else self.WAVE_WIDTH_SMALL
        return max(1, min(w // self.wave_shrink, chunk))

    @classmethod
    def _fast_wall_seed(cls, n_nodes: int) -> float:
        """Unmeasured-wall seed for the fast-path gates. The 1 ms base
        is the measured 5k-node solve_one wall; the wall is a full-N
        scan, so the seed scales linearly from that calibration point
        (200k → 40 ms). Without the scaling, a cold estimate at large N
        reads the serial drain ~100× too fast, opens the cap to its
        512 clamp, and one big dispatch serial-drains at ~125 ms/pod
        while the self-throttled wire hides the pressure from the
        mid-drain abort (measured: 243 pods, +30 s of 200k drain
        window)."""
        return cls.FAST_PATH_SEED_SOLVE_S \
            * max(1, n_nodes / cls.FAST_PATH_SEED_CALIB_N)

    @classmethod
    def fast_path_cap(cls, chunk_wall_s: float, fast_wall_s: float,
                      n_nodes: int = 0) -> int:
        """Largest dispatch the serving tier drains pod-by-pod through
        the fast path — pure policy over the two measured walls (the
        node count only shapes the seed while the fast wall is still
        unmeasured)."""
        if fast_wall_s <= 0:
            fast_wall_s = cls._fast_wall_seed(n_nodes)
        if chunk_wall_s <= 0:
            chunk_wall_s = cls.FAST_PATH_SEED_CHUNK_S
        return int(min(max(chunk_wall_s / fast_wall_s,
                           cls.FAST_PATH_CAP_MIN), cls.FAST_PATH_CAP_MAX))

    @classmethod
    def fast_path_rate_limit(cls, fast_wall_s: float,
                             n_nodes: int = 0) -> float:
        """Highest estimated offered rate (pods/s) the serving tier
        still serial-drains at — pure policy over the measured wall."""
        if fast_wall_s <= 0:
            fast_wall_s = cls._fast_wall_seed(n_nodes)
        return cls.FAST_PATH_UTILIZATION / fast_wall_s

    @classmethod
    def admission_window(cls, rate_est: float) -> float:
        """Coalesce window (seconds) for the serving admission tier —
        pure policy. 0.0 = dispatch immediately."""
        if rate_est <= cls.ADMISSION_IDLE_RATE:
            return 0.0
        return min(cls.ADMISSION_TARGET_PODS / rate_est,
                   cls.ADMISSION_MAX_WINDOW_S)

    def shortlist_k(self, chunk: int, n_real: int) -> int:
        """Shortlist width for a chunk, 0 = keep the full N-wide scan."""
        override = _shortlist_k_override()
        if override is not None:
            k = override
            return k if 0 < k < n_real else 0
        k = chunk * self.shortlist_boost
        if n_real < self.SHORTLIST_FACTOR * (k + chunk):
            return 0
        return k

    def block_width(self, n_pad: int, n_real: int, shortlist_k: int) -> int:
        """Block width for the two-pass block-sparse prefilter, 0 = the
        full-width r18/r21 prefilter (the structural kill-switch shape).

        Policy: the block index only composes with an active shortlist
        (it prunes the shortlist prefilter's own O(C·N) pass — without a
        threshold there is nothing to bound against), and only where the
        node count is the wall it was built for — n_real ≥ LARGE_N, the
        same STRUCTURAL signal as the large-N chunk and wavefront rows,
        so it lands on the first assign with no mid-measured-phase
        recompile. Below that the bound scan plus gather costs more than
        the pruned chunk-start pass saves (the shortlist's own 5k
        lesson, one level up). The M+1 ≤ B shape guard routes 0 for any
        width/N combination where the selection could not even leave one
        block unselected (top_k needs M+1 distinct blocks; a fully-
        selected index prunes nothing). KTPU_BLOCK_WIDTH overrides the
        width (0 disables the index).
        """
        override = flags.get("KTPU_BLOCK_WIDTH")
        bw = self.BLOCK_WIDTH if override is None else override
        if bw <= 0 or shortlist_k <= 0 or n_real < self.LARGE_N:
            return 0
        b = -(-n_pad // bw)
        m = 2 * (-(-(shortlist_k + 1) // bw))
        if m + 1 > b:
            return 0
        return bw

    def decide(self) -> int | None:
        """The pipeline depth to apply, or None while still warming up;
        also where the shortlist and wavefront feedback lands."""
        if self.solve_pods >= self.SHORTLIST_MIN_SAMPLE:
            if self.solve_fallbacks > self.SHORTLIST_FALLBACK_RATIO \
                    * self.solve_pods \
                    and self.shortlist_boost < self.SHORTLIST_MAX_BOOST:
                self.shortlist_boost *= 2
                logger.info(
                    "adaptive tuner: shortlist fallback rate %.0f%% "
                    "-> boost x%d", 100.0 * self.solve_fallbacks
                    / self.solve_pods, self.shortlist_boost)
            self.solve_pods = self.solve_fallbacks = 0
        wave_total = self.wave_commits + self.wave_replays
        if wave_total >= self.WAVE_MIN_SAMPLE:
            if self.wave_replays > self.WAVE_REPLAY_RATIO * wave_total \
                    and self.wave_shrink < self.WAVE_WIDTH_LARGE:
                self.wave_shrink *= 2
                logger.info(
                    "adaptive tuner: wavefront replay fraction %.0f%% "
                    "-> shrink x%d", 100.0 * self.wave_replays
                    / wave_total, self.wave_shrink)
            self.wave_commits = self.wave_replays = 0
        # The large-N row rides a STRUCTURAL signal (node count), so it
        # applies from the very first assign — the one recompile lands
        # in warmup, not a measured phase.
        if self.total_chunks < self.WARMUP_CHUNKS \
                and self.n_nodes < self.LARGE_N:
            return None
        return 2

#: Gang (PodGroup) slots per chunk for the solver's all-or-nothing masking;
#: fixed so the jit signature is stable. Overflow gangs keep the Permit
#: barrier as their only atomicity (the reference behavior).
_GANG_PAD = 16

#: Static node-predicate plugins whose (pod-spec → node row) is cacheable by
#: spec signature while the node set is unchanged.
STATIC_ROW_PLUGINS = {"NodeAffinity", "NodeName", "NodeUnschedulable"}
STATIC_SCORE_PLUGINS = {"NodeAffinity", "ImageLocality"}

#: O(1)-per-pod activity gates mirroring each stateful plugin's own
#: PreFilter/PreScore Skip condition. Without these, merely *asking* a plugin
#: to skip costs O(N) per pod (e.g. InterPodAffinity.pre_score scans all
#: nodes for pods-with-affinity before skipping) — the 5k-node profile's top
#: hotspot. Invariant: a gate may only say "inactive" when the plugin would
#: Skip — PodTopologySpread's gate therefore asks the plugin for its
#: effective constraints (system/profile DEFAULT constraints apply to
#: labeled pods even with no explicit spec constraints).
_FILTER_ACTIVE = {
    "InterPodAffinity": lambda plugin, pi, snap: bool(
        pi.required_affinity_terms or pi.required_anti_affinity_terms
        or snap.have_pods_with_required_anti_affinity),
    "PodTopologySpread": lambda plugin, pi, snap: bool(
        plugin._constraints_for(pi, "DoNotSchedule")),
    "NodePorts": lambda plugin, pi, snap: bool(pi.host_ports),
    "VolumeBinding": lambda plugin, pi, snap: bool(pi.pvc_names),
    "VolumeRestrictions": lambda plugin, pi, snap: bool(pi.pvc_names),
    "VolumeZone": lambda plugin, pi, snap: bool(pi.pvc_names),
    "NodeVolumeLimits": lambda plugin, pi, snap: bool(pi.pvc_names),
    "NodeResourceTopologyMatch":
        lambda plugin, pi, snap: plugin.active_for(pi),
    "DynamicResources":
        lambda plugin, pi, snap: plugin.active_for(pi),
    "TopologySlice":
        lambda plugin, pi, snap: plugin.active_for(pi),
}
_SCORE_ACTIVE = {
    "InterPodAffinity": lambda plugin, pi, snap: bool(
        pi.preferred_affinity_terms or pi.preferred_anti_affinity_terms
        or snap.have_pods_with_affinity),
    "PodTopologySpread": lambda plugin, pi, snap: bool(
        plugin._constraints_for(pi, "ScheduleAnyway")),
    "NodeResourceTopologyMatch":
        lambda plugin, pi, snap: plugin.active_for(pi),
}


def compress_score_wire(host_scores: "np.ndarray") -> "np.ndarray":
    """Pick the wire dtype for a dirty host-score plane.

    f16 (half the upload bytes) only while it's faithful: weighted sums
    past 1024 sit in f16's ≥0.5-resolution band (near-ties can flip vs
    the host path) and past 65504 overflow to inf. Oversized planes
    (plugin weights ~>10) ship f32 — 2× bytes on a rare path beats
    silently diverging from host-score parity. Scaling instead would skew
    this plane against the device-computed taint/fit/balanced terms it is
    summed with (the fused program casts to f32 on device either way).
    """
    import math
    if host_scores.size:
        # Two reductions, no temporaries (this sits on the dirty-upload
        # dispatch path): NaN/inf propagate through min/max, so the
        # finiteness check falls out of the same pass.
        lo, hi = float(host_scores.min()), float(host_scores.max())
        if not (math.isfinite(lo) and math.isfinite(hi)):
            raise ValueError("host score plane contains non-finite values")
        amax = max(-lo, hi)
    else:
        amax = 0.0
    return host_scores.astype(np.float16 if amax <= 1024.0 else np.float32)


@jax.jit
def _copy_pack(pack):
    """Chain-owned copy of a used-state pack: the donated fused solve
    consumes its carry input, so a buffer someone else keeps (the
    resident planes' base) must be copied before seeding the chain.
    Only called when donation is live (see _solve_program)."""
    return pack + 0


#: Lazily-resolved fused program: the chained used-state carry is
#: DONATED on accelerator backends only. The chain is the buffer's sole
#: consumer, so donation lets XLA update the (N, 2R+1) carry in place
#: instead of allocating per chunk. On CPU-jax it is measurably
#: CATASTROPHIC: input/output aliasing forces each dispatch to wait for
#: the previous program to release the buffer, serializing the chunk
#: pipeline the backend exists to overlap — the r18 same-container 50k
#: before/after measured 1644/1635 (no donation) vs 894–978 (donated)
#: pods/s, and 200k 1410 vs ~740 (BASELINE r18). Resolved on FIRST
#: dispatch, not import: jax.default_backend() initializes the jax
#: runtime, and the platform must stay configurable until then (the
#: conftest "set platform before jax initializes" contract).
_SOLVE_PROGRAM = None


def _solve_program():
    global _SOLVE_PROGRAM
    if _SOLVE_PROGRAM is None:
        if jax.default_backend() == "cpu":
            _SOLVE_PROGRAM = _mask_solve_update
        else:
            _SOLVE_PROGRAM = partial(
                jax.jit,
                static_argnames=("strategy", "use_spread", "shortlist_k",
                                 "wave_w", "solve_mode", "block_w"),
                donate_argnums=(1,))(_mask_solve_update.__wrapped__)
    return _SOLVE_PROGRAM


def _donation_live() -> bool:
    """True when the fused program donates its carry (accelerator
    backends) — the resident seed must be copied exactly then."""
    return _solve_program() is not _mask_solve_update


def solve_provenance() -> dict:
    """Solve-backend provenance for bench/perf output: which jax
    platform, device kind and count, library versions and host core
    count produced a number, and whether the fused program donates
    its carry — so a CPU pre-flight row can never be read as a chip
    row."""
    import jaxlib
    import libtpu
    return {
        "jax_platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "jax_device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "libtpu_version": libtpu.__version__,
        "cpu_count": os.cpu_count(),
        "carry_donation": _donation_live(),
    }


def _signature(plugin_name: str, pi: PodInfo) -> str:
    if plugin_name == "NodeName":
        return pi.node_name
    if plugin_name == "NodeUnschedulable":
        return repr(sorted(
            (t.get("key", ""), t.get("operator", ""))
            for t in pi.tolerations))
    if plugin_name == "NodeAffinity":
        return repr((pi.node_selector, pi.affinity.get("nodeAffinity")))
    if plugin_name == "ImageLocality":
        return repr(sorted(
            c.get("image", "") for c in pi.pod.get("spec", {}).get("containers") or []))
    raise KeyError(plugin_name)


@partial(jax.jit,
         static_argnames=("strategy", "use_spread", "shortlist_k",
                          "wave_w", "solve_mode", "block_w"))
def _mask_solve_update(alloc_q, used_pack, alloc_pods, class_pack,
                       cls_idx, exc_col,
                       taint_f_mat, taint_p_mat, class_mask, class_scores,
                       fit_col_w, bal_col_mask, shape_u, shape_s,
                       w_fit, w_bal, w_taint, taint_filter_on,
                       dom_onehot, cid_onehot, dom_counts, max_skew,
                       sp_min_ok, sp_haskey,
                       sp_applies, sp_contrib, perms, gang_onehot,
                       gang_required, sink_iters, sink_temp, n_real, p_real,
                       ipa, strategy: str, use_spread: bool, shortlist_k: int,
                       wave_w: int, solve_mode: str = "greedy",
                       block_w: int = 0):
    """One fused device pass: plugin masks → scores → assignment → state.

    The used-state (used_q ‖ used_nz_q ‖ used_pods, packed into ONE (N,2R+1)
    int32 array — every host→device transfer has a fixed cost, so inputs
    are packed to one upload apiece)
    is device-resident and CHAINED: the program returns the post-assignment
    state so the next chunk's solve can be dispatched without any host
    round-trip — SURVEY §2.8's pipelining row (solve batch k+1 overlaps
    verify/bind of batch k). Capacity accounting inside the solver is exact
    (quantized-conservative integers), so the chain is as correct as
    re-uploading from the host.

    CLASS-DICTIONARY planes (the native format — see _prep_chunk):

    - class_mask: (C, N/8) uint8 bit-packed host filter rows per pod
      equivalence class (row 0 = the reserved EMPTY class).
    - class_scores: (C, N) f16/f32 host score rows per class.
    - class_pack: (C, 2R+tf+tp) int32 — req_q ‖ req_nz_q ‖ untol_f ‖
      untol_p of each class's representative pod (identical across the
      class by the class key).
    - cls_idx: (P,) int32 pod → class row; exc_col: (P,) int32 — the
      sparse exception list: -1 = none, else the ONE column the pod is
      additionally restricted to (single-allowed-column host rows ride
      here instead of splitting a class).

    Every O(N) plane — mask unpack, fit/taint filter, taint score,
    chunk-start prefilter — is computed over C class rows, never P pod
    rows; the scans gather `cls_idx[pod]` per step (ops/solver.py
    `rows=`), so no (P, N) array exists anywhere in the program. The
    per-pod degenerate form (C == P, cls_idx == arange, the
    KTPU_CLASS_PAD=0 kill switch / class-overflow fallback) runs the
    SAME program and is bit-identical by construction.

    shortlist_k > 0 switches the solve to the SHORTLIST-PRUNED scans
    (ops/solver.py): the prefilter computes chunk-start live scores per
    CLASS directly off the class planes, takes the per-class top-K
    columns plus the (K+1)-th value as exactness threshold, and the scan
    re-scores K + P candidate columns per step instead of N — falling
    back to the full row exactly when the bound check cannot prove the
    narrow winner global. Assignments are bit-identical to the full scan
    by construction (tests/test_shortlist_solver.py is the differential
    guard).

    solve_mode == "optimal" is the r20 BATCH-OPTIMAL mode: an entropic
    transport plan (ops/solver.sinkhorn_plan) over the same (C,N) class
    planes replaces the greedy scorer for this chunk. The plan's cost
    matrix is the greedy scorer's own chunk-start scores (the warm
    start — it refines exactly the preferences the r18 scan would have
    ranked), its marginals are pods-per-class and remaining pod slots,
    and its log becomes the scan's `static_scores` with the live
    re-scoring weights zeroed — so the ROUNDING pass is the unmodified
    r18 scan machinery against live capacity planes and every emitted
    assignment is feasible by construction (gang all-or-nothing masking
    and multistart orders apply unchanged). "greedy" (the
    KTPU_SOLVE_MODE kill switch and the structural-fallback route for
    spread/per-pod chunks) traces the r18 call graph verbatim —
    `sink_iters`/`sink_temp` are dead inputs there and XLA drops them.

    wave_w > 1 switches to the SPECULATIVE WAVEFRONT scans: W pods per
    scan step against the same carry, prefix-distinct argmax commits,
    and exact serial replay of conflicted waves — assignments stay
    bit-identical at every W (tests/test_wavefront_solver.py), the scan
    length drops P → P/W on low-conflict workloads, and W is part of the
    chunk program key (one compile per (shapes, strategy, spread, K, W)).
    The spread∩shortlist combination keeps its W=1 scan — wavefront and
    shortlist compose, spread composes with wavefront, all three
    together would multiply the replay conditions for a chunk shape the
    presets never hit. wave_w == 0 is the KTPU_WAVEFRONT kill-switch
    shape: the pre-wavefront call graph, structurally.

    `used_pack` is DONATED on accelerator backends (the _solve_program
    variant): the chunk chain is its only consumer — each dispatch
    consumes the previous chunk's output (or the one-off seed _start
    uploads/copies), so XLA may update the carry in place instead of
    allocating a fresh (N, 2R+1) buffer per chunk. On CPU the aliasing
    serializes the pipeline and donation stays off (measured ~1.7–1.9×
    worse; see the _solve_program note and BASELINE r18). When donation
    is live, the resident planes' base pack is never passed here
    directly (the serving seed is copied first; see _start).

    `block_w > 0` (static, part of the program key) swaps the shortlist
    prefilter for the TWO-PASS BLOCK-SPARSE form (ops/solver.py
    `block_bound_prefilter`): per-block aggregate bounds gate which node
    columns the chunk-start score pass touches, exactly — an in-program
    lax.cond falls back to the full-width pass whenever the bound
    predicate cannot prove the gathered top-K global. `n_real` (traced)
    excludes bucket-padding columns from the aggregates. block_w == 0 is
    the KTPU_BLOCK_WIDTH=0 kill-switch shape: the full-width r18/r21
    prefilter call graph, structurally.

    `p_real` (traced int32) is the chunk's real pod count. The chunk is
    padded to P so that one program serves every chunk; the padding sits
    at its end, places nothing and moves no state, so every scan stops
    after `p_real` pods (ceil(p_real / W) waves) instead of walking the
    padded width (ops/solver.py `_scan_real`). Traced, not static: a
    trickled chunk of two pods and a full one run the same executable,
    and every output keeps its padded shape and contents.

    `ipa` is None, or the InterPodAffinity carry of a chunk whose own
    placements move some pod's InterPodAffinity score (ops/solver.py
    `_ipa_score`: the raw weights by node and topology key at the
    snapshot, what each placed pod adds, the per-pod rows, and `placed`
    (T, N), this assign()'s placements so far). Such a chunk takes the
    identity-order W = 1 scan (the spread scan where it has spread pods)
    and never a plan or a shortlist: a score that moves everywhere when
    its maximum moves has no chunk-start bound. Its shapes are the
    program key; None traces the call graph without it.

    Returns (assign (P+5,) — the tail is [shortlist fallbacks, wave
    commits, wave replays, blocks scanned, blocks pruned] riding the one
    fetch — used_pack', fit0 (C,N), taint_ok (C,N), dom_counts',
    placed' or None). The diagnostic planes are CLASS-level; consumers
    gather through cls_idx host-side.
    """
    # Wire decompression (see _prep_chunk): masks arrive bit-packed
    # uint8 (C, N/8) big-endian, scores float16 — unpack/cast on device
    # where the FLOPs are free and the upload bytes are not.
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    cmask = ((class_mask[:, :, None] >> shifts) & 1).reshape(
        class_mask.shape[0], -1).astype(jnp.bool_)[:, : alloc_q.shape[0]]
    host_scores = class_scores.astype(jnp.float32)

    r = alloc_q.shape[1]
    tf = taint_f_mat.shape[1]
    used_q = used_pack[:, :r]
    used_nz_q = used_pack[:, r:2 * r]
    used_pods = used_pack[:, 2 * r]
    c_req_q = class_pack[:, :r]
    c_req_nz_q = class_pack[:, r:2 * r]
    c_untol_f = class_pack[:, 2 * r:2 * r + tf].astype(jnp.bool_)
    c_untol_p = class_pack[:, 2 * r + tf:].astype(jnp.bool_)
    # Per-pod request rows are class gathers (tiny: (P,R)); the scans
    # debit with them while every plane stays (C,N).
    req_q = c_req_q[cls_idx]
    req_nz_q = c_req_nz_q[cls_idx]

    fit0 = kernels.fit_filter_mask(
        alloc_q, used_q, used_pods, alloc_pods, c_req_q)        # (C,N)
    taint_ok = kernels.taint_filter_mask(taint_f_mat, c_untol_f)
    taint_ok = taint_ok | jnp.logical_not(taint_filter_on)
    mask = cmask & taint_ok
    feasible = mask & fit0

    # Capacity-independent score components; the capacity-dependent plugins
    # (fit/balanced) are re-scored live inside the greedy scan. Taint
    # normalization runs over the CLASS feasible set: exception-pinned
    # pods keep their class row (their argmax ranges over one column, so
    # scores cannot change their assignment).
    static_scores = host_scores + w_taint * kernels.taint_toleration_score(
        taint_p_mat, c_untol_p, feasible)

    free_q = alloc_q - used_q
    free_pods = alloc_pods - used_pods
    dom_counts2 = dom_counts
    nfall = jnp.int32(0)
    wave_com = jnp.int32(0)
    wave_rep = jnp.int32(0)
    blk_scanned = jnp.int32(0)
    blk_pruned = jnp.int32(0)
    n_pad = alloc_q.shape[0]
    placed2 = None
    if solve_mode == "optimal" and not use_spread and ipa is None:
        # Batch-optimal mode (see docstring): transport plan over the
        # class planes, then the SAME scans round it against live
        # capacity with the re-scoring weights zeroed. Runs BEFORE the
        # shortlist prefilter so a composed shortlist prunes the plan
        # scores it will scan (exactness preserved).
        sc0_cost = kernels.chunk_start_scores(
            alloc_q, used_nz_q, c_req_nz_q, static_scores,
            fit_col_w, bal_col_mask, shape_u, shape_s, w_fit, w_bal,
            strategy)
        row_counts = jnp.zeros(
            (cmask.shape[0],), jnp.float32).at[cls_idx].add(1.0)
        static_scores, _ = solver.sinkhorn_plan(
            feasible, sc0_cost, row_counts, jnp.maximum(free_pods, 0),
            sink_iters, sink_temp)
        w_fit = jnp.float32(0.0)
        w_bal = jnp.float32(0.0)
    if shortlist_k:
        # Shortlist prefilter: chunk-start live scores per pod CLASS
        # (C rows, not P — the planes already ARE class rows), top-K
        # columns + the (K+1)-th value as the scans' exactness
        # threshold. Chunk-start capacity feasibility folds in (capacity
        # only decreases within a chunk); spread gating deliberately
        # does not (it is non-monotone and exact in-scan — see the
        # spread solver).
        # block_w > 0 routes the TWO-PASS BLOCK-SPARSE form: an O(C·B)
        # per-block bound scan gates which columns the chunk-start pass
        # touches, falling back to the full-width pass in-program
        # whenever its exactness predicate cannot prove the gathered
        # top-K global (solver.block_bound_prefilter). Static routing:
        # block_w is part of the fused-program key like wave_w, and 0
        # (the KTPU_BLOCK_WIDTH=0 kill switch / small-N tuner decision /
        # M+1 > B shape guard) traces the r18/r21 full-width call graph
        # verbatim.
        if block_w > 0:
            sc0, cand_s, thresh_s, blk_scanned, blk_pruned = \
                solver.block_bound_prefilter(
                    alloc_q, used_nz_q, c_req_nz_q, static_scores,
                    feasible, fit_col_w, bal_col_mask, shape_u, shape_s,
                    w_fit, w_bal, strategy, n_real, shortlist_k,
                    block_w)
        else:
            sc0 = kernels.chunk_start_scores(
                alloc_q, used_nz_q, c_req_nz_q, static_scores,
                fit_col_w, bal_col_mask, shape_u, shape_s, w_fit, w_bal,
                strategy)
            cand_s, thresh_s = solver.shortlist_prefilter(
                feasible, sc0, shortlist_k)
        # has_node: class-level any(), narrowed to the pinned column for
        # exception pods (their only possibly-feasible node).
        has_c = jnp.any(mask, axis=1)                           # (C,)
        has_node = has_c[cls_idx]
        safe_e = jnp.clip(exc_col, 0, n_pad - 1)
        has_node = jnp.where(exc_col >= 0, mask[cls_idx, safe_e], has_node)
    if use_spread:
        # Spread batches run the identity order only (domain counts and
        # permutations don't commute cheaply); gang masking still applies.
        if shortlist_k:
            a0, dom_counts2, nfall = \
                solver.greedy_assign_rescoring_spread_shortlist(
                    req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q,
                    mask, static_scores, fit_col_w, bal_col_mask, shape_u,
                    shape_s, w_fit, w_bal, strategy,
                    dom_onehot, cid_onehot, dom_counts, max_skew,
                    sp_min_ok, sp_haskey, sp_applies, sp_contrib,
                    sc0, cls_idx, cand_s[cls_idx], thresh_s[cls_idx],
                    has_node, rows=cls_idx, exc=exc_col, p_real=p_real)
        elif wave_w > 1:
            a0, dom_counts2, wave_com, wave_rep = \
                solver.greedy_assign_rescoring_spread_wave(
                    req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q,
                    mask, static_scores, fit_col_w, bal_col_mask, shape_u,
                    shape_s, w_fit, w_bal, strategy, wave_w,
                    dom_onehot, cid_onehot, dom_counts, max_skew,
                    sp_min_ok, sp_haskey, sp_applies, sp_contrib,
                    rows=cls_idx, exc=exc_col, p_real=p_real)
        else:
            out = solver.greedy_assign_rescoring_spread(
                req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
                static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
                w_fit, w_bal, strategy,
                dom_onehot, cid_onehot, dom_counts, max_skew,
                sp_min_ok, sp_haskey, sp_applies, sp_contrib,
                rows=cls_idx, exc=exc_col, p_real=p_real, ipa=ipa)
            a0, dom_counts2 = out[:2]
            if ipa is not None:
                placed2 = out[2]
        assign = solver.gang_filter(a0, gang_onehot, gang_required)
        # Gang-dropped pods bumped the chained counts in-scan (for the
        # constraints they CONTRIBUTE to) — fold them back out so later
        # chunks see the truth.
        dropped = (a0 >= 0) & (assign < 0)
        safe = jnp.clip(a0, 0, alloc_q.shape[0] - 1)
        contrib_d = sp_contrib @ cid_onehot.T                   # (P, D)
        dom_counts2 = dom_counts2 - jnp.sum(
            jnp.where(dropped[:, None],
                      dom_onehot[safe] * contrib_d, 0.0), axis=0)
    elif ipa is not None:
        a0, placed2 = solver.greedy_assign_rescoring(
            req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
            static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
            w_fit, w_bal, strategy, rows=cls_idx, exc=exc_col,
            p_real=p_real, ipa=ipa)
        assign = solver.gang_filter(a0, gang_onehot, gang_required)
    else:
        if shortlist_k and wave_w > 1:
            # The wave scan takes the shortlist as CLASS tables and never
            # expands them per pod. A slot's chunk-start masked value is
            # what the prefilter ranked: sc0 where the class is feasible
            # on the node, else -inf — one (C,K) lookup a chunk, so that
            # no untouched slot is looked up inside the scan at all.
            sl_val = jnp.where(
                jnp.take_along_axis(feasible, cand_s, axis=1),
                jnp.take_along_axis(sc0, cand_s, axis=1), solver.NEG_INF)
            assign, nfall, wave_com, wave_rep = \
                solver.multistart_greedy_assign_shortlist_wave(
                    req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q,
                    mask, static_scores, fit_col_w, bal_col_mask, shape_u,
                    shape_s, w_fit, w_bal, strategy, wave_w, perms,
                    gang_onehot, gang_required, cls_idx, cand_s, sl_val,
                    thresh_s, has_node, rows=cls_idx, exc=exc_col,
                    p_real=p_real)
        elif shortlist_k:
            assign, nfall = solver.multistart_greedy_assign_shortlist(
                req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q,
                mask, static_scores, fit_col_w, bal_col_mask, shape_u,
                shape_s, w_fit, w_bal, strategy, perms, gang_onehot,
                gang_required, sc0, cls_idx, cand_s[cls_idx],
                thresh_s[cls_idx], has_node, rows=cls_idx, exc=exc_col,
                p_real=p_real)
        elif wave_w > 1:
            assign, wave_com, wave_rep = \
                solver.multistart_greedy_assign_wave(
                    req_q, req_nz_q, free_q, free_pods, used_nz_q,
                    alloc_q, mask, static_scores, fit_col_w,
                    bal_col_mask, shape_u, shape_s, w_fit, w_bal,
                    strategy, wave_w, perms, gang_onehot,
                    gang_required, rows=cls_idx, exc=exc_col,
                    p_real=p_real)
        else:
            assign = solver.multistart_greedy_assign(
                req_q, req_nz_q, free_q, free_pods, used_nz_q, alloc_q, mask,
                static_scores, fit_col_w, bal_col_mask, shape_u, shape_s,
                w_fit, w_bal, strategy, perms, gang_onehot, gang_required,
                rows=cls_idx, exc=exc_col, p_real=p_real)

    # Post-assignment state update (scatter-add of assigned requests).
    # Padding/unassigned rows scatter to a dummy row (index N, dropped).
    n = alloc_q.shape[0]
    hit = assign >= 0
    tgt = jnp.where(hit, assign, n)
    inc = jnp.concatenate(
        [req_q, req_nz_q, hit.astype(jnp.int32)[:, None]], axis=1)
    used_pack2 = used_pack + jnp.zeros(
        (n + 1, used_pack.shape[1]), used_pack.dtype
    ).at[tgt].add(jnp.where(hit[:, None], inc, 0))[:n]
    # The observability tail rides the assign fetch (one transfer, not
    # six): consumers slice [:p_real] for assignments, then [-5] =
    # shortlist fallbacks, [-4]/[-3] = wavefront commits/replays,
    # [-2]/[-1] = block-prefilter blocks scanned/pruned.
    assign_out = jnp.concatenate(
        [assign, nfall[None], wave_com[None], wave_rep[None],
         blk_scanned[None], blk_pruned[None]])
    if placed2 is not None:
        # Gang-dropped pods were counted in-scan: take them out again.
        gone = (a0 >= 0) & (assign < 0)
        placed2 = placed2.at[jnp.maximum(ipa[-1], 0),
                             jnp.clip(a0, 0, n - 1)].add(
            jnp.where(gone & (ipa[-1] >= 0), -1.0, 0.0))
    return assign_out, used_pack2, fit0, taint_ok, dom_counts2, placed2


class TPUBackend:
    """Batched backend: `assign(pods, snapshot, fwk)` →
    ({pod_key: node_name|None}, {pod_key: {node_name: Status}})."""

    def __init__(self, max_batch: int | None = None, multistart: int = 4,
                 resources: Sequence[str] | None = None,
                 mesh: object = "auto"):
        # The one place that picks the device. CPU is a test and
        # pre-flight platform, entered only by asking for it; a JAX that
        # dropped to CPU by itself (libtpu failed to start) would
        # otherwise run the whole system under a device's name.
        if jax.default_backend() == "cpu" and not cpu_requested():
            raise RuntimeError(
                "TPUBackend: JAX fell back to the CPU backend without "
                "being asked (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '')!r}); set "
                "JAX_PLATFORMS=cpu to run on the CPU on purpose")
        #: solve chunk (the jit batch signature); an explicit value is
        #: for tests and --chunk sweeps.
        self.max_batch = max_batch if max_batch is not None \
            else _DEFAULT_CHUNK
        self._tuner = AdaptiveTuner()
        depth_override = _pipeline_depth_override()
        self.pipeline_depth = depth_override \
            if depth_override is not None else 4
        #: parallel permuted-order scans per chunk (1 = oracle-only order).
        #: Selection: most pods placed, then most request volume placed,
        #: identity on full ties — never fewer pods than the oracle order,
        #: and priority-block-stable permutations keep priority fairness.
        self.multistart = max(1, int(multistart))
        self._pinned_resources = list(resources) if resources else None
        #: SchedulerMetrics, injected by the Scheduler — degradation
        #: counters (spread poisoning, gang overflow) report through it.
        self.metrics = None
        #: control-plane shard count of the backing store, injected by
        #: Scheduler.attach_backend when the store advertises one
        #: (ShardedNodeStore.node_shards); None = the flagless policy.
        self.control_shards = None
        #: utils/tracing.Tracer, injected by Scheduler.attach_backend —
        #: per-chunk solver.dispatch/solver.solve spans nest under the
        #: scheduler's attempt span when tracing is on.
        self.tracer = None
        # Multi-device: shard the nodes axis over an ICI mesh
        # (SURVEY §5.7 — the TP-like axis). Inputs are placed with
        # NamedSharding and the SAME jit program auto-partitions (XLA
        # inserts the cross-shard reductions for the solver's per-step
        # argmax). mesh="auto" builds a 1-D nodes mesh over the largest
        # power-of-two device count (divides NODE_PAD, so any padded N
        # shards evenly); None forces single-device.
        if mesh == "auto":
            ndev = len(jax.devices())
            if ndev > 1:
                from kubernetes_tpu.parallel import build_mesh
                n = 1 << (ndev.bit_length() - 1)  # largest power of two ≤ ndev
                mesh = build_mesh(n)
            else:
                mesh = None
        self.mesh = mesh
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from kubernetes_tpu.parallel import NODES_AXIS, SLICE_AXIS
            # A multi-slice mesh (config #5) shards the node dimension over
            # BOTH axes, slice-major: XLA then lowers reductions over the
            # pair hierarchically (ICI within a slice, DCN across).
            axis = (SLICE_AXIS, NODES_AXIS) \
                if SLICE_AXIS in self.mesh.axis_names else NODES_AXIS
            self._sh_nodes_mat = NamedSharding(
                self.mesh, PartitionSpec(axis, None))
            self._sh_nodes_vec = NamedSharding(
                self.mesh, PartitionSpec(axis))
            self._sh_pn = NamedSharding(
                self.mesh, PartitionSpec(None, axis))
            self._sh_rep = NamedSharding(self.mesh, PartitionSpec())
        self._ct: ClusterTensors | None = None
        # (plugin, sig) -> np row; valid while _row_fp matches.
        self._row_cache: dict[
            tuple[str, str], tuple[np.ndarray, bool]] = {}
        self._row_fp: tuple | None = None
        # Device-resident constants for the common "no host rows" case:
        # clean chunks' class planes depend only on (plane rows, real
        # classes, node count), so one cached (C,N/8)+(C,N) pair serves
        # every clean chunk of that shape — the per-pod fallback's
        # (P,N)-shaped equivalents ride the same dicts.
        self._dev_base_mask: dict[tuple, object] = {}
        self._dev_zero_scores: dict[tuple, object] = {}
        # Static per-snapshot arrays (alloc, taints) re-uploaded only when
        # the node-static fingerprint moves.
        self._dev_static: dict[str, object] = {}
        self._dev_static_fp: tuple | None = None
        self._fwk_params_cache: dict[tuple, dict] = {}
        # Chained device-resident used-state, ONE packed (N, 2R+1) int32
        # array (used_q ‖ used_nz_q ‖ used_pods): uploaded fresh from the
        # snapshot at each assign() entry, then updated ON DEVICE by each
        # chunk's solve so successive chunks dispatch with no host
        # round-trip.
        self._dev_used = None
        #: serving/resident.ResidentPlanes, attached by the serving tier:
        #: when present, _start refreshes the used-state pack O(changed)
        #: from the cache's dirty-set deltas (scatter of re-quantized
        #: rows) instead of re-uploading the whole (N, 2R+1) array per
        #: assign() — the device-side twin of r13's incremental host
        #: prep. None (the KTPU_SERVING=0 shape) keeps the full upload.
        self.resident = None
        # Vectorized NodeResourceTopologyMatch zone state, cached per
        # (snapshot generation, snapshot identity) — see _nrt_state.
        self._nrt_cache: tuple | None = None
        self._dra_cache: tuple | None = None
        #: ops/affinity.AffinityCompiler, kept across generations (see
        #: _affinity_compiler).
        self._affinity = None
        #: (compiler, template keys, planes) of the spread table's latest
        #: build: everything in it that reads nodes and templates only,
        #: device copies included (see _build_spread_table).
        self._spread_planes: tuple | None = None
        # Fixed-shape placeholder device arrays for the fused program's
        # spread slots when use_spread=False (stable jit signature).
        self._spread_dummy_cache: dict[tuple, tuple] = {}
        # Device-resident permutation sets (keyed by sizes+priorities) and
        # the all-zeros gang arrays for the common no-gang case — one
        # upload each, not one per chunk.
        self._dev_perms_cache: dict[tuple, object] = {}
        self._dev_zero_gang: dict[int, tuple] = {}
        #: cached identity class index / no-exception vectors for the
        #: per-pod fallback and clean class chunks (tiny, but uploaded
        #: per chunk otherwise).
        self._dev_arange: dict[int, object] = {}
        self._dev_no_exc: dict[int, object] = {}

    # -- device placement ----------------------------------------------------

    def _put(self, arr, kind: str = "rep"):
        """Upload with the mesh sharding for `kind` ("nodes_mat",
        "nodes_vec", "pn", "rep"); plain transfer on a single device."""
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, getattr(self, "_sh_" + kind))

    # -- snapshot compilation ----------------------------------------------

    def _tensors(self, snapshot: Snapshot) -> ClusterTensors:
        if self._ct is None or self._ct.generation != snapshot.generation:
            t0 = time.perf_counter()
            self._ct = ClusterTensors(
                snapshot, resources=self._pinned_resources, prev=self._ct,
                shards=self.control_shards)
            built_s = time.perf_counter() - t0
            # The affinity compiler is NOT dropped with the tensors: it
            # is brought to the snapshot where a pod first needs it
            # (_affinity_compiler), by the same delta handles. What goes
            # here is its hold on the snapshot the cache has left behind.
            if self._affinity is not None:
                self._affinity.release()
            # Per-shard host-prep accounting (ROADMAP #5): only shards
            # whose rows this build rewrote count a rebuild — the
            # incremental delta path's observable witness.
            if self.metrics is not None:
                self.metrics.tensors_duration.observe(built_s)
                self.metrics.cluster_tensor_builds.inc(
                    kind=self._ct.build_kind)
                for s in self._ct.shard_rebuilds:
                    self.metrics.shard_tensor_rebuilds.inc(shard=str(s))
                topo = getattr(self._ct, "topology", None)
                if topo is not None and topo.rebuilt:
                    self.metrics.topology_plane_rebuilds.inc()
        if self._row_fp != self._ct._static_fp:
            self._row_cache.clear()
            self._row_fp = self._ct._static_fp
        return self._ct

    def _affinity_compiler(self, snapshot: Snapshot, ct: ClusterTensors):
        """The affinity compiler AT `snapshot`. One compiler serves every
        generation of a node set: it advances by the snapshot's
        changed-node log (AffinityCompiler.advance: the changed nodes'
        rows recounted, the carriers of residents' own terms moved by the
        pods that came to and left those nodes) and is built anew — one
        walk over every resident pod — only when the handles do not vouch
        for that, or the namespace resolver moved. The registry says which
        each build was, how many node rows it counted, how many
        term-carrying residents it looked at and how many of them moved."""
        resolver = getattr(self, "_ns_resolver", None)
        cached = self._affinity
        if cached is not None and cached.ns_resolver is not resolver:
            cached = None  # another profile's resolver: other namespace sets
        if cached is not None and cached.at(snapshot):
            cached.kept()
            return cached
        recounted = cached.advance(snapshot, ct.n_pad) \
            if cached is not None else None
        if recounted is None:
            from kubernetes_tpu.ops.affinity import AffinityCompiler
            cached = self._affinity = AffinityCompiler(
                snapshot, ct.n_pad, ns_resolver=resolver)
            recounted = ct.n_real
        if self.metrics is not None:
            self.metrics.affinity_compiler_builds.inc(kind=cached.reached)
            self.metrics.affinity_rows_recounted.inc(recounted)
            self.metrics.affinity_carriers_walked.inc(cached.walked)
            self.metrics.affinity_carriers_moved.inc(cached.came, dir="came")
            self.metrics.affinity_carriers_moved.inc(cached.gone, dir="gone")
        return cached

    def _affinity_rows(self, plugin, pods: list[PodInfo], skip: set[int],
                       snapshot: Snapshot, ct: ClusterTensors
                       ) -> dict[int, tuple[np.ndarray, list[int]]]:
        """The tensorized InterPodAffinity rows (ops/affinity.py) of a
        chunk's gated pods, grouped by row identity: `filter_row` hands
        every pod of one content signature the same cached object, so
        {id(row): (row, chunk indices of its pods)}. Whatever this spends
        in the compiler — reaching the snapshot (build, or advance by
        the changed nodes) and the rows themselves — is the span
        `solver.affinity_rows`, and its wall the histogram
        `scheduler_tpu_affinity_rows_seconds`: the span's own clock reads
        where tracing is on, two reads of that clock where it is off."""
        gate = _FILTER_ACTIVE["InterPodAffinity"]
        gated = [(i, pi) for i, pi in enumerate(pods)
                 if i not in skip and gate(plugin, pi, snapshot)]
        groups: dict[int, tuple[np.ndarray, list[int]]] = {}
        if not gated:
            return groups
        with self._span("solver.affinity_rows") as sp:
            t0 = time.monotonic() if sp is None else 0.0
            compiler = self._affinity_compiler(snapshot, ct)
            for i, pi in gated:
                row_full = compiler.filter_row(pi)
                grp = groups.get(id(row_full))
                if grp is None:
                    grp = groups[id(row_full)] = (row_full, [])
                grp[1].append(i)
            if sp is not None:
                sp.attrs.update(
                    build=compiler.reached, carriers=compiler.walked,
                    came=compiler.came, gone=compiler.gone,
                    terms=len(compiler.resident_anti), rows=len(groups))
        if self.metrics is not None:
            self.metrics.affinity_rows_duration.observe(
                sp.end - sp.start if sp is not None
                else time.monotonic() - t0)
        return groups

    # -- NodeResourceTopologyMatch vectorization (BASELINE config #4) -----

    def _nrt_state(self, plugin, snapshot: Snapshot,
                   ct: ClusterTensors) -> dict | None:
        """Batch-start zone-free tensors for NodeResourceTopologyMatch:
        free/cap (N, Zmax, T), zone_valid (N, Zmax), tracked (N, T) over
        the union T of zone-listed resources. Running the host plugin's
        pack_zones per (pod × node) is O(P·N·residents); this packs each
        node ONCE per assign() and answers rows with numpy broadcasting.
        Within-batch drift is caught by the stateful full re-check in
        _verify (same delta pattern as PodTopologySpread)."""
        # nrt_seq invalidates on NRT object churn (which does not move the
        # snapshot generation); id(plugin) separates per-profile instances.
        key = (ct.generation, id(snapshot), id(plugin), plugin.nrt_seq)
        if self._nrt_cache is not None and self._nrt_cache[0] == key:
            return self._nrt_cache[1]
        from kubernetes_tpu.scheduler.plugins.noderesourcetopology import (
            SINGLE_NUMA_POLICIES, _zone_caps, pack_zones)
        T = sorted(plugin._zone_resources)
        t_index = {r: j for j, r in enumerate(T)}
        N = ct.n_real
        per_node: list[tuple | None] = []
        zmax = 1
        for ni in snapshot.nodes:
            nrt = plugin._nrt(ni.name)
            if nrt is None or not (
                    set(nrt.get("topologyPolicies") or [])
                    & SINGLE_NUMA_POLICIES):
                per_node.append(None)
                continue
            caps = [c for _, c in _zone_caps(nrt)]
            per_node.append((pack_zones(nrt, ni), caps))
            zmax = max(zmax, len(caps))
        free = np.zeros((N, zmax, len(T)), dtype=np.int64)
        cap = np.zeros_like(free)
        zone_valid = np.zeros((N, zmax), dtype=np.bool_)
        tracked = np.zeros((N, len(T)), dtype=np.bool_)
        for n, entry in enumerate(per_node):
            if entry is None:
                continue
            zfree, zcaps = entry
            for z, (zf, zc) in enumerate(zip(zfree, zcaps)):
                zone_valid[n, z] = True
                for r, v in zc.items():
                    j = t_index[r]
                    cap[n, z, j] = v
                    tracked[n, j] = True
                for r, v in zf.items():
                    free[n, z, t_index[r]] = v
        state = {"T": T, "t_index": t_index, "free": free, "cap": cap,
                 "zone_valid": zone_valid, "tracked": tracked,
                 "strategy": plugin.strategy}
        self._nrt_cache = (key, state)
        return state

    @staticmethod
    def _nrt_req_vec(st: dict, pi: PodInfo) -> np.ndarray:
        q = np.zeros(len(st["T"]), dtype=np.int64)
        for r, v in pi.requests.items():
            j = st["t_index"].get(r)
            if j is not None and v > 0:
                q[j] = v
        return q

    def _nrt_pod_eval(self, st: dict, pi: PodInfo, memo: dict, i: int):
        """Per-pod (q, constrained, zone_fit), memoized per chunk — the
        Filter and Score phases share the (N, Zmax, T) reduction."""
        hit = memo.get(i)
        if hit is None:
            q = self._nrt_req_vec(st, pi)
            qpos = (q > 0)[None, None, :]
            constrained = (st["tracked"] & (q > 0)[None, :]).any(-1)
            viol = st["tracked"][:, None, :] & qpos \
                & (st["free"] < q[None, None, :])
            zone_fit = st["zone_valid"] & ~viol.any(-1)
            hit = memo[i] = (q, constrained, zone_fit)
        return hit

    def _nrt_filter_row(self, st: dict, pi: PodInfo, memo: dict,
                        i: int) -> np.ndarray:
        """(n_real,) bool: host plugin's filter() vectorized."""
        _, constrained, zone_fit = self._nrt_pod_eval(st, pi, memo, i)
        return ~constrained | zone_fit.any(-1)

    def _nrt_score_row(self, st: dict, pi: PodInfo, memo: dict,
                       i: int) -> np.ndarray:
        """(n_real,) float: host plugin's score() vectorized (best zone by
        the configured strategy; 0 for unconstrained/unfitting nodes)."""
        q, constrained, zone_fit = self._nrt_pod_eval(st, pi, memo, i)
        qpos = (q > 0)[None, None, :]
        m = (st["cap"] > 0) & qpos
        cnt = m.sum(-1)
        safe_cap = np.maximum(st["cap"], 1)
        fr = np.where(m, (st["free"] - q[None, None, :]) / safe_cap, 0.0)
        denom = np.maximum(cnt, 1)
        mean = fr.sum(-1) / denom
        if st["strategy"] == "MostAllocated":
            s = 100.0 * (1.0 - mean)
        elif st["strategy"] == "BalancedAllocation":
            var = (np.where(m, fr * fr, 0.0).sum(-1) / denom) - mean * mean
            s = 100.0 * (1.0 - np.sqrt(np.maximum(var, 0.0)))
        else:  # LeastAllocated
            s = 100.0 * mean
        s = np.where(zone_fit & (cnt > 0), s, -np.inf)
        best = s.max(-1)
        return np.where(constrained & np.isfinite(best),
                        np.maximum(best, 0.0), 0.0)

    @staticmethod
    def _ipa_score_relevant(pi: PodInfo, compiler) -> bool:
        """InterPodAffinity Score is nonzero only if the pod has preferred
        terms, or some resident pod contributes symmetry weight (preferred
        terms, or required affinity terms × hardPodAffinityWeight): the
        compiler at the snapshot keeps those by node
        (AffinityCompiler.resident_score), so nothing is walked to ask."""
        return bool(pi.preferred_affinity_terms
                    or pi.preferred_anti_affinity_terms
                    or compiler.resident_score)

    def _ipa_table(self, ctx: "_AssignCtx", pods: list[PodInfo]) -> None:
        """The InterPodAffinity carry of one assign() (`ctx.ipa`; None
        where no pod's score moves with the assign's own placements).

        Pods are grouped by what the score reads of them (namespace,
        labels, affinity terms). A group is
        CARRIED when placing a pod of the assign — of its own chunk or of
        an earlier one in flight — changes its raw score
        (`AffinityCompiler.score_delta` non-zero: a pod its preferred
        terms select, or a pod whose preferred or required affinity terms
        select it). A chunk-start row would be stale after the chunk's
        first such placement, so a carried group's score is left out of
        the static rows and the scan recomputes it at every step
        (ops/solver.py `_ipa_score`) from the raw weights at the snapshot
        (`score_parts`) plus `placed`, the assign's placements so far,
        chained from chunk to chunk on the device. The other groups keep
        their static rows. A topology key that names each keyed node
        alone (hostname) is a per-node vector; any other key goes through
        a node -> domain one-hot.

        Timed as the span `solver.affinity_score` and the histogram
        `scheduler_tpu_affinity_score_seconds`; the groups with a score
        are counted by kind (carried / static)."""
        ctx.ipa = None
        fwk, snapshot, ct = ctx.fwk, ctx.snapshot, ctx.ct
        plugin = next((p for p in fwk.score_plugins
                       if p.NAME == "InterPodAffinity"), None)
        if plugin is None or not fwk.score_weights.get(
                "InterPodAffinity", 1):
            return
        # No pod of the assign carries a term: nothing it places moves a
        # score (every score then stands as the snapshot's rows say).
        if not any(pi.preferred_affinity_terms
                   or pi.preferred_anti_affinity_terms
                   or pi.required_affinity_terms for pi in pods):
            return
        with self._span("solver.affinity_score") as sp:
            t0 = time.monotonic() if sp is None else 0.0
            compiler = self._affinity_compiler(snapshot, ct)
            hard = float(getattr(plugin, "hard_pod_affinity_weight", 1))
            group_of: dict[tuple, int] = {}
            reps: list[PodInfo] = []
            pod_group = np.empty((len(pods),), dtype=np.int64)
            for j, pi in enumerate(pods):
                key = (pi.namespace, tuple(sorted(pi.labels.items())),
                       repr(pi.affinity))
                g = group_of.get(key)
                if g is None:
                    g = group_of[key] = len(reps)
                    reps.append(pi)
                pod_group[j] = g
            movers = [g for g, pi in enumerate(reps)
                      if pi.preferred_affinity_terms
                      or pi.preferred_anti_affinity_terms
                      or pi.required_affinity_terms]
            deltas: dict[tuple[int, int], dict[str, float]] = {}
            for a, pa in enumerate(reps):
                own = pa.preferred_affinity_terms \
                    or pa.preferred_anti_affinity_terms
                for b in (range(len(reps)) if own else movers):
                    d = compiler.score_delta(pa, reps[b], hard)
                    if d:
                        deltas[(a, b)] = d
            carried = sorted({a for a, _ in deltas})
            static = sum(1 for g, pi in enumerate(reps)
                         if g not in carried
                         and self._ipa_score_relevant(pi, compiler))
            if carried:
                ctx.ipa = self._ipa_carry(
                    ctx, compiler, reps, pod_group, carried, deltas, hard,
                    float(fwk.score_weights.get("InterPodAffinity", 1)))
            if sp is not None:
                sp.attrs.update(classes=len(carried) + static,
                                carried=len(carried),
                                carriers=compiler.resident_score.terms())
        if self.metrics is not None:
            self.metrics.affinity_score_duration.observe(
                sp.end - sp.start if sp is not None
                else time.monotonic() - t0)
            self.metrics.affinity_score_classes.inc(
                len(carried), kind="carried")
            self.metrics.affinity_score_classes.inc(static, kind="static")

    def _ipa_carry(self, ctx, compiler, reps, pod_group, carried, deltas,
                   hard: float, weight: float) -> dict:
        """The device inputs of `_ipa_table`'s carried groups (see
        ops/solver.py `_ipa_score` for the layout), uploaded once per
        assign(), and per chunk the (P,) carried row and touching row of
        each pod (-1: none)."""
        touching = sorted({b for (a, b) in deltas})
        a_of = {a: i for i, a in enumerate(carried)}
        t_of = {b: i for i, b in enumerate(touching)}
        parts = {a: compiler.score_parts(reps[a], hard) for a in carried}
        keys = sorted({k for p in parts.values() for k in p}
                      | {k for d in deltas.values() for k in d})
        k_of = {k: i for i, k in enumerate(keys)}
        n = compiler.n_pad
        kp = _pow2(len(keys))
        keyed = np.zeros((kp, n), dtype=np.float32)
        by_domain = []
        for k, key in enumerate(keys):
            ids, num = compiler.topo.domains(key)
            has = ids > 0
            if np.unique(ids[has]).size == int(has.sum()):
                keyed[k] = has              # every keyed node its own domain
            else:
                by_domain.append((k, ids, num))
        n_dom = sum(num - 1 for _, _, num in by_domain)
        dp = _pow2(max(n_dom, 8)) if n_dom else 0
        dom = np.zeros((n, dp), dtype=np.float32)
        dom_key = np.zeros((dp, kp), dtype=np.float32)
        col = 0
        for k, ids, num in by_domain:
            at = np.nonzero(ids > 0)[0]
            dom[at, col + ids[at] - 1] = 1.0
            dom_key[col:col + num - 1, k] = 1.0
            col += num - 1
        base = np.zeros((_pow2(len(carried)), kp, n), dtype=np.float32)
        for a in carried:
            for key, vec in parts[a].items():
                base[a_of[a], k_of[key]] = vec
        delta = np.zeros((base.shape[0], _pow2(len(touching)), kp),
                         dtype=np.float32)
        for (a, b), d in deltas.items():
            for key, w in d.items():
                delta[a_of[a], t_of[b], k_of[key]] = w
        row_of = np.array([a_of.get(g, -1) for g in range(len(reps))],
                          dtype=np.int32)
        tpl_of = np.array([t_of.get(g, -1) for g in range(len(reps))],
                          dtype=np.int32)
        P = self.max_batch
        chunks, lo, last = [], 0, -1
        for k, chunk in enumerate(ctx.chunks):
            rows = np.full((P,), -1, dtype=np.int32)
            tpls = np.full((P,), -1, dtype=np.int32)
            g = pod_group[lo:lo + len(chunk)]
            rows[:len(chunk)] = row_of[g]
            tpls[:len(chunk)] = tpl_of[g]
            lo += len(chunk)
            if (rows >= 0).any():
                last = k
            chunks.append((rows, tpls))
        return {
            "chunks": chunks, "last": last,
            "dev": tuple(self._put(x) for x in (
                base, delta, keyed, dom, dom_key)) + (
                jnp.float32(weight * MAX_NODE_SCORE),),
            "placed": self._put(np.zeros((delta.shape[1], n),
                                         dtype=np.float32)),
        }

    # -- host rows -----------------------------------------------------------

    def _static_filter_row(self, plugin, pi: PodInfo, snapshot: Snapshot,
                           ct: ClusterTensors) -> tuple[np.ndarray, bool]:
        """Returns (row, all_true). all() is cached with the row: re-scanning
        a 5k-wide row per pod per plugin was a top-3 host cost at perf scale."""
        key = (plugin.NAME, _signature(plugin.NAME, pi))
        hit = self._row_cache.get(key)
        if hit is None:
            state = CycleState()
            st = plugin.pre_filter(state, pi, snapshot)
            if st.is_skip() or st.is_success():
                row = np.fromiter(
                    (plugin.filter(state, pi, ni).is_success()
                     for ni in snapshot.nodes),
                    dtype=np.bool_, count=ct.n_real)
            else:
                row = np.zeros((ct.n_real,), dtype=np.bool_)
            hit = self._row_cache[key] = (row, bool(row.all()))
        return hit

    def _static_score_row(self, plugin, pi: PodInfo, snapshot: Snapshot,
                          ct: ClusterTensors) -> tuple[np.ndarray, bool]:
        """Returns (row, any_nonzero); see _static_filter_row on caching."""
        key = (plugin.NAME + "/score", _signature(plugin.NAME, pi))
        hit = self._row_cache.get(key)
        if hit is None:
            state = CycleState()
            row = np.fromiter(
                (plugin.score(state, pi, ni) for ni in snapshot.nodes),
                dtype=np.float32, count=ct.n_real)
            hit = self._row_cache[key] = (row, bool(row.any()))
        return hit

    # -- profiling (SURVEY §5.1: jax.profiler hook) -----------------------

    def start_profile(self, log_dir: str) -> None:
        """Begin a device trace (TensorBoard/Perfetto readable). A trace
        that was asked for and cannot start is the run's error."""
        jax.profiler.start_trace(log_dir)
        self._profiling = True

    def stop_profile(self) -> None:
        if getattr(self, "_profiling", False):
            self._profiling = False
            jax.profiler.stop_trace()

    def _gang_args(self, prep: dict, batch) -> tuple:
        """(gang_onehot, gang_required) device arrays; the no-gang case
        reuses one cached zero pair per batch width."""
        if prep["gang_onehot"] is not None:
            return (self._put(prep["gang_onehot"]),
                    self._put(prep["gang_required"]))
        P = batch.req_q.shape[0]
        z = self._dev_zero_gang.get(P)
        if z is None:
            z = self._dev_zero_gang[P] = (
                self._put(np.zeros((P, _GANG_PAD), np.float32)),
                self._put(np.zeros((_GANG_PAD,), np.float32)))
        return z

    def _spread_dummies(self, n_pad: int, p: int) -> tuple:
        key = (n_pad, p)
        d = self._spread_dummy_cache.get(key)
        if d is None:
            d = (self._put(np.zeros((n_pad, 1), np.float32), "nodes_mat"),
                 self._put(np.zeros((1, 1), np.float32)),
                 self._put(np.zeros((1,), np.float32)),
                 self._put(np.zeros((1,), np.float32)),
                 self._put(np.zeros((1,), np.float32)),
                 self._put(np.zeros((n_pad, 1), np.float32), "nodes_mat"),
                 self._put(np.zeros((p, 1), np.float32)),
                 self._put(np.zeros((p, 1), np.float32)))
            self._spread_dummy_cache[key] = d
        return d

    @staticmethod
    def _spread_tpl_key(cs: list, pj: PodInfo) -> str:
        # EVERY semantic field participates: two templates differing only
        # in minDomains/namespaceSelector must NOT collide. The pod's
        # node-eligibility signature participates too — eligibility folds
        # into the template's constraint COLUMNS (domain membership and
        # counts are per eligible-node set), so pods with different
        # nodeSelector/affinity/tolerations need different columns even
        # for identical constraint lists.
        return repr((sorted((c.get("topologyKey", ""),
                             repr(c.get("labelSelector")),
                             c.get("maxSkew", 1),
                             repr(c.get("minDomains")),
                             repr(c.get("namespaceSelector")))
                            for c in cs), pj.namespace,
                     pj.node_selector,
                     pj.affinity.get("nodeAffinity"), pj.tolerations))

    def _build_spread_table(self, ctx, snapshot, ct, compiler,
                            plugin) -> None:
        """Union spread table, built ONCE per assign() from ALL chunks.

        Every distinct DoNotSchedule template in the batch contributes
        its constraints to one union list C; the scan gates each pod on
        ITS template's columns (`applies`) and counts every placed pod in
        the constraints its labels match (`contributes`) — heterogeneous
        batches and cross-matching non-spread pods stay on device. Every
        template shape compiles: namespaceSelector resolves to a
        namespace set at build time, minDomains becomes the per-
        constraint `min_ok` floor, restricted node eligibility folds into
        the template's domain columns, and non-self-matching selectors
        ride the per-pod selfMatch term (`contributes`). Templates whose
        constraints have NO domains anywhere get a static row (reject
        keyless nodes, fresh-pass the rest) instead of host fallback.

        What reads nodes and templates only (_spread_planes_for) is kept
        with the compiler and reused while the same templates come in the
        same order; the domain counts alone are taken per assign(), from
        the compiler's counts at this snapshot, and uploaded."""
        from kubernetes_tpu.api.labels import from_label_selector

        templates: dict[str, dict] = {}
        # A template's thousand pods are a thousand equal objects, not
        # one: what the template key reads is compared with the pod
        # before (stamped batches come in runs), else looked up by one
        # repr; the key's own sorts and reprs run once per distinct content.
        seen: set[str] = set()
        last = None
        for chunk in ctx.chunks:
            for pj in chunk:
                if not pj.topology_spread_constraints:
                    continue
                fields = (pj.topology_spread_constraints, pj.namespace,
                          pj.node_selector, pj.affinity.get("nodeAffinity"),
                          pj.tolerations)
                if fields == last:
                    continue
                last = fields
                raw = repr(fields)
                if raw in seen:
                    continue
                seen.add(raw)
                cs = plugin._constraints_for(pj, "DoNotSchedule")
                if not cs:
                    continue
                key = self._spread_tpl_key(cs, pj)
                t = templates.get(key)
                if t is None:
                    t = templates[key] = {
                        "cons": cs, "ns": pj.namespace, "rep": pj,
                        "sels": [from_label_selector(
                            c.get("labelSelector")) for c in cs],
                    }

        # A compiler that ADVANCED vouches for the node set, every node
        # object, n_pad and the namespace resolver's epoch; one built anew
        # is another object, and the planes go with it.
        last = self._spread_planes
        kept = last is not None and last[0] is compiler \
            and last[1] == tuple(templates)
        if kept:
            planes = last[2]
        else:
            planes = self._spread_planes_for(templates, ct, compiler)
            self._spread_planes = (compiler, tuple(templates), planes)
        if self.metrics is not None:
            self.metrics.spread_table_builds.inc(
                planes="kept" if kept else "built")
        # Per-assign state beside the shared planes: the chained domain
        # counts, and what chunk prep memoizes.
        sp = ctx.spread = dict(planes)
        sp["ineligible"] = set()
        if not sp["cons"]:
            return
        # A domain's count is its constraint's matching pods summed over
        # the domain's column of dom_onehot (its eligible nodes): whole
        # numbers in float32, exact in any order of summation.
        dom_onehot = sp["dom_onehot_host"]
        counts0 = np.concatenate([
            compiler.counts_for(c.get("labelSelector"), ns)
            @ dom_onehot[:, lo:hi]
            for c, ns, (lo, hi) in zip(sp["cons"], sp["con_ns"],
                                       sp["con_cols"])])
        # The table is built in _start BEFORE any chunk dispatches, so
        # ctx.delta is empty here by construction — every same-assign
        # placement is counted by the scan itself (sp_contrib).
        sp["dev_counts"] = self._put(counts0)

    def _spread_planes_for(self, templates: dict, ct, compiler) -> dict:
        """The spread table but for its counts: the union constraint list
        and every host and device plane that reads node labels, node
        eligibility and the templates alone. Shared between assign()
        calls and never written after this returns; the device copies are
        plain (non-donated) inputs of the fused program."""
        cons: list[dict] = []      # union constraint list
        con_ns: list[tuple] = []   # resolved namespace set per constraint
        con_sels: list = []
        con_elig: list[np.ndarray] = []
        tpl_cols: dict[str, list[int]] = {}
        static_rows: dict[str, np.ndarray] = {}
        for key, t in templates.items():
            # A template whose every constraint has zero eligible domains
            # imposes only the static has-key gate (each keyed node is a
            # "fresh" domain that placements never populate — counting is
            # over eligible nodes only), so its pods take one static row
            # and skip the scan entirely.
            elig = compiler.eligibility_row(t["rep"])
            if not any(
                    (compiler.topo.has_key(c["topologyKey"]) & elig).any()
                    for c in t["cons"]):
                row = np.ones((ct.n_real,), dtype=np.bool_)
                for c in t["cons"]:
                    row &= compiler.topo.has_key(
                        c["topologyKey"])[: ct.n_real]
                static_rows[key] = row
                continue
            cols = []
            for cidx, c in enumerate(t["cons"]):
                cols.append(len(cons))
                cons.append(c)
                con_ns.append(
                    compiler.spread_constraint_ns(c, t["ns"]))
                con_sels.append(t["sels"][cidx])
                con_elig.append(elig)
            tpl_cols[key] = cols

        if not cons:
            return {"cons": [], "tpl_cols": {}, "static_rows": static_rows}

        N = ct.n_pad
        C = len(cons)
        # Per constraint: its domain ids and the domains that exist over
        # the nodes that count (keyed and eligible), in column order.
        con_domains = []
        for cidx, c in enumerate(cons):
            dom_ids, _ = compiler.topo.domains(c["topologyKey"])
            active = (dom_ids > 0) & con_elig[cidx]
            con_domains.append((dom_ids, np.unique(dom_ids[active])))
        D = sum(len(existing) for _, existing in con_domains)
        dom_onehot = np.zeros((N, D), dtype=np.float32)
        cid_onehot = np.zeros((D, C), dtype=np.float32)
        has_key_nc = np.zeros((N, C), dtype=np.float32)
        min_ok = np.ones((C,), dtype=np.float32)
        con_cols: list[tuple[int, int]] = []  # a constraint's domain columns
        g = 0
        for cidx, (dom_ids, existing) in enumerate(con_domains):
            elig = con_elig[cidx]
            has_key_nc[:, cidx] = (dom_ids > 0).astype(np.float32)
            md = int(cons[cidx].get("minDomains") or 0)
            if md and len(existing) < md:
                min_ok[cidx] = 0.0  # minDomains deficit → global min = 0
            con_cols.append((g, g + len(existing)))
            for k in existing:
                # Domain membership over ELIGIBLE nodes only: placements
                # on keyed-but-ineligible nodes neither count nor gate.
                dom_onehot[(dom_ids == k) & elig, g] = 1.0
                cid_onehot[g, cidx] = 1.0
                g += 1
        return {
            "cons": cons, "con_ns": con_ns, "con_sels": con_sels,
            "con_cols": con_cols,
            "tpl_cols": tpl_cols,
            "static_rows": static_rows,
            "dom_onehot_host": dom_onehot,
            "cid_onehot_host": cid_onehot,
            "dev_dom": self._put(dom_onehot, "nodes_mat"),
            "dev_cid": self._put(cid_onehot),
            "dev_skew": self._put(np.array(
                [float(c.get("maxSkew", 1)) for c in cons], np.float32)),
            "dev_min_ok": self._put(min_ok),
            "dev_haskey": self._put(has_key_nc, "nodes_mat"),
        }

    def _process_spread_pods(self, spread_pods, pods, ctx, snapshot, ct,
                             apply_row, stateful_pods, dyn_states,
                             fwk) -> list[int]:
        """Hard (DoNotSchedule) PodTopologySpread routing.

        Every template rides the DEVICE scan
        (solver.greedy_assign_rescoring_spread): domain counts ride the
        scan carry, so tight maxSkew stays sequential-exact without the
        batch-then-verify requeue collapse — heterogeneous batches,
        namespaceSelector/minDomains constraints, restricted node
        eligibility, and non-self-matching selectors included. Templates
        with zero eligible domains take one static has-key row (exact —
        placements never move their counts). Host rows + stateful verify
        remain ONLY as the missing-table escape hatch, counted as
        spread_poisoned degradations (one per pod) — at steady state that
        counter stays zero."""
        if not spread_pods:
            return []
        compiler = self._affinity_compiler(snapshot, ct)
        plugin = next(p for p in fwk.filter_plugins
                      if p.NAME == "PodTopologySpread")
        sp = ctx.spread
        if sp is None:
            # _start builds the table eagerly whenever the batch carries
            # spread constraints; reaching here without one means the
            # batch mutated mid-assign — fall back rather than run the
            # scan against counts that missed in-flight chunks.
            logger.error("spread table missing at chunk prep; routing "
                         "%d pods to host rows", len(spread_pods))
            sp = {"tpl_cols": {}, "static_rows": {}}

        active: list[int] = []
        fallback: list[tuple[int, object, list]] = []
        for i, pi, cs in spread_pods:
            key = self._spread_tpl_key(cs, pi)
            if key in sp["tpl_cols"]:
                active.append(i)
                continue
            srow = sp["static_rows"].get(key)
            if srow is not None:
                # Zero-domain template: keyless nodes reject, keyed nodes
                # are fresh — static, no verify needed.
                if not srow.all():
                    apply_row("PodTopologySpread", i, srow)
                continue
            fallback.append((i, pi, cs))

        if fallback:
            if not ctx.spread_poisoned:
                logger.warning(
                    "PodTopologySpread: %d pods missed the union table "
                    "(batch mutated mid-assign?) — host rows + stateful "
                    "verify for them", len(fallback))
            ctx.spread_poisoned = True
            if self.metrics is not None:
                self.metrics.backend_degradations.inc(
                    len(fallback), kind="spread_poisoned")
            for i, pi, cs in fallback:
                row = compiler.spread_filter_row(pi, cs)[: ct.n_real]
                if not row.all():
                    apply_row("PodTopologySpread", i, row)
                stateful_pods.add(i)
        return active

    # -- DynamicResources (DRA) vectorization -------------------------------

    def _dra_state(self, plugin, snapshot: Snapshot,
                   ct: ClusterTensors) -> dict:
        """Batch-start free-device tensors for DynamicResources: per
        (node, class) total free counts plus, per device-attribute key,
        the largest single-value group — enough to answer count-N claims
        with or without a single matchAttribute constraint via numpy
        rows instead of O(N·claims) host plugin calls. Claims are charged
        from the allocation ledger + resident unallocated demand + the
        assume ledger ONCE per batch (same shape as _nrt_state); in-batch
        drift is caught by the stateful re-verify."""
        from kubernetes_tpu.scheduler.plugins.dynamicresources import (
            claim_allocated_node,
            pod_claim_keys,
        )
        key = (ct.generation, id(snapshot), id(plugin), plugin.dra_seq,
               plugin.assume_seq)
        if self._dra_cache is not None and self._dra_cache[0] == key:
            return self._dra_cache[1]
        classes = plugin._classes()
        class_names = sorted(classes)
        c_index = {c: j for j, c in enumerate(class_names)}
        N, C = ct.n_real, len(class_names)
        free_total = np.zeros((N, C), dtype=np.int32)
        #: attr key -> (N, C) best single-value group size
        max_group: dict[str, np.ndarray] = {}

        # One pass over the claim ledgers, grouped per node.
        charges: dict[str, dict[str, dict]] = {}

        def charge(node_name: str, claim: dict) -> None:
            from kubernetes_tpu.api.meta import namespaced_name as nn
            charges.setdefault(node_name, {})[nn(claim)] = claim

        for n, bucket in plugin._alloc_by_node.items():
            for claim in bucket.values():
                charge(n, claim)
        for ni in snapshot.nodes:
            for pi in ni.pods:
                for ckey in pod_claim_keys(pi):
                    claim = plugin._claim_informer.indexer.get(ckey) \
                        if plugin._claim_informer is not None else None
                    if claim is not None and \
                            claim_allocated_node(claim) is None:
                        charge(ni.name, claim)
        for a in plugin._assumed.values():
            charge(a["node"], a["claim"])

        attr_keys: set[str] = set()
        per_node_free: list[list[dict]] = []
        for idx, ni in enumerate(snapshot.nodes):
            devices = plugin.node_devices(ni.name)  # indexed by node
            if not devices:
                per_node_free.append([])
                continue
            taken: set[str] = set()
            for claim in (charges.get(ni.name) or {}).values():
                alloc = (claim.get("status") or {}).get("allocation")
                if alloc:
                    if alloc.get("nodeName") == ni.name:
                        taken.update(alloc.get("devices") or [])
                    continue
                picked = plugin._pick_devices(
                    claim, [d for d in devices if d["name"] not in taken],
                    classes)
                if picked is not None:
                    taken.update(picked)
            free = [d for d in devices if d["name"] not in taken]
            per_node_free.append(free)
            for d in free:
                attr_keys.update((d.get("attributes") or {}).keys())
        for a in attr_keys:
            max_group[a] = np.zeros((N, C), dtype=np.int32)
        for idx, free in enumerate(per_node_free):
            if not free:
                continue
            for j, cname in enumerate(class_names):
                cls = classes[cname]
                matching = [d for d in free
                            if plugin._class_matches(cls, d)]
                free_total[idx, j] = len(matching)
                for a in attr_keys:
                    groups: dict = {}
                    for d in matching:
                        v = (d.get("attributes") or {}).get(a)
                        groups[v] = groups.get(v, 0) + 1
                    if groups:
                        max_group[a][idx, j] = max(groups.values())
        state = {"c_index": c_index, "free_total": free_total,
                 "max_group": max_group, "_name_idx": ct.name_to_idx}
        self._dra_cache = (key, state)
        return state

    def _dra_filter_row(self, st: dict, plugin, pi: PodInfo,
                        memo: dict, i: int) -> np.ndarray | None:
        """(n_real,) bool row, or None when the pod's claims use a shape
        the tensors can't answer (multi-attribute constraints, unknown
        class/claim) — caller falls back to the host plugin row."""
        hit = memo.get(i)
        if hit is not None:
            return hit if hit is not False else None
        from kubernetes_tpu.scheduler.plugins.dynamicresources import (
            claim_allocated_node,
            claim_match_attrs,
            claim_requests,
            pod_claim_keys,
        )
        N = st["free_total"].shape[0]
        row = np.ones((N,), dtype=np.bool_)
        for ckey in pod_claim_keys(pi):
            claim = plugin._claim_informer.indexer.get(ckey) \
                if plugin._claim_informer is not None else None
            if claim is None:
                memo[i] = False
                return None
            pinned = claim_allocated_node(claim)
            if pinned is not None:
                pin_row = np.zeros((N,), dtype=np.bool_)
                # restrict to the allocated node (PreFilter pinning)
                # via positional lookup in the snapshot ordering
                idx = st.get("_name_idx")
                if idx is None:
                    memo[i] = False
                    return None
                j = idx.get(pinned)
                if j is not None:
                    pin_row[j] = True
                row &= pin_row
                continue
            attrs = claim_match_attrs(claim)
            if len(attrs) > 1 or (attrs and
                                  len(claim_requests(claim)) > 1):
                # Multi-attribute constraints, or a claim-wide constraint
                # spanning several requests, need whole-claim group
                # packing — host row answers exactly.
                memo[i] = False
                return None
            for req in claim_requests(claim):
                j = st["c_index"].get(req.get("deviceClassName", ""))
                if j is None:
                    row[:] = False
                    continue
                count = int(req.get("count", 1))
                if attrs:
                    mg = st["max_group"].get(attrs[0])
                    avail = mg[:, j] if mg is not None else 0
                else:
                    avail = st["free_total"][:, j]
                row &= avail >= count
        memo[i] = row
        return row

    def _dynamic_filter_row(self, plugin, pi: PodInfo, snapshot: Snapshot,
                            ct: ClusterTensors,
                            state: CycleState) -> np.ndarray | None:
        """Stateful plugins (InterPodAffinity/PodTopologySpread/NodePorts):
        None = plugin inactive for this pod (PreFilter Skip)."""
        st = plugin.pre_filter(state, pi, snapshot)
        if st.is_skip():
            return None
        if not st.is_success():
            return np.zeros((ct.n_real,), dtype=np.bool_)
        return np.fromiter(
            (plugin.filter(state, pi, ni).is_success() for ni in snapshot.nodes),
            dtype=np.bool_, count=ct.n_real)

    # -- main entry ----------------------------------------------------------

    def assign(self, pods: Sequence[PodInfo], snapshot: Snapshot,
               fwk: Framework, on_chunk=None):
        """Synchronous driver. Batches larger than max_batch are chunked
        internally and PIPELINED: chunk k+1's solve is dispatched (device
        state chains on device) before chunk k's assignments are fetched,
        so the host verify of chunk k overlaps the device solve of k+1.
        `on_chunk(run)` sees each chunk's record after its host verify
        (differentials read `chunk_feasibility(run)` there)."""
        ctx = self._start(pods, snapshot, fwk)
        for run in self._pipeline(ctx):
            self._finalize_chunk(run, self._fetch_assign(run), ctx)
            if on_chunk is not None:
                on_chunk(run)
        return ctx.assignments, ctx.diagnostics

    async def assign_async(self, pods: Sequence[PodInfo], snapshot: Snapshot,
                           fwk: Framework):
        """Pipelined driver for the scheduler's event loop: same chunk
        pipeline as assign(), with the device→host fetch awaited in a worker
        thread so binding tasks keep draining during the device wait."""
        ctx = None
        async for _chunk_pods, ctx in self.assign_stream(pods, snapshot, fwk):
            pass
        if ctx is None:  # empty batch
            return {}, {}
        return ctx.assignments, ctx.diagnostics

    async def assign_stream(self, pods: Sequence[PodInfo], snapshot: Snapshot,
                            fwk: Framework):
        """Chunk-streaming driver: yields (chunk_pods, ctx) as each chunk's
        host verify completes, so the CALLER's per-pod work (assume →
        Reserve → bindingCycle wire writes) overlaps the NEXT chunk's
        device solve instead of waiting for the whole super-batch — the
        schedule_one/bind asynchrony of SURVEY §2.8 applied between device
        and API boundary. ctx.assignments/diagnostics accumulate; the
        chunk's own keys are final once yielded."""
        import asyncio

        ctx = self._start(pods, snapshot, fwk)
        for run in self._pipeline(ctx):
            got = await asyncio.to_thread(self._fetch_assign, run)
            if (got[: run["batch"].p_real] < 0).any():
                # Solver failures → _finalize_chunk will need the unsat
                # planes for diagnostics. Fetch them HERE, off-loop and
                # overlapped (copy_to_host_async both, then block in the
                # worker): the synchronous np.asarray inside finalize
                # stalled the event loop one device round-trip per plane —
                # over half the wall on dense-failure (preemption) waves.
                await asyncio.to_thread(self._fetch_diag_planes, run)
            self._finalize_chunk(run, got, ctx)
            yield run["pods"], ctx

    def _span(self, name: str, **attrs):
        """A span under the scheduler's attempt (the tracer's shared no-op
        when tracing is off; per-chunk sites, where a call costs nothing)."""
        tr = self.tracer
        return tr.span(name, **attrs) if tr is not None else _NULL_CM

    def _fetch_assign(self, run: dict) -> np.ndarray:
        """Blocking device→host fetch of a chunk's assignments, timed.

        The r8 50k profile showed 98.3% main-thread idle with the cost
        hidden in XLA's compute threads — this wall (dispatch-to-ready of
        the fused solve, as seen by the consumer) is the observability
        for that blind spot: scheduler_tpu_solve_seconds per chunk, plus
        the solver scan width / shortlist fallback counters extracted
        from the same fetch in _finalize_chunk."""
        check_dispatch_seam("backend.fetch_assign")
        span = self._span("solver.solve", chunk=run.get("chunk_idx"),
                          pods=run["batch"].p_real)
        t0 = time.perf_counter()
        with span, _TRACE_ANNOTATION("ktpu.solve.fetch"):
            got = np.asarray(run["assign_d"])
        run["solve_wall_s"] = time.perf_counter() - t0
        if self.metrics is not None:
            self.metrics.solve_duration.observe(run["solve_wall_s"])
        return got

    def _pipeline(self, ctx: "_AssignCtx"):
        """Yield dispatched chunk runs in finalize order, keeping up to
        `pipeline_depth` solves in flight ahead of the consumer's fetch
        (tuner-chosen; KTPU_PIPELINE_DEPTH overrides for sweeps)."""
        from collections import deque

        pending: deque = deque()
        for chunk in ctx.chunks:
            # beside scheduler_tpu_prep_seconds: the histogram times the
            # call, the span's self-time is what the loop thread ran
            with self._span("solver.prep", pods=len(chunk)):
                prep = self._prep_chunk(chunk, ctx)
            pending.append(self._dispatch_chunk(prep, ctx))
            if len(pending) > self.pipeline_depth:
                yield pending.popleft()
        while pending:
            yield pending.popleft()

    def _start(self, pods: Sequence[PodInfo], snapshot: Snapshot,
               fwk: Framework) -> "_AssignCtx":
        # The tuner's feedback lands at assign() boundaries only. Node
        # count is a structural signal (the large-N row + shortlist
        # policy read it) — recorded before the decision.
        self._tuner.n_nodes = len(snapshot.nodes)
        depth = self._tuner.decide()
        if depth is not None and _pipeline_depth_override() is None:
            self.pipeline_depth = depth
        with self._span("solver.tensors", nodes=len(snapshot.nodes)):
            ct = self._tensors(snapshot)
        pods = list(pods)
        # namespaceSelector terms resolve through the framework's
        # InterPodAffinity plugin (its namespaces informer); spread
        # constraints share the mechanism, so PodTopologySpread's
        # resolver backs it when no InterPodAffinity profile exists.
        # Without either, resolve_term_namespaces' static rule applies.
        src = next((p for p in fwk.plugins
                    if p.NAME == "InterPodAffinity"), None) or next(
            (p for p in fwk.plugins
             if p.NAME == "PodTopologySpread"), None)
        self._ns_resolver = getattr(src, "ns_resolver", None)
        ctx = _AssignCtx()
        ctx.snapshot, ctx.fwk, ctx.ct = snapshot, fwk, ct
        # Class-plane cap resolved once per assign() (env-driven so tests
        # and the bench --class-pad sweep can flip it between calls).
        ctx.class_pad = class_pad()
        ctx.chunks = [pods[lo:lo + self.max_batch]
                      for lo in range(0, len(pods), self.max_batch)]
        ctx.assignments, ctx.diagnostics = {}, {}
        # Shared verify state: later chunks are checked against earlier
        # chunks' accepted placements (working snapshot + delta list).
        ctx.working = {}
        ctx.delta = []
        ctx.delta_has_terms = False
        ctx.sel_cache = {}
        ctx.delta_idx = _DeltaAffinityIndex(ctx.sel_cache,
                                            self._ns_resolver)
        ctx.wsnap = None
        # Device-side PodTopologySpread union table: built EAGERLY when
        # any pod in the batch carries spread constraints, so chunks
        # dispatched before the first spread pod still count their
        # selector-matching placements. Every template shape compiles;
        # the host fallback remains only as the missing-table escape
        # hatch (spread_poisoned observability, steady-state zero).
        ctx.spread = None
        ctx.spread_poisoned = False
        ctx.spread_last_gated = -1
        ctx.chunk_seq = -1
        if any(pj.topology_spread_constraints
               for chunk in ctx.chunks for pj in chunk):
            sp_plugin = next((p for p in fwk.filter_plugins
                              if p.NAME == "PodTopologySpread"), None)
            if sp_plugin is not None:
                with self._span("solver.spread_table"):
                    self._build_spread_table(
                        ctx, snapshot, ct,
                        self._affinity_compiler(snapshot, ct), sp_plugin)
                    if self.tracer is not None:
                        sp = ctx.spread
                        self.tracer.annotate(
                            templates=len(sp["tpl_cols"])
                            + len(sp["static_rows"]),
                            constraints=len(sp["cons"]),
                            domains=len(sp.get("cid_onehot_host", ())))
                # Last chunk with scan-GATED pods: contribute-only chunks
                # after it can keep the multistart solver (their counts
                # no longer influence any gating decision).
                if ctx.spread.get("cons"):
                    cols = ctx.spread["tpl_cols"]
                    for k, chunk in enumerate(ctx.chunks):
                        for pj in chunk:
                            if not pj.topology_spread_constraints:
                                continue
                            cs = sp_plugin._constraints_for(
                                pj, "DoNotSchedule")
                            if cs and self._spread_tpl_key(
                                    cs, pj) in cols:
                                ctx.spread_last_gated = k
                                break
        self._ipa_table(ctx, pods)
        ctx.params = self._fwk_params(fwk, ct)
        # Used-state seed for the on-device chunk chain: the serving
        # tier's resident planes refresh it O(changed) from the cache's
        # dirty set; without them, one fresh full upload per call.
        # Either way the chain's post-chunk arrays are NEW device values
        # — the resident base is never mutated by a batch. When the
        # fused program DONATES its used_pack input (accelerator
        # backends; see _solve_program), the resident base must be
        # copied into a chain-owned buffer first or the first chunk
        # would invalidate the planes the serving tier keeps warm; on
        # CPU (no donation) the base is safe as a plain input.
        if self.resident is not None:
            base = self.resident.used_pack(ct, snapshot)
            self._dev_used = _copy_pack(base) if _donation_live() else base
        else:
            self._dev_used = self._put(np.concatenate(
                [ct.used_q, ct.used_nz_q,
                 ct.used_pods.astype(np.int32)[:, None]], axis=1),
                "nodes_mat")
        return ctx

    def _fwk_params(self, fwk: Framework, ct: ClusterTensors) -> dict:
        # Cached per (framework, resource columns): the device scalars are
        # ~9 separate host→device transfers.
        # The entry HOLDS the framework so its id can't be recycled by a
        # new Framework and serve stale weights; identity is re-checked.
        key = (id(fwk), tuple(ct.resources))
        cached = self._fwk_params_cache.get(key)
        if cached is not None and cached[0] is fwk:
            return cached[1]
        if len(self._fwk_params_cache) > 64:
            self._fwk_params_cache.clear()
        score_plugins = {p.NAME: p for p in fwk.score_plugins}
        fit_plugin = score_plugins.get("NodeResourcesFit")
        strategy = getattr(fit_plugin, "strategy_type", "LeastAllocated")
        fit_col_w = np.zeros((len(ct.resources),), dtype=np.float32)
        if fit_plugin is not None:
            for spec in fit_plugin.score_resources:
                j = ct.r_index.get(spec["name"])
                if j is not None:
                    fit_col_w[j] = spec.get("weight", 1)
        bal_plugin = score_plugins.get("NodeResourcesBalancedAllocation")
        bal_col_mask = np.zeros((len(ct.resources),), dtype=np.bool_)
        if bal_plugin is not None:
            for r in bal_plugin.resources:
                j = ct.r_index.get(r)
                if j is not None:
                    bal_col_mask[j] = True
        shape_pts = getattr(fit_plugin, "shape", None) or [
            {"utilization": 0, "score": 0}, {"utilization": 100, "score": 10}]
        w = fwk.score_weights
        filter_names = {p.NAME for p in fwk.filter_plugins}
        params = {
            "strategy": strategy,
            "fit_col_w": self._put(fit_col_w),
            "bal_col_mask": self._put(bal_col_mask),
            "shape_u": self._put(
                np.array([p["utilization"] for p in shape_pts], np.float32)),
            "shape_s": self._put(
                np.array([p["score"] for p in shape_pts], np.float32)),
            "w_fit": jnp.float32(
                w.get("NodeResourcesFit", 1) if fit_plugin else 0),
            "w_bal": jnp.float32(
                w.get("NodeResourcesBalancedAllocation", 1) if bal_plugin else 0),
            "w_taint": jnp.float32(
                w.get("TaintToleration", 3)
                if "TaintToleration" in score_plugins else 0),
            "taint_filter_on": jnp.bool_("TaintToleration" in filter_names),
            "filter_names": filter_names,
        }
        self._fwk_params_cache[key] = (fwk, params)
        return params

    def _prep_chunk(self, pods: list[PodInfo], ctx: "_AssignCtx") -> dict:
        prep_t0 = time.perf_counter()
        ct, snapshot, fwk = ctx.ct, ctx.snapshot, ctx.fwk
        ctx.chunk_seq += 1
        chunk_idx = ctx.chunk_seq
        #: per pod, its carried InterPodAffinity row (-1: none)
        ipa_rows = ctx.ipa["chunks"][chunk_idx][0] \
            if ctx.ipa is not None else None
        P = self.max_batch
        batch = PodBatch(pods, ct, P)
        N = ct.n_pad

        filter_names = {p.NAME for p in fwk.filter_plugins}
        score_plugins = {p.NAME: p for p in fwk.score_plugins}

        # Host filter rows accumulate as INTERNED references, never a
        # (P,N) plane: each applied row is content-interned once (shared
        # row objects — the static/IPA caches — memoize by identity, so
        # the O(N) tobytes runs once per distinct row, not per pod), each
        # pod carries the list of its row ids, and the class build below
        # materializes ONE AND-folded row per distinct row-set. Rows with
        # exactly one allowed column (NodeName, DRA allocated-claim pins)
        # become the pod's sparse EXCEPTION column instead — they would
        # otherwise split every pinned pod into its own class.
        row_store: dict[int, np.ndarray] = {}      # cid -> ok row (n_real,)
        _row_bytes: dict[bytes, int] = {}
        _row_memo: dict[int, tuple] = {}           # id(row) -> (cid,nnz,col)
        _row_refs: list = []                       # pin ids against reuse
        pod_rows: dict[int, list[int]] = {}        # pod -> ordered cids
        pod_pin: dict[int, int] = {}               # pod -> exception column
        infeasible: set[int] = set()               # empty mask (class 0)

        def _intern_row(row: np.ndarray) -> tuple:
            got = _row_memo.get(id(row))
            if got is None:
                b = row.tobytes()
                cid = _row_bytes.get(b)
                if cid is None:
                    cid = _row_bytes[b] = len(_row_bytes)
                    row_store[cid] = row
                nnz = int(row.sum())
                col = int(np.argmax(row)) if nnz == 1 else -1
                got = _row_memo[id(row)] = (cid, nnz, col)
                _row_refs.append(row)
            return got

        # Pods requesting resources no tracked column covers are infeasible
        # everywhere (would silently drop a constraint on device).
        unknown_res: set[int] = set()
        for i, pi in enumerate(pods):
            if ct.has_unknown_resource(pi.requests):
                infeasible.add(i)
                unknown_res.add(i)

        # Host-side rows: static predicate plugins (signature-cached) and
        # stateful irregular plugins (per pod, Skip-gated).
        dyn_states: dict[int, CycleState] = {}
        nrt_memo: dict[int, tuple] = {}
        dra_memo: dict[int, object] = {}
        #: hard-spread pods deferred for template detection (see
        #: _process_spread_pods): (chunk index, PodInfo, constraints).
        spread_pods: list[tuple[int, PodInfo, list[dict]]] = []
        #: plugin -> {pod -> its (n_real,) ok row} for the lazy per-pod
        #: diagnostics (shared row objects — no plane, no copies).
        host_filter_fail: dict[str, dict[int, np.ndarray]] = {}
        #: pods whose NON-affinity stateful filter gate fired (full host
        #: re-verification). Affinity-handled pods are covered by the cheap
        #: delta verify inside _verify (routed by delta_has_terms /
        #: has_affinity_constraints), not by this set.
        stateful_pods: set[int] = set()
        #: DISTINCT pods that took at least one per-pod host plugin row
        #: this chunk — counted once per pod (not per plugin) into
        #: backend_degradations{kind="host_fallback"} below.
        fallback_pods: set[int] = set()

        def _apply_interned(i: int, cid: int, nnz: int, col: int) -> None:
            if nnz == 0:
                infeasible.add(i)
            elif col >= 0:
                prev = pod_pin.get(i)
                if prev is None:
                    pod_pin[i] = col
                elif prev != col:    # two pins disagree: no node survives
                    infeasible.add(i)
            else:
                lst = pod_rows.get(i)
                if lst is None:
                    lst = pod_rows[i] = []
                if cid not in lst:
                    lst.append(cid)

        def apply_row(pname: str, i: int, row: np.ndarray) -> None:
            # All-true rows are no-ops; applying them would dirty the
            # planes and force a re-upload every batch.
            if row.all():
                return
            fmap = host_filter_fail.get(pname)
            if fmap is None:
                fmap = host_filter_fail[pname] = {}
            prev = fmap.get(i)
            fmap[i] = row if prev is None else (prev & row)
            _apply_interned(i, *_intern_row(row))

        #: shared-row groups for the tensorized InterPodAffinity rows:
        #: template batches produce ONE row object per signature, so the
        #: per-pod O(N) mask AND collapses to one vectorized write per
        #: distinct row (see _affinity_rows).
        ipa_groups: dict[int, tuple[np.ndarray, list[int]]] = {}
        compiler = None

        for plugin in fwk.filter_plugins:
            if plugin.NAME in DEVICE_FILTER_PLUGINS:
                continue
            if plugin.NAME in STATIC_ROW_PLUGINS:
                for i, pi in enumerate(pods):
                    if i in unknown_res:
                        continue
                    row, all_true = self._static_filter_row(
                        plugin, pi, snapshot, ct)
                    if not all_true:
                        apply_row(plugin.NAME, i, row)
            elif plugin.NAME == "InterPodAffinity":
                # Tensorized path (ops/affinity.py): dense per-term masks
                # over interned label signatures instead of O(N) host
                # plugin calls per pod, applied once per distinct row below.
                ipa_groups = self._affinity_rows(
                    plugin, pods, unknown_res, snapshot, ct)
            else:
                gate = _FILTER_ACTIVE.get(plugin.NAME)
                for i, pi in enumerate(pods):
                    if i in unknown_res:
                        continue
                    if gate is not None and not gate(plugin, pi, snapshot):
                        continue
                    if plugin.NAME == "NodeResourceTopologyMatch":
                        # Vectorized zone-alignment rows from batch-start
                        # zone state; in-batch drift → stateful re-check.
                        st_nrt = self._nrt_state(plugin, snapshot, ct)
                        row = self._nrt_filter_row(st_nrt, pi, nrt_memo, i)
                        if not row.all():
                            apply_row(plugin.NAME, i, row)
                        stateful_pods.add(i)
                        continue
                    if plugin.NAME == "DynamicResources":
                        # Vectorized claim-fit rows from batch-start free-
                        # device tensors; in-batch consumption → stateful
                        # re-check (over-admission is corrected there).
                        st_dra = self._dra_state(plugin, snapshot, ct)
                        row = self._dra_filter_row(
                            st_dra, plugin, pi, dra_memo, i)
                        if row is None:
                            state = dyn_states.setdefault(i, CycleState())
                            row = self._dynamic_filter_row(
                                plugin, pi, snapshot, ct, state)
                            if row is not None:
                                fallback_pods.add(i)
                        if row is not None and not row.all():
                            apply_row(plugin.NAME, i, row)
                        stateful_pods.add(i)
                        continue
                    if plugin.NAME == "PodTopologySpread":
                        constraints = plugin._constraints_for(
                            pi, "DoNotSchedule")
                        if not constraints:
                            continue  # gate was conservative; nothing to do
                        spread_pods.append((i, pi, constraints))
                        continue
                    state = dyn_states.setdefault(i, CycleState())
                    row = self._dynamic_filter_row(plugin, pi, snapshot, ct, state)
                    if row is not None:
                        apply_row(plugin.NAME, i, row)
                        # Per-pod host-row residency is DATA (bench detail
                        # host_fallback_pods), not just stderr noise.
                        fallback_pods.add(i)
                    # NodePorts conflicts only affect pods with ports (each
                    # is individually re-verified); cross-pod plugins flip
                    # the whole batch into full re-verification. row None
                    # means the plugin itself skipped after all.
                    if plugin.NAME != "NodePorts" and row is not None:
                        stateful_pods.add(i)

        if fallback_pods and self.metrics is not None:
            self.metrics.backend_degradations.inc(
                len(fallback_pods), kind="host_fallback")

        for row_full, idxs in ipa_groups.values():
            row = row_full[: ct.n_real]
            if row.all():
                continue
            # One interned row per signature group — every member pod
            # references it (class sharing falls out of the shared cid).
            cid, nnz, col = _intern_row(row)
            fmap = host_filter_fail.get("InterPodAffinity")
            if fmap is None:
                fmap = host_filter_fail["InterPodAffinity"] = {}
            for i in idxs:
                prev = fmap.get(i)
                fmap[i] = row if prev is None else (prev & row)
                _apply_interned(i, cid, nnz, col)

        spread_active_idx = self._process_spread_pods(
            spread_pods, pods, ctx, snapshot, ct, apply_row, stateful_pods,
            dyn_states, fwk)
        # Per-pod constraint matrices over the UNION spread table:
        # applies gates the pod's own template's columns; contributes
        # marks which constraints count the pod when placed — built for
        # EVERY pod (non-spread pods can match a template's selector).
        sp_applies = sp_contrib = None
        spt = ctx.spread
        if spt is not None and spt.get("cons"):
            C = len(spt["cons"])
            sp_applies = np.zeros((P, C), dtype=np.float32)
            sp_contrib = np.zeros((P, C), dtype=np.float32)
            active_set = set(spread_active_idx)
            for i, pi, cs in spread_pods:
                if i in active_set:
                    key = self._spread_tpl_key(cs, pi)
                    for c in spt["tpl_cols"].get(key, ()):
                        sp_applies[i, c] = 1.0
            memo = spt.setdefault("contrib_memo", {})
            con_ns = spt["con_ns"]
            con_sels = spt["con_sels"]
            for i, pi in enumerate(pods):
                sig = (pi.namespace,
                       tuple(sorted(pi.labels.items())) if pi.labels
                       else ())
                row = memo.get(sig)
                if row is None:
                    row = np.fromiter(
                        (1.0 if (ns_contains(con_ns[c], pi.namespace)
                                 and con_sels[c].matches(pi.labels))
                         else 0.0 for c in range(C)),
                        dtype=np.float32, count=C)
                    memo[sig] = row
                if row.any():
                    sp_contrib[i] = row

        # Host score rows: computed over each pod's *feasible* node set only
        # (PreScore/Score receive filtered nodes in the reference), then the
        # plugin's own NormalizeScore, then the profile weight. Feasibility
        # here must match the full Filter outcome — static rows ∧ taints ∧
        # exact fit — or min-max normalizations get skewed by scores of
        # nodes the solver will mask anyway.
        # Scores accumulate as interned PARTS, mirroring the filter rows:
        # each contribution is one (n_real,) float32 row shared by every
        # pod of the signature, each pod carries its ordered part list,
        # and the class build sums parts once per class — the per-pod
        # (P,N) float32 plane (~170 MB at 8k×5k) never exists.
        score_store: dict[int, np.ndarray] = {}    # sid -> weighted row
        _score_bytes: dict[bytes, int] = {}
        pod_parts: dict[int, list[int]] = {}       # pod -> ordered sids
        fit_np: np.ndarray | None = None
        taint_np: np.ndarray | None = None

        def _intern_score(row: np.ndarray) -> int:
            b = row.tobytes()
            sid = _score_bytes.get(b)
            if sid is None:
                sid = _score_bytes[b] = len(_score_bytes)
                score_store[sid] = row
            return sid

        def add_score_row(i: int, row: np.ndarray) -> None:
            pod_parts.setdefault(i, []).append(_intern_score(row))

        #: pod FEASIBILITY-CLASS key: (fit class, taint class, the pod's
        #: host filter-row ids + exception column) — pods of one template
        #: share it, so the per-pod O(N) nonzero/normalize work below runs
        #: once per class. This is the SAME key the device-plane class
        #: build uses (plus score parts there).
        feas_memo: dict[tuple, np.ndarray] = {}
        norm_memo: dict[tuple, tuple] = {}

        _pck_memo: dict[int, tuple] = {}

        def pod_class_key(i: int) -> tuple:
            got = _pck_memo.get(i)
            if got is None:
                got = _pck_memo[i] = (
                    batch.req_class[i], batch.untol_class[i],
                    tuple(pod_rows.get(i, ())), pod_pin.get(i, -1),
                    i in infeasible)
            return got

        def feasible_idx(i: int) -> np.ndarray:
            # Class-level masks: one row per DISTINCT request/toleration
            # shape (equivalence classes), not per pod — the (P,N,R)
            # broadcast was a top host cost for score-bearing families.
            nonlocal fit_np, taint_np
            pk = pod_class_key(i)
            got = feas_memo.get(pk)
            if got is not None:
                return got
            if i in infeasible:
                got = feas_memo[pk] = np.zeros((0,), dtype=np.intp)
                return got
            if fit_np is None:
                uq = np.stack(batch.req_rows)  # (n_classes, R)
                fit_np = np.all(
                    ct.used_q[None, :, :] + uq[:, None, :]
                    <= ct.alloc_q[None, :, :], axis=-1)
                fit_np &= (ct.used_pods + 1 <= ct.alloc_pods)[None, :]
                if "TaintToleration" in filter_names:
                    ut = np.stack(batch.untol_rows)
                    taint_np = (ut.astype(np.int32)
                                @ ct.taint_filter_mat.T.astype(np.int32)) == 0
                else:
                    taint_np = np.ones(
                        (len(batch.untol_rows),
                         ct.taint_filter_mat.shape[0]), dtype=np.bool_)
            feas = fit_np[batch.req_class[i], : ct.n_real] \
                & taint_np[batch.untol_class[i], : ct.n_real]
            rows_i = pod_rows.get(i)
            if rows_i:
                feas = feas.copy()
                for cid in rows_i:
                    feas &= row_store[cid]
            pin = pod_pin.get(i)
            if pin is not None:
                keep = bool(feas[pin])
                feas = np.zeros_like(feas)
                feas[pin] = keep
            got = feas_memo[pk] = np.nonzero(feas)[0]
            return got

        for name, plugin in score_plugins.items():
            if name in DEVICE_SCORE_PLUGINS:
                continue
            w = fwk.score_weights.get(name, 1)
            for i, pi in enumerate(pods):
                if i in unknown_res:
                    continue
                if name in STATIC_SCORE_PLUGINS:
                    if name == "NodeAffinity" and not (
                            (pi.affinity.get("nodeAffinity") or {})
                            .get("preferredDuringSchedulingIgnoredDuringExecution")):
                        continue
                    row, any_nonzero = self._static_score_row(
                        plugin, pi, snapshot, ct)
                    if not any_nonzero:
                        continue
                    raw = {ct.node_names[j]: float(row[j])
                           for j in feasible_idx(i)}
                else:
                    gate = _SCORE_ACTIVE.get(name)
                    if gate is not None and not gate(plugin, pi, snapshot):
                        continue
                    if name == "NodeResourceTopologyMatch":
                        st_nrt = self._nrt_state(plugin, snapshot, ct)
                        srow = self._nrt_score_row(st_nrt, pi, nrt_memo, i)
                        if srow.any():
                            add_score_row(
                                i, (w * srow).astype(np.float32))
                        continue
                    if name == "PodTopologySpread":
                        # Tensorized raw counts + vectorized NormalizeScore
                        # (min-max inversion over the feasible set) — every
                        # constraint shape, namespaceSelector included.
                        # Memoized per (feasibility class, pod signature):
                        # template batches normalize once.
                        constraints = plugin._constraints_for(
                            pi, "ScheduleAnyway")
                        nk = ("pts", pod_class_key(i), pi.namespace,
                              tuple(sorted(pi.labels.items())),
                              repr(constraints),
                              repr(pi.node_selector),
                              repr(pi.affinity.get("nodeAffinity")),
                              repr(pi.tolerations))
                        got = norm_memo.get(nk)
                        if got is None:
                            if compiler is None:
                                compiler = self._affinity_compiler(
                                    snapshot, ct)
                            raw_row = compiler.spread_raw_scores(
                                pi, constraints)[: ct.n_real]
                            feas = feasible_idx(i)
                            wnorm = None
                            if feas.size:
                                vals = raw_row[feas]
                                mx, mn = vals.max(), vals.min()
                                if mx > mn:
                                    wnorm = w * 100.0 * (mx - vals) \
                                        / (mx - mn)
                                else:
                                    wnorm = np.full_like(vals, w * 100.0)
                            got = norm_memo[nk] = (feas, wnorm, [])
                        got[2].append(i)
                        continue
                    if name == "InterPodAffinity":
                        if ipa_rows is not None and ipa_rows[i] >= 0:
                            continue    # carried: the scan scores it
                        if compiler is None:
                            compiler = self._affinity_compiler(snapshot, ct)
                        if not self._ipa_score_relevant(pi, compiler):
                            # No preferred terms anywhere and no
                            # hard-affinity symmetry sources → every score
                            # is 0; skip the O(N × residents) walk.
                            continue
                        # Tensorized for every term shape
                        # (namespaceSelector terms resolve at compile
                        # time); memoized per (feasibility class, pod
                        # signature), so template batches compute and
                        # normalize once.
                        nk = ("ipa", pod_class_key(i), pi.namespace,
                              tuple(sorted(pi.labels.items())),
                              repr(pi.preferred_affinity_terms),
                              repr(pi.preferred_anti_affinity_terms))
                        got = norm_memo.get(nk)
                        if got is None:
                            feas = feasible_idx(i)
                            feas_mask = np.zeros(
                                (ct.n_pad,), dtype=np.bool_)
                            feas_mask[feas] = True
                            raw_row = compiler.score_row(
                                pi, float(getattr(
                                    plugin, "hard_pod_affinity_weight", 1)),
                                feas_mask)[: ct.n_real]
                            wnorm = None
                            if feas.size:
                                vals = raw_row[feas]
                                mx, mn = vals.max(), vals.min()
                                if mx > mn:
                                    wnorm = w * 100.0 * (vals - mn) \
                                        / (mx - mn)
                            got = norm_memo[nk] = (feas, wnorm, [])
                        got[2].append(i)
                        continue
                    state = dyn_states.setdefault(i, CycleState())
                    nodes_i = [snapshot.nodes[j] for j in feasible_idx(i)]
                    st = plugin.pre_score(state, pi, nodes_i)
                    if st.is_skip() or not st.is_success():
                        continue
                    raw = {ni.name: plugin.score(state, pi, ni)
                           for ni in nodes_i}
                state = dyn_states.get(i) or CycleState()
                plugin.normalize_scores(state, pi, raw)
                if raw:
                    srow = np.zeros((ct.n_real,), dtype=np.float32)
                    for nname, s in raw.items():
                        srow[ct.name_to_idx[nname]] += w * s
                    add_score_row(i, srow)

        # Flush of the memoized normalized score rows: each group's
        # sparse (feas, wnorm) pair densifies ONCE into an interned part
        # row shared by every member pod — the r7 row-dictionary wire
        # generalized; the class build below folds parts into (C,N)
        # class score rows, so no per-pod plane exists for ANY number of
        # distinct rows.
        for feas, wnorm, idxs in norm_memo.values():
            if wnorm is None or not idxs:
                continue
            srow = np.zeros((ct.n_real,), dtype=np.float32)
            srow[feas] = wnorm
            sid = _intern_score(srow)
            for i in idxs:
                pod_parts.setdefault(i, []).append(sid)

        # ---- class-dictionary plane build (the native device format) --
        # Pods dedupe into equivalence classes keyed by (request row,
        # toleration row, filter-row ids, ordered score-part ids) —
        # exception pins deliberately EXCLUDED, they ride the sparse
        # exc vector so a pinned pod shares its template's class. Class
        # 0 is reserved EMPTY (padding pods, unknown resources,
        # conflicting pins). Overflowing the cap — or the
        # KTPU_CLASS_PAD=0 kill switch (cap 0) — falls back to
        # per-pod planes (C == P, identity index): structurally the
        # pre-class dense format, bit-identical assignments.
        cap = ctx.class_pad
        mask_dirty = bool(pod_rows or pod_pin or infeasible)
        scores_dirty = bool(pod_parts)
        R = len(ct.resources)
        tf = batch.untol_filter.shape[1]
        tp = batch.untol_prefer.shape[1]
        class_reps: list[int] | None = None
        class_parts: list[tuple] = []
        cls_np = exc_np = None
        if cap:
            cls_map: dict[tuple, int] = {}
            class_reps = []
            cls_np = np.zeros((P,), dtype=np.int32)
            exc_np = np.full((P,), -1, dtype=np.int32)
            for i in range(batch.p_real):
                if i in infeasible:
                    continue                                   # class 0
                pin = pod_pin.get(i)
                # A pinned pod's argmax ranges over AT MOST one column,
                # so its score row cannot change its assignment — drop
                # its parts from the key (and the plane) rather than
                # let per-pin normalization split every pinned pod into
                # its own class. The class score row sums the KEY's
                # parts (class_parts), never the rep's, so a pinned rep
                # can't smuggle its dropped parts into a shared class.
                eff_parts = () if pin is not None \
                    else tuple(pod_parts.get(i, ()))
                ckey = (batch.req_class[i], batch.untol_class[i],
                        tuple(pod_rows.get(i, ())), eff_parts)
                c = cls_map.get(ckey)
                if c is None:
                    if len(class_reps) >= cap:
                        class_reps = None
                        break
                    c = cls_map[ckey] = len(class_reps) + 1
                    class_reps.append(i)
                    class_parts.append(eff_parts)
                cls_np[i] = c
                if pin is not None:
                    exc_np[i] = pin

        plane_bytes = 0
        if class_reps is not None:
            crows = _class_rows_bucket(len(class_reps))
            n_cls = len(class_reps)
            pack_np = np.zeros((crows, 2 * R + tf + tp), dtype=np.int32)
            if n_cls:
                ridx = np.asarray(class_reps, dtype=np.intp)
                pack_np[1: n_cls + 1] = np.concatenate(
                    [batch.req_q[ridx], batch.req_nz_q[ridx],
                     batch.untol_filter[ridx].astype(np.int32),
                     batch.untol_prefer[ridx].astype(np.int32)], axis=1)
            # Mask and score planes are cached INDEPENDENTLY (the r6
            # packed-wire discipline): a chunk with only score rows
            # keeps its clean cached mask and uploads scores alone, and
            # vice versa. Cache keys carry a format tag — the class and
            # per-pod keys are both 4-int tuples otherwise and could
            # collide at toy node pads.
            if mask_dirty:
                mask_np = np.zeros((crows, N), dtype=np.bool_)
                rowset_memo: dict[tuple, np.ndarray] = {}
                for c, rep in enumerate(class_reps, start=1):
                    rs = tuple(pod_rows.get(rep, ()))
                    row = rowset_memo.get(rs)
                    if row is None:
                        row = np.ones((ct.n_real,), dtype=np.bool_)
                        for cid in rs:
                            row = row & row_store[cid]
                        rowset_memo[rs] = row
                    mask_np[c, : ct.n_real] = row
                packed = np.packbits(mask_np, axis=1)
                dev_mask = self._put(packed, "pn")
                plane_bytes += packed.nbytes
            else:
                # Clean mask: all-true for every real class — depends
                # only on (plane rows, class count, node count), so one
                # cached upload serves every such chunk of the shape.
                mkey = ("cls", crows, n_cls, N, ct.n_real)
                dev_mask = self._dev_base_mask.get(mkey)
                if dev_mask is None:
                    mask_np = np.zeros((crows, N), dtype=np.bool_)
                    mask_np[1: n_cls + 1, : ct.n_real] = True
                    packed = np.packbits(mask_np, axis=1)
                    dev_mask = self._dev_base_mask[mkey] = \
                        self._put(packed, "pn")
                    plane_bytes += packed.nbytes
            if scores_dirty:
                scores_np = np.zeros((crows, N), dtype=np.float32)
                for c, parts in enumerate(class_parts, start=1):
                    for sid in parts:
                        scores_np[c, : ct.n_real] += score_store[sid]
                wire_scores = compress_score_wire(scores_np)
                dev_scores = self._put(wire_scores, "pn")
                plane_bytes += wire_scores.nbytes
            else:
                dev_scores = self._dev_zero_scores.get((crows, N))
                if dev_scores is None:
                    dev_scores = self._dev_zero_scores[(crows, N)] = \
                        self._put(np.zeros((crows, N), dtype=np.float16),
                                  "pn")
                    plane_bytes += crows * N * 2
        else:
            # Per-pod fallback (kill switch / class overflow): C == P,
            # identity index — the planes the pre-class format shipped.
            crows = P
            cls_np = None  # identity: served from the _dev_arange cache
            exc_np = np.full((P,), -1, dtype=np.int32)
            pack_np = np.concatenate(
                [batch.req_q, batch.req_nz_q,
                 batch.untol_filter.astype(np.int32),
                 batch.untol_prefer.astype(np.int32)], axis=1)
            if cap and self.metrics is not None:
                # Genuine class overflow (not the kill switch): counted
                # per pod, like the other degradation kinds.
                self.metrics.class_split_fallbacks.inc(batch.p_real)
            if mask_dirty:
                mask_np = np.zeros((P, N), dtype=np.bool_)
                mask_np[: batch.p_real, : ct.n_real] = True
                for i, lst in pod_rows.items():
                    for cid in lst:
                        mask_np[i, : ct.n_real] &= row_store[cid]
                for i, pin in pod_pin.items():
                    keep = mask_np[i, pin]
                    mask_np[i, :] = False
                    mask_np[i, pin] = keep
                for i in infeasible:
                    mask_np[i, :] = False
                packed = np.packbits(mask_np, axis=1)
                dev_mask = self._put(packed, "pn")
                plane_bytes += packed.nbytes
            else:
                mkey = ("pod", P, N, batch.p_real, ct.n_real)
                dev_mask = self._dev_base_mask.get(mkey)
                if dev_mask is None:
                    mask_np = np.zeros((P, N), dtype=np.bool_)
                    mask_np[: batch.p_real, : ct.n_real] = True
                    packed = np.packbits(mask_np, axis=1)
                    dev_mask = self._dev_base_mask[mkey] = \
                        self._put(packed, "pn")
                    plane_bytes += packed.nbytes
            if scores_dirty:
                scores_np = np.zeros((P, N), dtype=np.float32)
                for i, parts in pod_parts.items():
                    for sid in parts:
                        scores_np[i, : ct.n_real] += score_store[sid]
                wire_scores = compress_score_wire(scores_np)
                dev_scores = self._put(wire_scores, "pn")
                plane_bytes += wire_scores.nbytes
            else:
                dev_scores = self._dev_zero_scores.get((P, N))
                if dev_scores is None:
                    dev_scores = self._dev_zero_scores[(P, N)] = \
                        self._put(np.zeros((P, N), dtype=np.float16), "pn")
                    plane_bytes += P * N * 2

        # The (P,) class index + exception vector + (C, ·) rep-row pack
        # ride every chunk (tiny); the identity index (per-pod fallback)
        # and the no-exception vector reuse one cached upload per width.
        if cls_np is None:
            cls_np = np.arange(P, dtype=np.int32)
            dev_cls = self._dev_arange.get(P)
            if dev_cls is None:
                dev_cls = self._dev_arange[P] = self._put(cls_np)
                plane_bytes += cls_np.nbytes
        else:
            dev_cls = self._put(cls_np)
            plane_bytes += cls_np.nbytes
        dev_pack = self._put(pack_np)
        plane_bytes += pack_np.nbytes
        if pod_pin and class_reps is not None:
            dev_exc = self._put(exc_np)
            plane_bytes += exc_np.nbytes
        else:
            dev_exc = self._dev_no_exc.get(P)
            if dev_exc is None:
                dev_exc = self._dev_no_exc[P] = self._put(
                    np.full((P,), -1, dtype=np.int32))

        # Shortlist activation: the chunk-start prefilter reads the
        # class planes directly (O(C·N)), so the pruned solve runs for
        # EVERY class-mode chunk the tuner's width policy accepts —
        # heterogeneous score rows no longer defeat it (they are class
        # rows now). The per-pod fallback keeps the full N-wide scan: a
        # (P,N) prefilter would cost more than the pruning saves.
        shortlist_k = 0
        if class_reps is not None:
            shortlist_k = self._tuner.shortlist_k(P, ct.n_real)

        # Wavefront width: 0 = the KTPU_WAVEFRONT kill switch (the W=1
        # scan functions, structurally), else the tuner's policy W
        # (override-pinned or replay-feedback-narrowed). W is a static
        # arg of the fused program, so it is part of the chunk program
        # key like the shortlist width.
        wave_w = 0
        if flags.get("KTPU_WAVEFRONT"):
            wave_w = self._tuner.wave_width(P)

        # Block-index width: the two-pass block-sparse prefilter rides
        # the shortlist (it prunes the prefilter's own O(C·N) pass), so
        # it activates only with it — the tuner's structural large-N
        # row plus the KTPU_BLOCK_WIDTH knob. 0 is the full-width
        # prefilter, structurally (a static arg of the fused program,
        # part of the chunk program key like W and K).
        block_w = self._tuner.block_width(
            ct.n_pad, ct.n_real, shortlist_k) if shortlist_k else 0

        # Multi-start orders: identity first (ties → oracle-equivalent),
        # then size-desc / size-asc / seeded shuffles. Permutations are
        # PRIORITY-BLOCK-STABLE: pods only move within runs of equal
        # priority (queue order is priority order — reordering across
        # blocks could strand a high-priority pod behind a bulkier
        # low-priority order, a starvation the reference can't exhibit).
        # Padding stays in place; its mask is all-False anyway.
        K = self.multistart
        pr = batch.p_real
        if K > 1 and pr > 1:
            sizes = batch.req_q[:pr].sum(axis=1)
            prios = np.fromiter((p.priority for p in pods), dtype=np.int64,
                                count=pr)
            perms_key = (K, P, pr, sizes.tobytes(), prios.tobytes())
        else:
            perms_key = (K, P)
        dev_perms = self._dev_perms_cache.get(perms_key)
        if dev_perms is None:
            perms = np.tile(np.arange(P, dtype=np.int32), (K, 1))
            if K > 1 and pr > 1:
                blocks = []
                lo = 0
                for hi in range(1, pr + 1):
                    if hi == pr or prios[hi] != prios[lo]:
                        blocks.append((lo, hi))
                        lo = hi
                rng = np.random.default_rng(0xC0FFEE + pr)

                def fill(k, order_of):
                    for lo, hi in blocks:
                        perms[k, lo:hi] = lo + order_of(lo, hi)
                if K > 1:
                    fill(1, lambda lo, hi: np.argsort(
                        -sizes[lo:hi], kind="stable").astype(np.int32))
                if K > 2:
                    fill(2, lambda lo, hi: np.argsort(
                        sizes[lo:hi], kind="stable").astype(np.int32))
                for k in range(3, K):
                    fill(k, lambda lo, hi: rng.permutation(
                        hi - lo).astype(np.int32))
            dev_perms = self._put(perms)
            if len(self._dev_perms_cache) > 64:
                self._dev_perms_cache.clear()
            self._dev_perms_cache[perms_key] = dev_perms

        # Gang membership (Coscheduling): all-or-nothing inside the solve.
        # The quota is what the gang still NEEDS: minMember minus members
        # already assembled (bound or parked at Permit) — a fully-assembled
        # gang's stragglers place individually, like the Permit path.
        gang_onehot = None
        gang_required = None
        cosched = next(
            (pl for pl in fwk.plugins if pl.NAME == "Coscheduling"), None)
        if cosched is not None and getattr(cosched, "pg_informer", None) \
                is not None:
            groups: dict[str, list[int]] = {}
            for i, pi in enumerate(pods):
                gk = cosched.group_key(pi)
                if gk:
                    groups.setdefault(gk, []).append(i)
            if groups:
                gang_onehot = np.zeros((P, _GANG_PAD), dtype=np.float32)
                gang_required = np.zeros((_GANG_PAD,), dtype=np.float32)
                if len(groups) > _GANG_PAD:
                    # Overflow gangs lose in-solver all-or-nothing and
                    # fall back to the Permit barrier alone — weaker
                    # atomicity under contention; observable, not silent.
                    logger.warning(
                        "%d gangs in chunk exceed solver capacity %d; "
                        "%d gangs degrade to Permit-barrier-only "
                        "atomicity", len(groups), _GANG_PAD,
                        len(groups) - _GANG_PAD)
                    if self.metrics is not None:
                        self.metrics.backend_degradations.inc(
                            len(groups) - _GANG_PAD, kind="gang_overflow")
                for g, (gk, idxs) in enumerate(groups.items()):
                    if g >= _GANG_PAD:
                        break  # overflow gangs: Permit barrier only
                    pg = cosched._pod_group(gk)
                    mm = int(((pg or {}).get("spec") or {})
                             .get("minMember", 1))
                    assembled = len(cosched._bound.get(gk) or ()) + \
                        len(cosched._waiting.get(gk) or ())
                    for i in idxs:
                        gang_onehot[i, g] = 1.0
                    gang_required[g] = min(max(mm - assembled, 0), len(idxs))

        self._tuner.observe_chunk()
        if self.metrics is not None:
            self.metrics.plane_classes.set(
                len(class_reps) if class_reps is not None else batch.p_real)
            if plane_bytes:
                self.metrics.plane_bytes.inc(plane_bytes)
            self.metrics.prep_duration.observe(
                time.perf_counter() - prep_t0)
        return {
            "pods": pods, "batch": batch,
            "dev_mask": dev_mask, "dev_scores": dev_scores,
            "dev_cls": dev_cls, "dev_exc": dev_exc, "dev_pack": dev_pack,
            "cls_np": cls_np,
            "host_filter_fail": host_filter_fail,
            "unknown_res": unknown_res, "stateful_pods": stateful_pods,
            "spread_active_idx": spread_active_idx,
            "sp_applies": sp_applies, "sp_contrib": sp_contrib,
            "chunk_idx": chunk_idx,
            "dev_perms": dev_perms, "gang_onehot": gang_onehot,
            "gang_required": gang_required,
            "shortlist_k": shortlist_k,
            "wave_w": wave_w,
            "block_w": block_w,
            "class_mode": class_reps is not None,
            "scan_width": (shortlist_k + P) if shortlist_k else ct.n_real,
        }

    def _dispatch_chunk(self, prep: dict, ctx: "_AssignCtx") -> dict:
        """Dispatch the fused solve for one chunk; device used-state chains
        through self._dev_used without host sync. Bracketed with a
        StepTraceAnnotation (one profiler step per chunk) and, when
        tracing is on, a solver.dispatch span under the attempt."""
        with self._span("solver.dispatch", chunk=prep.get("chunk_idx"),
                        pods=prep["batch"].p_real):
            return self._dispatch_chunk_inner(prep, ctx)

    def _dispatch_chunk_inner(self, prep: dict, ctx: "_AssignCtx") -> dict:
        with _STEP_ANNOTATION("ktpu.solve",
                              step_num=prep.get("chunk_idx", 0)):
            return self._dispatch_chunk_jit(prep, ctx)

    def ensure_static(self, ct: ClusterTensors) -> dict:
        """Device-resident node-static arrays (alloc, taints), refreshed
        only when the static fingerprint moves — shared by the chunk
        dispatch and the serving tier's single-pod fast path."""
        if self._dev_static_fp != ct._static_fp or \
                self._dev_static.get("alloc_shape") != ct.alloc_q.shape:
            self._dev_static = {
                "alloc_q": self._put(ct.alloc_q, "nodes_mat"),
                "alloc_pods": self._put(ct.alloc_pods, "nodes_vec"),
                "taint_f": self._put(ct.taint_filter_mat, "nodes_mat"),
                "taint_p": self._put(ct.taint_prefer_mat, "nodes_mat"),
                "alloc_shape": ct.alloc_q.shape,
            }
            self._dev_static_fp = ct._static_fp
        return self._dev_static

    def _dispatch_chunk_jit(self, prep: dict, ctx: "_AssignCtx") -> dict:
        ct, p = ctx.ct, ctx.params
        batch = prep["batch"]
        self.ensure_static(ct)

        sp = ctx.spread
        # The spread scan must run for any chunk whose pods contribute to
        # the table's counts (a non-spread pod matching a template's
        # selector still moves domain counts) — UNLESS no later chunk has
        # gated pods, in which case the counts can't influence anything
        # and the chunk keeps the multistart solver.
        use_spread = bool(
            sp is not None and sp.get("cons")
            and prep["sp_contrib"] is not None
            and (prep["spread_active_idx"]
                 or (prep["sp_contrib"].any()
                     and prep["chunk_idx"] < ctx.spread_last_gated)))
        prep["spread_used"] = use_spread
        # spread∩shortlist keeps its W=1 scan (see _mask_solve_update);
        # pinning the static arg to 0 here avoids minting per-W program
        # variants that would all route to the same W=1 body.
        if use_spread and prep["shortlist_k"]:
            prep["wave_w"] = 0
        # A chunk whose placements move an InterPodAffinity score (or feed
        # a later chunk's) carries the counts: the W = 1 full-width scan,
        # no plan (the policy row below), no shortlist.
        ipa = ctx.ipa
        carried = False
        if ipa is not None:
            rows_np, tpls_np = ipa["chunks"][prep["chunk_idx"]]
            carried = bool((rows_np >= 0).any() or (
                (tpls_np >= 0).any() and prep["chunk_idx"] < ipa["last"]))
        if carried:
            prep["shortlist_k"] = 0
            prep["wave_w"] = 0
            ipa_args = ipa["dev"] + (ipa["placed"], self._put(rows_np),
                                     self._put(tpls_np))
        else:
            ipa_args = None
        # Solve-mode policy row (r20): greedy pins the r18 call graph;
        # optimal routes the Sinkhorn plan + rounding. Transport plans
        # tie across equally-attractive columns, so under optimal mode
        # wave speculation would conflict-replay nearly every wave and
        # the shortlist prefilter would re-derive what the plan already
        # encodes — the rounding keeps the W=1 kill-switch scan shape
        # (assignments are bit-identical at any W regardless; the
        # differential suite pins it) and the full-row scan.
        solve_mode, opt_fallback = self._tuner.solve_mode(
            batch.p_real,
            has_gang=prep["gang_onehot"] is not None,
            spread=use_spread,
            class_mode=prep.get("class_mode", False),
            exclusive=any(pi.has_required_anti_affinity
                          for pi in prep["pods"]),
            carried=carried)
        if solve_mode == "optimal":
            prep["shortlist_k"] = 0
            prep["wave_w"] = 0
        # The block index rides the shortlist; any route that zeroed K
        # (optimal mode) zeroes the block width with it.
        if not prep["shortlist_k"]:
            prep["block_w"] = 0
        prep["solve_mode"] = solve_mode
        prep["optimal_fallback"] = opt_fallback
        if use_spread:
            sp_args = (sp["dev_dom"], sp["dev_cid"], sp["dev_counts"],
                       sp["dev_skew"], sp["dev_min_ok"], sp["dev_haskey"],
                       self._put(prep["sp_applies"]),
                       self._put(prep["sp_contrib"]))
        else:
            sp_args = self._spread_dummies(ct.n_pad, batch.req_q.shape[0])
        assign_d, used_pack2, fit0_d, taint_ok_d, dom_counts2, placed2 = \
            _solve_program()(
                self._dev_static["alloc_q"], self._dev_used,
                self._dev_static["alloc_pods"], prep["dev_pack"],
                prep["dev_cls"], prep["dev_exc"],
                self._dev_static["taint_f"], self._dev_static["taint_p"],
                prep["dev_mask"], prep["dev_scores"],
                p["fit_col_w"], p["bal_col_mask"], p["shape_u"], p["shape_s"],
                p["w_fit"], p["w_bal"], p["w_taint"], p["taint_filter_on"],
                *sp_args,
                prep["dev_perms"], *self._gang_args(prep, batch),
                np.int32(max(1, flags.get("KTPU_SINKHORN_ITERS"))),
                np.float32(flags.get("KTPU_SINKHORN_TEMP")),
                np.int32(ct.n_real), np.int32(batch.p_real), ipa_args,
                p["strategy"], use_spread, prep["shortlist_k"],
                prep["wave_w"], solve_mode, prep["block_w"],
            )
        self._dev_used = used_pack2
        if use_spread:
            sp["dev_counts"] = dom_counts2
        if carried:
            ipa["placed"] = placed2
        if self.metrics is not None:
            # The scan's trip count as the program was just handed it.
            w = max(prep["wave_w"], 1)
            run = -(-batch.p_real // w)
            self.metrics.solver_scan_steps.inc(run, kind="run")
            self.metrics.solver_scan_steps.inc(
                batch.req_q.shape[0] // w - run, kind="skipped")
        # Start the device→host copy now; the fetch in _finalize_chunk then
        # overlaps the next chunk's solve (and, in assign_async, bind tasks).
        assign_d.copy_to_host_async()
        prep["assign_d"] = assign_d
        prep["fit0_d"] = fit0_d
        prep["taint_ok_d"] = taint_ok_d
        return prep

    def _finalize_chunk(self, run: dict, assign_np: np.ndarray,
                        ctx: "_AssignCtx") -> None:
        pods, batch = run["pods"], run["batch"]
        assign = assign_np[: batch.p_real]

        # Solve-side observability: the fused program appends the chunk's
        # [shortlist fallbacks, wave commits, wave replays, blocks
        # scanned, blocks pruned] tail to the assign vector (one fetch).
        # The tuner's hit-rate feedback widens K when fallbacks climb and
        # narrows W when replays climb. A poisoned multistart chunk
        # reports the PADDED width — clamp to real pods so rates never
        # exceed 100%. The block counters are (class, block) pair counts,
        # not pod counts — no clamp.
        nfall = min(int(assign_np[-5]), batch.p_real)
        wave_com = min(int(assign_np[-4]), batch.p_real)
        wave_rep = min(int(assign_np[-3]), batch.p_real)
        blk_scanned = int(assign_np[-2])
        blk_pruned = int(assign_np[-1])
        if run.get("shortlist_k"):
            self._tuner.observe_solve(batch.p_real, nfall)
        if run.get("wave_w", 0) > 1:
            self._tuner.observe_wave(wave_com, wave_rep)
        if self.metrics is not None:
            self.metrics.solver_scan_width.set(run["scan_width"])
            self.metrics.solver_wave_width.set(max(1, run.get("wave_w", 0)))
            if wave_com:
                self.metrics.solver_wave_commits.inc(wave_com)
            if wave_rep:
                self.metrics.solver_wave_replays.inc(wave_rep)
            if run.get("shortlist_k"):
                self.metrics.solver_shortlist_pods.inc(batch.p_real)
                if nfall:
                    self.metrics.solver_shortlist_fallbacks.inc(nfall)
            # Block-prefilter accounting: scanned counts every (class,
            # block) pair the bound scan walked for chunks routed with
            # block_w > 0; pruned counts the pairs the exactness
            # predicate proved losers (0 for a chunk whose predicate
            # fell back full-width in-program). block_w == 0 chunks
            # report neither — the zero-counter structural degrade the
            # smoke test pins.
            if run.get("block_w"):
                if blk_scanned:
                    self.metrics.solver_blocks_scanned.inc(blk_scanned)
                if blk_pruned:
                    self.metrics.solver_blocks_pruned.inc(blk_pruned)
            # Optimal-mode accounting (r20): solves count CHUNKS routed
            # through the Sinkhorn plan; fallbacks count chunks the
            # policy WANTED optimal but structure (spread / per-pod
            # planes) degraded to greedy. The iterations gauge records
            # what the latest optimal solve actually ran — fori_loop
            # runs the flag's count exactly.
            if run.get("solve_mode") == "optimal":
                self.metrics.solver_optimal_solves.inc()
                self.metrics.solver_sinkhorn_iterations.set(
                    max(1, flags.get("KTPU_SINKHORN_ITERS")))
            elif run.get("optimal_fallback"):
                self.metrics.solver_optimal_fallbacks.inc()
            if ctx.ct.prep_shards > 1:
                # Sharded-path solve accounting: the fused program spans
                # every shard, so the wall is labeled with the shard
                # COUNT; the top-level argmax merges once per pod step.
                self.metrics.shard_solve_seconds.inc(
                    run.get("solve_wall_s", 0.0),
                    shards=str(ctx.ct.prep_shards))
                self.metrics.cross_shard_reductions.inc(batch.p_real)

        # Host verify + working-state accumulation (hard part #1). The
        # verify context is shared across chunks, so later chunks are
        # checked against earlier chunks' accepted placements. Scan-trusted
        # spread pods skip the host re-check — UNLESS the template was
        # poisoned after this chunk was dispatched (a mixed chunk appeared):
        # then they re-enter the stateful set, restoring exactness.
        stateful = run["stateful_pods"]
        # (Templates are fixed at table-build time from ALL chunks, so a
        # later chunk can no longer invalidate scan-trusted placements.)
        with self._span("solver.verify", pods=len(pods)):
            rejects = self._verify(pods, assign, ctx, stateful)

        # Fold verify rejections back into the device-chained used-state so
        # later chunks don't see the rejected pods' resources as consumed.
        # Chunks already in flight were dispatched against the inflated
        # state — conservative only (a reject can make a later in-flight pod
        # look unschedulable; it just requeues). Adds commute, so
        # subtracting from the CURRENT chained state is exact for every
        # chunk dispatched after this point.
        if rejects:
            used = np.asarray(self._dev_used).copy()
            r = batch.req_q.shape[1]
            for i, idx in rejects:
                used[idx, :r] -= batch.req_q[i]
                used[idx, r:2 * r] -= batch.req_nz_q[i]
                used[idx, 2 * r] -= 1
            self._dev_used = self._put(used, "nodes_mat")
            # Rejected pods that CONTRIBUTED to spread counts fold out of
            # the chained domain counts (adds commute, same argument as
            # the used-state) — masked per constraint the pod matches.
            sp = ctx.spread
            contrib = run.get("sp_contrib")
            if sp is not None and run.get("spread_used") \
                    and contrib is not None:
                cid = sp["cid_onehot_host"]
                adj = None
                for i, idx in rejects:
                    row = contrib[i]
                    if not row.any():
                        continue
                    if adj is None:
                        adj = np.zeros(
                            sp["dom_onehot_host"].shape[1], np.float32)
                    adj -= sp["dom_onehot_host"][idx] * (cid @ row)
                if adj is not None:
                    sp["dev_counts"] = self._put(
                        np.asarray(sp["dev_counts"]) + adj)

        # Lazy per-plugin diagnostics for unassigned pods.
        need_diag = [i for i, pi in enumerate(pods)
                     if ctx.assignments.get(pi.key) is None
                     and pi.key not in ctx.diagnostics]
        if need_diag:
            fit0, taint_ok = self._diag_planes(run)
            self._build_diagnostics(
                need_diag, pods, ctx.ct, batch, fit0, taint_ok,
                run["cls_np"],
                run["host_filter_fail"], ctx.params["filter_names"],
                ctx.diagnostics, run["unknown_res"])

    @staticmethod
    def _fetch_diag_planes(run: dict) -> None:
        """Worker-thread fetch of the diagnostic unsat planes: start both
        device→host copies before blocking so the transfers overlap."""
        check_dispatch_seam("backend.fetch_diag_planes")
        for k in ("fit0_d", "taint_ok_d"):
            run[k].copy_to_host_async()
        run["fit0_np"] = np.asarray(run["fit0_d"])
        run["taint_ok_np"] = np.asarray(run["taint_ok_d"])

    @staticmethod
    def _diag_planes(run: dict) -> tuple[np.ndarray, np.ndarray]:
        """(fit0, taint_ok): the chunk-start fit and taint planes the
        fused program computed, on the host — as `_fetch_diag_planes`
        left them, else fetched now."""
        if "fit0_np" not in run:
            run["fit0_np"] = np.asarray(run["fit0_d"])
            run["taint_ok_np"] = np.asarray(run["taint_ok_d"])
        return run["fit0_np"], run["taint_ok_np"]

    @classmethod
    def chunk_feasibility(cls, run: dict) -> dict[str, np.ndarray]:
        """Pod key -> that pod's chunk-start feasibility row over the
        (padded) node axis AS THE DEVICE HELD IT: the bit-packed host-row
        mask it was sent, AND the fit and taint planes the fused program
        computed there, narrowed to the pod's single-allowed-column
        exception. The row a differential compares with the host
        plugins' Filter verdicts (chip_smoke.py); it is exact w.r.t. the
        snapshot for the first chunk only — later chunks start from
        earlier chunks' debits."""
        fit0, taint_ok = cls._diag_planes(run)
        width = fit0.shape[1]
        bits = np.unpackbits(np.asarray(run["dev_mask"]), axis=1)
        feas = bits[:, :width].astype(np.bool_) & fit0 & taint_ok
        exc = np.asarray(run["dev_exc"])
        out = {}
        for i, pi in enumerate(run["pods"]):
            row = feas[run["cls_np"][i]]
            if exc[i] >= 0:
                row = row & (np.arange(width) == exc[i])
            out[pi.key] = row
        return out

    # -- verification --------------------------------------------------------

    def _verify(self, pods, assign, ctx: "_AssignCtx", stateful_pods
                ) -> list[tuple[int, int]]:
        """Post-solve verification (hard part #1: solve → verify → requeue).
        Returns [(chunk index, node index)] for solver assignments the host
        rejected, so the caller can fold them out of the device used-state.

        The batch-start masks are EXACT w.r.t. the snapshot (host rows use
        the host plugins; the tensorized affinity rows are differential-
        tested), so verification only has to account for the *delta* —
        pods placed earlier in this same batch:

        - resources: exact integer re-check against the working node
        - inter-pod affinity (incl. symmetry both ways): checked against
          the delta placements only — O(|delta| × terms), not O(cluster)
        - host ports: against the working node's accumulated ports
        - anything else stateful (PodTopologySpread & friends in
          `stateful_pods`): full host re-check against a working snapshot

        The working snapshot / delta list live on ctx and are SHARED across
        chunks of one assign() call, so chunk k+1 is verified against chunk
        k's accepted placements.
        """
        snapshot, fwk, ct = ctx.snapshot, ctx.fwk, ctx.ct
        # The snapshot's own compiler or none: a kept one that no pod of
        # this assign() brought to ctx.snapshot counts another generation.
        compiler = self._affinity
        if compiler is not None and not compiler.at(snapshot):
            compiler = None
        assignments = ctx.assignments
        diagnostics = ctx.diagnostics
        working = ctx.working
        delta = ctx.delta
        delta_has_terms = ctx.delta_has_terms
        sel_cache = ctx.sel_cache

        def node_for(idx: int) -> NodeInfo:
            name = ct.node_names[idx]
            ni = working.get(name)
            if ni is None:
                ni = snapshot.get(name).clone()
                working[name] = ni
                # Patch the shared working snapshot in place (clones mutate
                # in place afterwards, so list entries stay current).
                w = ctx.wsnap
                if w is not None:
                    old = w._by_name.get(name)
                    w.nodes[idx] = ni
                    w._by_name[name] = ni
                    for lst in (w.have_pods_with_affinity,
                                w.have_pods_with_required_anti_affinity):
                        for k, entry in enumerate(lst):
                            if entry is old:
                                lst[k] = ni
                                break
            return ni

        full_check_batch = bool(stateful_pods)
        contention = Status.unschedulable(
            "node(s) exhausted by earlier pods in the batch"
        ).with_plugin("NodeResourcesFit")
        affinity_conflict = Status.unschedulable(
            "node(s) conflicted with pod affinity/anti-affinity of pods "
            "placed earlier in the batch").with_plugin("InterPodAffinity")
        port_conflict = Status.unschedulable(
            "node(s) didn't have free ports for the requested pod ports"
        ).with_plugin("NodePorts")

        rejects: list[tuple[int, int]] = []

        def reject(i: int, idx: int, plugin: str) -> None:
            rejects.append((i, idx))
            if self.metrics is not None:
                self.metrics.verify_rejects.inc(plugin=plugin)

        for i, pi in enumerate(pods):
            idx = int(assign[i])
            if idx < 0:
                assignments[pi.key] = None
                continue
            ni = node_for(idx)
            if insufficient_resources(pi, ni):
                assignments[pi.key] = None
                diagnostics[pi.key] = {ni.name: contention}
                reject(i, idx, "NodeResourcesFit")
                continue
            if pi.host_ports and any(
                    (ip == "0.0.0.0" or uip == "0.0.0.0" or ip == uip)
                    and proto == uproto and port == uport
                    for (ip, proto, port) in pi.host_ports
                    for (uip, uproto, uport) in ni.used_ports):
                assignments[pi.key] = None
                diagnostics[pi.key] = {ni.name: port_conflict}
                reject(i, idx, "NodePorts")
                continue
            if full_check_batch:
                # Non-IPA stateful plugins in play → full host re-check.
                # The working snapshot is built ONCE per assign() and kept
                # current: working clones mutate in place, and node_for
                # patches in new clones — rebuilding a Snapshot per pod was
                # O(N) per pod (the spread/NRT families' top host cost).
                wsnap = ctx.wsnap
                if wsnap is None:
                    wsnap = ctx.wsnap = Snapshot(
                        [working.get(n.name, n) for n in snapshot.nodes],
                        snapshot.generation)
                state = fwk.new_cycle_state()
                st = fwk.run_pre_filter(state, pi, wsnap)
                if st.is_success():
                    st = fwk.run_filters(state, pi, working.get(ni.name, ni))
                if not st.is_success():
                    assignments[pi.key] = None
                    diagnostics[pi.key] = {ni.name: st}
                    reject(i, idx, "other")
                    continue
            elif delta_has_terms or pi.has_affinity_constraints:
                if not _delta_affinity_ok(pi, ni, delta, ct, compiler,
                                          sel_cache, ctx.delta_idx):
                    assignments[pi.key] = None
                    diagnostics[pi.key] = {ni.name: affinity_conflict}
                    reject(i, idx, "InterPodAffinity")
                    continue
            assignments[pi.key] = ni.name
            ni.add_pod(pi)
            # Keep the shared working snapshot's affinity indexes current
            # (Snapshot.__init__ derives them; add_pod bypasses that).
            if ctx.wsnap is not None:
                if pi.has_affinity_constraints and \
                        ni not in ctx.wsnap.have_pods_with_affinity:
                    ctx.wsnap.have_pods_with_affinity.append(ni)
                if pi.has_required_anti_affinity and ni not in \
                        ctx.wsnap.have_pods_with_required_anti_affinity:
                    ctx.wsnap.have_pods_with_required_anti_affinity.append(ni)
            delta.append((pi, ni.labels))
            ctx.delta_idx.add(pi, ni.labels)
            if pi.required_affinity_terms or pi.required_anti_affinity_terms:
                delta_has_terms = True
        ctx.delta_has_terms = delta_has_terms
        return rejects

    # -- explainability ------------------------------------------------------

    def _build_diagnostics(self, idxs, pods, ct, batch, fit0, taint_ok,
                           cls_np, host_filter_fail, filter_names,
                           diagnostics, unknown_res):
        """Per-node, per-plugin failure reasons from the preserved unsat
        masks — feeds FitError's "0/N nodes are available: ..." summary.

        fit0/taint_ok are CLASS-level (C, N) planes; each pod reads its
        class row through cls_np (exact — the class shares the pod's
        request/toleration rows by construction). Host plugin failures
        come from the per-pod ok-row dicts the prep recorded (shared row
        objects, no plane)."""
        taint_st = Status.unschedulable(
            "node(s) had untolerated taint", resolvable=False
        ).with_plugin("TaintToleration")
        contention = Status.unschedulable(
            "node(s) exhausted by earlier pods in the batch"
        ).with_plugin("NodeResourcesFit")
        host_statuses = {
            name: Status.unschedulable(_HOST_REASONS.get(name, "node(s) filtered"),
                                       resolvable=name not in _UNRESOLVABLE)
            .with_plugin(name)
            for name in host_filter_fail
        }
        n_real = ct.n_real
        names = list(ct.node_names[:n_real])
        names_hash = hash(tuple(names))
        R = ct.alloc_q.shape[1]
        weights = 1 << np.arange(R, dtype=np.int64)
        too_many = (ct.used_pods + 1 > ct.alloc_pods)[:n_real]
        #: insufficiency bitmask (bit R = pod count) -> interned Status;
        #: shared across the whole wave — a dense failure wave repeats the
        #: same handful of shortage shapes across thousands of pods.
        res_status_cache: dict[int, Status] = {}
        taint_on = "TaintToleration" in filter_names
        for i in idxs:
            pi = pods[i]
            if i in unknown_res:
                st = Status.unschedulable(
                    "Insufficient " + ", ".join(
                        r for r in pi.requests if r not in ct.r_index),
                    resolvable=True).with_plugin("NodeResourcesFit")
                dm = DiagMap((n, st) for n in ct.node_names)
                dm.reason_counts = {r: len(ct.node_names)
                                    for r in st.reasons}
                dm.plugins = {st.plugin}
                dm.resolvable = True
                dm.banned_mask = np.zeros((n_real,), dtype=bool)
                dm.banned_nodes_hash = names_hash
                diagnostics[pi.key] = dm
                continue
            # One interned-Status object row per pod instead of a Python
            # loop per node — the per-node next()/nonzero() chain was the
            # top host cost of dense failure (preemption) waves.
            statuses = np.empty((n_real,), dtype=object)
            assigned = np.zeros((n_real,), dtype=bool)
            banned = np.zeros((n_real,), dtype=bool)
            agg: list[tuple[Status, int]] = []
            ci = int(cls_np[i])
            if taint_on:
                m = ~taint_ok[ci, :n_real]
                statuses[m] = taint_st
                assigned |= m
                banned |= m
                c = int(m.sum())
                if c:
                    agg.append((taint_st, c))
            for pname, okmap in host_filter_fail.items():
                ok_row = okmap.get(i)
                if ok_row is None:
                    continue
                m = ~ok_row[:n_real] & ~assigned
                statuses[m] = host_statuses[pname]
                assigned |= m
                if host_statuses[pname].code == \
                        UNSCHEDULABLE_AND_UNRESOLVABLE:
                    banned |= m
                c = int(m.sum())
                if c:
                    agg.append((host_statuses[pname], c))
            short = (ct.used_q + batch.req_q[i][None, :]
                     > ct.alloc_q)[:n_real]
            bits = (short @ weights) + (too_many.astype(np.int64) << R)
            bits[assigned] = -1
            for b in np.unique(bits):
                if b < 0:
                    continue
                m = bits == b
                if b == 0:
                    # Feasible at batch start but taken by earlier pods.
                    statuses[m] = contention
                    agg.append((contention, int(m.sum())))
                    continue
                st = res_status_cache.get(int(b))
                if st is None:
                    msgs = [f"Insufficient {ct.resources[r]}"
                            for r in range(R) if b & (1 << r)]
                    if b >> R:
                        msgs = ["Too many pods"] + msgs
                    st = Status.unschedulable(*msgs).with_plugin(
                        "NodeResourcesFit")
                    res_status_cache[int(b)] = st
                statuses[m] = st
                agg.append((st, int(m.sum())))
            dm = DiagMap(zip(names, statuses))
            for st, c in agg:
                for r in st.reasons:
                    dm.reason_counts[r] = dm.reason_counts.get(r, 0) + c
                if st.plugin:
                    dm.plugins.add(st.plugin)
                if st.code != UNSCHEDULABLE_AND_UNRESOLVABLE:
                    dm.resolvable = True
            dm.banned_mask = banned
            dm.banned_nodes_hash = names_hash
            diagnostics[pi.key] = dm


class DiagMap(dict):
    """Per-pod {node: Status} map with the two aggregates every consumer
    recomputes by iterating all N entries — FitError's reason counts and
    handleSchedulingFailure's plugin set — precomputed from the vectorized
    masks. At wave scale (1k failed pods × 5k nodes) the per-pod O(N)
    re-iterations were a measured top-3 host cost."""

    __slots__ = ("reason_counts", "plugins", "resolvable", "banned_mask",
                 "banned_nodes_hash")

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.reason_counts: dict[str, int] = {}
        self.plugins: set[str] = set()
        #: any node failed with a preemption-resolvable status
        self.resolvable: bool = False
        #: (n_real,) bool — nodes rejected UnschedulableAndUnresolvable
        #: (snapshot node order); preemption's banned set without an O(N)
        #: per-pod re-scan.
        self.banned_mask = None
        #: hash of the node-name tuple the mask indexes — consumers run
        #: against a LATER snapshot whose node set may have churned; a
        #: bare length check would let bans land on the wrong nodes.
        self.banned_nodes_hash = 0


class _AssignCtx:
    """Per-assign()-call state: the chunk list, per-framework device params,
    accumulated results, and the cross-chunk verify context."""

    __slots__ = ("snapshot", "fwk", "ct", "chunks", "params",
                 "assignments", "diagnostics",
                 "working", "delta", "delta_has_terms", "sel_cache",
                 "delta_idx", "wsnap", "spread", "spread_poisoned",
                 "spread_last_gated", "chunk_seq", "class_pad", "ipa")


def _cached_matcher(term: dict, owner_ns: str, sel_cache: dict,
                    resolver=None):
    """Compiled (namespace-set, Selector) per unique term — the delta loop
    is O(batch²) pairs, so per-pair selector re-parsing would dominate.
    The namespace set may be the ALL_NAMESPACES wildcard; membership goes
    through labels.ns_contains."""
    key = (id(term), owner_ns)
    got = sel_cache.get(key)
    if got is None:
        from kubernetes_tpu.api.labels import from_label_selector
        from kubernetes_tpu.scheduler.plugins.interpodaffinity import (
            resolve_term_namespaces,
        )
        nses = frozenset(resolve_term_namespaces(term, owner_ns, resolver))
        got = sel_cache[key] = (nses, from_label_selector(
            term.get("labelSelector")))
    return got


def _term_sig(term: dict, owner_ns: str, sel_cache: dict) -> tuple:
    """CONTENT-keyed term signature: pods stamped from one template carry
    equal-but-distinct term dicts, so id()-keyed indexes would grow one
    entry per pod and make delta maintenance O(batch) again."""
    key = ("sig", id(term), owner_ns)
    sig = sel_cache.get(key)
    if sig is None:
        sig = sel_cache[key] = (
            term.get("topologyKey", ""),
            tuple(sorted(term.get("namespaces") or [owner_ns])),
            repr(term.get("namespaceSelector")),
            repr(term.get("labelSelector")))
    return sig


class _DeltaAffinityIndex:
    """Incremental index over same-batch placements, answering the three
    delta-affinity questions in O(terms) per query instead of O(|delta|):

    - fwd[sig]: for a queried term, count of delta pods matching its
      selector, grouped by their NODE's topology value.
    - anti[sig]: for anti-affinity terms CARRIED BY delta pods, the same
      node-topology-value counts (symmetry: they forbid the querier).

    add() is O(registered signatures) per accepted pod — one per distinct
    template in the batch, not one per pod."""

    __slots__ = ("sel_cache", "fwd", "anti", "resolver")

    def __init__(self, sel_cache: dict, resolver=None):
        self.sel_cache = sel_cache
        self.resolver = resolver
        #: sig -> [nses, sel, tk, {node tk value -> count}, total]
        self.fwd: dict[tuple, list] = {}
        self.anti: dict[tuple, list] = {}

    def register(self, term: dict, owner_ns: str, delta: list) -> list:
        sig = _term_sig(term, owner_ns, self.sel_cache)
        e = self.fwd.get(sig)
        if e is None:
            nses, sel = _cached_matcher(term, owner_ns, self.sel_cache,
                                        self.resolver)
            tk = term.get("topologyKey", "")
            counts: dict = {}
            total = 0
            for d, labels_m in delta:  # back-fill placements so far
                if ns_contains(nses, d.namespace) and sel.matches(d.labels):
                    v = labels_m.get(tk)
                    counts[v] = counts.get(v, 0) + 1
                    total += 1
            e = self.fwd[sig] = [nses, sel, tk, counts, total]
        return e

    def add(self, d, node_labels: Mapping) -> None:
        for e in self.fwd.values():
            nses, sel, tk, counts, _total = e
            if ns_contains(nses, d.namespace) and sel.matches(d.labels):
                v = node_labels.get(tk)
                counts[v] = counts.get(v, 0) + 1
                e[4] += 1
        for term in d.required_anti_affinity_terms:
            sig = _term_sig(term, d.namespace, self.sel_cache)
            e = self.anti.get(sig)
            if e is None:
                nses, sel = _cached_matcher(
                    term, d.namespace, self.sel_cache, self.resolver)
                e = self.anti[sig] = [
                    nses, sel, term.get("topologyKey", ""), {}, 0]
            v = node_labels.get(e[2])
            e[3][v] = e[3].get(v, 0) + 1
            e[4] += 1


def _delta_affinity_ok(pi, ni, delta, ct, compiler, sel_cache,
                       delta_idx: "_DeltaAffinityIndex | None" = None) -> bool:
    """Inter-pod affinity check of `pi` on node `ni` against only the pods
    placed earlier in this batch (the batch-start tensor rows already cover
    the snapshot exactly). With a `_DeltaAffinityIndex` the three checks
    are O(terms) dictionary lookups; the list-walk fallback remains for
    callers without one."""
    labels_n = ni.labels

    if delta_idx is not None:
        # (1) pi's own anti-affinity vs delta placements.
        for term in pi.required_anti_affinity_terms:
            e = delta_idx.register(term, pi.namespace, delta)
            tv = labels_n.get(e[2])
            if tv is not None and e[3].get(tv):
                return False
        # (2) symmetry: delta pods' anti-affinity vs pi.
        for e in delta_idx.anti.values():
            nses, sel, tk, counts, _total = e
            tv = labels_n.get(tk)
            if tv is not None and counts.get(tv) \
                    and ns_contains(nses, pi.namespace) \
                    and sel.matches(pi.labels):
                return False
        # (3) pi's required affinity: delta pods can only ADD matches; the
        # one invalidation is the first-pod-in-group escape — once a
        # matching pod exists (placed in this batch), the term must be
        # satisfied in n's domain for real.
        for term in pi.required_affinity_terms:
            tk = term.get("topologyKey", "")
            tv = labels_n.get(tk)
            if tv is None:
                return False
            e = delta_idx.register(term, pi.namespace, delta)
            if e[3].get(tv):
                continue  # satisfied by a batch sibling in this domain
            if compiler is not None:
                per_node, _, total = compiler.affinity_term_presence(
                    term, pi.namespace)
                idx = ct.name_to_idx.get(ni.name)
                if idx is not None and per_node[idx] > 0:
                    continue  # satisfied by the snapshot already
                if total == 0 and e[4] == 0:
                    continue  # escape still valid: no match anywhere
                return False
            if e[4]:
                return False
        return True

    def matches(term, owner_ns, other) -> bool:
        nses, sel = _cached_matcher(term, owner_ns, sel_cache,
                                    getattr(compiler, "ns_resolver", None))
        return ns_contains(nses, other.namespace) and sel.matches(other.labels)

    # (1) pi's own anti-affinity vs delta placements.
    for term in pi.required_anti_affinity_terms:
        tk = term.get("topologyKey", "")
        tv = labels_n.get(tk)
        if tv is None:
            continue
        for d, labels_m in delta:
            if labels_m.get(tk) == tv and matches(term, pi.namespace, d):
                return False
    # (2) symmetry: delta pods' anti-affinity vs pi.
    for d, labels_m in delta:
        for term in d.required_anti_affinity_terms:
            tk = term.get("topologyKey", "")
            tv = labels_n.get(tk)
            if tv is not None and labels_m.get(tk) == tv \
                    and matches(term, d.namespace, pi):
                return False
    # (3) pi's required affinity: delta pods can only ADD matches; the one
    # invalidation is the first-pod-in-group escape — once a matching pod
    # exists (placed in this batch), the term must be satisfied in n's
    # domain for real.
    for term in pi.required_affinity_terms:
        tk = term.get("topologyKey", "")
        tv = labels_n.get(tk)
        if tv is None:
            return False
        delta_matches = [labels_m for d, labels_m in delta
                         if matches(term, pi.namespace, d)]
        if any(labels_m.get(tk) == tv for labels_m in delta_matches):
            continue  # satisfied by a batch sibling in this domain
        if compiler is not None:
            per_node, _, total = compiler.affinity_term_presence(
                term, pi.namespace)
            idx = ct.name_to_idx.get(ni.name)
            if idx is not None and per_node[idx] > 0:
                continue  # satisfied by the snapshot already
            if total == 0 and not delta_matches:
                continue  # escape still valid: no match exists anywhere
            return False
        # No compiler (shouldn't happen on this path) → be conservative.
        if delta_matches:
            return False
    return True


_HOST_REASONS = {
    "NodeAffinity": "node(s) didn't match Pod's node affinity/selector",
    "NodeName": "node didn't match the requested node name",
    "NodeUnschedulable": "node(s) were unschedulable",
    "NodePorts": "node(s) didn't have free ports for the requested pod ports",
    "InterPodAffinity": "node(s) didn't match pod affinity/anti-affinity rules",
    "PodTopologySpread": "node(s) didn't match pod topology spread constraints",
}
_UNRESOLVABLE = {"NodeAffinity", "NodeName", "NodeUnschedulable"}
