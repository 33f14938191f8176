"""Central registry of every `KTPU_*` environment flag.

Before this module, 30+ call sites read `os.environ` directly with
ad-hoc parsing: three different boolean spellings, two different
defaults for the SAME flag (`KTPU_TRACE_THRESHOLD_MS` defaulted to
"disabled" in the tracer and to 100 ms in the scheduler), import-time
reads that silently ignored env changes made after import (the bench
had to set overrides before importing the backend), and `float(env)` /
`int(env)` calls that crashed the process on a malformed value.

The registry is the single source of truth: name, default, parser,
one-line doc, and whether the flag is a structural kill switch. Every
read in the tree goes through `get()` — a LIVE `os.environ` read per
call, so tests and the bench can flip knobs between runs — and the
static-analysis flag pass (`kubernetes_tpu/analysis/flags_pass.py`)
fails the build on any `KTPU_*` environ read that bypasses it, on
registry entries without docs or tests, and on a README flag table
that drifted from `render_markdown_table()`.

Parsing is deliberately forgiving: a malformed value degrades to the
flag's default (a typo in an env var must never crash a control
plane), and booleans accept the union of the spellings that grew up in
the tree ("0"/"false"/"off"/"no", any case, disable).
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable

__all__ = ["Flag", "FLAGS", "get", "get_raw", "scoped_set",
           "render_markdown_table"]

#: spellings that read as "off" for boolean flags (case-insensitive);
#: everything else non-empty reads as "on".
_FALSE = frozenset(("0", "false", "off", "no"))


def _parse_bool(raw: str) -> bool:
    return raw.strip().lower() not in _FALSE


def _parse_int(raw: str) -> int:
    return int(raw.strip())


def _parse_float(raw: str) -> float:
    return float(raw.strip())


def _parse_ms(raw: str) -> float:
    return max(0.0, float(raw.strip()))


def _parse_str(raw: str) -> str:
    return raw


def _parse_solve_mode(raw: str) -> str:
    v = raw.strip().lower()
    if v not in ("greedy", "optimal", "auto"):
        raise ValueError(raw)  # degrades to the default, per read()
    return v


def _parse_wal_fsync(raw: str) -> str:
    v = raw.strip().lower()
    if v not in ("batch", "always"):
        raise ValueError(raw)  # degrades to the default, per read()
    return v


@dataclass(frozen=True)
class Flag:
    name: str
    default: Any
    parse: Callable[[str], Any] = field(repr=False)
    doc: str
    #: structural kill switch: flipping it degrades a subsystem to its
    #: pre-feature shape (the differential-test contract), rather than
    #: tuning a knob.
    kill_switch: bool = False

    def read(self) -> Any:
        raw = os.environ.get(self.name)
        if raw is None or raw == "":
            return self.default
        try:
            return self.parse(raw)
        except (ValueError, TypeError):
            return self.default


def _flag(name, default, parse, doc, kill_switch=False) -> Flag:
    return Flag(name=name, default=default, parse=parse, doc=doc,
                kill_switch=kill_switch)


#: The registry. Order is the README table order: kill switches first,
#: then tuning overrides, then debug/test knobs.
FLAGS: dict[str, Flag] = {f.name: f for f in (
    _flag("KTPU_SERVING", True, _parse_bool,
          "Online serving tier (admission window + resident planes + "
          "single-pod fast path). `0` degrades the dispatch loop "
          "structurally to the pre-serving shape.", kill_switch=True),
    _flag("KTPU_WAVEFRONT", True, _parse_bool,
          "Speculative wavefront solve (W pods per scan step with exact "
          "conflict replay). `0` degrades structurally to the "
          "one-pod-per-step W=1 scans, bit-identical assignments.",
          kill_switch=True),
    _flag("KTPU_WAVE_WIDTH", None, _parse_int,
          "Wavefront width override (pods evaluated per scan step). "
          "Unset = the AdaptiveTuner policy row picks W and shrinks it "
          "when the measured replay fraction climbs."),
    _flag("KTPU_SOLVE_MODE", "auto", _parse_solve_mode,
          "Batch solve mode: `greedy` pins the r18 wavefront scan call "
          "graph (bit-identical assignments — the kill switch), "
          "`optimal` forces the device-side Sinkhorn transport plan + "
          "feasible rounding for every eligible chunk, `auto` routes "
          "drain-scale and gang chunks to optimal per the tuner policy "
          "row (serving single-pod traffic never routes here; above "
          "the structural large-N row non-gang chunks keep the greedy "
          "scan — the plan's fixed dense (C,N) iteration cost is the "
          "linear-in-N wall the block index removes).",
          kill_switch=True),
    _flag("KTPU_SINKHORN_ITERS", 24, _parse_int,
          "Sinkhorn iterations per optimal-mode chunk (the temperature "
          "annealing's 3 stages split this count)."),
    _flag("KTPU_SINKHORN_TEMP", 0.05, _parse_float,
          "Final Sinkhorn temperature (entropic regularization weight "
          "on the row-normalized cost) — annealing runs 4x -> 2x -> 1x "
          "this value; lower = sharper, closer-to-argmax plans."),
    _flag("KTPU_DESCHEDULER", False, _parse_bool,
          "Default-enable the rebalance descheduler "
          "(controllers/descheduler.py) in ChurnDay scenarios that "
          "don't pin it: periodic evict-and-replace consolidation "
          "moves scored from the resident device planes."),
    _flag("KTPU_DESCHEDULER_BUDGET", 8, _parse_int,
          "Disruption budget: max evict-and-replace moves the "
          "descheduler may issue per sync cycle."),
    _flag("KTPU_TOPOLOGY", True, _parse_bool,
          "Topology-aware TPU-slice placement (kubernetes_tpu/topology): "
          "interconnect coordinate planes on the cluster tensors, the "
          "device-side contiguous sub-mesh Filter/Score behind the "
          "TopologySlice plugin, and Coscheduling's sliceShape contiguity "
          "check at Permit. `0` degrades structurally to flat capacity "
          "vectors — count-only gangs, no coordinate planes, assignments "
          "bit-identical on topology-free workloads.", kill_switch=True),
    _flag("KTPU_MESH_SHAPE", "auto", _parse_str,
          "Interconnect mesh dimensions, e.g. `4x8` (2D torus), `2x4x4` "
          "(3D torus) or `4x8:mesh` (no wraparound). `auto` derives a "
          "near-square 2D torus from the node count. Nodes map to "
          "coordinates via the `ktpu.io/topology-coord` label agents "
          "stamp at registration, falling back to the trailing integer "
          "in the node name (row-major)."),
    _flag("KTPU_WATCH_CACHE", True, _parse_bool,
          "Watch-cache serving tier (store/cacher.py). `0` degrades "
          "every LIST/watch to the direct-mvcc path.", kill_switch=True),
    _flag("KTPU_POLICY_INDEX", True, _parse_bool,
          "Pre-indexed ValidatingAdmissionPolicy matching (policy/"
          "vap.py): exact (resource, operation) reverse maps + interned "
          "namespace-selector signatures make admission O(matching "
          "policies). `0` degrades structurally to the linear "
          "all-policies scan, bit-identical verdicts.", kill_switch=True),
    _flag("KTPU_SHARDS", None, _parse_int,
          "Control-plane shard count override; `1` is the kill switch "
          "(plain single MVCCStore). Unset = the node-count threshold "
          "policy picks.", kill_switch=True),
    _flag("KTPU_SHARD_THRESHOLD", 100_000, _parse_int,
          "Node count at which the flagless shard policy switches from "
          "1 shard to 8 (store/sharded.control_plane_shards)."),
    _flag("KTPU_PROCESSES", None, _parse_int,
          "Control-plane OS-process count (multiproc/): each store "
          "shard becomes its own apiserver process on a unix-socket "
          "KTPU wire, the scheduler an active/standby process pair. "
          "`1` is the kill switch — the classic in-process tree, "
          "bit-identical call graph. Unset = in-process (the bench's "
          "--processes flag is the spawn path).", kill_switch=True),
    _flag("KTPU_WAL", True, _parse_bool,
          "Write-ahead log between KTPU_DATA_DIR snapshots (store/"
          "durable.py): append every committed mvcc write, replay from "
          "the snapshot RV on recovery. `0` degrades durability to "
          "snapshot-only (the pre-WAL r16 shape).", kill_switch=True),
    _flag("KTPU_WAL_FSYNC", "batch", _parse_wal_fsync,
          "WAL fsync policy: `always` fsyncs per commit (the etcd "
          "posture — an acknowledged write is on disk), `batch` group-"
          "commits on the flush tick (durability window = one flush "
          "interval, fsync off the commit path)."),
    _flag("KTPU_LEASE_DURATION", 15.0, _parse_float,
          "Leader-election lease duration in seconds (client/"
          "leaderelection.py). Renew deadline and retry period scale "
          "with it (2/3 and 2/15 of the lease, the reference's "
          "15/10/2 shape) — shorter lease = faster failover detection "
          "at more lease-write traffic."),
    _flag("KTPU_CLASS_PAD", 31, _parse_int,
          "Max real pod-equivalence classes per chunk of the "
          "class-dictionary (C,N) device planes before the per-pod "
          "fallback (plane rows bucket to the next power of two). `0` "
          "turns class planes off: per-pod planes (C == P identity), "
          "bit-identical assignments."),
    _flag("KTPU_PIPELINE_DEPTH", None, _parse_int,
          "Solve-pipeline depth override (chunks in flight ahead of "
          "the fetch). Unset = the AdaptiveTuner's table: 4, then 2 "
          "once warmed up (from the first assign at large N)."),
    _flag("KTPU_SHORTLIST_K", None, _parse_int,
          "Shortlist width override for the pruned solve; `0` disables "
          "pruning. Unset = the tuner derives K from chunk width and "
          "fallback rate."),
    _flag("KTPU_BLOCK_WIDTH", None, _parse_int,
          "Block width override (node columns per block) for the "
          "two-level block-sparse node index of the shortlist "
          "prefilter: an O(C·B) bound scan gates which node columns the "
          "chunk-start score pass touches, exactly. `0` disables it: "
          "the full-width prefilter call graph, bit-identical "
          "assignments. Unset = the AdaptiveTuner's structural policy "
          "row picks the width from the node count."),
    _flag("KTPU_ADMISSION_WINDOW", None, _parse_ms,
          "Serving admission coalesce window in MILLISECONDS (pinned "
          "for sweeps; `0` = always dispatch immediately). Unset = the "
          "AdaptiveTuner policy row sizes it."),
    _flag("KTPU_TRACE_THRESHOLD_MS", None, _parse_float,
          "Slow-attempt threshold in ms: root span trees and attempt "
          "traces slower than this log a step breakdown. Unset = no "
          "tree dumps; the scheduler's per-attempt logger falls back "
          "to the reference's 100 ms."),
    _flag("KTPU_DATA_DIR", None, _parse_str,
          "Durability directory (WAL + snapshots); the apiserver "
          "recovers state from it on construction when set."),
    _flag("KTPU_LOCK_CHECK", False, _parse_bool,
          "Runtime lock-order / dispatch-hygiene detector "
          "(utils/locking.py): instrumented locks record per-thread "
          "acquisition order and raise on observed inversions and on "
          "locks held across device-fetch/wire-send seams. Off = "
          "plain `threading.Lock`, zero overhead."),
    _flag("KTPU_DEBUG_FREEZE", False, _parse_bool,
          "Recursively freeze stored/watch-delivered objects so a "
          "mutating handler fails loudly (enabled by the test suite)."),
    _flag("KTPU_TEST_PLATFORM", "cpu", _parse_str,
          "jax platform the test suite runs against (tests/conftest.py "
          "exports it as JAX_PLATFORMS before jax initializes). `tpu` "
          "points a suite at the chip: tests that need more devices "
          "than it has skip; PERF.md records which suites have run "
          "there."),
)}


def get(name: str) -> Any:
    """Parsed live read of a registered flag (unset/empty/malformed →
    the registered default). KeyError on unregistered names — a typo'd
    flag read should fail loudly, same contract as the static pass."""
    return FLAGS[name].read()


def get_raw(name: str) -> str | None:
    """The raw environ value of a registered flag (None when unset)."""
    FLAGS[name]  # unregistered names fail loudly here too
    return os.environ.get(name)


@contextmanager
def scoped_set(name: str, value):
    """Set a flag for the duration of a block, restoring the previous
    value (or unset state) on exit — the save/restore idiom PerfRunner
    uses to scope a shard-count override to one run."""
    FLAGS[name]
    prev = os.environ.get(name)
    os.environ[name] = str(value)
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prev


def render_markdown_table() -> str:
    """The README "Flags" table, generated — the flag pass fails when
    the README's copy drifts from this render."""
    lines = [
        "| Flag | Default | Kill switch | What it does |",
        "|---|---|---|---|",
    ]
    for f in FLAGS.values():
        default = "unset" if f.default is None else str(f.default)
        ks = "yes" if f.kill_switch else ""
        doc = " ".join(f.doc.split())
        lines.append(f"| `{f.name}` | `{default}` | {ks} | {doc} |")
    return "\n".join(lines)
