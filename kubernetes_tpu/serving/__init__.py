"""The online serving tier: single-pod latency as a first-class path.

ROADMAP #3 (seeded by the r15 churn knee data): the batch pipeline is
worst at production's most common shape — a trickle of lone pods that
each want sub-millisecond placement. BASELINE r15 measured the 5k-node
knee at 1000/s with attempt p999 41.8 ms, while the 250/s trickle row
was 190.7 ms p999 / 3.8 ms p50: every lone pod paid a full per-pod host
scan (the batched backend only engages above one pod) with nothing to
amortize it. This package wins the `ScheduleOne` latency shape back
(SURVEY §3.1) without touching the batch headline, via three
cooperating layers:

- **Adaptive admission window** (admission.py): in front of the
  scheduler's `pop_batch` loop — dispatch immediately when arrivals are
  a trickle, hold the queue open for a few ms to coalesce a real batch
  under backlog. Thresholds ride the AdaptiveTuner's policy row
  (ops/backend.AdaptiveTuner.admission_window), seeded from the r15
  knee sweep; `KTPU_ADMISSION_WINDOW` (ms) / bench `--admission-window`
  override.
- **Resident device planes** (resident.py): the (N, 2R+1) packed
  used-state stays warm on device across cycles and is refreshed by
  scattering only the rows the cache's dirty set re-quantized
  (`changed_since` — the r13 O(changed) host prep, now matched on the
  device side) instead of a full re-upload per assign().
- **Pinned single-pod fast path** (fastpath.py + ops/solver.solve_one):
  a pre-compiled fixed-shape C=1 solve against the resident planes —
  gather → mask → score → argmax → debit, no chunk machinery, no tuner,
  no shortlist build — bit-identical to the batch path by construction
  (it composes the same kernels the fused chunk program does).

`KTPU_SERVING=0` is the kill switch: the scheduler's run loop degrades
STRUCTURALLY to the pre-serving shape (plain schedule_batch, full
used-state uploads, a lone pod as a batch of one).
"""

from __future__ import annotations

from kubernetes_tpu.serving.admission import AdmissionWindow
from kubernetes_tpu.serving.fastpath import SinglePodFastPath
from kubernetes_tpu.serving.loop import ServingTier
from kubernetes_tpu.serving.resident import ResidentPlanes
from kubernetes_tpu.utils import flags

__all__ = [
    "AdmissionWindow",
    "ResidentPlanes",
    "ServingTier",
    "SinglePodFastPath",
    "serving_enabled",
    "maybe_attach_serving",
]


def serving_enabled() -> bool:
    """KTPU_SERVING kill switch; default ON (the serving tier is the
    flagless production shape, like the class planes and the shortlist)."""
    return flags.get("KTPU_SERVING")


def maybe_attach_serving(sched) -> "ServingTier | None":
    """Build (once) and return the scheduler's serving tier, or None when
    the kill switch is set / no batched backend is attached. Called at
    run()-loop entry so tests can flip KTPU_SERVING between runs."""
    if not serving_enabled() or sched.backend is None:
        if sched.serving is not None:
            # Kill switch flipped between runs: detach so the backend's
            # _start returns to full used-state uploads.
            if sched.backend is not None:
                sched.backend.resident = None
            sched.serving = None
        return None
    if sched.serving is None:
        sched.serving = ServingTier(sched)
    return sched.serving
