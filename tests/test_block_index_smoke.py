"""Tier-1 smoke for the two-level block-sparse node index (ISSUE 20).

Pins: (a) the index is ACTIVE BY DEFAULT at large N — the AdaptiveTuner
block-width row turns on structurally at n_real >= LARGE_N with the
shortlist active, no flag needed; (b) the KTPU_BLOCK_WIDTH=0 kill switch
degrades STRUCTURALLY (width 0 → the full-width r18/r21 prefilter call
graph, not a masked no-op), as does every shape guard; (c) at small N
the counters must not drift — zero blocks scanned or pruned when the
policy row keeps the index off. The heavy parity battery lives in
test_block_index_solver.py; the perf numbers in bench (BASELINE).
"""

import numpy as np
import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops.backend import AdaptiveTuner, TPUBackend
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.types import PodInfo
from test_tpu_backend import default_fwk


class TestTunerPolicyRow:
    def test_active_by_default_at_large_n(self):
        """No flags set: the structural row turns the index on at
        n_real >= LARGE_N with a live shortlist — the default width."""
        t = AdaptiveTuner()
        n = AdaptiveTuner.LARGE_N
        assert t.block_width(n, n, 1024) == AdaptiveTuner.BLOCK_WIDTH

    def test_small_n_routes_zero(self):
        t = AdaptiveTuner()
        assert t.block_width(4096, 4096, 256) == 0

    def test_requires_shortlist(self):
        """The index prunes the shortlist prefilter's own O(C·N) pass —
        without a threshold there is nothing to bound against."""
        t = AdaptiveTuner()
        n = AdaptiveTuner.LARGE_N
        assert t.block_width(n, n, 0) == 0

    def test_kill_switch_structural(self, monkeypatch):
        monkeypatch.setenv("KTPU_BLOCK_WIDTH", "0")
        t = AdaptiveTuner()
        n = AdaptiveTuner.LARGE_N
        assert t.block_width(n, n, 1024) == 0

    def test_width_override_and_zero_disable(self, monkeypatch):
        t = AdaptiveTuner()
        n = AdaptiveTuner.LARGE_N
        monkeypatch.setenv("KTPU_BLOCK_WIDTH", "64")
        assert t.block_width(n, n, 1024) == 64
        monkeypatch.setenv("KTPU_BLOCK_WIDTH", "0")
        assert t.block_width(n, n, 1024) == 0

    def test_shape_guard_m_plus_one_exceeds_b(self, monkeypatch):
        """A width/N/K combination where selection could not leave one
        block unselected routes 0 — the ValueError stays unreachable."""
        t = AdaptiveTuner()
        monkeypatch.setenv("KTPU_BLOCK_WIDTH", "16")
        monkeypatch.setattr(AdaptiveTuner, "LARGE_N", 1)
        # n_pad=64 → B=4; K=63 → M=2·ceil(64/16)=8 → M+1 > B.
        assert t.block_width(64, 64, 63) == 0
        # Wide enough B passes.
        assert t.block_width(1024, 1024, 63) == 16


class TestCounterHygiene:
    def test_zero_drift_at_small_n(self):
        """Default policy at toy scale: the block counters must stay at
        exactly zero (the kill-switch/off shape is structural — a
        nonzero count here means the policy row leaked)."""
        cache = SchedulerCache()
        for i in range(24):
            cache.add_node(make_node(f"n{i}"))
        snap = cache.update_snapshot()
        pods = [PodInfo(make_pod(f"p{i}", uid=f"u{i}",
                                 requests={"cpu": "100m"}))
                for i in range(12)]
        b = TPUBackend(max_batch=16, mesh=None)
        b.metrics = SchedulerMetrics()
        b.assign(pods, snap, default_fwk())
        assert b.metrics.solver_blocks_scanned.value() == 0
        assert b.metrics.solver_blocks_pruned.value() == 0
