"""Tier-1 guard for the shortlist-pruned solve (small-N, fast).

Pins: (a) the tuner's policy table — including the r10 large-N row and
the shortlist-width policy with its fallback-rate boost; (b) the
shortlist path being ACTIVE by default once the node count clears the
activation threshold, with bounded fallbacks on a benign (template)
workload; (c) a (1,)-mesh backend degrading cleanly to the single-chip
path. The heavyweight randomized differential parity lives in
tests/test_shortlist_solver.py.
"""

import random

import pytest

from kubernetes_tpu.ops.backend import AdaptiveTuner


class TestTunerPolicy:
    def test_depth_table(self):
        """What is left of the chunk/depth table now that no row keys
        on transfer latency: depth 2 once the warm-up window has been
        observed — or from the FIRST assign at large N, where node
        count is structural and the recompile belongs in warmup, not
        the measured phase."""
        t = AdaptiveTuner()
        t.n_nodes = 50_000
        assert t.total_chunks == 0
        assert t.decide() == 2
        # Small-N waits out the warmup window.
        t2 = AdaptiveTuner()
        t2.n_nodes = 5_000
        assert t2.decide() is None
        for _ in range(t2.WARMUP_CHUNKS):
            t2.observe_chunk()
        assert t2.decide() == 2

    def test_shortlist_width_policy(self):
        t = AdaptiveTuner()
        # Active once N ≥ 4·(K + chunk); K defaults to the chunk width.
        # The 5k preset deliberately keeps its full scan (measured ~10%
        # faster than pruning at that width ratio — BASELINE r10).
        assert t.shortlist_k(1024, 50_000) == 1024
        assert t.shortlist_k(1024, 8_192) == 1024
        assert t.shortlist_k(1024, 5_000) == 0
        assert t.shortlist_k(16, 150) == 16
        assert t.shortlist_k(16, 127) == 0
        # Fallback-rate feedback doubles K at decide() boundaries.
        t.observe_solve(1024, 512)  # 50% fallbacks
        t.decide()
        assert t.shortlist_boost == 2
        assert t.shortlist_k(1024, 50_000) == 2048
        # ...but a widened K can deactivate on clusters it outgrew.
        assert t.shortlist_k(1024, 9_000) == 0

    def test_shortlist_boost_needs_sample_and_rate(self):
        t = AdaptiveTuner()
        t.observe_solve(100, 100)  # tiny sample: not trusted yet
        t.decide()
        assert t.shortlist_boost == 1
        t.observe_solve(1024, 100)  # ~10% < 25%: healthy
        t.decide()
        assert t.shortlist_boost == 1


class TestBackendSmoke:
    def _template_pods(self, n):
        from kubernetes_tpu.api.types import make_pod
        from kubernetes_tpu.scheduler.types import PodInfo
        return [PodInfo(make_pod(
            f"pend-{i}", requests={"cpu": "500m", "memory": "512Mi"},
            uid=f"uid-{i}")) for i in range(n)]

    def _uniform_cluster(self, n):
        from kubernetes_tpu.api.types import make_node
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        cache = SchedulerCache()
        for i in range(n):
            cache.add_node(make_node(
                f"n{i}", allocatable={"cpu": "8", "memory": "32Gi",
                                      "pods": "110"}))
        return cache.update_snapshot()

    def test_active_by_default_above_threshold(self):
        """No flags, no overrides: a cluster clearing the activation
        threshold (N ≥ 4·(K + chunk)) must take the pruned path, and a
        benign template workload must keep fallbacks bounded (the smoke
        bound is the tuner's own boost trigger — beyond it the pruning
        would be widening itself)."""
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        from kubernetes_tpu.ops.backend import TPUBackend
        snap = self._uniform_cluster(150)
        pods = self._template_pods(35)  # partial last chunk: padding rides
        b = TPUBackend(max_batch=16, mesh=None)
        b.metrics = SchedulerMetrics()
        assignments, _ = b.assign(pods, snap, default_fwk())
        m = b.metrics
        assert m.solver_shortlist_pods.value() == len(pods)
        # Scan width is the pruned K + P, not N.
        assert m.solver_scan_width.value() == 32
        fallbacks = m.solver_shortlist_fallbacks.value()
        assert fallbacks <= 0.25 * len(pods), fallbacks
        assert all(v is not None for v in assignments.values())
        # Per-chunk solve wall observed (the 98%-idle blind spot).
        assert m.solve_duration.count() >= 2

    def test_below_threshold_keeps_full_scan(self):
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        from kubernetes_tpu.ops.backend import TPUBackend
        snap = self._uniform_cluster(100)  # 100 < 4·(16+16)
        pods = self._template_pods(8)
        b = TPUBackend(max_batch=16, mesh=None)
        b.metrics = SchedulerMetrics()
        b.assign(pods, snap, default_fwk())
        assert b.metrics.solver_shortlist_pods.value() == 0
        assert b.metrics.solver_scan_width.value() == 100

    def test_one_device_mesh_degrades_to_single_chip(self):
        """A (1,)-mesh must behave exactly like mesh=None (the degrade
        guard for single-chip deployments of the sharded config)."""
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.parallel import build_mesh
        from kubernetes_tpu.ops.backend import TPUBackend
        snap = self._uniform_cluster(80)
        pods = self._template_pods(16)
        fwk = default_fwk()
        plain, _ = TPUBackend(max_batch=16, mesh=None).assign(
            pods, snap, fwk)
        meshed, _ = TPUBackend(max_batch=16, mesh=build_mesh(1)).assign(
            pods, snap, fwk)
        assert plain == meshed
