"""Block-sparse prefilter: randomized differential parity vs the full
shortlist prefilter (ops/solver.block_bound_prefilter vs
kernels.chunk_start_scores + shortlist_prefilter), end to end through
every scan variant that consumes the prefilter outputs.

The contract under test is absolute (ISSUE 20 / the KTPU_BLOCK_WIDTH
knob's README section): the two-pass block-bound form is a pruning of
the SAME argmax — assignments bit-identical to the full-width pass at
every width (KTPU_BLOCK_WIDTH), strategy, and shard count, including
the engineered-adversarial cases (tight capacity, exact score ties at
the K boundary, class exceptions through the backend, spread gating,
the shortlist∩wavefront composition, padding columns, N % width != 0
and N < width shapes). Pruning itself must also actually FIRE on the
shapes it was built for (uniform fleets, dominated blocks) — a suite
where every case falls back would vacuously pass parity.
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import kernels, solver
from test_shortlist_solver import prefilter, solver_args, synthetic


def block_prefilter(d, k, bw, strategy, w_fit=1.0, w_bal=1.0,
                    n_real=None):
    """Per-pod block-bound shortlist args the way the backend builds
    them, plus the (scanned, pruned) counters on the side."""
    free_q = d["alloc_q"] - d["used_q"]
    free_pods = d["alloc_pods"] - d["used_pods"]
    fits0 = np.all(d["req_q"][:, None, :] <= free_q[None], axis=-1) \
        & (free_pods >= 1)[None]
    N = d["alloc_q"].shape[0]
    n_real = N if n_real is None else n_real
    feas = d["mask"] & fits0 & (np.arange(N) < n_real)[None]
    sc0, cand, th, scanned, pruned = solver.block_bound_prefilter(
        jnp.asarray(d["alloc_q"]), jnp.asarray(d["used_q"]),
        jnp.asarray(d["req_q"]), jnp.asarray(d["static_sc"]),
        jnp.asarray(feas), jnp.asarray(d["col_w"]),
        jnp.asarray(d["col_mask"]), jnp.asarray(d["shape_u"]),
        jnp.asarray(d["shape_s"]), jnp.float32(w_fit),
        jnp.float32(w_bal), strategy, jnp.int32(n_real), k, bw)
    P = d["req_q"].shape[0]
    args = (sc0, jnp.arange(P, dtype=jnp.int32), cand, th,
            jnp.asarray(d["mask"].any(axis=1)))
    return args, int(scanned), int(pruned)


def _same_thresh(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.all((a == b) | (np.isneginf(a) & np.isneginf(b)))


# ---------------------------------------------------------------------------
# prefilter-level parity: candidates and thresholds must be identical
# ---------------------------------------------------------------------------

class TestPrefilterParity:
    @pytest.mark.parametrize("strategy", [
        "LeastAllocated", "MostAllocated", "RequestedToCapacityRatio"])
    @pytest.mark.parametrize("bw", [8, 16])
    def test_randomized(self, strategy, bw):
        for seed in range(4):
            rng = np.random.default_rng(seed)
            d = synthetic(rng)
            _, _, cand_f, th_f, _ = prefilter(d, 6, strategy)
            (_, _, cand_b, th_b, _), scanned, _ = \
                block_prefilter(d, 6, bw, strategy)
            np.testing.assert_array_equal(
                np.asarray(cand_f), np.asarray(cand_b))
            assert _same_thresh(th_f, th_b)
            assert scanned == d["req_q"].shape[0] \
                * -(-d["alloc_q"].shape[0] // bw)

    def test_padding_columns_excluded(self):
        """n_real < N (bucket padding): padded columns must influence
        neither the aggregates nor the candidates — parity vs a full
        prefilter whose feasibility masks them out the r18 way."""
        for seed in range(3):
            rng = np.random.default_rng(30 + seed)
            d = synthetic(rng, N=96)
            n_real = 77
            d2 = dict(d)
            d2["mask"] = d["mask"] & (np.arange(96) < n_real)[None]
            _, _, cand_f, th_f, _ = prefilter(d2, 5, "LeastAllocated")
            (_, _, cand_b, th_b, _), _, _ = block_prefilter(
                d, 5, 16, "LeastAllocated", n_real=n_real)
            np.testing.assert_array_equal(
                np.asarray(cand_f), np.asarray(cand_b))
            assert _same_thresh(th_f, th_b)

    def test_ragged_last_block(self):
        """N % width != 0: the tail block is partial and its fold fills
        ride the directional sentinels — still bit-identical."""
        rng = np.random.default_rng(40)
        d = synthetic(rng, N=72)  # 72 / 16 -> 4 full + 1 ragged block
        _, _, cand_f, th_f, _ = prefilter(d, 4, "LeastAllocated")
        (_, _, cand_b, th_b, _), _, _ = block_prefilter(
            d, 4, 16, "LeastAllocated")
        np.testing.assert_array_equal(
            np.asarray(cand_f), np.asarray(cand_b))
        assert _same_thresh(th_f, th_b)

    def test_width_wider_than_n_is_a_shape_error(self):
        """N < width leaves M+1 > B: the prefilter refuses (ValueError)
        — the tuner/block_width policy routes width 0 there instead
        (the KTPU_BLOCK_WIDTH override never reaches the kernel)."""
        rng = np.random.default_rng(41)
        d = synthetic(rng, N=8)
        with pytest.raises(ValueError):
            block_prefilter(d, 4, 16, "LeastAllocated")

    def test_score_ties_at_k_boundary(self):
        """Quantized scores, zero score weights: exact float ties
        straddle the shortlist boundary — the after-last-selected-block
        gate in the uniform arm must keep top_k's lowest-index tie rule
        exact."""
        for seed in range(4):
            rng = np.random.default_rng(200 + seed)
            d = synthetic(rng, score_levels=2)
            for k in (1, 4, 9):
                _, _, cand_f, th_f, _ = prefilter(
                    d, k, "LeastAllocated", w_fit=0.0, w_bal=0.0)
                (_, _, cand_b, th_b, _), _, _ = block_prefilter(
                    d, k, 8, "LeastAllocated", w_fit=0.0, w_bal=0.0)
                np.testing.assert_array_equal(
                    np.asarray(cand_f), np.asarray(cand_b))
                assert _same_thresh(th_f, th_b)

    def test_pruning_fires_on_dominated_blocks(self):
        """Strict-bound arm: two leading blocks carry every winner by a
        wide static-score margin — the other blocks must prune (the
        anti-vacuity half of the parity contract)."""
        n, r, c, k, bw = 256, 3, 4, 3, 16
        static = np.full((c, n), -100.0, np.float32)
        static[:, : bw * 2] = 100.0
        d = dict(
            alloc_q=np.full((n, r), 40_000, np.int32),
            used_q=np.full((n, r), 10_000, np.int32),
            alloc_pods=np.full((n,), 110, np.int32),
            used_pods=np.zeros((n,), np.int32),
            req_q=np.full((c, r), 5_000, np.int32),
            mask=np.ones((c, n), bool), static_sc=static,
            col_w=np.ones((r,), np.float32),
            col_mask=np.ones((r,), np.bool_),
            shape_u=np.array([0.0, 100.0], np.float32),
            shape_s=np.array([0.0, 10.0], np.float32))
        (_, _, cand_b, th_b, _), scanned, pruned = block_prefilter(
            d, k, bw, "LeastAllocated")
        assert pruned > 0
        _, _, cand_f, th_f, _ = prefilter(d, k, "LeastAllocated")
        np.testing.assert_array_equal(
            np.asarray(cand_f), np.asarray(cand_b))
        assert _same_thresh(th_f, th_b)

    def test_pruning_fires_on_uniform_fleet(self):
        """Uniform arm: the 50k-preset shape (identical nodes, identical
        scores) defeats the strict bound by construction — the uniform
        certificate must prune anyway, and stay exact."""
        n, r, c, k, bw = 256, 3, 4, 3, 16
        d = dict(
            alloc_q=np.full((n, r), 40_000, np.int32),
            used_q=np.full((n, r), 10_000, np.int32),
            alloc_pods=np.full((n,), 110, np.int32),
            used_pods=np.zeros((n,), np.int32),
            req_q=np.full((c, r), 5_000, np.int32),
            mask=np.ones((c, n), bool),
            static_sc=np.zeros((c, n), np.float32),
            col_w=np.ones((r,), np.float32),
            col_mask=np.ones((r,), np.bool_),
            shape_u=np.array([0.0, 100.0], np.float32),
            shape_s=np.array([0.0, 10.0], np.float32))
        (_, _, cand_b, th_b, _), _, pruned = block_prefilter(
            d, k, bw, "LeastAllocated")
        assert pruned > 0
        _, _, cand_f, th_f, _ = prefilter(d, k, "LeastAllocated")
        np.testing.assert_array_equal(
            np.asarray(cand_f), np.asarray(cand_b))
        assert _same_thresh(th_f, th_b)

    def test_pruning_survives_advancing_drain_frontier(self):
        """Drain steady state: the low blocks are already full, so the
        selection sits MID-RANGE (blocks 3..4 here, not 0..M-1). The
        uniform arm keys on the last selected block, not a fixed
        prefix — the filled frontier prunes via the empty arm, the
        uniform tail behind the selection still prunes, and nothing
        falls back. (A fixed 0..M-1 gate would drive pruned to 0 for
        every post-warmup chunk of the 200k/1m drain benches.)"""
        n, r, c, k, bw = 256, 3, 4, 3, 16
        used = np.full((n, r), 10_000, np.int32)
        used[: bw * 3] = 40_000  # three leading blocks fully drained
        d = dict(
            alloc_q=np.full((n, r), 40_000, np.int32),
            used_q=used,
            alloc_pods=np.full((n,), 110, np.int32),
            used_pods=np.zeros((n,), np.int32),
            req_q=np.full((c, r), 5_000, np.int32),
            mask=np.ones((c, n), bool),
            static_sc=np.zeros((c, n), np.float32),
            col_w=np.ones((r,), np.float32),
            col_mask=np.ones((r,), np.bool_),
            shape_u=np.array([0.0, 100.0], np.float32),
            shape_s=np.array([0.0, 10.0], np.float32))
        (_, _, cand_b, th_b, _), _, pruned = block_prefilter(
            d, k, bw, "LeastAllocated")
        assert pruned > 0
        _, _, cand_f, th_f, _ = prefilter(d, k, "LeastAllocated")
        np.testing.assert_array_equal(
            np.asarray(cand_f), np.asarray(cand_b))
        assert _same_thresh(th_f, th_b)


# ---------------------------------------------------------------------------
# scan-level parity: the prefilter outputs feed every shortlist scan
# ---------------------------------------------------------------------------

class TestScanParity:
    @pytest.mark.parametrize("strategy", ["LeastAllocated",
                                          "MostAllocated"])
    def test_randomized_identity_scan(self, strategy):
        for seed in range(4):
            rng = np.random.default_rng(seed)
            d = synthetic(rng)
            args = solver_args(d)
            full = np.asarray(solver.greedy_assign_rescoring(
                *args, strategy=strategy))
            bargs, _, _ = block_prefilter(d, 6, 8, strategy)
            sl, _ = solver.greedy_assign_rescoring_shortlist(
                *args, strategy, *bargs)
            np.testing.assert_array_equal(full, np.asarray(sl))

    def test_tight_capacity_forces_solve_fallback(self):
        """Capacity debits exhaust shortlists mid-scan: the scan's own
        full-row fallback must compose with the block prefilter (its
        sc0 zeros at pruned columns are never read — fallback rows are
        recomputed live) and stay bit-identical."""
        hit = 0
        for seed in range(4):
            rng = np.random.default_rng(100 + seed)
            d = synthetic(rng, P=20, N=48, tight=True)
            args = solver_args(d)
            full = np.asarray(solver.greedy_assign_rescoring(
                *args, strategy="LeastAllocated"))
            bargs, _, _ = block_prefilter(d, 4, 8, "LeastAllocated")
            sl, nfall = solver.greedy_assign_rescoring_shortlist(
                *args, "LeastAllocated", *bargs)
            np.testing.assert_array_equal(full, np.asarray(sl))
            hit += int(nfall)
        assert hit > 0

    def test_spread_scan(self):
        """Spread gating is prefilter-blind and non-monotone — the block
        prefilter must compose with the spread shortlist scan exactly."""
        from test_shortlist_solver import TestSpreadParity
        for seed in range(4):
            rng = np.random.default_rng(400 + seed)
            N, P = 48, 12
            d = synthetic(rng, P=P, N=N)
            args = solver_args(d)
            sp = TestSpreadParity._spread(TestSpreadParity(), rng, N, P)
            full, dc_full = solver.greedy_assign_rescoring_spread(
                *args, "LeastAllocated", *sp)
            bargs, _, _ = block_prefilter(d, 5, 8, "LeastAllocated")
            sl, dc_sl, _ = solver.greedy_assign_rescoring_spread_shortlist(
                *args, "LeastAllocated", *sp, *bargs)
            np.testing.assert_array_equal(
                np.asarray(full), np.asarray(sl))
            np.testing.assert_allclose(
                np.asarray(dc_full), np.asarray(dc_sl))


# ---------------------------------------------------------------------------
# backend end to end: the block index on vs off must be bit-identical
# ---------------------------------------------------------------------------

class TestBackendParity:
    def _cluster_and_pods(self, seed, n_nodes=160, n_pods=50):
        from test_tpu_backend import TOL_POOL, random_cluster
        from kubernetes_tpu.api.types import make_pod
        from kubernetes_tpu.scheduler.types import PodInfo
        rng = random.Random(seed)
        snap = random_cluster(rng, n_nodes)
        # Template pods with taints/tolerations: the class-exception
        # (exc) columns ride the masks the prefilter consumes.
        pods = [PodInfo(make_pod(
            f"pend-{i}",
            requests={"cpu": "500m", "memory": "512Mi"} if i % 2
            else {"cpu": "1", "memory": "2Gi"},
            tolerations=TOL_POOL if i % 2 else None,
            uid=f"uid-{i}")) for i in range(n_pods)]
        return snap, pods

    @pytest.mark.parametrize("wavefront", [False, True])
    def test_forced_on_off_identical(self, monkeypatch, wavefront):
        """Forced-on (small LARGE_N, KTPU_BLOCK_WIDTH=16) vs the
        KTPU_BLOCK_WIDTH=0 kill switch: identical assignments, and the
        forced run must actually scan blocks. The wavefront case pins
        the shortlist∩wave composition (the prefilter feeds the wave
        scan's candidates too)."""
        import kubernetes_tpu.ops.backend as backend_mod
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        snap, pods = self._cluster_and_pods(9)
        fwk = default_fwk()
        monkeypatch.setenv("KTPU_SHORTLIST_K", "16")
        if wavefront:
            monkeypatch.setenv("KTPU_WAVEFRONT", "1")
            monkeypatch.setenv("KTPU_WAVE_WIDTH", "4")
        monkeypatch.setenv("KTPU_BLOCK_WIDTH", "0")
        off, _ = backend_mod.TPUBackend(
            max_batch=16, mesh=None).assign(pods, snap, fwk)
        monkeypatch.setenv("KTPU_BLOCK_WIDTH", "16")
        monkeypatch.setattr(backend_mod.AdaptiveTuner, "LARGE_N", 1)
        b = backend_mod.TPUBackend(max_batch=16, mesh=None)
        b.metrics = SchedulerMetrics()
        on, _ = b.assign(pods, snap, fwk)
        assert off == on
        assert b.metrics.solver_blocks_scanned.value() > 0
