"""Shortlist-pruned solve: randomized differential parity vs the full
N-wide scans (ops/solver.py), including adversarial cases engineered to
force the exactness fallback (tight capacity, score ties at the K
boundary), the spread scan, the sharded path on the 8-virtual-device CPU
mesh, and the backend end to end.

The contract under test is absolute: shortlist and full solves must
produce IDENTICAL assignments (and therefore identical fragmentation) —
the shortlist is a pruning of the same argmax, never an approximation.
"""

import asyncio
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import kernels, solver


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def synthetic(rng, P=16, N=96, R=2, score_levels=None, tight=False,
              mask_p=0.9):
    alloc_q = rng.integers(4_000, 64_000, size=(N, R)).astype(np.int32)
    used_frac = rng.uniform(0, 0.9 if tight else 0.5, size=(N, R))
    used_q = (alloc_q * used_frac).astype(np.int32)
    alloc_pods = np.full((N,), 6 if tight else 110, np.int32)
    used_pods = rng.integers(0, 5 if tight else 30, size=(N,)).astype(np.int32)
    lo, hi = (2_000, 24_000) if tight else (100, 9_000)
    req_q = rng.integers(lo, hi, size=(P, R)).astype(np.int32)
    mask = rng.random((P, N)) < mask_p
    if score_levels is None:
        static_sc = rng.uniform(0, 10, size=(P, N)).astype(np.float32)
    else:
        # Quantized scores: exact ties everywhere, including at the K
        # boundary — the tie rule's adversarial case.
        static_sc = rng.integers(
            0, score_levels, size=(P, N)).astype(np.float32)
    col_w = np.ones((R,), np.float32)
    col_mask = np.ones((R,), np.bool_)
    shp = np.array([0.0, 100.0], np.float32), np.array([0.0, 10.0], np.float32)
    return dict(alloc_q=alloc_q, used_q=used_q, alloc_pods=alloc_pods,
                used_pods=used_pods, req_q=req_q, mask=mask,
                static_sc=static_sc, col_w=col_w, col_mask=col_mask,
                shape_u=shp[0], shape_s=shp[1])


def solver_args(d, w_fit=1.0, w_bal=1.0):
    free_q = d["alloc_q"] - d["used_q"]
    free_pods = d["alloc_pods"] - d["used_pods"]
    return [jnp.asarray(x) for x in (
        d["req_q"], d["req_q"], free_q, free_pods, d["used_q"],
        d["alloc_q"], d["mask"], d["static_sc"], d["col_w"], d["col_mask"],
        d["shape_u"], d["shape_s"])] + [jnp.float32(w_fit),
                                        jnp.float32(w_bal)]


def prefilter(d, k, strategy, w_fit=1.0, w_bal=1.0):
    """Per-pod shortlist args, the way the backend builds them (here with
    one class per pod — the class sharing is exercised separately)."""
    free_q = d["alloc_q"] - d["used_q"]
    free_pods = d["alloc_pods"] - d["used_pods"]
    sc0 = kernels.chunk_start_scores(
        jnp.asarray(d["alloc_q"]), jnp.asarray(d["used_q"]),
        jnp.asarray(d["req_q"]), jnp.asarray(d["static_sc"]),
        jnp.asarray(d["col_w"]), jnp.asarray(d["col_mask"]),
        jnp.asarray(d["shape_u"]), jnp.asarray(d["shape_s"]),
        jnp.float32(w_fit), jnp.float32(w_bal), strategy)
    fits0 = np.all(d["req_q"][:, None, :] <= free_q[None], axis=-1) \
        & (free_pods >= 1)[None]
    cand, th = solver.shortlist_prefilter(
        jnp.asarray(d["mask"] & fits0), sc0, k)
    P = d["req_q"].shape[0]
    return (sc0, jnp.arange(P, dtype=jnp.int32), cand, th,
            jnp.asarray(d["mask"].any(axis=1)))


# ---------------------------------------------------------------------------
# identity-order scan
# ---------------------------------------------------------------------------

class TestIdentityParity:
    @pytest.mark.parametrize("strategy", ["LeastAllocated", "MostAllocated"])
    def test_randomized(self, strategy):
        total_fallbacks = 0
        for seed in range(6):
            rng = np.random.default_rng(seed)
            d = synthetic(rng)
            args = solver_args(d)
            full = np.asarray(solver.greedy_assign_rescoring(
                *args, strategy=strategy))
            sl, nfall = solver.greedy_assign_rescoring_shortlist(
                *args, strategy, *prefilter(d, 6, strategy))
            np.testing.assert_array_equal(full, np.asarray(sl))
            total_fallbacks += int(nfall)
        # The suite must actually exercise BOTH paths across its seeds
        # (fallback traffic is strategy-dependent; LeastAllocated's
        # decreasing scores are the reliable generator).
        if strategy == "LeastAllocated":
            assert total_fallbacks > 0

    def test_tight_capacity_forces_fallback(self):
        """Capacity debits exhaust shortlists → the full-row fallback
        must fire AND stay bit-identical."""
        hit = 0
        for seed in range(4):
            rng = np.random.default_rng(100 + seed)
            d = synthetic(rng, P=20, N=48, tight=True)
            args = solver_args(d)
            full = np.asarray(solver.greedy_assign_rescoring(
                *args, strategy="LeastAllocated"))
            sl, nfall = solver.greedy_assign_rescoring_shortlist(
                *args, "LeastAllocated", *prefilter(d, 4, "LeastAllocated"))
            np.testing.assert_array_equal(full, np.asarray(sl))
            hit += int(nfall)
        assert hit > 0

    def test_score_ties_at_k_boundary(self):
        """Quantized scores (exact float ties straddling the shortlist
        boundary) — the untouched-winner tie rule must match the full
        scan's lowest-index tie-break exactly."""
        for seed in range(6):
            rng = np.random.default_rng(200 + seed)
            d = synthetic(rng, score_levels=2)
            # Zero score weights: ONLY tied static scores decide, so the
            # (K+1)-th bound equals the winner's score at nearly every
            # step — maximal pressure on the tie logic.
            args = solver_args(d, w_fit=0.0, w_bal=0.0)
            for k in (1, 4, 9):
                full = np.asarray(solver.greedy_assign_rescoring(
                    *args, strategy="LeastAllocated"))
                sl, _ = solver.greedy_assign_rescoring_shortlist(
                    *args, "LeastAllocated",
                    *prefilter(d, k, "LeastAllocated",
                               w_fit=0.0, w_bal=0.0))
                np.testing.assert_array_equal(full, np.asarray(sl))

    def test_uniform_cluster_round_robin_no_fallback(self):
        """The 50k-preset shape: identical nodes + template pods round-
        robin one fresh node per pod. With K ≥ P the whole chunk's
        winners sit in the shortlist — zero fallbacks, same assigns."""
        rng = np.random.default_rng(7)
        N, P, R = 128, 16, 2
        d = synthetic(rng, P=P, N=N)
        d["alloc_q"][:] = 32_000
        d["used_q"][:] = 0
        d["used_pods"][:] = 0
        d["req_q"][:] = 900
        d["mask"][:] = True
        d["static_sc"][:] = 0.0
        args = solver_args(d)
        full = np.asarray(solver.greedy_assign_rescoring(
            *args, strategy="LeastAllocated"))
        sl, nfall = solver.greedy_assign_rescoring_shortlist(
            *args, "LeastAllocated", *prefilter(d, P, "LeastAllocated"))
        np.testing.assert_array_equal(full, np.asarray(sl))
        assert int(nfall) == 0
        assert len(set(full.tolist())) == P  # it did round-robin


# ---------------------------------------------------------------------------
# multistart (vmapped orders, poisoned-chunk fallback)
# ---------------------------------------------------------------------------

class TestMultistartParity:
    def _perms(self, d, K=3):
        P = d["req_q"].shape[0]
        perms = np.tile(np.arange(P, dtype=np.int32), (K, 1))
        sizes = d["req_q"].sum(axis=1)
        perms[1] = np.argsort(-sizes, kind="stable").astype(np.int32)
        if K > 2:
            perms[2] = np.argsort(sizes, kind="stable").astype(np.int32)
        return jnp.asarray(perms)

    @pytest.mark.parametrize("tight", [False, True])
    def test_randomized(self, tight):
        poisoned = clean = 0
        for seed in range(5):
            rng = np.random.default_rng(300 + seed)
            d = synthetic(rng, tight=tight)
            args = solver_args(d)
            P = d["req_q"].shape[0]
            perms = self._perms(d)
            gz = jnp.zeros((P, 4), jnp.float32)
            gr = jnp.zeros((4,), jnp.float32)
            full = np.asarray(solver.multistart_greedy_assign(
                *args, "LeastAllocated", perms, gz, gr))
            sl, nf = solver.multistart_greedy_assign_shortlist(
                *args, "LeastAllocated", perms, gz, gr,
                *prefilter(d, 6, "LeastAllocated"))
            np.testing.assert_array_equal(full, np.asarray(sl))
            if int(nf):
                poisoned += 1
            else:
                clean += 1
        # Across both regimes the suite sees clean chunks AND whole-chunk
        # fallbacks (the vmapped scans can't repair per step).
        assert (poisoned + clean) == 5

    def test_gangs_ride_both_paths(self):
        rng = np.random.default_rng(42)
        d = synthetic(rng, P=12, N=64)
        args = solver_args(d)
        P = 12
        gang = np.zeros((P, 4), np.float32)
        gang[:4, 0] = 1.0  # one 4-member gang
        req = np.zeros((4,), np.float32)
        req[0] = 4.0
        perms = self._perms(d)
        full = np.asarray(solver.multistart_greedy_assign(
            *args, "LeastAllocated", perms,
            jnp.asarray(gang), jnp.asarray(req)))
        sl, _ = solver.multistart_greedy_assign_shortlist(
            *args, "LeastAllocated", perms,
            jnp.asarray(gang), jnp.asarray(req),
            *prefilter(d, 6, "LeastAllocated"))
        np.testing.assert_array_equal(full, np.asarray(sl))


# ---------------------------------------------------------------------------
# spread scan
# ---------------------------------------------------------------------------

class TestSpreadParity:
    def _spread(self, rng, N, P, D=4, C=2):
        dom_of = rng.integers(0, D, size=(N,))
        dom_onehot = np.zeros((N, D), np.float32)
        dom_onehot[np.arange(N), dom_of] = 1.0
        cid = np.zeros((D, C), np.float32)
        cid[: D // 2, 0] = 1.0
        cid[D // 2:, 1] = 1.0
        applies = (rng.random((P, C)) < 0.6).astype(np.float32)
        contrib = np.maximum(
            applies, (rng.random((P, C)) < 0.3)).astype(np.float32)
        return [jnp.asarray(x) for x in (
            dom_onehot, cid,
            rng.integers(0, 2, size=(D,)).astype(np.float32),
            np.array([1.0, 2.0], np.float32),       # max_skew
            np.ones((C,), np.float32),              # min_ok
            np.ones((N, C), np.float32),            # has_key
            applies, contrib)]

    def test_randomized(self):
        total_fallbacks = 0
        for seed in range(6):
            rng = np.random.default_rng(400 + seed)
            N, P = 48, 12
            d = synthetic(rng, P=P, N=N)
            args = solver_args(d)
            sp = self._spread(rng, N, P)
            full, dc_full = solver.greedy_assign_rescoring_spread(
                *args, "LeastAllocated", *sp)
            sl, dc_sl, nfall = \
                solver.greedy_assign_rescoring_spread_shortlist(
                    *args, "LeastAllocated", *sp,
                    *prefilter(d, 5, "LeastAllocated"))
            np.testing.assert_array_equal(
                np.asarray(full), np.asarray(sl))
            np.testing.assert_allclose(
                np.asarray(dc_full), np.asarray(dc_sl))
            total_fallbacks += int(nfall)
        # Spread gating is prefilter-blind, so skew-blocked score heads
        # must route through the fallback somewhere in the suite.
        assert total_fallbacks > 0

    def test_tight_skew_forces_fallback(self):
        """maxSkew=1 over few domains: the score head saturates its
        domain quickly and the allowed set moves away from the shortlist
        — heavy fallback traffic, still bit-identical (incl. the chained
        domain counts)."""
        rng = np.random.default_rng(77)
        N, P, D, C = 32, 16, 2, 1
        d = synthetic(rng, P=P, N=N, mask_p=1.0)
        d["static_sc"][:] = 0.0
        args = solver_args(d)
        dom_onehot = np.zeros((N, D), np.float32)
        dom_onehot[np.arange(N), np.arange(N) % D] = 1.0
        sp = [jnp.asarray(x) for x in (
            dom_onehot, np.ones((D, C), np.float32),
            np.zeros((D,), np.float32), np.array([1.0], np.float32),
            np.ones((C,), np.float32), np.ones((N, C), np.float32),
            np.ones((P, C), np.float32), np.ones((P, C), np.float32))]
        full, dc_full = solver.greedy_assign_rescoring_spread(
            *args, "LeastAllocated", *sp)
        sl, dc_sl, nfall = solver.greedy_assign_rescoring_spread_shortlist(
            *args, "LeastAllocated", *sp,
            *prefilter(d, 4, "LeastAllocated"))
        np.testing.assert_array_equal(np.asarray(full), np.asarray(sl))
        np.testing.assert_allclose(np.asarray(dc_full), np.asarray(dc_sl))


# ---------------------------------------------------------------------------
# backend end to end: forced-on vs forced-off must agree, classes shared
# ---------------------------------------------------------------------------

class TestBackendParity:
    def _cluster_and_pods(self, seed, n_nodes=128, n_pods=48):
        from test_tpu_backend import TOL_POOL, random_cluster
        from kubernetes_tpu.api.types import make_pod
        from kubernetes_tpu.scheduler.types import PodInfo
        rng = random.Random(seed)
        snap = random_cluster(rng, n_nodes)
        # Template pods (two classes) — the row-sharing case the class
        # key must get right; heterogeneous chunks are covered above.
        pods = [PodInfo(make_pod(
            f"pend-{i}",
            requests={"cpu": "500m", "memory": "512Mi"} if i % 2
            else {"cpu": "1", "memory": "2Gi"},
            tolerations=TOL_POOL if i % 2 else None,
            uid=f"uid-{i}")) for i in range(n_pods)]
        return snap, pods

    def test_forced_on_off_identical(self, monkeypatch):
        import kubernetes_tpu.ops.backend as backend_mod
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        # 50 pods over 16-wide chunks: the last chunk is PARTIAL, so the
        # padding rows ride the scan (all-false masks must resolve to -1
        # with no fallback and no poisoning).
        snap, pods = self._cluster_and_pods(9, n_pods=50)
        fwk = default_fwk()
        # The override is a LIVE env read now (utils/flags.py), so the
        # sweep knob is the flag itself — no module-state patching.
        monkeypatch.setenv("KTPU_SHORTLIST_K", "0")
        full, _ = backend_mod.TPUBackend(
            max_batch=16, mesh=None).assign(pods, snap, fwk)
        monkeypatch.setenv("KTPU_SHORTLIST_K", "16")
        b = backend_mod.TPUBackend(max_batch=16, mesh=None)
        b.metrics = SchedulerMetrics()
        sl, _ = b.assign(pods, snap, fwk)
        assert full == sl
        # The forced run must actually have taken the shortlist path.
        assert b.metrics.solver_shortlist_pods.value() == len(pods)
        assert b.metrics.solve_duration.count() > 0
