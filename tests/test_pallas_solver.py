"""Randomized differential parity for the fused Pallas wavefront kernel.

The contract under test: `ops/pallas_kernel.wave_solve` — one grid step
fusing plane gather → bit-mask unpack → fit/taint/balanced score →
prefix-distinct wave argmax → pairwise (W,W) conflict re-score →
capacity debit, with the used-state carry resident — produces
assignments BIT-IDENTICAL to the lax.scan reference
(`greedy_assign_rescoring_wave`) it replaces, in interpret mode on CPU:
vs the W=1 serial scan AND the W=64 scan, across tight-capacity
conflict storms, every packing strategy, class-plane indirection with
pinned-column exceptions, multistart permutations with gang
all-or-nothing, and the shard-local `wave_eval` fusion at {1, 4, 8}
shards. Commit/replay counters must match the scan EXACTLY — the
AdaptiveTuner's width policy reads them, so a kernel that assigns
identically but counts differently would still skew W.

The tier-1 activation/kill-switch/fallback-counter pins live in
tests/test_pallas_smoke.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import solver
from test_wavefront_solver import _problem

# interpret=True below is the Pallas interpreter, a CPU test mode. On an
# accelerator it would pass without ever meeting the compiler — and the
# compiler refuses these kernels (pallas_kernel.resolve_mode quotes it).
pytestmark = pytest.mark.skipif(
    jax.default_backend() != "cpu",
    reason="Pallas interpret mode is a CPU test mode; the kernels do "
           "not lower for this platform")

#: every width exercises a different padding shape (31 is the odd
#: chunk, 64 > P pads a whole trailing wave).
WIDTHS = (2, 8, 31, 64)


def _scan_ref(strategy, w, args):
    a, com, rep = solver.greedy_assign_rescoring_wave(
        strategy=strategy, wave_w=w, **args)
    return np.asarray(a), int(com), int(rep)


class TestPallasWaveParity:
    @pytest.mark.parametrize("strategy",
                             ["LeastAllocated", "MostAllocated",
                              "RequestedToCapacityRatio"])
    def test_conflict_storm_bit_identity(self, strategy):
        """Tight capacity: speculation must conflict and replay through
        the in-kernel fori_loop exactly like the scan's slow path —
        assignments AND the commit/replay split match at every W."""
        for seed in range(2):
            rng = np.random.default_rng(seed)
            args, _ = _problem(rng, n=24, p=31, r=2, tight=True)
            ref = np.asarray(solver.greedy_assign_rescoring(
                strategy=strategy, **args))
            for w in WIDTHS:
                sa, scom, srep = _scan_ref(strategy, w, args)
                np.testing.assert_array_equal(sa, ref)
                a, com, rep = solver.greedy_assign_rescoring_wave_pallas(
                    strategy=strategy, wave_w=w, interpret=True, **args)
                np.testing.assert_array_equal(
                    np.asarray(a), ref, err_msg=f"W={w} {strategy}")
                assert (int(com), int(rep)) == (scom, srep), \
                    f"W={w} {strategy}"

    def test_class_planes_and_exceptions(self):
        """Class-row indirection + pinned-column exceptions ride the
        fused gather/exception gate exactly like the scan."""
        for seed in range(2):
            rng = np.random.default_rng(100 + seed)
            args, _ = _problem(rng, n=40, p=26, r=3, classes=4)
            exc = np.full((26,), -1, np.int32)
            exc[rng.integers(0, 26, size=5)] = \
                rng.integers(0, 40, size=5).astype(np.int32)
            args["exc"] = jnp.asarray(exc)
            ref = np.asarray(solver.greedy_assign_rescoring(
                strategy="LeastAllocated", **args))
            for w in (2, 8):
                a, com, rep = solver.greedy_assign_rescoring_wave_pallas(
                    strategy="LeastAllocated", wave_w=w,
                    interpret=True, **args)
                np.testing.assert_array_equal(np.asarray(a), ref,
                                              err_msg=f"W={w}")
                assert int(com) + int(rep) == 26

    def test_uniform_template_commits_speculatively(self):
        """The template regime (the bench presets' shape): the kernel
        must commit whole waves without replays, like the scan — a
        bit-identical kernel that replays anyway buys nothing."""
        n, p, r = 128, 32, 2
        args = dict(
            req_q=jnp.asarray(np.full((p, r), 500, np.int32)),
            req_nz_q=jnp.asarray(np.full((p, r), 500, np.int32)),
            free_q=jnp.asarray(np.full((n, r), 8000, np.int32)),
            free_pods=jnp.asarray(np.full((n,), 110, np.int32)),
            used_nz_q=jnp.asarray(np.zeros((n, r), np.int32)),
            alloc_q=jnp.asarray(np.full((n, r), 8000, np.int32)),
            mask=jnp.asarray(np.ones((1, n), np.bool_)),
            static_scores=jnp.asarray(np.zeros((1, n), np.float32)),
            fit_col_w=jnp.ones((r,), jnp.float32),
            bal_col_mask=jnp.ones((r,), np.bool_),
            shape_u=jnp.zeros((2,), jnp.float32),
            shape_s=jnp.zeros((2,), jnp.float32),
            w_fit=jnp.float32(1.0), w_bal=jnp.float32(1.0),
            rows=jnp.asarray(np.zeros((p,), np.int32)))
        ref = np.asarray(solver.greedy_assign_rescoring(
            strategy="LeastAllocated", **args))
        a, com, rep = solver.greedy_assign_rescoring_wave_pallas(
            strategy="LeastAllocated", wave_w=8, interpret=True, **args)
        np.testing.assert_array_equal(np.asarray(a), ref)
        assert int(rep) == 0 and int(com) == p


class TestPallasMultistartParity:
    def test_permuted_orders_and_gangs(self):
        """K permuted starts with one unreachable gang quota: the
        poison-aware kernel (always-fast waves + poison OR) must select
        the same winner — and the poisoned rerun path the same full
        multistart — as the scan wrapper."""
        for seed in range(2):
            rng = np.random.default_rng(200 + seed)
            p = 24
            args, _ = _problem(rng, n=48, p=p, r=2, tight=(seed == 0))
            k = 4
            perms = np.tile(np.arange(p, dtype=np.int32), (k, 1))
            for i in range(1, k):
                perms[i] = rng.permutation(p).astype(np.int32)
            gang = np.zeros((p, 16), np.float32)
            gang[:5, 0] = 1.0
            grq = np.zeros((16,), np.float32)
            grq[0] = 5.0
            ref = np.asarray(solver.multistart_greedy_assign(
                strategy="LeastAllocated", perms=jnp.asarray(perms),
                gang_onehot=jnp.asarray(gang),
                gang_required=jnp.asarray(grq), **args))
            for w in (2, 8):
                sa, scom, srep = solver.multistart_greedy_assign_wave(
                    strategy="LeastAllocated", wave_w=w,
                    perms=jnp.asarray(perms), gang_onehot=jnp.asarray(gang),
                    gang_required=jnp.asarray(grq), **args)
                a, com, rep = solver.multistart_greedy_assign_wave_pallas(
                    strategy="LeastAllocated", wave_w=w,
                    perms=jnp.asarray(perms), gang_onehot=jnp.asarray(gang),
                    gang_required=jnp.asarray(grq), interpret=True, **args)
                np.testing.assert_array_equal(np.asarray(a), ref,
                                              err_msg=f"W={w}")
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(sa))
                assert (int(com), int(rep)) == (int(scom), int(srep))
                assert int(com) + int(rep) == p


class TestPallasShardedParity:
    @pytest.mark.parametrize("shards", [1, 4, 8])
    def test_mesh_bit_identity(self, shards):
        """pallas="interpret" fuses each wave's shard-local (W, local_n)
        evaluation (ops/pallas_kernel.wave_eval) under shard_map; the
        ICI reductions are untouched, so assignments match the scan
        reference at every shard count."""
        from kubernetes_tpu.parallel import build_mesh, \
            sharded_greedy_assign
        rng = np.random.default_rng(700 + shards)
        n, p, r = 64, 18, 2
        args, _ = _problem(rng, n=n, p=p, r=r)
        mesh = build_mesh(shards)
        ref = np.asarray(solver.greedy_assign_rescoring(
            strategy="LeastAllocated", **args))
        pos = (args["req_q"], args["req_nz_q"], args["free_q"],
               args["free_pods"], args["used_nz_q"], args["alloc_q"],
               args["mask"], args["static_scores"], args["fit_col_w"],
               args["bal_col_mask"], args["shape_u"], args["shape_s"],
               args["w_fit"], args["w_bal"])
        for w in (2, 8):
            got = np.asarray(sharded_greedy_assign(
                mesh, *pos, "LeastAllocated", wave_w=w, pallas="interpret"))
            np.testing.assert_array_equal(
                got, ref, err_msg=f"shards={shards} W={w}")

    def test_mesh_exceptions_global_coords(self):
        """Pinned columns are GLOBAL node ids: the fused eval receives
        the owner shard's local translation and must gate identically."""
        from kubernetes_tpu.parallel import build_mesh, \
            sharded_greedy_assign
        rng = np.random.default_rng(800)
        n, p, r = 64, 12, 2
        args, _ = _problem(rng, n=n, p=p, r=r)
        exc = np.full((p,), -1, np.int32)
        exc[[1, 5, 9]] = [60, 3, 33]
        ref = np.asarray(solver.greedy_assign_rescoring(
            strategy="LeastAllocated", exc=jnp.asarray(exc), **args))
        pos = (args["req_q"], args["req_nz_q"], args["free_q"],
               args["free_pods"], args["used_nz_q"], args["alloc_q"],
               args["mask"], args["static_scores"], args["fit_col_w"],
               args["bal_col_mask"], args["shape_u"], args["shape_s"],
               args["w_fit"], args["w_bal"])
        got = np.asarray(sharded_greedy_assign(
            build_mesh(4), *pos, "LeastAllocated",
            exc=jnp.asarray(exc), wave_w=4, pallas="interpret"))
        np.testing.assert_array_equal(got, ref)
