"""Randomized differential suite for the batch-optimal (Sinkhorn) solve.

The contract under test: the optimal mode is a SCORING change, never a
feasibility change — the transport plan's log-mass replaces the greedy
static scores and the same capacity-debiting scan rounds it, so every
assignment it emits is valid against the filter planes by construction.
On top of that: occupied-node fragmentation under optimal must not
exceed greedy on adversarial bin-packing fixtures (the headline r20
metric), `KTPU_SOLVE_MODE=greedy` must be bit-identical to the flagless
default at every wave width and shard count (the kill switch restores
the r18 call graph, it doesn't approximate it), the sharded shard_map
Sinkhorn must match the single-device plan at {1, 4, 8} devices, and
gang chunks routed through optimal keep all-or-nothing placement. The
tier-1 policy/NaN/budget pins live in tests/test_optimal_smoke.py.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetes_tpu.ops import solver
from kubernetes_tpu.utils import flags

WIDTHS = (1, 4, 8)


def _class_problem(rng, n, c, p, r, tight=False):
    """Random class-plane problem: per-class request rows, shared mask
    and score planes — the shape the optimal mode requires."""
    if tight:
        alloc_q = rng.integers(2, 6, size=(n, r)).astype(np.int32) * 1000
        class_req = rng.integers(500, 2500, size=(c, r)).astype(np.int32)
        free_pods = rng.integers(1, 3, size=(n,)).astype(np.int32)
    else:
        alloc_q = rng.integers(20, 60, size=(n, r)).astype(np.int32) * 1000
        class_req = rng.integers(100, 3000, size=(c, r)).astype(np.int32)
        free_pods = rng.integers(2, 8, size=(n,)).astype(np.int32)
    used_q = (alloc_q * rng.uniform(0, 0.4, size=(n, r))).astype(np.int32)
    rows = rng.integers(0, c, size=(p,)).astype(np.int32)
    req_q = class_req[rows]
    mask = rng.random((c, n)) > 0.2
    mask[:, 0] = True
    scores = rng.uniform(0, 4, size=(c, n)).astype(np.float32)
    return dict(alloc_q=alloc_q, used_q=used_q, free_pods=free_pods,
                rows=rows, req_q=req_q, mask=mask, scores=scores)


def _optimal_scores(pr, iters=32, temp=0.05):
    """The optimal path's scoring stage, solver-level: transport plan
    over the class planes, log-mass as the scan's static scores."""
    c = pr["mask"].shape[0]
    row_counts = np.bincount(pr["rows"], minlength=c).astype(np.float32)
    log_plan, plan = solver.sinkhorn_plan(
        jnp.asarray(pr["mask"]), jnp.asarray(pr["scores"]),
        jnp.asarray(row_counts), jnp.asarray(pr["free_pods"]),
        jnp.int32(iters), jnp.float32(temp))
    return np.asarray(log_plan), np.asarray(plan)


def _scan_args(pr, static_scores, zero_weights):
    r = pr["alloc_q"].shape[1]
    w = 0.0 if zero_weights else 1.0
    return dict(
        req_q=jnp.asarray(pr["req_q"]), req_nz_q=jnp.asarray(pr["req_q"]),
        free_q=jnp.asarray(pr["alloc_q"] - pr["used_q"]),
        free_pods=jnp.asarray(pr["free_pods"]),
        used_nz_q=jnp.asarray(pr["used_q"]),
        alloc_q=jnp.asarray(pr["alloc_q"]),
        mask=jnp.asarray(pr["mask"]),
        static_scores=jnp.asarray(static_scores.astype(np.float32)),
        fit_col_w=jnp.ones((r,), jnp.float32),
        bal_col_mask=jnp.ones((r,), np.bool_),
        shape_u=jnp.asarray([0.0, 100.0], jnp.float32),
        shape_s=jnp.asarray([0.0, 10.0], jnp.float32),
        w_fit=jnp.float32(w), w_bal=jnp.float32(w),
        rows=jnp.asarray(pr["rows"]))


def _check_feasible(pr, assign):
    """Replay the assignment sequentially against the filter planes:
    mask row, quantity capacity, pod-slot capacity — every placement
    must have been valid AT ITS TURN (the scan debits in pod order)."""
    free = (pr["alloc_q"] - pr["used_q"]).astype(np.int64)
    slots = pr["free_pods"].copy()
    for k, node in enumerate(np.asarray(assign)):
        if node < 0:
            continue
        cls = pr["rows"][k]
        assert pr["mask"][cls, node], (k, node)
        assert (pr["req_q"][k] <= free[node]).all(), (k, node)
        assert slots[node] > 0, (k, node)
        free[node] -= pr["req_q"][k]
        slots[node] -= 1


class TestOptimalFeasibility:
    @pytest.mark.parametrize("tight", [False, True])
    def test_rounding_respects_filter_planes(self, tight):
        """Random problems, loose and contested: every optimal-mode
        assignment replays cleanly against mask + capacity + slots."""
        for seed in range(4):
            rng = np.random.default_rng(seed)
            pr = _class_problem(rng, n=24, c=5, p=31, r=2, tight=tight)
            log_plan, _ = _optimal_scores(pr)
            a = solver.greedy_assign_rescoring(
                strategy="LeastAllocated",
                **_scan_args(pr, log_plan, zero_weights=True))
            _check_feasible(pr, a)

    def test_places_no_fewer_than_plan_mass_suggests(self):
        """Ample capacity: the rounding places every pod the greedy
        baseline places (the plan is a re-ranking, not a filter)."""
        rng = np.random.default_rng(7)
        pr = _class_problem(rng, n=32, c=4, p=24, r=2, tight=False)
        log_plan, _ = _optimal_scores(pr)
        a_opt = np.asarray(solver.greedy_assign_rescoring(
            strategy="LeastAllocated",
            **_scan_args(pr, log_plan, zero_weights=True)))
        a_greedy = np.asarray(solver.greedy_assign_rescoring(
            strategy="LeastAllocated",
            **_scan_args(pr, pr["scores"], zero_weights=False)))
        assert (a_opt >= 0).sum() >= (a_greedy >= 0).sum()


class TestFragmentationHeadline:
    def _assign(self, n_nodes, pods, mode, alloc=None):
        import sys
        sys.path.insert(0, "tests")
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.api.types import make_node
        from kubernetes_tpu.ops.backend import TPUBackend
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        cache = SchedulerCache()
        alloc = alloc or {"cpu": "8", "memory": "32Gi", "pods": "110"}
        for i in range(n_nodes):
            cache.add_node(make_node(f"fr{i}", allocatable=alloc))
        snap = cache.update_snapshot()
        b = TPUBackend(max_batch=256, mesh=None)
        with flags.scoped_set("KTPU_SOLVE_MODE", mode):
            got, _ = b.assign(pods, snap, default_fwk())
        return got

    @staticmethod
    def _occupied_frag(got, pods_by_name, n_nodes, cpu_alloc_m):
        used = {}
        for name, node in got.items():
            if node is None:
                continue
            used[node] = used.get(node, 0) \
                + pods_by_name[name.rsplit("/", 1)[-1]]
        if not used:
            return 0.0
        return 100.0 * sum(
            (cpu_alloc_m - u) / cpu_alloc_m for u in used.values()) \
            / len(used)

    def _pods(self, sizes):
        from kubernetes_tpu.api.types import make_pod
        from kubernetes_tpu.scheduler.types import PodInfo
        return [PodInfo(make_pod(
            f"bp-{i}", requests={"cpu": f"{m}m", "memory": "256Mi"},
            uid=f"bp-uid-{i}")) for i, m in enumerate(sizes)]

    def test_uniform_template_packs_strictly_tighter(self):
        """The adversarial spread fixture: uniform small pods on a wide
        cluster. LeastAllocated greedy spreads one pod per node (max
        occupied fragmentation); the transport plan's first-fit rounding
        packs — strictly lower occupied fragmentation."""
        sizes = [500] * 80
        pods = self._pods(sizes)
        by_name = {f"bp-{i}": m for i, m in enumerate(sizes)}
        f = {}
        for mode in ("greedy", "optimal"):
            got = self._assign(40, pods, mode)
            assert all(v is not None for v in got.values())
            f[mode] = self._occupied_frag(got, by_name, 40, 8000)
        assert f["optimal"] < f["greedy"]
        # the pack side must be near the capacity bound (5 nodes × 16)
        assert f["optimal"] < 20.0

    def test_mixed_classes_no_worse(self):
        """Two interleaved size classes (the bin-packing shape greedy
        fragments): optimal occupied fragmentation ≤ greedy."""
        sizes = [500 if i % 2 else 1500 for i in range(72)]
        pods = self._pods(sizes)
        by_name = {f"bp-{i}": m for i, m in enumerate(sizes)}
        f = {}
        for mode in ("greedy", "optimal"):
            got = self._assign(30, pods, mode)
            assert all(v is not None for v in got.values())
            f[mode] = self._occupied_frag(got, by_name, 30, 8000)
        assert f["optimal"] <= f["greedy"] + 1e-9


class TestKillSwitchBitIdentity:
    def _workload(self, seed, n_pods=48):
        from kubernetes_tpu.api.types import make_node, make_pod
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        from kubernetes_tpu.scheduler.types import PodInfo
        rng = np.random.default_rng(seed)
        cache = SchedulerCache()
        for i in range(36):
            cache.add_node(make_node(
                f"kn{i}", allocatable={
                    "cpu": str(int(rng.choice((4, 8, 16)))),
                    "memory": "32Gi", "pods": "110"}))
        snap = cache.update_snapshot()
        pods = [PodInfo(make_pod(
            f"kp-{i}",
            requests={"cpu": f"{int(rng.choice((100, 250, 500)))}m",
                      "memory": "256Mi"},
            uid=f"kp-uid-{i}")) for i in range(n_pods)]
        return snap, pods

    def test_greedy_flag_matches_flagless_at_every_width(self):
        """KTPU_SOLVE_MODE=greedy vs the flagless default (auto routes
        these sub-threshold chunks to greedy): identical assignment maps
        at W ∈ {1, 4, 8} — the kill switch re-pins the exact r18 call
        graph, wave speculation and all."""
        import sys
        sys.path.insert(0, "tests")
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.ops.backend import TPUBackend
        snap, pods = self._workload(3)
        fwk = default_fwk()
        for w in WIDTHS:
            with flags.scoped_set("KTPU_WAVE_WIDTH", str(w)):
                base, _ = TPUBackend(max_batch=64, mesh=None).assign(
                    pods, snap, fwk)
                with flags.scoped_set("KTPU_SOLVE_MODE", "greedy"):
                    got, _ = TPUBackend(max_batch=64, mesh=None).assign(
                        pods, snap, fwk)
            assert got == base, f"W={w}"

    @pytest.mark.parametrize("n_devices", [4, 8])
    def test_greedy_flag_matches_on_mesh(self, n_devices):
        """Same identity on the sharded backend: the solve-mode static
        rides the program key identically at every device count."""
        if len(jax.devices()) < n_devices:
            pytest.skip("not enough devices")
        import sys
        sys.path.insert(0, "tests")
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.ops.backend import TPUBackend
        from kubernetes_tpu.parallel import build_mesh
        snap, pods = self._workload(11, n_pods=32)
        fwk = default_fwk()
        mesh = build_mesh(n_devices)
        base, _ = TPUBackend(max_batch=32, mesh=mesh).assign(
            pods, snap, fwk)
        with flags.scoped_set("KTPU_SOLVE_MODE", "greedy"):
            got, _ = TPUBackend(max_batch=32, mesh=mesh).assign(
                pods, snap, fwk)
        assert got == base

    def test_optimal_ignores_wave_width(self):
        """Optimal mode pins W=0 at dispatch: KTPU_WAVE_WIDTH must not
        change a single optimal-mode assignment."""
        import sys
        sys.path.insert(0, "tests")
        from test_tpu_backend import default_fwk
        from kubernetes_tpu.ops.backend import TPUBackend
        snap, pods = self._workload(5, n_pods=72)
        fwk = default_fwk()
        outs = []
        for w in (1, 8):
            with flags.scoped_set("KTPU_SOLVE_MODE", "optimal"), \
                    flags.scoped_set("KTPU_WAVE_WIDTH", str(w)):
                got, _ = TPUBackend(max_batch=128, mesh=None).assign(
                    pods, snap, fwk)
            outs.append(got)
        assert outs[0] == outs[1]


class TestGangAllOrNothing:
    def _run(self, coro):
        return asyncio.run(coro)

    def test_feasible_gang_binds_together_infeasible_never_partially(self):
        """Under forced optimal: a gang that fits binds every member; a
        gang that can NEVER assemble binds none (the transport plan
        feeds the same gang-atomic rounding, so all-or-nothing
        survives the mode switch)."""
        async def body():
            from kubernetes_tpu.api.types import make_node
            from kubernetes_tpu.ops import TPUBackend
            from kubernetes_tpu.scheduler.plugins.coscheduling import (
                make_pod_group,
            )
            from kubernetes_tpu.store import (
                install_core_validation,
                new_cluster_store,
            )
            from test_coscheduling import bound_names, gang_pod, make_sched
            store = new_cluster_store()
            install_core_validation(store)
            try:
                # 2 nodes × 8 cpu: a 3×3cpu gang fits (2+1); a 3×7cpu
                # gang can never assemble (one member per node, max 2).
                for i in range(2):
                    await store.create("nodes", make_node(
                        f"gn{i}", allocatable={"cpu": "8",
                                               "memory": "32Gi",
                                               "pods": "110"}))
                await store.create("podgroups", make_pod_group(
                    "fits", min_member=3, schedule_timeout_seconds=5.0))
                await store.create("podgroups", make_pod_group(
                    "never", min_member=3, schedule_timeout_seconds=0.6))
                sched, factory = await make_sched(
                    store, backend=TPUBackend(max_batch=8))
                task = asyncio.ensure_future(sched.run())
                for i in range(3):
                    await store.create("pods", gang_pod(
                        f"ok-{i}", "fits", cpu="3"))
                for _ in range(200):
                    bound = await bound_names(store)
                    if {"ok-0", "ok-1", "ok-2"} <= bound:
                        break
                    await asyncio.sleep(0.05)
                assert {"ok-0", "ok-1", "ok-2"} <= await bound_names(store)
                # Now the impossible gang: with the cluster down to
                # <2cpu per node it can never assemble — no member may
                # EVER bind (a partial bind would strand resources).
                for i in range(3):
                    await store.create("pods", gang_pod(
                        f"no-{i}", "never", cpu="7"))
                await asyncio.sleep(1.2)
                bound = await bound_names(store)
                assert {"ok-0", "ok-1", "ok-2"} <= bound
                assert not bound & {"no-0", "no-1", "no-2"}
                await sched.stop()
                task.cancel()
                factory.stop()
            finally:
                store.stop()
        with flags.scoped_set("KTPU_SOLVE_MODE", "optimal"):
            self._run(body())
