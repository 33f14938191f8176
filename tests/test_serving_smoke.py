"""Tier-1 smoke for the online serving tier (kubernetes_tpu/serving).

Pins: (a) the serving tier is ACTIVE BY DEFAULT — a trickle of lone
pods rides the pinned single-pod fast path, counted in the metrics;
(b) fast-path assignments are BIT-IDENTICAL to the batch path
(randomized differential vs TPUBackend.assign — the same pod through
both machines lands on the same node); (c) the KTPU_SERVING=0 kill
switch degrades STRUCTURALLY (no tier attached, no resident planes, no
fast-path counts) with identical end-to-end placements; (d) the
resident device planes stay exact across node add / remove / cordon /
drain (mirror and device array equal a fresh full upload, fast path
still agrees with the batch path); (e) the admission-window policy row
and its KTPU_ADMISSION_WINDOW override. The heavy serve-vs-drain
numbers live in bench --serve (BASELINE r16).
"""

import asyncio
import random

import numpy as np
import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops.backend import AdaptiveTuner, TPUBackend
from kubernetes_tpu.scheduler.cache import SchedulerCache
from kubernetes_tpu.scheduler.types import PodInfo
from kubernetes_tpu.serving import serving_enabled
from kubernetes_tpu.serving.admission import AdmissionWindow
from kubernetes_tpu.serving.fastpath import SinglePodFastPath
from kubernetes_tpu.serving.loop import ServingTier
from kubernetes_tpu.serving.resident import ResidentPlanes
from kubernetes_tpu.utils import locking
from kubernetes_tpu.utils.tracing import Tracer
from test_tpu_backend import default_fwk

ZONE_LABEL = "topology.kubernetes.io/zone"


@pytest.fixture(autouse=True)
def _lock_check(monkeypatch):
    """Tier-1 rides the runtime lock/dispatch-hygiene detector: every
    lock built while this suite runs is instrumented, and the solve
    fetch / fast-path fetch / wire flush seams raise if entered with a
    lock held (utils/locking.py; the static pass's runtime twin)."""
    monkeypatch.setenv("KTPU_LOCK_CHECK", "1")
    locking.reset_observed()
    yield
    locking.reset_observed()


def _cluster(n, alloc=None, taint_every=0):
    cache = SchedulerCache()
    for i in range(n):
        taints = None
        if taint_every and i % taint_every == 0:
            taints = [{"key": "dedicated", "value": "infra",
                       "effect": "NoSchedule"}]
        cache.add_node(make_node(
            f"n{i}",
            allocatable=alloc or {"cpu": "8", "memory": "32Gi",
                                  "pods": "110"},
            taints=taints))
    return cache, cache.update_snapshot()


def _backend(chunk=16):
    b = TPUBackend(max_batch=chunk, mesh=None)
    b.metrics = SchedulerMetrics()
    return b


def _fast(backend):
    res = ResidentPlanes(backend)
    return res, SinglePodFastPath(backend, res)


class FastCycleScheduler:
    """What `ServingTier`'s fast cycle reaches of a Scheduler, over a
    bare cache: assume is the cache's own, and `placed` lists what the
    cycle assumed, in order."""

    def __init__(self, cache, tracer=None):
        self.cache = cache
        self.metrics = SchedulerMetrics()
        self.tracer = tracer if tracer is not None else Tracer()
        self.backend = _backend()
        self.backend.metrics = self.metrics
        self.backend.tracer = self.tracer
        self.fwk = default_fwk()
        self.profiles = {"default-scheduler": self.fwk}
        self.backend_profiles = None
        self.placed: list[tuple[str, str]] = []

    def _record_queue_wait(self, pi) -> None:
        pass

    async def _assume_and_bind(self, fwk, state, pi, node) -> None:
        self.cache.assume_pod(pi, node)
        self.placed.append((pi.key, node))


def _random_lone_pod(rng, t, n_nodes):
    kw = {"requests": {
        "cpu": f"{rng.choice([100, 250, 500, 900, 1700])}m",
        "memory": f"{rng.choice([128, 512, 1024])}Mi"}}
    if rng.random() < 0.3:
        kw["tolerations"] = [{"key": "dedicated", "operator": "Exists"}]
    if rng.random() < 0.25:
        # NodeAffinity static row rides the fast-path base mask.
        kw["node_selector"] = {
            "kubernetes.io/hostname": f"n{rng.randrange(n_nodes)}"}
    return PodInfo(make_pod(f"p{t}", uid=f"u{t}", **kw)), kw


class TestAdmissionPolicy:
    def test_tuner_policy_row(self):
        # At/below the r15 trickle (250/s): always immediate.
        assert AdaptiveTuner.admission_window(0.0) == 0.0
        assert AdaptiveTuner.admission_window(250.0) == 0.0
        # Above it: sized to ~TARGET pods, capped at 4 ms.
        assert AdaptiveTuner.admission_window(1000.0) \
            == AdaptiveTuner.ADMISSION_MAX_WINDOW_S
        assert AdaptiveTuner.admission_window(100000.0) \
            == pytest.approx(8.0 / 100000.0)

    def test_fast_path_cap_row(self):
        # Seeds before any measurement: 0.25 s chunk / 1 ms fast → 250.
        assert AdaptiveTuner.fast_path_cap(0.0, 0.0) == 250
        # Measured walls drive the crossover, clamped to [8, 512].
        assert AdaptiveTuner.fast_path_cap(0.4, 2e-3) == 200
        assert AdaptiveTuner.fast_path_cap(0.01, 5e-3) == 8
        assert AdaptiveTuner.fast_path_cap(10.0, 1e-3) == 512

    def test_fast_path_rate_limit_row(self):
        # Seed: 50% utilization of the optimistic 1 ms seed → 500/s
        # (clears the 250/s trickle with margin before any sample);
        # measured walls refine it (0.6 ms → ~833/s).
        assert AdaptiveTuner.fast_path_rate_limit(0.0) \
            == pytest.approx(500.0)
        assert AdaptiveTuner.fast_path_rate_limit(0.6e-3) \
            == pytest.approx(833.3, rel=1e-3)

    def test_fast_path_seed_scales_with_nodes(self):
        """An UNMEASURED fast wall seeds from the 5k calibration point
        scaled linearly with n (solve_one is a full-N scan): at 200k
        the cold cap must read ~0.25s/40ms = 8, not the 512 clamp that
        once let one big dispatch serial-drain 243 pods at ~125 ms
        each. Measured walls ignore the node count entirely, and at or
        below the calibration point the seeds are byte-identical to
        the old policy."""
        calib = AdaptiveTuner.FAST_PATH_SEED_CALIB_N
        assert AdaptiveTuner.fast_path_cap(0.0, 0.0, n_nodes=calib) == 250
        assert AdaptiveTuner.fast_path_rate_limit(0.0, n_nodes=calib) \
            == pytest.approx(500.0)
        # 200k: seed 40 ms → cap 0.25/0.04 ≈ 6 → clamped to the 8 floor,
        # rate limit 0.5/0.04 = 12.5/s (serial capacity there is ~8/s).
        assert AdaptiveTuner.fast_path_cap(0.0, 0.0, n_nodes=200_000) == 8
        assert AdaptiveTuner.fast_path_rate_limit(0.0, n_nodes=200_000) \
            == pytest.approx(12.5)
        # a measured wall wins over any node count
        assert AdaptiveTuner.fast_path_cap(0.4, 2e-3, n_nodes=200_000) \
            == 200
        assert AdaptiveTuner.fast_path_rate_limit(0.6e-3,
                                                  n_nodes=200_000) \
            == pytest.approx(833.3, rel=1e-3)

    def test_override_and_budget_gate(self, monkeypatch):
        monkeypatch.setenv("KTPU_ADMISSION_WINDOW", "2.5")
        win = AdmissionWindow()
        win.rate_est = 0.0  # override applies regardless of rate
        assert win.window_for(1, 0, 64) == pytest.approx(2.5e-3)
        # Budget already met (or the backlog meets it): never wait.
        assert win.window_for(64, 0, 64) == 0.0
        assert win.window_for(1, 64, 64) == 0.0
        monkeypatch.setenv("KTPU_ADMISSION_WINDOW", "0")
        assert win.window_for(1, 0, 64) == 0.0

    def test_rate_estimator_tracks_pops(self):
        win = AdmissionWindow()
        t = 100.0
        for _ in range(50):
            win.observe_pop(1, t)
            t += 0.001  # 1000/s trickle of lone pods
        assert win.rate_est == pytest.approx(1000.0, rel=0.1)


class TestFastPathDifferential:
    def test_randomized_single_pod_parity(self):
        """The same lone pod through solve_one-vs-the-fused-chunk must
        land identically, across random request shapes, taints, node
        selectors, and evolving cluster state."""
        cache, snap = _cluster(150, taint_every=7)
        fwk = default_fwk()
        rng = random.Random(0xBEEF)
        b_batch = _backend(chunk=16)
        b_fast = _backend(chunk=16)
        _, fp = _fast(b_fast)
        checked = 0
        for t in range(24):
            pi, kw = _random_lone_pod(rng, t, 150)
            a, _ = b_batch.assign([pi], snap, fwk)
            fast = fp.try_schedule(pi, snap, fwk)
            assert fast == a[pi.key], (t, kw)
            if fast is not None:
                checked += 1
                cache.assume_pod(pi, fast)
                snap = cache.update_snapshot()
        assert checked >= 12  # the differential actually exercised placements
        assert fp.placed == checked

    def test_async_cycle_matches_the_blocking_form(self):
        """The serving tier's cycle, which yields the loop until the
        index is ready and verifies against the live cache, places a
        random sequence of lone pods exactly where the blocking
        try_schedule does, each placement assumed before the next pod
        on both sides."""
        cache_b, _ = _cluster(150, taint_every=7)
        cache_a, _ = _cluster(150, taint_every=7)
        sched = FastCycleScheduler(cache_a)
        tier = ServingTier(sched)
        fwk = default_fwk()
        _, fp = _fast(_backend())
        rng = random.Random(0xFE7C)

        async def run():
            checked = 0
            for t in range(24):
                pi, kw = _random_lone_pod(rng, t, 150)
                blocking = fp.try_schedule(pi, cache_b.update_snapshot(),
                                           fwk)
                placed = len(sched.placed)
                took = await tier._try_fast_path(pi)
                assert took == (blocking is not None), (t, kw)
                if blocking is not None:
                    assert sched.placed[placed:] == [(pi.key, blocking)], \
                        (t, kw)
                    cache_b.assume_pod(pi, blocking)
                    checked += 1
            return checked

        checked = asyncio.run(run())
        assert checked >= 12
        assert tier.fastpath.placed == fp.placed == checked
        # one fetch per solve dispatched (placed or not)
        assert sched.metrics.fast_fetch_duration.count() \
            == tier.fastpath.placed + tier.fastpath.no_fit

    def test_ineligible_shapes_fall_through(self):
        _, snap = _cluster(20)
        fwk = default_fwk()
        b = _backend()
        _, fp = _fast(b)
        aff = {"podAffinity": {
            "requiredDuringSchedulingIgnoredDuringExecution": [{
                "topologyKey": "kubernetes.io/hostname",
                "labelSelector": {"matchLabels": {"app": "x"}}}]}}
        cases = [
            make_pod("affinity", uid="u-aff", affinity=aff,
                     requests={"cpu": "100m"}),
            make_pod("ports", uid="u-port", host_ports=[8080],
                     requests={"cpu": "100m"}),
            make_pod("spread", uid="u-spr", requests={"cpu": "100m"},
                     labels={"app": "x"},
                     topology_spread_constraints=[{
                         "maxSkew": 1,
                         "topologyKey": "kubernetes.io/hostname",
                         "whenUnsatisfiable": "DoNotSchedule",
                         "labelSelector": {"matchLabels": {"app": "x"}}}]),
        ]
        for pod in cases:
            assert fp.try_schedule(PodInfo(pod), snap, fwk) is None, \
                pod["metadata"]["name"]
        assert fp.placed == 0
        assert fp.ineligible == len(cases)
        # A nominated preemptor keeps its nominee-first path.
        pi = PodInfo(make_pod("nom", uid="u-nom", requests={"cpu": "100m"}))
        pi.nominated_node = "n0"
        assert fp.try_schedule(pi, snap, fwk) is None


class TestLightSnapshot:
    def test_light_snapshot_invalidates_cached_full_snapshot(self):
        """light_snapshot()'s clone maintenance clears the dirty set; a
        later update_snapshot() must NOT hand back the pre-mutation
        cached snapshot (its copied lists hold the old clones)."""
        cache, _ = _cluster(4)
        a = cache.update_snapshot()
        pi = PodInfo(make_pod("ls-p0", uid="ls-p0",
                              requests={"cpu": "1"}))
        cache.assume_pod(pi, "n0")
        light = cache.light_snapshot()
        assert light.get("n0").requested.get("cpu") == 1000
        b = cache.update_snapshot()
        assert b is not a
        assert b.get("n0").requested.get("cpu") == 1000
        # clean path still memoizes once no mutation intervenes
        assert cache.update_snapshot() is b


class TestResidentPlaneParity:
    def _fresh_pack(self, ct):
        return np.concatenate(
            [ct.used_q, ct.used_nz_q,
             ct.used_pods.astype(np.int32)[:, None]], axis=1)

    def test_refresh_parity_across_node_lifecycle(self):
        """Mirror + device array must equal a from-scratch upload after
        assumes, node add, node remove, and a cordon (drain prologue) —
        and the fast path must keep agreeing with the batch path."""
        cache, snap = _cluster(40)
        fwk = default_fwk()
        b = _backend()
        res, fp = _fast(b)

        def check(tag):
            ct = b._tensors(cache.update_snapshot())
            res.used_pack(ct)
            fresh = self._fresh_pack(ct)
            assert np.array_equal(res.host_mirror(), fresh), tag
            assert np.array_equal(np.asarray(res._dev), fresh), tag
            pi = PodInfo(make_pod(f"probe-{tag}", uid=f"probe-{tag}",
                                  requests={"cpu": "250m",
                                            "memory": "256Mi"}))
            ref = _backend()
            a, _ = ref.assign([pi], cache.update_snapshot(), fwk)
            assert fp.try_schedule(pi, cache.update_snapshot(), fwk) \
                == a[pi.key], tag

        # assumes drive incremental row refreshes
        for t in range(10):
            pi = PodInfo(make_pod(f"w{t}", uid=f"w{t}",
                                  requests={"cpu": "500m",
                                            "memory": "1Gi"}))
            node = fp.try_schedule(pi, cache.update_snapshot(), fwk)
            assert node is not None
            cache.assume_pod(pi, node)
        check("assume")
        assert res.row_refreshes > 0
        cache.add_node(make_node("extra-0"))
        check("node-add")
        cache.remove_node("n39")
        check("node-remove")
        # Cordon: NodeUnschedulable's static row must flow into the
        # fast-path base mask (and the cordoned node never wins).
        cordoned = make_node("n0", unschedulable=True)
        cache.update_node(cordoned)
        check("cordon")
        ct = b._tensors(cache.update_snapshot())
        pi = PodInfo(make_pod("post-cordon", uid="post-cordon",
                              requests={"cpu": "100m"}))
        node = fp.try_schedule(pi, cache.update_snapshot(), fwk)
        assert node is not None and node != "n0"


def _serving_workload():
    return [make_pod(f"p{t}", uid=f"sp{t}",
                     requests={"cpu": "100m", "memory": "250Mi"})
            for t in range(30)]


async def _run_workload(trickle=0):
    """Schedule the standard workload through a live scheduler; returns
    (assignments dict, SchedulerMetrics, serving tier or None).

    trickle > 0 paces the first `trickle` creates as lone-pod arrivals
    (the fast-path shape); trickle == 0 pre-creates everything BEFORE
    the dispatch loop starts, so the first pop drains one batch — the
    drain shape whose placements the kill-switch parity check compares
    (lone pods deliberately aren't compared across the switch: the
    pre-serving loop routes them through the HOST path, whose seeded
    reservoir tiebreak differs from the device argmax tie rule by
    design — the fast path's parity contract is with the BATCH path,
    pinned in TestFastPathDifferential)."""
    from conftest import start_scheduler
    from kubernetes_tpu.api.meta import namespaced_name
    from kubernetes_tpu.store import install_core_validation, \
        new_cluster_store
    store = new_cluster_store()
    install_core_validation(store)
    for i in range(25):
        await store.create("nodes", make_node(
            f"n{i}", allocatable={"cpu": "4", "memory": "16Gi",
                                  "pods": "32"}))
    sched, factory = await start_scheduler(
        store, backend=TPUBackend(max_batch=16, mesh=None))
    pods = _serving_workload()
    run = None
    if trickle:
        run = asyncio.ensure_future(sched.run(batch_size=64))
    for t, pod in enumerate(pods):
        await store.create("pods", pod)
        if trickle and t < trickle:
            await asyncio.sleep(0.02)  # lone-pod arrivals
    if run is None:
        # Let every informer add land in the queue, then open the loop:
        # the first pop sees the whole batch in both serving modes.
        await asyncio.sleep(0.2)
        run = asyncio.ensure_future(sched.run(batch_size=64))
    try:
        for _ in range(600):
            objs = (await store.list("pods")).items
            if len(objs) == len(pods) and all(
                    p["spec"].get("nodeName") for p in objs):
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError("pods never all bound")
        assignments = {namespaced_name(p): p["spec"]["nodeName"]
                       for p in (await store.list("pods")).items}
        return assignments, sched.metrics, sched.serving
    finally:
        await sched.stop()
        run.cancel()
        factory.stop()


class TestServingE2E:
    def test_active_by_default_fast_path_counts(self, monkeypatch):
        monkeypatch.delenv("KTPU_SERVING", raising=False)
        assert serving_enabled()
        _, m, tier = asyncio.run(_run_workload(trickle=8))
        assert tier is not None
        assert m.serving_fast_path_pods.value() > 0
        assert m.resident_plane_refreshes.value() > 0

    def test_kill_switch_structural_degrade_and_parity(self, monkeypatch):
        monkeypatch.delenv("KTPU_SERVING", raising=False)
        a_on, m_on, tier_on = asyncio.run(_run_workload())
        assert tier_on is not None
        assert m_on.resident_plane_refreshes.value() > 0

        monkeypatch.setenv("KTPU_SERVING", "0")
        assert not serving_enabled()
        a_off, m_off, tier_off = asyncio.run(_run_workload())
        # Structural degrade: no tier, no fast-path counts, no resident
        # refreshes — the pre-serving loop shape.
        assert tier_off is None
        assert m_off.serving_fast_path_pods.value() == 0
        assert m_off.resident_plane_refreshes.value() == 0
        # ... and bit-identical batch placements across the switch.
        assert a_on == a_off


def _outside_backend_profiles(sched):
    sched.backend_profiles = {"some-other-scheduler"}


class TestLonePodDispatch:
    """A one-pod dispatch has nothing to pipeline, so when the cap/rate
    gates decline the serial drain it still tries the fast path first;
    a pod the fast path cannot take rides the batch path as a batch of
    one (counted: backend_degradations{kind="lone_batch"}); only a
    shape the backend cannot take reaches the plugin-by-plugin host
    scheduler — and is counted there."""

    @staticmethod
    async def _lone_pods(pods, configure=None):
        """Create `pods` one at a time, each bound before the next is
        created, so every dispatch carries exactly one pod. The nodes
        lie in three zones; `configure(sched)` runs before the loop."""
        from conftest import start_scheduler
        from kubernetes_tpu.store import install_core_validation, \
            new_cluster_store
        store = new_cluster_store()
        install_core_validation(store)
        for i in range(6):
            await store.create("nodes", make_node(
                f"n{i}", labels={ZONE_LABEL: f"z{i % 3}"},
                allocatable={"cpu": "4", "memory": "16Gi",
                             "pods": "32"}))
        sched, factory = await start_scheduler(
            store, backend=TPUBackend(max_batch=16, mesh=None))
        if configure is not None:
            configure(sched)
        run = asyncio.ensure_future(sched.run(batch_size=64))
        try:
            for pod in pods:
                await store.create("pods", pod)
                key = "default/" + pod["metadata"]["name"]
                for _ in range(400):
                    got = await store.get("pods", key)
                    if got["spec"].get("nodeName"):
                        break
                    await asyncio.sleep(0.025)
                else:
                    raise AssertionError(f"{key} never bound")
            return sched.metrics
        finally:
            await sched.stop()
            run.cancel()
            factory.stop()

    @pytest.fixture
    def gates_decline(self, monkeypatch):
        """Every pop reads an offered rate far above
        AdaptiveTuner.fast_path_rate_limit: the serial-drain gates
        decline every dispatch."""
        monkeypatch.delenv("KTPU_SERVING", raising=False)
        monkeypatch.setattr(
            AdmissionWindow, "observe_pop",
            lambda self, n_pods, now=None: setattr(self, "rate_est", 1e9))
        assert 1e9 > AdaptiveTuner.fast_path_rate_limit(0.0, n_nodes=6)

    def test_declined_lone_pods_still_take_the_fast_path(
            self, gates_decline):
        pods = [make_pod(f"lone{t}", uid=f"lone{t}",
                         requests={"cpu": "100m", "memory": "250Mi"})
                for t in range(5)]
        m = asyncio.run(self._lone_pods(pods))
        assert m.serving_fast_path_pods.value() == len(pods)
        assert m.backend_degradations.value(kind="host_path") == 0
        assert m.solve_duration.count() == 0  # no batch solve either
        assert m.device_loss_counts() == {
            "backend_fallback_total": 0, "fast_path_failures_total": 0,
            "host_path_pods": 0}

    @pytest.mark.parametrize("pod_kw, configure, fast, lone_batch, host", [
        # a plain lone pod: the fast path, as before
        pytest.param({}, None, 1, 0, 0, id="plain-fast-path"),
        # what the fast path declines rides the backend as a batch of one
        pytest.param({"host_ports": [8080]}, None, 0, 1, 0,
                     id="host-port-batch-of-one"),
        pytest.param(
            {"labels": {"color": "blue"},
             "topology_spread_constraints": [{
                 "maxSkew": 1, "topologyKey": ZONE_LABEL,
                 "whenUnsatisfiable": "DoNotSchedule",
                 "labelSelector": {"matchLabels": {"color": "blue"}}}]},
            None, 0, 1, 0, id="zone-spread-batch-of-one"),
        # a shape the backend cannot take: its profile is not the
        # backend's to serve
        pytest.param({}, _outside_backend_profiles, 0, 0, 1,
                     id="profile-outside-backend-host-path"),
    ])
    def test_lone_pod_takes_the_fast_path_then_a_batch_of_one(
            self, gates_decline, pod_kw, configure, fast, lone_batch, host):
        pods = [make_pod("lone", uid="u-lone",
                         requests={"cpu": "100m"}, **pod_kw)]
        m = asyncio.run(self._lone_pods(pods, configure))
        assert m.serving_fast_path_pods.value() == fast
        assert m.backend_degradations.value(kind="lone_batch") == lone_batch
        assert m.backend_degradations.value(kind="host_path") == host
        assert m.solve_duration.count() == lone_batch  # one chunk, or none
        assert m.backend_degradations.value(kind="spread_poisoned") == 0
        assert m.serving_fast_path_failures.value() == 0
