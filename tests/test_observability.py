"""Observability polish (SURVEY §5.1/§3.2): utiltrace threshold logging,
RBAC-lite authorization, jax profiler hook."""

import asyncio
import logging

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.apiserver.client import RemoteStore
from kubernetes_tpu.apiserver.rbac import (
    RBACAuthorizer,
    make_cluster_role,
    make_cluster_role_binding,
)
from kubernetes_tpu.apiserver.server import APIServer
from kubernetes_tpu.store import install_core_validation, new_cluster_store
from kubernetes_tpu.utils.trace import Trace


def run(coro):
    return asyncio.run(coro)


class TestUtilTrace:
    def test_slow_trace_logs_steps(self, caplog):
        with caplog.at_level(logging.INFO, logger="kubernetes_tpu.trace"):
            with Trace("Scheduling", threshold_ms=0.0, pods=3) as tr:
                tr.step("snapshot")
                tr.step("solve")
        assert len(caplog.records) == 1
        msg = caplog.records[0].message
        assert "Trace[Scheduling{pods=3}]" in msg
        assert 'step "snapshot"' in msg and 'step "solve"' in msg

    def test_fast_trace_is_silent(self, caplog):
        with caplog.at_level(logging.INFO, logger="kubernetes_tpu.trace"):
            with Trace("Scheduling", threshold_ms=10_000.0) as tr:
                tr.step("snapshot")
        assert not caplog.records

    def test_scheduler_emits_trace_when_slow(self, caplog):
        """threshold 0 → every attempt traces, proving the wiring."""
        from kubernetes_tpu.client import InformerFactory
        from kubernetes_tpu.scheduler import Scheduler

        async def body():
            store = new_cluster_store()
            install_core_validation(store)
            await store.create("nodes", make_node("n1"))
            sched = Scheduler(store, seed=1, trace_threshold_ms=0.0)
            factory = InformerFactory(store)
            await sched.setup_informers(factory)
            factory.start()
            await factory.wait_for_sync()
            task = asyncio.ensure_future(sched.run())
            await store.create("pods", make_pod("p", requests={"cpu": "1"}))
            for _ in range(200):
                p = await store.get("pods", "default/p")
                if p["spec"].get("nodeName"):
                    break
                await asyncio.sleep(0.02)
            await sched.stop()
            task.cancel()
            factory.stop()
            store.stop()
        with caplog.at_level(logging.INFO, logger="kubernetes_tpu.trace"):
            run(body())
        assert any("Trace[Scheduling" in r.message for r in caplog.records)


class TestRBAC:
    def test_authorizer_decisions(self):
        authz = RBACAuthorizer(
            roles=[
                make_cluster_role("reader", [
                    {"verbs": ["get", "list", "watch"],
                     "resources": ["pods", "nodes"]}]),
                make_cluster_role("admin", [
                    {"verbs": ["*"], "resources": ["*"]}]),
            ],
            bindings=[
                make_cluster_role_binding("rb", "reader", ["alice"]),
                make_cluster_role_binding("ab", "admin", ["root"]),
            ])
        assert authz.allowed("alice", "get", "pods")
        assert authz.allowed("alice", "watch", "nodes")
        assert not authz.allowed("alice", "create", "pods")
        assert not authz.allowed("alice", "get", "secrets")
        assert authz.allowed("root", "delete", "pods")
        assert not authz.allowed("mallory", "get", "pods")

    def test_group_bindings_track_membership_not_names(self):
        """A Group binding grants members of the group (via groups=) and
        never a USER who merely shares the group's name (ADVICE r3)."""
        authz = RBACAuthorizer(
            roles=[make_cluster_role("admin", [
                {"verbs": ["*"], "resources": ["*"]}])])
        authz.add_binding({
            "roleRef": {"kind": "ClusterRole", "name": "admin"},
            "subjects": [{"kind": "Group", "name": "admins"}]})
        # user literally named "admins" gets nothing
        assert not authz.allowed("admins", "delete", "pods")
        # a member of the group does
        assert authz.allowed("alice", "delete", "pods", groups=["admins"])
        assert not authz.allowed("alice", "delete", "pods", groups=["dev"])

    def test_serviceaccount_subject_maps_to_token_username(self):
        authz = RBACAuthorizer(
            roles=[make_cluster_role("reader", [
                {"verbs": ["get"], "resources": ["pods"]}])])
        authz.add_binding({
            "roleRef": {"kind": "ClusterRole", "name": "reader"},
            "subjects": [{"kind": "ServiceAccount", "name": "builder",
                          "namespace": "ci"}]})
        assert authz.allowed("system:serviceaccount:ci:builder",
                             "get", "pods")
        assert not authz.allowed("builder", "get", "pods")

    def test_apiserver_group_membership_authz(self):
        """user_groups on the server feeds Group bindings end-to-end,
        including the implicit system:authenticated group."""
        async def body():
            store = new_cluster_store()
            install_core_validation(store)
            authz = RBACAuthorizer(
                roles=[make_cluster_role("podadmin", [
                    {"verbs": ["*"], "resources": ["pods"]}]),
                    make_cluster_role("discovery", [
                        {"verbs": ["get", "list"],
                         "resources": ["namespaces"]}])])
            authz.add_binding({
                "roleRef": {"kind": "ClusterRole", "name": "podadmin"},
                "subjects": [{"kind": "Group", "name": "sre"}]})
            authz.add_binding({
                "roleRef": {"kind": "ClusterRole", "name": "discovery"},
                "subjects": [{"kind": "Group",
                              "name": "system:authenticated"}]})
            srv = APIServer(
                store,
                bearer_tokens={"t-a": "alice", "t-b": "bob"},
                user_groups={"alice": ["sre"]},
                authorizer=authz)
            await srv.start()
            a = RemoteStore(srv.url, token="t-a")
            created = await a.create("pods", make_pod("p1"))
            assert created["metadata"]["name"] == "p1"
            # bob is authenticated (namespaces OK) but not in sre (pods 403)
            b = RemoteStore(srv.url, token="t-b")
            await b.list("namespaces")
            from kubernetes_tpu.store.mvcc import StoreError
            with pytest.raises(StoreError):
                await b.create("pods", make_pod("p2"))
            await a.close()
            await b.close()
            await srv.stop()
            store.stop()
        run(body())

    def test_apiserver_enforces_rbac(self):
        async def body():
            store = new_cluster_store()
            install_core_validation(store)
            authz = RBACAuthorizer(
                roles=[make_cluster_role("scheduler", [
                    {"verbs": ["*"], "resources": ["pods", "nodes"]}]),
                    make_cluster_role("reader", [
                        {"verbs": ["get", "list"],
                         "resources": ["pods"]}])],
                bindings=[
                    make_cluster_role_binding("b1", "scheduler", ["sched"]),
                    make_cluster_role_binding("b2", "reader", ["ro"])])
            srv = APIServer(
                store,
                bearer_tokens={"t-sched": "sched", "t-ro": "ro"},
                authorizer=authz)
            await srv.start()

            rw = RemoteStore(srv.url, token="t-sched")
            created = await rw.create("pods", make_pod("a"))
            assert created["metadata"]["name"] == "a"

            ro = RemoteStore(srv.url, token="t-ro")
            got = await ro.get("pods", "default/a")
            assert got["metadata"]["name"] == "a"
            from kubernetes_tpu.store.mvcc import StoreError
            with pytest.raises(StoreError):
                await ro.create("pods", make_pod("b"))   # 403
            with pytest.raises(StoreError):
                await ro.list("nodes")                   # 403

            await rw.close()
            await ro.close()
            await srv.stop()
            store.stop()
        run(body())


class TestProfilerHook:
    def test_start_stop_profile_writes_a_trace(self, tmp_path):
        from kubernetes_tpu.ops import TPUBackend
        backend = TPUBackend(max_batch=8)
        backend.start_profile(str(tmp_path / "trace"))
        backend.stop_profile()
        assert list((tmp_path / "trace").rglob("*.xplane.pb"))

    def test_profile_that_cannot_start_raises(self, tmp_path):
        """A trace that was asked for and cannot start is the run's
        error (here: one is already running), never a warning."""
        from kubernetes_tpu.ops import TPUBackend
        backend = TPUBackend(max_batch=8)
        backend.start_profile(str(tmp_path / "a"))
        try:
            with pytest.raises(RuntimeError):
                TPUBackend(max_batch=8).start_profile(str(tmp_path / "b"))
        finally:
            backend.stop_profile()


class TestBackendDegradationMetrics:
    """§5.5: the TPU backend's silent fallbacks are observable — spread
    residency here; gang overflow in test_coscheduling."""

    def test_heterogeneous_min_domains_batch_stays_on_device(self):
        async def body():
            import asyncio

            from kubernetes_tpu.api.types import make_node, make_pod
            from kubernetes_tpu.client import InformerFactory
            from kubernetes_tpu.ops import TPUBackend
            from kubernetes_tpu.scheduler import Scheduler
            from kubernetes_tpu.store import (
                install_core_validation,
                new_cluster_store,
            )
            store = new_cluster_store()
            install_core_validation(store)
            for i in range(4):
                await store.create("nodes", make_node(
                    f"n{i}",
                    labels={"topology.kubernetes.io/zone": f"z{i % 2}"}))
            sched = Scheduler(store, seed=4, backend=TPUBackend(max_batch=16))
            factory = InformerFactory(store)
            await sched.setup_informers(factory)
            factory.start()
            await factory.wait_for_sync()
            run_task = asyncio.ensure_future(sched.run(batch_size=16))

            def spread_pod(name, app, skew, extra=None):
                c = {"maxSkew": skew,
                     "topologyKey": "topology.kubernetes.io/zone",
                     "whenUnsatisfiable": "DoNotSchedule",
                     "labelSelector": {"matchLabels": {"app": app}}}
                if extra:
                    c.update(extra)
                return make_pod(name, labels={"app": app},
                                topology_spread_constraints=[c])
            # EVERY template rides the union table now — heterogeneous
            # batches and minDomains constraints included. The
            # spread_poisoned counter marks only the missing-table escape
            # hatch and must stay ZERO here.
            for i in range(4):
                await store.create("pods", spread_pod(f"a{i}", "a", 1))
                await store.create("pods", spread_pod(
                    f"b{i}", "b", 2, extra={"minDomains": 2}))
            for _ in range(300):
                pods = (await store.list("pods")).items
                if sum(1 for p in pods if p["spec"].get("nodeName")) == 8:
                    break
                await asyncio.sleep(0.02)
            pods = (await store.list("pods")).items
            assert sum(1 for p in pods if p["spec"].get("nodeName")) == 8
            assert sched.metrics.backend_degradations.value(
                kind="spread_poisoned") == 0
            await sched.stop()
            run_task.cancel()
            factory.stop()
            store.stop()
        run(body())


class TestAffinityCompilerBuilds:
    """How the backend's affinity compiler reached each snapshot is in the
    registry: one full walk, then delta advances while the node set stands;
    the spread table's node planes are built once and kept."""

    def test_n_drains_are_one_full_build_and_the_rest_delta(self):
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        from kubernetes_tpu.ops import TPUBackend
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        from kubernetes_tpu.scheduler.framework import Framework
        from kubernetes_tpu.scheduler.plugins.registry import (
            DEFAULT_SCORE_WEIGHTS,
            build_plugins,
        )
        from kubernetes_tpu.scheduler.types import PodInfo
        from kubernetes_tpu.utils.tracing import Tracer
        zone = "topology.kubernetes.io/zone"
        cache = SchedulerCache()
        for i in range(12):
            cache.add_node(make_node(f"n{i}", labels={zone: f"z{i % 3}"}))
        backend = TPUBackend(max_batch=16, mesh=None)
        backend.metrics = SchedulerMetrics()
        backend.tracer = Tracer(enabled=True)
        fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
        drains = 5
        try:
            for d in range(drains):
                batch = [PodInfo(make_pod(
                    f"d{d}-{j}", uid=f"d{d}-{j}", labels={"app": "a"},
                    topology_spread_constraints=[{
                        "maxSkew": 1, "topologyKey": zone,
                        "whenUnsatisfiable": "DoNotSchedule",
                        "labelSelector": {"matchLabels": {"app": "a"}}}]))
                    for j in range(6)]
                placed, _ = backend.assign(
                    batch, cache.update_snapshot(), fwk)
                for pi in batch:
                    cache.assume_pod(pi, placed[pi.key])
            spans = [s for s in backend.tracer.spans
                     if s.name == "solver.spread_table"
                     and getattr(s, "span_id", None)]
        finally:
            backend.tracer.enabled = False
        m = backend.metrics
        assert m.affinity_compiler_builds.value(kind="full") == 1
        assert m.affinity_compiler_builds.value(kind="delta") == drains - 1
        # a full build counts every node; each later one the (at most six)
        # nodes the drain before it placed pods on
        rows = m.affinity_rows_recounted.value()
        assert 12 + (drains - 1) <= rows <= 12 + 6 * (drains - 1)
        assert m.spread_table_builds.value(planes="built") == 1
        assert m.spread_table_builds.value(planes="kept") == drains - 1
        assert len(spans) == drains                     # once per assign()
        text = m.registry.render()
        assert 'scheduler_tpu_affinity_compiler_builds_total{kind="delta"} 4' \
            in text
        assert "scheduler_tpu_affinity_rows_recounted_total" in text


class TestScanStepCounter:
    """The chunk scan's trip count follows the chunk's real pods, and
    the registry says how many of the padded scan's steps that saved —
    counted on the host from what the program is handed."""

    def test_series_exist_at_zero_from_registration(self):
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        text = SchedulerMetrics().registry.render()
        for kind in ("run", "skipped"):
            assert 'scheduler_tpu_solver_scan_steps_total{kind="%s"} 0' \
                % kind in text

    def test_three_pods_at_w32_run_one_wave_of_32(self, monkeypatch):
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        from kubernetes_tpu.ops import TPUBackend
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        from kubernetes_tpu.scheduler.framework import Framework
        from kubernetes_tpu.scheduler.plugins.registry import (
            DEFAULT_SCORE_WEIGHTS,
            build_plugins,
        )
        from kubernetes_tpu.scheduler.types import PodInfo
        for k in ("KTPU_WAVEFRONT", "KTPU_WAVE_WIDTH", "KTPU_SOLVE_MODE"):
            monkeypatch.delenv(k, raising=False)
        cache = SchedulerCache()
        for i in range(12):
            cache.add_node(make_node(f"n{i}"))
        backend = TPUBackend(max_batch=None, mesh=None)   # P = 1,024
        backend.metrics = SchedulerMetrics()
        statics = []
        inner = TPUBackend._dispatch_chunk_jit

        def recording(self, prep, ctx):
            out = inner(self, prep, ctx)
            statics.append((out["batch"].req_q.shape[0], out["wave_w"]))
            return out

        monkeypatch.setattr(TPUBackend, "_dispatch_chunk_jit", recording)
        pods = [PodInfo(make_pod(f"p{i}", uid=f"p{i}",
                                 requests={"cpu": "100m"}))
                for i in range(3)]
        placed, _ = backend.assign(
            pods, cache.update_snapshot(),
            Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS))
        assert all(placed.values())
        assert statics == [(1024, 32)]
        steps = backend.metrics.solver_scan_steps
        assert steps.value(kind="run") == 1
        assert steps.value(kind="skipped") == 31


class TestRequestTracing:
    """§5.1 OTel-style spans: one trace covers a pod's create → schedule
    → bind across the apiserver and scheduler, exportable to Perfetto."""

    def test_pod_journey_trace_and_perfetto_export(self):
        async def body():
            import asyncio
            import json as _json

            from kubernetes_tpu.api.types import make_node, make_pod
            from kubernetes_tpu.apiserver import APIServer, RemoteStore
            from kubernetes_tpu.client import InformerFactory
            from kubernetes_tpu.scheduler import Scheduler
            from kubernetes_tpu.store import (
                install_core_validation,
                new_cluster_store,
            )
            from kubernetes_tpu.utils.tracing import DEFAULT_TRACER
            DEFAULT_TRACER.enabled = True
            DEFAULT_TRACER.clear()
            try:
                backing = new_cluster_store()
                install_core_validation(backing)
                srv = APIServer(backing)
                await srv.start()
                rs = RemoteStore(srv.url)
                await rs.create("nodes", make_node("n0"))
                sched = Scheduler(rs, seed=9)
                factory = InformerFactory(rs)
                await sched.setup_informers(factory)
                factory.start()
                await factory.wait_for_sync()
                run_task = asyncio.ensure_future(sched.run(batch_size=4))
                await rs.create("pods", make_pod("traced"))
                for _ in range(300):
                    p = await rs.get("pods", "default/traced")
                    if p["spec"].get("nodeName"):
                        break
                    await asyncio.sleep(0.02)
                assert p["spec"].get("nodeName") == "n0"
                await sched.stop()
                run_task.cancel()
                factory.stop()
                await rs.close()
                await srv.stop()
                backing.stop()

                journey = DEFAULT_TRACER.trace_for("default/traced")
                names = [s.name for s in journey]
                # create request, scheduling attempt, binding cycle, and
                # the binding POST back through the apiserver — ordered.
                assert "apiserver.create.pods" in names, names
                assert "scheduler.attempt" in names, names
                assert "scheduler.bind" in names, names
                assert names.index("apiserver.create.pods") \
                    < names.index("scheduler.attempt") \
                    < names.index("scheduler.bind"), names
                # the binding POST is a second pod-attributed apiserver
                # span after the bind began
                api_spans = [s for s in journey
                             if s.name.startswith("apiserver.")]
                assert len(api_spans) >= 2, names
                # W3C traceparent propagation: the binding POST's server
                # span belongs to scheduler.bind's TRACE (same trace_id),
                # not a fresh one.
                bind = next(s for s in journey
                            if s.name == "scheduler.bind")
                bind_post = next(
                    (s for s in api_spans
                     if s.start >= bind.start and s.trace_id ==
                     bind.trace_id), None)
                assert bind_post is not None, [
                    (s.name, s.trace_id) for s in journey]
                assert all(s.end is not None for s in journey)
                # Perfetto export round-trips
                doc = _json.loads(DEFAULT_TRACER.to_perfetto())
                evs = doc["traceEvents"]
                assert any(e["name"] == "scheduler.bind" for e in evs)
                assert any(e["name"] == "store.subresource.binding"
                           for e in evs)
                assert all("ts" in e and "dur" in e for e in evs)
            finally:
                DEFAULT_TRACER.enabled = False
                DEFAULT_TRACER.clear()
        run(body())
