"""Tier-1 guards for the chip bring-up rules (PR 21), all on the CPU:

- chip_smoke.py refuses to run without the chip and does no work;
- the compile cache is placed from outside or at one fixed in-checkout
  path, and not at all when the CPU was asked for;
- a run that loses the device — batch backend or fast path — shows it
  in its result and fails its entry point;
- TPUBackend refuses a CPU that JAX fell back to unasked;
- the parent of a multi-process device run never imports jax, from
  either entry point, and a backend built before the process count was
  known is refused, not silently dropped;
- a failed read of the children's counters still stops the children.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.client import InformerFactory
from kubernetes_tpu.scheduler import Scheduler
from kubernetes_tpu.store import install_core_validation, new_cluster_store
from test_resilience import _ExplodingBackend, wait_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True, text=True,
        timeout=120, env={**os.environ, **env})


class TestChipSmokeRefusal:
    def test_refuses_on_cpu_and_does_no_work(self):
        p = _run_py(["chip_smoke.py"], JAX_PLATFORMS="cpu")
        assert p.returncode not in (0, 1)
        assert "refusing to run" in p.stderr
        assert p.stdout == ""  # no first line, no phase, no result

    def test_last_line_is_ok_and_device_only(self):
        """The driver's check reads the last stdout line and refuses any
        key beyond `ok` and `device` {platform, kind, count}; the rest of
        the result goes on the line before."""
        import chip_smoke
        line = chip_smoke.verdict_line({
            "ok": True, "failures": [], "wall_seconds": 74.0,
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": 1}})
        assert "\n" not in line
        assert json.loads(line) == {"ok": True, "device": {
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


class TestCompileCachePlacement:
    def test_external_directory_is_left_to_jax(self, monkeypatch):
        import jax
        from kubernetes_tpu.utils import compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append(k))
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_persistent_cache_min_compile_time_secs" in updates

    def test_fixed_in_checkout_path_off_the_cpu(self, monkeypatch):
        from kubernetes_tpu.utils import compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        first = compile_cache.compile_cache_dir()
        assert first == compile_cache.compile_cache_dir()
        assert first == os.path.join(REPO, ".jax_cache")

    def test_no_cache_when_the_cpu_was_asked_for(self, monkeypatch):
        from kubernetes_tpu.utils import compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert compile_cache.enable_compile_cache() is None


class TestDeviceChoice:
    @pytest.mark.parametrize("platforms", [None, "", "tpu,cpu"])
    def test_unasked_cpu_is_refused(self, monkeypatch, platforms):
        """jax.default_backend() is cpu in this process; unless
        JAX_PLATFORMS asked for it, that is JAX having dropped to the
        CPU by itself, and no backend may be built on it."""
        from kubernetes_tpu.ops import TPUBackend
        if platforms is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", platforms)
        with pytest.raises(RuntimeError, match="fell back to the CPU"):
            TPUBackend()


class TestDeviceLossIsVisible:
    def test_exploding_backend_fails_the_entry_point(
            self, tmp_path, monkeypatch, capsys):
        """perf.scheduler_perf --backend tpu with a backend that raises:
        every pod still binds (the circuit breaker is product
        behaviour), the JSON says the device was lost, exit code 1."""
        from kubernetes_tpu import ops
        from kubernetes_tpu.perf import scheduler_perf
        cfg = tmp_path / "w.yaml"
        cfg.write_text(
            "- name: Basic\n"
            "  workloadTemplate:\n"
            "  - {opcode: createNodes, count: 3}\n"
            "  - {opcode: createPods, count: 12, collectMetrics: true}\n"
            "  - {opcode: barrier}\n")
        monkeypatch.setattr(
            ops, "TPUBackend", lambda max_batch=None: _ExplodingBackend(99))
        rc = scheduler_perf.main([str(cfg), "--backend", "tpu"])
        out, err = capsys.readouterr()
        detail = json.loads(out)["Basic/default"]
        assert detail["scheduled_total"] == 12
        assert detail["backend_fallback_total"] >= 1
        assert scheduler_perf.device_run_failures(detail)
        assert rc == 1 and "the device was lost" in err

    def test_healthy_detail_has_no_failures(self):
        from kubernetes_tpu.perf.scheduler_perf import (
            WorkloadResult,
            device_run_failures,
        )
        r = WorkloadResult()
        r.backend_attached = True
        assert device_run_failures(r.as_dict()) == []
        r.backend_attached = False                       # circuit opened
        assert device_run_failures(r.as_dict())
        # Asked for the device and nobody ever attached one: the host
        # path scheduled the whole run.
        r.backend_attached = None
        assert "backend_attached=null" in device_run_failures(
            r.as_dict())[0]

    def test_exploding_fast_path_is_counted(self, monkeypatch):
        """A fast-path solve that raises reroutes the pod (it still
        binds) — and now leaves a count behind."""
        from kubernetes_tpu.ops import TPUBackend
        from kubernetes_tpu.serving.fastpath import SinglePodFastPath

        def boom(self, *a, **kw):
            raise RuntimeError("device lost (injected)")
        monkeypatch.setattr(SinglePodFastPath, "try_schedule", boom)
        monkeypatch.setattr(SinglePodFastPath, "warm", boom)

        async def body():
            store = new_cluster_store()
            install_core_validation(store)
            for i in range(3):
                await store.create("nodes", make_node(f"n{i}"))
            sched = Scheduler(store, seed=3,
                              backend=TPUBackend(max_batch=8))
            factory = InformerFactory(store)
            await sched.setup_informers(factory)
            factory.start()
            await factory.wait_for_sync()
            task = asyncio.ensure_future(sched.run(batch_size=4))
            await store.create("pods", make_pod(
                "lone", requests={"cpu": "100m"}))

            async def bound():
                pod = await store.get("pods", "default/lone")
                return bool(pod["spec"].get("nodeName"))
            assert await wait_for(bound, timeout=10.0)
            m = sched.metrics
            assert m.serving_fast_path_failures.value() >= 2  # warm + solve
            # ...and the lone pod rode the batch path as a batch of
            # one: a scheduler with a backend places no pod plugin by
            # plugin because it was popped alone.
            assert m.backend_degradations.value(kind="lone_batch") == 1
            assert m.backend_degradations.value(kind="host_path") == 0
            await sched.stop()
            task.cancel()
            factory.stop()
            store.stop()
        asyncio.run(body())


class TestOneProcessPerChip:
    def test_parent_of_a_multiprocess_run_stays_off_jax(self):
        """With --processes >= 2 the chip is the leader replica's: the
        parent hands over a backend SPEC and must not even import jax."""
        code = (
            "import sys, bench\n"
            "a = bench.build_parser().parse_args("
            "['--preset', 'smoke', '--processes', '2'])\n"
            "n, w, m, shards, boundary, batch = bench.prepare(a)\n"
            "r = bench.make_runner(a, shards, boundary, batch)\n"
            "assert r.backend is None, r.backend\n"
            "assert r.backend_spec == {'kind': 'tpu', 'chunk': None}\n"
            "assert batch == a.batch_size\n"
            "assert 'jax' not in sys.modules, 'parent imported jax'\n")
        p = _run_py(["-c", code], JAX_PLATFORMS="cpu")
        assert p.returncode == 0, p.stderr

    def test_scheduler_perf_main_resolves_processes_first(self):
        """`perf.scheduler_perf --backend tpu` under KTPU_PROCESSES=2:
        the process count is read before anything is built, so the
        suite gets a spec and this process stays off jax."""
        code = (
            "import sys\n"
            "from kubernetes_tpu.perf import scheduler_perf as sp\n"
            "seen = []\n"
            "def suite(config, backend_factory=None, **kw):\n"
            "    seen.append(backend_factory())\n"
            "    return {}\n"
            "sp.run_suite = suite\n"
            "sp.load_config = lambda path: []\n"
            "rc = sp.main(['w.yaml', '--backend', 'tpu'])\n"
            "assert seen == [(None, {'kind': 'tpu', 'chunk': None})], seen\n"
            "assert 'jax' not in sys.modules, 'parent imported jax'\n"
            "assert rc == 0, rc\n")
        p = _run_py(["-c", code], JAX_PLATFORMS="cpu", KTPU_PROCESSES="2")
        assert p.returncode == 0, p.stderr

    def test_a_backend_in_a_multiprocess_run_is_refused(self, monkeypatch):
        """A parent that built a backend before it knew the process
        count holds the chip, and no child would schedule through it:
        the run must not start (it used to finish on the host path,
        exit 0, under the device's name)."""
        from kubernetes_tpu.perf.scheduler_perf import PerfRunner
        template = [{"opcode": "createNodes", "count": 1}]
        with pytest.raises(ValueError, match="backend_spec, not a backend"):
            asyncio.run(PerfRunner(backend=object(), processes=2).run(
                template, {}))
        monkeypatch.setenv("KTPU_PROCESSES", "2")
        with pytest.raises(ValueError, match="backend_spec, not a backend"):
            asyncio.run(PerfRunner(backend=object()).run(template, {}))

    @pytest.mark.parametrize("template,primary", [
        ([], KeyError),                                  # run completed
        ([{"opcode": "noSuchOpcode"}], ValueError),      # run already failing
    ])
    def test_failed_finalize_still_stops_the_children(
            self, template, primary):
        """The leader replica holds the chip: if reading the children's
        counters fails after the run, they are stopped before the error
        goes out (and an earlier failure stays the one that surfaces)."""
        from kubernetes_tpu.metrics.registry import SchedulerMetrics
        from kubernetes_tpu.perf.scheduler_perf import (
            PerfRunner,
            _SchedulerProxy,
        )
        stopped = []

        class FakeControlPlane:
            async def stop(self):
                stopped.append("cp")

        async def boom(result, backing):
            raise KeyError("provenance")

        async def body():
            backing = new_cluster_store()
            install_core_validation(backing)
            runner = PerfRunner(
                backend_spec={"kind": "tpu", "chunk": None}, processes=2)
            runner._finalize_multiproc = boom
            sched = _SchedulerProxy()
            factory = InformerFactory(backing)
            await sched.setup_informers(factory)
            with pytest.raises(primary):
                await runner._drive(
                    template, {}, 10.0, backing, backing,
                    SchedulerMetrics(), sched, factory, None, None,
                    FakeControlPlane())
            assert stopped == ["cp"]
        asyncio.run(body())
