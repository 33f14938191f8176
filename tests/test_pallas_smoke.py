"""Tier-1 guard for the fused Pallas wavefront kernel (small-N, fast).

Pins: (a) the KTPU_PALLAS policy table (pallas_kernel.resolve_mode) —
auto keeps the scan on every platform, off is the kill switch, on means
compile-or-fail, interpret is refused off the CPU — and every
structural gate (optimal mode, spread, shortlist, W<=1, working-set
ceiling) routes back to the scan with a labeled fallback reason;
(b) CPU default = the EXACT r20 scan call graph with both pallas
counters at zero (off-by-policy records neither solves nor fallbacks);
(c) KTPU_PALLAS=interpret activating the kernel end-to-end through
TPUBackend with identical assignments and solves counted; (d) the
shape gate counting reason="shape" when a chunk exceeds the kernel's
working-set ceiling. The heavyweight randomized differential parity
lives in tests/test_pallas_solver.py.
"""

import jax
import numpy as np
import pytest

from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops import pallas_kernel
from kubernetes_tpu.ops.backend import AdaptiveTuner, TPUBackend, \
    solve_provenance
from kubernetes_tpu.utils import flags

#: KTPU_PALLAS=interpret is refused off the CPU (the policy table), so
#: the tests that set it run only there.
interpret_only = pytest.mark.skipif(
    jax.default_backend() != "cpu",
    reason="KTPU_PALLAS=interpret is a CPU test mode")


class TestPallasPolicy:
    def test_auto_keeps_scan(self):
        """auto (the default) is off BY POLICY on every platform (the
        kernel does not lower for the TPU): the chunk keeps the scan
        with NO fallback count — the routing never wanted the kernel."""
        t = AdaptiveTuner()
        mode, fall = t.pallas_mode(8, 0, False, "greedy")
        assert mode == "off" and fall is None
        for platform in ("cpu", "tpu", "gpu"):
            assert pallas_kernel.resolve_mode("auto", platform) == "off"
            assert pallas_kernel.resolve_mode("off", platform) == "off"

    def test_never_interpret_off_the_cpu(self):
        """interpret is a CPU test mode: no flag value resolves to it on
        another platform — `on` means the real kernel, `interpret`
        itself is refused."""
        for platform in ("tpu", "gpu"):
            for flag in ("auto", "on", "off"):
                assert pallas_kernel.resolve_mode(flag, platform) \
                    != "interpret"
            with pytest.raises(ValueError, match="CPU test mode"):
                pallas_kernel.resolve_mode("interpret", platform)
            assert pallas_kernel.resolve_mode("on", platform) == "compiled"

    @interpret_only
    def test_kill_switch_and_force(self):
        t = AdaptiveTuner()
        with flags.scoped_set("KTPU_PALLAS", "off"):
            assert t.pallas_mode(8, 0, False, "greedy") == ("off", None)
        with flags.scoped_set("KTPU_PALLAS", "0"):  # boolean spelling
            assert t.pallas_mode(8, 0, False, "greedy") == ("off", None)
        with flags.scoped_set("KTPU_PALLAS", "interpret"):
            assert t.pallas_mode(8, 0, False, "greedy") == \
                ("interpret", None)
        with flags.scoped_set("KTPU_PALLAS", "on"):
            # "on" is the real kernel or nothing — never interpret.
            assert t.pallas_mode(8, 0, False, "greedy") == \
                ("compiled", None)

    @interpret_only
    def test_structural_gates_label_fallbacks(self):
        """The kernel fuses only the plain greedy wave branch; every
        other shape keeps the scan, labeled by why."""
        t = AdaptiveTuner()
        with flags.scoped_set("KTPU_PALLAS", "interpret"):
            assert t.pallas_mode(8, 0, False, "optimal") == \
                ("off", "optimal")
            assert t.pallas_mode(8, 0, True, "greedy") == \
                ("off", "spread")
            assert t.pallas_mode(8, 6, False, "greedy") == \
                ("off", "shortlist")
            assert t.pallas_mode(1, 0, False, "greedy") == \
                ("off", "wave_off")

    def test_shape_gate(self):
        """The working-set ceiling: per grid step the kernel holds the
        (C,N) planes + (W,N) evaluation + (N,R) carries resident."""
        assert pallas_kernel.unsupported_reason(128, 4, 2, 8) is None
        assert pallas_kernel.unsupported_reason(128, 4, 2, 1) == \
            "wave_off"
        big_n = pallas_kernel.MAX_STATE_BYTES  # bytes/row > 1 at any W
        assert pallas_kernel.unsupported_reason(big_n, 4, 2, 8) == "shape"


class TestBackendSmoke:
    def _cluster(self, n):
        from kubernetes_tpu.api.types import make_node
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        cache = SchedulerCache()
        for i in range(n):
            cache.add_node(make_node(
                f"pn{i}", allocatable={"cpu": "8", "memory": "32Gi",
                                       "pods": "110"}))
        return cache.update_snapshot()

    def _pods(self, n):
        from kubernetes_tpu.api.types import make_pod
        from kubernetes_tpu.scheduler.types import PodInfo
        return [PodInfo(make_pod(
            f"pk-{i}", requests={"cpu": "500m", "memory": "512Mi"},
            uid=f"pk-uid-{i}")) for i in range(n)]

    def test_cpu_default_is_scan_with_zero_counters(self):
        """Flagless on CPU: the scan solves every chunk and BOTH pallas
        counters stay zero — no kernel in disguise, no phantom
        fallbacks. KTPU_PALLAS=off produces the same call graph and the
        same assignments (the structural-degrade contract)."""
        from test_tpu_backend import default_fwk
        snap = self._cluster(100)
        pods = self._pods(24)
        fwk = default_fwk()
        b = TPUBackend(max_batch=16, mesh=None)
        b.metrics = SchedulerMetrics()
        auto, _ = b.assign(pods, snap, fwk)
        assert b.metrics.solver_pallas_solves.value() == 0
        assert sum(
            b.metrics.solver_pallas_fallbacks._values.values()) == 0
        prov = solve_provenance()
        assert prov["solve_kernel"] == "scan"
        assert prov["pallas_mode"] == "off"
        b2 = TPUBackend(max_batch=16, mesh=None)
        b2.metrics = SchedulerMetrics()
        with flags.scoped_set("KTPU_PALLAS", "off"):
            off, _ = b2.assign(pods, snap, fwk)
        assert off == auto
        assert b2.metrics.solver_pallas_solves.value() == 0

    @interpret_only
    def test_interpret_activates_with_identical_assignments(self):
        """KTPU_PALLAS=interpret routes wave chunks through the fused
        kernel end-to-end: assignments match the scan exactly and the
        solves counter records each kernel chunk."""
        from test_tpu_backend import default_fwk
        snap = self._cluster(100)
        pods = self._pods(24)
        fwk = default_fwk()
        base, _ = TPUBackend(max_batch=16, mesh=None).assign(
            pods, snap, fwk)
        b = TPUBackend(max_batch=16, mesh=None)
        b.metrics = SchedulerMetrics()
        with flags.scoped_set("KTPU_PALLAS", "interpret"):
            got, _ = b.assign(pods, snap, fwk)
            prov = solve_provenance()
        assert got == base
        assert b.metrics.solver_pallas_solves.value() > 0
        assert prov["solve_kernel"] == "pallas"
        assert prov["pallas_mode"] == "interpret"

    def test_on_raises_where_the_kernel_does_not_compile(self):
        """KTPU_PALLAS=on routes the chunk `compiled`; a backend that
        cannot lower the real kernel raises the compiler's own error
        from the fused program's compile — nothing catches it to fall
        through to the scan. Pallas has no compiled CPU lowering; on
        the v5e Mosaic refuses the block shapes (resolve_mode quotes
        it)."""
        from test_tpu_backend import default_fwk
        refusal = "[Oo]nly interpret mode" \
            if jax.default_backend() == "cpu" else "Pallas TPU lowering"
        b = TPUBackend(max_batch=16, mesh=None)
        with flags.scoped_set("KTPU_PALLAS", "on"), \
                flags.scoped_set("KTPU_SOLVE_MODE", "greedy"), \
                pytest.raises(ValueError, match=refusal):
            b.assign(self._pods(24), self._cluster(100), default_fwk())

    @interpret_only
    def test_shape_fallback_counted(self, monkeypatch):
        """A chunk above the working-set ceiling keeps the scan,
        counted under reason="shape", with identical assignments."""
        from test_tpu_backend import default_fwk
        snap = self._cluster(80)
        pods = self._pods(16)
        fwk = default_fwk()
        base, _ = TPUBackend(max_batch=16, mesh=None).assign(
            pods, snap, fwk)
        monkeypatch.setattr(pallas_kernel, "MAX_STATE_BYTES", 1)
        b = TPUBackend(max_batch=16, mesh=None)
        b.metrics = SchedulerMetrics()
        with flags.scoped_set("KTPU_PALLAS", "interpret"):
            got, _ = b.assign(pods, snap, fwk)
        assert got == base
        assert b.metrics.solver_pallas_solves.value() == 0
        assert b.metrics.solver_pallas_fallbacks.value(
            reason="shape") > 0
