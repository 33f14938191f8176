"""Mesh-sharded path on the 8-device virtual CPU mesh (conftest forces
xla_force_host_platform_device_count=8): the driver entry points and the
backend on a (slice × nodes) mesh. The differentials against one device
are tests/test_mesh_parity_plain.py and tests/test_mesh_parity_spread.py."""

import numpy as np
import pytest

import jax


class TestGraftEntry:
    def test_entry_compiles(self):
        import sys
        sys.path.insert(0, "/root/repo")
        import __graft_entry__ as ge
        fn, args = ge.entry()
        out = np.asarray(jax.jit(fn)(*args))
        assert out.shape == (16,)

    def test_dryrun_multichip(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        import __graft_entry__ as ge
        ge.dryrun_multichip(8)


class TestMultisliceSolver:
    """Config #5: (slice × nodes) mesh."""

    def test_backend_on_multislice_mesh(self):
        """TPUBackend accepts a (slice × nodes) mesh: the fused program
        auto-partitions the node dimension over both axes."""
        from kubernetes_tpu.api.types import make_node, make_pod
        from kubernetes_tpu.ops import TPUBackend
        from kubernetes_tpu.parallel import build_multislice_mesh
        from kubernetes_tpu.scheduler.cache import SchedulerCache
        from kubernetes_tpu.scheduler.framework import Framework
        from kubernetes_tpu.scheduler.plugins.registry import (
            DEFAULT_SCORE_WEIGHTS, build_plugins)
        from kubernetes_tpu.scheduler.types import PodInfo
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices")
        cache = SchedulerCache()
        for i in range(16):
            cache.add_node(make_node(f"n{i}"))
        snapshot = cache.update_snapshot()
        pods = [PodInfo(make_pod(f"p{i}", requests={"cpu": "500m"},
                                 uid=f"u{i}")) for i in range(12)]
        fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
        backend = TPUBackend(max_batch=16,
                             mesh=build_multislice_mesh(2, 4))
        assignments, _ = backend.assign(pods, snapshot, fwk)
        assert all(assignments[p.key] for p in pods)
