"""Tier-1 guard for the class-dictionary device planes (small-N, fast).

Pins: (a) class planes ACTIVE by default — a template chunk ships ONE
class row, a mixed chunk a handful, and the plane-byte/prep metrics
flow; (b) the KTPU_CLASS_PAD=0 kill switch degrading structurally to
per-pod planes (C == P) with identical assignments; (c) the exception
list carrying single-column host rows (NodeName pins) without splitting
a class; (d) the KTPU_CLASS_PAD overflow fallback counting its pods.
The heavyweight randomized parity lives in tests/test_class_planes.py.
"""

import pytest

from kubernetes_tpu.api.types import make_node, make_pod
from kubernetes_tpu.metrics.registry import SchedulerMetrics
from kubernetes_tpu.ops.backend import (
    TPUBackend,
    _class_rows_bucket,
    class_pad,
)
from kubernetes_tpu.scheduler.types import PodInfo


def _uniform_cluster(n):
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    cache = SchedulerCache()
    for i in range(n):
        cache.add_node(make_node(
            f"n{i}", allocatable={"cpu": "8", "memory": "32Gi",
                                  "pods": "110"}))
    return cache.update_snapshot()


def _template_pods(n, cpu="500m"):
    return [PodInfo(make_pod(
        f"pend-{i}", requests={"cpu": cpu, "memory": "512Mi"},
        uid=f"uid-{i}")) for i in range(n)]


def _backend(chunk=16):
    b = TPUBackend(max_batch=chunk, mesh=None)
    b.metrics = SchedulerMetrics()
    return b


class TestClassPlaneKnobs:
    def test_default_cap_and_bucket(self, monkeypatch):
        monkeypatch.delenv("KTPU_CLASS_PAD", raising=False)
        assert class_pad() == 31
        monkeypatch.setenv("KTPU_CLASS_PAD", "7")
        assert class_pad() == 7
        monkeypatch.setenv("KTPU_CLASS_PAD", "0")
        assert class_pad() == 0
        # Plane rows: power-of-two buckets with the reserved empty row 0.
        assert _class_rows_bucket(0) == 2
        assert _class_rows_bucket(1) == 2
        assert _class_rows_bucket(2) == 4
        assert _class_rows_bucket(7) == 8
        assert _class_rows_bucket(31) == 32


class TestActiveByDefault:
    def test_template_chunk_ships_one_class(self, monkeypatch):
        monkeypatch.delenv("KTPU_CLASS_PAD", raising=False)
        from test_tpu_backend import default_fwk
        snap = _uniform_cluster(100)
        pods = _template_pods(35)  # partial last chunk: padding rides
        b = _backend(chunk=16)
        assignments, _ = b.assign(pods, snap, default_fwk())
        assert all(v is not None for v in assignments.values())
        m = b.metrics
        assert m.plane_classes.value() == 1
        assert m.class_split_fallbacks.value() == 0
        # Plane payloads were uploaded and host prep was timed.
        assert m.plane_bytes.value() > 0
        assert m.prep_duration.count() >= 3

    def test_kill_switch_degrades_to_per_pod(self, monkeypatch):
        from test_tpu_backend import default_fwk
        snap = _uniform_cluster(100)
        pods = _template_pods(32)
        fwk = default_fwk()
        monkeypatch.delenv("KTPU_CLASS_PAD", raising=False)
        on = _backend(chunk=16)
        a_on, _ = on.assign(pods, snap, fwk)
        monkeypatch.setenv("KTPU_CLASS_PAD", "0")
        off = _backend(chunk=16)
        a_off, _ = off.assign(pods, snap, fwk)
        assert a_on == a_off
        # Structural degrade: per-pod planes (C == chunk pad), counted
        # as plain plane classes, NOT as class-split fallbacks.
        assert off.metrics.plane_classes.value() == 16
        assert off.metrics.class_split_fallbacks.value() == 0
        assert on.metrics.plane_classes.value() == 1

    def test_exception_list_path(self, monkeypatch):
        """A NodeName pod rides the exception column: same class as its
        template (C == 1), lands exactly on the named node — exercised
        under the SHORTLIST regime so the pinned-pod bound-check
        fallback runs too (N=150 ≥ 4·(K+chunk))."""
        monkeypatch.delenv("KTPU_CLASS_PAD", raising=False)
        from test_tpu_backend import default_fwk
        snap = _uniform_cluster(150)
        pods = _template_pods(16)
        pinned = PodInfo(make_pod(
            "pinned", requests={"cpu": "500m", "memory": "512Mi"},
            node_name="n149", uid="uid-pin"))
        pods = pods[:8] + [pinned] + pods[8:]
        b = _backend(chunk=16)
        assignments, _ = b.assign(pods, snap, default_fwk())
        assert assignments[pinned.key] == "n149"
        assert all(v is not None for v in assignments.values())
        m = b.metrics
        assert m.plane_classes.value() == 1
        assert m.solver_shortlist_pods.value() == len(pods)

    def test_overflow_fallback_counts_pods(self, monkeypatch):
        monkeypatch.setenv("KTPU_CLASS_PAD", "2")
        from test_tpu_backend import default_fwk
        snap = _uniform_cluster(60)
        pods = []
        for i in range(12):  # 4 distinct request templates > pad 2
            pods.append(PodInfo(make_pod(
                f"pend-{i}",
                requests={"cpu": f"{(1 + i % 4) * 100}m",
                          "memory": "256Mi"}, uid=f"uid-{i}")))
        b = _backend(chunk=16)
        assignments, _ = b.assign(pods, snap, default_fwk())
        assert all(v is not None for v in assignments.values())
        assert b.metrics.class_split_fallbacks.value() == len(pods)
        assert b.metrics.plane_classes.value() == len(pods)
