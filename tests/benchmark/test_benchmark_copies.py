"""The benchmark's copies give, at this commit, what the program's
originals give on a seeded input — and the yardstick's own arithmetic
(quantities, peaks, the work model, the trace reduction) holds."""

import inspect
import random
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.lib import (  # noqa: E402
    arrivals,
    fragmentation,
    peaks,
    percentiles,
    trace_reduce,
    work_model,
)
from benchmark.lib.reference import ClusterModel  # noqa: E402

TRACE = ROOT / "benchmark" / "lib" / "testdata" / "tiny.xplane.pb"


@pytest.mark.parametrize("rate,seed,duration", [
    (250.0, 17, 40.0), (1000.0, 3, 5.0), (7.5, 2**31 + 11, 60.0)])
def test_poisson_copy_equals_the_programs(rate, seed, duration):
    from kubernetes_tpu.perf.churn.arrivals import PoissonArrivals
    from kubernetes_tpu.perf.churn.arrivals import stable_seed as theirs
    assert arrivals.stable_seed("poisson", seed, rate, duration) == \
        theirs("poisson", seed, rate, duration)
    assert arrivals.poisson_timeline(rate, seed, duration) == \
        PoissonArrivals(rate, seed).timeline(duration)


@pytest.mark.parametrize("n", [1, 2, 19, 20, 21, 1000, 4097])
def test_percentile_copy_equals_the_programs_recorder(n):
    from kubernetes_tpu.metrics.registry import WindowedLatencyRecorder
    rng = random.Random(n)
    values = [rng.expovariate(40.0) for _ in range(n)]
    rec = WindowedLatencyRecorder()
    mark = rec.mark()
    for v in values:
        rec.observe(v)
    qs = (0.5, 0.9, 0.95, 0.99, 0.999)
    assert percentiles.percentiles(values, qs) == \
        rec.percentiles_since(mark, qs)


def test_percentiles_of_nothing_and_of_the_unbound():
    assert np.isnan(percentiles.percentile([], 0.5))
    assert percentiles.percentile([1.0, 2.0, float("inf")], 0.95) == \
        float("inf")


@pytest.mark.parametrize("text,milli", [
    ("100m", 100), ("8", 8000), ("250Mi", 250 * 2**20 * 1000),
    ("32Gi", 32 * 2**30 * 1000), ("1.5", 1500), (2, 2000), ("1k", 10**6)])
def test_quantity_copy_equals_the_programs(text, milli):
    from kubernetes_tpu.api.resource import parse_quantity
    assert fragmentation.milli(text) == milli == parse_quantity(text)


def test_quantity_refuses_what_is_none():
    for bad in ("", None, True, "ten", "5Qi"):
        with pytest.raises(ValueError):
            fragmentation.milli(bad)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fragmentation_copy_equals_the_programs(seed):
    """Seeded bindings on a small cluster: the program's figure from its
    scheduler cache, the copy's from the bindings alone."""
    from kubernetes_tpu.api.types import make_node, make_pod
    from kubernetes_tpu.perf.scheduler_perf import PerfRunner
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    from kubernetes_tpu.scheduler.types import PodInfo
    config = {"nodes": 40,
              "node_template": {"allocatable": {
                  "cpu": "8", "memory": "32Gi", "pods": "110"}},
              "pod_template": {"requests": {
                  "cpu": "100m", "memory": "250Mi"}}}
    rng = random.Random(seed)
    placed = [rng.randrange(25) for _ in range(300)]   # 15 nodes stay empty
    cache = SchedulerCache()
    for i in range(config["nodes"]):
        cache.add_node(make_node(f"node-{i}", **config["node_template"]))
    for j, i in enumerate(placed):
        cache.add_pod(PodInfo(make_pod(
            f"p{j}", node_name=f"node-{i}", **config["pod_template"])))

    class _Sched:
        pass
    sched = _Sched()
    sched.cache = cache
    theirs = PerfRunner._fragmentation_occupied(sched)
    model = ClusterModel(config)
    ours = model.fragmentation(np.array(placed), model.request_rows(
        [config["pod_template"]] * len(placed)))
    assert ours == pytest.approx(theirs, rel=1e-12)
    assert 0.0 < ours < 100.0


def test_fragmentation_of_an_empty_cluster_is_nought():
    z = np.zeros((3, 2))
    assert fragmentation.fragmentation_occupied_pct(
        z + 8, z, np.zeros(3, dtype=int)) == 0.0


def test_compile_log_copy_sees_what_the_programs_sees():
    import jax
    import jax.numpy as jnp

    import chip_smoke
    from benchmark.lib.compile_log import CompileLog
    theirs, ours = chip_smoke._CompileLog(), CompileLog()
    t0 = time.monotonic()

    @jax.jit
    def _bench_copy_probe(x):
        return jnp.cumsum(x * 3.0 + 1.0)
    _bench_copy_probe(jnp.arange(37.0)).block_until_ready()
    t1 = time.monotonic()
    assert [(s, f) for _, s, f in ours.compiles] == \
        [(s, f) for _, s, f in theirs.compiles]
    assert [s for _, s in ours.trace_lower] == \
        [s for _, s in theirs.trace_lower]
    inside = ours.window(t0, t1)
    assert inside["compiles"] >= 1 and inside["trace_lower_seconds"] > 0
    assert any("_bench_copy_probe" in f for f in inside["compiled"])
    assert ours.window(t1 + 1.0, t1 + 2.0)["compiles"] == 0
    assert t0 <= ours.last_event() <= t1


# -- the peaks table and the work model ------------------------------------

def test_peaks_are_keyed_by_device_kind_and_unknown_is_an_error():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    assert v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_work_model_is_a_function_of_the_problem_only():
    assert list(inspect.signature(work_model.solve_work).parameters) == [
        "nodes", "resources", "pods", "classes", "chunks"]
    ops, bytes_ = work_model.solve_work(5000, 3, 10000, 1, 10)
    # more nodes, pods, classes or chunks never read as less work
    for kw in ({"nodes": 50000}, {"pods": 20000}, {"classes": 4},
               {"chunks": 20}):
        base = dict(nodes=5000, resources=3, pods=10000, classes=1, chunks=10)
        o2, b2 = work_model.solve_work(**{**base, **kw})
        assert o2 >= ops and b2 >= bytes_ and (o2, b2) != (ops, bytes_)
    least, bound_by = work_model.least_seconds(
        ops, bytes_, peaks.peaks("TPU v5 lite"))
    assert bound_by == "bytes" and least == bytes_ / 819e9


def test_roofline_reads_the_same_work_whatever_program_shapes_say():
    """Two traces of the same problem — one program run as 10 wide
    executions, one with the same 10 chunks under another program's
    shapes and names — give the reader the same numerator."""
    sys.path.insert(0, str(ROOT / "benchmark" / "readers"))
    from benchmark.lib.manifest import Manifest
    read = Manifest().reader("roofline")

    class Ctx:
        traced_pods = 10000
        device_kind = "TPU v5 lite"
        model = ClusterModel({"nodes": 5000, "node_template": {
            "allocatable": {"cpu": "8", "memory": "32Gi", "pods": "110"}}})
    a, b = Ctx(), Ctx()
    a.trace = {"programs": {"jit__mask_solve_update":
                            {"seconds": 0.2, "runs": 10}}}
    b.trace = {"programs": {"jit__mask_solve_update":
                            {"seconds": 0.1, "runs": 10},
                            "jit_shortlist_w64_k2048":
                            {"seconds": 5.0, "runs": 400}}}
    ra = read(a, program="jit__mask_solve_update")
    rb = read(b, program="jit__mask_solve_update")
    assert ra * 0.2 == pytest.approx(rb * 0.1)      # same least time
    assert 0 < ra < 100
    Ctx.trace = None
    assert read(Ctx(), program="jit__mask_solve_update") is None


# -- the trace reduction, on a trace recorded on the v5e --------------------

@pytest.fixture(scope="module")
def trace():
    return trace_reduce.Trace.from_file(str(TRACE))


def test_recorded_trace_has_one_chip_three_executions(trace):
    assert set(trace.ops) == {0} and set(trace.modules) == {0}
    assert len(trace.modules[0]) == 3
    assert {trace_reduce.program_name(n) for n, _, _ in trace.modules[0]} \
        == {"jit_probe_step"}
    assert all(" = " not in n for n, _, _ in trace.ops[0])
    assert trace.marker("bench.marker") is not None
    assert trace.marker("no.such.annotation") is None


def test_recorded_trace_reduces_to_busy_idle_programs_and_gaps(trace):
    lo = trace.marker("bench.marker")
    hi = max(e for _, _, e in trace.host)
    steps = [(n, s, e) for n, s, e in trace.host if n == "bench.step"]
    r = trace_reduce.reduce(trace, steps, window=(lo, hi))
    assert r["chips"] == 1 and r["window_s"] == pytest.approx(hi - lo)
    assert 0 < r["busy_s"] < r["window_s"]
    prog = r["programs"]["jit_probe_step"]
    assert prog["runs"] == 3
    # the operations ran inside their executions
    assert r["busy_s"] <= prog["seconds"] * 1.001
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "jit_probe_step/while"
    assert all(n.startswith("jit_probe_step/") for n in names)
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"bench.step", "host.other"}
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)


def test_a_window_with_no_device_operation_reduces_to_nothing(trace):
    assert trace_reduce.reduce(trace, [], window=(0.0, 0.001)) is None
    assert trace_reduce.reduce(trace_reduce.Trace(), []) is None


def test_a_chip_the_cell_did_not_use_is_not_averaged_in(trace):
    """A one-chip cell on a four-chip host: the other devices have
    planes, and nothing on them."""
    lo = trace.marker("bench.marker")
    hi = max(e for _, _, e in trace.host)
    alone = trace_reduce.reduce(trace, [], window=(lo, hi))
    for chip in (1, 2, 3):
        trace.ops[chip], trace.modules[chip] = [], []
    try:
        beside = trace_reduce.reduce(trace, [], window=(lo, hi))
    finally:
        for chip in (1, 2, 3):
            del trace.ops[chip], trace.modules[chip]
    assert beside["chips"] == 1 and beside["busy_s"] == alone["busy_s"]
    assert beside["programs"] == alone["programs"]


def test_gaps_go_to_the_innermost_covering_span():
    gaps = [(0.0, 10.0), (20.0, 30.0)]
    spans = [("outer", 0.0, 25.0), ("inner", 2.0, 4.0),
             ("late", 22.0, 40.0)]
    out = trace_reduce.attribute_gaps(gaps, spans)
    assert out == pytest.approx(
        {"outer": 8.0 + 2.0, "inner": 2.0, "late": 8.0})
    assert trace_reduce.attribute_gaps(gaps, []) == {"host.other": 20.0}
    assert trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6)])[0] == 4
