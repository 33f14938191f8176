"""Does the system still start on the chip? `python chip_smoke.py`, no
arguments, one process, a few minutes; exit 0 and a last stdout line
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}`
— those keys and no others — only if every phase held. Everything else
it has to say is on the `chip_smoke: result {...}` line before it.

It refuses to run anywhere but on a TPU (and never sets JAX_PLATFORMS
itself), then drives the main path once through the entry points a user
calls — the same construction `python bench.py --preset 5k --serve`
takes (bench.prepare / make_runner / run_template):

1. **drain**: upstream scheduler_perf SchedulingBasic/5000Nodes_10000Pods
   — 5,000 nodes, 1,000 warm-up pods, 10,000 measured pods — client →
   KTPU wire apiserver → informer → queue → serving tier / TPUBackend
   fused solve → bind, flagless routing;
2. **trickle**: 20 s of open-loop single-pod arrivals at 250/s on a
   fresh 5,000-node cluster, so the serving fast path (solver.solve_one
   on resident planes) runs on the device too. Not shorter: the first
   multi-pod dispatch compiles a small greedy chunk program inside the
   window (8 s cold on the v5e, PR 21) and stalls the loop meanwhile;
   the window has to outlast that and the backlog it leaves;
3. **differential**: outside those runs, one direct TPUBackend.assign of
   a seeded 300-pod batch on a seeded heterogeneous 5,000-node snapshot
   under each solve route the router can pick on this platform (greedy
   wave scan, Sinkhorn optimal), compared with the
   plugin-by-plugin host path: feasibility masks equal exactly, every
   placement feasible with no node over capacity. Whether assignments
   EQUAL the host oracle's is reported, not gated: f32 score ties may
   round differently on the chip.

What it prints besides pass/fail is information, not metrics (transfer
probe, donation, compile seconds and counts before/inside the measured
windows, persistent-cache hits and misses, peak device bytes, wall):
the benchmark is another PR's. To rehearse the phases on the CPU at a
toy size: `JAX_PLATFORMS=cpu python -c "import chip_smoke;
chip_smoke.run('smoke', trickle_s=2.0, diff_nodes=200, diff_pods=48)"`.
"""

from __future__ import annotations

import json
import random
import sys
import time

TRICKLE_RATE = 250.0


class _CompileLog:
    """JAX's own compile and persistent-cache events (jax.monitoring),
    timestamped so they can be placed inside or outside a window."""

    #: Python-side cost of a new program — paid even on a cache hit.
    _TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        from jax import monitoring
        #: (time.monotonic() at the end, seconds, jitted function's name)
        self.compiles: list[tuple[float, float, str]] = []
        self.trace_lower: list[tuple[float, float]] = []
        self._seen_until = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(
                (time.monotonic(), secs, kw.get("fun_name", "?")))
        elif name in self._TRACE_LOWER:
            self.trace_lower.append((time.monotonic(), secs))

    def _event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def window(self, start: float, seconds: float) -> dict:
        """Compilations that ended before / inside [start, start+seconds]
        since the previous call's window closed."""
        end = start + seconds
        lo, self._seen_until = self._seen_until, end
        before = [s for t, s, _ in self.compiles if lo < t < start]
        inside = [(s, f) for t, s, f in self.compiles if start <= t <= end]
        return {"compiles_before": len(before),
                "compile_seconds_before": round(sum(before), 3),
                "compiles_inside_window": len(inside),
                "compile_seconds_inside_window": round(
                    sum(s for s, _ in inside), 3),
                "trace_lower_seconds_inside_window": round(sum(
                    s for t, s in self.trace_lower if start <= t <= end), 3),
                "compiled_inside_window": sorted({f for _, f in inside})}


def _device_arrays(obj, path: str, seen: set):
    """Every jax.Array reachable from an object's attributes through
    dicts, lists and tuples: (path, array)."""
    import jax
    if id(obj) in seen:
        return
    seen.add(id(obj))
    if isinstance(obj, jax.Array):
        yield path, obj
    elif isinstance(obj, dict):
        for k, v in obj.items():
            yield from _device_arrays(v, f"{path}[{k!r}]", seen)
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            yield from _device_arrays(v, f"{path}[{i}]", seen)


def _placement(backend) -> dict:
    """Where the fused program's inputs live after a run: every device
    array the backend (and its resident planes) holds must be on the
    accelerator, and with several devices the node-axis arrays must be
    sharded over ALL of them — "everything on device 0" fails here."""
    import jax
    devices = jax.devices()
    seen: set = set()
    arrays = list(_device_arrays(vars(backend), "backend", seen))
    if backend.resident is not None:
        arrays += list(_device_arrays(
            vars(backend.resident), "resident", seen))
    off = [p for p, a in arrays
           if any(d.platform != devices[0].platform for d in a.devices())]
    node_axis = {f"_dev_static[{k!r}]": backend._dev_static[k]
                 for k in ("alloc_q", "alloc_pods", "taint_f", "taint_p")}
    node_axis["_dev_used"] = backend._dev_used  # None: no batch assign ran
    if backend.resident is not None:
        node_axis["resident._dev"] = backend.resident._dev
    unsharded = [k for k, a in node_axis.items()
                 if a is not None and a.sharding.device_set != set(devices)]
    # (the CPU backend of a rehearsal reports no memory statistics)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices]
    return {"device_arrays": len(arrays), "off_device": off,
            "node_axis_not_on_all_devices": unsharded,
            "bytes_in_use_per_device": in_use,
            "ok": bool(arrays) and not off and not unsharded
                  and (len(devices) == 1 or all(in_use))}


def _run_facts(detail: dict, expect_scheduled: int) -> tuple[dict, list]:
    """The gated facts of one harness run, and what failed."""
    from kubernetes_tpu.perf.scheduler_perf import device_run_failures
    keys = ("scheduled_total", "unschedulable_total", "host_path_pods",
            "host_fallback_pods", "backend_fallback_total",
            "fast_path_failures_total", "backend_attached",
            "solver_solve_chunks", "serving_fast_path_pods_total",
            "solver_optimal_solves_total",
            "solver_wave_commits_total", "solver_wave_replays_total",
            "resident_plane_refreshes_total")
    facts = {k: detail[k] for k in keys}
    bad = device_run_failures(detail)
    if detail["scheduled_total"] != expect_scheduled:
        bad.append(f"scheduled_total={detail['scheduled_total']} "
                   f"!= {expect_scheduled}")
    # Every bound pod was placed by a device solve: none through the
    # plugin-by-plugin host path, none on a per-pod host plugin row.
    for k in ("unschedulable_total", "host_path_pods",
              "host_fallback_pods"):
        if detail[k]:
            bad.append(f"{k}={detail[k]}")
    return facts, bad


# -- phase 3: direct assign vs the plugin-by-plugin host path --------------

_SKUS = [("4", "16Gi", "110"), ("8", "32Gi", "110"), ("16", "64Gi", "110"),
         ("32", "128Gi", "64"), ("2", "8Gi", "16")]
_TAINTS = [
    {"key": "dedicated", "value": "infra", "effect": "NoSchedule"},
    {"key": "accel", "value": "true", "effect": "NoSchedule"},
    {"key": "flaky", "value": "", "effect": "PreferNoSchedule"},
]
_TOLERATE = [
    {"key": "dedicated", "operator": "Equal", "value": "infra",
     "effect": "NoSchedule"},
    {"key": "accel", "operator": "Exists"},
    {"key": "flaky", "operator": "Exists"},
]
#: pod templates: (requests, toleration indexes, nodeSelector)
_TEMPLATES = [
    ({"cpu": "250m", "memory": "512Mi"}, (), None),
    ({"cpu": "1", "memory": "2Gi"}, (2,), None),
    ({"cpu": "2", "memory": "8Gi"}, (0,), None),
    ({"cpu": "500m", "memory": "1Gi"}, (0, 1, 2), {"zone": "b"}),
    ({"cpu": "4", "memory": "4Gi"}, (1,), None),
    ({"cpu": "100m", "memory": "12Gi"}, (), {"zone": "a"}),
]


def _seeded_cluster(seed: int, n_nodes: int):
    from kubernetes_tpu.api.types import make_node, make_pod
    from kubernetes_tpu.scheduler.cache import SchedulerCache
    from kubernetes_tpu.scheduler.types import PodInfo
    rng = random.Random(seed)
    cache = SchedulerCache()
    for i in range(n_nodes):
        cpu, mem, pods = rng.choice(_SKUS)
        taints = [t for t in _TAINTS if rng.random() < 0.15]
        cache.add_node(make_node(
            f"n{i}", allocatable={"cpu": cpu, "memory": mem, "pods": pods},
            labels={"zone": rng.choice("abc")}, taints=taints or None))
        # Up to three residents of at most a quarter node each: partly
        # used, never over-committed.
        for j in range(rng.randrange(4)):
            cache.add_pod(PodInfo(make_pod(
                f"resident-{i}-{j}", node_name=f"n{i}",
                requests={
                    "cpu": f"{rng.randrange(50, int(cpu) * 250)}m",
                    "memory": f"{rng.randrange(64, int(mem[:-2]) * 256)}Mi"},
                tolerations=_TOLERATE)))
    return cache.update_snapshot()


def _seeded_pods(seed: int, n: int) -> tuple[list, list[int]]:
    from kubernetes_tpu.api.types import make_pod
    from kubernetes_tpu.scheduler.types import PodInfo
    rng = random.Random(seed)
    pods, tmpl = [], []
    for i in range(n):
        t = rng.randrange(len(_TEMPLATES))
        req, tols, sel = _TEMPLATES[t]
        pods.append(PodInfo(make_pod(
            f"pend-{i}", requests=dict(req), uid=f"uid-{i}",
            tolerations=[_TOLERATE[k] for k in tols] or None,
            node_selector=dict(sel) if sel else None)))
        tmpl.append(t)
    return pods, tmpl


def _host_feasible(fwk, pi, snapshot):
    import numpy as np
    from kubernetes_tpu.scheduler.framework import CycleState
    state = CycleState()
    fwk.run_pre_filter(state, pi, snapshot)
    return np.fromiter(
        (fwk.run_filters(state, pi, ni).is_success()
         for ni in snapshot.nodes), dtype=np.bool_, count=len(snapshot.nodes))


def _host_oracle(fwk, pods, snapshot) -> dict:
    """The plugin-by-plugin scheduler, one pod at a time in queue order
    against a working copy: Filter, Score, highest score wins, lowest
    node index on ties (the device solve's tie rule; the host's own
    selectHost breaks ties at random)."""
    from kubernetes_tpu.scheduler.framework import CycleState
    working = [ni.clone() for ni in snapshot.nodes]
    out = {}
    for pi in pods:
        state = CycleState()
        fwk.run_pre_filter(state, pi, snapshot)
        feasible = [ni for ni in working
                    if fwk.run_filters(state, pi, ni).is_success()]
        if not feasible:
            out[pi.key] = None
            continue
        fwk.run_pre_score(state, pi, feasible)
        scores = fwk.run_scores(state, pi, feasible)
        best = max(feasible, key=lambda ni: scores[ni.name])  # first max
        best.add_pod(pi)
        out[pi.key] = best.name
    return out


def _assign_with_masks(backend, pods, snapshot, fwk):
    """TPUBackend.assign as users call it, keeping each pod's
    chunk-start feasibility row as the device held it
    (`TPUBackend.chunk_feasibility`). The batch must be one chunk: only
    the first chunk's rows are the snapshot's."""
    chunks: list[dict] = []
    assignments, _ = backend.assign(
        pods, snapshot, fwk,
        on_chunk=lambda run: chunks.append(backend.chunk_feasibility(run)))
    if len(chunks) != 1:
        raise RuntimeError(
            f"the differential batch ran as {len(chunks)} chunks, not 1")
    n_real = len(snapshot.nodes)
    return assignments, {k: row[:n_real] for k, row in chunks[0].items()}


def _differential(n_nodes: int, n_pods: int, seed: int = 21) -> dict:
    import numpy as np
    from kubernetes_tpu.ops import TPUBackend
    from kubernetes_tpu.scheduler.framework import CycleState, Framework
    from kubernetes_tpu.scheduler.plugins.noderesources import (
        insufficient_resources,
    )
    from kubernetes_tpu.scheduler.plugins.registry import (
        DEFAULT_SCORE_WEIGHTS,
        build_plugins,
    )
    from kubernetes_tpu.utils import flags

    snapshot = _seeded_cluster(seed, n_nodes)
    pods, tmpl = _seeded_pods(seed + 1, n_pods)
    fwk = Framework(build_plugins(), DEFAULT_SCORE_WEIGHTS)
    host_mask = {}
    for pi, t in zip(pods, tmpl):
        if t not in host_mask:
            host_mask[t] = _host_feasible(fwk, pi, snapshot)
    oracle = _host_oracle(fwk, pods, snapshot)
    out = {"nodes": n_nodes, "pods": n_pods, "seed": seed,
           "host_oracle_placed": sum(v is not None for v in oracle.values()),
           "routes": {}, "ok": True}
    for route in ("greedy", "optimal"):
        backend = TPUBackend()
        with flags.scoped_set("KTPU_SOLVE_MODE", route):
            assignments, masks = _assign_with_masks(
                backend, pods, snapshot, fwk)
        mask_mismatch = sum(
            int((masks[pi.key] != host_mask[t]).sum())
            for pi, t in zip(pods, tmpl))
        # Replay on a working copy with the host plugins: each placement
        # feasible where it landed, no node over capacity afterwards.
        working = {ni.name: ni.clone() for ni in snapshot.nodes}
        infeasible = 0
        for pi in pods:
            node = assignments.get(pi.key)
            if node is None:
                continue
            ni = working[node]
            state = CycleState()
            fwk.run_pre_filter(state, pi, snapshot)
            if insufficient_resources(pi, ni) \
                    or not fwk.run_filters(state, pi, ni).is_success():
                infeasible += 1
            ni.add_pod(pi)
        over = sum(1 for ni in working.values()
                   if len(ni.pods) > ni.allocatable.pods
                   or any(ni.requested.get(r) > a
                          for r, a in ni.allocatable.res.items()))
        placed = sum(v is not None for v in assignments.values())
        same = sum(assignments.get(pi.key) == oracle[pi.key] for pi in pods)
        ok = mask_mismatch == 0 and infeasible == 0 and over == 0 \
            and placed >= out["host_oracle_placed"]
        out["routes"][route] = {
            "ok": ok, "placed": placed,
            "mask_cells_differing_from_host": mask_mismatch,
            "infeasible_placements": infeasible,
            "nodes_over_capacity": over,
            # reported, not gated:
            "assignments_equal_host_oracle": same == len(pods),
            "assignments_matching_host_oracle": same,
        }
        out["ok"] = out["ok"] and ok
    return out


# -- the run ---------------------------------------------------------------

def run(preset: str = "5k", trickle_s: float = 20.0,
        diff_nodes: int = 5000, diff_pods: int = 300) -> dict:
    """All three phases on whatever device JAX has; returns the result
    object (`ok`, `device` and everything the result line prints)."""
    import jax

    import bench
    from kubernetes_tpu.ops.backend import solve_provenance
    from kubernetes_tpu.utils.compile_cache import enable_compile_cache

    t_start = time.monotonic()
    cache_dir = enable_compile_cache()
    prov = solve_provenance()
    dev = jax.devices()[0]
    print(f"chip_smoke: platform={dev.platform} device_kind="
          f"{dev.device_kind!r} devices={len(jax.devices())} "
          f"jax={prov['jax_version']} jaxlib={prov['jaxlib_version']} "
          f"libtpu={prov['libtpu_version']} compile_cache={cache_dir}",
          flush=True)
    log = _CompileLog()
    failures: list[str] = []

    args = bench.build_parser().parse_args(["--preset", preset])
    nodes, warmup, measured, shards, boundary, batch = bench.prepare(args)

    runner, res = bench.run_template(
        args, bench.DRAIN_TEMPLATE,
        {"nodes": nodes, "warmup": warmup, "measured": measured},
        shards, boundary, batch)
    drain, bad = _run_facts(res.as_dict(), warmup + measured)
    drain.update(log.window(res.measured_start, res.measured_seconds))
    drain["placement"] = _placement(runner.backend)
    drain["transfer_probe_ms"] = round(
        1e3 * runner.backend._tuner.probe(), 3)
    if not drain["solver_solve_chunks"]:
        bad.append("no batch solve ran in the measured window")
    if not drain["placement"]["ok"]:
        bad.append("placement")
    failures += [f"drain: {b}" for b in bad]
    print(f"chip_smoke: drain {json.dumps(drain)}", flush=True)

    # bench --serve's template plus a closing barrier: at a rate this
    # far under the knee every arrival must end up bound.
    runner, res = bench.run_template(
        args, bench.SERVE_TEMPLATE + [{"opcode": "barrier"}],
        {"nodes": nodes, "warmup": warmup, "rate": TRICKLE_RATE,
         "duration": trickle_s}, shards, boundary, batch)
    detail = res.as_dict()
    arrivals = detail["churn_arrivals_total"]
    trickle, bad = _run_facts(detail, warmup + arrivals)
    trickle.update(log.window(res.measured_start, res.measured_seconds))
    trickle.update({k: detail[k] for k in (
        "churn_arrivals_total", "churn_backlog_peak",
        "churn_backlog_final", "churn_late_arrivals", "churn_saturated")})
    trickle["placement"] = _placement(runner.backend)
    if not detail["serving_fast_path_pods_total"]:
        bad.append(f"no fast-path solve in the window ({arrivals} "
                   "arrivals)")
    if not trickle["placement"]["ok"]:
        bad.append("placement")
    failures += [f"trickle: {b}" for b in bad]
    print(f"chip_smoke: trickle {json.dumps(trickle)}", flush=True)

    diff = _differential(diff_nodes, diff_pods)
    if not diff["ok"]:
        failures.append("differential")
    print(f"chip_smoke: differential {json.dumps(diff)}", flush=True)

    devices = jax.devices()
    return {
        "ok": not failures,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
        "failures": failures,
        "versions": {k: prov[k] for k in (
            "jax_version", "jaxlib_version", "libtpu_version")},
        "compile_cache_dir": cache_dir,
        "carry_donation": prov["carry_donation"],
        "drain": drain, "trickle": trickle, "differential": diff,
        "compiles_total": len(log.compiles),
        "compile_seconds_total": round(
            sum(s for _, s, _ in log.compiles), 3),
        "trace_lower_seconds_total": round(
            sum(s for _, s in log.trace_lower), 3),
        "persistent_cache_hits": log.cache_hits,
        "persistent_cache_misses": log.cache_misses,
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices],
        "wall_seconds": round(time.monotonic() - t_start, 1),
    }


def verdict_line(result: dict) -> str:
    """The last stdout line, as the driver's check reads it: `ok` and the
    device as JAX reports it, exactly these keys."""
    dev = result["device"]
    return json.dumps({"ok": bool(result["ok"]), "device": {
        "platform": str(dev["platform"]), "kind": str(dev["kind"]),
        "count": int(dev["count"])}})


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: refusing to run: jax.devices()[0].platform is "
              f"{dev.platform!r}, not 'tpu' (JAX found no accelerator, or "
              "JAX_PLATFORMS keeps it off one). This script only proves "
              "the system on the chip; nothing was run.", file=sys.stderr)
        return 2
    result = run()
    print(f"chip_smoke: result {json.dumps(result)}")
    print(verdict_line(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
