"""One run of one cell: bring JAX up, build the control plane, stage,
warm, measure for `seconds`, read, tear down, compare with the plain
reference, print.

Set-up is everything from process start to the window's first create.
The run with `--trace 0` reports the end-to-end metrics, taken on the
client's side of the wire with the profiler and the program's tracer
off; the run with `--trace 1` profiles the first `trace_seconds` of the
window, turns `utils.tracing.DEFAULT_TRACER` on for that stretch, and
reports the per-layer metrics.
"""

from __future__ import annotations

import asyncio
import gc
import glob
import json
import math
import os
import random
import shutil
import sys
import time
from collections import deque

from benchmark.lib import counters, trace_reduce
from benchmark.lib.arrivals import stable_seed
from benchmark.lib.gc_log import GcLog
from benchmark.lib.manifest import Manifest
from benchmark.lib.reference import is_correct
from benchmark.lib.traffic import Generator


def host_speed_index() -> float:
    """Seconds one fixed piece of pure Python takes on this host right
    now (dict, str and int work, the kind the control plane does). It
    is printed beside each run's rates so that a slow run can be told
    from a slow host; no metric is corrected by it."""
    t0 = time.perf_counter()
    d: dict = {}
    for i in range(200_000):
        d[f"pod-{i % 5000}"] = d.get(f"pod-{(i * 7) % 5000}", 0) + i
    return time.perf_counter() - t0


class Refused(Exception):
    """The run may not go on (no chip, too few chips, a backend on
    another number of chips than the cell's)."""


class Context:
    """What a per-layer reader may look at."""

    def __init__(self):
        self.window = None            # traffic.Window
        self.before: dict = {}        # counters at the window's start
        self.after: dict = {}         # ... and at its end
        self.metrics = None           # the program's SchedulerMetrics
        self.marks: dict = {}         # reader-kept marks taken at the start
        self.compile_log = None
        self.gc_log = None
        self.trace = None             # trace_reduce.reduce(...) or None
        self.traced_pods = 0          # pods bound inside the traced window
        self.config: dict = {}
        self.model = None             # the deployment (lib/reference.py)
        self.device_kind = ""
        self.memory_stats: list = []  # per device, {} where not reported

    @property
    def pods_bound(self) -> int:
        return len(self.window.created) - self.window.unbound


def device_facts(chips: int, require_chip: bool) -> dict:
    """The devices this cell runs on: exactly `chips` of them, whatever
    else the machine holds (`count` is the chips used)."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if len(devices) < chips or (require_chip and dev.platform != "tpu"):
        raise Refused(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {dev.platform!r} ({dev.device_kind!r}). "
            "Nothing was run.")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def _memory_stats() -> list[dict]:
    import jax
    return [d.memory_stats() or {} for d in jax.devices()]


class _Profile:
    """The profiler and the program's tracer, on for one stretch."""

    def __init__(self, directory: str):
        self.directory = directory
        self.mono_at_mark = 0.0
        self.stopped = asyncio.Event()
        self.tracer_spans: list = []

    def start(self) -> None:
        import jax
        from jax.profiler import ProfileOptions
        from kubernetes_tpu.utils.tracing import DEFAULT_TRACER
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        DEFAULT_TRACER.spans = deque(maxlen=1 << 21)
        DEFAULT_TRACER.enabled = True
        self.mono_at_mark = time.monotonic()
        with jax.profiler.TraceAnnotation(trace_reduce.MARK_START):
            pass

    async def stop_after(self, seconds: float) -> None:
        await asyncio.sleep(seconds)
        await self.stop()

    async def stop(self) -> None:
        import jax
        from kubernetes_tpu.utils.tracing import DEFAULT_TRACER
        if self.stopped.is_set():
            return
        self.stopped.set()
        DEFAULT_TRACER.enabled = False
        self.tracer_spans = [(s.name, s.start, s.end)
                             for s in DEFAULT_TRACER.spans
                             if s.end is not None]
        DEFAULT_TRACER.spans.clear()
        with jax.profiler.TraceAnnotation(trace_reduce.MARK_END):
            pass
        self.mono_at_end = time.monotonic()
        # writing the trace takes a while; off the loop, so the window's
        # traffic goes on meanwhile
        await asyncio.get_running_loop().run_in_executor(
            None, jax.profiler.stop_trace)

    def reduce(self, bench_spans: list) -> dict | None:
        paths = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return None
        trace = trace_reduce.Trace.from_file(max(paths, key=os.path.getmtime))
        at = trace.marker(trace_reduce.MARK_START)
        if at is None:
            return None
        shift = at - self.mono_at_mark
        # the program's spans first: at equal start the later entry wins
        spans = [(n, s + shift, e + shift)
                 for n, s, e in bench_spans + self.tracer_spans]
        spans += [(n, s, e) for n, s, e in trace.host
                  if n.startswith("ktpu.")]
        return trace_reduce.reduce(trace, spans)


async def _drive(model, mix: dict, kind, chips: int, seed: int,
                 seconds: float, trace: bool, ctx: Context,
                 t_process: float, cluster_factory, scratch: str) -> dict:
    """Set-up, the window and the read-back, on one event loop; returns
    what the comparison needs once the cluster is gone."""
    from benchmark.lib.cluster import Cluster
    cluster = (cluster_factory or Cluster)()
    profile = _Profile(os.path.join(scratch, "trace")) if trace else None
    stopper = None
    out: dict = {}
    try:
        await cluster.start(chips=chips)
        used = cluster.chips_used()
        if used is not None and used != chips:
            raise Refused(
                f"this cell runs on {chips} chip(s); the backend's mesh "
                f"spans {used}")
        ctx.metrics = cluster.metrics
        gen = Generator(cluster, model, mix, seed, ctx.compile_log,
                        ctx.gc_log, kind)
        await gen.stage()
        await gen.warm()
        gc.collect()
        gc.freeze()

        async def on_start():
            nonlocal stopper
            ctx.before = counters.snapshot(cluster.metrics.registry)
            ctx.marks["attempt_window"] = \
                cluster.metrics.attempt_window().mark()
            out["host_speed_before"] = host_speed_index()
            if profile is not None:
                profile.start()
                stopper = asyncio.ensure_future(profile.stop_after(
                    min(float(mix.get("trace_seconds", 10.0)), seconds)))
            out["cpu0"] = (time.thread_time(), time.process_time())
            out["setup_s"] = time.monotonic() - t_process

        win = await gen.window(seconds, on_start)
        out["loop_cpu_s"] = time.thread_time() - out["cpu0"][0]
        out["process_cpu_s"] = time.process_time() - out["cpu0"][1]
        out["host_speed_after"] = host_speed_index()
        ctx.window = win
        ctx.after = counters.snapshot(cluster.metrics.registry)
        ctx.memory_stats = _memory_stats()
        if profile is not None:
            t = time.monotonic()
            await profile.stop()
            await stopper
            out["trace_write_s"] = time.monotonic() - t
            lo = profile.mono_at_mark
            hi = profile.mono_at_end
            ctx.traced_pods = sum(
                1 for k in win.created
                if lo <= cluster.bound_at.get(k, -1.0) <= hi)

        # read back, through the wire, a sample drawn from the seed
        rng = random.Random(stable_seed("readback", seed))
        sample = rng.sample(win.created, min(
            int(mix.get("readback_sample", 256)), len(win.created)))
        readback = {}
        for key in sample:
            pod = await cluster.client.get("pods", key)
            readback[key] = (pod.get("spec") or {}).get("nodeName")

        lost = {
            "backend_fallback": counters.total(
                ctx.after, "scheduler_schedule_attempts_total",
                {"result": "backend_fallback"}) or 0,
            "fast_path_failures": counters.total(
                ctx.after, "serving_fast_path_failures_total") or 0,
            "host_path_pods": counters.total(
                ctx.after, "scheduler_tpu_backend_degradations_total",
                {"kind": "host_path"}) or 0,
            "host_fallback_pods": counters.total(
                ctx.after, "scheduler_tpu_backend_degradations_total",
                {"kind": "host_fallback"}) or 0,
            "backend_detached": int(cluster.backend_attached() is False),
        }
        out.update(
            bound=dict(cluster.bound), rebound=list(cluster.rebound),
            readback=readback, lost=lost, created_all=list(gen.all_created),
            specs_all=list(gen.all_specs), settled=list(gen.settled))
    finally:
        if stopper is not None and not stopper.done():
            stopper.cancel()
        if profile is not None and not profile.stopped.is_set():
            await profile.stop()
        await cluster.stop()
    if profile is not None:
        t = time.monotonic()
        ctx.trace = profile.reduce(win.spans)
        out["trace_reduce_s"] = time.monotonic() - t
        shutil.rmtree(profile.directory, ignore_errors=True)
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest: Manifest | None = None, require_chip: bool = True,
             t_process: float | None = None, cluster_factory=None,
             stdout=None, stderr=None) -> int:
    """Run one cell once and print its result; returns the exit code."""
    stdout = stdout or sys.stdout
    stderr = stderr or sys.stderr
    t_process = time.monotonic() if t_process is None else t_process
    manifest = manifest or Manifest()
    cell = manifest.cell(workload)
    config = manifest.config(cell)
    mix = manifest.traffic(cell)
    model = manifest.deployment(config)
    kind = None if Generator.defines(mix["kind"]) \
        else manifest.kind(mix["kind"])

    chips = int(cell["chips"])
    device = device_facts(chips, require_chip)
    from kubernetes_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmark.lib.compile_log import CompileLog
    compile_log = CompileLog()

    ctx = Context()
    ctx.config = config
    ctx.model = model
    ctx.device_kind = device["kind"]
    ctx.compile_log = compile_log
    ctx.gc_log = GcLog()
    scratch = os.path.join(manifest.root, ".bench_scratch")
    try:
        run = asyncio.run(_drive(
            model, mix, kind, chips, seed, seconds, trace, ctx, t_process,
            cluster_factory, scratch))
    finally:
        ctx.gc_log.close()
        gc.unfreeze()
    win = ctx.window

    peak = max((m.get("peak_bytes_in_use", 0) for m in ctx.memory_stats),
               default=0)
    device["memory_peak_bytes"] = int(peak)

    # -- the metrics of this run -------------------------------------------
    metrics: dict[str, dict] = {}
    if not trace:
        quantities = dict(win.quantities, setup_s=run["setup_s"])
        if win.packing_upto:
            n = win.packing_upto
            quantities["frag_at_packing_pct"] = model.fragmentation(
                *model.placed(run["created_all"][:n], run["specs_all"][:n],
                              run["bound"]))
        table = dict(mix.get("end_to_end", {}), setup_s="setup_s")
        for m in manifest.end_to_end(cell):
            value = quantities.get(table.get(m["name"], ""))
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest.per_layer(cell):
            spec = manifest.metric_file(m["name"])
            value = manifest.reader(spec["reader"])(
                ctx, **spec.get("args", {}))
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if ctx.trace is not None:
            device["busy_s"] = ctx.trace["busy_s"]
            device["window_s"] = ctx.trace["window_s"]

    # -- the noise line ----------------------------------------------------
    inside = compile_log.window(win.start, win.end)
    print("bench: window " + json.dumps({
        "seconds": win.end - win.start, "created": len(win.created),
        "unbound": win.unbound,
        "waves": [{k: (round(v, 4) if isinstance(v, float) else v)
                   for k, v in w.items()} for w in win.waves],
        "wave_rates": [round(w["pods"] / w["seconds"], 1)
                       for w in win.waves if w["seconds"] > 0],
        "compiles_in_window": inside["compiles"],
        "compiled_in_window": inside["compiled"],
        "trace_lower_s_in_window": round(inside["trace_lower_seconds"], 4),
        "gc_in_window": ctx.gc_log.window(win.start, win.end),
        "setup_s": round(run["setup_s"], 3),
        "loop_cpu_s": round(run["loop_cpu_s"], 3),
        "process_cpu_s": round(run["process_cpu_s"], 3),
        "host_speed_index_s": [round(run["host_speed_before"], 4),
                               round(run["host_speed_after"], 4)],
        "cache_hits": compile_log.cache_hits,
        "cache_misses": compile_log.cache_misses,
        # what a traced run spends after its window, outside every metric
        "trace_write_s": round(run.get("trace_write_s", 0.0), 3),
        "trace_reduce_s": round(run.get("trace_reduce_s", 0.0), 3)}),
        file=stderr)

    # -- correct: the plain reference, once the program's state is freed ---
    gc.collect()
    numbers = model.check(
        created=run["created_all"], specs=run["specs_all"],
        bound=run["bound"], rebound=run["rebound"],
        readback=run["readback"], settled=run["settled"],
        not_device_placed=sum(run["lost"].values()))
    correct = is_correct(numbers)
    failed = win.unbound + int(run["lost"]["host_path_pods"]
                               + run["lost"]["host_fallback_pods"])
    result = {
        "correct": correct,
        "attempted": len(win.created),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if trace and ctx.trace is not None:
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ctx.trace["device_ops"][:10]],
            "idle_gaps": [[n, s] for n, s in ctx.trace["idle_gaps"][:10]],
        }
    result["compared"] = numbers
    print("bench: lost " + json.dumps(run["lost"]), file=stderr)
    for name, n in numbers.items():
        print(f"bench: compared {name} = {n['value']} (limit {n['limit']})",
              file=stderr)
    print(json.dumps(result), file=stdout, flush=True)
    return 0
