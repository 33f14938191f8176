"""Headline benchmark: pods scheduled/sec at scale (BASELINE.json metric).

Runs the scheduler_perf SchedulingBasic workload (in-process store + real
scheduler + informers, Node objects as data — no kubelets, the reference's
own trick) with the TPU batch backend, and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": "pods/s", "vs_baseline": N/ref}

Baseline: the reference's default-scheduler sustains ~100–300 pods/s on
scheduler_perf (README "Reference envelope"); vs_baseline uses 300 —
the top of the published envelope — so the ratio is conservative.

Presets: --preset smoke (100 nodes/1k pods, quick), --preset 1k,
--preset 5k (default; upstream SchedulingBasic/5000Nodes_10000Pods).
Options: --backend host|tpu (default tpu), --batch-size (default 16384).

`--backend tpu` means "the device JAX has": the metric name and the
`backend` field carry the platform that actually ran (`..._nodes_cpu...`
on a JAX_PLATFORMS=cpu pre-flight), and the run exits non-zero if the
device was lost on the way (scheduler_perf.device_run_failures).
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys

REFERENCE_PODS_PER_SEC = 300.0


def _provenance(args, details: list[dict]) -> dict:
    """Solve-backend provenance stamped into every headline/detail JSON:
    the jax platform, device kind and count and library versions the
    run actually used, and whether the solve donates its carry. With
    --processes >= 2 it is the leader replica's (read off its status
    row into the detail): the parent never touches JAX, because the
    chip belongs to the one process that schedules."""
    if args.backend != "tpu":
        return {"solve_kernel": "host"}
    if args.processes > 1:
        return next((d["solve_provenance"] for d in details
                     if d.get("solve_provenance")), {})
    from kubernetes_tpu.ops.backend import solve_provenance
    return solve_provenance()


def _ran_on(args, prov: dict) -> str:
    """The backend name a result is printed under: the platform that
    ran, so no CPU number is ever printed under `tpu`."""
    if args.backend != "tpu":
        return args.backend
    return prov.get("jax_platform") or "unknown"


def _device_exit(args, details: list[dict]) -> int:
    """Exit code of a finished run: non-zero when it was asked for the
    device and lost it (any detail row — see device_run_failures)."""
    if args.backend != "tpu":
        return 0
    from kubernetes_tpu.perf.scheduler_perf import device_exit
    return device_exit(details, args.processes)


#: default --churn rate sweeps (pods/s arrival): bracket the knee from
#: a comfortable trickle to past the drain headline for the preset.
PRESET_CHURN_RATES = {
    "smoke": [50.0, 200.0, 800.0],
    "1k": [100.0, 400.0, 1600.0],
    "5k": [250.0, 1000.0, 4000.0],
    "50k": [250.0, 1000.0, 4000.0],
    "200k": [250.0, 1000.0, 4000.0],
    "1m": [250.0, 1000.0, 4000.0],
}

def _warn_policy_needs_boundary(args, boundary, what: str) -> None:
    """Shared "refuse to record a lie" guards (drain/churn/serve
    modes): the policy chain lives on the servers, so --policy-set/
    --audit-level without the apiserver boundary would measure nothing
    — and --policy-tenants only shapes a --policy-set, so alone it
    installs zero policies."""
    if args.policy_tenants and not args.policy_set:
        print("warning: --policy-tenants without --policy-set installs "
              f"NO policies; {what} will measure a policy-free chain",
              file=sys.stderr)
    if not boundary and (args.policy_set or args.audit_level):
        print("warning: --policy-set/--audit-level need "
              f"--through-apiserver; {what} will evaluate NO policies",
              file=sys.stderr)


PRESETS = {
    #       nodes, warmup pods, measured pods
    "smoke": (100, 200, 1000),
    "1k": (1000, 500, 3000),
    "5k": (5000, 1000, 10000),
    # config #5 scale: 50k nodes (KWOK-style, nodes are data); the node
    # dimension is what multi-slice sharding scales (SURVEY §5.7).
    "50k": (50000, 500, 5000),
    # Sharded-control-plane scale (ROADMAP #5): above KTPU_SHARD_THRESHOLD
    # the store/informer/host-prep path partitions into per-shard mvcc
    # stores (store/sharded.py) — flagless; --shards/KTPU_SHARDS override.
    "200k": (200000, 500, 5000),
    # r22 stretch preset: 1M nodes. Intended for --processes >= 2 (the
    # multi-process control plane).
    "1m": (1_000_000, 500, 5000),
}


def _proc_tag(args) -> str:
    """Metric-name suffix for multi-process rows: an N-process headline
    must never be mistaken for (or averaged with) the in-process one."""
    return f"_procs{args.processes}" if args.processes > 1 else ""


DRAIN_TEMPLATE = [
    # Warmup phase triggers jit compilation before the measured phase.
    {"opcode": "createNodes", "countParam": "$nodes"},
    {"opcode": "createPods", "countParam": "$warmup"},
    {"opcode": "barrier"},
    {"opcode": "createPods", "countParam": "$measured",
     "collectMetrics": True},
    {"opcode": "barrier"},
]
SERVE_TEMPLATE = [
    {"opcode": "createNodes", "countParam": "$nodes"},
    {"opcode": "createPods", "countParam": "$warmup"},
    {"opcode": "barrier"},
    {"opcode": "churnOpenLoop", "collectMetrics": True,
     "arrival": {"model": "poisson", "rate": "$rate"},
     "duration": "$duration", "seed": 17},
]


def make_runner(args, shards, boundary, batch: int, profile_dir=None):
    """The one PerfRunner construction — every mode here and
    chip_smoke.py build their runs through it. A fresh runner (and
    backend) per run, so one run's warmed programs never subsidize the
    next one's numbers. With --processes >= 2 the device backend is a
    SPEC the leader replica builds from: this process must not touch
    JAX, or it would hold the chip the leader needs."""
    from kubernetes_tpu.perf.scheduler_perf import PerfRunner, device_backend

    backend = spec = None
    if args.backend == "tpu":  # as resolved by prepare()
        backend, spec = device_backend(args.chunk, args.processes)
    return PerfRunner(backend=backend, backend_spec=spec, batch_size=batch,
                      through_apiserver=boundary, shards=shards,
                      profile_dir=profile_dir,
                      policy_count=args.policy_set,
                      policy_tenants=args.policy_tenants,
                      audit_rules=[{"level": args.audit_level}]
                      if args.audit_level else None,
                      processes=args.processes,
                      data_dir=args.data_dir or None)


def run_template(args, template: list, params: dict, shards, boundary,
                 batch: int, profile_dir=None, timeout: float = 1800.0):
    """One harness run of a workload template; returns the runner (its
    backend outlives the run, for inspection) and the WorkloadResult."""
    runner = make_runner(args, shards, boundary, batch, profile_dir)
    return runner, asyncio.run(
        runner.run(template, params, timeout=timeout))


def _run_churn(args, nodes: int, shards, boundary, batch: int) -> int:
    """ChurnDay mode: rate sweep to the knee (+ optional fault row).

    Headline = the knee (highest absorbed open-loop arrival rate) with
    its exact p999; per-row details (p50/p99/p999, backlog growth,
    fault/recovery records) go to stderr like the drain detail JSON."""
    from kubernetes_tpu.perf.churn.driver import run_rate_sweep

    rates = PRESET_CHURN_RATES[args.preset]
    if args.churn_rates:
        rates = [float(r) for r in args.churn_rates.split(",") if r]
    fault = None
    if args.churn_fault:
        kind, _, at = args.churn_fault.partition("@")
        fault = {"kind": kind, "at": float(at or 5.0)}
    if args.profile_dir:
        print("warning: --profile-dir is not supported in --churn mode "
              "(per-row runs would overwrite each other's traces); no "
              "trace will be written", file=sys.stderr)
    _warn_policy_needs_boundary(args, boundary, "churn rows")

    def runner_factory():
        return make_runner(args, shards, boundary, batch)

    sweep = run_rate_sweep(
        nodes=nodes, rates=rates, duration=args.churn_duration,
        seed=args.churn_seed, model=args.churn_model,
        warmup=args.churn_warmup, agents=args.churn_agents,
        fault=fault, fault_rate=args.churn_fault_rate,
        runner_factory=runner_factory, timeout=1800.0)
    details = sweep["rows"] + ([sweep["fault_row"]]
                               if sweep["fault_row"] is not None else [])
    prov = _provenance(args, details)
    ran_on = _ran_on(args, prov)
    print(json.dumps({"churn": sweep, "preset": args.preset,
                      "backend": ran_on,
                      "provenance": prov}), file=sys.stderr)
    knee = sweep["knee"]
    value = knee["knee_rate"] or 0.0
    out = {
        "provenance": prov,
        "metric": f"churn_knee_arrival_rate_{args.preset}_{ran_on}"
                  + (f"_apiserver_{args.transport}" if boundary else "")
                  + _proc_tag(args),
        "value": value,
        "unit": "pods/s",
        "vs_baseline": round(value / REFERENCE_PODS_PER_SEC, 3),
        "knee_p999_ms": knee["knee_p999_ms"],
        "first_saturated_rate": knee["first_saturated_rate"],
    }
    if sweep["fault_row"] is not None:
        out["fault_recovery_seconds_max"] = \
            sweep["fault_row"]["churn_recovery_seconds_max"]
    print(json.dumps(out))
    return _device_exit(args, details)


def _run_serve(args, nodes: int, warmup: int, measured: int, shards,
               boundary, batch: int) -> int:
    """--serve mode: the online-serving headline pair IN ONE RUN —
    (a) the unchanged bulk-drain throughput of the preset, then
    (b) a steady-state single-pod trickle (open-loop arrivals at
    --serve-rate, default the r15 worst-case 250/s) whose EXACT
    p50/p99/p999 attempt percentiles (r11 WindowedLatencyRecorder) are
    the serving tier's figure of merit. Fresh runner per phase so the
    drain's warmed chunk programs can't subsidize the serve numbers or
    vice versa."""
    _warn_policy_needs_boundary(args, boundary, "serve rows")
    _, drain = run_template(
        args, DRAIN_TEMPLATE, {"nodes": nodes, "warmup": warmup,
                               "measured": measured},
        shards, boundary, batch)
    _, serve = run_template(
        args, SERVE_TEMPLATE, {"nodes": nodes, "warmup": warmup,
                               "rate": args.serve_rate,
                               "duration": args.serve_duration},
        shards, boundary, batch)
    d, s = drain.as_dict(), serve.as_dict()
    prov = _provenance(args, [d, s])
    ran_on = _ran_on(args, prov)
    print(json.dumps({"serve": s, "drain": d, "preset": args.preset,
                      "backend": ran_on,
                      "provenance": prov}), file=sys.stderr)
    print(json.dumps({
        "provenance": prov,
        "metric": f"serve_single_pod_p50_ms_{args.preset}_{ran_on}"
                  + (f"_apiserver_{args.transport}" if boundary else "")
                  + _proc_tag(args),
        "value": s["attempt_p50_ms"],
        "unit": "ms",
        "serve_rate": args.serve_rate,
        "serve_p99_ms": s["attempt_p99_ms"],
        "serve_p999_ms": s["attempt_p999_ms"],
        "serve_percentiles_exact": s["attempt_percentiles_exact"],
        "serve_fast_path_pods": s["serving_fast_path_pods_total"],
        "drain_pods_per_sec": d["throughput_pods_per_sec"],
        "drain_vs_baseline": round(
            d["throughput_pods_per_sec"] / REFERENCE_PODS_PER_SEC, 3),
    }))
    return _device_exit(args, [d, s])


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", choices=PRESETS, default="5k")
    ap.add_argument("--backend", choices=["host", "tpu"], default="tpu")
    ap.add_argument("--batch-size", type=int, default=16384,
                    help="pods popped per scheduling super-batch; the "
                         "backend chunks + pipelines internally. One "
                         "super-batch per measured burst avoids the "
                         "batch-boundary stall (tensor delta + used-state "
                         "re-upload + first-chunk latency with no binding "
                         "work to overlap)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="OVERRIDE the backend solve chunk (jit batch "
                         "signature). Default: 1024, with the pipeline "
                         "depth from the AdaptiveTuner's table")
    ap.add_argument("--shards", type=int, default=None,
                    help="OVERRIDE the control-plane shard count (the "
                         "sweep knob; 1 = the classic single store). "
                         "Default: flagless — node counts at or above "
                         "KTPU_SHARD_THRESHOLD (100k) activate "
                         "KTPU_SHARDS or 8 shards; below it the r12 "
                         "single-store path runs bit-for-bit")
    ap.add_argument("--processes", type=int, default=None,
                    help="OVERRIDE the control-plane OS-process count "
                         "(r22 tentpole): N >= 2 runs one apiserver "
                         "process per shard plus a leader-elected "
                         "scheduler pair over the KTPU wire; 1 is the "
                         "kill switch (today's in-process tree, built "
                         "exactly as before). Default: flagless "
                         "KTPU_PROCESSES (unset = 1)")
    ap.add_argument("--data-dir", default="",
                    help="durability directory for the shard processes "
                         "(per-shard snapshots + write-ahead log; "
                         "KTPU_WAL_FSYNC picks the fsync policy). "
                         "Default: flagless KTPU_DATA_DIR (unset = "
                         "in-memory only)")
    ap.add_argument("--shortlist-k", type=int, default=None,
                    help="OVERRIDE the solver shortlist width (0 disables "
                         "the pruned solve — the before/after sweep knob). "
                         "Default: flagless — the tuner derives K from the "
                         "chunk width and observed fallback rate, active "
                         "only when the node count dwarfs the scan width")
    ap.add_argument("--class-pad", type=int, default=None,
                    help="OVERRIDE the class-dictionary plane cap (max "
                         "pod equivalence classes per chunk; 0 disables "
                         "class planes entirely — the per-pod-plane "
                         "before/after sweep knob). Default: flagless "
                         "KTPU_CLASS_PAD (31)")
    ap.add_argument("--serve", action="store_true",
                    help="online-serving mode (kubernetes_tpu/serving): "
                         "report steady-state single-pod placement "
                         "p50/p99/p999 (exact, open-loop trickle at "
                         "--serve-rate) ALONGSIDE the preset's unchanged "
                         "bulk-drain headline in one run")
    ap.add_argument("--serve-rate", type=float, default=250.0,
                    help="single-pod arrival rate for --serve (default "
                         "250/s — the r15 worst-case trickle row)")
    ap.add_argument("--serve-duration", type=float, default=10.0,
                    help="seconds of open-loop serve arrivals")
    ap.add_argument("--admission-window", type=float, default=None,
                    metavar="MS",
                    help="OVERRIDE the serving admission coalesce window "
                         "in milliseconds (0 = always dispatch "
                         "immediately). Default: flagless — the "
                         "AdaptiveTuner policy row sizes it from the "
                         "offered-rate estimate (thresholds seeded "
                         "from the r15 churn knee)")
    ap.add_argument("--serving", choices=["on", "off"], default="on",
                    help="KTPU_SERVING kill switch: 'off' degrades the "
                         "dispatch loop structurally to the pre-serving "
                         "shape (the before/after sweep knob)")
    ap.add_argument("--solve-mode", choices=["greedy", "optimal", "auto"],
                    default=None,
                    help="KTPU_SOLVE_MODE: 'greedy' pins the r18 "
                         "wavefront scan (bit-identical kill switch), "
                         "'optimal' forces the Sinkhorn transport plan + "
                         "feasible rounding on eligible chunks, 'auto' "
                         "(the default policy) routes drain-scale and "
                         "gang chunks only. The r20 fragmentation pair "
                         "sweeps greedy vs optimal on one preset")
    ap.add_argument("--churn", action="store_true",
                    help="ChurnDay mode (perf/churn): instead of one "
                         "bulk drain, sweep an OPEN-LOOP Poisson/burst/"
                         "ramp arrival rate over the preset's nodes to "
                         "find the knee; the headline becomes exact "
                         "p50/p99/p999 attempt latency + knee rate, "
                         "with queue growth as the saturation signal")
    ap.add_argument("--churn-rates", default="",
                    help="comma-separated arrival rates (pods/s) to "
                         "sweep; default per preset")
    ap.add_argument("--churn-duration", type=float, default=10.0,
                    help="seconds of open-loop arrivals per rate row")
    ap.add_argument("--churn-seed", type=int, default=17,
                    help="arrival/fault timeline seed (same seed = "
                         "bit-identical timelines)")
    ap.add_argument("--churn-model",
                    choices=["poisson", "burst", "ramp"],
                    default="poisson")
    ap.add_argument("--churn-warmup", type=int, default=300,
                    help="drained warmup pods before the open-loop "
                         "window (jit compile exclusion)")
    ap.add_argument("--churn-fault", default="",
                    help='inject a fault mid-wave, "kind@seconds" '
                         '(e.g. "nodeDeath@5.0"): reruns one rate with '
                         "agent-backed staging, the deterministic fault "
                         "timeline, and time-to-recovery measured")
    ap.add_argument("--churn-fault-rate", type=float, default=None,
                    help="arrival rate for the fault scenario (default: "
                         "the measured knee rate)")
    ap.add_argument("--churn-agents", action="store_true",
                    help="agent-backed staging for ALL churn rows (N "
                         "hollow-kubelet NodeAgents instead of "
                         "createNodes data staging)")
    ap.add_argument("--through-apiserver", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="cross the process boundary: workload writes, "
                         "informers, and binding POSTs go over the "
                         "apiserver (reference scheduler_perf topology). "
                         "DEFAULT ON so the headline measures the honest "
                         "boundary; --no-through-apiserver for the "
                         "in-process store topology")
    ap.add_argument("--transport", choices=["wire", "http"], default="wire",
                    help="apiserver transport for --through-apiserver: "
                         "'wire' = the multiplexed framed wire core "
                         "components use (the reference's HTTP/2+protobuf "
                         "analog); 'http' = per-request HTTP/1.1+JSON")
    ap.add_argument("--policy-set", type=int, default=0,
                    help="install N ValidatingAdmissionPolicies (+ "
                         "bindings) matching pod CREATEs before the "
                         "run — the policy-chain overhead knob "
                         "(BASELINE r9 measures 10 vs 0). Counted in "
                         "the detail JSON's policy_evaluations_total")
    ap.add_argument("--policy-tenants", type=int, default=0,
                    help="shard --policy-set across N tenant namespaces "
                         "(per-namespace selectors, disjoint "
                         "resourceRules, ~1%% of policies matching any "
                         "given request — the realistic multi-tenant "
                         "shape; the 1k-policy headline row uses "
                         "--policy-set 1000 --policy-tenants 100). "
                         "0 = the legacy uniform all-matching set")
    ap.add_argument("--audit-level", default="",
                    choices=["", "Metadata", "Request",
                             "RequestResponse"],
                    help="enable the audit pipeline at this level for "
                         "every request (default: no audit rules = "
                         "level None, zero cost)")
    ap.add_argument("--profile-dir", default="",
                    help="write a jax.profiler device trace of the "
                         "MEASURED phase to this directory (tpu backend "
                         "only)")
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="enable the in-process tracer and write the "
                         "run's span tree as Chrome trace-event JSON "
                         "(open in https://ui.perfetto.dev or "
                         "chrome://tracing). Spans cover apiserver "
                         "requests, admission, queue wait, framework "
                         "extension points, device-solve chunks and "
                         "binds; KTPU_TRACE_THRESHOLD_MS additionally "
                         "logs slow span trees")
    ap.add_argument("--feature-gates", default="",
                    help='e.g. "TPUScorer=true" — the north-star seam: the '
                         "batched device backend hangs off this gate "
                         "(--backend tpu is sugar for enabling it)")
    ap.add_argument("--lint", action="store_true",
                    help="run the repo's static analysis "
                         "(python -m kubernetes_tpu.analysis) and print a "
                         "finding summary; exit 0 clean / 1 findings / 2 "
                         "internal error")
    ap.add_argument("--lint-json", action="store_true",
                    help="--lint with machine-readable JSON on stdout")
    return ap


def prepare(args) -> tuple:
    """Apply a parsed command line to the process (flag overrides, the
    TPUScorer gate, the compile cache) and size the run; returns
    (nodes, warmup, measured, shards, boundary, batch). Shared with
    chip_smoke.py so the smoke takes the same path as `python bench.py`."""
    import os

    if args.shortlist_k is not None:
        # Flag reads are live (utils/flags.py), so ordering vs the
        # backend import does not matter.
        os.environ["KTPU_SHORTLIST_K"] = str(args.shortlist_k)
    if args.admission_window is not None:
        os.environ["KTPU_ADMISSION_WINDOW"] = str(args.admission_window)
    if args.serving == "off":
        os.environ["KTPU_SERVING"] = "0"
    if args.solve_mode is not None:
        os.environ["KTPU_SOLVE_MODE"] = args.solve_mode
    if args.class_pad is not None:
        os.environ["KTPU_CLASS_PAD"] = str(args.class_pad)

    from kubernetes_tpu.perf.scheduler_perf import resolve_processes
    from kubernetes_tpu.utils.featuregate import DEFAULT_FEATURE_GATES

    # Backend selection goes through the TPUScorer feature gate (SURVEY
    # §5.6 seam #3): CLI --backend only sets the gate's value.
    DEFAULT_FEATURE_GATES.set("TPUScorer", args.backend == "tpu")
    if args.feature_gates:
        DEFAULT_FEATURE_GATES.set_from_spec(args.feature_gates)
    device = DEFAULT_FEATURE_GATES.enabled("TPUScorer")
    args.backend = "tpu" if device else "host"
    args.processes = resolve_processes(args.processes)
    if device and args.processes <= 1:
        # Before the first compile. With children the leader replica
        # does this itself (multiproc/schedproc.py); this process
        # stays off JAX.
        from kubernetes_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()

    nodes, warmup, measured = PRESETS[args.preset]
    from kubernetes_tpu.store.sharded import control_plane_shards
    # PerfRunner owns propagating the override (it scopes KTPU_SHARDS
    # around the run so the host prep's policy sees the same S).
    shards = control_plane_shards(nodes, args.shards)
    boundary = False
    if args.through_apiserver:
        boundary = "wire" if args.transport == "wire" else True
    # The workload churns millions of short-lived dicts; default gen-0
    # collection every 700 allocations makes the interpreter spend ~6% of
    # the measured phase in GC (plus XLA's gc callback). Raising the
    # threshold trades peak RSS for wall, like tuning GOGC on the reference.
    gc.set_threshold(100_000, 50, 50)
    return (nodes, warmup, measured, shards, boundary,
            args.batch_size if device else 1)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    if args.lint or args.lint_json:
        from kubernetes_tpu.analysis import main as lint_main
        return lint_main(["--json"] if args.lint_json else [])

    tracer = None
    if args.trace:
        from kubernetes_tpu.utils.tracing import DEFAULT_TRACER
        tracer = DEFAULT_TRACER
        tracer.enabled = True

    nodes, warmup, measured, shards, boundary, batch = prepare(args)

    if args.profile_dir and (args.backend != "tpu" or args.processes > 1):
        print("warning: --profile-dir needs --backend tpu in one process; "
              "no trace will be written", file=sys.stderr)
        args.profile_dir = ""
    if args.processes > 1 and (args.policy_set or args.audit_level):
        print("warning: the multi-process control plane carries no "
              "policy chain yet; --policy-set/--audit-level are ignored "
              "at --processes >= 2", file=sys.stderr)
    if args.churn:
        return _run_churn(args, nodes, shards, boundary, batch)
    if args.serve:
        return _run_serve(args, nodes, warmup, measured, shards, boundary,
                          batch)
    _warn_policy_needs_boundary(args, boundary, "the run")
    _, res = run_template(
        args, DRAIN_TEMPLATE,
        {"nodes": nodes, "warmup": warmup, "measured": measured},
        shards, boundary, batch, profile_dir=args.profile_dir or None,
        # The 1m stretch preset stages and syncs ~200x the 5k object
        # count before the measured phase begins; everything else keeps
        # the tighter window so a hung run fails fast.
        timeout=5400.0 if args.preset == "1m" else 1800.0)

    if tracer is not None:
        with open(args.trace, "w") as f:
            f.write(tracer.to_perfetto())
        print(f"trace: {args.trace} ({len(tracer.spans)} spans; open in "
              "https://ui.perfetto.dev)", file=sys.stderr)

    detail = res.as_dict()
    prov = _provenance(args, [detail])
    ran_on = _ran_on(args, prov)
    print(json.dumps({"detail": detail, "preset": args.preset,
                      "backend": ran_on,
                      "provenance": prov}, ), file=sys.stderr)
    print(json.dumps({
        "provenance": prov,
        "metric": f"pods_per_sec_{args.preset}_nodes_{ran_on}"
                  + (f"_apiserver_{args.transport}"
                     if args.through_apiserver else "")
                  + _proc_tag(args),
        "value": detail["throughput_pods_per_sec"],
        "unit": "pods/s",
        "vs_baseline": round(
            detail["throughput_pods_per_sec"] / REFERENCE_PODS_PER_SEC, 3),
        # r20 headline: packing quality next to pods/s — occupied-node
        # fragmentation is the figure optimal mode moves; the all-nodes
        # figure stays for continuity with earlier rounds.
        "fragmentation_pct": detail["fragmentation_pct"],
        "fragmentation_occupied_pct": detail["fragmentation_occupied_pct"],
        "solve_mode": args.solve_mode or "auto",
    }))
    return _device_exit(args, [detail])


if __name__ == "__main__":
    raise SystemExit(main())
