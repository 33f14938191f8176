"""A jitted program's share of its roofline, in percent: the least time
the chip could take for the work its executions had to do
(lib/work_model.py, a function of the problem only) over the time the
trace shows it took. args: program; classes (distinct pod classes in
the traffic, default 1)."""

from benchmark.lib import peaks, work_model


def read(ctx, program, classes=1):
    if ctx.trace is None or not ctx.traced_pods:
        return None
    entry = ctx.trace["programs"].get(program)
    if entry is None or not entry["seconds"]:
        return None
    resources = len(ctx.config["node_template"]["allocatable"])
    ops, bytes_ = work_model.solve_work(
        nodes=int(ctx.config["nodes"]), resources=resources,
        pods=ctx.traced_pods, classes=classes, chunks=entry["runs"])
    least, _ = work_model.least_seconds(
        ops, bytes_, peaks.peaks(ctx.device_kind))
    return 100.0 * least / entry["seconds"]
