"""A jitted program's share of its roofline, in percent: the least time
the chip could take for the work its executions had to do
(lib/work_model.py, a function of the problem only) over the time the
trace shows it took. The problem (nodes, resource planes, distinct pod
classes in the traffic) is the deployment's to state. args: program."""

from benchmark.lib import peaks, work_model


def read(ctx, program):
    if ctx.trace is None or not ctx.traced_pods:
        return None
    entry = ctx.trace["programs"].get(program)
    if entry is None or not entry["seconds"]:
        return None
    ops, bytes_ = work_model.solve_work(
        pods=ctx.traced_pods, chunks=entry["runs"], **ctx.model.problem())
    least, _ = work_model.least_seconds(
        ops, bytes_, peaks.peaks(ctx.device_kind))
    return 100.0 * least / entry["seconds"]
