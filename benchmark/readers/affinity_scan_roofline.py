"""The carried scan's share of its roofline, in percent: the least time
the chip could take for the chunks' work and for the InterPodAffinity
carry of every pod that took a carried step (lib/affinity_work.py) over
the time the trace shows the program took. A deployment whose pods all
carry their score (its `problem()` says so) counts each pod bound in the
traced stretch as one step. args: program."""

from benchmark.lib import affinity_work, peaks, work_model


def read(ctx, program):
    if ctx.trace is None or not ctx.traced_pods:
        return None
    entry = ctx.trace["programs"].get(program)
    if entry is None or not entry["seconds"]:
        return None
    ops, bytes_ = affinity_work.carried_solve_work(
        pods=ctx.traced_pods, chunks=entry["runs"],
        steps=ctx.traced_pods, **ctx.model.problem())
    least, _ = work_model.least_seconds(
        ops, bytes_, peaks.peaks(ctx.device_kind))
    return 100.0 * least / entry["seconds"]
