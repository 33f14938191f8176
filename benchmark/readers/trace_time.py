"""Device time from the profiler's trace, in milliseconds per thousand
pods bound inside the traced stretch. args: program — a jitted
program's name (`jit__mask_solve_update`): the sum of its executions;
omitted: the union of every device operation (busy time)."""


def read(ctx, program=None):
    if ctx.trace is None or not ctx.traced_pods:
        return None
    if program is None:
        seconds = ctx.trace["busy_s"]
    else:
        entry = ctx.trace["programs"].get(program)
        if entry is None:
            return None
        seconds = entry["seconds"]
    return 1e3 * seconds / (ctx.traced_pods / 1000.0)
