"""A percentile of the scheduler's own attempt durations over the
window, in milliseconds: the program's exact recorder
(`SchedulerMetrics.attempt_window`), read from the mark taken at the
window's start. Scheduler-internal: it starts when the scheduler pops
the pod, so it never stands for what a client waits. args: q."""

import math


def read(ctx, q):
    mark = ctx.marks.get("attempt_window")
    if mark is None or ctx.metrics is None:
        return None
    recorder = ctx.metrics.attempt_window()
    if not recorder.count_since(mark):
        return None
    value = recorder.percentiles_since(mark, (q,))[q]
    return None if math.isnan(value) else 1e3 * value
