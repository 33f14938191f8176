"""Growth of a program counter over the window, over the growth of
another (or over the pods the client saw bound), times `scale`.

args: numerator {name, match?}; denominator {name, match?} | "pods" |
"kpods" | omitted (the bare growth); scale (default 1). Names are those
of the program's text exposition (a histogram's are `<name>_sum` and
`<name>_count`). Nothing to divide by, or no such series: no reading.
"""

from benchmark.lib import counters


def read(ctx, numerator, denominator=None, scale=1.0):
    top = counters.delta(ctx.before, ctx.after, numerator["name"],
                         numerator.get("match"))
    if top is None:
        return None
    if denominator is None:
        return scale * top
    if denominator == "pods":
        bottom = ctx.pods_bound
    elif denominator == "kpods":
        bottom = ctx.pods_bound / 1000.0
    else:
        bottom = counters.delta(ctx.before, ctx.after, denominator["name"],
                                denominator.get("match"))
    if not bottom:
        return None
    return scale * top / bottom
