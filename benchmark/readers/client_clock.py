"""An exact percentile of one of the series the load generator keeps on
its own clock (milliseconds): `create_ack_ms`, `gen_late_ms`,
`latency_ms`. args: series, q."""

from benchmark.lib.percentiles import percentile


def read(ctx, series, q):
    values = ctx.window.series.get(series)
    if not values:
        return None
    return percentile(values, q)
