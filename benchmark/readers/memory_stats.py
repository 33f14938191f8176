"""A device memory statistic after the window, on the fullest chip,
times `scale`. args: key (default peak_bytes_in_use), scale. A backend
that reports no statistics gives no reading."""


def read(ctx, key="peak_bytes_in_use", scale=1.0):
    values = [m[key] for m in ctx.memory_stats if key in m]
    if not values:
        return None
    return scale * max(values)
