"""JAX's compile events inside the window (lib/compile_log.py).
args: what — "compiles" (programs the backend compiled) or
"trace_lower_seconds" (Python trace + lower, paid even on a cache
hit)."""


def read(ctx, what):
    if ctx.compile_log is None:
        return None
    return float(ctx.compile_log.window(
        ctx.window.start, ctx.window.end)[what])
