"""JAX's own compile and persistent-cache events (jax.monitoring),
timestamped on time.monotonic so they can be placed inside or outside a
window — the benchmark's copy of `chip_smoke._CompileLog`."""

from __future__ import annotations

import time

#: Python-side cost of a new program — paid even on a cache hit.
_TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self):
        from jax import monitoring
        #: (time.monotonic() at the end, seconds, jitted function's name)
        self.compiles: list[tuple[float, float, str]] = []
        #: (time.monotonic() at the end, seconds)
        self.trace_lower: list[tuple[float, float]] = []
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == _BACKEND_COMPILE:
            self.compiles.append(
                (time.monotonic(), secs, kw.get("fun_name", "?")))
        elif name in _TRACE_LOWER:
            self.trace_lower.append((time.monotonic(), secs))

    def _event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def last_event(self) -> float:
        """time.monotonic() of the newest compile or trace/lower event
        (0.0 when there has been none)."""
        return max([t for t, _, _ in self.compiles[-1:]]
                   + [t for t, _ in self.trace_lower[-1:]] + [0.0])

    def window(self, start: float, end: float) -> dict:
        """What compiled, traced or lowered inside [start, end]."""
        inside = [(s, f) for t, s, f in self.compiles if start <= t <= end]
        return {
            "compiles": len(inside),
            "compile_seconds": sum(s for s, _ in inside),
            "trace_lower_seconds": sum(
                s for t, s in self.trace_lower if start <= t <= end),
            "compiled": sorted({f for _, f in inside}),
        }
