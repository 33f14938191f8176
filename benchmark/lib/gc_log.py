"""Collector runs, timestamped on time.monotonic (gc.callbacks)."""

from __future__ import annotations

import gc
import time


class GcLog:
    def __init__(self):
        #: (time.monotonic() at the end, generation, seconds)
        self.runs: list[tuple[float, int, float]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            now = time.monotonic()
            self.runs.append((now, info["generation"], now - self._t0))

    def close(self) -> None:
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)

    def window(self, start: float, end: float) -> dict:
        inside = [(g, s) for t, g, s in self.runs if start <= t <= end]
        return {"runs": len(inside),
                "gen2_runs": sum(1 for g, _ in inside if g == 2),
                "seconds": sum(s for _, s in inside)}
