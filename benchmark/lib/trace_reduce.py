"""From a profiler trace (`*.xplane.pb`) to numbers.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
`/device:TPU:<i>`, with a line `XLA Modules` (one event per execution of
a jitted program, named `jit_<fn>(<fingerprint>)`) and a line `XLA Ops`
(one event per HLO operation that ran, named by its whole HLO text,
nested operations inside their `while`; asynchronous copies are on a
line of their own and are not counted busy), and a host plane
`/host:CPU` whose lines are threads; the `python3` line carries the
`TraceAnnotation`s. All times are nanoseconds from the trace's start.

- busy: the union of the `XLA Ops` intervals inside the window, per
  chip, averaged over chips; idle share is 1 - busy / window.
- time per jitted program: the sum of its `XLA Modules` events, under
  the name with the fingerprint cut off.
- device operations by name: `<program>/<op>`, the op joined to the
  module execution that contains it.
- idle gaps: the complement of busy; each instant of a gap is given to
  the innermost span that covers it (the latest-started span still
  open), from the spans handed in — the program's `utils.tracing` spans
  laid over this clock, then the benchmark's own — else `host.other`.

The window is what lies between the two marker annotations the harness
writes (`bench.window.start` / `bench.window.end`).
"""

from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_START = "bench.window.start"
MARK_END = "bench.window.end"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def program_name(module_event_name: str) -> str:
    return _FINGERPRINT.sub("", module_event_name)


def op_name(op_event_name: str) -> str:
    """`while.31` of `%while.31 = (s32[]...) while(...)`: an `XLA Ops`
    event is named by the operation's whole HLO text."""
    head = op_event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or op_event_name


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """(total length, merged intervals) of a set of [start, end]."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class Trace:
    """Events of one trace, seconds on the profiler's clock."""

    def __init__(self):
        #: chip -> [(name, start, end)]
        self.ops: dict[int, list] = defaultdict(list)
        self.modules: dict[int, list] = defaultdict(list)
        #: host annotations: [(name, start, end)]
        self.host: list = []

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        tr = cls()
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name in (OPS_LINE, MODULES_LINE):
                    dest = (tr.ops if line.name == OPS_LINE
                            else tr.modules)[int(m.group(1))]
                    short = op_name if line.name == OPS_LINE else str
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dest.append(
                            (short(ev.name), s, s + ev.duration_ns * 1e-9))
                elif not m and plane.name.startswith("/host:"):
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        tr.host.append(
                            (ev.name, s, s + ev.duration_ns * 1e-9))
        return tr

    def marker(self, name: str) -> float | None:
        """Start of the first host annotation of that name."""
        starts = [s for n, s, _ in self.host if n == name]
        return min(starts) if starts else None


def attribute_gaps(gaps: list[tuple[float, float]],
                   spans: list[tuple[str, float, float]],
                   fallback: str = "host.other") -> dict[str, float]:
    """Seconds of `gaps` by the innermost covering span: at each instant
    the latest-started span that is still open."""
    out: dict[str, float] = defaultdict(float)
    if not gaps:
        return out
    starts = [g[0] for g in gaps]
    cum = [0.0]
    for s, e in gaps:
        cum.append(cum[-1] + (e - s))

    def gap_before(t: float) -> float:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        s, e = gaps[i]
        return cum[i] + min(max(t - s, 0.0), e - s)

    lo, hi = gaps[0][0], gaps[-1][1]
    spans = sorted((s for s in spans if s[2] > lo and s[1] < hi),
                   key=lambda s: s[1])
    points = sorted({lo, hi} | {min(max(t, lo), hi)
                                for _, s, e in spans for t in (s, e)})
    open_: list = []   # heap of (-start, end, name)
    nxt = 0
    for a, b in zip(points, points[1:]):
        while nxt < len(spans) and spans[nxt][1] <= a:
            n, s, e = spans[nxt]
            heapq.heappush(open_, (-s, e, n))
            nxt += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        # the top may be open while an older, longer span under it has
        # already closed: those are dropped when they reach the top
        idle = gap_before(b) - gap_before(a)
        if idle > 0:
            out[open_[0][2] if open_ else fallback] += idle
    return out


def reduce(trace: Trace, spans: list[tuple[str, float, float]] | None = None,
           window: tuple[float, float] | None = None) -> dict | None:
    """The numbers of one traced window; None when no operation ran on a
    device in it. `spans` are (name, start, end) on the trace's clock."""
    if window is None:
        lo, hi = trace.marker(MARK_START), trace.marker(MARK_END)
        ends = [e for evs in trace.ops.values() for _, _, e in evs]
        begins = [s for evs in trace.ops.values() for _, s, _ in evs]
        if not ends:
            return None
        window = (lo if lo is not None else min(begins),
                  hi if hi is not None else max(ends))
    lo, hi = window
    if hi <= lo:
        return None
    # the chips the cell used: a device the machine holds beside them
    # has a plane, and nothing on it
    chips = sorted(c for c in set(trace.ops) | set(trace.modules)
                   if trace.ops.get(c) or trace.modules.get(c))
    busy = []
    merged_first: list = []
    op_seconds: dict[str, float] = defaultdict(float)
    programs: dict[str, dict] = defaultdict(
        lambda: {"seconds": 0.0, "runs": 0})
    for chip in chips:
        mods = sorted((s, e, program_name(n))
                      for n, s, e in trace.modules.get(chip, ())
                      if e > lo and s < hi)
        mod_starts = [m[0] for m in mods]
        for s, e, prog in mods:
            programs[prog]["seconds"] += min(e, hi) - max(s, lo)
            programs[prog]["runs"] += 1
        evs = [(n, s, e) for n, s, e in trace.ops.get(chip, ())
               if e > lo and s < hi]
        if not evs and mods:   # a trace with modules only
            evs = [(prog, s, e) for s, e, prog in mods]
        total, merged = union_seconds(clip(
            [(s, e) for _, s, e in evs], lo, hi))
        busy.append(total)
        if chip == chips[0]:
            merged_first = merged
        for n, s, e in evs:
            i = bisect.bisect_right(mod_starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            op_seconds[f"{prog}/{n}"] += min(e, hi) - max(s, lo)
    if not busy or not sum(busy):
        return None
    n = len(chips)
    for p in programs.values():
        p["seconds"] /= n
        p["runs"] /= n
    gaps = []
    t = lo
    for s, e in merged_first:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return {
        "window_s": hi - lo,
        "busy_s": sum(busy) / n,
        "chips": n,
        "programs": dict(programs),
        "device_ops": sorted(op_seconds.items(), key=lambda kv: -kv[1]),
        "idle_gaps": sorted(attribute_gaps(gaps, spans or []).items(),
                            key=lambda kv: -kv[1]),
    }
