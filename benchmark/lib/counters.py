"""The program's counters, read through its public text exposition
(`Registry.render()`, the Prometheus format it serves on /metrics) and
never through its objects' fields: a snapshot is {(name, labels): value}
and a window is the difference of two."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")


def snapshot(registry) -> dict[tuple[str, str], float]:
    out: dict[tuple[str, str], float] = {}
    for line in registry.render().splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m:
            continue
        try:
            out[(m.group(1), m.group(2) or "")] = float(m.group(3))
        except ValueError:
            continue
    return out


def total(snap: dict, name: str, match: dict | None = None) -> float | None:
    """Sum of every series of `name` whose labels include `match`; None
    when the program exposes no such series."""
    want = [f'{k}="{v}"' for k, v in (match or {}).items()]
    vals = [v for (n, labels), v in snap.items()
            if n == name and all(w in labels for w in want)]
    return sum(vals) if vals else None


def delta(before: dict, after: dict, name: str,
          match: dict | None = None) -> float | None:
    """Growth of `name` between two snapshots; a series that did not
    exist before counts from 0; None when it does not exist after."""
    b = total(after, name, match)
    if b is None:
        return None
    return b - (total(before, name, match) or 0.0)
