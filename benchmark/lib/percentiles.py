"""Exact order statistics — the benchmark's copy of the nearest-rank
rule in `kubernetes_tpu/metrics/registry.py`
(WindowedLatencyRecorder.percentiles_since): the q-quantile of n sorted
values is the value at rank ceil(q*n), never an interpolation and never
a bucket edge."""

from __future__ import annotations

import math
from typing import Iterable, Sequence


def percentiles(values: Sequence[float],
                qs: Iterable[float]) -> dict[float, float]:
    """{q: value}; NaN for every q when there are no values."""
    vals = sorted(values)
    n = len(vals)
    if not n:
        return {q: math.nan for q in qs}
    return {q: vals[min(max(math.ceil(q * n) - 1, 0), n - 1)] for q in qs}


def percentile(values: Sequence[float], q: float) -> float:
    return percentiles(values, (q,))[q]
