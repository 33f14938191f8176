"""Seeded Poisson arrivals — the benchmark's copy of the arithmetic in
`kubernetes_tpu/perf/churn/arrivals.py` (PoissonArrivals, stable_seed).

A timeline is a pure function of (rate, seed, duration): the same seed
gives the same offsets in every run and on every commit. The copy is
held equal to the original by tests/benchmark/test_copies.py.
"""

from __future__ import annotations

import hashlib
import random


def stable_seed(*parts) -> int:
    """An rng seed from mixed parts: sha256 of their text, not hash()
    (str hashes change from process to process)."""
    h = hashlib.sha256("|".join(str(p) for p in parts).encode()).digest()
    return int.from_bytes(h[:8], "big")


def poisson_timeline(rate: float, seed: int, duration: float) -> list[float]:
    """Sorted arrival offsets in [0, duration) of a homogeneous Poisson
    process at `rate` arrivals a second."""
    if rate <= 0:
        raise ValueError(f"arrival rate must be > 0, got {rate}")
    rate, duration = float(rate), float(duration)
    rng = random.Random(stable_seed("poisson", int(seed), rate, duration))
    out: list[float] = []
    t = rng.expovariate(rate)
    while t < duration:
        out.append(t)
        t += rng.expovariate(rate)
    return out
