"""Which platform the environment asked JAX for (no JAX import)."""

from __future__ import annotations

import os


def cpu_requested() -> bool:
    """Did the environment ask for the CPU backend? JAX takes the first
    entry of JAX_PLATFORMS as its default backend. Tier-1, the verify
    recipe and a pre-flight before a chip call set it; nothing in the
    repo does."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"
