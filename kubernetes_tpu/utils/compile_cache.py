"""Where JAX keeps compiled programs between runs.

The fused solve's program key is seven static routing arguments times
power-of-two shape buckets, and each first compile on the chip takes
seconds, so a run that starts with no compiled code spends most of its
set-up compiling. JAX's persistent compilation cache removes that for
every run after the first — provided the directory does not move: its
path is part of the cache key.

One rule, applied by `enable_compile_cache()`. Every entry point calls
it before its first compile (`bench.py`, `chip_smoke.py`,
`perf/scheduler_perf.main`, the leader in `multiproc/schedproc`);
nothing calls it at import time.

- `JAX_COMPILATION_CACHE_DIR` set: the directory is the environment's
  and JAX reads it itself; no directory is set in code.
- unset, CPU asked for (`JAX_PLATFORMS=cpu` — tests, pre-flight): no
  cache. CPU compiles take well under a second each, and XLA:CPU logs a
  multi-kilobyte machine-feature warning for every executable it
  reloads (measured on jax 0.9.0: two such lines per cache hit).
- unset otherwise: `<checkout>/.jax_cache`, derived from this file's
  location — never a temporary directory, a pid or a timestamp.

Either way the thresholds that would keep this repo's many small
programs out of the cache are lowered.
"""

from __future__ import annotations

import os
from pathlib import Path

from kubernetes_tpu.utils.jax_platform import cpu_requested

#: the fixed in-checkout directory (listed in .gitignore).
_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str | None:
    """The directory the rule above resolves to; None = no cache."""
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external:
        return external
    if cpu_requested():
        return None
    return str(_CHECKOUT_CACHE)


def enable_compile_cache() -> str | None:
    """Apply the rule; returns the directory in use (None = no cache)."""
    import jax

    directory = compile_cache_dir()
    if directory is None:
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory
