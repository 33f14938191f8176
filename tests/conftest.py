"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so mesh/sharding code paths are
exercised without TPU hardware (the chip runs chip_smoke.py and bench.py).

JAX reads JAX_PLATFORMS/XLA_FLAGS when it initializes, so both are set here
before anything imports it. KTPU_TEST_PLATFORM points a suite at real
hardware instead: tests that need more devices than the machine has skip
themselves there (one process per chip also rules out the suites that
spawn children). PERF.md records
which suites have run on the chip; tier-1 is the CPU run.
"""

import os

# Enforce the "handlers never mutate delivered/stored objects" convention in
# tests: watch events share the stored dict, so a violating handler must fail
# loudly here rather than silently corrupt the store (see store/mvcc.py).
os.environ.setdefault("KTPU_DEBUG_FREEZE", "1")

_platform = os.environ.get("KTPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def event_loop_policy():
    return asyncio.DefaultEventLoopPolicy()


def run_async(coro):
    """Run a coroutine to completion on a fresh loop (test helper)."""
    return asyncio.run(coro)


async def start_scheduler(store, seed=42, **kw):
    """Shared scheduler bootstrap for e2e-style tests."""
    from kubernetes_tpu.client import InformerFactory
    from kubernetes_tpu.scheduler import Scheduler
    sched = Scheduler(store, seed=seed, **kw)
    factory = InformerFactory(store)
    await sched.setup_informers(factory)
    factory.start()
    await factory.wait_for_sync()
    return sched, factory
