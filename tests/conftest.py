"""Test env: tier-1 runs on eight virtual CPU devices, so the sharding tests
need no TPU hardware and several xdist workers can run side by side (a chip
belongs to one process at a time).  The env vars are set before jax is
imported and the platform is pinned through jax.config as well, before any
backend initializes."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# hermetic worker heartbeats: the suite saturates single-core CI hosts, and
# real loadavg-derived cpu_load would flip every worker to overloaded
os.environ["CORDUM_HOST_LOAD"] = "0"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import asyncio
import inspect

import pytest


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (no pytest-asyncio in env)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(autouse=True)
def _syncsan_zero_reports():
    """CORDUM_SYNC_SANITIZER=1 runs: any interleave race the sanitizer
    diagnosed during a test fails that test (CI runs tier-1 under the
    sanitizer as its own step).  Free when the sanitizer is off."""
    from cordum_tpu.infra import syncsan

    if syncsan.enabled():
        syncsan.reset()
    yield
    if syncsan.enabled():
        reps = syncsan.reports()
        syncsan.reset()
        assert not reps, "sync sanitizer diagnosed interleave races:\n" + \
            "\n".join(str(r) for r in reps)


@pytest.fixture(autouse=True)
def _fresh_startup_record():
    """The start-up record is the process's (``obs/startup.py``): a test
    starts with an empty, open one, whatever the tests before it stamped."""
    from cordum_tpu.obs import startup

    startup.reset()
    yield


@pytest.fixture
def kv():
    from cordum_tpu.infra.kv import MemoryKV

    return MemoryKV()


@pytest.fixture
def bus():
    from cordum_tpu.infra.bus import LoopbackBus

    return LoopbackBus()
