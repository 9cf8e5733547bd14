"""Test config.

- Forces JAX onto a virtual 8-device CPU mesh (the reference's mocker-style
  GPU-free CI, SURVEY.md §4) before jax initializes.
- Runs `async def` tests via asyncio.run (no pytest-asyncio in this env).
- Resets in-process discovery/event-bus state between tests.
"""

import os

# force-set, before jax is imported and reads them: tests run on the
# virtual 8-device CPU mesh whatever the caller's environment says.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Tests (and the worker processes they spawn, which inherit this) run with
# JAX's persistent compilation cache OFF, as they always have: every JAX
# entry point now enables it (dynamo_tpu.enable_compilation_cache), which
# would fill <checkout>/.jax_cache from the suite and make XLA:CPU log a
# multi-KB line per cache hit into stdout pipes the tests do not drain.
# tests/test_fast_resume.py turns it back on for the run it checks.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: deep-budget tests excluded from tier-1 (-m 'not slow')")


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }

        async def _run():
            return await fn(**kwargs)

        asyncio.run(_run())
        return True
    return None


@pytest.fixture(autouse=True)
def _reset_inproc_state():
    yield
    from dynamo_tpu.runtime.discovery import MemDiscovery
    from dynamo_tpu.runtime.event_plane import _InProcBus

    MemDiscovery.reset()
    _InProcBus.reset()
