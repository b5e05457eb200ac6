"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Mirrors the reference's single-node CT strategy (SURVEY §4): the full
match/dispatch logic runs on one host; multi-chip behaviour is
exercised on a virtual device mesh (xla_force_host_platform_device_count)
exactly as the driver's dryrun does.

Env vars must be set before jax initializes a backend. The tests
run on the CPU; the program runs on the chip (``chip_smoke.py``).
The persistent compile cache that ``Node.start`` switches on is kept
off here: tests must not depend on, or race over, a cache directory.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio  # noqa: E402
import inspect  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(autouse=True)
def _zone_isolation():
    """The zone registry is process-global (the reference's ETS
    snapshot); tests that register zones (config-file suite) must
    not leak them — a poisoned 'default' zone (tiny max_packet_size)
    breaks unrelated suites in run-order-dependent ways."""
    from emqx_tpu import zone
    saved = dict(zone._zones)
    yield
    zone._zones.clear()
    zone._zones.update(saved)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running tests the tier-1 filter (-m 'not slow') "
        "skips; the full ci.sh pytest run includes them")


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests with asyncio.run (no pytest-asyncio in
    this image)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {name: pyfuncitem.funcargs[name]
                  for name in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None
