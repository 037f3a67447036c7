"""Test session setup: 8 host devices for sharding/shard_map tests.

Must run before the first ``import jax`` anywhere in the test session --
XLA reads the flag once at backend init.

Tiers: the ``multidevice`` marker (registered in pyproject.toml, and
excluded from the default addopts selection next to ``slow``) guards
tests that only make sense with several devices -- the device-parallel
retrieval mesh regression suite.  They run as their own CI step with
``-m multidevice``; the ``host_devices`` fixture skips them gracefully
if the forced device count did not take (e.g. jax was already
initialised by a plugin).
"""

import os

import pytest

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"


@pytest.fixture(autouse=True)
def _reset_obs():
    """Isolate the process-wide obs singletons across tests: counters
    accumulated by one test (e.g. retrace counts, serve totals) must not
    bleed into the next test's snapshot.  Lazy imports keep collection
    cheap for tests that never touch repro."""
    yield
    from repro.obs.metrics import get_registry
    from repro.obs.trace import get_tracer
    get_registry().reset()
    get_tracer().reset(enabled=False)


@pytest.fixture(scope="session")
def host_devices():
    """The forced 8-CpuDevice set; skips if the forcing didn't take."""
    import jax

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip(f"needs 8 forced host devices, found {len(devs)} "
                    "(jax initialised before conftest set XLA_FLAGS?)")
    return devs
