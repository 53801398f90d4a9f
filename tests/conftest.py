"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see 1 device;
only tests that spawn their own debug mesh (xla_force_host_platform_device_count
in a subprocess) use more."""
import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.key(0)
