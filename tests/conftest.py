"""Test harness config.

Forces JAX onto the CPU with eight virtual devices, so that the tests
never need a chip and the multi-chip sharding paths still run.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


import pytest  # noqa: E402


@pytest.fixture
def sim_seed_base():
    """Seed base for the sim sweep lane: fresh per CI run via
    SIM_SEED_BASE (scripts/sim_sweep.sh derives one from the date), a
    pinned default otherwise so plain pytest stays reproducible."""
    return int(os.environ.get("SIM_SEED_BASE", "1000"))
