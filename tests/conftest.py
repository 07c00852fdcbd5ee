"""Test configuration: run everything on a virtual 8-device CPU mesh so
multi-chip sharding is exercised without TPU hardware (the reference has no
distributed tests at all — SURVEY.md §4).

Tier-1 runs with JAX_PLATFORMS=cpu already; the config update below is for
a bare `pytest` on a machine that has a chip, which would otherwise take it.
Both settings must land before the backend initializes.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
