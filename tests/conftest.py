"""Test configuration: the CPU and an 8-device virtual mesh.

Tests pin JAX to the CPU and give it eight virtual devices, so the
sharded and multi-device paths run here without a chip.  The chip is
reached only through ``chip_smoke.py`` (see README.md).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402  (import after env setup on purpose)

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
