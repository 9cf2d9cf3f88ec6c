"""Test environment: force a virtual 8-device CPU platform before jax import.

Multi-chip hardware is not available in CI; sharding paths are validated on
a virtual CPU mesh (xla_force_host_platform_device_count), mirroring the
reference's dummy-device strategy (edgetpu device_type:dummy,
tests/nnstreamer_filter_edgetpu/unittest_edgetpu.cc:30).
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the suite always runs on the virtual CPU mesh, whatever imported jax first
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
