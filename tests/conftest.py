"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-device tests run without TPU hardware via
--xla_force_host_platform_device_count (SURVEY.md section 4). Both
variables are set before jax is imported, which is all it takes to keep
the suite on the CPU platform.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
