"""The benchmark's CPU tests rehearse the four-chip cell too, so the CPU
backend gets four devices (unless a count is set already).  This runs
before any test starts JAX's backend."""

import os

_FLAGS = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _FLAGS:
    os.environ["XLA_FLAGS"] = \
        f"{_FLAGS} --xla_force_host_platform_device_count=4".strip()
