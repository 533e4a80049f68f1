"""Pre-jax-import environment setup for the virtual CPU device mesh.

Every tool that lowers multi-device programs without hardware (ffcheck
--comm, tools/comm_audit.py, tools/memory_audit.py, tests/conftest.py)
must force the XLA host-platform device count BEFORE the first jax
import — and must strip any stale count already in XLA_FLAGS, or the
duplicate flag aborts backend init. This module is deliberately
import-free (no jax, nothing heavy), so calling it never defeats its
own purpose.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = r"--xla_force_host_platform_device_count=\d+"


def force_virtual_device_count(n: int, cpu_platform: bool = False) -> None:
    """Set XLA_FLAGS to expose `n` virtual host-platform devices
    (replacing any stale count). `cpu_platform=True` additionally pins
    JAX to CPU."""
    if cpu_platform:
        os.environ["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(_COUNT_FLAG, "", os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={int(n)}"
    ).strip()
