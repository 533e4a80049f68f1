"""Test config: force an 8-device virtual CPU platform BEFORE jax import.

This is the TPU-native analogue of the reference's missing fake-cluster
(SURVEY.md §4): multi-device sharding tests run on a virtual CPU mesh via
--xla_force_host_platform_device_count, so the full tp/pp/dp/sp lowering is
exercised without TPU hardware. Chip runs (chip_smoke.py, benchmark/run.py) do
NOT import this.
"""

import os
import sys

# Pin the platform before any backend initializes so tests really run on
# the virtual 8-device CPU mesh (the chip is reached only through
# chip_smoke.py / benchmark/run.py).
import re

os.environ["JAX_PLATFORMS"] = "cpu"
flags = re.sub(
    r"--xla_force_host_platform_device_count=\d+",
    "",
    os.environ.get("XLA_FLAGS", ""),
)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running perf/regression tests (excluded from tier-1 "
        "via -m 'not slow')",
    )
assert all(d.platform == "cpu" for d in jax.devices()), jax.devices()
assert len(jax.devices()) == 8, jax.devices()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture
def top_k_jvp_refused(monkeypatch):
    """Until the test ends, differentiating `top_k`'s values raises: JAX
    does it by a gather of single elements, whose transpose is a scatter-add
    (`kernels/moe.route` ranks a `stop_gradient` and uses the indices)."""
    from jax.interpreters import ad

    def refuse(primals, tangents, **params):
        raise AssertionError("top_k was differentiated")

    monkeypatch.setitem(ad.primitive_jvps, jax.lax.top_k_p, refuse)


@pytest.fixture
def entered():
    """`entered(cm)` enters a context manager until the test ends (the
    last entered leaves first): how a test opens a node's scope
    (`kernels/context.lowering_node`) or lowers as if for a TPU
    (`context.described_tpu`) part of the way through its body."""
    import contextlib

    with contextlib.ExitStack() as stack:
        yield stack.enter_context
