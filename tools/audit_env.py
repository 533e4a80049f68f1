"""Shared pre-jax-import bootstrap for the audit CLIs (ISSUE 14
satellite).

Every audit tool under tools/ (ffcheck, memory_audit, comm_audit,
exec_audit) needs the same two things before its first jax import: the
repo root on sys.path (the tools run as scripts, so `flexflow_tpu` is
not importable until then), and — for anything that lowers multi-device
programs — the virtual CPU device mesh forced into XLA_FLAGS with the
platform pinned to CPU. ffcheck, memory_audit, and comm_audit each used
to hand-roll both; this module is the one home, delegating the env
mechanics to `flexflow_tpu.utils.virtual_mesh_env` (deliberately
import-light so calling it never defeats its own purpose).
"""

from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bootstrap_repo_path() -> str:
    """Make `flexflow_tpu` importable from a tools/ script; returns the
    repo root."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return REPO


def bootstrap_virtual_mesh(
    n_devices: int = 8, cpu_platform: bool = True
) -> None:
    """Force the `n_devices` virtual CPU mesh BEFORE the first jax
    import (the same mesh tests/conftest.py pins for tier-1). A repeat
    call whose environment is already in force (audit tools import each
    other's builders, re-running their module-level bootstraps) is a
    no-op; a call that would CHANGE the mesh after jax initialized
    raises — it would silently leave the tool on the wrong platform and
    every multi-device lowering would lie."""
    bootstrap_repo_path()
    wanted = f"--xla_force_host_platform_device_count={int(n_devices)}"
    if "jax" in sys.modules:
        # exact token membership: a substring test would accept count=80
        # as satisfying count=8
        if wanted in os.environ.get("XLA_FLAGS", "").split() and (
            not cpu_platform or os.environ.get("JAX_PLATFORMS") == "cpu"
        ):
            return  # already in force before jax initialized
        raise RuntimeError(
            "bootstrap_virtual_mesh must run before the first jax import"
        )
    from flexflow_tpu.utils.virtual_mesh_env import (
        force_virtual_device_count,
    )

    force_virtual_device_count(n_devices, cpu_platform=cpu_platform)


def bootstrap_multislice_mesh(
    n_slices: int = 2, devices_per_slice: int = 4
) -> None:
    """The 2-slice 4+4 virtual topology (ISSUE 17): the same 8 virtual
    CPU devices tier-1 pins, PRESENTED as `n_slices` ICI islands joined
    by DCN. The slice structure is a property of the machine
    specification (`multislice_machine_spec`), not of XLA — the flat
    device list is identical; only the cost model and the slice-aware
    view enumeration see the boundary."""
    bootstrap_virtual_mesh(n_slices * devices_per_slice)


def multislice_machine_spec(
    n_slices: int = 2,
    devices_per_slice: int = 4,
    ici_gbps: float = 2.0,
    dcn_gbps: float = 0.2,
):
    """MachineSpecification of the emulated multi-slice machine: slices
    are the node axis (INTER = DCN, INTRA = ICI). The defaults mirror
    the CPU-emulated search constants (ffmodel._compile_searched) with a
    10x ICI/DCN bandwidth gap — the regime where slice-aware search
    separates from flat (pass dcn_gbps == ici_gbps for the uniform
    counter-example)."""
    bootstrap_repo_path()
    from flexflow_tpu.pcg.machine_view import MachineSpecification

    return MachineSpecification(
        num_nodes=n_slices,
        num_cpus_per_node=1,
        num_devices_per_node=devices_per_slice,
        inter_node_bandwidth=dcn_gbps,
        intra_node_bandwidth=ici_gbps,
    )
