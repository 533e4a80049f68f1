"""Per-device machine constants, keyed by `jax.devices()[0].device_kind`.

The one table the analytic search (`FFModel._compile_searched`), the
serving planner (`serving/plan.py`) and the benchmark's utilization
figures (`chip_smoke.py`) read. A device kind that is not in
the table raises: pricing an unknown chip with another chip's peaks gives
plans and utilizations that look plausible and mean nothing.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class MachineConstants:
    peak_flops: float  # dense bf16 matmul FLOP/s, one device
    hbm_gbps: float  # device memory bandwidth, GB/s
    intra_node_gbps: float  # chip-to-chip (ICI) bandwidth, GB/s
    inter_node_gbps: float  # host-to-host (DCN) bandwidth, GB/s
    ici_latency_ms: float  # per-collective dispatch cost inside a node
    dcn_latency_ms: float  # per-collective dispatch cost across nodes
    source: str


MACHINE_CONSTANTS: Dict[str, MachineConstants] = {
    "TPU v5 lite": MachineConstants(
        peak_flops=197e12,
        hbm_gbps=819.0,
        intra_node_gbps=200.0,
        inter_node_gbps=25.0,
        ici_latency_ms=0.001,
        dcn_latency_ms=0.01,
        source=(
            'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
            "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect. "
            "The DCN bandwidth and both latencies are planning assumptions "
            "(no multi-host run has measured them)."
        ),
    ),
    "cpu": MachineConstants(
        peak_flops=5e10,
        hbm_gbps=10.0,
        intra_node_gbps=2.0,
        inter_node_gbps=1.0,
        ici_latency_ms=0.1,
        dcn_latency_ms=0.2,
        source=(
            "Emulation constants for the virtual CPU test mesh: a search "
            "costed with TPU link numbers but executed on host-emulated "
            "collectives picks plans the emulation cannot afford. Not a "
            "description of any CPU."
        ),
    ),
}


def machine_constants(device_kind: Optional[str] = None) -> MachineConstants:
    """Constants of `device_kind` (default: the attached device's)."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return MACHINE_CONSTANTS[device_kind]
    except KeyError:
        raise ValueError(
            f"no machine constants for device_kind {device_kind!r}: add a "
            "sourced row to flexflow_tpu/compiler/machine_constants.py "
            f"(known: {sorted(MACHINE_CONSTANTS)})"
        ) from None
