"""The attention kernels' share of their roofline: the least time the chip
could take for their FLOPs and bytes (the larger of FLOPs over peak FLOP/s
and bytes over peak bytes/s; the functions are the configuration's
`kernel_costs`) over the device time of the kernel calls in the trace.
Absent where the compiled step holds no such call."""

from peaks import peaks_for
from trace_reduce import PALLAS, family_seconds

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

# every Pallas call in these configurations' steps is an attention kernel;
# a configuration with another kind of kernel needs the program to name its
# kernels (PERF.md, open questions) and a reader of its own
KERNEL_NAMES = "^" + PALLAS


def bound(ctx):
    """("compute" | "memory", least seconds per step on one chip)."""
    peaks = peaks_for(ctx["device_kind"])
    cost = ctx["module"].kernel_costs(
        ctx["config"], ctx["job"]["batch_per_chip"], ctx["job"]["seq"]
    )["flash"]
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return ("compute", by_flops) if by_flops >= by_bytes else ("memory", by_bytes)


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["steps_traced"]:
        return None
    seconds = family_seconds(trace, KERNEL_NAMES)
    if not seconds:
        return None
    _, least = bound(ctx)
    return 100.0 * least * ctx["steps_traced"] / seconds
