"""Model FLOPs of a step over the chip's peak for the seconds it was busy:
what utilization would be if the device never idled."""

from peaks import peaks_for

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["steps_traced"]:
        return None
    peak = peaks_for(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    busy_per_step = trace["busy_s"] / ctx["steps_traced"]
    return 100.0 * ctx["flops_per_step"] / (busy_per_step * peak)
