"""The latent expert nodes' share of their roofline: the least time the chip
could take for the router, both latent projections, the held groups' matmuls
at the rows a uniform router sends them and the shared expert of a step,
forward and backward (the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s; `kernel_costs()["latent_moe"]` of the configuration) over the
device time of everything under the expert nodes' scopes (`latent_moe_ms`),
so the sort, the gathers and the scatter-add count against the kernels.
Absent where `latent_moe_ms` is, or the configuration states no such cost."""

from peaks import peaks_for
from step_anatomy import ms_per_step

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

KINDS = ("experts",)


def bound(ctx):
    """("compute" | "memory", least seconds per step on one chip), or None."""
    costs = getattr(ctx["module"], "kernel_costs", None)
    cost = costs and costs(
        ctx["config"], ctx["job"]["batch_per_chip"], ctx["job"]["seq"]
    ).get("latent_moe")
    if not cost:
        return None
    peaks = peaks_for(ctx["device_kind"])
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return ("compute", by_flops) if by_flops >= by_bytes else ("memory", by_bytes)


def read(ctx):
    ms = ms_per_step(ctx, kinds=KINDS)
    least = bound(ctx) if ms else None
    if not least:
        return None
    return 100.0 * least[1] * 1e3 / ms
