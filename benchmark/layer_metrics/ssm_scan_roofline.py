"""The selective scan's share of its roofline: the least time the chip could
take for the scan of every state-space layer, forward and backward (the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s;
`kernel_costs()["ssm_scan"]` of the configuration) over the device time of
the operations under the scan's own scope inside the node's
(`ff.ssm.<name>/scan`), recomputation included. The program's parser names
those operations `<name>/scan`, so they are rows of `step_anatomy`'s one
table. Absent where the trace holds no such row, or the configuration states
no such cost."""

from peaks import peaks_for
from step_anatomy import for_context

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def scan_ms(ctx):
    """Milliseconds per traced step under the scans' scopes, mean over
    chips; None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    seconds = sum(
        s for (_phase, kind, name, _family), s in table["rows"].items()
        if kind == "ssm" and name.endswith("/scan")
    )
    return 1e3 * seconds / ctx["steps_traced"]


def bound(ctx):
    """("compute" | "memory", least seconds per step on one chip), or None."""
    costs = getattr(ctx["module"], "kernel_costs", None)
    cost = costs and costs(
        ctx["config"], ctx["job"]["batch_per_chip"], ctx["job"]["seq"]
    ).get("ssm_scan")
    if not cost:
        return None
    peaks = peaks_for(ctx["device_kind"])
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return ("compute", by_flops) if by_flops >= by_bytes else ("memory", by_bytes)


def read(ctx):
    ms = scan_ms(ctx)
    least = bound(ctx) if ms else None
    if not least:
        return None
    return 100.0 * least[1] * 1e3 / ms
