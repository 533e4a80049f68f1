"""The gated delta rule's share of its roofline: the least time the chip could
take for the recurrence of every such layer, forward and backward (the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s;
`kernel_costs()["kda_scan"]` of the configuration: the chunk's products at
their least, the triangular system solved once, and q, k, v, the log-decays,
beta and o once a pass) over the device time of the operations under the
recurrence's two parts inside the node's scope (`ff.kda.<name>/scan`, the
chunk-to-chunk pass, and `ff.kda.<name>/prep`, the chunks' operands: the
decayed scores and the triangular inverse), recomputation included: the
recurrence IS its operands and its pass, and the least work counts both. The
program's parser names those operations `<name>/scan` and `<name>/prep`, so
they are rows of `step_anatomy`'s one table. Absent where the trace holds no such row, or
the configuration states no such cost."""

from peaks import peaks_for
from step_anatomy import for_context

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def scan_ms(ctx, parts=("scan", "prep")):
    """Milliseconds per traced step under the given parts' scopes of the
    `kda` nodes, mean over chips; None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    seconds = sum(
        s for (_phase, kind, name, _family), s in table["rows"].items()
        if kind == "kda" and name.rpartition("/")[2] in parts
    )
    return 1e3 * seconds / ctx["steps_traced"]


def bound(ctx, kernel="kda_scan"):
    """("compute" | "memory", least seconds per step on one chip) for
    `kernel_costs()[kernel]` of the configuration, or None where it states
    no such cost."""
    costs = getattr(ctx["module"], "kernel_costs", None)
    cost = costs and costs(
        ctx["config"], ctx["job"]["batch_per_chip"], ctx["job"]["seq"]
    ).get(kernel)
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
