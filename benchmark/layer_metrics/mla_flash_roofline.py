"""The latent-attention kernels' share of their roofline: `flash_roofline`'s
arithmetic for the causal calls on a 192-wide key beside a 128-wide value.
The least time the chip could take for `kernel_costs()["flash"]` of the
configuration (which counts the TRUE 192-wide contraction, not the 256 the
kernel pads it to) over the device time of the Pallas calls under the
attention nodes' scopes (`ff.ring_attention.<name>`, forward and backward
with its delta kernel). By scope and not by every Pallas call of the step, as
`flash_roofline` takes them: this cell's step holds the gated delta rule's
kernels too, and a Pallas call's family name (`pallas/custom-call`) does not
say whose it is. Absent where the trace holds no such row, or the
configuration states no such cost."""

from layer_metrics.kda_scan_roofline import bound as _bound
from step_anatomy import for_context
from trace_reduce import PALLAS

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

KIND = "ring_attention"


def kernel_ms(ctx):
    """Milliseconds per traced step of the Pallas calls under the attention
    nodes' scopes, mean over chips; None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    seconds = sum(
        s for (_phase, kind, _name, family), s in table["rows"].items()
        if kind == KIND and family.startswith(PALLAS)
    )
    return 1e3 * seconds / ctx["steps_traced"]


def bound(ctx):
    """("compute" | "memory", least seconds per step on one chip), or None."""
    return _bound(ctx, "flash")


def read(ctx):
    ms = kernel_ms(ctx)
    least = bound(ctx) if ms else None
    if not least:
        return None
    return 100.0 * least[1] * 1e3 / ms
