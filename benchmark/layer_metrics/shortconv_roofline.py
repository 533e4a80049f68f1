"""The short-convolution nodes' share of their roofline: the least time the
chip could take for every such node of a step (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s; `kernel_costs()["shortconv"]` of the
configuration: the two projections over three passes, and the node's input,
weights and output once a pass) over the device time of everything under the
nodes' scopes (`shortconv_ms`), so the chain between the projections and the
projection the backward recomputes count against the matmuls. The whole node
and not the chain's rows (`<name>/conv`): XLA may fuse the gates into the
matmuls' epilogues, and a least for the chain alone over those rows could
then read over 100%. The chain's own share of the node's time goes to
standard error beside it. Absent where `shortconv_ms` is, or the
configuration states no such cost."""

import json
import sys

from layer_metrics.kda_scan_roofline import bound as _bound
from layer_metrics.shortconv_ms import KINDS
from step_anatomy import for_context, ms_per_step

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def chain_ms(ctx):
    """Milliseconds per traced step under the nodes' `conv` part, mean over
    chips; None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    seconds = sum(
        s for (_phase, kind, name, _family), s in table["rows"].items()
        if kind in KINDS and name.rpartition("/")[2] == "conv"
    )
    return 1e3 * seconds / ctx["steps_traced"]


def bound(ctx):
    """("compute" | "memory", least seconds per step on one chip), or None."""
    return _bound(ctx, "shortconv")


def read(ctx):
    ms = ms_per_step(ctx, kinds=KINDS)
    least = bound(ctx) if ms else None
    if not least:
        return None
    print("shortconv_roofline: " + json.dumps({
        "node_ms": ms, "chain_ms": chain_ms(ctx), "bound": least[0],
        "least_ms": 1e3 * least[1],
    }), file=sys.stderr)
    return 100.0 * least[1] * 1e3 / ms
