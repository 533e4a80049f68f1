"""The causal d = 128 attention kernels' share of their roofline under the
looped attention nodes: the least time the chip could take for
`kernel_costs()["flash"]` of the configuration (7 products over the causal
half of the pairs at the true 16 heads of 128 and 8,192 positions, times the
layer applications of a step) over the device time of the Pallas calls that
do that work under the scopes of the attention nodes of every pass
(`ff.ring_attention.attn<i>#<pass>`): the forward kernel in the forward
phase, the backward kernel and its delta kernel. A forward kernel in the
BACKWARD phase is a recomputed one (`recomputed_passes` of the
configuration, a choice of the plan's): its time is in neither side of the
share and goes to standard error beside the kept kernels', so that the share
moves with the kernels alone and a change of plan shows in its own number.
The projections, the rotary and the norms are the node's time and not the
kernels'. Absent where the trace holds no such Pallas call, or the
configuration states no such cost."""

import json
import sys

from trace_reduce import PALLAS

from layer_metrics.flash_roofline import bound
from layer_metrics.ouro_loop_ms import rows_of

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

ATTENTION = "ring_attention"
FORWARD_KERNEL = "flash_fwd"


def read(ctx):
    names = getattr(ctx["module"], "layer_nodes", None)
    if names is None or not hasattr(ctx["module"], "kernel_costs"):
        return None
    kept = again = 0.0
    for phase, kind, _node, _at, family, ms in rows_of(
        ctx, names(ctx["config"])
    ) or ():
        if kind != ATTENTION or not family.startswith(PALLAS):
            continue
        if phase == "bwd" and FORWARD_KERNEL in family:
            again += ms
        else:
            kept += ms
    if not kept:
        return None
    _, least = bound(ctx)
    print("ouro_flash_roofline: " + json.dumps({
        "kept_kernels_ms": kept, "recomputed_forward_kernels_ms": again,
        "least_ms": 1e3 * least,
    }), file=sys.stderr)
    return 100.0 * least / (1e-3 * kept)
