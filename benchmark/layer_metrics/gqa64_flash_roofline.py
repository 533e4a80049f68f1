"""The grouped-query attention kernels' share of their roofline at heads of
64 over a causal context of more than one tile: `flash_roofline`'s
arithmetic. The least time the chip could take for `kernel_costs()["flash"]`
of the configuration (the causal half of the pairs at the TRUE 32 query
heads and d = 64; q and o at 32 heads, k and v at the 8 published key/value
heads) over the device time of the Pallas calls under the attention nodes'
scopes (`ff.ring_attention.<name>`, forward and backward), by scope as
`mla_flash_roofline` reads them. What the program does around the kernels
(the per-head norm, the rotary embedding, the repeat of the key/value heads,
any transpose into the kernels' layout) is the node's time and not the
kernels': `attention_ms` has it. The route each attention node took
(`fused_row` / `rows` / `dense`, the program's own counter) goes to standard
error beside the share, so that a change of route says so in its own
output. Absent where the trace holds no such Pallas call (the route is
`dense`), or the configuration states no such cost."""

import json
import sys

from layer_metrics.mla_flash_roofline import bound, kernel_ms

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def attention_routes():
    """The program's `{scope: route}` of the attention nodes it lowered, or
    None for a program that keeps no such counter."""
    try:
        from flexflow_tpu.observability import trace
    except ImportError:
        return None
    routes = getattr(trace, "attention_routes", None)
    return routes() if routes else None


def read(ctx):
    routes = attention_routes()
    ms = kernel_ms(ctx)
    least = bound(ctx) if ms else None
    print("gqa64_flash_roofline: " + json.dumps({
        "attention_routes": routes, "kernel_ms": ms,
        "bound": least and least[0], "least_ms": least and 1e3 * least[1],
    }), file=sys.stderr)
    if not least:
        return None
    return 100.0 * least[1] * 1e3 / ms
