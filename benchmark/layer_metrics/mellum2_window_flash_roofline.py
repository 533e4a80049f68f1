"""The banded causal kernels' share of their roofline: `flash_roofline`'s
arithmetic. The least time the chip could take for
`kernel_costs()["flash_window"]` of the configuration (7 products over the
PAIRS inside the band, `1024 s - 1024 x 1023 / 2` a head and sequence, at the
TRUE 32 query heads of 128; q and o at 32 heads, k and v at the 4 published
key/value heads; whatever tiles a kernel visits) over the device time of the
Pallas calls under the sliding-window nodes' scopes: the `*_window` forward
and backward and the backward's delta kernel, by scope as `mla_flash_roofline`
reads them (the delta kernel's name carries no `_window`: the scope says
which node's backward it serves). The band
visits 45 of the causal schedule's 136 tiles for 23% of its pairs, so this
share reads at most about two thirds of the full kernels'. What the program
does around the kernels (norm, rotary, repeat) is the node's time:
`mellum2_window_attn_ms` has it. The program's counters (routes, tiles,
rotaries) go to standard error beside the share. Absent where the trace holds
no such Pallas call (the route is `dense`), or the configuration states no
such cost."""

import json
import sys

from layer_metrics.kda_scan_roofline import bound
from layer_metrics.mellum2_window_attn_ms import nodes_ms, program_counters

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

LAYER_TYPE = "sliding_attention"
COST = "flash_window"


def read_share(ctx, layer_type, cost, label):
    nodes = nodes_ms(ctx, layer_type, pallas_only=True)
    ms = sum(nodes.values()) if nodes else None
    least = bound(ctx, cost) if ms else None
    print(label + ": " + json.dumps(dict(
        kernel_ms=ms, bound=least and least[0],
        least_ms=least and 1e3 * least[1], **program_counters(),
    )), file=sys.stderr)
    if not least:
        return None
    return 100.0 * least[1] * 1e3 / ms


def read(ctx):
    return read_share(ctx, LAYER_TYPE, COST, "mellum2_window_flash_roofline")
