"""The unbanded causal kernels' share of their roofline under the full
node's scope: `mellum2_window_flash_roofline`'s reader for
`kernel_costs()["flash"]` of the configuration (7 products over the causal
half of the pairs at the TRUE 32 query heads of 128) over the device time of
the Pallas calls under the full-attention node's scope (forward, backward and
its delta kernel). Absent where the trace holds no such Pallas call, or the
configuration states no such cost."""

from layer_metrics.mellum2_window_flash_roofline import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read_share,
)

LAYER_TYPE = "full_attention"
COST = "flash"


def read(ctx):
    return read_share(ctx, LAYER_TYPE, COST, "mellum2_full_flash_roofline")
