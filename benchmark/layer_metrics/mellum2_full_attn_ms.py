"""Device time per step of the full-attention node (one of the cell's four:
`layer_types` `full_attention`, the YaRN rotary), forward and backward,
projections included: `mellum2_window_attn_ms`'s reader on the other layer
type. Absent where the trace holds no such scope."""

from layer_metrics.mellum2_window_attn_ms import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read_ms,
)

LAYER_TYPE = "full_attention"


def read(ctx):
    return read_ms(ctx, LAYER_TYPE, "mellum2_full_attn_ms")
