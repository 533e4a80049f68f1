"""Device time per step of expert nodes that HOLD a share of their experts
(8 of 256, 8 a token, beside a shared expert; four layers' nodes and the
module's), forward and backward: `lfm2_moe_held_ms`'s reader (`moe_ms`'s,
with the routing counter on standard error) under this cell's name (that
metric and `moe_held_ms` list their cells, and this PR may not edit the
lists). Every operation under a scope of kind `experts` counts: the router
over all 256 experts, the sort, the gathers, the grouped matmuls over 8
groups of about 256 rows of width 768, the rows' way back to their tokens
and the shared expert. Absent where the trace holds no such scope."""

from layer_metrics.lfm2_moe_held_ms import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read,
)
