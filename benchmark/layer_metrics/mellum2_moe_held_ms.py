"""Device time per step of expert nodes that HOLD a share of their experts
(16 of 64, 8 a token, renormalised softmax, no shared expert), forward and
backward: `moe_ms`'s reader under this cell's name, as `lfm2_moe_held_ms`
is. Every operation under a scope of kind `experts` counts: the router over
all 64 experts, the sort, the gathers, the grouped matmuls over the held
groups and the combine. The program's routing counter goes to standard error
beside it. Absent where the trace holds no such scope."""

from layer_metrics.lfm2_moe_held_ms import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read,
)
