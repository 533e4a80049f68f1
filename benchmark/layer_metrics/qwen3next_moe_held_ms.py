"""Device time per step of expert nodes that HOLD a share of their experts
(32 of 512, 10 a token, a gated shared expert beside them), forward and
backward: `moe_ms`'s reader under this cell's name, as `lfm2_moe_held_ms`
is. Every operation under a scope of kind `experts` counts: the router over
all 512 experts, the sort, the gathers, the grouped matmuls over the held
groups, the combine, the shared expert and its gate. The program's routing
counter goes to standard error beside it. Absent where the trace holds no
such scope."""

from layer_metrics.lfm2_moe_held_ms import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read,
)
