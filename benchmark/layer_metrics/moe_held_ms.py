"""Device time per step of expert nodes that HOLD a share of their experts,
forward and backward: `moe_ms`'s reader under this cell's name (that metric
lists its cells, and this PR may not edit the list). Every operation under a
scope of kind `experts` counts: the router over all the experts, the sort,
the gathers, the grouped matmuls over the held groups, the combine and the
shared expert. Absent where the trace holds no such scope."""

from layer_metrics.moe_ms import LAYER, MOVES, SOURCE, UNIT, read  # noqa: F401
