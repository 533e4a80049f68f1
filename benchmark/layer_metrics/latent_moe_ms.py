"""Device time per step of the expert nodes whose experts live in a latent
space, forward and backward: `moe_ms`'s reader under this cell's name (that
metric lists its cells, and this PR may not edit the list). Every operation
under a scope of kind `experts` counts: the router over all the experts, both
latent projections, the sort, the gathers, the grouped matmuls over the held
groups, the scatter-add and the shared expert. Absent where the trace holds
no such scope."""

from layer_metrics.moe_ms import LAYER, MOVES, SOURCE, UNIT, read  # noqa: F401
