"""Device time per step of expert nodes that HOLD a share of their experts
(8 of 64, 4 a token, no shared expert beside them), forward and backward:
`moe_ms`'s reader under this cell's name (that metric and `moe_held_ms` list
their cells, and this PR may not edit the lists). Every operation under a
scope of kind `experts` counts: the router over all the experts, the sort,
the gathers, the grouped matmuls over the held groups and the combine.
The program's routing counter (`moe_held_rows_pct`'s source: the share of
the last `fit` call's decisions that landed on held experts, by node) goes
to standard error beside it. Absent where the trace holds no such scope."""

from layer_metrics import moe_held_rows_pct
from layer_metrics.moe_ms import LAYER, MOVES, SOURCE, UNIT  # noqa: F401
from layer_metrics.moe_ms import read as _read


def read(ctx):
    ms = _read(ctx)
    if ms is not None:
        moe_held_rows_pct.read(ctx)  # prints the counter, where there is one
    return ms
