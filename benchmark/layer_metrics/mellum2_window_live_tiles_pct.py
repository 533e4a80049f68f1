"""The (q block, k block) tiles the sliding-window nodes' forward visits, as
a share of those the causal tile schedule visits at the same shape:
`window_live_tiles_pct`'s reader (the program's own counter,
`observability/trace.window_tiles`) under this cell's name (that metric lists
its cell, and this PR may not edit the list). A 1,024-key window over 8,192
positions in tiles of 512 visits 45 of 136, 33.1%, for 23% of the pairs.
Absent where the program keeps no such counter, or lowered no window node
onto the kernels."""

from layer_metrics.window_live_tiles_pct import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read,
)
