"""Of the routing decisions of the last `fit` call (N tokens x 8 experts a
step and expert node), the share that landed on experts this chip holds: the
rows its grouped matmuls really ran. `latent_held_rows_pct`'s reader
(`flexflow_tpu.observability.routing`, with the windows of rows each node ran
a step on standard error) under this cell's name; a uniform router gives
held / all experts, 16 / 64 = 25 %. Absent where the program keeps no such
counter or the graph holds every expert it routes to."""

from layer_metrics.latent_held_rows_pct import (  # noqa: F401
    LAYER, MOVES, SOURCE, UNIT, read,
)
