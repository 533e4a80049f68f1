"""Of the routing decisions of the last `fit` call (N tokens x k experts a
step and expert node), the share that landed on experts this chip holds: the
latent rows its grouped matmuls really ran. `moe_held_rows_pct`'s counter
(`flexflow_tpu.observability.routing`) under this cell's name (that metric
lists its cells, and this PR may not edit the list); a uniform router gives
held / all experts, 8 / 512 = 1.5625 %. The windows of rows each node ran a
step (1 where the straight-line first window held every row) go to standard
error beside the shares by node. Absent where the program keeps no such
counter or the graph holds every expert it routes to."""

import json
import sys

LAYER = "kernels"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(ctx):
    try:
        from flexflow_tpu.observability import routing
    except ImportError:  # a program from before the counter
        return None
    counted = routing.published()
    if counted is None:
        return None
    windows = counted.get("windows_per_step")
    print("latent_held_rows_pct: " + json.dumps({
        "nodes": counted["nodes"],
        "held_rows_pct_by_node": [
            100.0 * r.sum() / d
            for r, d in zip(counted["rows"], counted["decisions"])
        ],
        "max_over_mean_held_load": counted["max_over_mean_held_load"],
        "windows_per_step_by_node": (
            None if windows is None else [float(w) for w in windows]
        ),
    }), file=sys.stderr)
    return counted["held_rows_pct"]
