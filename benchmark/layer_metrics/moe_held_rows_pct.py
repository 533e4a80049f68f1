"""Of the routing decisions of the last `fit` call (N tokens x k experts a
step and expert node), the share that landed on experts this chip holds:
the rows its grouped matmuls really ran. From the program's own counter
(`flexflow_tpu.observability.routing`: the step keeps the held groups' sizes
among its metric values, `fit` sums them, the model publishes the sum). A
uniform router gives held / all experts; the fullest held expert over the
mean held expert goes to standard error beside it. Absent where the program
keeps no such counter or the graph holds every expert it routes to."""

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
    print("moe_held_rows_pct: " + json.dumps({
        "nodes": counted["nodes"],
        "held_rows_pct_by_node": [
            100.0 * r.sum() / d
            for r, d in zip(counted["rows"], counted["decisions"])
        ],
        "max_over_mean_held_load": counted["max_over_mean_held_load"],
    }), file=sys.stderr)
    return counted["held_rows_pct"]
