"""The column blocks a group's scan goes as on the Pallas kernels, the
fewest over the state-space nodes the program lowered: its own counter
(`observability/trace.scan_column_blocks`, kept where a state-space node is
lowered: 4 for a 4,096-column group in blocks of 1,024, 1 for a group a
program holds whole, 0 for a node that fell back to the XLA form, whose
`[chunks, heads, Q, Q]` decay masks go through HBM: the regression this
guards). By node on standard error. Absent where the program keeps no such
counter, or lowered no state-space node."""

import json
import sys

LAYER = "kernels"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(ctx):
    try:
        from flexflow_tpu.observability import trace
    except ImportError:
        return None
    counted = getattr(trace, "scan_column_blocks", None)
    counted = counted() if counted else {}
    if not counted:
        return None
    print("granite_scan_column_blocks: " + json.dumps(counted), file=sys.stderr)
    return min(counted.values())
