"""Host seconds JAX's traces of the train step spent under the program's
node scopes, all node kinds together: the interpreter's Python for each
node, its kernels' bodies and the `jnp` calls under it
(`observability/trace.py`: `node_trace_seconds()`, the rows that are no part
of `STEP_SCOPES`). What the step's trace spent outside them is the loss, the
optimizer, the backward pass and the glue; the table by kind is part of the
report `step_lower_own_s` sends to standard error. Absent where the program
keeps no such table."""

from host_spans import program_trace

LAYER = "lowering and backends"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    trace = program_trace()
    if trace is None or not hasattr(trace, "node_trace_seconds"):
        return None
    return sum(
        row["seconds"]
        for kind, row in trace.node_trace_seconds().items()
        if kind not in trace.STEP_SCOPES
    )
