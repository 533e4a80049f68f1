"""Seconds JAX spent tracing the program's own train step to a jaxpr and
lowering that jaxpr to MLIR, each second once: the step's rows of the
program's by-function table (`observability/trace.py`:
`lowering_by_function()`; a top-level trace event in which the step's body
ran, and the `jit(<name>)` lowering that followed it), summed over the
process. `step_lower_s` holds these seconds with every nested `jax.jit`
counted again and with the benchmark's own reference and loss reader; what
the difference is made of goes to standard error as the program's
`setup_report()`: the seconds before the program, lowering by function, the
step's trace by node kind and the double count. Read when the window has
ended. Absent where the program keeps no such table."""

import sys

from host_spans import program_trace

LAYER = "lowering and backends"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    trace = program_trace()
    if trace is None or not hasattr(trace, "lowering_by_function"):
        return None
    print("set-up by owner\n" + trace.setup_report(top=24), file=sys.stderr)
    return sum(
        row["step_s"]
        for stages in trace.lowering_by_function().values()
        for stage, row in stages.items() if stage in ("trace", "to_mlir")
    )
