"""Seconds this process spent tracing Python to jaxprs and lowering jaxprs
to MLIR, as `jax.monitoring` reports them to the program's own table
(`observability/trace.py`: `LOWERING_EVENTS`): what the program's Python
costs set-up (the interpreter over the graph, the Pallas bodies), as against
XLA's compile (`xla_compile_s`). Read when the window has ended; the window
adds nothing where `compiles_in_window` reads 0. Absent where the program
keeps no such table."""

from host_spans import program_trace, total_seconds

LAYER = "lowering and backends"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    trace = program_trace()
    if trace is None:
        return None
    return total_seconds(ctx, *trace.LOWERING_EVENTS)
