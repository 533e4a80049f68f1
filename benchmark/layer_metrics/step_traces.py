"""How many times JAX traced the train step in this process: the program's
`step_trace` counter, bumped in the step functions' bodies, which run only
under a trace. One is the least; every lowering whose arguments are not
placed and typed as `fit`'s costs another. Absent where the program keeps no
such counter."""

from host_spans import program_trace, totals_for_context

LAYER = "lowering and backends"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    trace = program_trace()
    if trace is None:
        return None
    return totals_for_context(ctx).get(trace.STEP_TRACE, {"count": 0})["count"]
