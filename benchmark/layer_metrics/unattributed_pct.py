"""Busy device time under no `ff.` scope over busy device time: how much of
the step the program's scopes do not cover (XLA's own operations, such as
the copies of memory-space assignment). `fwd_ms`, `bwd_ms`, `opt_ms`,
`attention_ms` and `parallel_op_ms` mean little where this is large."""

from step_anatomy import for_context, seconds

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    table = for_context(ctx)
    if table is None:
        return None
    return 100.0 * seconds(table, phase="unattributed") / table["busy_s"]
