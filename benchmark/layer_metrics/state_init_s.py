"""Seconds in `instance.initialize` inside `FFModel.compile`: parameters,
masters, moments and their placement; the total of the program's
`compile/init_state` spans. Absent where the program has no such span."""

from host_spans import total_seconds

LAYER = "entry points"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    return total_seconds(ctx, "compile/init_state")
