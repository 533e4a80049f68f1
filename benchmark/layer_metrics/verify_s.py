"""Seconds in the static verifiers of the searched winner: the total of the
program's `compile/verify` spans (PCG and memory verification in the search,
the overlap plan, the movement-edge predictions, the execution-contract
analysis of the lowered step; not the lowering they read, which is
`compile/lower_step`). Absent where the program has no such span or the
compile searched nothing."""

from host_spans import total_seconds

LAYER = "static verifiers"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(ctx):
    return total_seconds(ctx, "compile/verify")
