"""Seconds inside `FFModel.compile`: search, verifiers and state init."""

LAYER = "entry points"
UNIT = "s"
SOURCE = "host_clock"
MOVES = "setup_s"


def read(ctx):
    return ctx["spans"].get("compile_call")
