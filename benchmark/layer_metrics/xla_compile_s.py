"""Seconds in XLA backend compilation (or loading from the persistent
cache) during set-up, summed over every program, from jax.monitoring."""

LAYER = "lowering and backends"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    return ctx["counters"].compile_seconds["setup"]
