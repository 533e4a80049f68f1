"""XLA compilations after warm-up, from jax.monitoring; has to read 0."""

LAYER = "entry points"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(ctx):
    return ctx["counters"].compiles["window"]
