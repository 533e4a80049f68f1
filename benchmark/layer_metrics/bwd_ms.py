"""Device time of the backward pass per step: every operation under a node's
scope or `ff.loss` inside JAX's `transpose(...)`, recomputation included.
Mean over chips."""

from step_anatomy import ms_per_step

LAYER = "lowering and backends"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    return ms_per_step(ctx, phase="bwd")
