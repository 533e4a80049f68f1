"""Device time of the forward pass per step: every operation under a node's
scope (`ff.<kind>.<name>`) or `ff.loss` that JAX did not put inside
`transpose(...)` or a rematerialized computation. Mean over chips."""

from step_anatomy import ms_per_step

LAYER = "lowering and backends"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    return ms_per_step(ctx, phase="fwd")
