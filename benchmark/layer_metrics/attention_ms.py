"""Device time of the attention nodes per step, forward and backward: every
operation under a scope of kind `mha`, `ring_attention` or
`ulysses_attention`, so the node's projections and, where the step holds no
Pallas call, XLA's dense attention count too (`flash_roofline` stays the
kernel-only number). Mean over chips."""

from step_anatomy import ATTENTION_KINDS, ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    return ms_per_step(ctx, kinds=ATTENTION_KINDS)
