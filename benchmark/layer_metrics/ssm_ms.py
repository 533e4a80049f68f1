"""Device time of the state-space nodes per step, forward and backward: every
operation under a scope of kind `ssm` (`ff.ssm.<name>`), so the two
projections, the short convolution, the scan (and what the backward pass
recomputes of it) and the gated norm all count. Mean over chips. Absent where
the trace holds no such scope (a program without the op, or a configuration
without state-space layers)."""

from step_anatomy import ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

KINDS = ("ssm",)


def read(ctx):
    ms = ms_per_step(ctx, kinds=KINDS)
    return ms if ms else None
