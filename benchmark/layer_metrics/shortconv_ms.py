"""Device time of the double-gated short-convolution nodes per step, forward
and backward: every operation under a scope of kind `shortconv`
(`ff.shortconv.<name>`), so the two projections, the chain between them (the
input gate, the taps, the output gate; `<name>/conv`) and what the backward
pass recomputes of them all count. Mean over chips. Absent where the trace
holds no such scope (a program without the op, or a configuration without
such layers)."""

from step_anatomy import ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

KINDS = ("shortconv",)


def read(ctx):
    ms = ms_per_step(ctx, kinds=KINDS)
    return ms if ms else None
