"""Device time of the gated delta-rule nodes per step, forward and backward:
every operation under a scope of kind `kda` (`ff.kda.<name>`), so the
projections, the short convolution, the gates, the chunks' operands, the
chunk-to-chunk pass (and what the backward pass recomputes of them) and the
gated norm all count. Mean over chips. Absent where the trace holds no such
scope (a program without the op, or a configuration without such layers)."""

from step_anatomy import ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

KINDS = ("kda",)


def read(ctx):
    ms = ms_per_step(ctx, kinds=KINDS)
    return ms if ms else None
