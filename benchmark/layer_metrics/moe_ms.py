"""Device time of the expert nodes per step, forward and backward: every
operation under a scope of kind `experts` (`ff.experts.<name>`), so the
router, the sort, the gathers and the combine count with the grouped matmuls.
Mean over chips. Absent where the trace holds no such scope (a program
without the op, or a configuration without experts)."""

from step_anatomy import ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

KINDS = ("experts",)


def read(ctx):
    ms = ms_per_step(ctx, kinds=KINDS)
    return ms if ms else None
