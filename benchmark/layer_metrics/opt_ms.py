"""Device time of the optimizer's update per step: every operation under the
scope `ff.optimizer`. Mean over chips."""

from step_anatomy import ms_per_step

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    return ms_per_step(ctx, phase="opt")
