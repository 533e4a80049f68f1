"""Share of the traced window in which no operation ran, worst chip."""

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    return 100.0 * trace["idle_share_worst_chip"]
