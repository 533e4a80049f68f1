"""Share of the traced window in which a collective ran on a chip while no
compute operation did, on the chip where that share is largest."""

LAYER = "lowering and backends"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["chips"] < 2:
        return None
    return 100.0 * trace["collective_exposed_share_worst_chip"]
