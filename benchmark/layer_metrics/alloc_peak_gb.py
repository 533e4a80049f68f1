"""The allocator's `peak_bytes_in_use`, largest chip: resident state and
whatever else the process kept on the device."""

LAYER = "device"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "step_hbm_gb"


def read(ctx):
    if ctx["alloc_peak_bytes"] is None:
        return None
    return ctx["alloc_peak_bytes"] / 1e9
