"""Measured step time over the searched winner's estimate (1 = the cost
model priced the plan right; the search ranks plans by this estimate)."""

import statistics

LAYER = "search"
UNIT = "ratio"
SOURCE = "host_clock"
MOVES = "tokens_per_s"


def read(ctx):
    prov = ctx["provenance"]
    if (
        not ctx["on_chip"]
        or not isinstance(prov, dict)
        or not prov.get("estimated_ms")
        or not ctx["step_seconds"]
    ):
        return None
    measured_ms = 1e3 * statistics.median(ctx["step_seconds"])
    return measured_ms / float(prov["estimated_ms"])
