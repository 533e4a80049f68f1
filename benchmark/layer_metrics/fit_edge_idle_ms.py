"""Milliseconds a `fit` call that the worst chip sat idle while the fit loop
was in `fit/begin` (iterator, supervision and checkpoint set-up, before the
first pull) or `fit/end` (the last wait, the metric conversion): `fit`'s
per-call cost as the device sees it, from the idle gaps of the device plane
crossed with the program's spans on the host plane of the same trace. The
rest of the idle table (by span, with the per-step host cost of `dispatch`
and the longest span of each name) goes to standard error. Absent where the
trace holds no program span or no device plane."""

from host_spans import for_context

LAYER = "device"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

EDGES = ("fit/begin", "fit/end")


def read(ctx):
    if ctx.get("trace") is None:
        return None
    reduced = for_context(ctx)
    if reduced is None or not reduced["fit_calls"]:
        return None
    idle = sum(reduced["idle_by_span_s"].get(name, 0.0) for name in EDGES)
    return 1e3 * idle / reduced["fit_calls"]
