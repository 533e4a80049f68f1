"""Host milliseconds a step that the fit loop spent pulling its next batch:
the total of the program's `fit/next_batch` spans in the traced window over
the steps traced (slicing the host arrays and placing one batch; under fused
dispatch the wait for the producer's window). Absent where the trace holds
no program span."""

from host_spans import for_context

LAYER = "entry points"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(ctx):
    reduced = for_context(ctx)
    if reduced is None or "fit/next_batch" not in reduced["spans"]:
        return None
    pulls = reduced["spans"]["fit/next_batch"]
    return pulls["total_ns"] / 1e6 / ctx["steps_traced"]
