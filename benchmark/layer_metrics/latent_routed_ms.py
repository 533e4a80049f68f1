"""Device time per step of what the latent space changes in an expert node,
forward and backward: the operations under the node's `latent` scope (the
projection down in front of the dispatch and up after the combine) and under
its `routed` scope (sort, gathers, grouped matmuls over the held groups,
scatter-add, all on latent rows). The program's parser names those operations
`<name>/latent` and `<name>/routed` (`flexflow_tpu/observability/trace.py`,
`NODE_PARTS`), so they are rows of `step_anatomy`'s one table; the router and
the shared expert, which read the full row, are the rest of `latent_moe_ms`.
Mean over chips. Absent where the trace holds no such row: a program that
does not scope the parts, or a configuration without experts."""

from step_anatomy import for_context

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

PARTS = ("latent", "routed")


def part_ms(ctx, parts):
    """Milliseconds per traced step under the expert nodes' scopes of those
    parts, mean over chips; None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    seconds = sum(
        s for (_phase, kind, name, _family), s in table["rows"].items()
        if kind == "experts" and "/" in name and name.rsplit("/", 1)[1] in parts
    )
    return 1e3 * seconds / ctx["steps_traced"]


def read(ctx):
    ms = part_ms(ctx, PARTS)
    return ms if ms else None
