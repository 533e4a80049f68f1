"""Device time per step of the latent-attention nodes with a low-rank query
and a rotary on the shared key slice, forward and backward: every operation
under a scope of kind `ring_attention` (`ff.ring_attention.<name>`; the
module's node counts with the trunk's five). By part of the node, on standard
error: `latent` (the two query projections and their norm, W_kva, the
latent norm, W_kvb and the key's assembly), `rows` (the rotary pass over
the shared slice and the queries' matching columns), `core` (the causal
kernels) and what lies under no part (W_o). The program's counters go beside
them: the form each latent node was lowered with (query rank, rotated
columns, pairing, the core's entry) and the route each attention node took.
Mean over chips. Absent where the trace holds no such scope."""

import json
import sys

from step_anatomy import for_context

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

KIND = "ring_attention"
REST = "projections"  # under the node's scope and no part's


def parts_ms(ctx):
    """{part: ms per traced step} of the attention nodes, mean over chips;
    None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    parts = {}
    for (_phase, kind, name, _family), s in table["rows"].items():
        if kind == KIND:
            part = name.partition("/")[2] or REST
            parts[part] = parts.get(part, 0.0) + 1e3 * s / ctx["steps_traced"]
    return parts


def program_counters():
    """What the program says of its attention nodes, where it keeps such
    counters (a program from before them keeps none)."""
    try:
        from flexflow_tpu.observability import trace
    except ImportError:
        return {}
    return {
        name: getattr(trace, name)()
        for name in ("latent_attention_forms", "attention_routes")
        if hasattr(trace, name)
    }


def read(ctx):
    parts = parts_ms(ctx)
    if not parts:
        return None
    print("mla_rope_ms: " + json.dumps(
        dict(parts_ms=parts, **program_counters())
    ), file=sys.stderr)
    return sum(parts.values())
