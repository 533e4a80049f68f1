"""Device time per step of the sliding-window attention nodes (three of the
cell's four: `layer_types` `sliding_attention`), forward and backward,
projections included: every operation under those nodes' scopes
(`ff.ring_attention.<name>`, the names the configuration's `.py` gives by
layer type). The per-head norm, the rotary, the 8-fold repeat of the
key/value heads and the banded kernels all count; by node on standard error,
with the program's counters beside them: the route each attention node took
with its ` window=<keys>` mark, the tiles a banded node visits against the
causal schedule's, and each node's rotary. Mean over chips. Absent where the
trace holds no such scope."""

import json
import sys

from step_anatomy import for_context

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

LAYER_TYPE = "sliding_attention"
ATTENTION = "ring_attention"


def program_counters():
    """What the program says of its attention nodes, where it keeps such
    counters (a program from before them keeps none)."""
    try:
        from flexflow_tpu.observability import trace
    except ImportError:
        return {}
    return {
        name: getattr(trace, name)()
        for name in ("attention_routes", "window_tiles", "rotaries")
        if hasattr(trace, name)
    }


def nodes_ms(ctx, layer_type, pallas_only=False):
    """{node: ms per traced step} of the attention nodes of one layer type
    (every operation under their scopes, or the Pallas calls alone), mean
    over chips; None where there is no table or the configuration names no
    such nodes."""
    from trace_reduce import PALLAS

    names = getattr(ctx["module"], "attention_names", None)
    table = for_context(ctx)
    if table is None or names is None:
        return None
    wanted = set(names(ctx["config"], layer_type))
    out = {}
    for (_phase, kind, name, family), s in table["rows"].items():
        node = name.partition("/")[0]
        if kind != ATTENTION or node not in wanted:
            continue
        if pallas_only and not family.startswith(PALLAS):
            continue
        out[node] = out.get(node, 0.0) + 1e3 * s / ctx["steps_traced"]
    return out


def read_ms(ctx, layer_type, label):
    nodes = nodes_ms(ctx, layer_type)
    if not nodes:
        return None
    print(label + ": " + json.dumps(
        dict(nodes_ms=nodes, **program_counters())
    ), file=sys.stderr)
    return sum(nodes.values())


def read(ctx):
    return read_ms(ctx, LAYER_TYPE, "mellum2_window_attn_ms")
