"""Device time per step of the looped layers, forward and backward: every
operation under the scope of a node that `layer_nodes()` of the
configuration names, whatever its pass (`ff.<kind>.<name>#<pass>`: the four
norms, the attention node, the SwiGLU's three matmuls with its activation and
product, and the two residual adds of every layer, each of the passes). By
pass and phase on standard error, with the route each attention node took
beside them. Mean over chips. Absent where the trace holds no such scope (a
program whose nodes carry no pass), or the configuration names no such
nodes."""

import json
import sys

from step_anatomy import for_context

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

PASS = "#"


def rows_of(ctx, names):
    """[(phase, kind, node, pass, family, ms per traced step)] of the
    operations under the scopes of the nodes `names` (without their pass)
    that carry a pass, mean over chips; None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    wanted = set(names)
    out = []
    for (phase, kind, name, family), s in table["rows"].items():
        node, mark, at = name.partition("/")[0].partition(PASS)
        if not mark or node not in wanted:
            continue
        out.append((phase, kind, node, at, family, 1e3 * s / ctx["steps_traced"]))
    return out


def by_pass_and_phase(rows):
    table = {}
    for phase, _kind, _node, at, _family, ms in rows:
        key = f"pass{at}.{phase}"
        table[key] = table.get(key, 0.0) + ms
    return dict(sorted(table.items()))


def attention_routes():
    try:
        from flexflow_tpu.observability import trace
    except ImportError:
        return None
    return trace.attention_routes() if hasattr(trace, "attention_routes") else None


def read(ctx):
    names = getattr(ctx["module"], "layer_nodes", None)
    rows = names and rows_of(ctx, names(ctx["config"]))
    if not rows:
        return None
    print("ouro_loop_ms: " + json.dumps({
        "by_pass_and_phase_ms": by_pass_and_phase(rows),
        "attention_routes": attention_routes(),
    }), file=sys.stderr)
    return sum(row[-1] for row in rows)
