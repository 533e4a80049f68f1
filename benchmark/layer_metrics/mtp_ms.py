"""Device time per step of the multi-token-prediction module, forward and
backward: every operation under the scope of a node whose name starts with
`mtp_` (the shared embedding's second use, the module's three norms, its
[2D, D] projection, its latent-attention and expert nodes with their norms
and residual adds, the head's second use) and under the second loss's scope
(`ff.label_loss.mtp_loss`). The gradients of the shared embedding and head
are summed over both uses where the uses meet, which lies under no node's
scope. The step's loss terms by name, with their weights and their means over
the last `fit` call (`flexflow_tpu.observability.trace.loss_terms`: `ff.loss`
the main one), go to standard error beside it. Mean over chips. Absent where
the trace holds no such scope."""

import json
import sys

from step_anatomy import for_context

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"

PREFIX = "mtp_"


def by_node_ms(ctx):
    """{`<kind>.<node>`: ms per traced step} of the module's nodes, mean
    over chips; None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    nodes = {}
    for (_phase, kind, name, _family), s in table["rows"].items():
        node = name.partition("/")[0]
        if node.startswith(PREFIX):
            key = f"{kind}.{node}"
            nodes[key] = nodes.get(key, 0.0) + 1e3 * s / ctx["steps_traced"]
    return nodes


def loss_terms():
    try:
        from flexflow_tpu.observability import trace
    except ImportError:
        return None
    return trace.loss_terms() if hasattr(trace, "loss_terms") else None


def read(ctx):
    nodes = by_node_ms(ctx)
    if not nodes:
        return None
    print("mtp_ms: " + json.dumps(
        {"by_node_ms": nodes, "loss_terms": loss_terms()}
    ), file=sys.stderr)
    return sum(nodes.values())
