"""Device time per step of the exits, forward and backward: the final norm,
the head and the gate after every pass (`EXIT_NODES` of the configuration,
`ff.<kind>.<name>#<pass>`: the loss node `exit#<pass>` and the elementwise
nodes of the exit distribution between the gate and the losses among them)
and the entropy term's nodes (`ENTROPY` and the sums named after it). By
pass on standard error, with the step's loss terms by name
(`flexflow_tpu.observability.trace.loss_terms`: each exit's expected loss
`ff.label_loss.exit#<pass>` with its `mass`, the mean exit probability of
that pass, and the entropy term `ff.mean_loss.entropy`, means over the last
`fit` call). Mean over chips. Absent where the trace holds no such scope."""

import json
import sys

from layer_metrics.ouro_loop_ms import by_pass_and_phase, rows_of
from step_anatomy import for_context

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def loss_terms():
    try:
        from flexflow_tpu.observability import trace
    except ImportError:
        return None
    return trace.loss_terms() if hasattr(trace, "loss_terms") else None


def entropy_ms(ctx):
    name = getattr(ctx["module"], "ENTROPY", None)
    table = for_context(ctx)
    if table is None or name is None:
        return 0.0
    return sum(
        1e3 * s / ctx["steps_traced"]
        for (_phase, _kind, node, _family), s in table["rows"].items()
        if node.partition("/")[0].partition("#")[0] in (name, name + "_sum")
    )


def read(ctx):
    names = getattr(ctx["module"], "EXIT_NODES", None)
    rows = names and rows_of(ctx, names)
    if not rows:
        return None
    entropy = entropy_ms(ctx)
    print("ouro_exit_ms: " + json.dumps({
        "by_pass_and_phase_ms": by_pass_and_phase(rows),
        "entropy_ms": entropy,
        "loss_terms": loss_terms(),
    }), file=sys.stderr)
    return sum(row[-1] for row in rows) + entropy
