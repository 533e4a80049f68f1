"""Device time per step of the feed-forwards' matmuls, forward and backward:
every operation under the scope of a dense node that `swiglu_nodes()` of the
configuration names (`ff.dense.ffn<i>_w1|w3|w2`: the gate, the up and the
down projection of every layer's SwiGLU, with whatever XLA fused into them:
the SiLU, the product, the residual's multiplier), the other half of this
cell's step beside the mixers. By node and phase on standard error. Mean
over chips. Absent where the trace holds no such scope, or the configuration
names no such nodes."""

import json
import sys

from step_anatomy import for_context

LAYER = "kernels"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    names = getattr(ctx["module"], "swiglu_nodes", None)
    table = for_context(ctx) if names else None
    if table is None:
        return None
    wanted = set(names(ctx["config"]))
    by_phase = {}
    for (phase, kind, name, _family), s in table["rows"].items():
        if kind == "dense" and name.partition("/")[0] in wanted:
            by_phase[phase] = by_phase.get(phase, 0.0) + (
                1e3 * s / ctx["steps_traced"]
            )
    if not by_phase:
        return None
    print("granite_swiglu_ms: " + json.dumps(by_phase), file=sys.stderr)
    return sum(by_phase.values())
