"""Routing counters of the expert nodes that hold a share of their experts
(`ExpertsAttrs.held_experts`): how many of a step's N x k routing decisions
landed on each expert held here, and how many windows of rows the node took
to run them (`kernels/moe._held_rows_forward`: 1 where the straight-line
first window was the whole call, more where its loop ran).

The counts exist inside the step program anyway (they are the group sizes
of the grouped matmuls, and the loop's trip count), so keeping them costs
one small vector among the step's metric values and no pass over anything.
The path:

- `kernels/moe.experts_forward` hands each held node's counts [held], the
  N x k decisions they are a part of and the windows it ran to `record`
  while the step is traced;
- the training instance traces its loss under `collecting()` and returns
  what was recorded, stacked [nodes, held + 3], as the step's metric value
  `ROUTING_KEY`; `fit` sums metric values over the steps of a call as it
  does for every other one;
- at the end of a `fit` call the model hands that sum to
  `publish_recorded`, and `published()` is where a reader (the benchmark's
  `moe_held_rows_pct`) finds the latest.

A graph without such a node records nothing, its step has no such metric
value and its program is the one it always was.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional

ROUTING_KEY = "routing_held_rows"

_tls = threading.local()
_published: Optional[Dict[str, object]] = None


@contextlib.contextmanager
def collecting():
    """While the body traces, `record` appends to the list this yields."""
    previous = getattr(_tls, "sink", None)
    sink: List[object] = []
    _tls.sink = sink
    try:
        yield sink
    finally:
        _tls.sink = previous


def record(held_counts, decisions: int, windows) -> None:
    """One held node's decisions per held expert, [held] int32 (a tracer),
    of the `decisions` (N x k) its router made this step, and the windows
    it ran for them (an int32 scalar tracer). The row ends in a 1, so that
    the sum over a call's steps ends in their number. Dropped where nobody
    collects."""
    sink = getattr(_tls, "sink", None)
    if sink is not None:
        import jax.numpy as jnp

        tail = jnp.asarray([decisions, windows, 1], held_counts.dtype)
        sink.append(jnp.concatenate([held_counts, tail]))


def held_nodes(graph) -> List[str]:
    """Names of the expert nodes of `graph` that hold a share, in the order
    their counts are recorded (the graph's topological order)."""
    from flexflow_tpu.op_attrs.ops import ExpertsAttrs

    names = []
    for n in graph.topological_ordering():
        attrs = graph.op_attrs(n)
        if isinstance(attrs, ExpertsAttrs) and attrs.held_experts is not None:
            names.append(graph.layer_attrs(n).name or f"n{n.idx}")
    return names


def publish(rows, nodes: List[str], windows=None) -> None:
    """`rows` [nodes, held + 1], summed over the steps of one `fit` call:
    decisions that landed on each held expert, then all the node's
    decisions. `windows` [nodes, 2], summed alike: the windows each node
    ran, then the steps."""
    global _published
    _published = {"rows": rows, "nodes": list(nodes), "windows": windows}


def publish_recorded(table, nodes: List[str]) -> None:
    """`publish` of what `record` stacked, [nodes, held + 3], summed over
    the steps of one `fit` call."""
    import numpy as np

    table = np.asarray(table)
    publish(table[:, :-2], nodes, table[:, -2:])


def published() -> Optional[Dict[str, object]]:
    """The latest `publish`, worked out on the host: `rows` [nodes, held],
    `decisions` [nodes], `held_rows_pct` (decisions on held experts over all
    decisions, mean over nodes), `max_over_mean_held_load` (the fullest
    held expert over the mean held expert, worst node) and
    `windows_per_step` [nodes] (the windows a node ran a step, mean over the
    call's steps: exactly 1 where no step's loop ran; None where `publish`
    was given none); None before any."""
    if _published is None:
        return None
    import numpy as np

    table = np.asarray(_published["rows"], dtype=np.float64)
    rows, decisions = table[:, :-1], table[:, -1]
    mean_load = np.maximum(rows.mean(axis=1), 1e-30)
    windows = _published["windows"]
    if windows is not None:
        ran, steps = np.asarray(windows, dtype=np.float64).T
        windows = ran / steps
    return {
        "nodes": _published["nodes"],
        "rows": rows,
        "decisions": decisions,
        "held_rows_pct": float(100.0 * (rows.sum(axis=1) / decisions).mean()),
        "max_over_mean_held_load": float((rows.max(axis=1) / mean_load).max()),
        "windows_per_step": windows,
    }
