"""Search-phase attribution: where compile-time search wall-clock goes.

Extends the step-trace span recorder (observability/trace.py) into the
Unity search: the search loops install a per-search accumulator
(collect_search_phases), and the hot call sites mark their work with
search_phase("tree_build" | "dp" | "leaf_cost" | "match" | "seed_build").
Each phase both emits a `search/<name>` span (`record_span`: the profiler's
host plane, `span_totals()`, and the active TraceRecorder's timeline) and
accumulates milliseconds into the collector, which the search telemetry
reports as `phase_ms` (graph_optimize/mcmc_optimize telemetry ->
FFModel.search_provenance; `tools/profile_search.py` prints it).

Phases NEST (leaf_cost runs inside dp, both inside an evaluation): each
name accumulates independently, so phase_ms is per-phase attribution, not
a partition of wall time.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

from flexflow_tpu.observability.trace import record_span

_ACTIVE: Optional[Dict[str, float]] = None


def active_phase_collector() -> Optional[Dict[str, float]]:
    return _ACTIVE


@contextlib.contextmanager
def collect_search_phases() -> Iterator[Dict[str, float]]:
    """Install a fresh phase accumulator for the body; yields the dict the
    enclosed search_phase calls accumulate into (name -> milliseconds)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = acc = {}
    try:
        yield acc
    finally:
        _ACTIVE = prev


@contextlib.contextmanager
def search_phase(name: str, **args):
    """Attribute the body to `name`: accumulate into the active collector
    (if any) and emit a `search/<name>` span."""
    acc = _ACTIVE
    if acc is None:
        with record_span(f"search/{name}", **args):
            yield
        return
    t0 = time.perf_counter()
    try:
        with record_span(f"search/{name}", **args):
            yield
    finally:
        acc[name] = acc.get(name, 0.0) + (time.perf_counter() - t0) * 1000.0
