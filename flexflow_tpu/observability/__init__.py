"""Observability: what the program records about itself while it compiles
and runs. Speeds are the benchmark's (`benchmark/`), read from the device
trace through the scopes `trace` puts on every operation.

- `trace`       -- the device-trace scopes of every operation, and
                   `record_span`: the program's host spans as profiler
                   annotations on the device trace's clock, totalled in
                   `span_totals()`, with each thread's open spans for
                   the watchdog's hang forensics; set-up by owner:
                   JAX's trace, lowering and compile seconds by
                   function, the step's trace by node kind, the seconds
                   before the program (`setup_report()`).
- `step_account` -- the compiled step's BYTES by the same scopes: XLA's
                   totals and its own peak, who holds the peak, what the
                   forward pass leaves for the backward pass, what lies
                   in `S(1)` (`FFModel.step_account()`,
                   `step_account.report()`); nothing runs until asked.
- `search_phases` -- compile-time twin of `trace`: per-phase wall-clock
                   attribution of the Unity search (tree_build / dp /
                   leaf_cost / match), reported as `phase_ms` in search
                   telemetry and `FFModel.search_provenance`.
- `metrics`     -- run-health telemetry: counter/gauge/histogram registry
                   plus the per-step JSONL event stream (loss, wallclock,
                   tokens/s, grad/param global norms, update ratio) under
                   `--metrics-dir`, with the norms fused into the jitted
                   step.
- `health`      -- nonfinite-grad/loss monitor with warn | skip_step |
                   raise policies and a first-bad-op localizer that
                   replays the step un-fused per-layer.
- `plan_audit`  -- predicted-vs-measured audit of the searched plan:
                   per-op and per-movement-edge misprediction ratios
                   against the cost model that picked it.
"""

from flexflow_tpu.observability.trace import (
    HOST_SPANS,
    TraceRecorder,
    active_recorder,
    count,
    lowering_by_function,
    node_trace_seconds,
    open_span_names,
    pre_program_s,
    record_span,
    set_recorder,
    setup_report,
    span_totals,
)
from flexflow_tpu.observability import step_account
from flexflow_tpu.observability.search_phases import (
    collect_search_phases,
    search_phase,
)
from flexflow_tpu.observability.metrics import (
    EVENT_SCHEMA_VERSION,
    STEP_EVENT_FIELDS,
    MetricsRegistry,
    StepEventLog,
    finalize_step,
    global_norm,
    guard_nonfinite,
    read_events,
    step_statistics,
)
from flexflow_tpu.observability.health import (
    HEALTH_POLICIES,
    HealthMonitor,
    NonFiniteError,
    NonFiniteReport,
    localize_first_nonfinite,
    record_step_health,
)
from flexflow_tpu.observability.plan_audit import (
    AUDIT_SCHEMA_VERSION,
    audit_plan,
)

__all__ = [
    "HOST_SPANS",
    "TraceRecorder",
    "active_recorder",
    "count",
    "lowering_by_function",
    "node_trace_seconds",
    "open_span_names",
    "pre_program_s",
    "record_span",
    "set_recorder",
    "setup_report",
    "span_totals",
    "step_account",
    "collect_search_phases",
    "search_phase",
    "EVENT_SCHEMA_VERSION",
    "STEP_EVENT_FIELDS",
    "MetricsRegistry",
    "StepEventLog",
    "finalize_step",
    "global_norm",
    "guard_nonfinite",
    "read_events",
    "step_statistics",
    "HEALTH_POLICIES",
    "HealthMonitor",
    "NonFiniteError",
    "NonFiniteReport",
    "localize_first_nonfinite",
    "record_step_health",
    "AUDIT_SCHEMA_VERSION",
    "audit_plan",
]
