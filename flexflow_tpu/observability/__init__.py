"""Observability: what the program records about itself while it compiles
and runs. Speeds are the benchmark's (`benchmark/`), read from the device
trace through the scopes `trace` puts on every operation.

- `trace`       -- span/event recorder with host-readback sync boundaries
                   (kernels/profiling.force_sync discipline), emitting
                   Chrome-trace JSON next to the XLA trace in
                   `--profile-trace-dir`.
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
    TraceRecorder,
    active_recorder,
    record_span,
    set_recorder,
    trace_session,
)
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
    "TraceRecorder",
    "active_recorder",
    "record_span",
    "set_recorder",
    "trace_session",
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
