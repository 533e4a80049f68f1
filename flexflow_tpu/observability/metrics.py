"""Run-health telemetry: metrics registry + per-step JSONL event stream.

The training-time twin of `trace.py` (which answers "where did the step's
wall-clock go"): this module answers "is the run itself healthy" — loss,
throughput, gradient/parameter global norms, update-to-param ratio, and
skipped/nonfinite accounting, one JSON object per step appended to
`<metrics_dir>/events.jsonl` so a live run can be tailed and a finished run
diffed against another.

The norm scalars are computed INSIDE the jitted train step
(`step_statistics` below, called from the `_step` functions in
`local_execution/training_backing.py` and `parallel/executor.py`): each
global norm is one fused reduction over the parameter pytree, not a host
round-trip per leaf. The host pays exactly one readback per step, and only
when an event log or health monitor is actually installed.

The event schema is versioned and pinned by a tier-1 test
(tests/test_run_health.py) — downstream dashboards parse these files, so
the key set cannot drift silently.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Dict, List, Optional

# ---------------------------------------------------------------------------
# step event schema
# ---------------------------------------------------------------------------

EVENT_SCHEMA_VERSION = 1

# Every step event carries exactly these keys (tests pin the set; bump
# EVENT_SCHEMA_VERSION when it changes so consumers can dispatch).
STEP_EVENT_FIELDS = (
    "schema",          # EVENT_SCHEMA_VERSION
    "step",            # global step index (FFModel._step_count)
    "loss",            # scalar training loss (may be non-finite)
    "wallclock_ms",    # host wall-clock of this step incl. dispatch+sync
    "tokens_per_s",    # label elements per second at this step's wallclock
    "grad_norm",       # global L2 norm over all parameter gradients
    "param_norm",      # global L2 norm over all parameters (post-update)
    "update_ratio",    # ||param_new - param_old|| / (||param_old|| + eps)
    "skipped",         # True when the skip_step policy dropped the update
    "nonfinite",       # True when loss or grad_norm was non-finite
)


# ---------------------------------------------------------------------------
# in-jit step statistics
# ---------------------------------------------------------------------------


def global_norm(tree) -> "object":
    """Global L2 norm over a pytree of arrays as ONE fused reduction chain
    (sum of per-leaf square-sums, sqrt once). f32 accumulation so bf16
    compute params don't overflow the squares."""
    import jax
    import jax.numpy as jnp

    leaves = [x for x in jax.tree_util.tree_leaves(tree) if hasattr(x, "dtype")]
    if not leaves:
        return jnp.zeros((), jnp.float32)
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    return jnp.sqrt(sq)


def step_statistics(old_params, new_params, grads, loss) -> Dict[str, object]:
    """The per-step health scalars, traced inside the jitted step: gradient
    and parameter global norms, update-to-param ratio, and the finiteness
    flag the health policies key off. Returns a dict of device scalars."""
    import jax
    import jax.numpy as jnp

    grad_norm = global_norm(grads)
    param_norm = global_norm(new_params)
    update = jax.tree_util.tree_map(
        lambda n, o: n.astype(jnp.float32) - o.astype(jnp.float32),
        new_params,
        old_params,
    )
    update_ratio = global_norm(update) / (global_norm(old_params) + 1e-12)
    # param_norm is over the POST-update params: an optimizer-math overflow
    # (finite grads, non-finite update — e.g. lr*grad overflowing f32) must
    # trip `ok` too, or guard_nonfinite would commit the poisoned params
    # and permanently stall a skip_step run
    ok = (
        jnp.isfinite(loss.astype(jnp.float32))
        & jnp.isfinite(grad_norm)
        & jnp.isfinite(param_norm)
    )
    return {
        "grad_norm": grad_norm,
        "param_norm": param_norm,
        "update_ratio": update_ratio,
        "ok": ok,
    }


def finalize_step(
    collect: bool,
    guard: bool,
    old_params,
    new_params,
    grads,
    loss,
    old_opt_state,
    new_opt_state,
):
    """The shared tail of every training backend's jitted `_step`
    (ModelTrainingInstance and DistributedTrainingInstance — ONE
    definition so the DP and searched-PCG telemetry can never diverge):
    compute the fused step statistics and, under the skip_step/raise
    guard, keep the pre-step params/optimizer state when the step went
    non-finite. Returns (params, opt_state, stats-or-None).

    guard implies collect (the guard needs the `ok` flag): a backend that
    asks for the guard alone must still get it, not a silent no-op."""
    collect = collect or guard
    if not collect:
        return new_params, new_opt_state, None
    stats = step_statistics(old_params, new_params, grads, loss)
    if guard:
        new_params = guard_nonfinite(stats["ok"], new_params, old_params)
        new_opt_state = guard_nonfinite(
            stats["ok"], new_opt_state, old_opt_state
        )
    return new_params, new_opt_state, stats


def guard_nonfinite(ok, new_tree, old_tree):
    """Keep `old_tree` wherever the step went non-finite (the skip_step /
    raise policies: a NaN update must never reach the parameters). Traced
    inside the jitted step; `ok` is the scalar flag from step_statistics."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(ok, n, o) if hasattr(n, "dtype") else n,
        new_tree,
        old_tree,
    )


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class Counter:
    """Monotonic event count (steps, skipped steps, nonfinite trips)."""

    def __init__(self) -> None:
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    """Last-observed scalar (current loss, current grad norm)."""

    def __init__(self) -> None:
        self.value: Optional[float] = None

    def set(self, v: float) -> None:
        self.value = float(v)


def nearest_rank_percentile(sorted_samples, q: float) -> Optional[float]:
    """Nearest-rank percentile over pre-sorted samples: ceil(q/100 * n) - 1.

    The ONE percentile convention for the whole repo (serving `summary()`
    and `Histogram.percentile` both route here — they disagreed once:
    Histogram's old `int(round(q/100*(n-1)))` index reported the MEAN of a
    2-sample p50 position, serving's nearest-rank the lower sample, so the
    same stream summarized differently per subsystem). Pinned by a shared
    test in tests/test_drift.py."""
    import math

    n = len(sorted_samples)
    if not n:
        return None
    return sorted_samples[min(n - 1, max(math.ceil(q / 100.0 * n) - 1, 0))]


class Histogram:
    """Streaming scalar distribution: count/sum/min/max + reservoir for
    percentile summaries (bounded memory over long runs)."""

    def __init__(self, reservoir: int = 512) -> None:
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self._reservoir_size = reservoir
        self._samples: List[float] = []

    def observe(self, v: float) -> None:
        import random

        v = float(v)
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        if len(self._samples) < self._reservoir_size:
            self._samples.append(v)
        else:
            # reservoir sampling keeps a uniform sample of the stream
            j = random.randrange(self.count)
            if j < self._reservoir_size:
                self._samples[j] = v

    def percentile(self, q: float) -> Optional[float]:
        return nearest_rank_percentile(sorted(self._samples), q)

    def summary(self) -> Dict[str, Optional[float]]:
        return {
            "count": self.count,
            "sum": self.sum,
            "mean": self.sum / self.count if self.count else None,
            "min": self.min,
            "max": self.max,
            "p50": self.percentile(50),
            "p95": self.percentile(95),
        }


class MetricsRegistry:
    """Named counters/gauges/histograms with a JSON-serializable snapshot.
    Get-or-create semantics so emitters never coordinate registration."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, Gauge] = {}
        self.histograms: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            return self.counters.setdefault(name, Counter())

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            return self.gauges.setdefault(name, Gauge())

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            return self.histograms.setdefault(name, Histogram())

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {
                "counters": {k: c.value for k, c in self.counters.items()},
                "gauges": {k: g.value for k, g in self.gauges.items()},
                "histograms": {
                    k: h.summary() for k, h in self.histograms.items()
                },
            }


# ---------------------------------------------------------------------------
# step event log
# ---------------------------------------------------------------------------


def _scalar(v) -> Optional[float]:
    """Host float of a device/np scalar; None stays None; non-finite floats
    serialize as strings ("nan"/"inf") because JSON has no literal for them
    and these are exactly the values the log exists to record."""
    if v is None:
        return None
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f


def _json_safe(f):
    import math

    if isinstance(f, float) and not math.isfinite(f):
        return repr(f)  # "nan" / "inf" / "-inf"
    return f


class StepEventLog:
    """Append-only JSONL step event stream under `metrics_dir`.

    One `emit()` per training step; the registry keeps run-level aggregates
    (steps/skipped/nonfinite counters, loss/grad-norm histograms) which
    `close()` writes as `<metrics_dir>/metrics.json` next to the events."""

    def __init__(
        self, metrics_dir: str, registry: Optional[MetricsRegistry] = None
    ) -> None:
        os.makedirs(metrics_dir, exist_ok=True)
        self.metrics_dir = metrics_dir
        self.path = os.path.join(metrics_dir, "events.jsonl")
        self.registry = registry or MetricsRegistry()
        self._f = open(self.path, "a")

    def emit(
        self,
        step: int,
        loss,
        wallclock_ms: float,
        tokens_per_s: Optional[float] = None,
        grad_norm=None,
        param_norm=None,
        update_ratio=None,
        skipped: bool = False,
        nonfinite: bool = False,
    ) -> Dict[str, object]:
        import math

        event = {
            "schema": EVENT_SCHEMA_VERSION,
            "step": int(step),
            "loss": _scalar(loss),
            "wallclock_ms": _scalar(wallclock_ms),
            "tokens_per_s": _scalar(tokens_per_s),
            "grad_norm": _scalar(grad_norm),
            "param_norm": _scalar(param_norm),
            "update_ratio": _scalar(update_ratio),
            "skipped": bool(skipped),
            "nonfinite": bool(nonfinite),
        }
        assert tuple(event) == STEP_EVENT_FIELDS
        reg = self.registry
        reg.counter("steps_total").inc()
        if skipped:
            reg.counter("steps_skipped").inc()
        if nonfinite:
            reg.counter("nonfinite_steps").inc()
        if event["loss"] is not None and math.isfinite(event["loss"]):
            reg.gauge("loss").set(event["loss"])
            reg.histogram("loss").observe(event["loss"])
        if event["grad_norm"] is not None and math.isfinite(
            event["grad_norm"]
        ):
            reg.gauge("grad_norm").set(event["grad_norm"])
            reg.histogram("grad_norm").observe(event["grad_norm"])
        if event["wallclock_ms"] is not None:
            reg.histogram("step_ms").observe(event["wallclock_ms"])
        self._f.write(
            json.dumps({k: _json_safe(v) for k, v in event.items()}) + "\n"
        )
        self._f.flush()  # tail-able while the run is live
        return event

    def close(self) -> None:
        if self._f.closed:
            return
        self._f.close()
        with open(os.path.join(self.metrics_dir, "metrics.json"), "w") as f:
            json.dump(self.registry.snapshot(), f, indent=2)


def read_events(metrics_dir: str) -> List[Dict[str, object]]:
    """Parse `<metrics_dir>/events.jsonl` (the test/tooling read path)."""
    path = os.path.join(metrics_dir, "events.jsonl")
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def tail_events(
    metrics_dir: str, cursor: int = 0
) -> "tuple[List[Dict[str, object]], int]":
    """Incremental read of `<metrics_dir>/events.jsonl`: events appended at
    or after byte offset `cursor`, plus the next cursor to pass back in.

    The DriftMonitor and `ffreport --follow` poll a live stream every few
    seconds; re-parsing the whole file each poll is O(run-length^2) over a
    long run, so this seeks. Torn writes are tolerated two ways: a trailing
    line with no newline yet (the writer is mid-`write()`) is NOT consumed
    — the cursor stays before it so the next call re-reads it complete —
    and a newline-terminated line that still fails to parse (interleaved
    multi-process writers) is skipped rather than wedging the tail forever.
    A missing file is an empty stream, not an error (the monitor may start
    before the first step event lands)."""
    path = os.path.join(metrics_dir, "events.jsonl")
    events: List[Dict[str, object]] = []
    try:
        # idle polls are the common case for a live monitor: one stat —
        # no open, no read — when nothing landed since the last call
        if cursor and os.stat(path).st_size == cursor:
            return events, cursor
        f = open(path, "rb")
    except OSError:
        return events, cursor
    with f:
        f.seek(0, os.SEEK_END)
        size = f.tell()
        if cursor > size:  # stream was truncated/rotated: start over
            cursor = 0
        f.seek(cursor)
        buf = f.read()
    next_cursor = cursor
    for raw in buf.split(b"\n"):
        if next_cursor + len(raw) >= cursor + len(buf):
            break  # no trailing newline: torn write, leave for next call
        next_cursor += len(raw) + 1
        line = raw.strip()
        if not line:
            continue
        try:
            events.append(json.loads(line.decode("utf-8")))
        except (ValueError, UnicodeDecodeError):
            continue  # corrupt but complete line: skip, don't wedge
    return events, next_cursor


def append_run_event(metrics_dir: str, kind: str, **payload) -> Dict[str, object]:
    """Out-of-band run lifecycle event (degraded-grid recovery, grid
    resizes) appended to the SAME events.jsonl stream as the per-step
    events, marked by an `event` key instead of `step` — the frozen step
    schema stays untouched and step-event consumers can filter on it."""
    os.makedirs(metrics_dir, exist_ok=True)
    event = {"schema": EVENT_SCHEMA_VERSION, "event": str(kind), **payload}
    with open(os.path.join(metrics_dir, "events.jsonl"), "a") as f:
        f.write(json.dumps(event) + "\n")
    return event


def read_run_events(
    metrics_dir: str, kind: Optional[str] = None
) -> List[Dict[str, object]]:
    """The lifecycle events of a metrics stream (optionally one kind)."""
    return [
        e
        for e in read_events(metrics_dir)
        if "event" in e and (kind is None or e["event"] == kind)
    ]


def _sanitize_doc(obj):
    """Recursively JSON-safe copy: non-finite floats become their repr
    strings (the events.jsonl convention), unknown objects their str —
    a provenance snapshot must never fail to serialize."""
    import math

    if isinstance(obj, dict):
        return {str(k): _sanitize_doc(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_sanitize_doc(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return repr(obj)
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    return str(obj)


def write_provenance(metrics_dir: str, doc: Dict[str, object]) -> str:
    """Snapshot the model's `search_provenance` beside the event stream
    as `<metrics_dir>/provenance.json` (atomic replace) — what lets
    `tools/ffreport.py` render plan-audit fidelity, pipeline bubbles, and
    drift advisories for a metrics dir without the live model object."""
    os.makedirs(metrics_dir, exist_ok=True)
    path = os.path.join(metrics_dir, "provenance.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(_sanitize_doc(doc), f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def read_provenance(metrics_dir: str) -> Optional[Dict[str, object]]:
    """The provenance snapshot of a metrics dir, or None when the run
    never wrote one (metrics predate ISSUE 18, or fit never started)."""
    path = os.path.join(metrics_dir, "provenance.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None
