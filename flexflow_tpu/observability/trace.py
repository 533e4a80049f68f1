"""Two kinds of instrumentation, on two clocks.

**Device-trace scopes** (`node_scope`, `step_scope`, `parse_scope`): every
operation the step programs lower carries the name of the PCG node and the
part of the step it came from, as a `jax.named_scope` of the form
`ff.<kind>.<name>` (a node) or `ff.<part>` (`cast`, `loss`, `optimizer`,
`metrics`, `health`). A named scope is HLO metadata and nothing else: the
program is the same with and without it, there is no switch, and its
"spans" are the device events of a `jax.profiler` trace, on the device's
own clock. JAX wraps the scope in `jvp(...)` / `transpose(...)` as it
differentiates, which is where the phase comes from. This is what answers
"where does the step's time go" since PR 22 put the benchmark on the device
trace: `benchmark/step_anatomy.py` reads these scopes back with
`parse_scope`, which lives here so that format and parser cannot drift.

**Host spans** (`TraceRecorder`, `record_span`, `trace_session`): a
span/event recorder on the host's `perf_counter` clock. The jitted train
step is ONE XLA program, so all it can see of a step is dispatch (enqueue of
the donated step) and device_sync (the wait for results); every sync
boundary waits on the result pytree (`kernels/profiling.force_sync`) before
the span's end timestamp is taken, which serializes host and device (16 ms
of a 262 ms step; my chip run, PR 22), and it cannot look inside the step.
It is not how step time is measured or attributed. What it is still for:

- the watchdog's hang forensics: `open_span_names(tid)` says what a hung
  thread was doing (`step / dispatch / device_sync`, `checkpoint/...`);
- `--profile-trace-dir`'s host timeline: `trace_session` writes the spans
  as Chrome-trace JSON (`chrome://tracing` / Perfetto "traceEvents")
  beside the XLA trace, with the search's phases (`search/<name>`), the
  checkpoint writes and the input pipeline's `host_to_device` transfers.

Under fused multi-step dispatch (steps_per_dispatch=K) the `step` span
covers the whole K-step window and carries a `fused_steps` arg. Spans nest
PER THREAD, so the producer thread's transfers land beside (not inside) the
consumer's step spans.

`record_span(...)` is a null context unless a recorder is installed (via
`set_recorder` or `trace_session`).
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

from flexflow_tpu.op_attrs.core import PARALLEL_OP_TYPES, op_type_of


@dataclass
class TraceSpan:
    """One completed span. Times are milliseconds since the recorder epoch."""

    name: str
    start_ms: float
    dur_ms: float
    depth: int  # nesting depth at record time (0 = top level)
    parent: Optional[int]  # index of the enclosing span in recorder.spans
    tid: int
    args: Dict[str, object] = field(default_factory=dict)


class TraceRecorder:
    """Collects spans/instants; thread-safe; exports Chrome-trace JSON."""

    def __init__(self, clock=time.perf_counter) -> None:
        self._clock = clock
        self._epoch = clock()
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.spans: List[TraceSpan] = []
        self.instants: List[Dict[str, object]] = []
        # per-thread stacks of OPEN span indices, readable from OTHER
        # threads (the TLS stack above is not): the watchdog's
        # HangDiagnostic reads the hung thread's live span stack here
        self._open: Dict[int, List[int]] = {}

    # -- recording ---------------------------------------------------------

    def _now_ms(self) -> float:
        return (self._clock() - self._epoch) * 1000.0

    def _stack(self) -> list:
        if not hasattr(self._tls, "stack"):
            self._tls.stack = []
        return self._tls.stack

    @contextlib.contextmanager
    def span(self, name: str, sync=None, **args):
        """Record `name` around the body. `sync` is a pytree waited on
        (force_sync) BEFORE the end timestamp, so device work launched
        inside the span is charged to it, not to whoever reads the result
        later."""
        stack = self._stack()
        start = self._now_ms()
        tid = threading.get_ident()
        # reserve the span's slot now so children can point at their parent
        with self._lock:
            idx = len(self.spans)
            self.spans.append(
                TraceSpan(
                    name=name,
                    start_ms=start,
                    dur_ms=0.0,
                    depth=len(stack),
                    parent=stack[-1] if stack else None,
                    tid=tid,
                    args=dict(args),
                )
            )
            self._open.setdefault(tid, []).append(idx)
        stack.append(idx)
        try:
            yield self
        finally:
            if sync is not None:
                _force_sync(sync)
            end = self._now_ms()
            stack.pop()
            with self._lock:
                self.spans[idx].dur_ms = end - start
                open_stack = self._open.get(tid)
                if open_stack and open_stack[-1] == idx:
                    open_stack.pop()
                elif open_stack and idx in open_stack:
                    open_stack.remove(idx)

    def instant(self, name: str, **args) -> None:
        with self._lock:
            self.instants.append(
                {
                    "name": name,
                    "ts_ms": self._now_ms(),
                    "tid": threading.get_ident(),
                    "args": dict(args),
                }
            )

    # -- queries (the test surface) ----------------------------------------

    def spans_named(self, name: str) -> List[TraceSpan]:
        return [s for s in self.spans if s.name == name]

    def open_span_names(self, tid: int) -> List[str]:
        """The names of thread `tid`'s currently-OPEN spans, outermost
        first — what that thread is doing RIGHT NOW, readable from any
        thread (the watchdog's hang forensics)."""
        with self._lock:
            return [self.spans[i].name for i in self._open.get(tid, [])]

    def children_of(self, span: TraceSpan) -> List[TraceSpan]:
        idx = self.spans.index(span)
        return [s for s in self.spans if s.parent == idx]

    # -- export ------------------------------------------------------------

    def to_chrome_trace(self) -> dict:
        """The `chrome://tracing` JSON object format. Timestamps in µs."""
        pid = os.getpid()
        events = []
        for s in self.spans:
            events.append(
                {
                    "name": s.name,
                    "ph": "X",
                    "ts": round(s.start_ms * 1000.0, 3),
                    "dur": round(s.dur_ms * 1000.0, 3),
                    "pid": pid,
                    "tid": s.tid,
                    "args": s.args,
                }
            )
        for i in self.instants:
            events.append(
                {
                    "name": i["name"],
                    "ph": "i",
                    "s": "t",
                    "ts": round(i["ts_ms"] * 1000.0, 3),
                    "pid": pid,
                    "tid": i["tid"],
                    "args": i["args"],
                }
            )
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        """Write the Chrome trace to `path` (a directory gets a default
        file name). Returns the file path written."""
        if os.path.isdir(path) or not path.endswith(".json"):
            os.makedirs(path, exist_ok=True)
            path = os.path.join(path, "flexflow_trace.json")
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


def _force_sync(out) -> None:
    from flexflow_tpu.kernels.profiling import force_sync

    force_sync(out)


# -- module-level active recorder ----------------------------------------

_ACTIVE: Optional[TraceRecorder] = None


def active_recorder() -> Optional[TraceRecorder]:
    return _ACTIVE


def set_recorder(recorder: Optional[TraceRecorder]) -> Optional[TraceRecorder]:
    """Install (or clear, with None) the process-wide recorder; returns the
    previous one so callers can restore it."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = recorder
    return prev


@contextlib.contextmanager
def record_span(name: str, sync=None, **args):
    """Span against the active recorder; a no-op null context when tracing
    is off (the hot-path guard — instrumented step functions call this
    unconditionally)."""
    rec = _ACTIVE
    if rec is None:
        yield None
        return
    with rec.span(name, sync=sync, **args) as r:
        yield r


# -- device-trace scopes ---------------------------------------------------

# scopes of the step that belong to no node, and the phase each is booked to
STEP_SCOPES = {
    "cast": "other",
    "loss": None,  # forward or backward, as JAX's transforms say
    "optimizer": "opt",
    "metrics": "other",
    "health": "other",
}
PHASES = ("fwd", "bwd", "opt", "other", "unattributed")
# kinds whose OperatorType value is not the name the tables use
_KIND_NAMES = {
    "linear": "dense", "multihead_attention": "mha", "state_space": "ssm",
}
_NOT_IN_NAME = re.compile(r"[^A-Za-z0-9_.\-]")
# the first `ff.` token of a name stack: a kind holds no dot, so the first
# two dots split the scope; a name ends at the first `/` or `)`
_SCOPE = re.compile(
    r"(?<![A-Za-z0-9_.\-])ff\.([a-z0-9_]+)(?:\.([A-Za-z0-9_.\-]+))?"
)
# a state-space node puts its scan under a further scope inside its own
# (`ff.ssm.<name>/scan`, `kernels/ssm.py`): `parse_scope` keeps that in the
# name, `<name>/scan`, so that one table tells the scan from the node's
# projections. The node's scope may be closed by JAX's `jvp(...)` /
# `transpose(...)` before the scan's begins.
_SCAN_PART = re.compile(r"\)*/scan(?:[/)]|$)")


def scope_kind(op_type) -> str:
    """The `<kind>` of a node's scope: its `OperatorType` in lower case,
    `dense`, `mha` and `ssm` for the three the tables abbreviate,
    `parallel_<op>` for the four parallel ops."""
    if op_type in PARALLEL_OP_TYPES:
        return "parallel_" + op_type.value
    return _KIND_NAMES.get(op_type.value, op_type.value)


def scope_name(graph, n) -> str:
    """`ff.<kind>.<name>` for node `n` of a computation graph or PCG:
    `<name>` is the layer's name, or `n<idx>` where it has none, with every
    character the name stack could not carry (it splits on `/` and wraps in
    `jvp(...)`, `transpose(...)`) replaced by `_`."""
    la = graph.layer_attrs(n)
    name = la.name if la.name else f"n{n.idx}"
    kind = scope_kind(op_type_of(la.attrs))
    return f"ff.{kind}.{_NOT_IN_NAME.sub('_', name)}"


def node_scope(graph, n):
    """The `jax.named_scope` everything lowered for node `n` goes under."""
    return jax.named_scope(scope_name(graph, n))


def step_scope(part: str):
    """`jax.named_scope("ff.<part>")` for a part of the step that is no
    node: one of `STEP_SCOPES`."""
    assert part in STEP_SCOPES, part
    return jax.named_scope("ff." + part)


def parse_scope(op_name: str) -> Tuple[str, str, str]:
    """The inverse of `node_scope` / `step_scope` on an HLO `op_name`
    (`jit(step)/transpose(jvp(ff.dense.l0))/mul`): `(phase, kind, name)`.
    The phase is what JAX itself wrote around the scope: inside
    `transpose(` or a rematerialized computation it is `bwd`, under
    `ff.optimizer` `opt`, under `ff.cast` / `ff.metrics` / `ff.health`
    `other`, under any other `ff.` scope `fwd`; with no `ff.` scope it is
    `("unattributed", "", "")`. An operation of a state-space node's scan
    has the name `<name>/scan`."""
    m = _SCOPE.search(op_name)
    if m is None:
        return "unattributed", "", ""
    kind, name = m.group(1), m.group(2)
    if name is None and STEP_SCOPES.get(kind) is not None:
        return STEP_SCOPES[kind], kind, ""
    backward = (
        "transpose(" in op_name[: m.start()]
        or "rematted_computation" in op_name
    )
    if kind == "ssm" and _SCAN_PART.match(op_name, m.end()):
        name = f"{name}/scan"
    return ("bwd" if backward else "fwd"), kind, name or ""


@contextlib.contextmanager
def trace_session(trace_dir: str, label: str = "flexflow_trace"):
    """Install a fresh recorder for the body and write
    `<trace_dir>/<label>.json` (Chrome-trace format) on exit. Used by
    FFModel.fit when `--profile-trace-dir` is set, alongside the XLA/xprof
    trace jax.profiler writes into the same directory."""
    rec = TraceRecorder()
    prev = set_recorder(rec)
    try:
        yield rec
    finally:
        set_recorder(prev)
        os.makedirs(trace_dir, exist_ok=True)
        rec.save(os.path.join(trace_dir, f"{label}.json"))
