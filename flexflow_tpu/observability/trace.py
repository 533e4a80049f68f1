"""Two kinds of instrumentation, on one clock: the profiler's.

**Device-trace scopes** (`node_scope`, `step_scope`, `parse_scope`): every
operation the step programs lower carries the name of the PCG node and the
part of the step it came from, as a `jax.named_scope` of the form
`ff.<kind>.<name>` (a node) or `ff.<part>` (`cast`, `loss`, `optimizer`,
`metrics`, `health`). A named scope is HLO metadata and nothing else: the
program is the same with and without it, there is no switch, and its
"spans" are the device events of a `jax.profiler` trace. JAX wraps the
scope in `jvp(...)` / `transpose(...)` as it differentiates, which is where
the phase comes from. `benchmark/step_anatomy.py` reads these scopes back
with `parse_scope`, which lives here so that format and parser cannot drift.

**Host spans** (`record_span`, `count`, `span_totals`): `record_span` is the
one call the program makes around a piece of host work, and every call does
three things.

- It enters a `jax.profiler.TraceAnnotation` (the `step` span a
  `StepTraceAnnotation` whose `step_num` counts the process's dispatches):
  an event on the `/host:CPU` plane of the same `.xplane.pb` the device
  planes are written to, on their clock. So what the host was doing while a
  chip sat idle, and when a step ended on the device, are read off ONE
  timeline (`benchmark/host_spans.py` does); nothing is forced on the host
  to learn it. With no profiler session running the annotation is a flag
  test.
- It adds the span to a process-wide table, `span_totals()`: name -> count,
  total seconds, longest single span, on `perf_counter`. Kept in memory and
  read when a run ends: where set-up went (`compile/...`), what a stalled
  chunk stalled in (the longest `fit/next_batch` or `dispatch`).
- Where a `TraceRecorder` is installed (`set_recorder`, `trace_session`) it
  records the span there too, nested per thread. The recorder is what the
  watchdog's hang forensics read (`open_span_names(tid)`: what a hung
  thread was doing, `fit / step / dispatch`, `checkpoint/...`) and what
  tests assert nesting on. `trace_session` still writes it as Chrome-trace
  JSON (`flexflow_trace.json`) beside the XLA trace under
  `--profile-trace-dir`, but **the xplane is the timeline to read**: it
  holds the same spans laid over the device's operations.

`HOST_SPANS` lists the names `compile` and `fit` emit, for the readers;
`search/<name>` (the search's phases), `checkpoint...` and `<phase>/<layer>`
(`--profiling`) come through the same call. `count(name)` adds an event with
no duration to the same table: `step_trace`, bumped in the step functions'
bodies, says how many times JAX traced the step in this process. The table
also holds what `jax.monitoring` reports under `LOWERING_EVENTS`: the
seconds JAX spent tracing Python to jaxprs and lowering jaxprs to MLIR, the
part of set-up that is this program's own Python (the interpreter over the
graph, the Pallas bodies).

The jitted train step is ONE XLA program, so of a step the host sees only
`dispatch` (the enqueue of the donated program). No span waits for the
device: a traced job is the job, not a serialized copy of it. Spans nest
PER THREAD, so the checkpoint writer's `checkpoint` spans land beside (not
inside) the fit thread's `fit/next_batch` and `step` spans.
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
    def span(self, name: str, **args):
        """Record `name` around the body."""
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


# -- host spans ---------------------------------------------------------------

# the spans `FFModel.compile` and `FFModel.fit` emit, outermost first; the
# readers (`benchmark/host_spans.py`) take the names from here
HOST_SPANS = (
    "compile",
    "compile/search",  # the body `search_provenance["search_seconds"]` times
    "compile/verify",  # the winner's static verifiers, not the lowering they read
    "compile/lower_step",  # every static lowering of the step (analysis/lowering.py)
    "compile/build_instance",  # the backend's constructor
    "compile/init_state",  # `instance.initialize`: parameters, masters, moments
    "fit",
    "fit/begin",  # entry to the first pull: iterator, supervision, checkpointing
    "fit/next_batch",  # each pull from the iterator, the one that ends an epoch too
    "step",
    "dispatch",  # the enqueue of the step program, inside `step`
    "fit/end",  # the last wait, the metric conversion, the routing counters
)
# events with no duration, counted by `count`
STEP_TRACE = "step_trace"
# `jax.monitoring` durations kept in the same table: what tracing the
# program's Python and lowering its jaxprs cost this process
LOWERING_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)

_TOTALS: Dict[str, List[float]] = {}  # name -> [count, total s, longest s]
_TOTALS_LOCK = threading.Lock()


def _add(name: str, seconds: float) -> None:
    with _TOTALS_LOCK:
        row = _TOTALS.get(name)
        if row is None:
            _TOTALS[name] = [1, seconds, seconds]
        else:
            row[0] += 1
            row[1] += seconds
            if seconds > row[2]:
                row[2] = seconds


def count(name: str) -> None:
    """One more of an event with no duration (`STEP_TRACE`)."""
    _add(name, 0.0)


def span_totals() -> Dict[str, Dict[str, float]]:
    """`{name: {"count", "total_s", "longest_s"}}` of every span, counter
    and `LOWERING_EVENTS` duration of this process so far."""
    with _TOTALS_LOCK:
        return {
            name: {"count": row[0], "total_s": row[1], "longest_s": row[2]}
            for name, row in _TOTALS.items()
        }


def reset_span_totals() -> None:
    with _TOTALS_LOCK:
        _TOTALS.clear()


def _on_duration(event: str, duration: float, **_) -> None:
    if event in LOWERING_EVENTS:
        _add(event, duration)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


class record_span:
    """`with record_span(name, **args):` is how the program marks a piece of
    host work (module docstring): a profiler annotation, a row of
    `span_totals()`, and a span of the active recorder where there is one
    (which the `with` then binds, else None)."""

    __slots__ = ("name", "args", "_annotation", "_recorded", "_t0")

    def __init__(self, name: str, **args) -> None:
        self.name = name
        self.args = args

    def __enter__(self):
        if self.name == "step":
            row = _TOTALS.get("step")
            self._annotation = jax.profiler.StepTraceAnnotation(
                "step", step_num=row[0] if row else 0, **self.args
            )
        else:
            self._annotation = jax.profiler.TraceAnnotation(
                self.name, **self.args
            )
        self._annotation.__enter__()
        rec = _ACTIVE
        self._recorded = None
        if rec is not None:
            self._recorded = rec.span(self.name, **self.args)
            self._recorded.__enter__()
        self._t0 = time.perf_counter()
        return rec

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        if self._recorded is not None:
            self._recorded.__exit__(*exc)
        self._annotation.__exit__(*exc)
        _add(self.name, seconds)
        return False


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
    "gated_delta": "kda", "short_conv": "shortconv",
}
_NOT_IN_NAME = re.compile(r"[^A-Za-z0-9_.\-]")
# the first `ff.` token of a name stack: a kind holds no dot, so the first
# two dots split the scope; a name ends at the first `/` or `)`
_SCOPE = re.compile(
    r"(?<![A-Za-z0-9_.\-])ff\.([a-z0-9_]+)(?:\.([A-Za-z0-9_.\-]+))?"
)
# a node may put a part of itself under a further scope inside its own
# (`NODE_PARTS`: the state-space node its scan, its convolution with SiLU and
# its gated norm, `ff.ssm.<name>/scan|conv|norm`, `kernels/ssm.py`; the
# experts node its router, latent projections, routed experts and shared
# expert, `kernels/moe.py`): `parse_scope` keeps that in the name,
# `<name>/<part>`, so that one table tells the scan from the node's
# projections and the router from the experts. The node's scope may be closed
# by JAX's `jvp(...)` / `transpose(...)` before the part's begins.
NODE_PARTS = {
    "ssm": ("scan", "conv", "norm"),
    "experts": ("router", "latent", "routed", "shared"),
    # the gated delta-rule node (`kernels/kda.py`): the chunk-to-chunk pass,
    # the chunks' operands (decayed scores, the triangular inverse), the
    # gates, the convolution, the gated norm. On the "kda" route the scores'
    # kernels read q, k and the decay's pre-activation in the model's layout
    # and normalise and take the softplus in VMEM (PR 45), so that is `prep`
    # there, and `gates` holds the two rank-128 gate matmuls, beta's sigmoid,
    # v's heads-first copy (dv's back) and the small reductions; on the "xla"
    # route `gates` also holds the two norms, the softplus and the
    # heads-first copies of q, k and the pre-activation
    "kda": ("scan", "prep", "gates", "conv", "norm"),
    # latent attention (`kernels/ops._latent_mha_forward`): the low-rank
    # key/value projections with their norm, and the attention core
    "ring_attention": ("latent", "core"),
    # the double-gated short-convolution node (`kernels/short_conv.py`): the
    # input gate, the taps and the output gate between its two projections
    "shortconv": ("conv",),
}
# Between the node's scope and the part's, JAX may also write what a
# `jax.checkpoint` around the parts leaves in the backward's names: the
# rematerialised forward's own `jvp(ff.<kind>.<name>)`, `checkpoint`,
# `rematted_computation` (the gated delta-rule node keeps only its inputs and
# recomputes its parts under one checkpoint, `kernels/kda.py`).
_PART = {
    kind: re.compile(
        r"\)*(?:/(?:jvp\([^()]*\)|checkpoint|rematted_computation))*/("
        + "|".join(parts) + r")(?:[/)]|$)"
    )
    for kind, parts in NODE_PARTS.items()
}


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


# the scope of the node being lowered on this thread, and the attention core
# each attention node took when it was last lowered in this process; the
# grouped matmuls' tiles each expert node took likewise
_lowering = threading.local()
_ATTENTION_ROUTES: Dict[str, str] = {}
_GROUPED_MATMUL_TILES: Dict[str, Dict[str, dict]] = {}


@contextlib.contextmanager
def node_scope(graph, n):
    """The `jax.named_scope` everything lowered for node `n` goes under."""
    name = scope_name(graph, n)
    previous = getattr(_lowering, "scope", None)
    _lowering.scope = name
    try:
        with jax.named_scope(name):
            yield
    finally:
        _lowering.scope = previous


def note_attention_route(route: str) -> None:
    """The attention core (`kernels/ops.mha_core_route`'s names) the node
    being lowered took; dropped where no node's scope is open (a kernel
    called by itself)."""
    scope = getattr(_lowering, "scope", None)
    if scope is not None:
        _ATTENTION_ROUTES[scope] = route


def attention_routes() -> Dict[str, str]:
    """`{ff.<kind>.<name>: route}` of every attention node this process has
    lowered on one device or in the global view, as it was lowered last: a
    program counter a reader (the benchmark's `gqa64_flash_roofline`) prints
    beside what it measures, so that a change of route says so itself."""
    return dict(_ATTENTION_ROUTES)


def note_grouped_matmul_tiles(entries: Dict[str, dict]) -> None:
    """What `kernels/moe.py` gave the Pallas grouped matmuls of the expert
    node being lowered: `{"<matrix>/<call>": {"shape", "tile",
    "padded_over_true"}}`; dropped where no node's scope is open."""
    scope = getattr(_lowering, "scope", None)
    if scope is not None:
        _GROUPED_MATMUL_TILES[scope] = dict(entries)


def grouped_matmul_tiles() -> Dict[str, Dict[str, dict]]:
    """`{ff.experts.<name>: {"<matrix>/<call>": entry}}` of every expert node
    this process has lowered onto the `gmm` / `tgmm` kernels, as it was
    lowered last: for each of the node's matrices (`w1`, `w3`, `w2`) and each
    of a grouped matmul's three calls (`forward`, `input_gradient`,
    `weight_gradient`) the call's `shape` (rows, contraction, columns, in the
    kernel's own names), the `tile` it was given and `padded_over_true`, the
    contraction and column sides in whole tiles over their true size (the
    row side is the data's). A node on XLA's `ragged_dot` has no entry."""
    return {scope: dict(entries) for scope, entries in _GROUPED_MATMUL_TILES.items()}


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
    `("unattributed", "", "")`. An operation of a part of a node
    (`NODE_PARTS`) has the name `<name>/<part>`."""
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
    part = kind in _PART and _PART[kind].match(op_name, m.end())
    if part:
        name = f"{name}/{part.group(1)}"
    return ("bwd" if backward else "fwd"), kind, name or ""


@contextlib.contextmanager
def trace_session(trace_dir: str, label: str = "flexflow_trace"):
    """Install a fresh recorder for the body and write
    `<trace_dir>/<label>.json` (Chrome-trace format) on exit. Used by
    FFModel.fit when `--profile-trace-dir` is set, beside the xplane
    jax.profiler writes into the same directory, which holds the same spans
    over the device's operations."""
    rec = TraceRecorder()
    prev = set_recorder(rec)
    try:
        yield rec
    finally:
        set_recorder(prev)
        os.makedirs(trace_dir, exist_ok=True)
        rec.save(os.path.join(trace_dir, f"{label}.json"))
