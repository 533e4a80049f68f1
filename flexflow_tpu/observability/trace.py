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
four things.

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
- It pushes its name on the thread's list of OPEN spans, which another
  thread may read (`open_span_names(tid)`): what the watchdog's hang
  forensics name, `fit / step / dispatch`, `checkpoint/...`, in every job.
- Where a `TraceRecorder` is installed (`set_recorder`) it records the span
  there too, nested per thread: the span tree tests assert nesting on. The
  timeline to read is the xplane, which holds the same spans laid over the
  device's operations.

`HOST_SPANS` lists the names `compile` and `fit` emit, for the readers;
`search/<name>` (the search's phases), `checkpoint...` and `<phase>/<layer>`
(`--profiling`) come through the same call. `count(name)` adds an event with
no duration to the same table: `step_trace`, bumped in the step functions'
bodies, says how many times JAX traced the step in this process.

**Set-up by owner** (`lowering_by_function`, `node_trace_seconds`,
`pre_program_s`, `setup_report`). `jax.monitoring` reports the start and the
end of every trace of Python to a jaxpr, every lowering of a jaxpr to MLIR
and every backend compile (or cache load), with the function's name. A
nested `jax.jit` (every `jnp` function is one) reports its own trace inside
its caller's, so the plain sums `span_totals()` keeps under
`LOWERING_EVENTS` count those seconds twice. The listeners here keep the
events open on each thread as a stack and file every event, as it ends,
under its function: inclusive, exclusive (less what was nested in it) and
top-level seconds (where nothing on its thread was open around it).
Top-level events of one thread do not overlap, so their sum is wall time.
`count(STEP_TRACE)` marks the outermost event open on its thread: that trace
is a trace of the program's step, and the `jit(<name>)` lowering and compile
that follow it on the thread are the step's; everything else is somebody
else's (a caller's reference, the eager initialisers), under its own name.
`node_scope` and `step_scope` time themselves on the host inside such a
trace: the step's trace seconds by node kind. Memory is a row a function
name and a row a node kind; nothing here runs unless JAX traces or compiles.

The jitted train step is ONE XLA program, so of a step the host sees only
`dispatch` (the enqueue of the donated program). No span waits for the
device: a traced job is the job, not a serialized copy of it. Spans nest
PER THREAD, so the checkpoint writer's `checkpoint` spans land beside (not
inside) the fit thread's `fit/next_batch` and `step` spans.
"""

from __future__ import annotations

import contextlib
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

import flexflow_tpu
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
    """Collects spans as a tree, nested per thread; thread-safe."""

    def __init__(self, clock=time.perf_counter) -> None:
        self._clock = clock
        self._epoch = clock()
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.spans: List[TraceSpan] = []

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
        stack.append(idx)
        try:
            yield self
        finally:
            end = self._now_ms()
            stack.pop()
            with self._lock:
                self.spans[idx].dur_ms = end - start

    # -- queries (the test surface) ----------------------------------------

    def spans_named(self, name: str) -> List[TraceSpan]:
        return [s for s in self.spans if s.name == name]

    def children_of(self, span: TraceSpan) -> List[TraceSpan]:
        idx = self.spans.index(span)
        return [s for s in self.spans if s.parent == idx]


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
# program's Python and lowering its jaxprs cost this process, every nested
# `jax.jit` counted again in its caller (`lowering_by_function` counts once)
LOWERING_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
)
# the three stages of a compile as `jax.monitoring` names them, and the names
# `lowering_by_function()` files them under
COMPILE_STAGES = {
    LOWERING_EVENTS[0]: "trace",
    LOWERING_EVENTS[1]: "to_mlir",
    "/jax/core/compile/backend_compile_duration": "compile",
}

_TOTALS: Dict[str, List[float]] = {}  # name -> [count, total s, longest s]
_TOTALS_LOCK = threading.Lock()
# fun_name -> stage -> [count, inclusive s, exclusive s, top-level s, the
# step's count, the step's s]
_LOWERING: Dict[str, Dict[str, List[float]]] = {}
_LOWERING_KEYS = (
    "count", "inclusive_s", "exclusive_s", "top_level_s", "step_count", "step_s",
)
_NODE_TRACE: Dict[str, List[float]] = {}  # kind or step part -> [calls, s]
# what is open on each thread: `.stages`, the compile-stage events as
# [event, start, nested s, is the step's]; `.step_program`, the `jit(<name>)`
# of the step traced last; `.spans`, the list `_OPEN_SPANS` holds for it
_thread = threading.local()
# thread ident -> names of its open `record_span`s, outermost first
_OPEN_SPANS: Dict[int, List[str]] = {}


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
    """One more of an event with no duration. `STEP_TRACE`, counted in a
    step function's body, also marks the outermost compile-stage event open
    on this thread: JAX is tracing the program's step there."""
    _add(name, 0.0)
    if name == STEP_TRACE:
        stages = getattr(_thread, "stages", None)
        if stages:
            stages[0][3] = True


def span_totals() -> Dict[str, Dict[str, float]]:
    """`{name: {"count", "total_s", "longest_s"}}` of every span, counter
    and `LOWERING_EVENTS` duration of this process so far."""
    with _TOTALS_LOCK:
        return {
            name: {"count": row[0], "total_s": row[1], "longest_s": row[2]}
            for name, row in _TOTALS.items()
        }


def reset_span_totals() -> None:
    """Empty `span_totals()`, `lowering_by_function()` and
    `node_trace_seconds()`; what is open stays open."""
    with _TOTALS_LOCK:
        _TOTALS.clear()
        _LOWERING.clear()
        _NODE_TRACE.clear()


# -- set-up by owner ------------------------------------------------------------


def _on_stage_start(event: str, value: float, **_) -> None:
    if event in COMPILE_STAGES:
        stages = getattr(_thread, "stages", None)
        if stages is None:
            stages = _thread.stages = []
        stages.append([event, value, 0.0, False])


def _on_stage_end(event: str, start: float, end: float, fun_name="", **_) -> None:
    stage = COMPILE_STAGES.get(event)
    if stage is None:
        return
    seconds = end - start
    if event in LOWERING_EVENTS:
        _add(event, seconds)
    # children end before their parents, on the same thread: the event's own
    # entry is the innermost open one (what lies above it was left by an
    # event that never reported its end, and goes with it)
    stages = getattr(_thread, "stages", None) or []
    nested, of_step = 0.0, False
    for i in range(len(stages) - 1, -1, -1):
        if stages[i][0] == event and stages[i][1] == start:
            nested, of_step = stages[i][2:]
            del stages[i:]
            break
    top_level = not stages
    if stages:
        stages[-1][2] += seconds
    elif stage == "trace":
        # the lowerings and compiles that follow a trace of the step on its
        # thread under its name are the step's (a later call that finds the
        # trace in JAX's cache lowers under that name with no stamp)
        if of_step:
            _thread.step_program = f"jit({fun_name})"
    else:
        of_step = fun_name == getattr(_thread, "step_program", None)
    with _TOTALS_LOCK:
        row = _LOWERING.setdefault(fun_name, {}).setdefault(
            stage, [0, 0.0, 0.0, 0.0, 0, 0.0]
        )
        row[0] += 1
        row[1] += seconds
        row[2] += seconds - nested
        if top_level:
            row[3] += seconds
            if of_step:
                row[4] += 1
                row[5] += seconds


jax.monitoring.register_scalar_listener(_on_stage_start)
jax.monitoring.register_event_time_span_listener(_on_stage_end)


def lowering_by_function() -> Dict[str, Dict[str, Dict[str, float]]]:
    """`{fun_name: {stage: {"count", "inclusive_s", "exclusive_s",
    "top_level_s", "step_count", "step_s"}}}` of every trace (`trace`),
    lowering to MLIR (`to_mlir`) and backend compile or cache load
    (`compile`) JAX has finished in this process, under the name JAX gives
    the function (`_step`, then `jit(_step)` once it is a module).
    `exclusive_s` is the events' seconds less those of the events nested in
    them, `top_level_s` their seconds where nothing on their thread was open
    around them: summed over a thread's rows that is wall time, each second
    once. `step_count` and `step_s` are the part of the top-level events that
    belongs to the program's step: a trace in which `count(STEP_TRACE)` ran,
    and the lowering and compile that followed it on its thread under its
    name."""
    with _TOTALS_LOCK:
        return {
            name: {
                stage: dict(zip(_LOWERING_KEYS, row))
                for stage, row in stages.items()
            }
            for name, stages in _LOWERING.items()
        }


def _in_step_trace() -> bool:
    stages = getattr(_thread, "stages", None)
    return bool(stages) and stages[0][3]


@contextlib.contextmanager
def _traced_scope(scope: str, kind: str):
    """`jax.named_scope(scope)`, timed on the host into `node_trace_seconds()`
    under `kind` where JAX is tracing the step."""
    t0 = time.perf_counter()
    try:
        with jax.named_scope(scope):
            yield
    finally:
        if _in_step_trace():
            seconds = time.perf_counter() - t0
            with _TOTALS_LOCK:
                row = _NODE_TRACE.setdefault(kind, [0, 0.0])
                row[0] += 1
                row[1] += seconds


def node_trace_seconds() -> Dict[str, Dict[str, float]]:
    """`{kind: {"calls", "seconds"}}`: the host seconds JAX's traces of the
    step spent inside `node_scope`, by the node's kind as `scope_kind`
    writes it (`dense`, `mha`, `experts`, ...: the interpreter's Python for
    the node, its kernels' bodies, the `jnp` calls under it), and inside
    `step_scope`, by part (the names of `STEP_SCOPES`). The step's top-level
    trace seconds less all of these is the backward pass and the glue: JAX
    runs the `custom_vjp` rules and transposes outside any `with` of ours."""
    with _TOTALS_LOCK:
        return {
            kind: {"calls": row[0], "seconds": row[1]}
            for kind, row in _NODE_TRACE.items()
        }


def pre_program_s() -> Optional[float]:
    """The process's age at the first import of `flexflow_tpu`: interpreter
    start, `import jax`, and the backend's start-up where the caller asked
    for `jax.devices()` first. None where there is no `/proc`."""
    return flexflow_tpu.PROCESS_AGE_AT_IMPORT_S


def _table(header: Tuple[str, ...], rows: List[tuple]) -> List[str]:
    """Lines of a table: the first column left, the others right."""
    cells = [header] + [
        tuple(f"{c:.3f}" if isinstance(c, float) else str(c) for c in row)
        for row in rows
    ]
    widths = [max(len(row[i]) for row in cells) for i in range(len(header))]
    return [
        ("  " + row[0].ljust(widths[0]) + "".join(
            "  " + c.rjust(w) for c, w in zip(row[1:], widths[1:])
        )).rstrip()
        for row in cells
    ]


def setup_report(top: int = 12) -> str:
    """What an operator prints after `compile()` or the first step to see
    where set-up went, as text in five parts: the seconds before the
    program, the `compile/*` spans, JAX's trace, lowering and compile
    seconds by function (the `top` largest by top-level seconds, the step's
    marked `*`), the step's trace by node kind, and the double count (what
    the `LOWERING_EVENTS` rows of `span_totals()` hold over the seconds
    counted once)."""
    totals, table, nodes = span_totals(), lowering_by_function(), node_trace_seconds()
    age = pre_program_s()
    lines = [
        "before the program (process start to the first import of "
        "flexflow_tpu): "
        + ("no /proc to read it from" if age is None else f"{age:.2f} s"),
        "compile spans:",
    ]
    lines += _table(
        ("span", "count", "total s", "longest s"),
        [
            (name, row["count"], row["total_s"], row["longest_s"])
            for name, row in totals.items() if name.startswith("compile")
        ],
    )
    rows = sorted(
        (
            (name, stage, row) for name, stages in table.items()
            for stage, row in stages.items()
        ),
        key=lambda r: -r[2]["top_level_s"],
    )
    once = {stage: 0.0 for stage in COMPILE_STAGES.values()}
    step = dict(once)
    for _, stage, row in rows:
        once[stage] += row["top_level_s"]
        step[stage] += row["step_s"]
    lines.append(
        f"lowering by function, each second once (top-level events; the {top} "
        f"largest of {len(rows)} rows; * the program's step):"
    )
    lines += _table(
        ("function", "stage", "count", "top-level s", "exclusive s", "inclusive s"),
        [
            (("* " if row["step_s"] else "  ") + name, stage, row["count"],
             row["top_level_s"], row["exclusive_s"], row["inclusive_s"])
            for name, stage, row in rows[:top]
        ] + [
            ("  every function", stage, "", once[stage], "", "")
            for stage in once
        ] + [
            ("* the step's", stage, "", step[stage], "", "") for stage in step
        ],
    )
    step_trace_s = step["trace"]
    scoped = sum(row["seconds"] for row in nodes.values())
    lines.append(
        f"the step's trace by node kind ({step_trace_s:.3f} s in "
        f"{totals.get(STEP_TRACE, {'count': 0})['count']} trace(s)):"
    )
    lines += _table(
        ("kind", "calls", "seconds"),
        sorted(
            ((kind, row["calls"], row["seconds"]) for kind, row in nodes.items()),
            key=lambda r: -r[2],
        ) + [("backward+glue", "", step_trace_s - scoped)],
    )
    naive = sum(totals.get(e, {"total_s": 0.0})["total_s"] for e in LOWERING_EVENTS)
    counted_once = once["trace"] + once["to_mlir"]
    lines.append(
        f"double count: the LOWERING_EVENTS rows sum {naive:.3f} s, counted "
        f"once {counted_once:.3f} s: {naive - counted_once:.3f} s of nested jits "
        "counted again in their callers"
    )
    return "\n".join(lines)


# -- host spans, the call -------------------------------------------------------


def open_span_names(tid: int) -> List[str]:
    """The names of thread `tid`'s OPEN `record_span`s, outermost first:
    what that thread is doing right now, readable from any thread (the
    watchdog's hang forensics), recorder or none."""
    return list(_OPEN_SPANS.get(tid, ()))


class record_span:
    """`with record_span(name, **args):` is how the program marks a piece of
    host work (module docstring): a profiler annotation, a row of
    `span_totals()`, a name on the thread's open spans, and a span of the
    active recorder where there is one (which the `with` then binds, else
    None)."""

    __slots__ = ("name", "args", "_annotation", "_recorded", "_open", "_t0")

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
        try:
            self._open = _thread.spans
        except AttributeError:
            self._open = _thread.spans = _OPEN_SPANS.setdefault(
                threading.get_ident(), []
            )
        self._open.append(self.name)
        self._t0 = time.perf_counter()
        return rec

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        self._open.pop()
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
    # gates, the convolution, the gated norm (with one decay a head, `prep`
    # is `head_kernel_operands` on the "kda" route, kernels that take q, k
    # NORMALISED and g, and `head_decay_operands` on the "xla" route; `gates`
    # is what the "xla" route's is on both). With a decay a key channel, on
    # the "kda" route the scores'
    # kernels read q, k and the decay's pre-activation in the model's layout
    # and normalise and take the softplus in VMEM (PR 45), so that is `prep`
    # there, and `gates` holds the two rank-128 gate matmuls, beta's sigmoid,
    # v's heads-first copy (dv's back) and the small reductions; on the "xla"
    # route `gates` also holds the two norms, the softplus and the
    # heads-first copies of q, k and the pre-activation
    "kda": ("scan", "prep", "gates", "conv", "norm"),
    # latent attention (`kernels/ops._latent_mha_forward`): the low-rank
    # key/value projections with their norm, and the attention core
    # and of a gated grouped-query node (`kernels/ops._mha_forward` with
    # `output_gate`): the norm-and-rotary pass over the projected rows and
    # the gate (its split from the query, its sigmoid on the context),
    # apart from the flash kernels (`core`)
    # (on latent attention with a query rank `latent` holds the query's two
    # projections and their norm too, and with a rotary `rows` is the rotary
    # pass over the shared key slice and the queries' matching columns)
    "ring_attention": ("latent", "core", "rows", "gate"),
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
_LATENT_ATTENTION_FORMS: Dict[str, dict] = {}
_DELTA_RULE_OPERANDS: Dict[str, str] = {}
_TRIANGULAR_PRODUCTS: Dict[str, str] = {}
_GROUPED_MATMUL_TILES: Dict[str, Dict[str, dict]] = {}
_HELD_ROW_SUMS: Dict[str, Dict[str, dict]] = {}


@contextlib.contextmanager
def node_scope(graph, n):
    """The `jax.named_scope` everything lowered for node `n` goes under;
    inside a trace of the step, a row of `node_trace_seconds()`."""
    name = scope_name(graph, n)
    previous = getattr(_lowering, "scope", None)
    _lowering.scope = name
    try:
        with _traced_scope(name, name.split(".")[1]):
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


def note_latent_attention_form(form: dict) -> None:
    """The form the latent-attention node being lowered took
    (`kernels/ops._latent_mha_forward`); dropped where no node's scope is
    open."""
    scope = getattr(_lowering, "scope", None)
    if scope is not None:
        _LATENT_ATTENTION_FORMS[scope] = dict(form)


def latent_attention_forms() -> Dict[str, dict]:
    """`{ff.<kind>.<name>: form}` of every latent-attention node this
    process has lowered, as it was lowered last: `query_rank` (None: one
    full-rank query projection), `rotated_columns` (of the shared key slice
    and of each query head; 0: no position encoding), `pairing`
    (`interleaved`: columns (2j, 2j + 1); `halves`: (j, j + width / 2); None)
    and `core` (the forward kernel of the wide-key entry, or `dense`), so
    that a run says itself which node it measured."""
    return {scope: dict(f) for scope, f in _LATENT_ATTENTION_FORMS.items()}


def note_delta_rule_operands(form: str) -> None:
    """The form of the chunks' operands (`kernels/kda.operand_form`'s names)
    the delta-rule node being lowered took; dropped where no node's scope is
    open."""
    scope = getattr(_lowering, "scope", None)
    if scope is not None:
        _DELTA_RULE_OPERANDS[scope] = form


def delta_rule_operands() -> Dict[str, str]:
    """`{ff.kda.<name>: form}` of every gated delta-rule node this process
    has lowered, as it was lowered last: `head_kernels` (one decay a head,
    the Pallas kernels `gdn_prep_fwd` / `gdn_prep_bwd`), `head_xla` (the same
    form, `head_decay_operands`), `channel_kernels` (a decay a key channel,
    `kda_prep_fwd` / `kda_prep_bwd`) or `xla` (`chunk_operands`), so that a
    run that fell back to XLA's operands says so itself."""
    return dict(_DELTA_RULE_OPERANDS)


def note_triangular_products(form: str) -> None:
    """The form the products around the triangular inverse took in the
    delta-rule node being lowered (`kernels/kda.py`: noted where the route
    or the number of chunk-heads chooses); dropped where no node's scope is
    open."""
    scope = getattr(_lowering, "scope", None)
    if scope is not None:
        _TRIANGULAR_PRODUCTS[scope] = form


def triangular_products() -> Dict[str, str]:
    """`{ff.kda.<name>: form}` of every gated delta-rule node this process
    has lowered, as it was lowered last, beside `delta_rule_operands()`:
    `kernels` (T (K exp(G)), T V and the triangular system's whole backward
    from the Pallas kernels `kda_corrected_fwd` / `kda_corrected_bwd`) or
    `xla` (`kernels/kda._corrected` with `unit_lower_inverse`, differentiated
    by JAX: the "xla" route, and an odd number of chunk-heads on the "kda"
    route), so that a run that fell back says so itself."""
    return dict(_TRIANGULAR_PRODUCTS)


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


def note_held_row_sums(entries: Dict[str, dict]) -> None:
    """How `kernels/moe.py` sums a held share's window rows over their
    tokens in the expert node being lowered: `{"forward" | "backward":
    {"form", "window_rows", "width", "dtype", "sum_dtype", "token_tile"}}`;
    dropped where no node's scope is open."""
    scope = getattr(_lowering, "scope", None)
    if scope is not None:
        _HELD_ROW_SUMS[scope] = {site: dict(e) for site, e in entries.items()}


def held_row_sums() -> Dict[str, Dict[str, dict]]:
    """`{ff.experts.<name>: {"forward": entry, "backward": entry}}` of every
    expert node with a held share this process has lowered, as it was
    lowered last: for the forward's sum of a window's output rows over their
    tokens and for the backward's sum of the rows' cotangent (the gradient
    of the node's input), the `form` (`pallas`: the kernel `held_rows_sum`;
    `xla`: a scatter-add), the window's rows (`window_rows`), the row's
    `width` and `dtype`, the sum's (`sum_dtype`), and the tokens a program
    of the kernel (`token_tile`, None on `xla`)."""
    return {
        scope: {site: dict(e) for site, e in entries.items()}
        for scope, entries in _HELD_ROW_SUMS.items()
    }


# -- the step's loss terms --------------------------------------------------
#
# A graph with a loss NODE (`LabelCrossEntropyAttrs`: a multi-token-prediction
# module's loss) trains on a sum of terms. The terms exist in the step anyway;
# keeping them apart costs one small vector among the step's metric values.
# `kernels/loss.label_cross_entropy` hands each node's unweighted mean to
# `record_loss_term` while the step is traced, the training instance records
# the main loss first (`ff.loss`, weight 1) under `collecting_loss_terms` and
# returns the values with a trailing 1 as the metric value `LOSS_TERMS_KEY`,
# `fit` sums metric values over a call's steps, and `publish_loss_terms` at
# the call's end is where `loss_terms()` finds the latest. A graph without
# such a node records nothing and its step is the one it always was.

LOSS_TERMS_KEY = "loss_terms"
_published_loss_terms: Optional[Dict[str, Dict[str, float]]] = None


@contextlib.contextmanager
def collecting_loss_terms():
    """While the body traces, `record_loss_term` appends (scope, weight,
    value) to the list this yields."""
    previous = getattr(_lowering, "loss_terms", None)
    sink: list = []
    _lowering.loss_terms = sink
    try:
        yield sink
    finally:
        _lowering.loss_terms = previous


def record_loss_term(weight: float, value, scope: Optional[str] = None) -> None:
    """One term of the step's loss: its weight in the sum and its UNWEIGHTED
    value (a float32 scalar tracer), under `scope` or the scope of the node
    being lowered. Dropped where nobody collects."""
    sink = getattr(_lowering, "loss_terms", None)
    if sink is not None:
        name = scope or getattr(_lowering, "scope", None) or f"term{len(sink)}"
        sink.append((name, float(weight), value))


def publish_loss_terms(terms, sums) -> None:
    """`terms` [(scope, weight)] as they were recorded and `sums` [terms + 1]
    summed over the steps of one `fit` call: each term's values, then the
    steps."""
    global _published_loss_terms
    import numpy as np

    sums = np.asarray(sums, dtype=np.float64)
    _published_loss_terms = {
        name: {"weight": weight, "mean": float(total / sums[-1])}
        for (name, weight), total in zip(terms, sums[:-1])
    }


def loss_terms() -> Optional[Dict[str, Dict[str, float]]]:
    """`{scope: {"weight", "mean"}}` of the last `fit` call of a graph with a
    loss node: each term of the training loss by the scope it is computed
    under (`ff.loss` the main one, `ff.label_loss.<name>` a node's), its
    weight in the sum and its unweighted mean over the call's steps; None
    before any, and in a process whose graphs have one loss."""
    return _published_loss_terms and {
        name: dict(term) for name, term in _published_loss_terms.items()
    }


def step_scope(part: str):
    """`jax.named_scope("ff.<part>")` for a part of the step that is no
    node: one of `STEP_SCOPES`; timed as `node_scope` is."""
    assert part in STEP_SCOPES, part
    return _traced_scope("ff." + part, part)


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
