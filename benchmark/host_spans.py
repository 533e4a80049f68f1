"""What the host was doing, laid over what the chips were doing: the program's
host spans (`flexflow_tpu/observability/trace.py`: `record_span` enters a
`jax.profiler.TraceAnnotation`, an event on the `/host:CPU` plane of the same
`.xplane.pb` the device planes are in, on their clock) read back and crossed
with the worst chip's idle gaps.

    python3 benchmark/host_spans.py <trace dir or .xplane.pb>
    python3 benchmark/host_spans.py <trace dir> --setup
    python3 benchmark/host_spans.py <trace dir> --record <out.json.gz> <seconds>

The first form prints, for the traced window, the worst chip's idle seconds
by the program span the fit loop's thread was in, the host milliseconds a
step inside each span, and the longest single span of each name: the
operator's answer to "why was the chip idle there". `--setup` prints the
set-up table of the run that wrote the trace: the program's `span_totals()`
(count, total and longest seconds of every span, the `step_trace` counter
and JAX's own trace and lowering seconds), which a traced `run.py` leaves
beside the `.xplane.pb` as `span_totals.json` when the first of the readers
below runs. `--record` writes the first seconds of the window as plain events for
`benchmark/testdata/`.

`load` keeps, in one pass over the file, each chip's operations as bare
intervals, the benchmark's own annotations (`trace_reduce.ANNOTATIONS`: they
give the window, as everywhere) and the program's spans with the thread they
were made on; `trace_reduce.load_xplane` drops the latter two's names and
threads. `idle_by_span` books every idle gap of at least
`trace_reduce.GAP_FLOOR_NS` to the innermost program span of the fit loop's
thread that covers it, piece by piece where several do; what no program span
covers goes to the benchmark's own annotation around it (`run.py:fit_chunk`,
`run.py:between_chunks`: the benchmark's code between two `fit` calls), and
to `none` where there is none either. Spans of other
threads (`host_to_device` on the producer, `checkpoint` on the writer) count
in the per-span tables and not in the gaps: the chip waits for the thread
that dispatches.

Six per-layer metrics read this file (`for_context`, `totals_for_context`),
one reader each under `layer_metrics/`: `input_wait_ms` and
`fit_edge_idle_ms` from the trace, `verify_s`, `state_init_s`,
`step_lower_s` and `step_traces` from `span_totals()`. A program from before
the spans has neither: its trace holds the benchmark's annotations alone and
its `trace` module no table, and every reader returns nothing.
"""

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _path in (ROOT, BENCH):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import step_anatomy
import trace_reduce

NO_SPAN = "none"
# how the benchmark's own annotations read in the idle table: time outside
# every program span, named by what `run.py` was doing there
BENCHMARK = "run.py:"
# span names that are not in the program's list because they carry a
# suffix: the search's phases and the checkpoint writes
SPAN_PREFIXES = ("search/", "checkpoint")
TOTALS_FILE = "span_totals.json"
_CACHE_KEY = "host_spans"


# -- the program's side ------------------------------------------------------


def program_trace():
    """The program's `observability.trace` module if it has the host spans,
    else None (a checkout from before them)."""
    try:
        from flexflow_tpu.observability import trace
    except ImportError:
        return None
    return trace if hasattr(trace, "HOST_SPANS") else None


def span_names():
    """The names the program gives its spans, from the program; empty for
    a program without them."""
    trace = program_trace()
    return frozenset(trace.HOST_SPANS) if trace else frozenset()


def is_program_span(name, names):
    return name in names or (bool(names) and name.startswith(SPAN_PREFIXES))


# -- from the file -----------------------------------------------------------


def load(path, names=None):
    """`{"devices": {chip: [(start_ns, end_ns), ...]}, "host": [(annotation,
    start_ns, duration_ns), ...], "spans": [(name, thread, start_ns,
    duration_ns), ...]}`: every chip's operations, the benchmark's
    annotations, and the events of the host plane that carry one of the
    program's span `names` (the program's own list where none is given)."""
    from jax.profiler import ProfileData

    names = span_names() if names is None else names
    devices, host, spans = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        match = trace_reduce.DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    devices[int(match.group(1))] = [
                        (int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name == trace_reduce.HOST_PLANE:
            for thread, line in enumerate(plane.lines):
                for e in line.events:
                    event = (e.name, int(e.start_ns), int(e.duration_ns))
                    if e.name in trace_reduce.ANNOTATIONS:
                        host.append(event)
                    elif is_program_span(e.name, names):
                        spans.append((e.name, thread) + event[1:])
    return {
        "devices": devices,
        "host": sorted(host, key=lambda e: e[1]),
        "spans": sorted(spans, key=lambda e: e[2]),
    }


def window_of(events):
    """[lo, hi) as `trace_reduce.reduce_events` takes it."""
    chunks = [e for e in events["host"] if e[0] == "fit_chunk"]
    if chunks:
        return (
            min(s for _, s, _ in chunks), max(s + d for _, s, d in chunks)
        )
    ops = [iv for chip in events["devices"].values() for iv in chip]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(s for s, _ in ops), max(e for _, e in ops)


# -- the arithmetic ----------------------------------------------------------


def worst_chip_gaps(events):
    """`(chip, [(start_ns, end_ns), ...])`: the idle intervals inside the
    window of the chip that was idle longest, every one of them."""
    lo, hi = window_of(events)
    best = None
    for chip, ops in events["devices"].items():
        busy = trace_reduce.union(
            [(max(s, lo), min(e, hi)) for s, e in ops if e > lo and s < hi]
        )
        gaps = trace_reduce.subtract([(lo, hi)], busy)
        if best is None or trace_reduce.total(gaps) > trace_reduce.total(best[1]):
            best = (chip, gaps)
    if best is None:
        raise ValueError("the trace holds no device plane")
    return best


def fit_thread(spans):
    """The thread that runs the fit loop: the one the `fit` spans are on
    (the first such, should there be several)."""
    for name, thread, _, _ in spans:
        if name == "fit":
            return thread
    return None


def idle_by_span(gaps, spans):
    """`{span name or "none": ns}` over the gaps of at least `GAP_FLOOR_NS`:
    each gap is cut where the spans that cover it begin and end, and every
    piece goes to the covering span that started last (spans of one thread
    nest, so that is the innermost); what no span covers goes to `none`.
    `spans` is `[(name, start_ns, duration_ns), ...]` of ONE thread."""
    spans = sorted(spans, key=lambda s: s[1])
    out = {}
    for lo, hi in gaps:
        if hi - lo < trace_reduce.GAP_FLOOR_NS:
            continue
        covering = [
            (name, max(start, lo), min(start + dur, hi))
            for name, start, dur in spans
            if start < hi and start + dur > lo
        ]
        booked = step_anatomy.exclusive_ns(covering)
        for name, ns in booked.items():
            out[name] = out.get(name, 0) + ns
        rest = (hi - lo) - sum(booked.values())
        if rest:
            out[NO_SPAN] = out.get(NO_SPAN, 0) + rest
    return out


def span_table(spans, lo, hi):
    """`{name: {"count", "total_ns", "longest_ns"}}` of the spans that began
    inside [lo, hi), all threads."""
    out = {}
    for name, _thread, start, dur in spans:
        if lo <= start < hi:
            row = out.setdefault(
                name, {"count": 0, "total_ns": 0, "longest_ns": 0}
            )
            row["count"] += 1
            row["total_ns"] += dur
            row["longest_ns"] = max(row["longest_ns"], dur)
    return out


def reduce_events(events):
    """Everything the readers and the CLI take from a trace; None where the
    host plane holds no `fit` span (a program from before the spans)."""
    thread = fit_thread(events["spans"])
    if thread is None:
        return None
    lo, hi = window_of(events)
    # the rehearsal on the CPU mesh has a host plane and no device plane:
    # no gap to book, the host's side is read all the same
    chip, gaps = worst_chip_gaps(events) if events["devices"] else (None, [])
    on_thread = [
        (name, start, dur)
        for name, t, start, dur in events["spans"] if t == thread
    ]
    # the benchmark's annotations lie around the program's spans, so they
    # take only what no program span covers
    around = [(BENCHMARK + name, start, dur) for name, start, dur in events["host"]]
    idle = idle_by_span(gaps, around + on_thread)
    table = span_table(events["spans"], lo, hi)
    long_gaps = [g for g in gaps if g[1] - g[0] >= trace_reduce.GAP_FLOOR_NS]
    return {
        "window_s": (hi - lo) / 1e9,
        "worst_chip": chip,
        "idle_s": trace_reduce.total(long_gaps) / 1e9,
        "idle_by_span_s": {k: ns / 1e9 for k, ns in idle.items()},
        "idle_under_program_s": sum(
            ns for k, ns in idle.items()
            if k != NO_SPAN and not k.startswith(BENCHMARK)
        ) / 1e9,
        "fit_calls": table.get("fit", {}).get("count", 0),
        "dispatches": table.get("dispatch", {}).get("count", 0),
        "spans": table,
    }


def report(reduced, steps=None):
    """The three tables as text. `steps` is the number of steps in the
    window; where nobody says, the dispatches are counted."""
    steps = steps or reduced["dispatches"] or 1
    idle = reduced["idle_s"]
    lines = [
        f"window {reduced['window_s']:.3f} s, {reduced['fit_calls']} fit "
        f"call(s), {steps} steps; chip {reduced['worst_chip']} idle "
        f"{1e3 * idle:.3f} ms in gaps of {trace_reduce.GAP_FLOOR_NS} ns or more, "
        f"{1e3 * reduced['idle_under_program_s']:.3f} of them under a program "
        "span",
        "",
        f"{'idle under span':<24}{'ms':>10}{'share':>8}",
    ]
    for name, s in sorted(reduced["idle_by_span_s"].items(),
                          key=lambda kv: -kv[1]):
        share = 100 * s / idle if idle else 0.0
        lines.append(f"{name:<24}{1e3 * s:>10.3f}{share:>7.1f}%")
    lines += ["", f"{'span':<24}{'count':>8}{'host ms/step':>14}"
              f"{'longest ms':>12}"]
    for name, row in sorted(reduced["spans"].items(),
                            key=lambda kv: -kv[1]["total_ns"]):
        lines.append(
            f"{name:<24}{row['count']:>8}"
            f"{row['total_ns'] / 1e6 / steps:>14.4f}"
            f"{row['longest_ns'] / 1e6:>12.3f}"
        )
    return "\n".join(lines)


def setup_report(totals):
    """`span_totals()` as text, largest first; counters last."""
    lines = [f"{'span or counter':<52}{'count':>8}{'total s':>10}{'longest s':>11}"]
    for name, row in sorted(totals.items(), key=lambda kv: -kv[1]["total_s"]):
        lines.append(
            f"{name:<52}{row['count']:>8}{row['total_s']:>10.3f}"
            f"{row['longest_s']:>11.3f}"
        )
    return "\n".join(lines)


# -- the readers' side -------------------------------------------------------


def totals_for_context(ctx):
    """The program's `span_totals()` as the run stands, read once and kept
    in the readers' shared `ctx` (and left beside the trace for `--setup`);
    None for a program without the table."""
    key = _CACHE_KEY + "_totals"
    if key not in ctx:
        trace = program_trace()
        ctx[key] = trace.span_totals() if trace else None
        if ctx[key] is not None:
            print("host_spans: set-up by span\n" + setup_report(ctx[key]),
                  file=sys.stderr)
            try:
                xplane = step_anatomy._trace_path(sys.argv)
                with open(_totals_path(xplane), "w") as f:
                    json.dump(ctx[key], f)
            except Exception as e:  # the table on stderr is enough
                print(f"host_spans: {TOTALS_FILE} not written: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
    return ctx[key]


def total_seconds(ctx, *names):
    """Seconds `span_totals()` holds under `names`, together; None where
    the program has no table or none of the names is in it."""
    totals = totals_for_context(ctx)
    if totals is None:
        return None
    rows = [totals[n] for n in names if n in totals]
    return sum(r["total_s"] for r in rows) if rows else None


def for_context(ctx):
    """`reduce_events` of the run a reader is called in, parsed once and
    kept in `ctx`, with its tables on standard error; None where the run
    was not traced, the program has no spans, or the trace cannot be read."""
    if not ctx.get("steps_traced"):
        return None
    if _CACHE_KEY not in ctx:
        reduced = None
        try:
            reduced = reduce_events(load(step_anatomy._trace_path(sys.argv)))
        except Exception as e:  # a reader finds nothing; it never fails a run
            print(f"host_spans: no table: {type(e).__name__}: {e}",
                  file=sys.stderr)
        if reduced is not None:
            print("host_spans: the traced window\n"
                  + report(reduced, ctx["steps_traced"]), file=sys.stderr)
        ctx[_CACHE_KEY] = reduced
    return ctx[_CACHE_KEY]


# -- a recorded trace small enough to keep with the tests ----------------------


def record(events, out, span_s):
    """Write the first `span_s` seconds of the window (times from its start)
    with `reduce_events` of them."""
    import gzip

    lo, _ = window_of(events)
    hi = lo + int(span_s * 1e9)
    blob = {
        "seconds": span_s,
        "devices": {
            str(chip): [
                [max(s, lo) - lo, min(e, hi) - lo]
                for s, e in trace_reduce.union(ops) if s < hi and e > lo
            ]
            for chip, ops in events["devices"].items()
        },
        "host": [
            [name, s - lo, min(d, hi - s)]
            for name, s, d in events["host"] if lo <= s < hi
        ],
        "spans": [
            [name, thread, max(s, lo) - lo, min(s + d, hi) - max(s, lo)]
            for name, thread, s, d in events["spans"]
            if s < hi and s + d > lo
        ],
    }
    blob["expected"] = reduce_events(unpack(blob))
    with gzip.open(out, "wt") as f:
        json.dump(blob, f)
    print(f"{out}: {os.path.getsize(out)} bytes; expected "
          f"{json.dumps(blob['expected'])[:600]}")


def unpack(blob):
    """The events of a recorded trace, as `load` gives them."""
    return {
        "devices": {
            int(chip): [tuple(iv) for iv in ops]
            for chip, ops in blob["devices"].items()
        },
        "host": [tuple(e) for e in blob["host"]],
        "spans": [tuple(e) for e in blob["spans"]],
    }


def _totals_path(xplane):
    """Where a run's `span_totals()` is left: beside its `.xplane.pb`."""
    return os.path.join(os.path.dirname(xplane), TOTALS_FILE)


def main(argv):
    if len(argv) < 2:
        print(__doc__)
        return 2
    path = argv[1]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    if "--setup" in argv:
        with open(_totals_path(path)) as f:
            print(setup_report(json.load(f)))
        return 0
    events = load(path)
    if len(argv) >= 5 and argv[2] == "--record":
        record(events, argv[3], float(argv[4]))
        return 0
    reduced = reduce_events(events)
    if reduced is None:
        print("the host plane holds no `fit` span: a program from before "
              "the host spans, or a trace of something else")
        return 1
    print(report(reduced))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
