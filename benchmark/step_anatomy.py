"""Where a step's device time goes, by PCG node and phase: the table the
program's `jax.named_scope`s (`flexflow_tpu/observability/trace.py`:
`ff.<kind>.<name>` per node, `ff.cast|loss|optimizer|metrics|health` for the
rest of the step) make readable from a profiler trace.

    python3 benchmark/step_anatomy.py <trace dir or .xplane.pb> [nodes]
    python3 benchmark/step_anatomy.py <trace dir> --record <out.json.gz> <seconds>

The first form prints kind x phase in ms per step, the costliest nodes and
who holds the collectives, the copies and the kernels: the operator's view,
and the source of `PERF.md` section 5. The second writes the first `seconds`
of the traced window as plain events for `benchmark/testdata/`.

`trace_reduce.load_xplane` keeps `(name, start, duration)` of a device event
and drops what else the trace says about it; `load_scoped` here keeps the
framework's name for the operation as well (`metadata_op_names` says where
the trace holds it), and `anatomy` books every nanosecond a chip was busy in
the window `trace_reduce.reduce_events` uses to exactly one `(phase, kind,
name, family)`: `parse_scope` of the event's framework name, and the
operation family `trace_reduce.op_family` gives. Where events nest or overlap
on a chip's line the time goes to the one that started last, so the rows add
up to the union of the intervals, which is `busy_s`. A fusion carries the one
name XLA gave it (its root's), a combined collective the one name it kept:
time is booked to that node whole.

The per-layer metrics `fwd_ms`, `bwd_ms`, `opt_ms`, `attention_ms`,
`parallel_op_ms` and `unattributed_pct` are sums over this table
(`for_context`, `ms_per_step`); a trace with no `ff.` scope in it (a program
from before the scopes) has no table and they report nothing.

The names are those the EXECUTABLE was compiled with. jax's persistent
compilation cache leaves metadata out of its key
(`jax_compilation_cache_include_metadata_in_key` is False), so a program
that differs from another only in its scopes loads the other's executable
from a cache directory they share, with the other's names (seen on the chip,
PR 24: a checkout with scopes after one without, dense-attention cell, one
cache directory: no scope in the trace). Give each checkout a cache directory
of its own, or clear it, before trusting the names of a traced run.
"""

import glob
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _path in (ROOT, BENCH):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import trace_reduce

PHASES = ("fwd", "bwd", "opt", "other", "unattributed")
ATTENTION_KINDS = ("mha", "ring_attention", "ulysses_attention")
PARALLEL_PREFIX = "parallel_"
# the statistic of an event's metadata that holds the framework's name for
# the operation (`jit(_step)/transpose(jvp(ff.dense.ff1_3))/dot_general:`)
OP_NAME_STAT = "tf_op"
# a trace from a program without the scopes still parses: everything is
# `unattributed` there
_NO_SCOPE = ("unattributed", "", "")
_CACHE_KEY = "step_anatomy"


def _parse_scope():
    """The program's own parser, so that format and reader cannot drift; a
    program from before the scopes has none, and nothing to parse."""
    try:
        from flexflow_tpu.observability.trace import parse_scope
    except ImportError:
        return lambda op_name: _NO_SCOPE
    return parse_scope


# -- what `jax.profiler.ProfileData` leaves out --------------------------------
#
# An event of the `XLA Ops` line has three statistics of its own (offset,
# duration, time scale). The framework name, with XLA's category, FLOPs and
# bytes, is a statistic of the event's METADATA (`XEventMetadata.stats`),
# which `ProfileData` does not expose (looked at by hand, PR 24). So the
# metadata tables of the device planes are read from the file's protobuf
# wire format directly: `XSpace.planes = 1`; `XPlane.name = 2`, `.lines = 3`
# (skipped whole), `.event_metadata = 4`, `.stat_metadata = 5` (both maps:
# key = 1, value = 2); `XEventMetadata.name = 2`, `.stats = 5`;
# `XStatMetadata.name = 2`; `XStat.metadata_id = 1`, `.str_value = 5`,
# `.ref_value = 7` (tsl/profiler/protobuf/xplane.proto).


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of one message: an int for a varint field, a
    `(start, end)` span of `buf` for a length-delimited one; fixed-width
    fields are skipped."""
    while i < end:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, (i, i + size)
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, spans):
    for start, end in spans:
        for number, value in _fields(buf, start, end):
            if number == 2:
                yield value


def metadata_op_names(path):
    """`{chip: {event name: framework op name}}` from the event metadata of
    every `/device:TPU:<n>` plane; an event whose metadata has no such
    statistic (XLA's own copies, for one) is left out."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, events, stats = "", [], []
        for field, value in _fields(buf, *plane):
            if field == 2:
                name = _text(buf, value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                stats.append(value)
        match = trace_reduce.DEVICE_PLANE.match(name)
        if not match:
            continue
        stat_names = {}
        for span in _map_values(buf, stats):
            stat_id, stat_name = None, ""
            for field, value in _fields(buf, *span):
                if field == 1:
                    stat_id = value
                elif field == 2:
                    stat_name = _text(buf, value)
            stat_names[stat_id] = stat_name
        wanted = {i for i, n in stat_names.items() if n == OP_NAME_STAT}
        names = out.setdefault(int(match.group(1)), {})
        for span in _map_values(buf, events):
            event_name, op_name = "", ""
            for field, value in _fields(buf, *span):
                if field == 2:
                    event_name = _text(buf, value)
                elif field == 5:
                    stat = dict(_fields(buf, *value))
                    if stat.get(1) not in wanted:
                        continue
                    if 5 in stat:
                        op_name = _text(buf, stat[5])
                    elif 7 in stat:
                        op_name = stat_names.get(stat[7], "")
            if op_name:
                names.setdefault(event_name, op_name)
    return out


def load_scoped(path):
    """`trace_reduce.load_xplane` with the framework name kept: `{"devices":
    {chip: {"ops": [(short name, op name, start_ns, duration_ns), ...],
    "modules": [(name, start_ns, duration_ns), ...]}}, "host": [(annotation,
    start_ns, duration_ns), ...]}`."""
    from jax.profiler import ProfileData

    op_names = metadata_op_names(path)
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        match = trace_reduce.DEVICE_PLANE.match(plane.name)
        if match:
            chip = int(match.group(1))
            names = op_names.get(chip, {})
            known = {}  # whole HLO text -> (short name, op name)
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    lines["modules"] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events
                    ]
                elif line.name == trace_reduce.OPS_LINE:
                    for e in line.events:
                        name = e.name
                        if name not in known:
                            known[name] = (
                                trace_reduce.short_name(name),
                                names.get(name, ""),
                            )
                        lines["ops"].append(
                            (*known[name], int(e.start_ns), int(e.duration_ns))
                        )
            devices[chip] = lines
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in trace_reduce.ANNOTATIONS:
                        host.append(
                            (e.name, int(e.start_ns), int(e.duration_ns))
                        )
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def window_of(events):
    """[lo, hi) as `trace_reduce.reduce_events` takes it: first `fit_chunk`
    start to the last one's end, or first to last device operation where the
    host plane has no annotation."""
    chunks = [e for e in events["host"] if e[0] == "fit_chunk"]
    if chunks:
        return (
            min(s for _, s, _ in chunks), max(s + d for _, s, d in chunks)
        )
    ops = [e for d in events["devices"].values() for e in d["ops"]]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(e[2] for e in ops), max(e[2] + e[3] for e in ops)


def exclusive_ns(intervals):
    """`{key: ns}` from `[(key, start, end), ...]` with every instant some
    interval covers booked once, to the covering interval that started
    last: the values add up to the length of the intervals' union."""
    out = {}
    stack = []  # (end, key), innermost last
    cursor = None

    def book(key, upto):
        nonlocal cursor
        if upto > cursor:
            out[key] = out.get(key, 0) + upto - cursor
            cursor = upto

    for key, start, end in sorted(intervals, key=lambda i: (i[1], -i[2])):
        while stack and stack[-1][0] <= start:
            top_end, top_key = stack.pop()
            book(top_key, top_end)
        if stack:
            book(stack[-1][1], start)
        cursor = start if cursor is None else max(cursor, start)
        stack.append((end, key))
    while stack:
        top_end, top_key = stack.pop()
        book(top_key, top_end)
    return out


def anatomy(events, parse_scope=None):
    """The table: `rows` is `{(phase, kind, name, family): seconds}` as the
    mean over chips (its values add up to `busy_s`), `per_chip` the same for
    each chip, `scoped` whether any operation carried an `ff.` scope."""
    parse_scope = parse_scope or _parse_scope()
    lo, hi = window_of(events)
    per_chip = {}
    parsed = {}
    for chip, lines in events["devices"].items():
        intervals = []
        for short, op_name, start, dur in lines["ops"]:
            s, e = max(start, lo), min(start + dur, hi)
            if e <= s:
                continue
            if op_name not in parsed:
                parsed[op_name] = parse_scope(op_name)
            key = parsed[op_name] + (trace_reduce.op_family(short),)
            intervals.append((key, s, e))
        per_chip[chip] = {
            k: ns / 1e9 for k, ns in exclusive_ns(intervals).items()
        }
    if not per_chip:
        raise ValueError("the trace holds no device plane")
    rows = {}
    for table in per_chip.values():
        for key, seconds in table.items():
            rows[key] = rows.get(key, 0.0) + seconds / len(per_chip)
    return {
        "window_s": (hi - lo) / 1e9,
        "chips": len(per_chip),
        "busy_s": sum(rows.values()),
        "rows": rows,
        "per_chip": per_chip,
        "scoped": any(key[0] != "unattributed" for key in rows),
    }


def seconds(table, phase=None, kinds=None, kind_prefix=None, family=None):
    """Seconds of the rows that match every filter given."""
    total = 0.0
    for (p, kind, _name, fam), s in table["rows"].items():
        if phase is not None and p != phase:
            continue
        if kinds is not None and kind not in kinds:
            continue
        if kind_prefix is not None and not kind.startswith(kind_prefix):
            continue
        if family is not None and not re.search(family, fam):
            continue
        total += s
    return total


def by(table, *fields):
    """The rows summed over everything but `fields` (of `phase`, `kind`,
    `name`, `family`), largest first."""
    index = [("phase", "kind", "name", "family").index(f) for f in fields]
    out = {}
    for key, s in table["rows"].items():
        sub = tuple(key[i] for i in index)
        out[sub] = out.get(sub, 0.0) + s
    return sorted(out.items(), key=lambda kv: -kv[1])


# -- the readers' side --------------------------------------------------------


def _trace_path(argv):
    """The trace `run.py` has just written: under `.bench_out/trace/` by the
    `--workload` on the command line, else the newest one there."""
    base = os.path.join(ROOT, ".bench_out", "trace")
    workload = None
    for i, arg in enumerate(argv):
        if arg == "--workload" and i + 1 < len(argv):
            workload = argv[i + 1]
        elif arg.startswith("--workload="):
            workload = arg.split("=", 1)[1]
    if workload and os.path.isdir(os.path.join(base, workload)):
        return trace_reduce.find_xplane(os.path.join(base, workload))
    paths = glob.glob(
        os.path.join(base, "*", "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {base}")
    return max(paths, key=os.path.getmtime)


def for_context(ctx):
    """The table of the run a reader is called in, parsed once and kept in
    the readers' shared `ctx`; None where there is no device trace (the CPU
    rehearsal), no traced step, no scope in the trace (a program from before
    them), or the trace cannot be read."""
    if ctx.get("trace") is None or not ctx.get("steps_traced"):
        return None
    if _CACHE_KEY not in ctx:
        try:
            table = anatomy(load_scoped(_trace_path(sys.argv)))
        except Exception as e:  # a reader finds nothing; it never fails a run
            print(f"step_anatomy: no table: {type(e).__name__}: {e}",
                  file=sys.stderr)
            table = None
        if table and not table["scoped"]:
            # either the program has no scopes (a checkout from before PR
            # 24), or its executable came from a compile cache another
            # checkout filled: the cache's key leaves metadata out, and a
            # cached executable carries the names it was compiled with
            print("step_anatomy: no table: no operation in the trace carries "
                  "an `ff.` scope (a program without them, or an executable "
                  "from a compile cache that such a program filled)",
                  file=sys.stderr)
            table = None
        ctx[_CACHE_KEY] = table
    return ctx[_CACHE_KEY]


def ms_per_step(ctx, **filters):
    """A reader's whole body: milliseconds per traced step of the rows that
    match, as the mean over chips; None where there is no table."""
    table = for_context(ctx)
    if table is None:
        return None
    return 1e3 * seconds(table, **filters) / ctx["steps_traced"]


# -- the operator's view ---------------------------------------------------------


def scope_text(kind, name):
    if not kind:
        return "(no scope)"
    return f"ff.{kind}.{name}" if name else f"ff.{kind}"


def report(table, steps, nodes=10):
    """The table as text: kind x phase and the costliest nodes, ms a step."""
    per_step = 1e3 / steps
    busy = table["busy_s"] * per_step
    lines = [
        f"{table['chips']} chip(s), window {table['window_s']:.3f} s, "
        f"{steps} steps, busy {busy:.3f} ms a step (mean over chips)",
        "",
        f"{'phase':<14}{'ms/step':>10}{'share':>8}",
    ]
    for (phase,), s in by(table, "phase"):
        lines.append(
            f"{phase:<14}{s * per_step:>10.3f}{100 * s / table['busy_s']:>7.1f}%"
        )
    kinds = {}
    for (kind, phase), s in by(table, "kind", "phase"):
        kinds.setdefault(kind or "(no scope)", {})[phase] = s * per_step
    lines += ["", f"{'kind':<24}" + "".join(f"{p:>13}" for p in PHASES)
              + f"{'total':>10}"]
    for kind, row in sorted(kinds.items(), key=lambda kv: -sum(kv[1].values())):
        lines.append(
            f"{kind:<24}"
            + "".join(f"{row.get(p, 0.0):>13.3f}" for p in PHASES)
            + f"{sum(row.values()):>10.3f}"
        )
    lines += ["", f"the {nodes} costliest nodes (both phases):"]
    for (kind, name), s in by(table, "kind", "name")[:nodes]:
        lines.append(f"  {s * per_step:>9.3f}  {scope_text(kind, name)}")
    lines += ["", "operation families without a scope:"]
    bare = [(k, s) for k, s in by(table, "phase", "family")
            if k[0] == "unattributed"]
    for (_, family), s in bare[:nodes]:
        lines.append(f"  {s * per_step:>9.3f}  {family}")
    return "\n".join(lines)


def owners(table, family, steps, n=6):
    """Who holds the time of the operation families matching `family`:
    `[(phase, kind, number of nodes, ms a step), ...]`, largest first."""
    out = {}
    for (phase, kind, name, fam), s in table["rows"].items():
        if re.search(family, fam):
            ms, names = out.get((phase, kind), (0.0, set()))
            out[phase, kind] = (ms + 1e3 * s / steps, names | {name})
    rows = [(p, k, len(names), ms) for (p, k), (ms, names) in out.items()]
    return sorted(rows, key=lambda r: -r[3])[:n]


def traced_steps(events):
    """Steps in the window, where nobody says: the runs of the program that
    held a chip longest (one run of the step program is one step, unless
    the job fuses several into one dispatch)."""
    lo, hi = window_of(events)
    lines = next(iter(events["devices"].values()))
    held, runs = {}, {}
    for name, start, dur in lines["modules"]:
        if lo <= start < hi:
            held[name] = held.get(name, 0) + dur
            runs[name] = runs.get(name, 0) + 1
    return runs[max(held, key=held.get)] if held else 1


# -- a recorded trace small enough to keep with the tests ------------------------


def summary(table):
    """The numbers a recorded trace is checked against."""
    return {
        "window_s": table["window_s"],
        "busy_s": table["busy_s"],
        "phase_s": {p: seconds(table, phase=p) for p in PHASES},
        "attention_s": seconds(table, kinds=ATTENTION_KINDS),
        "families": len({key[3] for key in table["rows"]}),
    }


def record(events, out, span_s):
    """Write the first `span_s` seconds of the window as plain events (names
    interned, times from the window's start) with `summary` of them."""
    import gzip
    import json

    lo, _ = window_of(events)
    hi = lo + int(span_s * 1e9)
    shorts, op_names = {}, {}
    devices = {}
    for chip, lines in events["devices"].items():
        devices[str(chip)] = {
            "ops": [
                [shorts.setdefault(short, len(shorts)),
                 op_names.setdefault(op_name, len(op_names)),
                 max(start, lo) - lo, min(start + dur, hi) - max(start, lo)]
                for short, op_name, start, dur in lines["ops"]
                if start < hi and start + dur > lo
            ],
            "modules": [
                [name, start - lo, dur] for name, start, dur in lines["modules"]
                if lo <= start < hi
            ],
        }
    host = [
        [name, start - lo, min(dur, hi - start)]
        for name, start, dur in events["host"] if lo <= start < hi
    ]
    blob = {
        "seconds": span_s, "short_names": list(shorts),
        "op_names": list(op_names), "devices": devices, "host": host,
    }
    blob["expected"] = summary(anatomy(unpack(blob)))
    with gzip.open(out, "wt") as f:
        json.dump(blob, f)
    print(f"{out}: {os.path.getsize(out)} bytes; expected {blob['expected']}")


def unpack(blob):
    """The events of a recorded trace, as `load_scoped` gives them."""
    shorts, op_names = blob["short_names"], blob["op_names"]
    return {
        "devices": {
            int(chip): {
                "ops": [
                    (shorts[s], op_names[o], start, dur)
                    for s, o, start, dur in lines["ops"]
                ],
                "modules": [tuple(m) for m in lines["modules"]],
            }
            for chip, lines in blob["devices"].items()
        },
        "host": [tuple(h) for h in blob["host"]],
    }


def main(argv):
    path = argv[1]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    events = load_scoped(path)
    if len(argv) > 2 and argv[2] == "--record":
        return record(events, argv[3], float(argv[4]))
    table = anatomy(events)
    steps = traced_steps(events)
    print(report(table, steps, int(argv[2]) if len(argv) > 2 else 10))
    for family in ("^all-reduce", "^all-gather", "^copy",
                   "^" + trace_reduce.PALLAS):
        rows = owners(table, family, steps)
        if rows:
            print(f"\nwho holds {family!r} (ms a step, phase, kind x nodes):")
            for phase, kind, nodes, ms in rows:
                print(f"  {ms:>9.3f}  {phase:<13} "
                      f"{scope_text(kind, '')} x {nodes}")


if __name__ == "__main__":
    main(sys.argv)
