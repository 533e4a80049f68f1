"""From a profiler trace to numbers: the one place where device events become
busy and idle time, per-operation time, exposed collective time and labelled
idle gaps. Per-layer metrics read the dictionary `reduce_events` returns.

Two stages, so that the arithmetic can be tested on a recorded trace without
a chip: `load_xplane` turns the `.xplane.pb` that `jax.profiler` writes into
plain lists of `(name, start_ns, duration_ns)`; `reduce_events` does the rest.

How a TPU trace is laid out (looked at by hand, PR 22): one plane per chip
named `/device:TPU:<n>`, whose line `XLA Ops` holds one event per executed
HLO operation of the TensorCore, serial in time (`XLA Modules` holds one
event per program run, `Steps` one per step); the host's threads are lines
of the plane `/host:CPU`, and a `jax.profiler.TraceAnnotation` is an event
on the line of the thread that made it. All planes share one clock. An
operation's event is named by its whole HLO instruction
(`%fusion.18 = bf16[24,512,1024]{...} fusion(...), kind=kOutput, ...`);
`short_name` keeps the instruction's name and marks a Pallas kernel, which is
a `custom-call` whose target is `tpu_custom_call`, with the prefix `pallas/`
(its instruction name comes from JAX's name stack, `transpose_jvp___` for a
backward kernel, and says nothing stable).
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# the benchmark's own annotations, innermost last
ANNOTATIONS = ("fit_chunk", "between_chunks")
# HLO operations that move data between chips; `-start` only enqueues, the
# matching `-done` is where the TensorCore waits
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|send|recv|async-collective)(-start|-done)?"
    r"(\.\d+|/.*)?$"
)
# the opcode of an HLO instruction: the first lower-case word before a "("
# (types and layouts such as `bf16[8,128]{1,0:T(8,128)S(1)}` have none)
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
# idle gaps shorter than this are the spaces between back-to-back operations
GAP_FLOOR_NS = 2_000


PALLAS = "pallas/"
FUSION_KIND = re.compile(r"\bkind=(k[A-Za-z]+)")


def short_name(hlo):
    """`%fusion.18 = bf16[...] fusion(...), kind=kOutput` -> `fusion.kOutput.18`,
    `%copy.3 = ...` -> `copy.3`, `%psum.7 = ... all-reduce(...)` ->
    `all-reduce/psum.7`; a Pallas kernel call -> `pallas/<instruction name>`."""
    name, _, rest = hlo.partition(" = ")
    name = name.strip().lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in hlo:
        return PALLAS + name
    opcode = OPCODE.search(" " + rest)
    if opcode and COLLECTIVE.match(opcode.group(1)) and not COLLECTIVE.match(name):
        # a collective under another name: `%psum.7 = ... all-reduce(...)`
        # is what `lax.psum` inside `shard_map` becomes
        return f"{opcode.group(1)}/{name}"
    kind = FUSION_KIND.search(hlo)
    if kind and name.startswith("fusion"):
        # XLA's plain `fusion.N` says nothing; its kind tells a matmul with a
        # fused epilogue (kOutput) from an elementwise pass (kLoop)
        serial = name[len("fusion"):]
        return f"fusion.{kind.group(1)}{serial}"
    return name


def load_xplane(path):
    """`{"devices": {chip: {"ops": [...], "modules": [...]}}, "host": [...]}`
    with every event as `(name, start_ns, duration_ns)`; the host list holds
    the benchmark's annotations only."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                lines[key] = [
                    (short_name(e.name), int(e.start_ns), int(e.duration_ns))
                    for e in line.events
                ]
            devices[int(match.group(1))] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in ANNOTATIONS:
                        host.append(
                            (e.name, int(e.start_ns), int(e.duration_ns))
                        )
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def find_xplane(trace_dir):
    paths = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals):
    """Sorted, merged copy of `[(start, end), ...]`."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def total(intervals):
    return sum(end - start for start, end in intervals)


def subtract(intervals, holes):
    """The part of merged `intervals` that merged `holes` do not cover."""
    out = []
    j = 0
    for start, end in intervals:
        cursor = start
        while j < len(holes) and holes[j][1] <= cursor:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cursor:
                out.append((cursor, holes[k][0]))
            cursor = max(cursor, holes[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def clip(events, lo, hi):
    """Events cut to the window [lo, hi); those outside are dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def op_family(name):
    """`fusion.123` -> `fusion`: the name without XLA's serial number, so
    that the same operation of every layer adds up under one name."""
    return re.sub(r"[._]\d+$", "", name)


def is_collective(name):
    return bool(COLLECTIVE.match(name))


def export_events(events, seconds):
    """The events of the first `seconds` of the window, for a recorded trace
    small enough to keep with the tests."""
    chunks = [e for e in events["host"] if e[0] == "fit_chunk"]
    lo = min(s for _, s, _ in chunks)
    hi = lo + int(seconds * 1e9)
    host = [
        (n, s, min(d, hi - s)) for n, s, d in events["host"] if lo <= s < hi
    ]
    return {
        "devices": {
            chip: {k: clip(v, lo, hi) for k, v in lines.items()}
            for chip, lines in events["devices"].items()
        },
        "host": host,
    }


def label_at(host, t):
    """The benchmark's annotation that covers instant `t`, innermost first."""
    for wanted in reversed(ANNOTATIONS):
        for name, start, dur in host:
            if name == wanted and start <= t < start + dur:
                return name
    return "outside_annotations"


def reduce_events(events):
    """Every number the per-layer metrics take from a trace.

    The window runs from the start of the first `fit_chunk` annotation to
    the end of the last (from the first to the last device operation where
    the host plane has none). Busy time is the union of the operations'
    intervals on a chip; shares are reported for the worst chip, `busy_s`
    as the mean over chips.
    """
    devices = events["devices"]
    host = events["host"]
    chunks = [e for e in host if e[0] == "fit_chunk"]
    all_ops = [e for d in devices.values() for e in d["ops"]]
    if not all_ops:
        raise ValueError("the trace holds no device operation")
    if chunks:
        lo = min(s for _, s, _ in chunks)
        hi = max(s + d for _, s, d in chunks)
    else:
        lo = min(s for _, s, _ in all_ops)
        hi = max(s + d for _, s, d in all_ops)
    window = hi - lo
    per_chip = {}
    for chip, lines in devices.items():
        ops = clip(lines["ops"], lo, hi)
        busy = union([(s, s + d) for _, s, d in ops])
        collective = union(
            [(s, s + d) for n, s, d in ops if is_collective(n)]
        )
        compute = union(
            [(s, s + d) for n, s, d in ops if not is_collective(n)]
        )
        by_family = {}
        for name, _, dur in ops:
            by_family[op_family(name)] = by_family.get(op_family(name), 0) + dur
        gaps = subtract([(lo, hi)], busy)
        by_label = {}
        for start, end in gaps:
            if end - start < GAP_FLOOR_NS:
                label = "between_ops"
            else:
                label = label_at(host, (start + end) // 2)
            by_label[label] = by_label.get(label, 0) + (end - start)
        per_chip[chip] = {
            "busy_ns": total(busy),
            "collective_ns": total(collective),
            "collective_exposed_ns": total(subtract(collective, compute)),
            "by_family_ns": by_family,
            "idle_by_label_ns": by_label,
            "modules": len(clip(lines["modules"], lo, hi)),
        }
    worst = max(per_chip, key=lambda c: window - per_chip[c]["busy_ns"])
    most_exposed = max(
        per_chip, key=lambda c: per_chip[c]["collective_exposed_ns"]
    )
    mean_busy = sum(c["busy_ns"] for c in per_chip.values()) / len(per_chip)

    def top(table, n=10):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in rows]

    return {
        "window_s": window / 1e9,
        "busy_s": mean_busy / 1e9,
        "chips": len(per_chip),
        "idle_share_worst_chip": 1.0 - per_chip[worst]["busy_ns"] / window,
        "collective_exposed_share_worst_chip": (
            per_chip[most_exposed]["collective_exposed_ns"] / window
        ),
        "collective_s_worst_chip": (
            per_chip[most_exposed]["collective_ns"] / 1e9
        ),
        "program_runs": per_chip[worst]["modules"],
        # seconds by operation family on the worst chip, all of them
        "family_s": {
            k: v / 1e9 for k, v in per_chip[worst]["by_family_ns"].items()
        },
        "breakdown": {
            "device_ops": top(per_chip[worst]["by_family_ns"]),
            "idle_gaps": top(per_chip[worst]["idle_by_label_ns"]),
        },
    }


def family_seconds(reduced, pattern):
    """Seconds of the operation families whose name matches `pattern`, on
    the worst chip; None where none does."""
    rx = re.compile(pattern)
    hits = [s for name, s in reduced["family_s"].items() if rx.search(name)]
    return sum(hits) if hits else None


def reduce_trace_dir(trace_dir):
    return reduce_events(load_xplane(find_xplane(trace_dir)))
