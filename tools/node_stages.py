"""What lies under the scopes of one kind of node in a traced run, by node,
phase, the LAST PRIMITIVE of the framework's op name and the operation's
family, in ms a step: the booking behind `PERF.md` section 5's by-stage rows.

    python tools/node_stages.py <trace dir | .xplane.pb | testdata .json.gz> [kind]

`kind` is a scope kind (`ring_attention` by default; `experts`, `ssm`, ...).
`benchmark/step_anatomy.py` books a busy nanosecond to (phase, kind, name,
family); a family alone misleads: XLA names a `copy` after the operation it
FEEDS, so the 7 ms of `copy` under Mellum2's attention nodes read as the
repeat of k and v until they were booked by their last primitive (`mul`,
`tile`, `convert_element_type`: the rotary's and the norm's; the repeat was
the `broadcast_in_dim` rows. `PERF.md` section 6, PR 63). Reads the trace
only: run it with `JAX_PLATFORMS=cpu`. Prints one JSON object."""

import gzip
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (ROOT, os.path.join(ROOT, "benchmark")):
    if _path not in sys.path:
        sys.path.insert(0, _path)


def load(path):
    """The scoped events of a trace directory, an `.xplane.pb`, or a recorded
    trace of `benchmark/testdata` (`step_anatomy.py --record`)."""
    import step_anatomy
    import trace_reduce

    if path.endswith(".json.gz"):
        with gzip.open(path) as f:
            return step_anatomy.unpack(json.load(f))
    return step_anatomy.load_scoped(trace_reduce.find_xplane(path))


def last_primitive(op_name):
    """The last primitive of a framework op name; a Pallas kernel's with the
    kernel's own name before it (`norm_rotary_fwd/pallas_call`), so that a
    node's kernels are rows apart and no sum called `pallas_call`."""
    parts = op_name.rstrip(":").split("/") if op_name else [""]
    return "/".join(parts[-2:]) if parts[-1] == "pallas_call" else parts[-1]


def stages(events, kind="ring_attention"):
    """{"steps", "busy_ms", "rows": [[node, phase, last primitive, family,
    ms a step], ...] largest first, "copies": [[kind, phase, ms], ...]}: the
    rows of `kind`'s nodes, and every kind's `copy` family for comparison."""
    import step_anatomy

    from flexflow_tpu.observability.trace import parse_scope

    def scope(op_name):
        phase, node_kind, name = parse_scope(op_name)
        return phase, node_kind, name + "|" + last_primitive(op_name)

    steps = step_anatomy.traced_steps(events)
    table = step_anatomy.anatomy(events, scope)
    rows, copies = {}, {}
    for (phase, node_kind, name, family), s in table["rows"].items():
        ms = 1e3 * s / steps
        if family.startswith("copy"):
            copies[node_kind, phase] = copies.get((node_kind, phase), 0.0) + ms
        if node_kind == kind:
            node, _, last = name.partition("|")
            key = (node, phase, last, family)
            rows[key] = rows.get(key, 0.0) + ms
    return {
        "steps": steps,
        "busy_ms": 1e3 * table["busy_s"] / steps,
        "rows": sorted(([*k, v] for k, v in rows.items()), key=lambda r: -r[-1]),
        "copies": sorted(([*k, v] for k, v in copies.items()), key=lambda r: -r[-1]),
    }


if __name__ == "__main__":
    print(json.dumps(stages(load(sys.argv[1]), *sys.argv[2:3])))
