"""Print what a profiler trace holds, to look at it by hand before (or after)
changing `trace_reduce.py`: planes, their lines, and on each line the event
names that took most time.

    python3 benchmark/trace_dump.py <trace dir or .xplane.pb> [names per line]
    python3 benchmark/trace_dump.py <trace dir> --record <out.json.gz> <seconds>

The second form writes the first `seconds` of the traced window as plain
events, with what `trace_reduce.reduce_events` makes of them, for
`benchmark/testdata/`: a recorded trace that the tests reduce again.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def record(path, out, seconds):
    import gzip
    import json

    import trace_reduce

    events = trace_reduce.export_events(trace_reduce.load_xplane(path), seconds)
    reduced = trace_reduce.reduce_events(events)
    patterns = ["^" + trace_reduce.PALLAS, "^all-reduce", "^all-gather", "^fusion"]
    expected = {
        k: reduced[k]
        for k in ("window_s", "busy_s", "idle_share_worst_chip",
                  "collective_exposed_share_worst_chip", "program_runs")
    }
    expected["family_seconds"] = {
        p: s for p in patterns
        if (s := trace_reduce.family_seconds(reduced, p)) is not None
    }
    with gzip.open(out, "wt") as f:
        json.dump({"source": path, "seconds": seconds, "events": events,
                   "expected": expected}, f)
    print(f"{out}: {os.path.getsize(out)} bytes; expected {expected}")


def main(argv):
    from jax.profiler import ProfileData

    import trace_reduce

    path = argv[1]
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    if len(argv) > 2 and argv[2] == "--record":
        return record(path, argv[3], float(argv[4]))
    top = int(argv[2]) if len(argv) > 2 else 12
    data = ProfileData.from_file(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            by_name = {}
            count = 0
            first = last = None
            example = {}
            for e in line.events:
                count += 1
                by_name[e.name] = by_name.get(e.name, 0) + e.duration_ns
                first = e.start_ns if first is None else min(first, e.start_ns)
                end = e.start_ns + e.duration_ns
                last = end if last is None else max(last, end)
                if e.name not in example:
                    example[e.name] = dict(e.stats)
            if not count:
                continue
            print(
                f"  line {line.name!r}: {count} events, {len(by_name)} names, "
                f"span {(last - first) / 1e6:.1f} ms"
            )
            rows = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
            for name, ns in rows:
                stats = {
                    k: (str(v)[:60]) for k, v in list(example[name].items())[:6]
                }
                print(f"    {ns / 1e6:10.3f} ms  {name[:90]}  {stats}")


if __name__ == "__main__":
    main(sys.argv)
