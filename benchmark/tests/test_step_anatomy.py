"""The step's anatomy from scoped device events: the table's arithmetic on
hand-made events, the reader of the event metadata on a hand-made xplane
file, the six per-layer readers on traces with and without scopes, and a
trace recorded on the chip reduced again."""

import gzip
import json
import os
import sys

import pytest

import run as bench
import step_anatomy as sa
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(
    os.path.dirname(HERE), "testdata", "step_anatomy_events.json.gz"
)
READERS = ("fwd_ms", "bwd_ms", "opt_ms", "attention_ms", "parallel_op_ms",
           "unattributed_pct")

FWD = "jit(_step)/jvp(ff.dense.ff1_0)/dot_general:"
BWD = "jit(_step)/transpose(jvp(ff.dense.ff1_0))/dot_general:"
ATTN_F = "jit(_step)/jvp(ff.mha.attn0)/flash_fwd_pair/pallas_call:"
ATTN_B = "jit(_step)/transpose(jvp(ff.mha.attn0))/flash_bwd_fused_pair/pallas_call:"
OPT = "jit(_step)/ff.optimizer/sub:"
CAST = "jit(_step)/jvp(ff.cast)/convert_element_type:"
PAR = "jit(_step)/transpose(jvp(ff.parallel_combine.n41))/sharding_constraint:"


def events_of(ops, host=(), chips=(0,)):
    """`ops`: (short name, op name, start, duration), the same on each chip
    unless given as a dict by chip."""
    by_chip = ops if isinstance(ops, dict) else {c: ops for c in chips}
    return {
        "devices": {
            c: {"ops": list(o), "modules": []} for c, o in by_chip.items()
        },
        "host": list(host),
    }


def test_exclusive_time_adds_up_to_the_union():
    # b nested in a, c overlapping a's end, d apart
    spans = [("a", 0, 100), ("b", 10, 30), ("c", 90, 120), ("d", 200, 210)]
    got = sa.exclusive_ns(spans)
    assert got == {"a": 70, "b": 20, "c": 30, "d": 10}
    assert sum(got.values()) == tr.total(tr.union([(s, e) for _, s, e in spans]))
    # the one that started last holds the instant, however the list is ordered
    assert sa.exclusive_ns(list(reversed(spans))) == got
    # two events over the same interval are counted once
    assert sum(sa.exclusive_ns([("a", 0, 10), ("b", 0, 10)]).values()) == 10
    # a long event over many short ones keeps only what they leave
    many = [("w", 0, 1000)] + [("k", 100 * i, 100 * i + 50) for i in range(10)]
    assert sa.exclusive_ns(many) == {"w": 500, "k": 500}
    assert sa.exclusive_ns([]) == {}


def test_phases_add_up_to_busy_time_in_the_window():
    ops = [
        ("fusion.kOutput.1", FWD, 0, 1_000),          # before the window
        ("fusion.kOutput.1", FWD, 9_000, 2_000),      # cut at the start
        ("pallas/flash_fwd_pair.2", ATTN_F, 11_000, 3_000),
        ("pallas/flash_bwd_fused_pair.2", ATTN_B, 14_000, 6_000),
        ("fusion.kOutput.7", BWD, 20_000, 4_000),
        ("fusion.kLoop.3", OPT, 24_000, 1_000),
        ("convert.9", CAST, 25_000, 500),
        ("copy-done.4", "", 26_000, 1_500),           # XLA's own: no name
        ("fusion.kLoop.3", OPT, 29_000, 3_000),       # cut at the end
    ]
    host = [("fit_chunk", 10_000, 20_000)]
    table = sa.anatomy(events_of(ops, host))
    assert table["window_s"] == pytest.approx(20e-6)
    phase = {p: sa.seconds(table, phase=p) for p in sa.PHASES}
    assert phase == pytest.approx({
        "fwd": 4e-6, "bwd": 10e-6, "opt": 2e-6, "other": 0.5e-6,
        "unattributed": 1.5e-6,
    })
    assert sum(phase.values()) == pytest.approx(table["busy_s"])
    # the same busy time trace_reduce reports for the same events
    plain = {
        "devices": {0: {"ops": [(n, s, d) for n, _, s, d in ops], "modules": []}},
        "host": host,
    }
    assert tr.reduce_events(plain)["busy_s"] == pytest.approx(table["busy_s"])
    assert sa.seconds(table, kinds=sa.ATTENTION_KINDS) == pytest.approx(9e-6)
    assert sa.seconds(table, family="^pallas/") == pytest.approx(9e-6)
    assert sa.seconds(table, kind_prefix=sa.PARALLEL_PREFIX) == 0.0
    assert table["scoped"]
    # one family per kernel, whichever layer called it
    assert {k[3] for k in table["rows"] if k[3].startswith("pallas/")} == {
        "pallas/flash_fwd_pair", "pallas/flash_bwd_fused_pair",
    }


def test_mean_over_chips_and_window_without_annotations():
    ops = {
        0: [("fusion.1", FWD, 100, 100), ("all-reduce/psum.2", PAR, 200, 300)],
        1: [("fusion.1", FWD, 100, 300), ("all-reduce/psum.2", PAR, 400, 100)],
    }
    table = sa.anatomy(events_of(ops))
    assert table["chips"] == 2
    assert table["window_s"] == pytest.approx(400e-9)  # first to last operation
    assert sa.seconds(table, phase="fwd") == pytest.approx(200e-9)
    assert sa.seconds(table, kind_prefix="parallel_") == pytest.approx(200e-9)
    assert table["busy_s"] == pytest.approx(400e-9)
    assert table["per_chip"][0][("fwd", "dense", "ff1_0", "fusion")] == 100e-9
    assert sa.owners(table, "^all-reduce", steps=2) == [
        ("bwd", "parallel_combine", 1, pytest.approx(1e-4))
    ]
    assert sa.by(table, "phase")[0][0] in {("fwd",), ("bwd",)}


@pytest.mark.parametrize(
    "op_name, want",
    [
        ("jit(_step)/jvp(ff.mha.enc_block_0__attn_)/dot_general:",
         ("fwd", "mha", "enc_block_0__attn_")),
        ("jit(_step)/transpose(jvp(ff.layer_norm.ln.1-a))/mul:",
         ("bwd", "layer_norm", "ln.1-a")),
        ("jit(_multi_step)/while/body/ff.optimizer/add:", ("opt", "optimizer", "")),
        ("jit(_step)/jvp(jit(take_along_axis))/gather:", ("unattributed", "", "")),
        ("params['n3']", ("unattributed", "", "")),
        ("", ("unattributed", "", "")),
    ],
)
def test_worst_case_names(op_name, want):
    table = sa.anatomy(events_of([("fusion.1", op_name, 0, 10)]))
    assert list(table["rows"]) == [want + ("fusion",)]
    assert table["scoped"] == (want[0] != "unattributed")


def test_a_program_without_the_parser_parses_as_unscoped(monkeypatch):
    monkeypatch.setitem(sys.modules, "flexflow_tpu.observability.trace", None)
    parse = sa._parse_scope()
    assert parse(FWD) == ("unattributed", "", "")
    assert not sa.anatomy(events_of([("fusion.1", FWD, 0, 10)]), parse)["scoped"]


# -- the event metadata, from the file's wire format ---------------------------------


def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, payload):
    if isinstance(payload, int):
        return varint(number << 3) + varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return varint(number << 3 | 2) + varint(len(payload)) + payload


def plane(name, stat_names, event_stats, lines=b""):
    """An XPlane: `stat_names` {id: name}; `event_stats` {id: (event name,
    [XStat bytes])}."""
    out = field(2, name) + lines
    for i, stat in stat_names.items():
        out += field(5, field(1, i) + field(2, field(1, i) + field(2, stat)))
    for i, (event, stats) in event_stats.items():
        meta = field(1, i) + field(2, event) + b"".join(
            field(5, s) for s in stats
        )
        out += field(4, field(1, i) + field(2, meta))
    return field(1, out)


def test_metadata_op_names_from_a_hand_made_xplane(tmp_path):
    stats = {1: "flops", 2: "tf_op", 3: "jit(_step)/ff.optimizer/sub:"}
    # a fixed64 field and a line (skipped whole) in the way
    noise = varint(9 << 3 | 1) + b"\0" * 8 + field(3, field(2, "XLA Ops"))
    device = plane("/device:TPU:1", stats, {
        7: ("%fusion.1 = f32[8] fusion()", [
            field(1, 1) + field(3, 99), field(1, 2) + field(5, FWD)]),
        8: ("%fusion.2 = f32[8] fusion()", [field(1, 2) + field(7, 3)]),
        9: ("%copy-done.4 = f32[8] copy-done()", [field(1, 1) + field(3, 5)]),
    }, lines=noise)
    host = plane("/host:CPU", {1: "tf_op"}, {
        1: ("fit_chunk", [field(1, 1) + field(5, "not a device")])})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(host + device)
    assert sa.metadata_op_names(str(path)) == {1: {
        "%fusion.1 = f32[8] fusion()": FWD,
        "%fusion.2 = f32[8] fusion()": "jit(_step)/ff.optimizer/sub:",
    }}


# -- the readers ----------------------------------------------------------------------


def reader(name):
    return bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", name + ".py")
    )


SCOPED_OPS = [
    ("fusion.1", FWD, 0, 2_000_000),
    ("pallas/flash_fwd_pair.1", ATTN_F, 2_000_000, 1_000_000),
    ("pallas/flash_bwd_fused_pair.1", ATTN_B, 3_000_000, 2_000_000),
    ("fusion.2", BWD, 5_000_000, 3_000_000),
    ("all-gather.3", PAR, 8_000_000, 500_000),
    ("fusion.4", OPT, 8_500_000, 1_000_000),
    ("copy-done.5", "", 9_500_000, 500_000),
]


def context(trace, chips=1, steps=2):
    return {"trace": trace, "steps_traced": steps, "chips": chips}


def test_readers_on_a_scoped_trace(monkeypatch):
    loads = []

    def load(path):
        loads.append(path)
        return events_of(SCOPED_OPS, chips=(0, 1))

    monkeypatch.setattr(sa, "_trace_path", lambda argv: "the.xplane.pb")
    monkeypatch.setattr(sa, "load_scoped", load)
    ctx = context({"busy_s": 0.010}, chips=2)
    got = {name: reader(name).read(ctx) for name in READERS}
    assert got == pytest.approx({
        "fwd_ms": 1.5, "bwd_ms": 2.75, "opt_ms": 0.5, "attention_ms": 1.5,
        "parallel_op_ms": 0.25, "unattributed_pct": 5.0,
    })
    # six readers, one parse: the table is kept in the context they share
    assert loads == ["the.xplane.pb"]
    other = sa.seconds(ctx["step_anatomy"], phase="other")
    total = (got["fwd_ms"] + got["bwd_ms"] + got["opt_ms"]) * 2 / 1e3 + other
    total += ctx["step_anatomy"]["busy_s"] * got["unattributed_pct"] / 100
    assert total == pytest.approx(ctx["trace"]["busy_s"])
    # the plan's parallel ops exist only across chips
    assert reader("parallel_op_ms").read(context({"busy_s": 1.0})) is None


def test_readers_find_nothing_without_scopes(monkeypatch, capsys):
    bare = [(n, "", s, d) for n, _, s, d in SCOPED_OPS]
    monkeypatch.setattr(sa, "_trace_path", lambda argv: "the.xplane.pb")
    monkeypatch.setattr(sa, "load_scoped", lambda path: events_of(bare))
    ctx = context({"busy_s": 0.010}, chips=4)
    assert [reader(name).read(ctx) for name in READERS] == [None] * 6
    # and says why, once: a stale executable from a shared compile cache
    # looks the same as a program from before the scopes
    assert capsys.readouterr().err.count("carries an `ff.` scope") == 1


def test_readers_find_nothing_without_a_trace(monkeypatch, tmp_path, capsys):
    # the CPU rehearsal: no device plane was reduced
    assert [reader(n).read(context(None)) for n in READERS] == [None] * 6
    # no step was traced
    ctx = context({"busy_s": 1.0}, steps=0)
    assert [reader(n).read(ctx) for n in READERS] == [None] * 6
    # the trace is not where it should be: nothing, and no exception
    monkeypatch.setattr(sa, "ROOT", str(tmp_path))
    ctx = context({"busy_s": 1.0})
    assert [reader(n).read(ctx) for n in READERS] == [None] * 6
    assert "no table" in capsys.readouterr().err


def test_trace_path_by_workload_then_newest(monkeypatch, tmp_path):
    monkeypatch.setattr(sa, "ROOT", str(tmp_path))
    made = {}
    for age, cell in enumerate(("cell_a", "cell_b")):
        d = tmp_path / ".bench_out" / "trace" / cell / "plugins" / "profile" / "t0"
        d.mkdir(parents=True)
        made[cell] = d / "host.xplane.pb"
        made[cell].write_bytes(b"")
        os.utime(made[cell], (1000 + age, 1000 + age))
    argv = ["run.py", "--workload", "cell_a", "--seed", "1"]
    assert sa._trace_path(argv) == str(made["cell_a"])
    assert sa._trace_path(["run.py", "--workload=cell_a"]) == str(made["cell_a"])
    assert sa._trace_path(["pytest"]) == str(made["cell_b"])
    assert sa._trace_path(["run.py", "--workload", "gone"]) == str(made["cell_b"])


# -- a trace recorded on the chip -------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_trace_reduces_to_what_it_did(recorded):
    events = sa.unpack(recorded)
    table = sa.anatomy(events)
    got = sa.summary(table)
    want = recorded["expected"]
    assert got["families"] == want["families"]
    for key in ("window_s", "busy_s", "attention_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert got["phase_s"] == pytest.approx(want["phase_s"], rel=1e-9)
    # the phases are the whole of the busy time, and trace_reduce's busy time
    assert sum(got["phase_s"].values()) == pytest.approx(got["busy_s"], rel=1e-9)
    plain = {
        "devices": {
            chip: {"ops": [(n, s, d) for n, _, s, d in lines["ops"]],
                   "modules": lines["modules"]}
            for chip, lines in events["devices"].items()
        },
        "host": events["host"],
    }
    assert tr.reduce_events(plain)["busy_s"] == pytest.approx(
        got["busy_s"], rel=1e-6
    )


def test_recorded_trace_is_scoped_as_the_program_scopes_it(recorded):
    table = sa.anatomy(sa.unpack(recorded))
    assert table["scoped"]
    phases = {key[0] for key in table["rows"]}
    assert {"fwd", "bwd", "opt", "other", "unattributed"} <= phases
    kinds = {key[1] for key in table["rows"]}
    assert {"dense", "mha", "layer_norm", "embedding", "loss", "optimizer",
            "cast"} <= kinds
    # every Pallas family is a kernel's name, not a layer's
    kernels = {k[3] for k in table["rows"] if k[3].startswith(tr.PALLAS)}
    assert kernels and all(
        k.startswith(tr.PALLAS + "flash_") for k in kernels
    ), kernels
    assert len(kernels) <= 4
    # what has no scope is XLA's own data movement
    bare = {k[3] for k in table["rows"] if k[0] == "unattributed"}
    assert {"copy-done"} <= bare
    text = sa.report(table, sa.traced_steps(sa.unpack(recorded)))
    assert "ff.optimizer" in text and "copy-done" in text
