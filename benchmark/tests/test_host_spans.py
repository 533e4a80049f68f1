"""`host_spans.py`: the idle-by-span arithmetic on hand-made events, and the
six readers on a recorded host plane (`testdata/host_spans_events.json.gz`:
the first second of a traced window of `bertlarge_s128_1chip`, my chip run,
PR 34) and on what a parent's trace holds, where each returns nothing."""

import gzip
import json
import os

import pytest

import host_spans
import run as bench
import trace_reduce

US = 1_000
RECORDED = os.path.join(bench.BENCH, "testdata", "host_spans_events.json.gz")


def _reader(name):
    return bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", name + ".py")
    )


# -- idle by span -------------------------------------------------------------


def test_gap_split_across_two_spans():
    spans = [("fit/end", 0, 40 * US), ("fit/begin", 60 * US, 40 * US)]
    idle = host_spans.idle_by_span([(10 * US, 90 * US)], spans)
    assert idle == {
        "fit/end": 30 * US, "fit/begin": 30 * US, host_spans.NO_SPAN: 20 * US,
    }
    assert sum(idle.values()) == 80 * US


def test_nested_spans_book_to_the_innermost():
    spans = [
        ("fit", 0, 100 * US),
        ("step", 20 * US, 50 * US),
        ("dispatch", 30 * US, 10 * US),
    ]
    idle = host_spans.idle_by_span([(10 * US, 80 * US)], spans)
    assert idle == {
        "fit": 20 * US,  # 10-20 before the step, 70-80 after it
        "step": 40 * US,  # 20-30 and 40-70 around the dispatch
        "dispatch": 10 * US,
    }


def test_gap_under_no_span_and_gaps_under_the_floor():
    spans = [("fit", 100 * US, 10 * US)]
    floor = trace_reduce.GAP_FLOOR_NS
    idle = host_spans.idle_by_span(
        [(0, 50 * US), (60 * US, 60 * US + floor - 1), (102 * US, 104 * US)],
        spans,
    )
    assert idle == {host_spans.NO_SPAN: 50 * US, "fit": 2 * US}
    assert host_spans.idle_by_span([], spans) == {}


def _events(spans, ops, chunks):
    return {
        "devices": {chip: list(iv) for chip, iv in ops.items()},
        "host": [("fit_chunk", s, e - s) for s, e in chunks],
        "spans": sorted(spans, key=lambda s: s[2]),
    }


def test_reduce_events_takes_the_worst_chip_and_the_fit_threads_spans():
    spans = [
        ("fit", 7, 0, 100 * US),
        ("fit/begin", 7, 0, 10 * US),
        ("fit/next_batch", 7, 10 * US, 5 * US),
        ("step", 7, 15 * US, 5 * US),
        ("dispatch", 7, 16 * US, 3 * US),
        ("fit/end", 7, 90 * US, 10 * US),
        # the producer's transfer covers an idle stretch of the chip, on
        # another thread: counted in the table, not in the gaps
        ("host_to_device", 9, 0, 100 * US),
    ]
    ops = {
        0: [(20 * US, 95 * US)],  # idle 0-20 and 95-100
        1: [(5 * US, 100 * US)],  # idle 0-5 only
    }
    reduced = host_spans.reduce_events(
        _events(spans, ops, [(0, 100 * US)])
    )
    assert reduced["worst_chip"] == 0
    assert reduced["fit_calls"] == 1 and reduced["dispatches"] == 1
    assert reduced["idle_by_span_s"] == pytest.approx({
        "fit/begin": 10e-6, "fit/next_batch": 5e-6, "step": 2e-6,
        "dispatch": 3e-6, "fit/end": 5e-6,
    })
    assert reduced["idle_s"] == pytest.approx(25e-6)
    assert reduced["spans"]["host_to_device"]["count"] == 1
    assert host_spans.NO_SPAN not in reduced["idle_by_span_s"]
    assert reduced["idle_under_program_s"] == pytest.approx(25e-6)
    text = host_spans.report(reduced, steps=1)
    assert "fit/begin" in text and "dispatch" in text


def test_idle_outside_the_program_goes_to_the_benchmarks_annotation():
    spans = [("fit", 7, 10 * US, 50 * US)]
    events = _events(spans, {0: [(20 * US, 50 * US)]}, [(0, 70 * US)])
    events["host"].append(("between_chunks", 70 * US, 20 * US))
    events["host"].append(("fit_chunk", 90 * US, 10 * US))
    reduced = host_spans.reduce_events(events)
    assert reduced["idle_by_span_s"] == pytest.approx({
        "fit": 20e-6,  # 10-20 and 50-60
        "run.py:fit_chunk": 30e-6,  # 0-10, 60-70, 90-100: outside `fit`
        "run.py:between_chunks": 20e-6,
    })
    assert reduced["idle_under_program_s"] == pytest.approx(20e-6)


def test_a_trace_without_program_spans_reduces_to_nothing():
    parent = _events([], {0: [(10, 20)]}, [(0, 100)])
    assert host_spans.reduce_events(parent) is None


def test_span_names_come_from_the_program():
    from flexflow_tpu.observability import trace

    names = host_spans.span_names()
    assert names == frozenset(trace.HOST_SPANS)
    assert host_spans.is_program_span("fit/next_batch", names)
    assert host_spans.is_program_span("search/dp", names)
    assert host_spans.is_program_span("checkpoint", names)
    assert not host_spans.is_program_span("fit_chunk", names)
    # a program without the spans has no names, and nothing is one
    assert not host_spans.is_program_span("search/dp", frozenset())


# -- the recorded host plane ---------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_window_reduces_to_what_was_recorded(recorded):
    reduced = host_spans.reduce_events(host_spans.unpack(recorded))
    expected = recorded["expected"]
    assert reduced["fit_calls"] == expected["fit_calls"] >= 1
    assert reduced["dispatches"] == expected["dispatches"] >= 1
    assert reduced["idle_s"] == pytest.approx(expected["idle_s"])
    assert reduced["idle_by_span_s"] == pytest.approx(
        expected["idle_by_span_s"]
    )
    # every gap is booked once: the table adds up to the idle time
    assert sum(reduced["idle_by_span_s"].values()) == pytest.approx(
        reduced["idle_s"]
    )
    # the fit loop's spans are all there, one pull and one dispatch a step
    spans = reduced["spans"]
    assert {"fit", "fit/begin", "fit/next_batch", "step", "dispatch"} <= set(
        spans
    )
    assert spans["step"]["count"] == spans["dispatch"]["count"]
    assert spans["fit/next_batch"]["count"] >= spans["step"]["count"]


def _trace_context(monkeypatch, events, steps):
    """The readers' `ctx` of a traced run whose trace holds `events`."""
    monkeypatch.setattr(host_spans, "load", lambda path: events)
    monkeypatch.setattr(
        host_spans.step_anatomy, "_trace_path", lambda argv: "unused"
    )
    return {"steps_traced": steps, "trace": {"busy_s": 1.0}}


def test_trace_readers_on_the_recorded_window(recorded, monkeypatch, capsys):
    events = host_spans.unpack(recorded)
    steps = recorded["expected"]["dispatches"]
    ctx = _trace_context(monkeypatch, events, steps)
    wait = _reader("input_wait_ms").read(ctx)
    edge = _reader("fit_edge_idle_ms").read(ctx)
    pulls = recorded["expected"]["spans"]["fit/next_batch"]
    assert wait == pytest.approx(pulls["total_ns"] / 1e6 / steps)
    assert 0 < wait < 5  # a pull places one batch of token ids
    idle = recorded["expected"]["idle_by_span_s"]
    assert edge == pytest.approx(
        1e3 * (idle.get("fit/begin", 0) + idle.get("fit/end", 0))
        / recorded["expected"]["fit_calls"]
    )
    # the rest of the table goes to standard error, once for both readers
    err = capsys.readouterr().err
    assert err.count("host_spans: the traced window") == 1
    assert "idle under span" in err and "dispatch" in err
    # no device plane (the rehearsal on the CPU mesh): the host's side alone
    ctx = _trace_context(monkeypatch, events, steps)
    ctx["trace"] = None
    assert _reader("fit_edge_idle_ms").read(ctx) is None
    assert _reader("input_wait_ms").read(ctx) == pytest.approx(wait)


def test_trace_readers_return_nothing_on_a_parents_trace(
    recorded, monkeypatch
):
    events = host_spans.unpack(recorded)
    events["spans"] = []  # what `load` finds where the program has no span
    ctx = _trace_context(monkeypatch, events, 8)
    assert _reader("input_wait_ms").read(ctx) is None
    assert _reader("fit_edge_idle_ms").read(ctx) is None
    # an untraced run, and a trace that cannot be read, are nothing too
    assert _reader("input_wait_ms").read({"steps_traced": 0}) is None
    monkeypatch.setattr(
        host_spans, "load", lambda path: (_ for _ in ()).throw(OSError("x"))
    )
    assert _reader("input_wait_ms").read(
        {"steps_traced": 8, "trace": {}}
    ) is None


# -- the readers of the program's table ------------------------------------------

TABLE_READERS = ("verify_s", "state_init_s", "step_lower_s", "step_traces")


def test_table_readers_read_the_programs_span_totals(monkeypatch, tmp_path):
    from flexflow_tpu.observability import trace

    monkeypatch.setattr(
        host_spans.step_anatomy, "_trace_path",
        lambda argv: str(tmp_path / "plugins/profile/t/h.xplane.pb"),
    )
    os.makedirs(tmp_path / "plugins/profile/t")
    trace.reset_span_totals()
    try:
        for name, seconds in (
            ("compile/verify", 0.25), ("compile/verify", 0.5),
            ("compile/init_state", 2.0),
            (trace.LOWERING_EVENTS[0], 3.0), (trace.LOWERING_EVENTS[1], 1.5),
        ):
            trace._add(name, seconds)
        trace.count(trace.STEP_TRACE)
        trace.count(trace.STEP_TRACE)
        ctx = {}
        values = {name: _reader(name).read(ctx) for name in TABLE_READERS}
    finally:
        trace.reset_span_totals()
    assert values == {
        "verify_s": 0.75, "state_init_s": 2.0, "step_lower_s": 4.5,
        "step_traces": 2,
    }
    # the table is left beside the trace for `host_spans.py --setup`
    with open(tmp_path / "plugins/profile/t" / host_spans.TOTALS_FILE) as f:
        assert json.load(f)["compile/verify"]["count"] == 2
    # a span the run never entered is absent, not zero
    assert _reader("verify_s").read({}) is None
    assert _reader("step_traces").read({}) == 0


@pytest.mark.parametrize("name", TABLE_READERS)
def test_table_readers_return_nothing_on_a_parents_program(name, monkeypatch):
    # a program from before the spans: its trace module has no such table
    # (patched before the reader's file is run: it binds the name then)
    monkeypatch.setattr(host_spans, "program_trace", lambda: None)
    assert _reader(name).read({}) is None


def test_manifest_lists_the_six_metrics_with_their_readers():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    names = ["verify_s", "state_init_s", "step_lower_s", "step_traces",
             "input_wait_ms", "fit_edge_idle_ms"]
    assert [m["name"] for m in manifest["per_layer"][-6:]] == names
    for name in names:
        reader, entry = _reader(name), by_name[name]
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"]
        )
        assert entry["better"] == "lower"
    assert by_name["verify_s"]["workloads"] == ["bertlarge_s512_4chip"]
    assert all("workloads" not in by_name[n] for n in names[1:])
