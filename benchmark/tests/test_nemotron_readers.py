"""The four readers of the hybrid cell (`ssm_ms`, `ssm_scan_roofline`,
`moe_held_ms`, `moe_held_rows_pct`) on a trace recorded on the chip from
`twotower30b_s4096_1chip` (0.45 s of it: its `ff.ssm.*` scopes with the
scan's own scope inside them, its `ff.experts.*` scopes with the `gmm` /
`tgmm` kernels), and where there is nothing to read, where they return
nothing."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "nemotron_events.json.gz")
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")


def reader(name):
    return bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", name + ".py")
    )


def context(monkeypatch, recorded):
    with gzip.open(recorded, "rt") as f:
        events = sa.unpack(json.load(f))
    monkeypatch.setattr(sa, "_trace_path", lambda argv: "the.xplane.pb")
    monkeypatch.setattr(sa, "load_scoped", lambda path: events)
    spec = bench.load_cell(
        os.path.join(bench.ROOT, "BENCHMARK.json"), "twotower30b_s4096_1chip"
    )
    return {
        "trace": {"busy_s": 1.0}, "steps_traced": sa.traced_steps(events),
        "chips": 1, "device_kind": "TPU v5 lite", "config": spec["config"],
        "job": spec["job"], "module": bench.load_module(spec["module_path"]),
    }


def test_trace_readers_on_a_recorded_trace_of_the_cell(monkeypatch):
    ctx = context(monkeypatch, RECORDED)
    steps = ctx["steps_traced"]
    assert steps >= 1
    ssm_ms = reader("ssm_ms").read(ctx)
    table = ctx["step_anatomy"]
    assert ssm_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("ssm",)) / steps
    )
    for phase in ("fwd", "bwd"):
        assert sa.seconds(table, phase=phase, kinds=("ssm",)) > 0
        assert sa.seconds(table, phase=phase, kinds=("experts",)) > 0
    # the scan is a part of the node, and what is left is the projections,
    # the convolution and the norm
    roofline = reader("ssm_scan_roofline")
    scan_ms = roofline.scan_ms(ctx)
    assert 0.2 * ssm_ms < scan_ms < 0.9 * ssm_ms
    # rows of the one table: the program's parser names them `<name>/scan`
    names = {name for (_, kind, name, _) in table["rows"] if kind == "ssm"}
    assert {n + "/scan" for n in names if "/" not in n} <= names
    kind, least = roofline.bound(ctx)
    # 1.02 GB of rows three times over against 0.14 TFLOP
    assert kind == "memory" and least == pytest.approx(1.245e-3, rel=0.01)
    share = roofline.read(ctx)
    assert share == pytest.approx(100 * least * 1e3 / scan_ms)
    assert 0 < share <= 100
    held_ms = reader("moe_held_ms").read(ctx)
    assert held_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("experts",)) / steps
    )
    grouped = sa.seconds(table, kinds=("experts",), family="^pallas/t?gmm")
    assert 0 < 1e3 * grouped / steps < held_ms
    # the attention node keeps a kind `attention_ms` counts, kernels inside
    assert reader("attention_ms").read(ctx) > 0
    assert sa.seconds(table, kinds=sa.ATTENTION_KINDS, family="^pallas/") > 0


def test_trace_readers_find_nothing_where_the_trace_has_no_such_scope(
    monkeypatch,
):
    ctx = context(monkeypatch, WITHOUT)
    for name in ("ssm_ms", "ssm_scan_roofline"):
        assert reader(name).read(ctx) is None
    bare = dict(ctx, trace=None)
    bare.pop("step_anatomy", None)
    for name in ("ssm_ms", "ssm_scan_roofline", "moe_held_ms"):
        assert reader(name).read(bare) is None


def test_routing_counter_reader(monkeypatch):
    from flexflow_tpu.observability import routing

    read = reader("moe_held_rows_pct").read
    monkeypatch.setattr(routing, "_published", None)
    assert read({}) is None
    # two nodes, two held experts each: (30 + 10) of 400 and 60 of 400
    routing.publish([[30, 10, 400], [20, 40, 400]], ["moe1", "moe3"])
    assert read({}) == pytest.approx(100 * (0.1 + 0.15) / 2)
    assert routing.published()["max_over_mean_held_load"] == pytest.approx(1.5)
    monkeypatch.setattr(routing, "_published", None)
