"""The two readers of the expert nodes (`moe_ms`, `moe_roofline`) on a trace
recorded on the chip from `olmoe_s4096_1chip` (0.45 s of it: its
`ff.experts.*` scopes with the `gmm` / `tgmm` kernels under them), and on
traces without such a scope, where they return nothing."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
WITH_EXPERTS = os.path.join(TESTDATA, "olmoe_events.json.gz")
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
        os.path.join(bench.ROOT, "BENCHMARK.json"), "olmoe_s4096_1chip"
    )
    return {
        "trace": {"busy_s": 1.0}, "steps_traced": sa.traced_steps(events),
        "chips": 1, "device_kind": "TPU v5 lite", "config": spec["config"],
        "job": spec["job"], "module": bench.load_module(spec["module_path"]),
    }


def test_readers_on_a_recorded_trace_with_expert_scopes(monkeypatch):
    ctx = context(monkeypatch, WITH_EXPERTS)
    assert ctx["steps_traced"] >= 1
    moe_ms = reader("moe_ms").read(ctx)
    table = ctx["step_anatomy"]
    assert moe_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("experts",)) / ctx["steps_traced"]
    )
    # forward and backward both, and the grouped matmul a family of its own
    assert sa.seconds(table, phase="fwd", kinds=("experts",)) > 0
    assert sa.seconds(table, phase="bwd", kinds=("experts",)) > 0
    grouped = sa.seconds(table, kinds=("experts",), family="^pallas/t?gmm")
    assert 0.4 * moe_ms < 1e3 * grouped / ctx["steps_traced"] < moe_ms
    kind, least = reader("moe_roofline").bound(ctx)
    # 4.95 TFLOP of grouped matmuls a step against 5.4 GB of weights and rows
    assert kind == "compute" and least == pytest.approx(0.0252, rel=0.01)
    share = reader("moe_roofline").read(ctx)
    assert share == pytest.approx(100 * least * 1e3 / moe_ms)
    assert 0 < share <= 100


def test_readers_find_nothing_where_the_trace_has_no_expert_scope(monkeypatch):
    ctx = context(monkeypatch, WITHOUT)
    assert sa.seconds(ctx.setdefault("step_anatomy", sa.for_context(ctx)),
                      kinds=sa.ATTENTION_KINDS) > 0  # a scoped trace
    assert reader("moe_ms").read(ctx) is None
    assert reader("moe_roofline").read(ctx) is None
    # no trace at all (the CPU rehearsal), and a configuration with no cost
    bare = dict(ctx, trace=None)
    bare.pop("step_anatomy")
    assert reader("moe_ms").read(bare) is None
    assert reader("moe_roofline").read(bare) is None
