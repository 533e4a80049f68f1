"""The four readers of the short-convolution / grouped-query cell
(`shortconv_ms`, `shortconv_roofline`, `gqa64_flash_roofline`,
`lfm2_moe_held_ms`) on a trace recorded on the chip from
`lfm2moe24b_s8192_1chip` (its four `ff.shortconv.*` scopes with the part
`conv` inside them, its one `ff.ring_attention.*` scope with `core`, its four
`ff.experts.*` scopes), `kernel_costs` checked by hand, `parse_scope` on the
new kind and its part, and where there is nothing to read (a trace of a
program without such scopes, no trace at all), where the readers return
nothing."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "lfm2_events.json.gz")
# programs without a `shortconv` scope; the second has causal flash kernels
# under an `ff.ring_attention` scope, and expert scopes
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")
OTHER_MODEL = os.path.join(TESTDATA, "kimi_events.json.gz")
READERS = (
    "shortconv_ms", "shortconv_roofline", "gqa64_flash_roofline",
    "lfm2_moe_held_ms",
)
CELL = "lfm2moe24b_s8192_1chip"


def reader(name):
    return bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", name + ".py")
    )


def context(monkeypatch, recorded):
    with gzip.open(recorded, "rt") as f:
        events = sa.unpack(json.load(f))
    monkeypatch.setattr(sa, "_trace_path", lambda argv: "the.xplane.pb")
    monkeypatch.setattr(sa, "load_scoped", lambda path: events)
    spec = bench.load_cell(os.path.join(bench.ROOT, "BENCHMARK.json"), CELL)
    return {
        "trace": {"busy_s": 1.0}, "steps_traced": sa.traced_steps(events),
        "chips": 1, "device_kind": "TPU v5 lite", "config": spec["config"],
        "job": spec["job"], "module": bench.load_module(spec["module_path"]),
    }


def test_the_cell_lists_the_four_readers_and_they_exist():
    spec = bench.load_cell(os.path.join(bench.ROOT, "BENCHMARK.json"), CELL)
    assert spec["job"]["seq"] == 8192 and spec["job"]["batch_per_chip"] == 2
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL]
        module = reader(name)
        assert (module.UNIT, module.MOVES, module.SOURCE, module.LAYER) == (
            listed[name]["unit"], "tokens_per_s", "device_trace", "kernels",
        )


def test_kernel_costs_by_hand():
    spec = bench.load_cell(os.path.join(bench.ROOT, "BENCHMARK.json"), CELL)
    module = bench.load_module(spec["module_path"])
    costs = module.kernel_costs(spec["config"], 2, 8192)
    tokens = 16384
    # four nodes, three passes, a token's two projections 2048 -> 6144 and
    # 2048 -> 2048: 33,554,432 FLOPs
    assert costs["shortconv"]["flops"] == 4 * 3 * tokens * 33_554_432
    # u, the output and the three weights in bf16 once a pass
    weights = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert costs["shortconv"]["bytes"] == 4 * 3 * 2 * (2 * tokens * 2048 + weights)
    ctx = {"module": module, "config": spec["config"], "job": spec["job"],
           "device_kind": "TPU v5 lite"}
    # compute binds: 6.6 TFLOP at 197 TFLOP/s against 2.0 GB at 819 GB/s
    kind, least = reader("shortconv_roofline").bound(ctx)
    assert kind == "compute" and least == pytest.approx(33.49e-3, rel=0.01)
    # attention: 32 TRUE query heads of 64, the causal half of 8,192 x 8,192
    # pairs, two sequences, seven products
    pairs = 8192 * 8193 / 2
    assert costs["flash"]["flops"] == 2 * 7 * 2 * pairs * 32 * 64
    # q, o at 32 heads and k, v at the 8 published heads: six tensors each
    assert costs["flash"]["bytes"] == 6 * 2 * tokens * 64 * (32 + 8)
    kind, least = reader("gqa64_flash_roofline").bound(ctx)
    assert kind == "compute" and least == pytest.approx(9.77e-3, rel=0.01)


def test_parse_scope_on_the_new_kind_and_its_part():
    from flexflow_tpu.observability import trace

    assert trace.NODE_PARTS["shortconv"] == ("conv",)
    assert trace.parse_scope(
        "jit(_step)/jvp(ff.shortconv.conv3)/conv/mul"
    ) == ("fwd", "shortconv", "conv3/conv")
    assert trace.parse_scope(
        "jit(_step)/transpose(jvp(ff.shortconv.conv3))/conv/reduce_sum"
    ) == ("bwd", "shortconv", "conv3/conv")
    assert trace.parse_scope(
        "jit(_step)/transpose(jvp(ff.shortconv.conv3))/dot_general"
    ) == ("bwd", "shortconv", "conv3")


def test_readers_on_a_recorded_trace_of_the_cell(monkeypatch, capsys):
    ctx = context(monkeypatch, RECORDED)
    steps = ctx["steps_traced"]
    assert steps >= 1
    node_ms = reader("shortconv_ms").read(ctx)
    table = ctx["step_anatomy"]
    assert node_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("shortconv",)) / steps
    )
    # four nodes, each with its chain as rows of the one table, both phases
    names = {
        name.partition("/")[0] for (_p, kind, name, _f) in table["rows"]
        if kind == "shortconv"
    }
    assert names == {"conv0", "conv2", "conv3", "conv4"}
    for phase in ("fwd", "bwd"):
        assert any(
            s > 0 for (p, kind, name, _), s in table["rows"].items()
            if p == phase and kind == "shortconv" and name.endswith("/conv")
        ), phase
    roof = reader("shortconv_roofline")
    chain = roof.chain_ms(ctx)
    assert 0 < chain < node_ms
    share = roof.read(ctx)
    assert share == pytest.approx(100 * roof.bound(ctx)[1] * 1e3 / node_ms)
    assert 0 < share <= 100
    # the nodes are the largest kind of the step's forward and backward
    assert node_ms > 0.2 * 1e3 * table["busy_s"] / steps
    # attention: the Pallas calls under its scope, the causal d % 128 kernels
    flash = reader("gqa64_flash_roofline")
    share = flash.read(ctx)
    assert 0 < share <= 100
    kernels = {
        family for (_p, kind, _n, family), s in table["rows"].items()
        if kind == "ring_attention" and family.startswith("pallas/") and s > 0
    }
    assert {"pallas/flash_fwd_causal_bshf", "pallas/flash_bwd_causal_bshf"} <= kernels
    err = capsys.readouterr().err
    assert "gqa64_flash_roofline: " in err and "shortconv_roofline: " in err
    # the held experts' nodes
    held = reader("lfm2_moe_held_ms").read(ctx)
    assert held == pytest.approx(1e3 * sa.seconds(table, kinds=("experts",)) / steps)
    assert held > 0


@pytest.mark.parametrize("recorded", [WITHOUT, OTHER_MODEL])
def test_readers_find_nothing_where_there_is_nothing_to_read(
    monkeypatch, recorded
):
    """A program without the op (the parent's): no `shortconv` row; where the
    configuration states no `flash` cost nothing for the attention reader
    either; nothing raises."""
    ctx = context(monkeypatch, recorded)
    for name in ("shortconv_ms", "shortconv_roofline"):
        assert reader(name).read(ctx) is None
    other = bench.load_cell(
        os.path.join(bench.ROOT, "BENCHMARK.json"), "super120b_s4096_1chip"
    )
    ctx.update(config=other["config"], job=other["job"],
               module=bench.load_module(other["module_path"]))
    assert reader("gqa64_flash_roofline").read(ctx) is None
    bare = dict(ctx, trace=None)
    bare.pop("step_anatomy", None)
    for name in READERS:
        assert reader(name).read(bare) is None


def test_route_counter_reaches_the_reader(monkeypatch):
    from flexflow_tpu.observability import trace

    flash = reader("gqa64_flash_roofline")
    monkeypatch.setattr(trace, "attention_routes", lambda: {"ff.x.a": "rows"})
    assert flash.attention_routes() == {"ff.x.a": "rows"}
    # a program from before the counter: nothing, and nothing raises
    monkeypatch.delattr(trace, "attention_routes")
    assert flash.attention_routes() is None
