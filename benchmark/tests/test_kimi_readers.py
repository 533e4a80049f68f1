"""The three readers of the delta-rule / latent-attention cell (`kda_ms`,
`kda_scan_roofline`, `mla_flash_roofline`) on a trace recorded on the chip
from `kimilinear48b_s4096_1chip` (its `ff.kda.*` scopes with the parts
`scan`, `prep`, `gates`, `conv`, `norm` inside them, its one
`ff.ring_attention.*` scope with `latent` and `core`), `kernel_costs` checked
by hand, and where there is nothing to read (a trace of a program without
such scopes, no trace at all), where the readers return nothing."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "kimi_events.json.gz")
# programs without a `kda` scope, the second with causal flash kernels under
# `ff.ring_attention` scopes that have no parts
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")
OTHER_MODEL = os.path.join(TESTDATA, "super_events.json.gz")
READERS = ("kda_ms", "kda_scan_roofline", "mla_flash_roofline")
CELL = "kimilinear48b_s4096_1chip"


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


def test_the_cell_lists_the_three_readers_and_they_exist():
    spec = bench.load_cell(os.path.join(bench.ROOT, "BENCHMARK.json"), CELL)
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
    costs = module.kernel_costs(spec["config"], 1, 4096)
    # the recurrence, four layers, three passes: a position's row is q, k,
    # v, o in bf16 (4 x 8,192 B), the log-decays (16,384 B) and beta (128 B)
    assert costs["kda_scan"]["bytes"] == 4 * 3 * 4096 * (32768 + 16384 + 128)
    # and a head's chunk of 64 costs a position 2*32.5*(256+256+128) FLOPs
    # over the causal half plus three [128, 128] state products
    per_head = 2 * 32.5 * 640 + 6 * 128 * 128
    assert costs["kda_scan"]["flops"] == 4 * 3 * 4096 * 32 * per_head
    # memory binds: 2.42 GB at 819 GB/s against 0.22 TFLOP at 197 TFLOP/s
    scan = reader("kda_scan_roofline")
    ctx = {"module": module, "config": spec["config"], "job": spec["job"],
           "device_kind": "TPU v5 lite"}
    kind, least = scan.bound(ctx)
    assert kind == "memory" and least == pytest.approx(2.958e-3, rel=0.01)
    # latent attention: 32 heads, the causal half of 4,096 x 4,096 pairs,
    # the key 192 wide in four of seven products, the value 128 in three
    pairs = 4096 * 4097 / 2
    assert costs["flash"]["flops"] == 2 * pairs * 32 * (4 * 192 + 3 * 128)
    assert costs["flash"]["bytes"] == 6 * 2 * 4096 * 32 * (192 + 128)
    kind, least = reader("mla_flash_roofline").bound(ctx)
    assert kind == "compute" and least == pytest.approx(3.14e-3, rel=0.01)


def test_readers_on_a_recorded_trace_of_the_cell(monkeypatch):
    ctx = context(monkeypatch, RECORDED)
    steps = ctx["steps_traced"]
    assert steps >= 1
    kda_ms = reader("kda_ms").read(ctx)
    table = ctx["step_anatomy"]
    assert kda_ms == pytest.approx(1e3 * sa.seconds(table, kinds=("kda",)) / steps)
    # the parts are rows of the one table, in both phases
    scan = reader("kda_scan_roofline")
    by_part = {
        part: scan.scan_ms(ctx, (part,))
        for part in ("scan", "prep", "gates", "conv", "norm")
    }
    for part, ms in by_part.items():
        assert ms > 0, part
        for phase in ("fwd", "bwd"):
            assert any(
                s > 0 for (p, kind, name, _), s in table["rows"].items()
                if p == phase and kind == "kda" and name.endswith("/" + part)
            ), (part, phase)
    assert sum(by_part.values()) <= kda_ms * (1 + 1e-9)
    # the chunk-to-chunk pass is the Pallas kernels
    kernels = 1e3 * sa.seconds(table, kinds=("kda",), family="^pallas/") / steps
    assert 0 < kernels <= by_part["scan"] * (1 + 1e-9)
    # the recurrence is its operands and its pass: the share counts both
    share = scan.read(ctx)
    least = scan.bound(ctx)[1]
    assert share == pytest.approx(
        100 * least * 1e3 / (by_part["scan"] + by_part["prep"])
    )
    assert 0 < share <= 100
    # the four nodes are the largest kind of the step
    assert kda_ms > 0.5 * 1e3 * table["busy_s"] / steps
    # latent attention: the Pallas calls under its scope, by scope and not
    # by every Pallas call of the step
    mla = reader("mla_flash_roofline")
    flash_ms = mla.kernel_ms(ctx)
    every_call = 1e3 * sa.seconds(table, family="^pallas/") / steps
    assert 0 < flash_ms < every_call
    assert 0 < mla.read(ctx) <= 100
    assert mla.read(ctx) == pytest.approx(100 * mla.bound(ctx)[1] * 1e3 / flash_ms)
    # its low-rank projections and its core are told apart
    for part in ("latent", "core"):
        assert any(
            s > 0 for (_p, kind, name, _), s in table["rows"].items()
            if kind == "ring_attention" and name.endswith("/" + part)
        ), part


@pytest.mark.parametrize("recorded", [WITHOUT, OTHER_MODEL])
def test_readers_find_nothing_where_there_is_nothing_to_read(
    monkeypatch, recorded
):
    """A program without the op (the parent's): no `kda` row, and where the
    configuration states no `flash` cost nothing for the attention reader
    either; nothing raises."""
    ctx = context(monkeypatch, recorded)
    for name in ("kda_ms", "kda_scan_roofline"):
        assert reader(name).read(ctx) is None
    other = bench.load_cell(
        os.path.join(bench.ROOT, "BENCHMARK.json"), "super120b_s4096_1chip"
    )
    ctx.update(config=other["config"], job=other["job"],
               module=bench.load_module(other["module_path"]))
    assert reader("mla_flash_roofline").read(ctx) is None
    bare = dict(ctx, trace=None)
    bare.pop("step_anatomy", None)
    for name in READERS:
        assert reader(name).read(bare) is None
