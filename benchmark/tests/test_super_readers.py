"""The six readers of the latent-expert cell (`latent_moe_ms`,
`latent_routed_ms`, `latent_moe_roofline`, `latent_held_rows_pct`,
`ssm_g16_ms`, `ssm_g16_scan_roofline`) on a trace recorded on the chip from
`super120b_s4096_1chip` (0.45 s of it: its `ff.experts.*` scopes with the
parts `router`, `latent`, `routed`, `shared` inside them, its `ff.ssm.*`
scopes with the scan's), and where there is nothing to read (a trace of a
program without such scopes, a trace whose expert nodes carry no parts, no
trace at all), where they return nothing."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "super_events.json.gz")
# `twotower30b_s4096_1chip` before the parts existed: expert nodes, no parts
WITHOUT_PARTS = os.path.join(TESTDATA, "nemotron_events.json.gz")
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")
TRACE_READERS = (
    "latent_moe_ms", "latent_routed_ms", "latent_moe_roofline", "ssm_g16_ms",
    "ssm_g16_scan_roofline",
)


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
        os.path.join(bench.ROOT, "BENCHMARK.json"), "super120b_s4096_1chip"
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
    moe_ms = reader("latent_moe_ms").read(ctx)
    table = ctx["step_anatomy"]
    assert moe_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("experts",)) / steps
    )
    # the parts are rows of the one table, in both phases, and add up to
    # the node (what is left under the bare name is reshapes and casts)
    routed = reader("latent_routed_ms")
    by_part = {
        part: routed.part_ms(ctx, (part,))
        for part in ("router", "latent", "routed", "shared")
    }
    for part, ms in by_part.items():
        assert ms > 0, part
        for phase in ("fwd", "bwd"):
            assert any(
                s > 0 for (p, kind, name, _), s in table["rows"].items()
                if p == phase and kind == "experts" and name.endswith("/" + part)
            ), (part, phase)
    assert 0.9 * moe_ms < sum(by_part.values()) <= moe_ms * (1 + 1e-9)
    assert routed.read(ctx) == pytest.approx(
        by_part["latent"] + by_part["routed"]
    )
    # the whole shared expert is the largest part of this cut
    assert by_part["shared"] == max(by_part.values())
    grouped = sa.seconds(table, kinds=("experts",), family="^pallas/t?gmm")
    assert 0 < 1e3 * grouped / steps < by_part["routed"]
    roofline = reader("latent_moe_roofline")
    kind, least = roofline.bound(ctx)
    # 6.94 TFLOP against 3.9 GB moved: compute-bound, 35.2 ms
    assert kind == "compute" and least == pytest.approx(35.24e-3, rel=0.01)
    share = roofline.read(ctx)
    assert share == pytest.approx(100 * least * 1e3 / moe_ms)
    assert 0 < share <= 100
    # the state-space nodes and their widened scan
    ssm_ms = reader("ssm_g16_ms").read(ctx)
    assert ssm_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("ssm",)) / steps
    )
    scan = reader("ssm_g16_scan_roofline")
    scan_ms = scan.scan_ms(ctx)
    assert 0 < scan_ms < ssm_ms
    kernels = sa.seconds(table, kinds=("ssm",), family="^pallas/ssd_")
    assert 0 < 1e3 * kernels / steps <= scan_ms
    kind, least = scan.bound(ctx)
    # 287 MB of rows three times over against 0.045 TFLOP
    assert kind == "memory" and least == pytest.approx(0.3505e-3, rel=0.01)
    assert 0 < scan.read(ctx) <= 100


def test_trace_readers_find_nothing_where_the_trace_has_no_such_scope(
    monkeypatch,
):
    ctx = context(monkeypatch, WITHOUT)
    for name in TRACE_READERS:
        assert reader(name).read(ctx) is None
    bare = dict(ctx, trace=None)
    bare.pop("step_anatomy", None)
    for name in TRACE_READERS:
        assert reader(name).read(bare) is None


def test_parts_reader_finds_nothing_in_a_program_that_scopes_no_parts(
    monkeypatch,
):
    """The parent's program: expert nodes under `ff.experts.<name>` and
    nothing inside them that the parser names. The node's time is read,
    the parts' is not, and nothing raises."""
    ctx = context(monkeypatch, WITHOUT_PARTS)
    assert reader("latent_moe_ms").read(ctx) > 0
    assert reader("latent_routed_ms").read(ctx) is None


def test_routing_counter_reader(monkeypatch, capsys):
    from flexflow_tpu.observability import routing

    read = reader("latent_held_rows_pct").read
    monkeypatch.setattr(routing, "_published", None)
    assert read({}) is None
    # two nodes, two held experts each: (30 + 10) of 400 and 60 of 400; the
    # second node ran 3 windows in 2 steps
    routing.publish(
        [[30, 10, 400], [20, 40, 400]], ["moe1", "moe3"], [[2, 2], [3, 2]]
    )
    assert read({}) == pytest.approx(100 * (0.1 + 0.15) / 2)
    said = json.loads(
        capsys.readouterr().err.split("latent_held_rows_pct: ")[1]
    )
    assert said["windows_per_step_by_node"] == [1.0, 1.5]
    assert said["held_rows_pct_by_node"] == pytest.approx([10.0, 15.0])
    monkeypatch.setattr(routing, "_published", None)
