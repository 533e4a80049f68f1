"""The four readers of the dense hybrid-SSM cell (`granite_ssm_ms`,
`granite_ssm_scan_roofline`, `granite_scan_column_blocks`,
`granite_swiglu_ms`) on a trace recorded on the chip from
`granite4hmicro_s4096_1chip` (0.45 s of it: its nine `ff.ssm.*` scopes with
the scan's, the convolution's and the norm's parts inside them, its thirty
`ff.dense.ffn<i>_w<j>` scopes), and where there is nothing to read (a trace
of a program without such scopes, no trace at all, a program without the
counter), where they return nothing. And what the manifest and the
configuration's file say of the cut."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "granite_events.json.gz")
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")
CELL = "granite4hmicro_s4096_1chip"
TRACE_READERS = (
    "granite_ssm_ms", "granite_ssm_scan_roofline", "granite_swiglu_ms",
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
    spec = bench.load_cell(os.path.join(bench.ROOT, "BENCHMARK.json"), CELL)
    return {
        "trace": {"busy_s": 1.0}, "steps_traced": sa.traced_steps(events),
        "chips": 1, "device_kind": "TPU v5 lite", "config": spec["config"],
        "job": spec["job"], "module": bench.load_module(spec["module_path"]),
    }


def test_trace_readers_on_a_recorded_trace_of_the_cell(monkeypatch, capsys):
    ctx = context(monkeypatch, RECORDED)
    steps = ctx["steps_traced"]
    assert steps >= 1
    ssm_ms = reader("granite_ssm_ms").read(ctx)
    table = ctx["step_anatomy"]
    assert ssm_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("ssm",)) / steps
    )
    nodes = {
        name.partition("/")[0] for (_, kind, name, _) in table["rows"]
        if kind == "ssm"
    }
    assert nodes == {f"mamba{i}" for i in (0, 1, 2, 3, 4, 6, 7, 8, 9)}
    scan = reader("granite_ssm_scan_roofline")
    scan_ms = scan.scan_ms(ctx)
    assert 0 < scan_ms < ssm_ms
    # the scans are the three Pallas kernels and the running sums around
    # them; no decay mask goes through HBM
    kernels = sa.seconds(table, kinds=("ssm",), family="^pallas/ssd_")
    assert 0.6 * scan_ms < 1e3 * kernels / steps <= scan_ms
    kind, least = scan.bound(ctx)
    # nine scans: 1.90 GB of rows (B and C once a position) against 0.35
    # TFLOP: memory-bound, 2.3 ms
    assert kind == "memory" and least == pytest.approx(2.316e-3, rel=0.01)
    share = scan.read(ctx)
    assert share == pytest.approx(100 * least * 1e3 / scan_ms)
    assert 0 < share <= 100
    swiglu = reader("granite_swiglu_ms").read(ctx)
    said = json.loads(capsys.readouterr().err.split("granite_swiglu_ms: ")[1])
    assert swiglu == pytest.approx(sum(said.values()))
    assert said["bwd"] > said["fwd"] > 0
    dense = 1e3 * sa.seconds(table, kinds=("dense",)) / steps
    # the head is the one dense node outside the feed-forwards
    assert 0.85 * dense < swiglu < dense
    # the mixers and the feed-forwards are most of the step
    busy = 1e3 * sum(table["rows"].values()) / steps
    assert 0.6 * busy < ssm_ms + swiglu < busy


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
    # a configuration that names no such nodes (the parent's files)
    other = context(monkeypatch, RECORDED)
    other["module"] = object()
    assert reader("granite_swiglu_ms").read(other) is None
    assert reader("granite_ssm_scan_roofline").read(other) is None


def test_column_blocks_counter_reader(monkeypatch, capsys):
    from flexflow_tpu.observability import trace

    read = reader("granite_scan_column_blocks").read
    monkeypatch.setattr(trace, "_SCAN_COLUMN_BLOCKS", {})
    assert read({}) is None  # no state-space node lowered
    monkeypatch.setattr(
        trace, "_SCAN_COLUMN_BLOCKS",
        {"ff.ssm.mamba0": 4, "ff.ssm.mamba1": 4},
    )
    assert read({}) == 4
    said = json.loads(
        capsys.readouterr().err.split("granite_scan_column_blocks: ")[1]
    )
    assert said == {"ff.ssm.mamba0": 4, "ff.ssm.mamba1": 4}
    # one node that fell back to the XLA form is what the metric shows
    monkeypatch.setattr(
        trace, "_SCAN_COLUMN_BLOCKS",
        {"ff.ssm.mamba0": 4, "ff.ssm.mamba1": 0},
    )
    assert read({}) == 0
    # a program from before the counter (the parent's)
    monkeypatch.delattr(trace, "scan_column_blocks")
    assert read({}) is None


def test_manifest_and_file_state_the_cut():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in manifest["configs"]}["granite-4.0-h-micro"]
    assert entry["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_rows_held"
    ]
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro", "pretrain_s4096_b1_1chip", 1
    )
    for name in TRACE_READERS + ("granite_scan_column_blocks",):
        metric = {m["name"]: m for m in manifest["per_layer"]}[name]
        assert metric["workloads"] == [CELL]
    config = bench.load_json(os.path.join(bench.ROOT, entry["file"]))
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    # every published width and multiplier as the catalog row gives it
    for key, value in dict(
        hidden_size=2048, mamba_n_heads=64, mamba_d_head=64,
        mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
        mamba_chunk_size=256, mamba_expand=2, num_attention_heads=32,
        num_key_value_heads=8, shared_intermediate_size=8192,
        intermediate_size=8192, attention_multiplier=0.015625,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
        rms_norm_eps=1e-5, vocab_size=100352, tie_word_embeddings=True,
        num_local_experts=0, position_embedding_type="nope",
    ).items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 10 and config["layer_types"] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    )
    assert config["vocab_rows_held"] * 8 == config["vocab_size"]
    for key in ("assumed", "departures", "parameters", "deployment",
                "recompute"):
        assert config[key], key
    assert config["deployment"].startswith("A pipeline of four stages")
    module = bench.load_module(
        os.path.join(bench.ROOT, entry["file"][:-5] + ".py")
    )
    assert module.parameter_counts(config)["total"] == 772_160_448
    assert module.OPERANDS is None  # `precision_control.py` can round them
    # ~19.5 TFLOP a step
    assert module.flops_per_token(config, 4096) * 4096 == pytest.approx(
        19.5e12, rel=0.01
    )
