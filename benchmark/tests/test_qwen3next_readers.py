"""The five readers of the Gated DeltaNet / gated grouped-query cell
(`gdn_ms`, `gdn_scan_roofline`, `gqa256_flash_roofline`,
`qwen3next_moe_held_ms`, `qwen3next_held_rows_pct`) on a trace recorded on the
chip from `qwen3next80b_s8192_1chip` (its three `ff.kda.*` scopes with the
parts `scan`, `prep`, `gates`, `conv` and `norm` inside them, its one
`ff.ring_attention.*` scope with `core`, `rows` and `gate`, its four
`ff.experts.*` scopes), `kernel_costs` checked by hand, `parse_scope` on the
attention node's new parts, each share bounded by 100, and where there is
nothing to read (a trace of a program without such scopes, no trace at all),
where the readers return nothing."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "qwen3next_events.json.gz")
# a program without a `kda` scope, and one with `kda` scopes but another
# configuration (no `gdn_scan` cost)
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")
TRACE_READERS = (
    "gdn_ms", "gdn_scan_roofline", "gqa256_flash_roofline",
    "qwen3next_moe_held_ms",
)
READERS = TRACE_READERS + ("qwen3next_held_rows_pct",)
CELL = "qwen3next80b_s8192_1chip"


def reader(name):
    return bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", name + ".py")
    )


def cell():
    return bench.load_cell(os.path.join(bench.ROOT, "BENCHMARK.json"), CELL)


def context(monkeypatch, recorded):
    with gzip.open(recorded, "rt") as f:
        events = sa.unpack(json.load(f))
    monkeypatch.setattr(sa, "_trace_path", lambda argv: "the.xplane.pb")
    monkeypatch.setattr(sa, "load_scoped", lambda path: events)
    spec = cell()
    return {
        "trace": {"busy_s": 1.0}, "steps_traced": sa.traced_steps(events),
        "chips": 1, "device_kind": "TPU v5 lite", "config": spec["config"],
        "job": spec["job"], "module": bench.load_module(spec["module_path"]),
    }


def test_the_manifest_lists_the_cell_its_configuration_and_the_five_readers():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    # appended, and found by `run.py` without an edit to it
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": "qwen3-next-80b-a3b",
        "traffic": "pretrain_s8192_b1_1chip", "chips": 1,
        "why": manifest["workloads"][-1]["why"],
    }
    entry = manifest["configs"][-1]
    assert entry["name"] == "qwen3-next-80b-a3b"
    assert entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_rows_held"
    ]
    assert [m["name"] for m in manifest["per_layer"][-5:]] == list(READERS)
    spec = cell()
    assert spec["job"]["seq"] == 8192 and spec["job"]["batch_per_chip"] == 1
    assert spec["job"]["dataset_batches"] == 16
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL]
        module = reader(name)
        source = (
            "program_counter" if name == "qwen3next_held_rows_pct"
            else "device_trace"
        )
        assert (module.UNIT, module.MOVES, module.SOURCE, module.LAYER) == (
            listed[name]["unit"], "tokens_per_s", source, "kernels",
        )
        assert listed[name]["source"] == source


def test_the_file_states_the_published_widths_and_the_cut():
    config = cell()["config"]
    published = {
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 2, "head_dim": 256,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "num_experts_per_tok": 10,
        "norm_topk_prob": True, "rms_norm_eps": 1e-6,
        "full_attention_interval": 4, "vocab_size": 151936,
    }
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_rows_held"
    ]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_experts_total"], config["vocab_rows_held"]) == (
        4, 32, 512, 18992
    )
    assert config["vocab_rows_held"] * 8 == config["vocab_size"]
    assert config["deployment"].startswith("16 chips share each layer")
    assert config["training"]["state_dtype"] == "float32"


def test_kernel_costs_by_hand():
    spec = cell()
    module = bench.load_module(spec["module_path"])
    costs = module.kernel_costs(spec["config"], 1, 8192)
    tokens = 8192
    # a position of one node, forward: 16 key heads x (K K^T and Q K^T over
    # 32.5 of the chunk's positions, 128 wide) + 32 value heads x (the
    # scores' product with U, the solve for 256 columns, three [128, 128]
    # products): 4,210,688 FLOPs
    assert module.gdn_scan_flops_per_token(spec["config"]) == 4_210_688
    assert costs["gdn_scan"]["flops"] == 3 * tokens * 3 * 4_210_688
    # q, k (16 heads), v, o (32) in bf16, the decay and beta in float32
    assert module.gdn_row_bytes(spec["config"]) == 24_832
    assert costs["gdn_scan"]["bytes"] == 3 * tokens * 3 * 24_832
    ctx = {"module": module, "config": spec["config"], "job": spec["job"],
           "device_kind": "TPU v5 lite"}
    # 0.31 TFLOP at 197 TFLOP/s is 1.58 ms; 1.83 GB at 819 GB/s is 2.24 ms:
    # the bytes bind
    from layer_metrics.kda_scan_roofline import bound

    kind, least = bound(ctx, "gdn_scan")
    assert kind == "memory" and least == pytest.approx(2.235e-3, rel=0.01)
    # attention: 16 TRUE query heads of 256, the causal half of 8,192 x 8,192
    # pairs, seven products
    pairs = 8192 * 8193 / 2
    assert costs["flash"]["flops"] == 7 * 2 * pairs * 16 * 256
    assert costs["flash"]["bytes"] == 6 * 2 * tokens * 256 * (16 + 2)
    kind, least = bound(ctx, "flash")
    assert kind == "compute" and least == pytest.approx(9.77e-3, rel=0.01)


def test_parse_scope_on_the_attention_nodes_new_parts():
    from flexflow_tpu.observability import trace

    assert trace.NODE_PARTS["ring_attention"] == (
        "latent", "core", "rows", "gate"
    )
    assert trace.parse_scope(
        "jit(_step)/jvp(ff.ring_attention.attn3)/rows/mul"
    ) == ("fwd", "ring_attention", "attn3/rows")
    assert trace.parse_scope(
        "jit(_step)/transpose(jvp(ff.ring_attention.attn3))/gate/logistic"
    ) == ("bwd", "ring_attention", "attn3/gate")
    assert trace.parse_scope(
        "jit(_step)/jvp(ff.ring_attention.attn3)/core/"
        "flash_fwd_causal_grouped/pallas_call"
    ) == ("fwd", "ring_attention", "attn3/core")
    assert trace.parse_scope(
        "jit(_step)/transpose(jvp(ff.kda.gdn1))/jvp(ff.kda.gdn1)/checkpoint/"
        "prep/dot_general"
    ) == ("bwd", "kda", "gdn1/prep")


def test_readers_on_a_recorded_trace_of_the_cell(monkeypatch, capsys):
    ctx = context(monkeypatch, RECORDED)
    steps = ctx["steps_traced"]
    assert steps >= 1
    node_ms = reader("gdn_ms").read(ctx)
    table = ctx["step_anatomy"]
    assert node_ms == pytest.approx(1e3 * sa.seconds(table, kinds=("kda",)) / steps)
    # three nodes, each with its five parts as rows of the one table
    rows = {name for (_p, kind, name, _f) in table["rows"] if kind == "kda"}
    assert {name.partition("/")[0] for name in rows} == {"gdn0", "gdn1", "gdn2"}
    for part in ("scan", "prep", "gates", "conv", "norm"):
        assert f"gdn1/{part}" in rows, part
    roof = reader("gdn_scan_roofline")
    share = roof.read(ctx)
    from layer_metrics.kda_scan_roofline import bound, scan_ms

    recurrence = scan_ms(ctx)
    assert 0 < recurrence < node_ms
    assert share == pytest.approx(
        100 * bound(ctx, "gdn_scan")[1] * 1e3 / recurrence
    )
    assert 0 < share <= 100
    # the pass is the per-channel form's three kernels, the inverse a fourth
    kernels = {
        family for (_p, kind, _n, family), s in table["rows"].items()
        if kind == "kda" and family.startswith("pallas/") and s > 0
    }
    assert {"pallas/kda_fwd_chunk", "pallas/kda_states_chunk",
            "pallas/kda_bwd_chunk", "pallas/kda_prep_inverse"} <= kernels
    # attention: the grouped causal kernels under the node's `core`
    flash = reader("gqa256_flash_roofline")
    share = flash.read(ctx)
    assert 0 < share <= 100
    kernels = {
        family for (_p, kind, _n, family), s in table["rows"].items()
        if kind == "ring_attention" and family.startswith("pallas/") and s > 0
    }
    assert kernels == {
        "pallas/flash_fwd_causal_grouped", "pallas/flash_bwd_causal_grouped",
        "pallas/flash_delta_grouped",
    }
    parts = {
        name for (_p, kind, name, _f) in table["rows"] if kind == "ring_attention"
    }
    assert {"attn3/core", "attn3/rows", "attn3/gate"} <= parts
    assert "gqa64_flash_roofline: " in capsys.readouterr().err
    # the held experts' nodes
    held = reader("qwen3next_moe_held_ms").read(ctx)
    assert held == pytest.approx(1e3 * sa.seconds(table, kinds=("experts",)) / steps)
    assert held > 0
    # the delta-rule nodes and the attention are most of the step's mixers
    assert node_ms > 0.2 * 1e3 * table["busy_s"] / steps


def test_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    """A program without the op (the parent's): no `kda` row, nothing for the
    two delta-rule readers; where the configuration states no `gdn_scan` or
    `flash` cost nothing for the shares either; nothing raises."""
    ctx = context(monkeypatch, WITHOUT)
    for name in ("gdn_ms", "gdn_scan_roofline"):
        assert reader(name).read(ctx) is None
    other = bench.load_cell(
        os.path.join(bench.ROOT, "BENCHMARK.json"), "super120b_s4096_1chip"
    )
    ctx.update(config=other["config"], job=other["job"],
               module=bench.load_module(other["module_path"]))
    assert reader("gqa256_flash_roofline").read(ctx) is None
    assert reader("gdn_scan_roofline").read(ctx) is None
    bare = dict(ctx, trace=None)
    bare.pop("step_anatomy", None)
    for name in TRACE_READERS:
        assert reader(name).read(bare) is None


def test_held_rows_reader_reads_the_programs_counter(monkeypatch, capsys):
    import numpy as np

    from flexflow_tpu.observability import routing

    held = reader("qwen3next_held_rows_pct")
    monkeypatch.setattr(routing, "published", lambda: None)
    assert held.read({}) is None
    counted = {
        "nodes": ["moe0"], "rows": [np.array([160.0] * 32)],
        "decisions": [81920], "held_rows_pct": 6.25,
        "max_over_mean_held_load": 1.0, "windows_per_step": [1.0],
    }
    monkeypatch.setattr(routing, "published", lambda: counted)
    assert held.read({}) == 6.25
    err = capsys.readouterr().err
    assert '"held_rows_pct_by_node": [6.25]' in err
    assert '"windows_per_step_by_node": [1.0]' in err


def test_rehearsal_manifest_finds_its_files():
    manifest = os.path.join(bench.BENCH, "rehearsal-qwen3next.json")
    spec = bench.load_cell(manifest, "rehearsal_qwen3next_s128_1chip")
    assert spec["job"]["rehearsal"] and spec["job"]["seq"] == 128
    assert spec["module_path"].endswith("qwen3-next-80b-a3b.py")
    assert [m["name"] for m in spec["per_layer"]][-5:] == list(READERS)
    assert spec["config"]["loss_tolerance"] == 2e-2
