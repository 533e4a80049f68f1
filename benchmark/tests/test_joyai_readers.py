"""The five readers of the latent-attention / multi-token-prediction cell
(`mla_rope_ms`, `mla_rope_flash_roofline`, `mtp_ms`, `joyai_moe_held_ms`,
`joyai_held_rows_pct`) on a trace recorded on the chip from
`joyaiflash48b_s8192_1chip` (its six `ff.ring_attention.*` scopes with the
parts `latent`, `rows` and `core` inside them, its five `ff.experts.*`
scopes, the module's nodes under names that start with `mtp_` and the second
loss under `ff.label_loss.mtp_loss`), `kernel_costs` checked by hand, each
share bounded by 100, and where there is nothing to read (a trace of a
program without such scopes, no trace at all, a program without the
counters), where the readers return nothing."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "joyai_events.json.gz")
# a program without an `experts`, a `label_loss` or an `mtp_*` scope
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")
TRACE_READERS = (
    "mla_rope_ms", "mla_rope_flash_roofline", "mtp_ms", "joyai_moe_held_ms",
)
READERS = TRACE_READERS + ("joyai_held_rows_pct",)
CELL = "joyaiflash48b_s8192_1chip"


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
    # found by `run.py` without an edit to it, and the job file is one the
    # benchmark had
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "joyai-llm-flash",
        "traffic": "pretrain_s8192_b1_1chip", "chips": 1,
        "why": cells[CELL]["why"],
    }
    assert len(cells[CELL]["why"]) <= 200
    entry = {c["name"]: c for c in manifest["configs"]}["joyai-llm-flash"]
    assert entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_rows_held"
    ]
    assert not any(
        key.endswith(("_dim", "_rank", "_size")) for key in entry["reduced"]
    )
    spec = cell()
    assert spec["job"]["seq"] == 8192 and spec["job"]["batch_per_chip"] == 1
    assert spec["job"]["dataset_batches"] == 16
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL]
        module = reader(name)
        source = (
            "program_counter" if name == "joyai_held_rows_pct"
            else "device_trace"
        )
        assert (module.UNIT, module.MOVES, module.SOURCE, module.LAYER) == (
            listed[name]["unit"], "tokens_per_s", source, "kernels",
        )
        assert listed[name]["source"] == source
    # every accepted metric without a list of cells is the new cell's too
    for metric in manifest["per_layer"]:
        if "workloads" not in metric:
            assert metric["name"] in listed


def test_the_file_states_the_published_widths_and_the_cut():
    config = cell()["config"]
    published = {
        "hidden_size": 2048, "num_attention_heads": 32, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "rope_theta": 32000000, "rope_interleave": True,
        "intermediate_size": 7168, "moe_intermediate_size": 768,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "n_shared_experts": 1, "rms_norm_eps": 1e-6,
        "num_nextn_predict_layers": 1, "first_k_dense_replace": 1,
        "vocab_size": 129280, "tie_word_embeddings": False,
    }
    for key, value in published.items():
        assert config[key] == value, key
    assert sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_rows_held"
    ]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["num_experts_total"], config["vocab_rows_held"]) == (
        5, 8, 256, 16160
    )
    assert config["vocab_rows_held"] * 8 == config["vocab_size"]
    assert config["deployment"].startswith("32 chips share each layer")
    assert config["training"]["state_dtype"] == "float32"
    for key in ("mtp_loss_weight", "mtp input", "mtp block", "selection bias"):
        assert key in config["assumed"], key
    assert config["mtp_loss_weight"] == 0.3


def test_kernel_costs_by_hand():
    spec = cell()
    module = bench.load_module(spec["module_path"])
    costs = module.kernel_costs(spec["config"], 1, 8192)
    assert module.counts(spec["config"]) == (6, 1, 5, 2)
    # six nodes, 32 TRUE heads of 192 | 128, the causal half of 8,192 x 8,192
    # pairs: four key-wide and three value-wide products
    pairs = 8192 * 8193 / 2
    assert costs["flash"]["flops"] == 6 * 2 * pairs * 32 * (4 * 192 + 3 * 128)
    assert costs["flash"]["bytes"] == 6 * 6 * 2 * 8192 * 32 * (192 + 128)
    ctx = {"module": module, "config": spec["config"], "job": spec["job"],
           "device_kind": "TPU v5 lite"}
    from layer_metrics.kda_scan_roofline import bound

    # 14.84 TFLOP at 197 TFLOP/s is 75.3 ms: compute binds
    kind, least = bound(ctx, "flash")
    assert kind == "compute" and least == pytest.approx(75.3e-3, rel=0.01)
    # a step's model FLOPs: the module's block and the head's second use in
    assert module.flops_per_token(spec["config"], 8192) == pytest.approx(
        3.363e9, rel=0.01
    )


def test_parse_scope_on_the_latent_nodes_parts_and_the_second_loss():
    from flexflow_tpu.observability import trace

    assert trace.parse_scope(
        "jit(_step)/jvp(ff.ring_attention.mla2)/rows/concatenate"
    ) == ("fwd", "ring_attention", "mla2/rows")
    assert trace.parse_scope(
        "jit(_step)/transpose(jvp(ff.ring_attention.mtp_mla))/latent/dot_general"
    ) == ("bwd", "ring_attention", "mtp_mla/latent")
    assert trace.parse_scope(
        "jit(_step)/jvp(ff.ring_attention.mla0)/core/"
        "flash_fwd_causal_wide_key/pallas_call"
    ) == ("fwd", "ring_attention", "mla0/core")
    assert trace.parse_scope(
        "jit(_step)/jvp(ff.label_loss.mtp_loss)/reduce_sum"
    ) == ("fwd", "label_loss", "mtp_loss")
    # the main loss stays `ff.loss`
    assert trace.parse_scope("jit(_step)/jvp(ff.loss)/reduce_max")[1] == "loss"


def test_readers_on_a_recorded_trace_of_the_cell(monkeypatch, capsys):
    ctx = context(monkeypatch, RECORDED)
    steps = ctx["steps_traced"]
    assert steps >= 1
    attention = reader("mla_rope_ms")
    node_ms = attention.read(ctx)
    table = ctx["step_anatomy"]
    assert node_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("ring_attention",)) / steps
    )
    rows = {
        name for (_p, kind, name, _f) in table["rows"]
        if kind == "ring_attention"
    }
    nodes = {"mla0", "mla1", "mla2", "mla3", "mla4", "mtp_mla"}
    assert {name.partition("/")[0] for name in rows} == nodes
    for part in ("latent", "rows", "core"):
        assert f"mla3/{part}" in rows, part
    parts = attention.parts_ms(ctx)
    assert set(parts) == {"latent", "rows", "core", attention.REST}
    assert sum(parts.values()) == pytest.approx(node_ms)
    # the kernels are most of the node, and the rotary pass the least part
    assert parts["core"] > 0.5 * node_ms
    assert parts["rows"] < parts["latent"]
    err = capsys.readouterr().err
    assert '"parts_ms"' in err and '"latent_attention_forms"' in err
    # the share of the roofline: the wide-key kernels by scope
    share = reader("mla_rope_flash_roofline").read(ctx)
    assert 0 < share <= 100
    kernels = {
        family for (_p, kind, _n, family), s in table["rows"].items()
        if kind == "ring_attention" and family.startswith("pallas/") and s > 0
    }
    assert kernels == {
        "pallas/flash_fwd_causal_wide_key", "pallas/flash_bwd_causal_bshf",
        "pallas/flash_delta_bshf",
    }
    # the module: its nodes by name, the second loss among them
    module = reader("mtp_ms")
    mtp = module.read(ctx)
    by_node = module.by_node_ms(ctx)
    assert mtp == pytest.approx(sum(by_node.values()))
    for node in ("ring_attention.mtp_mla", "experts.mtp_moe", "dense.mtp_proj",
                 "dense.mtp_head", "embedding.mtp_embed", "label_loss.mtp_loss",
                 "rms_norm.mtp_norm_e", "rms_norm.mtp_norm_h",
                 "rms_norm.mtp_norm_f"):
        assert by_node[node] > 0, node
    assert '"by_node_ms"' in capsys.readouterr().err
    # one block of six and a head: between a tenth and a third of the step
    busy = 1e3 * table["busy_s"] / steps
    assert 0.1 * busy < mtp < 0.33 * busy
    # the held experts' nodes, the module's among them
    held = reader("joyai_moe_held_ms").read(ctx)
    assert held == pytest.approx(1e3 * sa.seconds(table, kinds=("experts",)) / steps)
    experts = {
        name.partition("/")[0] for (_p, kind, name, _f) in table["rows"]
        if kind == "experts"
    }
    assert experts == {"moe1", "moe2", "moe3", "moe4", "mtp_moe"}
    # the six latent nodes are most of the step
    assert node_ms > 0.5 * busy


def test_readers_find_nothing_where_there_is_nothing_to_read(monkeypatch):
    """A program without the ops (the parent's): no `experts` row, no node
    named `mtp_*`; where the configuration states no `flash` cost nothing for
    the share either; nothing raises."""
    ctx = context(monkeypatch, WITHOUT)
    for name in ("mtp_ms", "joyai_moe_held_ms"):
        assert reader(name).read(ctx) is None
    other = bench.load_cell(
        os.path.join(bench.ROOT, "BENCHMARK.json"), "super120b_s4096_1chip"
    )
    ctx.update(config=other["config"], job=other["job"],
               module=bench.load_module(other["module_path"]))
    assert reader("mla_rope_flash_roofline").read(ctx) is None
    bare = dict(ctx, trace=None)
    bare.pop("step_anatomy", None)
    for name in TRACE_READERS:
        assert reader(name).read(bare) is None


def test_readers_stand_a_program_without_the_new_counters(monkeypatch):
    """On the parent's program `trace` has neither `latent_attention_forms`
    nor `loss_terms`: the readers print what there is and raise nothing."""
    from flexflow_tpu.observability import trace

    monkeypatch.delattr(trace, "latent_attention_forms")
    monkeypatch.delattr(trace, "loss_terms")
    assert list(reader("mla_rope_ms").program_counters()) == ["attention_routes"]
    assert reader("mtp_ms").loss_terms() is None


def test_held_rows_reader_reads_the_programs_counter(monkeypatch, capsys):
    import numpy as np

    from flexflow_tpu.observability import routing

    held = reader("joyai_held_rows_pct")
    monkeypatch.setattr(routing, "published", lambda: None)
    assert held.read({}) is None
    counted = {
        "nodes": ["moe1"], "rows": [np.array([256.0] * 8)],
        "decisions": [65536], "held_rows_pct": 3.125,
        "max_over_mean_held_load": 1.0, "windows_per_step": [1.0],
    }
    monkeypatch.setattr(routing, "published", lambda: counted)
    assert held.read({}) == 3.125
    err = capsys.readouterr().err
    assert '"held_rows_pct_by_node": [3.125]' in err
    assert '"windows_per_step_by_node": [1.0]' in err


def test_rehearsal_manifest_finds_its_files():
    manifest = os.path.join(bench.BENCH, "rehearsal-joyai.json")
    spec = bench.load_cell(manifest, "rehearsal_joyai_s128_1chip")
    assert spec["job"]["rehearsal"] and spec["job"]["seq"] == 128
    assert spec["module_path"].endswith("joyai-llm-flash.py")
    assert [m["name"] for m in spec["per_layer"]][-5:] == list(READERS)
    assert spec["config"]["loss_tolerance"] == 2e-2
    assert spec["config"]["num_nextn_predict_layers"] == 1
