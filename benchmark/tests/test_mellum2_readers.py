"""The seven readers of the window / YaRN / held-experts cell
(`mellum2_window_attn_ms`, `mellum2_full_attn_ms`,
`mellum2_window_flash_roofline`, `mellum2_full_flash_roofline`,
`mellum2_window_live_tiles_pct`, `mellum2_moe_held_ms`,
`mellum2_held_rows_pct`) on a trace recorded on the chip from
`mellum2_12b_s8192_1chip` (its four `ff.ring_attention.*` scopes, three
with the banded kernels under them and one with the unbanded ones, and its
four `ff.experts.*` scopes), `kernel_costs` checked by hand, the program's
counters, and where there is nothing to read (a trace of a program without
such scopes, no trace at all, a program without the counters), where the
readers return nothing."""

import gzip
import json
import os

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "mellum2_events.json.gz")
# programs whose attention nodes carry other names, or none
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")
OTHER_MODEL = os.path.join(TESTDATA, "joyai_events.json.gz")
READERS = (
    "mellum2_window_attn_ms", "mellum2_full_attn_ms",
    "mellum2_window_flash_roofline", "mellum2_full_flash_roofline",
    "mellum2_window_live_tiles_pct", "mellum2_moe_held_ms",
    "mellum2_held_rows_pct",
)
CELL = "mellum2_12b_s8192_1chip"
MANIFEST = os.path.join(bench.ROOT, "BENCHMARK.json")


def reader(name):
    return bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", name + ".py")
    )


def context(monkeypatch, recorded):
    with gzip.open(recorded, "rt") as f:
        events = sa.unpack(json.load(f))
    monkeypatch.setattr(sa, "_trace_path", lambda argv: "the.xplane.pb")
    monkeypatch.setattr(sa, "load_scoped", lambda path: events)
    spec = bench.load_cell(MANIFEST, CELL)
    return {
        "trace": {"busy_s": 1.0}, "steps_traced": sa.traced_steps(events),
        "chips": 1, "device_kind": "TPU v5 lite", "config": spec["config"],
        "job": spec["job"], "module": bench.load_module(spec["module_path"]),
    }


def test_the_cell_lists_the_seven_readers_and_they_exist():
    spec = bench.load_cell(MANIFEST, CELL)
    assert spec["job"]["seq"] == 8192 and spec["job"]["batch_per_chip"] == 1
    assert spec["cell"]["traffic"] == "pretrain_s8192_b1_1chip"
    assert spec["cell"]["chips"] == 1
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL]
        module = reader(name)
        assert (module.UNIT, module.MOVES, module.SOURCE, module.LAYER) == (
            listed[name]["unit"], "tokens_per_s", listed[name]["source"],
            "kernels",
        )
    # the accepted metrics with no list read this cell as they read every other
    assert {"attention_ms", "fwd_ms", "busy_mfu_pct"} <= set(listed)
    # and the accepted metrics that list their cells were left as they were
    manifest = bench.load_json(MANIFEST)
    for metric in manifest["per_layer"]:
        if not metric["name"].startswith("mellum2_"):
            assert CELL not in metric.get("workloads", [])


def test_the_configuration_holds_the_catalog_rows_numbers():
    config = bench.load_json(
        os.path.join(bench.BENCH, "configs", "mellum2-12b-a2.5b.json")
    )
    row = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True,
    }
    differs = {k for k, v in row.items() if config.get(k) != v}
    assert differs == {"num_experts", "num_hidden_layers"}
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782,
        },
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    }
    assert set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
        "vocab_rows_held",
    }
    assert config["vocab_rows_held"] * 4 == config["vocab_size"]
    assert config["num_experts"] * 4 == config["num_experts_total"] == 64
    for key in ("assumed", "departures", "parameters", "deployment"):
        assert config[key]


def test_kernel_costs_and_bounds_by_hand():
    spec = bench.load_cell(MANIFEST, CELL)
    module = bench.load_module(spec["module_path"])
    config = spec["config"]
    costs = module.kernel_costs(config, 1, 8192)
    band = 1024 * 8192 - 1024 * 1023 // 2
    causal = 8192 * 8193 // 2
    assert costs["flash_window"]["flops"] == 3 * 7 * 2 * band * 32 * 128
    assert costs["flash"]["flops"] == 7 * 2 * causal * 32 * 128
    ctx = {"module": module, "config": config, "job": spec["job"],
           "device_kind": "TPU v5 lite"}
    from layer_metrics.kda_scan_roofline import bound

    kind, least = bound(ctx, "flash_window")
    assert kind == "compute" and least == pytest.approx(6.87e-3, rel=0.01)
    kind, least = bound(ctx, "flash")
    assert kind == "compute" and least == pytest.approx(9.77e-3, rel=0.01)
    # the band keeps 23.4% of the causal pairs and visits 33.1% of its tiles
    assert band / causal == pytest.approx(0.2344, abs=1e-3)
    # the whole step at 6 products a core: about 12.3 TFLOP
    step = module.flops_per_token(config, 8192) * 8192
    assert 12.0e12 < step < 12.6e12


def test_readers_on_a_recorded_trace_of_the_cell(monkeypatch, capsys):
    ctx = context(monkeypatch, RECORDED)
    steps = ctx["steps_traced"]
    assert steps >= 1
    window_ms = reader("mellum2_window_attn_ms").read(ctx)
    full_ms = reader("mellum2_full_attn_ms").read(ctx)
    table = ctx["step_anatomy"]
    nodes = {
        name.partition("/")[0] for (_p, kind, name, _f) in table["rows"]
        if kind == "ring_attention"
    }
    assert nodes == {"attn0", "attn1", "attn2", "attn3"}
    assert window_ms + full_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("ring_attention",)) / steps
    )
    by_node = reader("mellum2_window_attn_ms").nodes_ms(ctx, "sliding_attention")
    assert set(by_node) == {"attn0", "attn1", "attn2"}
    assert sum(by_node.values()) == pytest.approx(window_ms)
    # a banded node costs less than the full one, and the three more
    assert max(by_node.values()) < full_ms < window_ms
    # the kernels under each kind of node, by the names the program gave them

    def kernels(names):
        return {
            family for (_p, kind, name, family), s in table["rows"].items()
            if kind == "ring_attention" and name.partition("/")[0] in names
            and family.startswith("pallas/") and s > 0
        }

    assert kernels(by_node) == {
        "pallas/flash_fwd_causal_bshf_window",
        "pallas/flash_bwd_causal_bshf_window", "pallas/flash_delta_bshf",
    }
    assert kernels({"attn3"}) == {
        "pallas/flash_fwd_causal_bshf", "pallas/flash_bwd_causal_bshf",
        "pallas/flash_delta_bshf",
    }
    banded = reader("mellum2_window_flash_roofline").read(ctx)
    full = reader("mellum2_full_flash_roofline").read(ctx)
    assert 0 < banded < full <= 100
    # 45 of 136 tiles for 23.4% of the pairs: at most 0.71 of the full share
    assert banded <= 0.75 * full
    moe_ms = reader("mellum2_moe_held_ms").read(ctx)
    assert moe_ms == pytest.approx(
        1e3 * sa.seconds(table, kinds=("experts",)) / steps
    )
    err = capsys.readouterr().err
    for name in READERS[:4]:
        assert f"{name}: " in err


@pytest.mark.parametrize("recorded", [WITHOUT, OTHER_MODEL])
def test_readers_find_nothing_where_there_is_nothing_to_read(
    monkeypatch, recorded
):
    """A program whose attention nodes are not this configuration's: no row
    for the four trace readers; a configuration that states no such cost or
    names no such nodes: nothing; no trace: nothing; nothing raises."""
    from flexflow_tpu.observability import routing, trace

    monkeypatch.setattr(trace, "window_tiles", lambda: {})
    monkeypatch.setattr(routing, "published", lambda: None)
    ctx = context(monkeypatch, recorded)
    for name in READERS[:5] + READERS[6:]:
        assert reader(name).read(ctx) is None
    other = bench.load_cell(MANIFEST, "super120b_s4096_1chip")
    ctx.update(config=other["config"], job=other["job"],
               module=bench.load_module(other["module_path"]))
    for name in READERS[:4]:
        assert reader(name).read(ctx) is None
    bare = dict(ctx, trace=None)
    bare.pop("step_anatomy", None)
    for name in READERS:
        assert reader(name).read(bare) is None


def test_the_programs_counters_reach_the_readers(monkeypatch):
    from flexflow_tpu.observability import trace

    tiles = reader("mellum2_window_live_tiles_pct")
    monkeypatch.setattr(trace, "window_tiles", lambda: {
        f"ff.ring_attention.attn{i}": (45, 136) for i in range(3)
    })
    assert tiles.read({}) == pytest.approx(100 * 45 / 136)
    monkeypatch.setattr(trace, "rotaries", lambda: {
        "ff.ring_attention.attn3": "yarn factor=16 low=18 high=35 amp=1.2773"
    })
    counters = reader("mellum2_window_attn_ms").program_counters()
    assert counters["window_tiles"]["ff.ring_attention.attn0"] == (45, 136)
    assert "yarn" in counters["rotaries"]["ff.ring_attention.attn3"]
    # a program from before the counters (the parent): nothing, nothing raises
    monkeypatch.delattr(trace, "rotaries")
    monkeypatch.delattr(trace, "window_tiles")
    assert tiles.read({}) is None
    counters = reader("mellum2_window_attn_ms").program_counters()
    assert "rotaries" not in counters and "window_tiles" not in counters


def test_the_rehearsal_manifest_lists_the_toy_cell():
    manifest = os.path.join(bench.BENCH, "rehearsal-mellum2.json")
    spec = bench.load_cell(manifest, "rehearsal_mellum2_s128_1chip")
    assert spec["job"]["rehearsal"] and spec["job"]["seq"] == 128
    assert spec["config"]["py"] == "mellum2-12b-a2.5b.py"
    assert {m["name"] for m in spec["per_layer"]} >= set(READERS)
