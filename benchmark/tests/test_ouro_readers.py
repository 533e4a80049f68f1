"""The three readers of the looped cell (`ouro_loop_ms`, `ouro_exit_ms`,
`ouro_flash_roofline`) on a trace recorded on the chip from
`ouro26b_s8192_1chip` (its nodes' scopes carry their pass,
`ff.<kind>.<name>#<pass>`), `kernel_costs` and `parameter_counts` checked
by hand, the configuration against the catalog's row, the rehearsal files
and the toy cell's run on the CPU mesh through them, and where there is
nothing to read (a trace of a program without such scopes, no trace at all,
a program without the counters), where the readers return nothing."""

import gzip
import json
import os
import subprocess
import sys

import pytest

import run as bench
import step_anatomy as sa

TESTDATA = os.path.join(bench.BENCH, "testdata")
RECORDED = os.path.join(TESTDATA, "ouro_events.json.gz")
# programs whose nodes carry no pass
WITHOUT = os.path.join(TESTDATA, "step_anatomy_events.json.gz")
OTHER_MODEL = os.path.join(TESTDATA, "mellum2_events.json.gz")
READERS = ("ouro_loop_ms", "ouro_exit_ms", "ouro_flash_roofline")
CELL = "ouro26b_s8192_1chip"
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


def test_the_cell_lists_the_three_readers_and_they_exist():
    spec = bench.load_cell(MANIFEST, CELL)
    assert spec["job"]["seq"] == 8192 and spec["job"]["batch_per_chip"] == 1
    assert spec["cell"]["traffic"] == "pretrain_s8192_b1_1chip"
    assert spec["cell"]["chips"] == 1 and spec["cell"]["config"] == "ouro-2.6b"
    listed = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL]
        module = reader(name)
        assert (module.UNIT, module.MOVES, module.SOURCE, module.LAYER) == (
            listed[name]["unit"], "tokens_per_s", listed[name]["source"],
            listed[name]["layer"],
        )
    # the accepted metrics with no list read this cell as they read every other
    assert {"attention_ms", "fwd_ms", "busy_mfu_pct", "unattributed_pct"} <= set(listed)
    # and the accepted metrics that list their cells were left as they were
    manifest = bench.load_json(MANIFEST)
    for metric in manifest["per_layer"]:
        if not metric["name"].startswith("ouro_"):
            assert CELL not in metric.get("workloads", [])
    entry = {c["name"]: c for c in manifest["configs"]}["ouro-2.6b"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert entry["source"] == spec["config"]["source"]


def test_the_configuration_holds_the_catalog_rows_numbers():
    config = bench.load_json(
        os.path.join(bench.BENCH, "configs", "ouro-2.6b.json")
    )
    row = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152,
    }
    differs = {k for k, v in row.items() if config.get(k) != v}
    assert differs == {"num_hidden_layers"}
    assert config["num_hidden_layers"] in (4, 5, 6)  # the floor is four
    assert config["layer_types"] == (
        ["full_attention"] * config["num_hidden_layers"]
    )
    assert set(config["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert config["training"]["loss"] == "loss_nodes"
    assert 0 <= config["recomputed_passes"] <= config["total_ut_steps"]
    for key in ("assumed", "departures", "parameters", "deployment"):
        assert config[key]


def test_kernel_costs_and_parameter_counts_by_hand():
    spec = bench.load_cell(MANIFEST, CELL)
    module = bench.load_module(spec["module_path"])
    config = spec["config"]
    layers = config["num_hidden_layers"]
    counts = module.parameter_counts(config)
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert sum(counts["layer"].values()) == per_layer == 51_388_416
    assert counts["embedding"] + counts["head"] == 201_326_592
    assert counts["final_norm"] + counts["gate"] == 4_097
    assert counts["total"] == layers * per_layer + 201_326_592 + 4_097
    assert format(counts["total"], ",") in config["parameters"]["as_built"]
    # ONE set of weights: the passes are not in the count
    assert module.parameter_counts(dict(config, total_ut_steps=8)) == counts
    costs = module.kernel_costs(config, 1, 8192)
    causal = 8192 * 8193 // 2
    applications = 4 * layers
    assert costs["flash"]["flops"] == applications * 7 * 2 * causal * 16 * 128
    ctx = {"module": module, "config": config, "job": spec["job"],
           "device_kind": "TPU v5 lite"}
    from layer_metrics.flash_roofline import bound

    kind, least = bound(ctx)
    # one application's core is `cgpt13b`'s eight layers' over: 0.96 TFLOP
    assert kind == "compute"
    assert least == pytest.approx(applications * 4.885e-3, rel=0.01)
    # the whole step at 6 products a core: 12.2 GFLOP a token at six layers
    per_token = module.flops_per_token(config, 8192)
    head = 3 * 4 * 2 * 2048 * 49152
    assert per_token == pytest.approx(
        3 * applications * (2 * 4 * 2048**2 + 4 * 2048 * 4096.5
                            + 6 * 2048 * 5632) + head, rel=1e-4,
    )


def test_readers_on_a_recorded_trace_of_the_cell(monkeypatch, capsys):
    ctx = context(monkeypatch, RECORDED)
    steps = ctx["steps_traced"]
    assert steps >= 1
    config = ctx["config"]
    loop = reader("ouro_loop_ms")
    loop_ms = loop.read(ctx)
    exit_ms = reader("ouro_exit_ms").read(ctx)
    table = ctx["step_anatomy"]
    rows = loop.rows_of(ctx, ctx["module"].layer_nodes(config))
    passes = {row[3] for row in rows}
    assert passes == {str(t) for t in range(1, config["total_ut_steps"] + 1)}
    attention = {row[2] for row in rows if row[1] == "ring_attention"}
    assert attention == {f"attn{i}" for i in range(config["num_hidden_layers"])}
    by_pass = loop.by_pass_and_phase(rows)
    assert sum(by_pass.values()) == pytest.approx(loop_ms)
    # a recomputed pass pays its forward twice: once forward, once as
    # backward time; the last pass, kept whole, does not
    recomputed = config["recomputed_passes"]
    if 0 < recomputed < config["total_ut_steps"]:
        assert by_pass["pass1.bwd"] > 1.2 * by_pass[f"pass{recomputed + 1}.bwd"]
    for t in passes:
        assert by_pass[f"pass{t}.fwd"] == pytest.approx(
            by_pass["pass1.fwd"], rel=0.05
        )
    # the loop is most of the step and the exits are the next largest part
    step_ms = 1e3 * sum(table["rows"].values()) / steps
    assert 0.6 * step_ms < loop_ms < 0.9 * step_ms
    assert 0.08 * step_ms < exit_ms < 0.35 * step_ms

    def kernels(kind):
        return {
            family for (_p, k, _name, family), s in table["rows"].items()
            if k == kind and family.startswith("pallas/") and s > 0
        }

    assert kernels("ring_attention") == {
        "pallas/flash_fwd_causal_bshf", "pallas/flash_bwd_causal_bshf",
        "pallas/flash_delta_bshf",
    }
    share = reader("ouro_flash_roofline").read(ctx)
    assert 30 < share <= 100
    err = capsys.readouterr().err
    # the recomputed forward kernels are on neither side of the share: they
    # are the forward kernels of `recomputed_passes` of the passes, again
    split = json.loads(err.split("ouro_flash_roofline: ")[1].splitlines()[0])
    forward = sum(
        row[-1] for row in rows
        if row[0] == "fwd" and row[4] == "pallas/flash_fwd_causal_bshf"
    )
    assert split["recomputed_forward_kernels_ms"] == pytest.approx(
        forward * recomputed / config["total_ut_steps"], rel=0.02
    )
    assert share == pytest.approx(
        100 * split["least_ms"] / split["kept_kernels_ms"]
    )
    assert "ouro_loop_ms: " in err and "ouro_exit_ms: " in err
    assert '"pass4.bwd"' in err and '"entropy_ms"' in err


@pytest.mark.parametrize("recorded", [WITHOUT, OTHER_MODEL])
def test_readers_find_nothing_where_there_is_nothing_to_read(
    monkeypatch, recorded
):
    """A program whose nodes carry no pass (the parent's, on any cell): no
    row for any of the three; a configuration that names no such nodes:
    nothing; no trace: nothing; nothing raises."""
    ctx = context(monkeypatch, recorded)
    for name in READERS:
        assert reader(name).read(ctx) is None
    other = bench.load_cell(MANIFEST, "cgpt13b_s2048_1chip")
    ctx.update(config=other["config"], job=other["job"],
               module=bench.load_module(other["module_path"]))
    for name in READERS:
        assert reader(name).read(ctx) is None
    bare = dict(context(monkeypatch, RECORDED), trace=None)
    for name in READERS:
        assert reader(name).read(bare) is None


def test_the_programs_counters_reach_the_exit_reader(monkeypatch):
    from flexflow_tpu.observability import trace

    exits = reader("ouro_exit_ms")
    terms = {
        "ff.label_loss.exit#1": {"weight": 1.0, "mean": 2.7, "mass": 0.25},
        "ff.mean_loss.entropy": {"weight": 0.1, "mean": -1.38},
    }
    monkeypatch.setattr(trace, "loss_terms", lambda: terms)
    assert exits.loss_terms() == terms
    # a program from before the counter (the parent): nothing, nothing raises
    monkeypatch.delattr(trace, "loss_terms")
    assert exits.loss_terms() is None
    monkeypatch.delattr(trace, "attention_routes")
    assert reader("ouro_loop_ms").attention_routes() is None


def test_the_rehearsal_files_run_the_toy_cell_on_the_cpu_mesh():
    manifest = os.path.join(bench.BENCH, "rehearsal-ouro.json")
    spec = bench.load_cell(manifest, "rehearsal_ouro_s128_1chip")
    assert spec["job"]["rehearsal"] and spec["job"]["seq"] == 128
    assert spec["config"]["py"] == "ouro-2.6b.py"
    assert spec["config"]["total_ut_steps"] == 4
    assert spec["config"]["loss_tolerance"] == 2e-2
    assert {m["name"] for m in spec["per_layer"]} >= set(READERS)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    run = subprocess.run(
        [sys.executable, os.path.join(bench.BENCH, "run.py"),
         "--manifest", manifest, "--workload", "rehearsal_ouro_s128_1chip",
         "--seed", "3000000019", "--seconds", "2", "--trace", "1"],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert run.returncode == 0, run.stderr[-3000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0, result
    assert result["device"]["platform"] == "cpu"
    # no device plane on the CPU mesh: the three report nothing, and say so
    assert not set(READERS) & set(result["metrics"])
    assert "ouro-2.6b reference: " in run.stderr
