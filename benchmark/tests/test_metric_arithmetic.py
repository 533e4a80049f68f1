"""The arithmetic the metrics rest on, and that the files agree with
`BENCHMARK.json`: FLOPs per token against a hand count, the peaks table, each
per-layer reader against its manifest entry, names and files as the contract
wants them."""

import json
import os
import re

import pytest

import run as bench
from peaks import peaks_for

ROOT = bench.ROOT
MANIFEST = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def config_and_module(name):
    path = os.path.join(bench.BENCH, "configs", name)
    return bench.load_json(path + ".json"), bench.load_module(path + ".py")


def test_bert_flops_per_token_by_hand():
    config, module = config_and_module("bert-large-uncased")
    # per layer: 4 projections 1024x1024, scores+context over 512 positions,
    # two 1024x4096 matmuls; head 1024x30522; x2 FLOPs per MAC, x3 fwd+bwd
    macs = 24 * (4 * 1024 * 1024 + 2 * 512 * 1024 + 2 * 1024 * 4096)
    macs += 1024 * 30522
    assert module.flops_per_token(config, 512) == pytest.approx(6 * macs)
    # attention is the only term that depends on the sequence length
    short = module.flops_per_token(config, 128)
    assert module.flops_per_token(config, 512) - short == pytest.approx(
        6 * 24 * 2 * (512 - 128) * 1024
    )


def test_gpt_flops_per_token_by_hand():
    config, module = config_and_module("cerebras-gpt-1.3b")
    layers = config["n_layer"]
    macs = layers * (
        4 * 2048 * 2048 + 2 * 2048 * (2048 + 1) / 2 + 2 * 2048 * 8192
    )
    macs += 2048 * 50257
    assert module.flops_per_token(config, 2048) == pytest.approx(6 * macs)


def test_flash_kernel_costs():
    config, module = config_and_module("bert-large-uncased")
    cost = module.kernel_costs(config, 24, 512)["flash"]
    # 7 [s,s]x[d] matmuls a layer over all heads, fwd 2 + bwd 5
    assert cost["flops"] == 24 * 7 * 2 * 24 * 512 * 512 * 1024
    # 12 tensors of [b, s, hidden] bf16 a layer
    assert cost["bytes"] == 24 * 12 * 2 * 24 * 512 * 1024


def test_unknown_device_kind_raises():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
    with pytest.raises(KeyError):
        peaks_for("_source")


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_reader_agrees_with_manifest(metric):
    reader = bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", metric["name"] + ".py")
    )
    assert reader.LAYER == metric["layer"]
    assert reader.UNIT == metric["unit"]
    assert reader.SOURCE == metric["source"]
    assert reader.MOVES == metric["moves"]
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    assert callable(reader.read)


def test_manifest_names_files_and_limits():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    cells = MANIFEST["workloads"]
    assert 2 <= len(cells) <= 24
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert {c["config"] for c in cells} == set(configs)
    for entry in configs.values():
        assert entry["file"].startswith("benchmark/")
        held = bench.load_json(os.path.join(ROOT, entry["file"]))
        # every key the manifest calls reduced is explained in the file
        assert set(entry["reduced"]) == set(held["reduced"])
        assert not any(
            k.endswith(("_dim", "_rank", "_size")) for k in entry["reduced"]
        )
    names = (
        [c["name"] for c in cells] + [c["traffic"] for c in cells]
        + list(configs) + [m["name"] for m in MANIFEST["end_to_end"]]
        + [m["name"] for m in MANIFEST["per_layer"]]
    )
    for name in names:
        assert NAME.match(name), name
    for cell in cells:
        assert len(cell["why"]) <= 200
        job = bench.load_json(
            os.path.join(bench.BENCH, "jobs", cell["traffic"] + ".json")
        )
        assert job["chips"] == cell["chips"]
        assert not job.get("rehearsal")
    for metric in MANIFEST["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])
    runs = 2 + 14 * 24
    assert runs * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(MANIFEST)) < 64 * 1024


def test_every_cell_reports_what_the_contract_asks():
    for cell in MANIFEST["workloads"]:
        spec = bench.load_cell(os.path.join(ROOT, "BENCHMARK.json"), cell["name"])
        e2e = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec["per_layer"]
        assert {m["moves"] for m in spec["per_layer"]} <= e2e
