"""bin/ CLI tools (VERDICT round-1 missing #7).

Reference: bin/export-model-arch/src/export_model_arch.cc (model positional
arg + --sp-decomposition/--dot flags) and bin/substitution-to-dot (json-file
+ rule-name -> dot).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_tool(tool, *args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", tool), *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
    )


@pytest.mark.parametrize("model", ["split_test", "single_operator"])
def test_export_model_arch_json(model):
    r = run_tool("export_model_arch.py", model, "--sp-decomposition")
    assert r.returncode == 0, r.stderr[-1500:]
    doc = json.loads(r.stdout)
    assert "computation_graph" in doc
    assert "sp_decomposition" in doc
    # the decomposition is a nested series/parallel/int tree
    top = doc["sp_decomposition"]
    assert isinstance(top, (int, dict))


def test_export_model_arch_dot():
    r = run_tool("export_model_arch.py", "single_operator", "--dot")
    assert r.returncode == 0, r.stderr[-1500:]
    assert r.stdout.startswith("digraph")


def test_export_unknown_model_rejected():
    r = run_tool("export_model_arch.py", "nonexistent_model")
    assert r.returncode != 0


LEGACY = "/root/reference/substitutions/test_subst.json"


@pytest.mark.skipif(not os.path.exists(LEGACY), reason="corpus not mounted")
def test_substitution_to_dot():
    r = run_tool("substitution_to_dot.py", LEGACY, "example_subst")
    assert r.returncode == 0, r.stderr[-1500:]
    assert r.stdout.startswith("digraph substitution")
    assert "OP_EW_ADD" in r.stdout
    assert "OP_PARTITION" in r.stdout


@pytest.mark.skipif(not os.path.exists(LEGACY), reason="corpus not mounted")
def test_substitution_to_dot_missing_rule():
    r = run_tool("substitution_to_dot.py", LEGACY, "no_such_rule")
    assert r.returncode == 1
    assert "Could not find rule" in r.stderr


# -- protobuf_to_json + arg_parser (reference bin/protobuf_to_json,
# bin/arg_parser) -----------------------------------------------------------


def _varint(v):
    if v < 0:
        v += 1 << 64
    out = b""
    while True:
        b7 = v & 0x7F
        v >>= 7
        if v:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def _field(n, wt, payload):
    tag = _varint((n << 3) | wt)
    if wt == 0:
        return tag + _varint(payload)
    return tag + _varint(len(payload)) + payload


def _make_rule_collection():
    """One rule: Linear(graph input, PM_ACTI=NONE) -> same, output mapped."""
    tensor = _field(1, 0, -1) + _field(2, 0, 0)
    para = _field(1, 0, 9) + _field(2, 0, 0)  # PM_ACTI = AC_MODE_NONE
    lin = _field(1, 0, 5) + _field(2, 2, tensor) + _field(3, 2, para)
    mo = (
        _field(1, 0, 0) + _field(2, 0, 0) + _field(3, 0, 0) + _field(4, 0, 0)
    )
    rule = _field(1, 2, lin) + _field(2, 2, lin) + _field(3, 2, mo)
    return _field(1, 2, rule)


def test_protobuf_to_json_roundtrip(tmp_path):
    pb = tmp_path / "rules.pb"
    out = tmp_path / "rules.json"
    pb.write_bytes(_make_rule_collection())
    r = run_tool("protobuf_to_json.py", str(pb), str(out))
    assert r.returncode == 0, r.stderr
    assert "Loaded 1 rules." in r.stdout
    doc = json.loads(out.read_text())
    assert doc["_t"] == "RuleCollection"
    (rule,) = doc["rule"]
    assert rule["name"] == "taso_rule_0"
    assert rule["srcOp"][0]["type"] == "OP_LINEAR"
    assert rule["srcOp"][0]["input"][0]["opId"] == -1  # sign-extended varint
    assert rule["srcOp"][0]["para"][0] == {
        "_t": "Parameter", "key": "PM_ACTI", "value": "AC_MODE_NONE",
    }

    # the converted JSON must feed the legacy-rules loader
    sys.path.insert(0, REPO)
    from flexflow_tpu.substitutions.legacy_rules import (
        load_rule_collection_from_path,
    )

    collection = load_rule_collection_from_path(str(out))
    assert len(collection.rules) == 1
    assert collection.rules[0].srcOp[0].op_type == "OP_LINEAR"


def test_arg_parser_dumps_config():
    r = run_tool(
        "arg_parser.py",
        "-e", "3", "-b", "32", "--search-budget", "20", "--perform-fusion",
    )
    assert r.returncode == 0, r.stderr
    cfg = json.loads(r.stdout)
    assert cfg["epochs"] == 3
    assert cfg["batch_size"] == 32
    assert cfg["search_budget"] == 20
    assert cfg["perform_fusion"] is True
