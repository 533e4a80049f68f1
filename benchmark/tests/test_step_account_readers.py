"""The four readers of the program's account of the compiled step
(`step_xla_peak_gb`, `step_walk_peak_gb`, `step_s1_gb`, `step_code_mb`): in
the manifest, on a program that has no `observability/step_account.py` (the
parent's), and in the result line of a traced rehearsal cell on the CPU mesh
(bytes of a CPU compile there: counts, no device number). And the account's
operation families under the names `trace_reduce` prints."""

import json
import os
import subprocess
import sys

import pytest

import run as bench
import trace_reduce

READERS = ("step_xla_peak_gb", "step_walk_peak_gb", "step_s1_gb", "step_code_mb")
WANT = {
    "step_xla_peak_gb": ("GB", "lower", "step_hbm_gb"),
    "step_walk_peak_gb": ("GB", "lower", "step_hbm_gb"),
    "step_s1_gb": ("GB", "higher", "tokens_per_s"),
    "step_code_mb": ("MB", "lower", "setup_s"),
}


def _reader(name):
    return bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", name + ".py")
    )


def test_manifest_holds_the_four_metrics_in_every_cell():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    names = [m["name"] for m in manifest["per_layer"]]
    # in the list, in this order, wherever later entries have been appended
    assert [n for n in names if n in READERS] == list(READERS)
    for name in READERS:
        entry, reader = entries[name], _reader(name)
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"]
        )
        assert entry["layer"] == "lowering and backends"
        assert entry["source"] == "program_counter"
        assert (entry["unit"], entry["better"], entry["moves"]) == WANT[name]
        assert "workloads" not in entry


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_on_a_parents_program(name, monkeypatch):
    import flexflow_tpu.observability as observability

    # the parent's package: no such module to import
    monkeypatch.delattr(observability, "step_account")
    monkeypatch.setitem(
        sys.modules, "flexflow_tpu.observability.step_account", None
    )
    assert _reader(name).read({}) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_where_no_step_was_lowered(name, monkeypatch):
    from flexflow_tpu.observability import step_account

    monkeypatch.setattr(step_account, "_noted", None)
    monkeypatch.setattr(step_account, "_account", None)
    assert _reader(name).read({}) is None


def test_a_reader_that_cannot_make_the_account_says_so_and_fails_no_run(
    monkeypatch, capsys
):
    from flexflow_tpu.observability import step_account

    def broken():
        raise ValueError("no size known for dtype 'q7'")

    monkeypatch.setattr(step_account, "last", broken)
    ctx = {}
    assert [_reader(name).read(ctx) for name in READERS] == [None] * 4
    assert capsys.readouterr().err.count("no size known for dtype 'q7'") == 1


def test_traced_rehearsal_cell_reports_the_four(tmp_path):
    manifest = bench.load_json(os.path.join(bench.BENCH, "rehearsal.json"))
    real = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    wanted = READERS + ("step_traces", "step_lower_s", "step_lower_own_s")
    manifest["per_layer"] += [
        m for m in real["per_layer"] if m["name"] in wanted
    ]
    path = tmp_path / "rehearsal.json"
    path.write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, os.path.join(bench.BENCH, "run.py"), "--workload",
         "rehearsal_bert_s512_1chip", "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--manifest", str(path)],
        env=env, capture_output=True, text=True, timeout=900, cwd=bench.ROOT,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], result["checks"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(wanted) | {"compiles_in_window", "xla_compile_s"} <= set(got)
    # the account found the step `fit` ran: no second trace, nothing
    # compiled in the window, and the same bytes `run.py` read itself
    assert got["step_traces"] == 1 and got["compiles_in_window"] == 0
    step_bytes = result["run"]["step_program_bytes"]
    assert got["step_code_mb"] == step_bytes["code"] / 1e6
    assert 0 < got["step_xla_peak_gb"]
    assert got["step_walk_peak_gb"] >= step_bytes["arguments"] / 1e9
    assert got["step_s1_gb"] == 0  # the CPU has one memory space
    # the report once, and one line of it for a table
    err = done.stderr
    assert err.count("step_account: the compiled step by node") == 1
    for part in ("memory (MB): arguments", "held at the peak",
                 "kept for the backward pass", "made in S(1)", "not walked"):
        assert part in err, part
    [line] = [
        l for l in err.splitlines() if l.startswith("step_account: {")
    ]
    summary = json.loads(line[len("step_account: "):])
    assert summary["memory"]["total"] == step_bytes["total"]
    assert summary["walk_peak_bytes"] == pytest.approx(
        got["step_walk_peak_gb"] * 1e9
    )
    assert summary["seconds"] > 0 and len(summary["holders"]) == 3
    assert all(
        set(h) == {"phase", "kind", "name", "bytes"} for h in summary["holders"]
    )
    assert summary["kept_for_backward_bytes"] > 0
    # what the account cost is booked to none of the set-up readers: they
    # were read before it ran (their entries come first)
    assert got["xla_compile_s"] == result["run"]["compile_seconds"]["setup"]


@pytest.mark.parametrize("hlo", [
    '%fusion.18 = bf16[24,512,1024]{2,1,0} fusion(%a), kind=kOutput, calls=%c',
    '%copy.3 = f32[8]{0} copy(%a)',
    '%psum.7 = f32[8]{0} all-reduce(%a), to_apply=%add',
    '%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) all-gather-start(%a)',
    '%flash_fwd_causal_bshf.4 = f32[8]{0} custom-call(%a), '
    'custom_call_target="tpu_custom_call"',
    '%convert_element_type.25 = f32[128]{0} convert(%a)',
    '%broadcast_select_fusion = f32[8]{0} fusion(%a), kind=kLoop, calls=%c',
])
def test_the_accounts_families_are_the_traces(hlo):
    from flexflow_tpu.observability import step_account

    [(name, _, opcode, _, line)] = step_account.entry_instructions(
        "ENTRY %m (a: f32[8]) -> f32[8] {\n  " + hlo + "\n}\n"
    )
    assert step_account.family(name, opcode, line) == trace_reduce.op_family(
        trace_reduce.short_name(hlo)
    )
