"""The three readers of set-up by owner (`step_lower_own_s`, `node_trace_s`,
`pre_program_s`): on the program's tables, on a parent's program that keeps
none, in the manifest, and in the result line of a traced rehearsal cell on
the CPU mesh (seconds of a host clock there: counts and order, no device
number)."""

import json
import os
import subprocess
import sys
import time

import pytest

import host_spans
import run as bench

READERS = ("step_lower_own_s", "node_trace_s", "pre_program_s")


def _reader(name):
    return bench.load_module(
        os.path.join(bench.BENCH, "layer_metrics", name + ".py")
    )


@pytest.mark.parametrize("name", READERS)
def test_readers_return_nothing_on_a_parents_program(name, monkeypatch):
    import types

    from flexflow_tpu.observability import trace

    # the parent's module: the host spans and no table by owner
    parent = types.SimpleNamespace(
        HOST_SPANS=trace.HOST_SPANS, LOWERING_EVENTS=trace.LOWERING_EVENTS,
        span_totals=trace.span_totals,
    )
    monkeypatch.setattr(host_spans, "program_trace", lambda: parent)
    assert _reader(name).read({}) is None
    monkeypatch.setattr(host_spans, "program_trace", lambda: None)
    assert _reader(name).read({}) is None


def test_manifest_ends_with_the_three_metrics_in_every_cell():
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    last = manifest["per_layer"][-3:]
    assert [m["name"] for m in last] == list(READERS)
    for entry in last:
        reader = _reader(entry["name"])
        assert (reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES) == (
            entry["layer"], entry["unit"], entry["source"], entry["moves"]
        )
        assert entry["better"] == "lower" and "workloads" not in entry


def test_traced_rehearsal_cell_reports_the_three(tmp_path):
    manifest = bench.load_json(os.path.join(bench.BENCH, "rehearsal.json"))
    real = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    wanted = READERS + ("step_lower_s", "step_traces")
    manifest["per_layer"] += [
        m for m in real["per_layer"] if m["name"] in wanted
    ]
    path = tmp_path / "rehearsal.json"
    path.write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    t0 = time.time()
    done = subprocess.run(
        [sys.executable, os.path.join(bench.BENCH, "run.py"), "--workload",
         "rehearsal_bert_s128_1chip", "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--manifest", str(path)],
        env=env, capture_output=True, text=True, timeout=600, cwd=bench.ROOT,
    )
    wall = time.time() - t0
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], result["checks"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(wanted) <= set(got)
    assert got["step_traces"] == 1
    # the step's own is under the process's, the nodes' under the step's
    assert 0 < got["node_trace_s"] < got["step_lower_own_s"] < got["step_lower_s"]
    assert 0 < got["pre_program_s"] < wall
    # the whole table goes to standard error beside it
    for part in ("set-up by owner", "before the program", "* _step",
                 "backward+glue", "double count:"):
        assert part in done.stderr, part
