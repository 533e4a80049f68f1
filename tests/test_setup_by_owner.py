"""Set-up by owner (observability/trace.py `lowering_by_function`,
`node_trace_seconds`, `pre_program_s`, `setup_report`): JAX's trace, lowering
and compile seconds are filed under the function that paid them and counted
once, the step's are told from everybody else's on every backend, the step's
trace is split by node kind, and the seconds before the program are read.

All on the virtual CPU mesh. The seconds asserted on are the test's own
`time.sleep`s inside traced bodies: a host clock, which is what these
counters keep."""

import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu
from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.core import op_type_of
from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = trace.LOWERING_EVENTS[0]


def _stage(table, stage):
    """`{fun_name: row}` of one stage."""
    return {name: rows[stage] for name, rows in table.items() if stage in rows}


def _probe_jitted_inner():
    @jax.jit
    def probe_inner(x):
        time.sleep(0.1)
        return x

    def probe_outer(x):
        time.sleep(0.1)
        return probe_inner(x), probe_inner(x)

    return probe_outer, {"probe_inner"}


def _probe_jnp_calls():
    def probe_outer(x):
        time.sleep(0.2)
        return jnp.sin(x) * 2 + jnp.cos(x)

    return probe_outer, {"sin", "cos", "multiply", "add"}


@pytest.mark.parametrize(
    "probe", [_probe_jitted_inner, _probe_jnp_calls],
    ids=["jitted_inner_twice", "jnp_calls"],
)
def test_nested_traces_fold_into_their_caller(probe):
    outer, children = probe()
    trace.reset_span_totals()
    t0 = time.perf_counter()
    jax.jit(outer).trace(np.float32(1.0))
    wall = time.perf_counter() - t0
    rows = _stage(trace.lowering_by_function(), "trace")
    assert set(rows) == {"probe_outer"} | children
    top = rows["probe_outer"]
    nested = sum(rows[c]["inclusive_s"] for c in children)
    # counted once: the caller alone is top-level, and it is the wall time
    assert all(rows[c]["top_level_s"] == 0 for c in children)
    assert top["top_level_s"] == top["inclusive_s"]
    assert top["top_level_s"] <= wall
    assert top["top_level_s"] == pytest.approx(wall, rel=0.1)
    assert top["exclusive_s"] + nested == pytest.approx(top["inclusive_s"])
    # the plain row still holds the naive sum, every nested event again
    naive = trace.span_totals()[TRACE]
    assert naive["count"] == sum(r["count"] for r in rows.values())
    assert naive["total_s"] == pytest.approx(top["inclusive_s"] + nested)


def test_threads_tracing_at_once_do_not_nest():
    trace.reset_span_totals()
    barrier = threading.Barrier(2)

    def work(name):
        def body(x):
            barrier.wait(5.0)
            time.sleep(0.2)
            return x

        body.__name__ = name
        jax.jit(body).trace(np.float32(1.0))

    threads = [
        threading.Thread(target=work, args=(f"probe_thread_{i}",))
        for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert not any(t.is_alive() for t in threads)
    rows = _stage(trace.lowering_by_function(), "trace")
    assert set(rows) == {"probe_thread_0", "probe_thread_1"}
    for row in rows.values():
        # the two overlap in time and neither lies inside the other
        assert row["top_level_s"] == row["inclusive_s"] == row["exclusive_s"]
        assert row["inclusive_s"] >= 0.2


def test_one_row_a_function_name_however_often_it_is_traced():
    def probe_repeated(x):
        return x

    trace.reset_span_totals()
    sizes = []
    for n in range(1, 6):
        jax.jit(probe_repeated).lower(np.zeros(n, np.float32))
        sizes.append(len(trace.lowering_by_function()))
    table = trace.lowering_by_function()
    assert sizes == [2] * 5 and set(table) == {
        "probe_repeated", "jit(probe_repeated)",
    }
    assert table["probe_repeated"]["trace"]["count"] == 5
    assert table["jit(probe_repeated)"]["to_mlir"]["count"] == 5
    # nothing is left open, and nothing is kept an event
    assert trace._thread.stages == []


# the four backends `FFModel.compile` builds, as `tests/test_ffmodel_api.py`
# asks for them, and the name each gives its step under `jax.jit`
BACKENDS = {
    "ModelTrainingInstance": (dict(max_devices=1), "_step"),
    "DataParallelTrainingInstance": (dict(), "step_with_mesh_ctx"),
    "DistributedTrainingInstance": (dict(search_budget=2), "_step"),
    "PipelinedTrainingInstance": (
        dict(search_budget=1, pipeline=True, force_strategy_seed="pp2m4xdp4"),
        "_step",
    ),
}


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_the_step_and_only_the_step_is_marked(backend):
    config, step_name = BACKENDS[backend]
    trace.reset_span_totals()
    m = FFModel(FFConfig(batch_size=16, seed=0, print_freq=0, **config))
    h = m.create_tensor([16, 16], name="x")
    for i in range(4):
        h = m.relu(m.dense(h, 16, name=f"fc{i}"))
    m.compile(
        AdamOptimizer(alpha=1e-2), "sparse_categorical_crossentropy",
        logit_tensor=h,
    )
    assert type(m.instance).__name__ == backend
    rs = np.random.RandomState(0)
    m.fit(rs.randn(32, 16).astype(np.float32), rs.randint(0, 16, 32),
          epochs=1, verbose=False)
    # somebody else's, traced on the same thread after the step
    jax.jit(lambda x: x + 1, inline=False).lower(np.float32(1.0))

    table = trace.lowering_by_function()
    marked = {
        (name, stage): row["step_count"]
        for name, rows in table.items() for stage, row in rows.items()
        if row["step_count"]
    }
    assert set(marked) <= {
        (step_name, "trace"), (f"jit({step_name})", "to_mlir"),
        (f"jit({step_name})", "compile"),
    }
    step_traces = trace.span_totals()[trace.STEP_TRACE]["count"]
    assert marked[(step_name, "trace")] == step_traces >= 1
    assert marked[(f"jit({step_name})", "to_mlir")] >= 1
    row = table[step_name]["trace"]
    assert 0 < row["step_s"] <= row["top_level_s"]
    # the rest of the process's top-level events are under other names
    others = [
        name for name, row in _stage(table, "trace").items()
        if row["top_level_s"] and not row["step_count"]
    ]
    assert "<lambda>" in others and step_name not in others


def _two_block_model():
    m = FFModel(FFConfig(batch_size=8, seed=0, print_freq=0, max_devices=1))
    h = m.create_tensor([8, 32], name="x")
    for i in range(2):
        r = m.rms_norm(h, name=f"norm{i}")
        r = m.dense(m.gelu(m.dense(r, 64, name=f"up{i}")), 32, name=f"down{i}")
        h = m.add(h, r)
    m.dense(h, 8, name="head")
    m.compile(AdamOptimizer(alpha=1e-3), "sparse_categorical_crossentropy")
    return m


def test_the_steps_trace_by_node_kind():
    trace.reset_span_totals()
    m = _two_block_model()
    rs = np.random.RandomState(0)
    m.fit(rs.randn(16, 32).astype(np.float32), rs.randint(0, 8, 16),
          epochs=1, verbose=False)
    # the same nodes outside a trace of the step are nobody's row
    m.eval(x=rs.randn(8, 32).astype(np.float32), y=rs.randint(0, 8, 8))

    cg = m.instance.cg
    nodes = {}
    for n in cg.topological_ordering():
        attrs = cg.op_attrs(n)
        if not isinstance(attrs, (InputAttrs, WeightAttrs)):
            kind = trace.scope_kind(op_type_of(attrs))
            nodes[kind] = nodes.get(kind, 0) + 1
    assert {"dense", "rms_norm"} <= set(nodes) and nodes["dense"] == 5, nodes
    assert len(nodes) >= 4
    traces = trace.span_totals()[trace.STEP_TRACE]["count"]
    table = trace.node_trace_seconds()
    by_kind = {k: v for k, v in table.items() if k not in trace.STEP_SCOPES}
    assert {k: v["calls"] for k, v in by_kind.items()} == {
        k: n * traces for k, n in nodes.items()
    }
    # the parts of the step that are no node, once a trace each
    parts = {k: v["calls"] for k, v in table.items() if k in trace.STEP_SCOPES}
    assert parts["loss"] == parts["optimizer"] == traces
    step_trace_s = sum(
        row["step_s"]
        for row in _stage(trace.lowering_by_function(), "trace").values()
    )
    scoped = sum(v["seconds"] for v in table.values())
    assert 0 < sum(v["seconds"] for v in by_kind.values()) <= scoped
    assert scoped < step_trace_s  # what is left: the backward and the glue


def test_pre_program_seconds_against_two_other_readings():
    # the child sleeps before the import: its own clock from its first line
    # to the import is under the process's age, the parent's clock from
    # before the spawn to the child's reading is over it
    code = (
        "import time; t0 = time.time(); time.sleep(0.3)\n"
        "import flexflow_tpu; t1 = time.time()\n"
        "print(flexflow_tpu.PROCESS_AGE_AT_IMPORT_S, t1 - t0, t1)"
    )
    spawned = time.time()
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        timeout=120, check=True,
    ).stdout.split()
    age, in_child, at_import = (float(v) for v in out)
    resolution = 0.02  # the start time is in ticks of 10 ms
    assert in_child - resolution <= age <= at_import - spawned + resolution
    assert age >= 0.3
    # in this process the value is the one noted at the import
    assert trace.pre_program_s() == flexflow_tpu.PROCESS_AGE_AT_IMPORT_S
    assert 0 < trace.pre_program_s() <= flexflow_tpu._process_age_s()


def test_pre_program_seconds_are_none_without_proc(tmp_path, monkeypatch):
    assert flexflow_tpu._process_age_s(proc=str(tmp_path)) is None
    (tmp_path / "self").mkdir()
    (tmp_path / "self" / "stat").write_text("1 (python) R 1")
    assert flexflow_tpu._process_age_s(proc=str(tmp_path)) is None
    monkeypatch.setattr(flexflow_tpu, "PROCESS_AGE_AT_IMPORT_S", None)
    assert trace.pre_program_s() is None
    assert "no /proc" in trace.setup_report()


def test_setup_report_names_its_five_parts():
    trace.reset_span_totals()
    m = _two_block_model()
    rs = np.random.RandomState(0)
    m.fit(rs.randn(8, 32).astype(np.float32), rs.randint(0, 8, 8),
          epochs=1, verbose=False)
    text = trace.setup_report(top=4)
    for part in (
        "before the program", "compile spans:", "compile/init_state",
        "lowering by function", "* _step", "* jit(_step)", "every function",
        "the step's trace by node kind", "dense", "backward+glue",
        "double count:",
    ):
        assert part in text, (part, text)
    # the rows printed are the largest, and the totals are of all rows
    table = text.split("lowering by function")[1].split("the step's trace")[0]
    assert len(table.splitlines()) == 1 + 1 + 4 + 3 + 3


def test_a_span_left_by_an_exception_leaves_no_open_name():
    tid = threading.get_ident()
    with pytest.raises(RuntimeError, match="boom"):
        with trace.record_span("fit"), trace.record_span("step"):
            assert trace.open_span_names(tid) == ["fit", "step"]
            raise RuntimeError("boom")
    assert trace.open_span_names(tid) == []
