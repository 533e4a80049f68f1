"""Host spans (observability/trace.py `record_span` / `count` /
`span_totals`): every name in `HOST_SPANS` is emitted by `compile` + `fit`
where the work happens and nests as the module says, the table counts what
the loop does, the spans are events of the profiler's host plane, and none
of it touches the step program or waits for the device.

All on the virtual CPU mesh: names, counts and nesting, never a time."""

import glob
import threading

import jax
import numpy as np
import pytest

from flexflow_tpu.analysis.lowering import lower_step_trace
from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.kernels import context
from flexflow_tpu.observability import trace

STEPS, BATCH = 4, 16


def _data(n=STEPS * BATCH):
    rs = np.random.RandomState(0)
    return (
        rs.randn(n, 32).astype(np.float32),
        rs.randint(0, 32, (n,)).astype(np.int32),
    )


def _model(**config):
    m = FFModel(FFConfig(batch_size=BATCH, print_freq=0, seed=3, **config))
    x = m.create_tensor([BATCH, 32], name="x")
    h = m.relu(m.dense(x, 64, name="fc1"))
    m.dense(h, 32, name="fc2")
    m.compile(AdamOptimizer(alpha=1e-3), "sparse_categorical_crossentropy")
    return m


@pytest.fixture
def recorder():
    rec = trace.TraceRecorder()
    prev = trace.set_recorder(rec)
    try:
        yield rec
    finally:
        trace.set_recorder(prev)


def _compile_and_fit(**config):
    """(backend name, {span name: set of parent names}) of one toy
    `compile` + `fit` under a fresh recorder."""
    rec = trace.TraceRecorder()
    prev = trace.set_recorder(rec)
    try:
        m = _model(**config)
        m.fit(*_data(), epochs=1, shuffle=True, verbose=False)
        # the one span neither emits: the account of the step, on demand
        m.step_account()
    finally:
        trace.set_recorder(prev)
    parents = {}
    for s in rec.spans:
        parent = None if s.parent is None else rec.spans[s.parent].name
        parents.setdefault(s.name, set()).add(parent)
    return type(m.instance).__name__, parents


@pytest.fixture(scope="module")
def emitted():
    """The three toy jobs whose spans together are all of `HOST_SPANS`."""
    return {
        "single": _compile_and_fit(max_devices=1),
        "searched": _compile_and_fit(max_devices=4, search_budget=2),
        "dp": _compile_and_fit(max_devices=4),
    }


# where each span has to sit, and which of the toy jobs has to emit it
PARENT = {
    "compile": None,
    "compile/search": "compile",
    "compile/verify": "compile",
    "compile/lower_step": "compile",
    "compile/build_instance": "compile",
    "compile/init_state": "compile",
    "fit": None,
    "fit/begin": "fit",
    "fit/next_batch": "fit",
    "step": "fit",
    "dispatch": "step",
    "fit/end": "fit",
    "step_account": None,
}
SEARCH_ONLY = {"compile/search", "compile/verify", "compile/lower_step"}


def test_parent_table_covers_the_exported_names():
    assert set(PARENT) == set(trace.HOST_SPANS)


@pytest.mark.parametrize("name", trace.HOST_SPANS)
def test_every_host_span_is_emitted_where_the_module_says(emitted, name):
    backends = {job: backend for job, (backend, _) in emitted.items()}
    assert backends == {
        "single": "ModelTrainingInstance",
        "searched": "DistributedTrainingInstance",
        "dp": "DataParallelTrainingInstance",
    }
    if name in SEARCH_ONLY:
        jobs = ["searched"]
    else:
        jobs = ["single", "searched", "dp"]
    for job in jobs:
        parents = emitted[job][1]
        assert parents.get(name) == {PARENT[name]}, (job, parents.get(name))


def test_search_phases_nest_under_the_search_span(emitted):
    parents = emitted["searched"][1]
    phases = {n for n in parents if n.startswith("search/")}
    assert {"search/dp", "search/match", "search/tree_build"} <= phases
    for name in phases - {"search/leaf_cost"}:
        assert parents[name] == {"compile/search"}, name
    assert "compile/search" not in emitted["single"][1]


class TestSpanTotals:
    def test_counts_one_dispatch_a_step_and_one_pull_more_than_steps(self):
        m = _model(max_devices=1)
        trace.reset_span_totals()
        m.fit(*_data(), epochs=2, shuffle=False, verbose=False)
        totals = trace.span_totals()
        counts = {k: v["count"] for k, v in totals.items()}
        assert counts["fit"] == counts["fit/begin"] == counts["fit/end"] == 1
        assert counts["step"] == counts["dispatch"] == 2 * STEPS
        # one pull a step, and one an epoch that finds it over
        assert counts["fit/next_batch"] == 2 * STEPS + 2
        fit, step = totals["fit"], totals["step"]
        assert fit["total_s"] == fit["longest_s"] >= step["total_s"] > 0
        assert step["longest_s"] <= step["total_s"]

    @pytest.mark.parametrize(
        "backend,config",
        [
            ("ModelTrainingInstance", dict(max_devices=1)),
            ("DataParallelTrainingInstance", dict(max_devices=4)),
            ("DistributedTrainingInstance",
             dict(max_devices=4, search_budget=2)),
        ],
        ids=["single", "dp", "searched"],
    )
    def test_a_step_span_is_one_step_and_holds_its_dispatch(
        self, recorder, backend, config
    ):
        """The one loop: a `step` span a step, nothing under it but the
        enqueue, and the batch's transfer inside `fit/next_batch` (no span,
        and no thread, of its own)."""
        m = _model(**config)
        recorder.spans.clear()  # `compile`'s spans are another test's
        m.fit(*_data(), epochs=1, shuffle=False, verbose=False)
        steps = recorder.spans_named("step")
        assert len(steps) == STEPS
        assert all("fused_steps" not in s.args for s in steps)
        assert all(s.args["backend"] == backend for s in steps)
        assert [
            [c.name for c in recorder.children_of(s)] for s in steps
        ] == [["dispatch"]] * STEPS
        assert len(recorder.spans_named("fit/next_batch")) == STEPS + 1
        assert recorder.spans_named("host_to_device") == []
        assert "host_to_device" not in trace.HOST_SPANS
        assert {s.tid for s in recorder.spans} == {threading.get_ident()}

    def test_span_and_counter_need_no_recorder(self):
        assert trace.active_recorder() is None
        trace.reset_span_totals()
        with trace.record_span("anything", tag=1) as rec:
            assert rec is None
        trace.count("an_event")
        trace.count("an_event")
        totals = trace.span_totals()
        assert totals["anything"]["count"] == 1
        assert totals["an_event"] == {
            "count": 2, "total_s": 0.0, "longest_s": 0.0,
        }

    def test_spans_of_other_threads_land_beside(self, recorder):
        def writer():
            with trace.record_span("checkpoint", step=2):
                pass

        with trace.record_span("fit"):
            t = threading.Thread(target=writer)
            t.start()
            t.join()
        (ckpt,) = recorder.spans_named("checkpoint")
        assert ckpt.parent is None and ckpt.tid != threading.get_ident()

    def test_jax_reports_its_tracing_and_lowering_to_the_table(self):
        trace.reset_span_totals()
        jax.jit(lambda x: x * 3 + 1).lower(np.float32(2.0))
        totals = trace.span_totals()
        for event in trace.LOWERING_EVENTS:
            assert totals[event]["count"] >= 1
            assert totals[event]["total_s"] > 0

    def test_begin_span_closes_when_set_up_raises(self, recorder, monkeypatch):
        m = _model(max_devices=1)
        monkeypatch.setattr(
            m, "_setup_supervision",
            lambda: (_ for _ in ()).throw(RuntimeError("no supervision")),
        )
        trace.reset_span_totals()
        with pytest.raises(RuntimeError, match="no supervision"):
            m.fit(*_data(), epochs=1, verbose=False)
        assert trace.open_span_names(threading.get_ident()) == []
        counts = {k: v["count"] for k, v in trace.span_totals().items()}
        assert counts["fit"] == counts["fit/begin"] == 1
        assert "fit/end" not in counts


class TestStepTraceCounter:
    @pytest.mark.parametrize(
        "config", [{"max_devices": 1}, {"max_devices": 4, "search_budget": 2}],
        ids=["single", "searched"],
    )
    def test_one_trace_serves_compile_fit_and_like_placed_lowerings(
        self, config
    ):
        trace.reset_span_totals()
        m = _model(**config)
        m.fit(*_data(), epochs=1, verbose=False)
        m.fit(*_data(), epochs=1, verbose=False, epoch_offset=1)

        def traces():
            return trace.span_totals()[trace.STEP_TRACE]["count"]

        # the searched compile lowers the step for its contract pass, fit
        # runs it: JAX keeps the traced jaxpr by function and abstract
        # arguments, so they share one trace
        assert traces() == 1
        # and so does a lowering whose example arguments are placed as the
        # dataloader places batches (analysis/lowering.py)
        lower_step_trace(
            m.instance, m.loss_attrs, params=m.params, opt_state=m.opt_state
        )
        assert traces() == 1

    def test_a_lowering_with_other_arguments_costs_one_more(self):
        from flexflow_tpu.analysis.lowering import step_example_args_cg

        trace.reset_span_totals()
        m = _model(max_devices=1)
        m.fit(*_data(), epochs=1, verbose=False)
        before = trace.span_totals()[trace.STEP_TRACE]["count"]
        batch, label, rng = step_example_args_cg(m.instance, m.loss_attrs)
        half = {k: v[: BATCH // 2] for k, v in batch.items()}
        m.instance.compiled_step().lower(
            m.params, m.opt_state, half, label[: BATCH // 2], rng
        )
        assert trace.span_totals()[trace.STEP_TRACE]["count"] == before + 1 == 2


def _fresh_step_text():
    m = _model(max_devices=1)
    return lower_step_trace(
        m.instance, m.loss_attrs, params=m.params, opt_state=m.opt_state
    ).as_text()


class TestStepProgramUntouched:
    def test_lowered_text_is_the_same_whoever_watches(self, tmp_path):
        plain = _fresh_step_text()
        rec = trace.TraceRecorder()
        prev = trace.set_recorder(rec)
        try:
            recorded = _fresh_step_text()
        finally:
            trace.set_recorder(prev)
        with jax.profiler.trace(str(tmp_path)):
            profiled = _fresh_step_text()
        assert rec.spans_named("compile/lower_step")
        assert plain == recorded == profiled

    def test_train_step_returns_the_same_values_on_the_one_path(self):
        x, y = _data(BATCH)
        outs = []
        for watched in (False, True):
            m = _model(max_devices=1)
            prev = trace.set_recorder(
                trace.TraceRecorder() if watched else None
            )
            try:
                params, opt_state, loss, mvals = m.instance.train_step(
                    m.params, m.opt_state, {"x": jax.numpy.asarray(x)},
                    jax.numpy.asarray(y), jax.random.PRNGKey(1),
                )
            finally:
                trace.set_recorder(prev)
            outs.append(jax.device_get((params, opt_state, loss)))
        plain, watched = (jax.tree_util.tree_leaves(o) for o in outs)
        assert len(plain) == len(watched)
        for a, b in zip(plain, watched):
            np.testing.assert_array_equal(a, b)


def test_spans_are_events_of_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    m = _model(max_devices=1)
    x, y = _data()
    m.fit(x, y, epochs=1, verbose=False)  # compile outside the session
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        m.fit(x, y, epochs=1, verbose=False, epoch_offset=1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    (host,) = [
        p for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"
    ]
    wanted = {"fit", "fit/begin", "fit/next_batch", "step", "dispatch",
              "fit/end"}
    lines = {}
    for number, line in enumerate(host.lines):
        for e in line.events:
            if e.name in wanted:
                lines.setdefault(number, []).append(e)
    # all on the fit loop's thread, one line of the host plane
    (events,) = lines.values()
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)
    assert set(by_name) == wanted
    assert len(by_name["step"]) == len(by_name["dispatch"]) == STEPS
    assert len(by_name["fit/next_batch"]) == STEPS + 1
    # the step span is the profiler's step annotation, numbered
    numbers = [dict(e.stats)["step_num"] for e in by_name["step"]]
    assert numbers == list(range(numbers[0], numbers[0] + STEPS))
    assert dict(by_name["step"][0].stats)["backend"] == "ModelTrainingInstance"
    # on one clock: every span lies inside `fit`, each dispatch in its step
    (fit,) = by_name["fit"]
    for e in events:
        assert fit.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= fit.start_ns + fit.duration_ns
    for step, dispatch in zip(by_name["step"], by_name["dispatch"]):
        assert step.start_ns <= dispatch.start_ns
        assert (dispatch.start_ns + dispatch.duration_ns
                <= step.start_ns + step.duration_ns)


BETWEEN = {
    # scope: (the node form of `test_mha_between_kernel.FORMS`, more attrs,
    # the backend forced, the counter's word)
    "ff.ring_attention.mellum2_window": (
        "mellum2_default", dict(window=300), True, "pallas"),
    "ff.ring_attention.mellum2_full": ("mellum2_yarn", {}, True, "pallas"),
    "ff.ring_attention.ouro": ("ouro_rotary_alone", {}, True, "pallas"),
    "ff.ring_attention.olmoe": ("olmoe_row_norm", {}, True, "pallas"),
    "ff.ring_attention.lfm2": ("lfm2_heads_of_64", {}, True, "pallas"),
    # Qwen3-Next's: 64 columns of a head turned
    "ff.ring_attention.qwen3next": (
        "heads_of_256_zero_centred", dict(rotary_dim=64), True,
        "xla (rotary_dim)"),
    "ff.ring_attention.gated": (
        "mellum2_default", dict(output_gate=True), True, "xla (output_gate)"),
    # no kernel core on the plain CPU: the dense route
    "ff.ring_attention.on_the_cpu": ("mellum2_default", {}, False, "xla (route)"),
}


@pytest.mark.parametrize("scope", list(BETWEEN))
def test_the_form_of_norm_and_rotary_is_counted_by_node(
    monkeypatch, scope, entered
):
    """`observability/trace.kernel_choices("between_passes")` names the form
    the norm and the rotary took in each plain attention node as it was lowered:
    `pallas` for the Mellum2, Ouro, OLMoE and LFM2 node forms on the
    "fused_row" route, `xla` with the rule's reason elsewhere, nothing for a
    node that has neither, nothing where no node's scope is open; and
    `rotaries()` keeps its words."""
    import functools

    import jax.numpy as jnp
    from test_mha_between_kernel import attrs_of, node_step

    from flexflow_tpu.kernels import flash_attention as flash
    from flexflow_tpu.kernels import ops

    form, more, forced, want = BETWEEN[scope]
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_Q", "512")
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_K", "512")
    if forced:
        entered(context.described_tpu())
        monkeypatch.setattr(
            flash, "flash_attention_bshf",
            functools.partial(flash.flash_attention_bshf, interpret=True),
        )
    monkeypatch.setattr(context, "_CHOICES", {})
    attrs = attrs_of(form, **more)
    step, (x, flat, gains) = node_step(attrs)

    def forward(x, flat, gains):
        return ops._mha_forward(attrs, x, x, x, flat, causal=True, qk_gains=gains)

    entered(context.lowering_node(scope))
    jax.eval_shape(forward, x, flat, gains)
    assert trace.kernel_choices("between_passes") == {scope: want}
    assert trace.rotaries()[scope].startswith(
        "yarn factor=16" if form == "mellum2_yarn" else "default theta="
    )
    # a node with neither norm nor rotary is not counted, nor is a pass
    # called by itself, under no node's scope
    from flexflow_tpu.op_attrs.ops import RingAttentionAttrs

    bare = RingAttentionAttrs(256, 4, 128, 128, causal=True, num_kv_heads=2)
    entered(context.lowering_node("ff.ring_attention.bare"))
    _, (x, flat, _) = node_step(bare)
    jax.eval_shape(
        lambda x, flat: ops._mha_forward(bare, x, x, x, flat, causal=True),
        x, flat,
    )
    entered(context.lowering_node(None))
    jax.eval_shape(forward, x, node_step(attrs)[1][1], gains)
    assert trace.kernel_choices("between_passes") == {scope: want}
    report = trace.setup_report()
    assert f"(between_passes()): 1 {want}" in report
