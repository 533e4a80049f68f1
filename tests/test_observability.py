"""Observability subsystem tests: structured span tracing and
search-provenance telemetry.

These pin trace-span nesting and the {evaluations, infeasible, dedup_hits,
symmetry_dedup, cost_model} record in a dry-run search provenance.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.observability import (
    TraceRecorder,
    active_recorder,
    record_span,
    set_recorder,
)
from flexflow_tpu.pcg import ComputationGraphBuilder


def small_mlp(batch=8, hidden=16, classes=4):
    b = ComputationGraphBuilder()
    x = b.create_input([batch, hidden], name="x")
    h = b.dense(x, hidden, use_bias=False, name="fc1")
    h = b.relu(h)
    logits = b.dense(h, classes, use_bias=False, name="head")
    return b.graph, logits


def training_instance(batch=8, hidden=16, classes=4):
    from flexflow_tpu.local_execution import ModelTrainingInstance
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs

    cg, logits = small_mlp(batch, hidden, classes)
    inst = ModelTrainingInstance(
        cg,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        SGDOptimizerAttrs(lr=0.01),
    )
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(batch, hidden), jnp.float32)
    yv = jnp.asarray(rs.randint(0, classes, (batch,)), jnp.int32)
    return cg, logits, inst, xv, yv


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------


class TestTraceRecorder:
    def test_span_nesting(self):
        rec = TraceRecorder()
        with rec.span("step"):
            with rec.span("dispatch"):
                pass
            with rec.span("after"):
                pass
        (step,) = rec.spans_named("step")
        assert step.depth == 0 and step.parent is None
        kids = rec.children_of(step)
        assert [s.name for s in kids] == ["dispatch", "after"]
        assert all(k.depth == 1 for k in kids)
        # children are contained in the parent's interval
        for k in kids:
            assert k.start_ms >= step.start_ms
            assert k.start_ms + k.dur_ms <= step.start_ms + step.dur_ms + 1e-6

    def test_sibling_spans_do_not_nest(self):
        rec = TraceRecorder()
        with rec.span("a"):
            pass
        with rec.span("b"):
            pass
        (b,) = rec.spans_named("b")
        assert b.depth == 0 and b.parent is None

    def test_no_span_waits_for_the_device(self):
        # a span times the host; nothing in the recorder takes a pytree to
        # wait on (the device's side of a step is read off the trace)
        import inspect

        assert "sync" not in inspect.signature(TraceRecorder.span).parameters
        assert "sync" not in inspect.signature(record_span).parameters

    def test_record_span_is_noop_without_recorder(self):
        assert active_recorder() is None
        with record_span("anything") as r:
            assert r is None

    def test_record_span_targets_active_recorder(self):
        rec = TraceRecorder()
        prev = set_recorder(rec)
        try:
            with record_span("x", tag=1):
                pass
        finally:
            set_recorder(prev)
        (x,) = rec.spans_named("x")
        assert x.args == {"tag": 1}


class TestStepInstrumentation:
    def test_train_step_emits_phase_spans(self):
        _, _, inst, xv, yv = training_instance()
        params, opt_state = inst.initialize(seed=0)
        rec = TraceRecorder()
        prev = set_recorder(rec)
        try:
            params, opt_state, loss, _ = inst.train_step(
                params, opt_state, {"x": xv}, yv
            )
        finally:
            set_recorder(prev)
        (step,) = rec.spans_named("step")
        assert [s.name for s in rec.children_of(step)] == ["dispatch"]
        assert step.args == {"backend": "ModelTrainingInstance"}
        assert np.isfinite(float(loss))

    def test_train_step_unchanged_without_recorder(self):
        _, _, inst, xv, yv = training_instance()
        params, opt_state = inst.initialize(seed=0)
        out = inst.train_step(params, opt_state, {"x": xv}, yv)
        assert len(out) == 4


# ---------------------------------------------------------------------------
# search telemetry / provenance
# ---------------------------------------------------------------------------

from flexflow_tpu.compiler import (  # noqa: E402
    AnalyticTPUCostEstimator,
    MachineMappingContext,
    OptimizerConfig,
    MachineMappingCache,
    evaluate_pcg,
    graph_optimize,
    make_default_allowed_machine_views,
)
from flexflow_tpu.pcg.machine_view import MachineSpecification  # noqa: E402
from flexflow_tpu.pcg.parallel_computation_graph import (  # noqa: E402
    pcg_from_computation_graph,
)
from flexflow_tpu.substitutions import (  # noqa: E402
    generate_parallelization_rules,
)

SPEC = MachineSpecification(
    num_nodes=1,
    num_cpus_per_node=1,
    num_devices_per_node=4,
    inter_node_bandwidth=25.0,
    intra_node_bandwidth=400.0,
)


def make_context():
    return MachineMappingContext(
        AnalyticTPUCostEstimator(SPEC), make_default_allowed_machine_views()
    )


def mlp_pcg(batch=64, hidden=1024):
    b = ComputationGraphBuilder()
    x = b.create_input([batch, hidden], name="x")
    h = b.dense(x, hidden, use_bias=False, name="fc1")
    h = b.relu(h)
    h = b.dense(h, hidden, use_bias=False, name="fc2")
    return pcg_from_computation_graph(b.graph)


class TestSearchTelemetry:
    def test_graph_optimize_records_telemetry(self):
        rules = generate_parallelization_rules([4])
        result = graph_optimize(
            mlp_pcg(), make_context(), SPEC, rules,
            OptimizerConfig(alpha=1.3, budget=4),
        )
        t = result.telemetry
        assert t["algorithm"] == "unity"
        assert t["evaluations"] >= 1
        assert t["infeasible"] >= 0
        assert t["evaluations"] > t["infeasible"]
        assert (
            t["dedup_hits"]
            == t["dedup_key_hits"]
            + t["dedup_signature_hits"]
            + t["dedup_site_hits"]
        )
        assert isinstance(t["symmetry_dedup"], bool)
        if t["symmetry_dedup"]:
            from flexflow_tpu.compiler.unity_algorithm import (
                COST_SIGNATURE_VERSION,
            )

            assert t["signature_version"] == COST_SIGNATURE_VERSION
        else:
            assert t["signature_version"] is None

    def test_mcmc_records_telemetry(self):
        from flexflow_tpu.compiler import MCMCConfig, mcmc_optimize

        rules = generate_parallelization_rules([4])
        result = mcmc_optimize(
            mlp_pcg(), make_context(), SPEC, rules,
            MCMCConfig(budget=10, rng_seed=0),
        )
        t = result.telemetry
        assert t["algorithm"] == "mcmc"
        # evaluations counts every fresh evaluate_pcg call (+ the start)
        assert t["evaluations"] == result.explored + t["infeasible"] + 1
        assert t["dedup_hits"] >= 0 and t["iterations"] >= 1
        assert t["symmetry_dedup"] is False

    def test_ffmodel_dry_run_provenance(self):
        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

        batch = 32
        m = FFModel(FFConfig(batch_size=batch, seed=0, search_budget=4))
        x = m.create_tensor([batch, 64], name="x")
        h = m.dense(x, 64, name="fc1")
        h = m.relu(h)
        logits = m.dense(h, 10, name="head")
        m.compile(
            SGDOptimizer(lr=0.01),
            "sparse_categorical_crossentropy",
            logit_tensor=logits,
        )
        prov = m.search_provenance or {}
        # the ISSUE acceptance record: how the plan was found
        assert prov["evaluations"] >= 1
        assert prov["infeasible"] >= 0
        assert prov["dedup_hits"] >= 0
        assert isinstance(prov["symmetry_dedup"], bool)
        assert prov["cost_model"]
        assert prov["search_algorithm"] in ("unity", "mcmc", "forced_seed")
        assert prov["telemetry"]["algorithm"] in ("unity", "mcmc")
        # and the whole block is artifact-serializable
        json.dumps(
            {k: v for k, v in prov.items() if k != "calibration"},
            default=str,
        )

    # The provenance key set downstream consumers (benchmark/layer_metrics,
    # chip_smoke.py, the *_audit tools) may rely on.
    # FFModel.search_provenance is Dict[str, object]: several values are
    # NESTED dicts / strings / bools, not floats (ISSUE 3 satellite — the
    # old Dict[str, float] annotation lied).
    PROVENANCE_KEYS = frozenset({
        "explored", "estimated_ms", "serial_ms", "search_seconds",
        "seed_runtimes", "parallel_degrees", "cost_model",
        "search_algorithm", "evaluations", "infeasible", "dedup_hits",
        "symmetry_dedup", "signature_version", "mm_cache_hits",
        "mm_cache_misses", "native_dp", "phase_ms", "telemetry",
        "calibration",
    })

    def test_provenance_schema_stability(self):
        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
        import flexflow_tpu.core.ffmodel as ffmodel_mod

        batch = 32
        m = FFModel(FFConfig(batch_size=batch, seed=0, search_budget=2))
        x = m.create_tensor([batch, 32], name="x")
        h = m.dense(x, 32, name="fc1")
        logits = m.dense(h, 8, name="head")
        m.compile(
            SGDOptimizer(lr=0.01),
            "sparse_categorical_crossentropy",
            logit_tensor=logits,
        )
        prov = m.search_provenance
        # every pinned key is present (plan_audit joins only when
        # config.plan_audit is set, so it is not in the required set)
        assert self.PROVENANCE_KEYS <= set(prov), (
            self.PROVENANCE_KEYS - set(prov)
        )
        # nested/non-float values really occur — the reason the annotation
        # is Dict[str, object]
        assert isinstance(prov["seed_runtimes"], dict)
        assert isinstance(prov["parallel_degrees"], dict)
        assert isinstance(prov["cost_model"], str)
        assert isinstance(prov["symmetry_dedup"], bool)
        # and the annotation itself says object, not float (scoped to the
        # search_provenance line so unrelated future attributes may still
        # legitimately use Dict[str, float])
        src = open(ffmodel_mod.__file__).read()
        assert (
            "self.search_provenance: Optional[Dict[str, object]]" in src
        )


class TestCostSignatureWiring:
    """ADVICE round 5, item 1: the edge multiset separates differently-
    wired graphs whose per-node local records coincide."""

    @staticmethod
    def _pcg(chain1, chain2, hidden=16):
        b = ComputationGraphBuilder()
        for i, chain in enumerate((chain1, chain2)):
            t = b.create_input([8, hidden], name=f"x{i}")
            for j, op in enumerate(chain):
                t = getattr(b, op)(t, name=f"c{i}_{j}")
        return pcg_from_computation_graph(b.graph)

    def test_edge_multiset_separates_wiring(self):
        from flexflow_tpu.compiler.unity_algorithm import _cost_signature

        # A = {relu->tanh, tanh->relu}; B = {relu->relu, tanh->tanh}.
        # Node records (attrs, in shapes, out shape + fan-out) coincide:
        # both have one relu/tanh at fan-out 1 and one at fan-out 0 on
        # identical shapes — only the WIRING differs (non-isomorphic).
        a = _cost_signature(self._pcg(["relu", "tanh"], ["tanh", "relu"]))
        b = _cost_signature(self._pcg(["relu", "relu"], ["tanh", "tanh"]))
        nodes_a, edges_a = a
        nodes_b, edges_b = b
        assert nodes_a == nodes_b  # the v1 signature was blind to this
        assert edges_a != edges_b  # v2's edge multiset separates them
        assert a != b

    def test_isomorphic_graphs_share_signature(self):
        from flexflow_tpu.compiler.unity_algorithm import _cost_signature

        a = _cost_signature(self._pcg(["relu", "tanh"], ["tanh", "relu"]))
        b = _cost_signature(self._pcg(["tanh", "relu"], ["relu", "tanh"]))
        assert a == b


class TestMCMCInfeasibleRegression:
    """ADVICE round 5, item 2 + ISSUE 12 satellite: infeasible
    evaluations must not drain the budget, must not reset the stale
    counter — and a stream of FRESH-but-infeasible candidates must still
    trigger the stale early-exit instead of spinning to the 20x-budget
    iteration cap."""

    def test_always_infeasible_neighborhood(self, monkeypatch):
        from flexflow_tpu.compiler import MCMCConfig, mcmc_optimize
        from flexflow_tpu.compiler import mcmc_search as mcmc_mod

        pcg = mlp_pcg(batch=16, hidden=32)
        ctx = make_context()
        baseline = evaluate_pcg(pcg, ctx, SPEC, MachineMappingCache())
        rules = generate_parallelization_rules([4])

        calls = {"n": 0}
        real = mcmc_mod.evaluate_pcg

        def first_real_then_infeasible(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 1:
                return real(*args, **kwargs)  # the start state
            return None

        monkeypatch.setattr(
            mcmc_mod, "evaluate_pcg", first_real_then_infeasible
        )
        budget = 30
        result = mcmc_optimize(
            pcg, ctx, SPEC, rules, MCMCConfig(budget=budget, rng_seed=0)
        )
        t = result.telemetry
        # budget buys FEASIBLE evaluations only: none happened, so none
        # was spent (the pre-fix code charged each infeasible candidate
        # and exited with explored == budget)
        assert result.explored == 0
        assert t["infeasible"] >= 1
        # the STALE early exit terminated the walk: every proposal was a
        # fresh-but-infeasible candidate or a cache hit, each advancing
        # the stale counter, so the walk stops within the 64-stale window
        # — far below the 20x-budget iteration cap it used to spin to
        assert t["iterations"] <= 64 + 1
        assert t["iterations"] < 20 * budget + 100
        # the infeasible neighborhood never displaced the start state
        assert result.runtime == baseline.runtime


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
