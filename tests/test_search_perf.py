"""Search-time performance overhaul tests.

Pins the three layers of the overhaul: (1) the native machine-mapping DP
agrees with the Python DP on a real budgeted search, (2) the shared
MachineMappingCache is actually shared (hit counter regression), and (3)
search telemetry / FFModel.search_provenance carry the mm_cache counters
and per-phase milliseconds.
"""

import pytest

from flexflow_tpu.compiler import (
    AnalyticTPUCostEstimator,
    MachineMappingContext,
    OptimizerConfig,
    graph_optimize,
    make_default_allowed_machine_views,
)
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.machine_view import MachineSpecification
from flexflow_tpu.pcg.parallel_computation_graph import pcg_from_computation_graph
from flexflow_tpu.substitutions import generate_parallelization_rules


SPEC = MachineSpecification(1, 1, 4, 25.0, 400.0)


def mlp_pcg(batch=64, hidden=1024):
    b = ComputationGraphBuilder()
    x = b.create_input([batch, hidden], name="x")
    h = b.dense(x, hidden, use_bias=False, name="fc1")
    h = b.relu(h)
    b.dense(h, hidden, use_bias=False, name="fc2")
    return pcg_from_computation_graph(b.graph)


def make_context():
    return MachineMappingContext(
        AnalyticTPUCostEstimator(SPEC), make_default_allowed_machine_views()
    )


class TestNativeSearchSmoke:
    def test_budget4_search_native_python_cost_parity(self, monkeypatch):
        """Tier-1 smoke: the same budget-4 search priced by the native DP
        and by the pure-Python fallback (FF_TPU_NO_NATIVE=1) returns the
        identical winning-plan cost."""
        rules = generate_parallelization_rules([4])
        cfg = OptimizerConfig(alpha=1.2, budget=4)

        native = graph_optimize(mlp_pcg(), make_context(), SPEC, rules, cfg)
        assert native.telemetry["native_dp"] is True, (
            "native DP unavailable — the smoke test must exercise it"
        )
        monkeypatch.setenv("FF_TPU_NO_NATIVE", "1")
        python = graph_optimize(mlp_pcg(), make_context(), SPEC, rules, cfg)
        assert python.telemetry["native_dp"] is False
        assert native.runtime == python.runtime
        assert native.serial_runtime == python.serial_runtime
        assert native.seed_runtimes == python.seed_runtimes


class TestSharedCacheRegression:
    def test_search_cache_hits_across_candidates(self):
        """The search threads ONE MachineMappingCache through every
        candidate; with hash-consed subtrees that shared cache must
        actually hit across candidates (this was silently a no-op when
        evaluate_pcg defaulted to a throwaway cache)."""
        rules = generate_parallelization_rules([4])
        result = graph_optimize(
            mlp_pcg(), make_context(), SPEC, rules,
            OptimizerConfig(alpha=1.2, budget=4),
        )
        t = result.telemetry
        assert t["mm_cache_hits"] > 0, t
        assert t["mm_cache_misses"] > 0, t

    def test_evaluate_pcg_requires_cache(self):
        from flexflow_tpu.compiler import evaluate_pcg

        with pytest.raises((TypeError, AssertionError)):
            evaluate_pcg(mlp_pcg(), make_context(), SPEC)  # no cache


class TestSearchPhaseTelemetry:
    REQUIRED_PHASES = ("tree_build", "dp", "leaf_cost", "match")

    def test_graph_optimize_phase_ms(self):
        rules = generate_parallelization_rules([4])
        result = graph_optimize(
            mlp_pcg(), make_context(), SPEC, rules,
            OptimizerConfig(alpha=1.2, budget=4),
        )
        phase_ms = result.telemetry["phase_ms"]
        for phase in self.REQUIRED_PHASES:
            assert phase in phase_ms, (phase, phase_ms)
            assert phase_ms[phase] >= 0.0
        assert "seed_build" in phase_ms

    def test_mcmc_phase_ms_and_cache_counters(self):
        from flexflow_tpu.compiler import MCMCConfig, mcmc_optimize

        result = mcmc_optimize(
            mlp_pcg(), make_context(), SPEC,
            generate_parallelization_rules([4]),
            MCMCConfig(budget=10, rng_seed=0),
        )
        t = result.telemetry
        assert t["mm_cache_hits"] >= 0 and t["mm_cache_misses"] > 0
        for phase in ("tree_build", "dp"):
            assert phase in t["phase_ms"]

    def test_ffmodel_search_provenance_carries_attribution(self):
        """FFModel.search_provenance (the field A/B artifacts record) must
        carry {mm_cache_hits, mm_cache_misses, phase_ms}."""
        import jax
        import numpy as np

        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")
        cfg = FFConfig(batch_size=8, epochs=1, search_budget=1)
        m = FFModel(cfg)
        x = m.create_tensor([8, 16])
        m.dense(x, 8, use_bias=False)
        m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
        prov = m.search_provenance
        assert prov is not None
        assert isinstance(prov["mm_cache_hits"], int)
        assert isinstance(prov["mm_cache_misses"], int)
        assert prov["mm_cache_hits"] + prov["mm_cache_misses"] > 0
        assert isinstance(prov["phase_ms"], dict)
        assert "dp" in prov["phase_ms"] and "tree_build" in prov["phase_ms"]
