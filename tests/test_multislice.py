"""Hierarchical multi-slice search tests (ISSUE 17).

Covers the two-level ICI/DCN DP: python/native parity over the seed
templates on the 2-slice topology, the v2->v3 movement-store migration
(foreign link-class entries are never served), and — slow-marked — the
acceptance gate: on the 4+4 topology the hierarchical search beats the
flat search's truthfully-re-priced winner by >= 1.2x when DCN is 10x
slower than ICI.
"""

import json

import pytest

from flexflow_tpu.compiler import (
    AnalyticTPUCostEstimator,
    MachineMappingContext,
    OptimizerConfig,
    graph_optimize,
    make_default_allowed_machine_views,
)
from flexflow_tpu.compiler.movement_store import (
    LEGACY_V2_PREFIX,
    MovementCostStore,
)
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.machine_view import MachineSpecification
from flexflow_tpu.pcg.parallel_computation_graph import (
    pcg_from_computation_graph,
)
from flexflow_tpu.substitutions import generate_parallelization_rules

# the emulated 2-slice 4+4 machine: slices are the node axis, DCN is the
# inter-node link (tools/audit_env.multislice_machine_spec)
SPEC_2x4 = MachineSpecification(2, 1, 4, 0.2, 2.0)


def mlp_pcg(hidden=64, batch=32):
    b = ComputationGraphBuilder()
    x = b.create_input([batch, hidden], name="x")
    h = b.dense(x, hidden, use_bias=False, name="fc1")
    h = b.relu(h)
    b.dense(h, hidden, use_bias=False, name="fc2")
    return pcg_from_computation_graph(b.graph)


def hier_context(spec):
    return MachineMappingContext(
        AnalyticTPUCostEstimator(spec),
        make_default_allowed_machine_views(),
        slice_aware=True,
        slice_hierarchy=True,
    )


class TestTwoLevelDpParity:
    def test_native_python_parity_on_2slice_topology(self, monkeypatch):
        """The two-level DP priced by the native slice table (ffc_mm_dp
        ABI v10) and by the pure-Python fallback returns bitwise-equal
        costs for the winner AND every seed template."""
        rules = generate_parallelization_rules([2, 4])
        cfg = OptimizerConfig(alpha=1.2, budget=2)

        native = graph_optimize(
            mlp_pcg(), hier_context(SPEC_2x4), SPEC_2x4, rules, cfg
        )
        assert native.telemetry["native_dp"] is True, (
            "native DP unavailable — the parity test must exercise it"
        )
        monkeypatch.setenv("FF_TPU_NO_NATIVE", "1")
        python = graph_optimize(
            mlp_pcg(), hier_context(SPEC_2x4), SPEC_2x4, rules, cfg
        )
        assert python.telemetry["native_dp"] is False
        assert native.runtime == python.runtime
        assert native.seed_runtimes == python.seed_runtimes
        # both arms ran the two-level DP and agree on the outer winner
        assert native.hierarchical is not None
        assert python.hierarchical is not None
        assert (
            native.hierarchical["winner"] == python.hierarchical["winner"]
        )


class TestWinnerCommCensus:
    @pytest.mark.filterwarnings("ignore")
    def test_comm_census_verifies_searched_winner(self):
        """`ffcheck --comm` semantics on the two-level winner: the
        link-classed movement predictions cross-check clean against the
        lowered step's collective census (the winner's DCN bytes are
        verified, not assumed)."""
        from flexflow_tpu.analysis.comm_analysis import verify_comm
        from flexflow_tpu.analysis.diagnostics import has_errors

        ctx = hier_context(SPEC_2x4)
        res = graph_optimize(
            mlp_pcg(),
            ctx,
            SPEC_2x4,
            generate_parallelization_rules([2, 4]),
            OptimizerConfig(alpha=1.2, budget=2),
        )
        analysis, diags = verify_comm(
            res.pcg,
            mapping=res.machine_mapping,
            machine_spec=SPEC_2x4,
            estimator=ctx.cost_estimator,
        )
        assert not has_errors(diags), [str(d) for d in diags]


class TestStoreMigrationV3:
    V2_KEY = "CombineAttrs|64|x|v|cpu:cpu"

    def test_v2_entries_fenced_never_served(self, tmp_path):
        """A v2 movement table migrates on read under legacy2| — its
        measurements carry no link class, so serving them for EITHER
        interconnect (~100x apart) would be contamination."""
        path = str(tmp_path / "mv.json")
        with open(path, "w") as f:
            json.dump({"schema": 2, "entries": {self.V2_KEY: 0.5}}, f)
        s = MovementCostStore(path)
        # preserved under the fence, but no lookup ever matches it
        assert s.get(LEGACY_V2_PREFIX + self.V2_KEY) is not None
        assert s.get(self.V2_KEY) is None
        for lc in ("ici", "dcn"):
            assert s.get(f"{self.V2_KEY}|{lc}") is None

    def test_v3_link_classes_do_not_cross_serve(self, tmp_path):
        path = str(tmp_path / "mv3.json")
        s = MovementCostStore(path)
        s.put(self.V2_KEY + "|ici", 0.25)
        s.save()
        r = MovementCostStore(path)
        assert r.get(self.V2_KEY + "|ici") == 0.25
        assert r.get(self.V2_KEY + "|dcn") is None


def _multislice_proxy_pcg(L=4, d=1024, B=512):
    """The multi-slice proxy: a uniform weight-heavy dense chain whose
    dp-hybrid plan replicates d x d weight blocks across the slice (DCN)
    boundary every step. The shapes sit in the disagreement band the A/B
    needs: under FLAT (uniform-constant) pricing the full-machine
    dp-over-the-boundary hybrid wins (the 2x compute advantage beats
    uniformly-priced weight replication), while under the TRUE 10x
    ICI/DCN gap those same replicate edges dominate and the optimum
    stays inside the slice."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.parallel_tensor_shape import lift_to_parallel
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape
    from flexflow_tpu.pcg.parallel_computation_graph_builder import (
        ParallelComputationGraphBuilder,
    )

    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(
        lift_to_parallel(TensorShape((B, d), DataType.FLOAT)), name="x"
    )
    h = x
    for i in range(L):
        h = b.dense(h, d, activation=Activation.RELU, name=f"l{i}")
    return b.graph


def _multislice_spec(gap=10.0, ici_gbps=2.0):
    """The 2-slice 4+4 virtual machine: slices are the node axis (INTER =
    DCN at ici/gap GB/s, INTRA = ICI). gap=1.0 is the uniform-bandwidth
    machine of the counter-example — identical constants on every link,
    i.e. exactly what the flat (slice-blind) cost model assumes the
    machine always looks like."""
    from flexflow_tpu.pcg.machine_view import MachineSpecification

    return MachineSpecification(2, 1, 4, ici_gbps / gap, ici_gbps)


def _multislice_ctx(spec, slice_aware=False, hierarchy=False, flat=False):
    """Estimator + mapping context on `spec`. `flat=True` builds the
    slice-BLIND arm: the same machine geometry priced with one constant
    per link class pair (dcn latency = ici latency; the spec passed in
    should carry uniform bandwidths) — the pre-slice-aware worldview the
    tentpole replaces."""
    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
        AnalyticTPUCostEstimator,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingContext,
    )

    est = AnalyticTPUCostEstimator(
        spec, peak_flops=5e10, hbm_gbps=10.0,
        ici_latency_ms=0.1,
        dcn_latency_ms=0.1 if flat else 0.2,
        emulated_mesh=True,
    )
    ctx = MachineMappingContext(
        est, make_default_allowed_machine_views(),
        overlap_fraction=0.5,
        slice_aware=slice_aware, slice_hierarchy=hierarchy,
    )
    return est, ctx


@pytest.mark.slow
def test_hierarchical_beats_flat_by_1p2x_under_10x_gap():
    """Acceptance gate (ISSUE 17): on the 4+4 topology the hierarchical
    search's winner is >= 1.2x cheaper than the flat (slice-blind)
    search's winner re-priced under the true 10x ICI/DCN gap."""
    from flexflow_tpu.compiler.unity_algorithm import price_mapped_plan

    pcg = _multislice_proxy_pcg()
    rules = generate_parallelization_rules([2, 4, 8])
    spec_true = _multislice_spec(10.0)
    spec_uni = _multislice_spec(1.0)
    _, ctx_true = _multislice_ctx(spec_true)
    _, ctx_flat = _multislice_ctx(spec_uni, flat=True)
    _, ctx_hier = _multislice_ctx(
        spec_true, slice_aware=True, hierarchy=True
    )

    res_flat = graph_optimize(
        pcg, ctx_flat, spec_uni, rules, OptimizerConfig(budget=2)
    )
    flat_true_ms = price_mapped_plan(
        res_flat.pcg, res_flat.machine_mapping, ctx_true, spec_true
    )
    assert flat_true_ms is not None
    res_hier = graph_optimize(
        pcg, ctx_hier, spec_true, rules, OptimizerConfig(budget=2)
    )
    assert res_hier.runtime > 0
    assert flat_true_ms / res_hier.runtime >= 1.2
