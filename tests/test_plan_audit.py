"""Plan-audit tests (ISSUE 3 tentpole): the predicted-vs-measured replay of
the searched plan — per-op ratios against the pricing estimator, movement
edges measured as real reshards, geomean/worst-op summary, and the
provenance plumbing (`FFModel.search_provenance["plan_audit"]`)."""

import json
import math
import os
import sys

import numpy as np
import pytest

from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.observability.plan_audit import (
    AUDIT_SCHEMA_VERSION,
    _geomean,
    _ratio,
    audit_plan,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BATCH = 32


def compile_mlp(**cfg_kwargs):
    m = FFModel(FFConfig(batch_size=BATCH, seed=0, **cfg_kwargs))
    x = m.create_tensor([BATCH, 64], name="x")
    h = m.dense(x, 64, name="fc1")
    h = m.relu(h)
    logits = m.dense(h, 10, name="head")
    m.compile(
        SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    return m


class TestSummaryMath:
    def test_geomean(self):
        assert _geomean([2.0, 8.0]) == pytest.approx(4.0)
        # non-positive / non-finite / None entries are excluded, not fatal
        assert _geomean([4.0, None, 0.0, float("inf")]) == pytest.approx(4.0)
        assert _geomean([]) is None
        assert _geomean([None]) is None

    def test_ratio_guards(self):
        assert _ratio(2.0, 4.0) == pytest.approx(0.5)
        assert _ratio(None, 1.0) is None
        assert _ratio(1.0, 0.0) is None
        assert _ratio(1.0, float("inf")) is None
        assert _ratio(0.0, 1.0) is None


class TestForcedSeedAudit:
    """The dp seed's plan always contains parallel ops, so its audit
    exercises every row type: compute ops AND movement edges."""

    @pytest.fixture(scope="class")
    def audit(self):
        m = compile_mlp(
            search_budget=1, plan_audit=True,
            force_strategy_seed="dp8xtp1xsp1",
        )
        return m.search_provenance["plan_audit"]

    def test_block_shape(self, audit):
        assert audit["schema"] == AUDIT_SCHEMA_VERSION
        assert audit["num_ops"] == len(audit["ops"]) == 3  # 2 dense + relu
        assert audit["num_movement_edges"] == len(audit["movement_edges"])
        assert audit["num_movement_edges"] > 0
        assert audit["movement_measured"] is True  # 8-device test mesh
        json.dumps(audit)  # artifact-serializable

    def test_op_rows(self, audit):
        for o in audit["ops"]:
            assert set(o) == {
                "name", "op_type", "predicted_ms", "measured_ms", "ratio",
            }
            assert o["predicted_ms"] > 0
            assert o["measured_ms"] > 0
            # rows are rounded to 4 decimals, so tiny predicted values make
            # the re-derived ratio coarse — bound it loosely
            assert o["ratio"] > 0
            rounding = 5e-5 / o["predicted_ms"] + 5e-5 / o["measured_ms"]
            assert o["ratio"] == pytest.approx(
                o["measured_ms"] / o["predicted_ms"],
                rel=2 * rounding + 1e-3,
            )
        names = {o["name"] for o in audit["ops"]}
        assert {"fc1", "head"} <= names

    def test_movement_rows(self, audit):
        kinds = {e["kind"] for e in audit["movement_edges"]}
        # the dp seed wraps weights in Replicate and the input/output in
        # Repartition/Combine — the per-step weight-sync collectives
        assert "ReplicateAttrs" in kinds
        for e in audit["movement_edges"]:
            # predicted_collective_bytes: the static comm model's byte
            # side (ISSUE 11) recorded beside the ms measurement
            assert set(e) == {
                "name", "kind", "bytes", "predicted_ms", "measured_ms",
                "ratio", "predicted_collective_bytes",
            }
            assert e["bytes"] > 0
            assert e["measured_ms"] is not None and e["measured_ms"] > 0
            assert e["predicted_collective_bytes"] >= 0

    def test_summary(self, audit):
        s = audit["summary"]
        assert s["num_ops_measured"] == 3
        assert s["num_edges_measured"] == audit["num_movement_edges"]
        assert s["op_geomean_ratio"] > 0
        assert s["movement_geomean_ratio"] > 0
        # combined geomean sits between the per-class geomeans
        lo = min(s["op_geomean_ratio"], s["movement_geomean_ratio"])
        hi = max(s["op_geomean_ratio"], s["movement_geomean_ratio"])
        assert lo <= s["geomean_ratio"] <= hi
        # worst ops sorted by log-distance from a perfect prediction
        dists = [abs(math.log(w["ratio"])) for w in s["worst_ops"]]
        assert dists == sorted(dists, reverse=True)
        assert len(s["worst_ops"]) <= 5


class TestSearchedAudit:
    def test_searched_compile_records_audit(self):
        m = compile_mlp(search_budget=2, plan_audit=True)
        audit = m.search_provenance["plan_audit"]
        assert audit["schema"] == AUDIT_SCHEMA_VERSION
        assert audit["summary"]["op_geomean_ratio"] > 0
        # the audit replays the WINNER: op count matches the searched PCG's
        # compute ops
        from flexflow_tpu.op_attrs.core import is_parallel_op
        from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs

        pcg = m.instance.pcg
        n_compute = sum(
            1 for n in pcg.topological_ordering()
            if not isinstance(pcg.op_attrs(n), (InputAttrs, WeightAttrs))
            and not is_parallel_op(pcg.op_attrs(n))
        )
        assert audit["num_ops"] == n_compute

    def test_audit_off_by_default(self):
        m = compile_mlp(search_budget=2)
        assert "plan_audit" not in (m.search_provenance or {})


class TestAuditPlanDirect:
    def test_no_mesh_means_unmeasured_movement(self):
        # audit_plan without a mesh still prices + measures compute ops but
        # leaves movement edges unmeasured (measured_ms None) rather than
        # lying with a same-device number
        from flexflow_tpu.compiler import (
            AnalyticTPUCostEstimator,
            MachineMappingCache,
            MachineMappingContext,
            evaluate_pcg,
            make_default_allowed_machine_views,
        )
        from flexflow_tpu.compiler.unity_algorithm import greedy_apply
        from flexflow_tpu.pcg import ComputationGraphBuilder
        from flexflow_tpu.pcg.machine_view import MachineSpecification
        from flexflow_tpu.pcg.parallel_computation_graph import (
            pcg_from_computation_graph,
        )
        from flexflow_tpu.substitutions import generate_parallelization_rules

        b = ComputationGraphBuilder()
        x = b.create_input([16, 32], name="x")
        h = b.dense(x, 32, use_bias=False, name="fc1")
        pcg = pcg_from_computation_graph(b.graph)
        pcg = greedy_apply(
            pcg, generate_parallelization_rules([4])[:1], max_steps=1
        )
        spec = MachineSpecification(1, 1, 4, 25.0, 400.0)
        est = AnalyticTPUCostEstimator(spec)
        ctx = MachineMappingContext(est, make_default_allowed_machine_views())
        r = evaluate_pcg(pcg, ctx, spec, MachineMappingCache())
        audit = audit_plan(r.pcg, r.machine_mapping, est)
        assert audit["movement_measured"] is False
        for e in audit["movement_edges"]:
            assert e["measured_ms"] is None and e["ratio"] is None
        assert all(o["measured_ms"] is not None for o in audit["ops"])


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
