"""Fused collective-matmul lowering + overlap-aware movement pricing.

Covers the ISSUE-6 vertical slice end to end on the virtual 8-device CPU
mesh: kernel-level numerics parity of the ring all-gather-matmul and
matmul-reduce-scatter against the plain-XLA lowering (across dtypes and
shard degrees), the executor's pattern-matched fused lowering behind
FF_TPU_OVERLAP, the DP's overlapped movement entry (Python/native cost
parity + the derive_overlap_plan annotation), the PCG008 verifier rule,
the LINT004 shard_map host-read lint, the persisted movement-cost store,
and a slow-marked >=1.15x regression gate on a bandwidth-bound proxy with
the FF_TPU_OVERLAP_BASELINE=1 revert switch.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu.compiler.machine_mapping.overlap import classify_op
from flexflow_tpu.kernels.collective_matmul import (
    all_gather_matmul,
    matmul_reduce_scatter,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops.loss_functions import (
    SparseCategoricalCrossEntropyLossAttrs,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorDims,
    ParallelTensorShape,
    ShardParallelDim,
)
from flexflow_tpu.parallel import DistributedTrainingInstance, MachineMesh
from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs
from flexflow_tpu.pcg.parallel_computation_graph_builder import (
    ParallelComputationGraphBuilder,
)


def pts(sizes, degrees=None, sum_degree=1, copy=1):
    degrees = degrees or [1] * len(sizes)
    return ParallelTensorShape(
        ParallelTensorDims(
            tuple(ShardParallelDim(s, d) for s, d in zip(sizes, degrees)),
            sum_degree,
            copy,
        ),
        DataType.FLOAT,
    )


def flat_mesh():
    return Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("a", "b", "c"))


# ---------------------------------------------------------------------------
# kernel-level parity: fused vs plain-XLA across dtypes and shard degrees
# ---------------------------------------------------------------------------


class TestKernelParity:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "axes", [("a",), ("a", "b"), ("a", "b", "c")]
    )
    def test_all_gather_matmul_matches_xla(self, dtype, axes):
        mesh = flat_mesh()
        rs = np.random.RandomState(0)
        m, k, n = 16, 24, 12
        x = jnp.asarray(rs.randn(m, k), dtype)
        w = jnp.asarray(rs.randn(k, n), dtype)
        spec = axes if len(axes) > 1 else axes[0]
        x_spec, w_spec = P(spec, None), P(None, None)
        fused = jax.jit(
            lambda x, w: all_gather_matmul(
                x, w, mesh, x_spec, w_spec, 0, fused=True
            )
        )(x, w)
        serial = jax.jit(
            lambda x, w: all_gather_matmul(
                x, w, mesh, x_spec, w_spec, 0, fused=False
            )
        )(x, w)
        # the all-gather form is exact: each output row is one full-depth
        # matmul either way (bf16 still reassociates inside dot)
        np.testing.assert_allclose(
            np.asarray(fused, np.float32),
            np.asarray(serial, np.float32),
            rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6,
            atol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
        )

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("axes", [("a",), ("a", "b"), ("a", "b", "c")])
    def test_matmul_reduce_scatter_matches_xla(self, dtype, axes):
        mesh = flat_mesh()
        rs = np.random.RandomState(1)
        m, k, n = 16, 32, 12
        x = jnp.asarray(rs.randn(m, k), dtype)
        w = jnp.asarray(rs.randn(k, n), dtype)
        spec = axes if len(axes) > 1 else axes[0]
        x_spec, w_spec = P(None, spec), P(spec, None)
        fused = jax.jit(
            lambda x, w: matmul_reduce_scatter(
                x, w, mesh, x_spec, w_spec, fused=True
            )
        )(x, w)
        serial = jax.jit(
            lambda x, w: matmul_reduce_scatter(
                x, w, mesh, x_spec, w_spec, fused=False
            )
        )(x, w)
        # ring partial-sum order differs from psum's: allclose, not
        # bitwise — and bf16 rounds at EVERY partial add, so an 8-way sum
        # reassociated can move a value by several ulps of ~0.04
        np.testing.assert_allclose(
            np.asarray(fused, np.float32),
            np.asarray(serial, np.float32),
            rtol=1.5e-1 if dtype == jnp.bfloat16 else 1e-5,
            atol=1e-1 if dtype == jnp.bfloat16 else 1e-4,
        )

    def test_gather_axis_one_with_bias_activation_and_sharded_out(self):
        from flexflow_tpu.op_attrs.activation import Activation

        mesh = flat_mesh()
        rs = np.random.RandomState(2)
        b, s, e, n = 4, 8, 16, 8
        x = jnp.asarray(rs.randn(b, s, e), jnp.float32)
        w = jnp.asarray(rs.randn(e, n), jnp.float32)
        bias = jnp.asarray(rs.randn(n), jnp.float32)
        ref = jax.nn.relu(x @ w + bias)
        out = jax.jit(
            lambda x, w, bb: all_gather_matmul(
                x, w, mesh, P(None, ("a", "b"), None), P(None, "c"), 1,
                bias=bb, activation=Activation.RELU,
            )
        )(x, w, bias)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
        )

    def test_inapplicable_ring_falls_back(self):
        """Indivisible chunking and gather-on-contraction both take the
        plain-XLA path rather than failing."""
        mesh = flat_mesh()
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(6, 10), jnp.float32)  # 6 % 4 != 0
        w = jnp.asarray(rs.randn(10, 4), jnp.float32)
        out = all_gather_matmul(
            x, w, mesh, P(("a", "b"), None), P(None, None), 0
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(x @ w), rtol=1e-6, atol=1e-5
        )


# ---------------------------------------------------------------------------
# executor lowering: pattern match + numerics + gradients + ring in HLO
# ---------------------------------------------------------------------------


def build_combine_linear(m=16, k=32, n=10, deg=4):
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([m, k], [deg, 1]), name="x")
    xc = b.parallel_combine(x, 0, deg)
    logits = b.dense(xc, n, use_bias=False, name="head")
    return b.graph, logits


def build_row_reduction(m=16, k=32, n=10, deg=4):
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([m, k], [1, deg]), name="x")
    y = b.dense(x, n, use_bias=False, name="fc")
    logits = b.parallel_reduce(y, deg)
    return b.graph, logits


class TestExecutorOverlapLowering:
    loss = SparseCategoricalCrossEntropyLossAttrs()
    opt = SGDOptimizerAttrs(lr=0.1)

    @pytest.mark.parametrize(
        "build,kind",
        [(build_combine_linear, "ag_matmul"), (build_row_reduction, "matmul_rs")],
    )
    def test_fused_lowering_matches_serial(self, build, kind):
        pcg, logits = build()
        rs = np.random.RandomState(0)
        xv = jnp.asarray(rs.randn(16, 32), jnp.float32)
        ref = DistributedTrainingInstance(
            pcg, logits, self.loss, self.opt, MachineMesh.for_devices(8)
        )
        assert ref.overlap_sites == {}  # off by default
        inst = DistributedTrainingInstance(
            pcg, logits, self.loss, self.opt, MachineMesh.for_devices(8),
            overlap=True,
        )
        assert list(inst.overlap_sites.values()) == [kind]
        p0, _ = ref.initialize(0)
        p1, o1 = inst.initialize(0)
        np.testing.assert_allclose(
            np.asarray(inst.forward(p1, {"x": xv})),
            np.asarray(ref.forward(p0, {"x": xv})),
            rtol=1e-4, atol=1e-5,
        )
        # the ring is real: the fused forward carries collective-permutes
        with inst.machine_mesh.mesh:
            txt = inst._jit_fwd.lower(p1, {"x": xv}).compile().as_text()
        assert "collective-permute" in txt
        # differentiable: a train step through the fused lowering runs and
        # produces a finite loss (ppermute transposes to the reverse ring)
        yv = jnp.asarray(rs.randint(0, 10, 16), jnp.int32)
        out = inst.train_step(p1, o1, {"x": xv}, yv)
        assert np.isfinite(float(out[2]))

    def test_baseline_switch_reverts(self, monkeypatch):
        monkeypatch.setenv("FF_TPU_OVERLAP_BASELINE", "1")
        pcg, logits = build_combine_linear()
        inst = DistributedTrainingInstance(
            pcg, logits, self.loss, self.opt, MachineMesh.for_devices(8),
            overlap=True,
        )
        assert inst.overlap_sites == {}

    def test_env_switch_enables(self, monkeypatch):
        monkeypatch.setenv("FF_TPU_OVERLAP", "1")
        pcg, logits = build_combine_linear()
        inst = DistributedTrainingInstance(
            pcg, logits, self.loss, self.opt, MachineMesh.for_devices(8)
        )
        assert list(inst.overlap_sites.values()) == ["ag_matmul"]

    def test_bias_activation_linear_not_rs_fused(self):
        """The matmul_rs pattern keeps the pinned-reduction exactness
        guards: a bias'd Linear's partial sums cannot ring."""
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(pts([16, 32], [1, 4]), name="x")
        y = b.dense(x, 10, use_bias=True, name="fc")
        logits = b.parallel_reduce(y, 4)
        inst = DistributedTrainingInstance(
            b.graph, logits, self.loss, self.opt, MachineMesh.for_devices(8),
            overlap=True,
        )
        assert inst.overlap_sites == {}


# ---------------------------------------------------------------------------
# DP: overlapped movement entry — combine arithmetic, eligibility, parity
# ---------------------------------------------------------------------------


class TestClassifyOp:
    """The roofline class that seeds an overlapped entry
    (`leaf_roofline_class`'s one helper)."""

    PEAK = 1e12  # FLOP/s
    HBM = 100.0  # GB/s

    def test_classify_mxu_bound(self):
        # compute roofline 3 ms, memory roofline ~0; measured at roofline
        assert classify_op(1e9, 1e3, 3.0, self.PEAK, self.HBM) == "mxu"

    def test_classify_bandwidth_bound(self):
        # memory roofline 2 ms dominates; measured at roofline
        assert (
            classify_op(1e3, 1e8, 2.0, self.PEAK, self.HBM) == "bandwidth"
        )

    def test_classify_dispatch_bound(self):
        # both rooflines are microseconds; a 1 ms measurement is overhead
        assert classify_op(1e3, 1e3, 1.0, self.PEAK, self.HBM) == "dispatch"

    def test_classify_zero_time_is_dispatch(self):
        assert classify_op(1e9, 1e3, 0.0, self.PEAK, self.HBM) == "dispatch"


class TestOverlapPricing:
    def test_series_combine_takes_cheaper_exposure(self):
        from flexflow_tpu.compiler.machine_mapping.result import (
            FeasibleMachineMappingResult,
            series_combine,
        )

        pre = FeasibleMachineMappingResult(1.0, (None, "v"))
        post = FeasibleMachineMappingResult(2.0, (None, "v"))
        # serial exposure at fraction 0: comm = 3.0
        serial = series_combine(3.0, pre, post, overlap_fraction=0.0)
        assert serial.runtime == 6.0
        # overlapped entry cheaper: used
        ov = series_combine(3.0, pre, post, overlap_fraction=0.0, ov_cost=0.5)
        assert ov.runtime == 3.5
        # overlapped entry worse than the haircut exposure: ignored
        ov2 = series_combine(
            3.0, pre, post, overlap_fraction=1.0, ov_cost=2.5
        )
        assert ov2.runtime == series_combine(
            3.0, pre, post, overlap_fraction=1.0
        ).runtime

    def _ctx(self, spec, overlap, fraction=0.0):
        from flexflow_tpu.compiler import (
            AnalyticTPUCostEstimator,
            make_default_allowed_machine_views,
        )
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingContext,
        )

        est = AnalyticTPUCostEstimator(
            spec, peak_flops=197e12, hbm_gbps=820.0,
            ici_latency_ms=0.001, dcn_latency_ms=0.01,
        )
        return MachineMappingContext(
            est,
            make_default_allowed_machine_views(),
            overlap_fraction=fraction,
            overlap_lowering=overlap,
        )

    def _flagship_pcg(self):
        from flexflow_tpu.models.flagship import build_flagship_pcg

        return build_flagship_pcg(
            batch=64, seq=512, embed=1024, heads=8, layers=2, vocab=32000
        )

    def test_eligibility_mirrors_executor_patterns(self):
        from flexflow_tpu.compiler.machine_mapping.overlap import (
            series_split_overlap,
        )
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            MMProblemTreeSeriesSplit,
            UnmappedOpCostEstimateKey,
            get_machine_mapping_problem_tree,
        )
        from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds
        from flexflow_tpu.pcg.machine_view import MachineSpecification

        spec = MachineSpecification(1, 1, 8, 25.0, 400.0)
        ctx = self._ctx(spec, overlap=True)
        pcg = self._flagship_pcg()
        kinds = set()

        def walk(t):
            if isinstance(t, UnmappedOpCostEstimateKey):
                return
            if isinstance(t, MMProblemTreeSeriesSplit):
                info = series_split_overlap(t, ctx)
                if info is not None:
                    kinds.add(info.kind)
                    assert info.chunks > 1
                    assert info.roofline_class in ("mxu", "bandwidth")
                    assert info.adjacent_ms > 0
                    assert info.movement is not None
            walk(t.left)
            walk(t.right)

        for label, s in enumerate_seeds(pcg, 8):
            if label in ("dp1xtp8xsp1", "dp2xtp4xsp1"):
                tree, _ = get_machine_mapping_problem_tree(s)
                walk(tree)
        # tp seeds fuse their row/head reductions; their Combine seams sit
        # on the CONTRACTION dim, which the ring cannot chunk — so no
        # ag_matmul from pure seeds (eligibility mirrors the executor,
        # which skips those too)
        assert kinds == {"matmul_rs"}
        # a non-contraction Combine -> Linear adjacency (mixed/partial
        # plans, and the executor's ag_matmul fixture) IS eligible — at
        # shapes big enough to clear the roofline's dispatch floor (a
        # too-tiny adjacent matmul has nothing to hide a collective
        # behind, and the seed correctly rejects it)
        tiny_pcg, _ = build_combine_linear()
        tree, _ = get_machine_mapping_problem_tree(tiny_pcg)
        walk(tree)
        assert kinds == {"matmul_rs"}  # dispatch-class adjacent: rejected
        ag_pcg, _ = build_combine_linear(m=512, k=1024, n=512)
        tree, _ = get_machine_mapping_problem_tree(ag_pcg)
        walk(tree)
        assert kinds == {"matmul_rs", "ag_matmul"}

        # off switch: no split is eligible
        ctx_off = self._ctx(spec, overlap=False)
        tree, _ = get_machine_mapping_problem_tree(
            dict(enumerate_seeds(pcg, 8))["dp1xtp8xsp1"]
        )

        def assert_none(t):
            if isinstance(t, UnmappedOpCostEstimateKey):
                return
            if isinstance(t, MMProblemTreeSeriesSplit):
                assert series_split_overlap(t, ctx_off) is None
            assert_none(t.left)
            assert_none(t.right)

        assert_none(tree)

    def test_native_python_parity_with_overlap(self):
        from flexflow_tpu.compiler import MachineMappingCache
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            get_optimal_machine_mapping_python,
        )
        from flexflow_tpu.compiler.machine_mapping.native_dp import (
            NATIVE_MISS,
            try_native_dp,
        )
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            get_machine_mapping_problem_tree,
        )
        from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds
        from flexflow_tpu.pcg.machine_view import MachineSpecification

        pcg = self._flagship_pcg()
        checked = 0
        for spec in (
            MachineSpecification(1, 1, 8, 25.0, 400.0),
            MachineSpecification(2, 1, 4, 25.0, 400.0),
        ):
            for fraction in (0.0, 0.5):
                ctx = self._ctx(spec, overlap=True, fraction=fraction)
                for label, s in enumerate_seeds(pcg, 8):
                    if label not in ("dp1xtp8xsp1", "dp2xtp4xsp1"):
                        continue
                    tree, _ = get_machine_mapping_problem_tree(s)
                    nat = try_native_dp(
                        MachineMappingCache(), ctx, tree, spec
                    )
                    assert nat is not NATIVE_MISS
                    py = get_optimal_machine_mapping_python(
                        MachineMappingCache(), ctx, tree, spec
                    )
                    assert (nat is None) == (py is None)
                    if nat is not None:
                        assert nat.runtime == py.runtime, (
                            label, spec, fraction,
                        )
                        checked += 1
        assert checked >= 4

    def test_dp_selects_overlap_on_flagship_edge(self):
        """Acceptance: with overlap on, the DP selects the overlapped
        lowering for at least one flagship movement edge (reference-strict
        fraction — the uncalibrated 0.5 haircut already hides sub-ms edges
        under a hundreds-of-ms downstream stage), the annotation's
        recomputed root cost matches the winner's, and the overlapped
        price is what series_combine used."""
        import math

        from flexflow_tpu.compiler import MachineMappingCache
        from flexflow_tpu.compiler.unity_algorithm import (
            enumerate_seeds,
            evaluate_pcg,
        )
        from flexflow_tpu.pcg.machine_view import MachineSpecification

        spec = MachineSpecification(1, 1, 8, 25.0, 400.0)
        pcg = self._flagship_pcg()
        ctx_on = self._ctx(spec, overlap=True, fraction=0.0)
        ctx_off = self._ctx(spec, overlap=False, fraction=0.0)
        seeds = dict(enumerate_seeds(pcg, 8))
        s = seeds["dp2xtp4xsp1"]
        r_on = evaluate_pcg(s, ctx_on, spec, MachineMappingCache())
        r_off = evaluate_pcg(s, ctx_off, spec, MachineMappingCache())
        assert r_on is not None and r_off is not None
        chosen = [e for e in r_on.overlap_edges if e["chosen"]]
        assert chosen, "no flagship edge selected the overlapped lowering"
        for e in chosen:
            assert e["overlapped_exposed_ms"] < e["serial_exposed_ms"]
            assert e["kind"] in ("ag_matmul", "matmul_rs")
            assert math.isclose(
                e["recomputed_root_ms"], e["winner_root_ms"],
                rel_tol=1e-6, abs_tol=1e-4,
            )
        # pricing the cheaper lowering can only lower the plan's cost
        assert r_on.runtime <= r_off.runtime
        assert r_on.runtime < r_off.runtime  # something actually hid


# ---------------------------------------------------------------------------
# PCG008: fused-lowering annotation verification
# ---------------------------------------------------------------------------


class TestOverlapAnnotationRule:
    def test_valid_annotations_pass(self):
        from flexflow_tpu.analysis.pcg_verify import verify_overlap_plan

        pcg, _ = build_combine_linear()
        combine = [
            n for n in pcg.nodes
            if type(pcg.op_attrs(n)).__name__ == "CombineAttrs"
        ]
        assert verify_overlap_plan(pcg, {combine[0]: "ag_matmul"}) == []
        pcg2, _ = build_row_reduction()
        red = [
            n for n in pcg2.nodes
            if type(pcg2.op_attrs(n)).__name__ == "ReductionAttrs"
        ]
        assert verify_overlap_plan(pcg2, {red[0]: "matmul_rs"}) == []

    def test_negative_paths_pin_rule_id(self):
        from flexflow_tpu.analysis.pcg_verify import verify_overlap_plan

        pcg, _ = build_combine_linear()
        by_type = {
            type(pcg.op_attrs(n)).__name__: n for n in pcg.nodes
        }
        # ag_matmul on a non-Combine node
        diags = verify_overlap_plan(
            pcg, {by_type["LinearAttrs"]: "ag_matmul"}
        )
        assert [d.rule_id for d in diags] == ["PCG008"]
        # matmul_rs on a Combine (not a Reduction draining partial sums)
        diags = verify_overlap_plan(
            pcg, {by_type["CombineAttrs"]: "matmul_rs"}
        )
        assert [d.rule_id for d in diags] == ["PCG008"]
        # unknown kind / missing node
        diags = verify_overlap_plan(pcg, {by_type["LinearAttrs"]: "bogus"})
        assert [d.rule_id for d in diags] == ["PCG008"]
        diags = verify_overlap_plan(pcg, {10 ** 6: "ag_matmul"})
        assert [d.rule_id for d in diags] == ["PCG008"]

    def test_verify_pcg_forwards_overlap_plan(self):
        from flexflow_tpu.analysis.pcg_verify import verify_pcg

        pcg, _ = build_combine_linear()
        lin = [
            n for n in pcg.nodes
            if type(pcg.op_attrs(n)).__name__ == "LinearAttrs"
        ]
        diags = verify_pcg(pcg, overlap_plan={lin[0]: "ag_matmul"})
        assert any(d.rule_id == "PCG008" for d in diags)


# ---------------------------------------------------------------------------
# LINT004: host reads inside shard_map bodies
# ---------------------------------------------------------------------------


class TestShardMapLint:
    def test_flags_host_read_in_shard_map_body(self):
        from flexflow_tpu.analysis.source_lints import lint_source

        src = (
            "import numpy as np\n"
            "from flexflow_tpu.utils.shard_map_compat import"
            " shard_map_compat\n"
            "def ring(mesh, specs, x):\n"
            "    def body(x_blk):\n"
            "        host = np.asarray(x_blk)\n"
            "        return x_blk * host.mean()\n"
            "    return shard_map_compat(body, mesh, specs, specs[0])(x)\n"
        )
        diags = lint_source(src)
        assert [d.rule_id for d in diags] == ["LINT004"]

    def test_item_in_aliased_shard_map_body(self):
        from flexflow_tpu.analysis.source_lints import lint_source

        src = (
            "from flexflow_tpu.utils.shard_map_compat import"
            " shard_map_compat as _shard_map\n"
            "def f(mesh, specs, x, t):\n"
            "    def local_fn(x_blk):\n"
            "        return x_blk + t.item()\n"
            "    return _shard_map(local_fn, mesh, specs, specs[0])(x)\n"
        )
        diags = lint_source(src)
        assert [d.rule_id for d in diags] == ["LINT004"]

    def test_clean_ring_body_passes(self):
        from flexflow_tpu.analysis.source_lints import lint_source

        src = (
            "from jax import lax\n"
            "from flexflow_tpu.utils.shard_map_compat import"
            " shard_map_compat\n"
            "def ring(mesh, specs, x):\n"
            "    def body(x_blk):\n"
            "        return lax.ppermute(x_blk, 'd', [(0, 1), (1, 0)])\n"
            "    return shard_map_compat(body, mesh, specs, specs[0])(x)\n"
        )
        assert lint_source(src) == []


# ---------------------------------------------------------------------------
# movement-cost store: roundtrip + estimator preference
# ---------------------------------------------------------------------------


class TestMovementCostStore:
    def test_roundtrip_and_atomic_save(self, tmp_path):
        from flexflow_tpu.compiler.movement_store import MovementCostStore

        path = str(tmp_path / "store.json")
        s = MovementCostStore(path)
        assert len(s) == 0
        s.put("k1", 1.25)
        s.put("k2", float("nan"))  # rejected
        s.put("k3", -1.0)  # rejected
        assert len(s) == 1
        s.save()
        s2 = MovementCostStore(path)
        assert s2.get("k1") == 1.25 and len(s2) == 1

    def test_estimator_prefers_cached_measurement(self):
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            AnalyticTPUCostEstimator,
        )
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            OpCostEstimateKey,
        )
        from flexflow_tpu.compiler.movement_store import (
            MovementCostStore,
            movement_edge_key,
        )
        from flexflow_tpu.op_attrs.ops import CombineAttrs
        from flexflow_tpu.pcg.machine_view import (
            MachineSpaceCoordinate,
            MachineSpecification,
            MachineView,
            MachineViewDimension,
            ProjectionType,
        )

        spec = MachineSpecification(1, 1, 8, 25.0, 400.0)
        attrs = CombineAttrs(0, 4)
        in_shape = pts([16, 32], [4, 1])
        view = MachineView(
            MachineSpaceCoordinate(0, 0),
            (MachineViewDimension(1, ProjectionType.INTRA_NODE),),
        )
        key = OpCostEstimateKey(attrs, (in_shape,), (pts([16, 32]),), view)
        import tempfile

        store = MovementCostStore(
            os.path.join(tempfile.mkdtemp(), "s.json")
        )
        base = AnalyticTPUCostEstimator(spec)
        analytic = base.estimate_op_cost(key)
        assert analytic > 0
        store.put(movement_edge_key(attrs, [in_shape], view), 0.0625)
        est = AnalyticTPUCostEstimator(spec, movement_store=store)
        assert est.estimate_op_cost(key) == 0.0625
        # a different view misses the store and falls back to analytic
        other = MachineView(
            MachineSpaceCoordinate(0, 0),
            (MachineViewDimension(1, ProjectionType.INTER_NODE),),
        )
        key2 = OpCostEstimateKey(
            attrs, (in_shape,), (pts([16, 32]),), other
        )
        assert est.estimate_op_cost(key2) == base.estimate_op_cost(key2)


# ---------------------------------------------------------------------------
# FFModel end-to-end: compile with --overlap, audit fused edges, store file
# ---------------------------------------------------------------------------


class TestEndToEnd:
    def test_compile_audit_and_store(self, tmp_path):
        import json

        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

        store_path = str(tmp_path / "movement_costs.json")
        cfg = FFConfig(
            batch_size=8, seed=0, search_budget=2, plan_audit=True,
            overlap=True, movement_cost_store=store_path,
            force_strategy_seed="dp1xtp8xsp1",
        )
        m = FFModel(cfg)
        x = m.create_tensor([8, 16, 32], name="x")
        h = m.dense(x, 128, use_bias=False, name="ff1")
        h = m.relu(h)
        h = m.dense(h, 32, use_bias=False, name="ff2")
        logits = m.dense(h, 64, use_bias=False, name="head")
        m.compile(
            SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
            logit_tensor=logits,
        )
        prov = m.search_provenance
        ov = prov.get("overlap")
        assert ov is not None and ov["enabled"]
        assert ov["eligible"] >= 1
        assert ov["executor_fused_edges"]  # PCG008-verified annotation
        audit = prov["plan_audit"]
        fused_rows = [
            e for e in audit["movement_edges"] if e.get("fused")
        ]
        assert fused_rows, "no movement edge measured as fused"
        assert audit["summary"]["num_fused_edges"] == len(fused_rows)
        # the store captured the standalone-measured reshards
        assert os.path.exists(store_path)
        data = json.load(open(store_path))
        assert data["schema"] == 3 and len(data["entries"]) >= 1
        # a second compile prefers the stored measurements (smoke: no error
        # and the store is read back non-empty)
        from flexflow_tpu.compiler.movement_store import MovementCostStore

        assert len(MovementCostStore(store_path)) == len(data["entries"])


# ---------------------------------------------------------------------------
# slow regression gate: fused >= 1.15x on the bandwidth-bound proxy
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_overlap_regression_bandwidth_bound_proxy():
    """The fused all-gather-matmul must beat the serial lowering by
    >=1.15x on the bandwidth-bound proxy (a fat row-sharded activation
    into a thin matmul: the serial path materializes the full gathered
    tensor per device, the ring streams chunks). FF_TPU_OVERLAP_BASELINE=1
    is the documented revert switch; the baseline here IS the fused=False
    plain-XLA path that switch falls back to (measured 3.2x on this host
    at capture time — the gate leaves wide headroom for slower CI)."""
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("d",))
    rs = np.random.RandomState(0)
    m, k, n = 8192, 2048, 8
    x = jax.device_put(
        jnp.asarray(rs.randn(m, k), jnp.float32),
        NamedSharding(mesh, P("d", None)),
    )
    w = jnp.asarray(rs.randn(k, n), jnp.float32)

    def bench(fused):
        fn = jax.jit(
            lambda x, w: all_gather_matmul(
                x, w, mesh, P("d", None), P(None, None), 0, fused=fused
            )
        )
        out = fn(x, w)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(3):
                out = fn(x, w)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / 3)
        return best

    fused_s = bench(True)
    serial_s = bench(False)
    speedup = serial_s / fused_s
    assert speedup >= 1.15, (
        f"fused {fused_s * 1e3:.1f} ms vs serial {serial_s * 1e3:.1f} ms "
        f"= {speedup:.2f}x < 1.15x"
    )
