"""Pipeline parallelism as a first-class PCG axis (ISSUE 13).

Covers every layer of the stage axis: StagePartition/StageMerge op
attrs + file-format round trip, the 1F1B schedule generator's invariants,
stage insertion/analysis (pcg/pipeline.py), the PCG009-PCG011 verifier
rules, bubble-aware DP pricing with exact python/native parity (ABI v9),
the 1F1B activation-stash memory model and its agreement with the search
pruner, budgeted-search-selects-pipelined end to end, the shard_map +
ppermute 1F1B executor's BITWISE parity against the sequential microbatch
reference (dropout on), the stage-op substitution rule's soundness audit,
and the FFModel e2e path including kill-and-resume from a checkpoint on a
pipelined plan.
"""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.analysis.diagnostics import has_errors
from flexflow_tpu.analysis.memory_analysis import analyze_memory, verify_memory
from flexflow_tpu.analysis.pcg_verify import PCG_RULE_CATALOG, verify_pcg
from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
    AnalyticTPUCostEstimator,
    make_default_allowed_machine_views,
    stage_transfer_cost_ms,
)
from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
    MachineMappingCache,
    MachineMappingContext,
    leaf_pipeline_factor,
)
from flexflow_tpu.compiler.unity_algorithm import (
    OptimizerConfig,
    enumerate_pipeline_seeds,
    evaluate_pcg,
    graph_optimize,
    pipeline_seed,
)
from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import StageMergeAttrs, StagePartitionAttrs
from flexflow_tpu.op_attrs.parallel_tensor_shape import lift_to_parallel
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.pcg.file_format import pcg_from_json, pcg_to_json
from flexflow_tpu.pcg.machine_view import MachineSpecification
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
from flexflow_tpu.pcg.parallel_computation_graph_builder import (
    ParallelComputationGraphBuilder,
)
from flexflow_tpu.pcg.pipeline import (
    analyze_pipeline,
    insert_pipeline_stages,
    one_f_one_b_schedule,
    pipeline_bubble_fraction,
    pipeline_contexts,
    pipeline_leaf_factor as plf,
    stage_inflight_bound,
)
from flexflow_tpu.op_attrs.ops.loss_functions import (
    SparseCategoricalCrossEntropyLossAttrs,
)
from flexflow_tpu.substitutions.rules import (
    generate_parallelization_rules,
    pipeline_stage_pair_rule,
)

SPEC8 = MachineSpecification(1, 1, 8, 1.0, 2.0)


def _estimator(spec=SPEC8):
    return AnalyticTPUCostEstimator(
        spec, peak_flops=5e10, hbm_gbps=10.0,
        ici_latency_ms=0.1, dcn_latency_ms=0.2, emulated_mesh=True,
    )


def _ctx(spec=SPEC8, budget=0.0):
    return MachineMappingContext(
        _estimator(spec), make_default_allowed_machine_views(),
        overlap_fraction=0.5, memory_budget_bytes=budget,
        optimizer_state_slots=2,
    )


def _chain_pcg(L=8, d=64, B=32, dropout=0.0):
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(
        lift_to_parallel(TensorShape((B, d), DataType.FLOAT)), name="x"
    )
    h = x
    for i in range(L):
        h = b.dense(h, d, activation=Activation.RELU, name=f"l{i}")
        if dropout > 0:
            from flexflow_tpu.op_attrs.ops import DropoutAttrs

            (h,) = b.add_layer(DropoutAttrs(dropout), [h], [], f"do{i}")
    return b.graph


def _logit(pcg):
    from flexflow_tpu.analysis.lowering import find_logit_tensor

    return find_logit_tensor(pcg)


def _seed_peaks(pcg, spec=SPEC8):
    """label -> (runtime, max per-device peak) over flat + pipeline seeds."""
    from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds

    ctx = _ctx(spec)
    out = {}
    for label, seed in list(enumerate_seeds(pcg, spec.num_devices)) + list(
        enumerate_pipeline_seeds(pcg, spec.num_devices)
    ):
        r = evaluate_pcg(seed, ctx, spec, MachineMappingCache())
        if r is None:
            continue
        mem = analyze_memory(seed, spec, r.machine_mapping)
        out[label] = (r.runtime, mem.max_peak_bytes())
    return out


# ---------------------------------------------------------------------------
# schedule + formulas
# ---------------------------------------------------------------------------


class TestSchedule:
    def test_shape_and_bubble(self):
        for S, M in [(2, 2), (2, 8), (4, 8), (3, 5), (8, 16)]:
            fwd, bwd = one_f_one_b_schedule(S, M)
            T = 2 * (M + S - 1)
            assert fwd.shape == bwd.shape == (T, S)
            # productive units per stage = 2M; the rest is the bubble
            busy = (fwd >= 0).sum() + (bwd >= 0).sum()
            assert busy == 2 * M * S
            assert pipeline_bubble_fraction(S, M) == pytest.approx(
                (T - 2 * M) / T
            )

    def test_leaf_factor_decomposition(self):
        # f = (1/S) * 1/(1 - bubble)
        for S, M in [(2, 4), (4, 8), (8, 16)]:
            b = pipeline_bubble_fraction(S, M)
            assert plf(S, M) == pytest.approx((1 / S) / (1 - b))
        assert plf(1, 1) == 1.0

    def test_inflight_bound_is_tight_for_stage0(self):
        fwd, bwd = one_f_one_b_schedule(4, 8)
        # generator asserts <= min(S-s, M) internally; stage 0 reaches it
        done_f = done_b = 0
        peak = 0
        for t in range(fwd.shape[0]):
            if fwd[t, 0] >= 0:
                done_f += 1
            if bwd[t, 0] >= 0:
                done_b += 1
            peak = max(peak, done_f - done_b)
        assert peak == stage_inflight_bound(4, 0, 8) == 4


# ---------------------------------------------------------------------------
# op attrs + structure
# ---------------------------------------------------------------------------


class TestStageOps:
    def test_shape_inference_identity(self):
        shape = lift_to_parallel(TensorShape((16, 32), DataType.FLOAT))
        assert StagePartitionAttrs(2, 4, 0).parallel_output_shape(shape) == shape
        assert StageMergeAttrs(2, 4).parallel_output_shape(shape) == shape
        ts = TensorShape((16, 32), DataType.FLOAT)
        assert StagePartitionAttrs(2, 4, 1).output_shape(ts) == ts

    def test_kernel_forward_identity(self):
        from flexflow_tpu.kernels import forward

        x = jnp.arange(8.0).reshape(2, 4)
        (y,) = forward(StagePartitionAttrs(2, 2, 0), [x])
        assert (y == x).all()
        (y,) = forward(StageMergeAttrs(2, 2), [x])
        assert (y == x).all()

    def test_not_a_parallel_op_but_a_stage_op(self):
        from flexflow_tpu.op_attrs.core import is_parallel_op, is_stage_op

        assert not is_parallel_op(StagePartitionAttrs(2, 2, 0))
        assert is_stage_op(StagePartitionAttrs(2, 2, 0))
        assert is_stage_op(StageMergeAttrs(2, 2))

    def test_builder_and_file_format_round_trip(self):
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(
            lift_to_parallel(TensorShape((8, 16), DataType.FLOAT)), name="x"
        )
        h = b.parallel_stage_partition(x, 2, 4, 0)
        h = b.dense(h, 16, name="a")
        h = b.parallel_stage_partition(h, 2, 4, 1)
        h = b.dense(h, 16, name="b")
        h = b.parallel_stage_merge(h, 2, 4)
        pcg2 = pcg_from_json(pcg_to_json(b.graph))
        region = analyze_pipeline(pcg2)
        assert region is not None and region.ok
        assert (region.num_stages, region.num_microbatches) == (2, 4)

    def test_normalization_preserves_stage_ops(self):
        """The reshard-chain canonicalizers must never erase a stage
        boundary (stage ops are layout-identity — exactly what net-effect
        chain collapse would eat if they counted as parallel ops)."""
        from flexflow_tpu.pcg.parallel_computation_graph import (
            canonicalize_parallel_chains,
            cse_parallel_ops,
            merge_parallel_chains,
        )

        p = insert_pipeline_stages(_chain_pcg(L=4), 2, 4)
        out = canonicalize_parallel_chains(
            merge_parallel_chains(cse_parallel_ops(p))
        )
        region = analyze_pipeline(out)
        assert region is not None and region.ok


class TestInsertAndAnalyze:
    def test_insert_and_contexts(self):
        p = insert_pipeline_stages(_chain_pcg(L=8), 4, 8)
        region = analyze_pipeline(p)
        assert region.ok and region.num_stages == 4
        ctx = pipeline_contexts(p)
        stages = {c.stage for c in ctx.values()}
        assert stages == {0, 1, 2, 3}
        # weights join their consuming stage
        from flexflow_tpu.op_attrs.ops import WeightAttrs

        for n, c in ctx.items():
            if isinstance(p.op_attrs(n), WeightAttrs):
                consumer_stages = {
                    ctx[u.node].stage
                    for o in p.outputs_of(n)
                    for u in p.uses_of(o)
                }
                assert consumer_stages == {c.stage}

    def test_indivisible_microbatches_rejected(self):
        with pytest.raises(ValueError):
            insert_pipeline_stages(_chain_pcg(L=8, B=32), 2, 3)

    def test_unbalanced_stage_count_rejected(self):
        with pytest.raises(ValueError):
            insert_pipeline_stages(_chain_pcg(L=8), 3, 4)

    def test_flat_pcg_has_no_contexts(self):
        assert pipeline_contexts(_chain_pcg(L=4)) == {}


# ---------------------------------------------------------------------------
# verifier rules (PCG009-PCG011)
# ---------------------------------------------------------------------------


class TestVerifierRules:
    def test_catalog_has_pipeline_rules(self):
        for rid in ("PCG009", "PCG010", "PCG011"):
            assert rid in PCG_RULE_CATALOG

    def _ids(self, diags):
        return {d.rule_id for d in diags}

    def test_pcg009_missing_interior_boundary(self):
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(
            lift_to_parallel(TensorShape((8, 16), DataType.FLOAT)), name="x"
        )
        h = b.parallel_stage_partition(x, 3, 4, 0)  # declares 3 stages
        h = b.dense(h, 16)
        h = b.parallel_stage_partition(h, 3, 4, 1)  # ... but no stage 2
        h = b.dense(h, 16)
        h = b.parallel_stage_merge(h, 3, 4)
        assert "PCG009" in self._ids(verify_pcg(b.graph, check_sp=False))

    def test_pcg009_inconsistent_stage_attrs(self):
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(
            lift_to_parallel(TensorShape((8, 16), DataType.FLOAT)), name="x"
        )
        h = b.parallel_stage_partition(x, 2, 4, 0)
        h = b.dense(h, 16)
        h = b.parallel_stage_partition(h, 2, 8, 1)  # M disagrees
        h = b.dense(h, 16)
        h = b.parallel_stage_merge(h, 2, 4)
        assert "PCG009" in self._ids(verify_pcg(b.graph, check_sp=False))

    def test_pcg010_microbatch_divisibility(self):
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(
            lift_to_parallel(TensorShape((10, 16), DataType.FLOAT)), name="x"
        )
        h = b.parallel_stage_partition(x, 2, 4, 0)  # 10 % 4 != 0
        h = b.dense(h, 16)
        h = b.parallel_stage_partition(h, 2, 4, 1)
        h = b.dense(h, 16)
        h = b.parallel_stage_merge(h, 2, 4)
        assert "PCG010" in self._ids(verify_pcg(b.graph, check_sp=False))

    def test_pcg011_stage_submesh_disjointness(self):
        # 4 stages x in-stage dp4 wants 16 devices; the 8-device machine
        # cannot give each stage a disjoint submesh
        p = pipeline_seed(_chain_pcg(L=8, B=64), 4, 8, inner_dp=4)
        diags = verify_pcg(p, machine_spec=SPEC8)
        assert "PCG011" in self._ids(diags)
        # the fitting variant is clean
        p_ok = pipeline_seed(_chain_pcg(L=8, B=64), 4, 8, inner_dp=2)
        assert "PCG011" not in self._ids(
            verify_pcg(p_ok, machine_spec=SPEC8)
        )

    def test_well_formed_pipelined_pcg_is_clean(self):
        p = insert_pipeline_stages(_chain_pcg(L=8), 2, 4)
        diags = verify_pcg(p, machine_spec=SPEC8)
        assert not has_errors(diags), [str(d) for d in diags]


# ---------------------------------------------------------------------------
# DP pricing: bubble factor, p2p edges, native parity (ABI v9)
# ---------------------------------------------------------------------------


class TestDPPricing:
    def test_stage_transfer_pricing(self):
        shape = lift_to_parallel(TensorShape((32, 64), DataType.FLOAT))
        interior = stage_transfer_cost_ms(
            StagePartitionAttrs(2, 4, 1), [shape], SPEC8, 0.1, 0.2
        )
        # 2*M*latency + 2*piece/bw = 2*4*0.1 + 2*32*64*4 / (2.0 GB/s)
        assert interior == pytest.approx(0.8 + 2 * 32 * 64 * 4 / 2e6)
        assert stage_transfer_cost_ms(
            StagePartitionAttrs(2, 4, 0), [shape], SPEC8, 0.1, 0.2
        ) == 0.0
        assert stage_transfer_cost_ms(
            StageMergeAttrs(2, 4), [shape], SPEC8, 0.1, 0.2
        ) == 0.0

    def test_leaf_factor_only_for_in_region_compute(self):
        p = insert_pipeline_stages(_chain_pcg(L=4), 2, 4)
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            _leaf_key,
        )
        from flexflow_tpu.op_attrs.core import is_stage_op
        from flexflow_tpu.op_attrs.ops import LinearAttrs

        ctxmap = pipeline_contexts(p)
        saw_linear = saw_stage = False
        for n in p.topological_ordering():
            leaf = _leaf_key(p, n, ctxmap)
            if isinstance(p.op_attrs(n), LinearAttrs):
                assert leaf_pipeline_factor(leaf) == pytest.approx(
                    plf(2, 4)
                )
                saw_linear = True
            if is_stage_op(p.op_attrs(n)):
                assert leaf_pipeline_factor(leaf) == 1.0
                saw_stage = True
        assert saw_linear and saw_stage

    def test_native_python_parity_on_pipelined_pcg(self, monkeypatch):
        p = pipeline_seed(_chain_pcg(L=8, B=32), 2, 4, inner_dp=4)
        for budget in (0.0, 4 * 2**20):
            ctx = _ctx(budget=budget)
            monkeypatch.setenv("FF_TPU_NO_NATIVE", "1")
            py = evaluate_pcg(p, ctx, SPEC8, MachineMappingCache())
            monkeypatch.delenv("FF_TPU_NO_NATIVE")
            nat = evaluate_pcg(p, ctx, SPEC8, MachineMappingCache())
            assert (py is None) == (nat is None)
            if py is not None:
                assert py.runtime == nat.runtime  # EXACT, not approx

    def test_pipelined_cost_reflects_bubble(self):
        """The same pipelined PCG priced at two microbatch counts under a
        zero-latency link: larger M => smaller bubble => cheaper plan
        (the p2p bandwidth term is M-independent, so the only difference
        left is the (M+S-1)/(M*S) leaf factor). With a real per-hop
        latency the M sweep is a genuine trade-off — that is the knob the
        search prices, not a monotone rule."""
        base = _chain_pcg(L=8, B=64)
        est = AnalyticTPUCostEstimator(
            SPEC8, peak_flops=5e10, hbm_gbps=10.0,
            ici_latency_ms=0.0, dcn_latency_ms=0.0, emulated_mesh=True,
        )
        ctx = MachineMappingContext(
            est, make_default_allowed_machine_views(), overlap_fraction=0.5
        )
        r_small = evaluate_pcg(
            insert_pipeline_stages(base, 4, 4), ctx, SPEC8,
            MachineMappingCache(),
        )
        r_big = evaluate_pcg(
            insert_pipeline_stages(base, 4, 16), ctx, SPEC8,
            MachineMappingCache(),
        )
        assert r_small is not None and r_big is not None
        assert r_big.runtime < r_small.runtime


# ---------------------------------------------------------------------------
# memory: 1F1B stash accounting + pruner/verifier agreement
# ---------------------------------------------------------------------------


class TestMemory:
    def test_leaf_stash_scaling_hand_computed(self):
        from flexflow_tpu.analysis.memory_accounting import (
            leaf_step_memory_bytes,
        )
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            _leaf_key,
        )
        from flexflow_tpu.op_attrs.ops import LinearAttrs

        flat = _chain_pcg(L=4, d=64, B=32)
        p = insert_pipeline_stages(flat, 2, 4)
        ctxmap = pipeline_contexts(p)
        # find one mid-chain Linear per graph and compare
        def linear_leaf(g, cmap):
            for n in g.topological_ordering():
                if isinstance(g.op_attrs(n), LinearAttrs):
                    return _leaf_key(g, n, cmap if cmap else {})
            raise AssertionError

        lf = linear_leaf(flat, {})
        lp = linear_leaf(p, ctxmap)
        flat_bytes = leaf_step_memory_bytes(lf, 2)
        pipe_bytes = leaf_step_memory_bytes(lp, 2)
        # hand computation: weights side unchanged; activations+outputs
        # x keep/M (stage 0 of S=2, M=4: keep=min(2,4)=2 -> x 2/4), the
        # activation/output grads x 1/M
        x = 32 * 64 * 4  # [B, d] f32
        w = 64 * 64 * 4 + 64 * 4  # kernel + bias
        weights_side = w * (2 + 2)  # w + grad + 2 Adam slots
        assert flat_bytes == weights_side + 2 * x + 2 * x
        assert pipe_bytes == weights_side + (2 * x) // 2 + (2 * x) // 4

    def test_stage_submesh_placement_cuts_per_device_peak(self):
        flat = _chain_pcg(L=8, d=128, B=32)
        p = insert_pipeline_stages(flat, 4, 8)
        flat_mem = analyze_memory(flat, SPEC8)
        pipe_mem = analyze_memory(p, SPEC8)
        # per-device weights drop ~4x (each device holds one stage's
        # parameters) and activations stash at the 1F1B bound
        assert pipe_mem.max_peak_bytes() < 0.5 * flat_mem.max_peak_bytes()

    def test_flat_infeasible_pipelined_feasible_at_budget(self):
        pcg = _chain_pcg(L=8, d=128, B=32)
        peaks = _seed_peaks(pcg)
        pipe = {k: v for k, v in peaks.items() if k.startswith("pp")}
        flat = {k: v for k, v in peaks.items() if not k.startswith("pp")}
        assert pipe and flat
        best_pipe = min(v[1] for v in pipe.values())
        best_flat = min(v[1] for v in flat.values())
        assert best_pipe < best_flat
        budget = (best_pipe + best_flat) / 2
        ctx = _ctx(budget=budget)
        # every flat seed (and serial) is infeasible at this budget...
        assert (
            evaluate_pcg(pcg, ctx, SPEC8, MachineMappingCache()) is None
        )
        # ...while the best pipelined seed survives, and the winner passes
        # the verifier at the SAME capacity (search/ffcheck agreement)
        rules = generate_parallelization_rules([2, 4, 8])
        res = graph_optimize(
            pcg, ctx, SPEC8, rules,
            OptimizerConfig(budget=1, pipeline_seeds=True),
        )
        region = analyze_pipeline(res.pcg)
        assert region is not None and region.ok
        assert res.serial_runtime is None  # flat serial was infeasible
        _, diags = verify_memory(
            res.pcg, SPEC8, res.machine_mapping, hbm_bytes=budget
        )
        assert not has_errors(diags)
        # and the flat graph is rejected by ffcheck --memory semantics
        flat_res = evaluate_pcg(
            pcg, _ctx(), SPEC8, MachineMappingCache()
        )
        _, flat_diags = verify_memory(
            pcg, SPEC8, flat_res.machine_mapping, hbm_bytes=budget
        )
        assert has_errors(flat_diags)


# ---------------------------------------------------------------------------
# the 1F1B executor
# ---------------------------------------------------------------------------


def _pipelined_instance(pcg, **kw):
    from flexflow_tpu.parallel.pipeline import PipelinedTrainingInstance

    return PipelinedTrainingInstance(
        pcg, _logit(pcg), SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-2), **kw
    )


def _train(inst, steps, B, d, seed=7):
    params, opt = inst.initialize(seed=0)
    rng = jax.random.PRNGKey(seed)
    rs = np.random.RandomState(seed)
    xv = jnp.asarray(rs.randn(B, d), jnp.float32)
    yv = jnp.asarray(rs.randint(0, d, (B,)), jnp.int32)
    losses = []
    for _ in range(steps):
        rng, srng = jax.random.split(rng)
        params, opt, loss, _ = inst.train_step(
            params, opt, {"x": xv}, yv, srng
        )
        losses.append(np.asarray(loss))
    return losses, params, opt


class TestExecutor1F1B:
    def test_bitwise_vs_sequential_reference_dropout_on(self, monkeypatch):
        """The tentpole numerics claim: the 1F1B schedule is bitwise the
        sequential microbatch reference — loss trajectory AND final
        params — with dropout active (the RNG stream position is
        load-bearing)."""
        p = insert_pipeline_stages(
            _chain_pcg(L=4, d=16, B=16, dropout=0.1), 2, 4
        )
        inst = _pipelined_instance(p)
        losses, params, opt = _train(inst, 4, 16, 16)
        monkeypatch.setenv("FF_TPU_PIPELINE_BASELINE", "1")
        ref = _pipelined_instance(p)
        ref_losses, ref_params, ref_opt = _train(ref, 4, 16, 16)
        monkeypatch.delenv("FF_TPU_PIPELINE_BASELINE")
        assert [float(a) for a in losses] == [float(a) for a in ref_losses]
        for key in params:
            assert np.array_equal(
                np.asarray(params[key]), np.asarray(ref_params[key])
            ), key
        for a, b in zip(
            jax.tree_util.tree_leaves(opt),
            jax.tree_util.tree_leaves(ref_opt),
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_allclose_vs_flat_gspmd_executor(self):
        """Stage ops are value-identity: the flat GSPMD executor on the
        SAME pipelined PCG converges to the same losses (allclose, not
        bitwise — microbatching reassociates the batch reduction)."""
        from flexflow_tpu.parallel.executor import (
            DistributedTrainingInstance,
        )
        from flexflow_tpu.parallel.mesh import MachineMesh

        p = insert_pipeline_stages(_chain_pcg(L=4, d=16, B=16), 2, 4)
        pipe = _pipelined_instance(p)
        lp, _, _ = _train(pipe, 3, 16, 16)
        flat = DistributedTrainingInstance(
            p, _logit(p), SparseCategoricalCrossEntropyLossAttrs(),
            AdamOptimizerAttrs(alpha=1e-2), MachineMesh.for_devices(8),
        )
        lf, _, _ = _train(flat, 3, 16, 16)
        np.testing.assert_allclose(
            np.asarray(lp), np.asarray(lf), rtol=2e-4, atol=2e-5
        )

    def test_in_stage_data_parallel_matches_dp1(self):
        """Within-stage batch sharding (the (stage, data) mesh's data
        axis) changes placement only: same losses as the S-devices-only
        run (allclose; reductions over shards reassociate)."""
        p8 = insert_pipeline_stages(_chain_pcg(L=4, d=16, B=16), 2, 4)
        dp4 = _pipelined_instance(p8)  # 8 devices -> (stage 2, data 4)
        l_dp4, _, _ = _train(dp4, 3, 16, 16)
        dp1 = _pipelined_instance(p8, devices=jax.devices()[:2])
        l_dp1, _, _ = _train(dp1, 3, 16, 16)
        np.testing.assert_allclose(
            np.asarray(l_dp4), np.asarray(l_dp1), rtol=2e-4, atol=2e-5
        )

    def test_training_reduces_loss(self):
        p = insert_pipeline_stages(_chain_pcg(L=4, d=32, B=32), 4, 8)
        inst = _pipelined_instance(p)
        losses, _, _ = _train(inst, 8, 32, 32)
        assert float(losses[-1]) < float(losses[0])

    def test_unsupported_structures_raise(self):
        from flexflow_tpu.parallel.pipeline import (
            PipelineUnsupported,
            extract_executable_pipeline,
        )

        # non-uniform stages: widths differ between the two stages
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(
            lift_to_parallel(TensorShape((8, 16), DataType.FLOAT)), name="x"
        )
        h = b.parallel_stage_partition(x, 2, 4, 0)
        h = b.dense(h, 32, name="wide")  # stage 0: 16 -> 32
        h = b.parallel_stage_partition(h, 2, 4, 1)
        h = b.dense(h, 16, name="narrow")  # stage 1: 32 -> 16
        h = b.parallel_stage_merge(h, 2, 4)
        with pytest.raises(PipelineUnsupported):
            extract_executable_pipeline(b.graph)

    def test_trace_spans_carry_pipeline_attrs(self, tmp_path):
        from flexflow_tpu.observability.trace import (
            TraceRecorder,
            set_recorder,
        )

        p = insert_pipeline_stages(_chain_pcg(L=4, d=16, B=16), 2, 4)
        inst = _pipelined_instance(p)
        rec = TraceRecorder()
        set_recorder(rec)
        try:
            _train(inst, 1, 16, 16)
        finally:
            set_recorder(None)
        spans = rec.spans_named("step")
        assert spans and spans[0].args["pipeline_stages"] == 2
        assert spans[0].args["pipeline_microbatches"] == 4


# ---------------------------------------------------------------------------
# search end to end + substitution rule audit
# ---------------------------------------------------------------------------


class TestSearchAndRules:
    def test_pipeline_seeds_enumerate(self):
        labels = [
            label
            for label, _ in enumerate_pipeline_seeds(
                _chain_pcg(L=8, B=64), 8
            )
        ]
        assert labels and all(l.startswith("pp") for l in labels)

    def test_flat_search_winners_unchanged_without_flag(self):
        """pipeline_seeds defaults OFF: a flat search must never see the
        stage candidates (pinned winners stay pinned)."""
        pcg = _chain_pcg(L=4, B=32)
        res = graph_optimize(
            pcg, _ctx(), SPEC8,
            generate_parallelization_rules([2]),
            OptimizerConfig(budget=1),
        )
        assert analyze_pipeline(res.pcg) is None
        assert not any(
            k.startswith("pp") for k in (res.seed_runtimes or {})
        )

    def test_pipeline_rule_audits_sound(self):
        from flexflow_tpu.analysis.rule_audit import audit_substitution

        for M in (2, 4):
            for use_bias in (False, True):
                audit = audit_substitution(
                    pipeline_stage_pair_rule(M, use_bias)
                )
                assert audit.status == "ok", (M, use_bias, audit.diagnostics)

    def test_pipeline_rule_applies_and_verifies(self):
        from flexflow_tpu.compiler.unity_algorithm import greedy_apply

        pcg = _chain_pcg(L=2, d=16, B=16)
        out = greedy_apply(
            pcg, [pipeline_stage_pair_rule(4, use_bias=True)], max_steps=4
        )
        region = analyze_pipeline(out)
        assert region is not None and region.ok
        assert (region.num_stages, region.num_microbatches) == (2, 4)
        assert not has_errors(verify_pcg(out, machine_spec=SPEC8))


# ---------------------------------------------------------------------------
# FFModel end to end: compile, fit, kill-and-resume (PR-7 path)
# ---------------------------------------------------------------------------

BATCH = 16
STEPS_PER_EPOCH = 8
N = BATCH * STEPS_PER_EPOCH
DIM = 16


def _ffdata(seed=0):
    rs = np.random.RandomState(seed)
    return (
        rs.randn(N, DIM).astype(np.float32),
        rs.randint(0, DIM, N),
    )


def _ffbuild(metrics_dir="", ckpt_dir="", every=0, dropout=True):
    from flexflow_tpu.core import FFConfig, FFModel

    cfg = FFConfig(
        batch_size=BATCH, seed=0, print_freq=0,
        search_budget=1, metrics_dir=metrics_dir,
        checkpoint_dir=ckpt_dir, checkpoint_every_n_steps=every,
        pipeline=True, force_strategy_seed="pp2m4xdp4",
    )
    m = FFModel(cfg)
    x = m.create_tensor([BATCH, DIM], name="x")
    h = x
    for i in range(4):
        h = m.dense(h, DIM, name=f"fc{i}")
        h = m.relu(h)
        if dropout:
            h = m.dropout(h, 0.1)
    m.compile(
        AdamOptimizerAttrs(alpha=1e-2),
        "sparse_categorical_crossentropy",
        logit_tensor=h,
    )
    return m


class TestFFModelPipeline:
    def test_compile_selects_1f1b_executor(self):
        from flexflow_tpu.parallel.pipeline import PipelinedTrainingInstance

        m = _ffbuild(dropout=False)
        assert isinstance(m.instance, PipelinedTrainingInstance)
        prov = m.search_provenance
        assert prov["pipeline"]["executor"] == "1f1b"
        assert prov["pipeline"]["num_stages"] == 2
        assert prov["pipeline"]["mesh"] == {"stage": 2, "data": 4}

    def test_fit_trains(self):
        m = _ffbuild(dropout=False)
        xv, yv = _ffdata()
        hist = m.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        losses = hist["loss"] if isinstance(hist, dict) else None
        # at minimum: fit completes and params are finite
        for v in jax.tree_util.tree_leaves(m.params):
            assert bool(jnp.isfinite(v).all())

    def test_fit_trajectory_is_train_step_by_step(self):
        """`train_step` is the whole contract of a backend towards `fit`:
        the loop's losses are, to the bit, those of the same instance's
        `train_step` driven by hand on the same batches with the loop's
        key stream (dropout on)."""
        from flexflow_tpu.observability.metrics import read_events

        xv, yv = _ffdata()
        d1 = tempfile.mkdtemp()
        m1 = _ffbuild(metrics_dir=d1)
        m1.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        fit_losses = [e["loss"] for e in read_events(d1) if "step" in e]
        assert len(fit_losses) == STEPS_PER_EPOCH

        m2 = _ffbuild(metrics_dir=tempfile.mkdtemp())
        rng = jax.random.fold_in(jax.random.PRNGKey(m2.config.seed), 0)
        params, opt = m2.params, m2.opt_state
        by_hand = []
        for batch, label in m2._make_iterator(xv, yv, BATCH, shuffle=False):
            rng, step_rng = jax.random.split(rng)
            params, opt, loss, _ = m2.instance.train_step(
                params, opt, batch, label, step_rng
            )
            by_hand.append(float(loss))
        assert by_hand == fit_losses
        for key in params:
            assert np.array_equal(
                np.asarray(params[key]), np.asarray(m1.params[key])
            ), key

    def test_kill_and_resume_bitwise(self, monkeypatch):
        """The PR-7 elastic contract on a PIPELINED plan: kill mid-epoch,
        resume from the step-8 snapshot, and the loss
        trajectory + final params + Adam moments are bitwise the
        uninterrupted run's (dropout on: the restored RNG position is
        load-bearing through the per-(stage, microbatch) fold chain)."""
        from flexflow_tpu.observability.metrics import read_events
        from flexflow_tpu.runtime.fault import SimulatedFault

        def losses_by_step(d):
            return {
                e["step"]: e["loss"]
                for e in read_events(d)
                if "step" in e
            }

        xv, yv = _ffdata()
        d1, c1 = tempfile.mkdtemp(), tempfile.mkdtemp()
        m1 = _ffbuild(metrics_dir=d1, ckpt_dir=c1, every=8)
        m1.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        ref = losses_by_step(d1)
        assert sorted(ref) == list(range(1, 2 * STEPS_PER_EPOCH + 1))

        d2, c2 = tempfile.mkdtemp(), tempfile.mkdtemp()
        m2 = _ffbuild(metrics_dir=d2, ckpt_dir=c2, every=8)
        monkeypatch.setenv("FF_TPU_FAULT_STEP", "10")
        with pytest.raises(SimulatedFault):
            m2.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        monkeypatch.delenv("FF_TPU_FAULT_STEP")

        m2b = _ffbuild(metrics_dir=d2, ckpt_dir=c2, every=8)
        m2b.fit(xv, yv, epochs=2, shuffle=True, verbose=False, resume=True)
        got = losses_by_step(d2)
        assert sorted(got) == sorted(ref)
        for s in ref:
            assert ref[s] == got[s], f"step {s}: {ref[s]} vs {got[s]}"
        for key in m1.params:
            assert np.array_equal(
                np.asarray(m1.params[key]), np.asarray(m2b.params[key])
            ), key
        for a, b in zip(
            jax.tree_util.tree_leaves(m1.opt_state),
            jax.tree_util.tree_leaves(m2b.opt_state),
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# regression gate (slow): the HBM-infeasible-flat case compiles and trains
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_pipeline_gate_budgeted_search_trains():
    """The CI gate (ISSUE 13 satellite): on the deep proxy under a binding
    memory budget the flat SPMD mapping is INFEASIBLE, the search selects
    a pipelined plan, and that plan compiles and trains (loss decreases)
    through the 1F1B executor — the same pattern as the overlap
    gates."""
    pcg = _chain_pcg(L=8, d=128, B=32)
    peaks = _seed_peaks(pcg)
    pipe_best = min(
        v[1] for k, v in peaks.items() if k.startswith("pp")
    )
    flat_best = min(
        v[1] for k, v in peaks.items() if not k.startswith("pp")
    )
    budget = (pipe_best + flat_best) / 2
    res = graph_optimize(
        pcg, _ctx(budget=budget), SPEC8,
        generate_parallelization_rules([2, 4, 8]),
        OptimizerConfig(budget=2, pipeline_seeds=True),
    )
    region = analyze_pipeline(res.pcg)
    assert region is not None and region.ok
    assert res.serial_runtime is None
    inst = _pipelined_instance(res.pcg)
    losses, _, _ = _train(inst, 8, 32, 128)
    assert float(losses[-1]) < float(losses[0])
