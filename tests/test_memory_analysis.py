"""Static memory-safety analysis tests (ISSUE 10).

Covers: the shared accounting module (hand-computed Linear / attention /
input-batch footprints), the liveness-based
per-device timeline, negative paths pinning every MEM00x rule id, the DP
memory pruner (python + native exact parity, and search/verify agreement:
a budgeted search never selects a plan `ffcheck --memory` rejects), the
`ffcheck --memory --json` schema + exit codes, and the compile-time
provenance/XLA cross-check.
"""

import json
import os
import subprocess
import sys

import pytest

from flexflow_tpu.analysis import (
    PCG_RULE_CATALOG,
    analyze_memory,
    errors_of,
    estimate_memory,
    leaf_step_memory_bytes,
    verify_memory,
)
from flexflow_tpu.op_attrs.ops import (
    CombineAttrs,
    InputAttrs,
    LinearAttrs,
    MultiHeadAttentionAttrs,
    RepartitionAttrs,
    WeightAttrs,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.machine_view import MachineSpecification
from flexflow_tpu.pcg.parallel_computation_graph import (
    pcg_from_computation_graph,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FFCHECK = os.path.join(REPO, "tools", "ffcheck.py")

SPEC8 = MachineSpecification(1, 1, 8, 1.0, 2.0)


def _mlp_pcg(width=1024, batch=64):
    b = ComputationGraphBuilder()
    x = b.create_input([batch, width], name="x")
    h = b.dense(x, width, use_bias=False, name="fc1")
    h = b.relu(h)
    b.dense(h, width, use_bias=False, name="fc2")
    return pcg_from_computation_graph(b.graph)


def rule_ids(diags):
    return {d.rule_id for d in diags}


# ---------------------------------------------------------------------------
# shared accounting module (the satellite: one implementation for the
# estimator, the DP pruner, and the verifier)
# ---------------------------------------------------------------------------


class TestAccounting:
    def test_linear_hand_computed(self):
        # Linear [4,8] x [8,16] -> [4,16], f32, Adam (2 slots):
        #   inputs  4*8*4  = 128 B * 2 (act + grad)
        #   weight  8*16*4 = 512 B * 4 (w + grad + m + v)
        #   output  4*16*4 = 256 B * 2 (out + grad)
        m = estimate_memory(
            LinearAttrs(out_channels=16, use_bias=False),
            [TensorShape((4, 8))],
            [TensorShape((8, 16))],
            [TensorShape((4, 16))],
            optimizer_state_slots=2,
        )
        assert m.activations == 128 and m.activation_grads == 128
        assert m.weights == 512 and m.weight_grads == 512
        assert m.optimizer_state == 1024
        assert m.outputs == 256 and m.output_grads == 256
        assert m.total == 128 * 2 + 512 * 4 + 256 * 2

    def test_attention_hand_computed(self):
        # MHA embed=32 heads=4 on [8,16,32] f32: packed weight [1024,4]
        #   q/k/v inputs 3 * 8*16*32*4 = 49152 B * 2
        #   weight 1024*4*4 = 16384 B * 4 (Adam)
        #   output 8*16*32*4 = 16384 B * 2
        from flexflow_tpu.op_attrs.core import (
            get_output_shapes,
            get_weight_shapes,
        )

        attrs = MultiHeadAttentionAttrs(embed_dim=32, num_heads=4)
        ins = [TensorShape((8, 16, 32))] * 3
        m = estimate_memory(
            attrs,
            ins,
            get_weight_shapes(attrs, ins),
            get_output_shapes(attrs, ins),
            optimizer_state_slots=2,
        )
        assert m.total == 49152 * 2 + 16384 * 4 + 16384 * 2

    def test_input_layer_charges_one_batch(self):
        # an input is its batch once: no gradient, no optimizer slot
        m = estimate_memory(InputAttrs(TensorShape((4, 8))), [])
        assert m.input_batch == m.total == 4 * 8 * 4

    def test_sharded_input_leaf_charges_piece_bytes(self):
        """A batch-sharded input's residency is the per-device
        PIECE: the estimator agrees with the DP pruner and the verifier
        (the output's parallel shape carries the degree)."""
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            UnmappedOpCostEstimateKey,
        )
        from flexflow_tpu.kernels.profiling import ProfilingSettings
        from flexflow_tpu.local_execution.cost_estimator import (
            LocalCostEstimator,
        )
        from test_static_analysis import pts

        attrs = InputAttrs(TensorShape((64, 32)))
        sharded_out = pts([64, 32], [8, 1])
        est = LocalCostEstimator(
            ProfilingSettings(warmup_iters=1, measure_iters=2)
        )
        got = est.estimate_operator_cost_parallel(
            attrs, [], [sharded_out]
        ).mem_bytes
        piece = 64 * 32 * 4 // 8
        assert got == piece
        leaf = UnmappedOpCostEstimateKey(attrs, (), (sharded_out,), ())
        assert leaf_step_memory_bytes(leaf, 2) == got

    def test_local_cost_estimator_reads_shared_module(self):
        """The estimator's mem model is the shared implementation: the
        input's batch shows up in CostDetails.mem_bytes too."""
        from flexflow_tpu.kernels.profiling import ProfilingSettings
        from flexflow_tpu.local_execution.cost_estimator import (
            LocalCostEstimator,
        )

        settings = ProfilingSettings(warmup_iters=1, measure_iters=2)
        attrs = InputAttrs(TensorShape((4, 8)))
        est = LocalCostEstimator(settings)
        assert est.estimate_operator_cost(attrs, []).mem_bytes == 128

    def test_leaf_memory_parallel_op_staging(self):
        """A Combine back to degree 1 charges src piece + FULL dst piece:
        the collective materializes the whole tensor per device."""
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            UnmappedOpCostEstimateKey,
        )
        from flexflow_tpu.op_attrs.core import get_parallel_output_shapes
        from test_static_analysis import pts

        sharded = pts([64, 1024], [8, 1])
        attrs = CombineAttrs(0, 8)
        (out,) = get_parallel_output_shapes(attrs, [sharded])
        leaf = UnmappedOpCostEstimateKey(attrs, (sharded,), (out,), (False,))
        need = leaf_step_memory_bytes(leaf, 2)
        piece = 64 * 1024 * 4 // 8
        assert need == piece + 64 * 1024 * 4  # src piece + full gather

    def test_weight_storage_charged_at_consumer_not_weight_layer(self):
        """Parameters are stored in the sharded form the consumer reads
        (executor initialize() places them post-reshard), so the Weight
        layer and its reshard chain charge zero and the consuming leaf's
        weight slots carry value + grad + optimizer slots."""
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            UnmappedOpCostEstimateKey,
        )
        from test_static_analysis import pts

        shape = pts([1024, 1024])
        w_leaf = UnmappedOpCostEstimateKey(
            WeightAttrs(TensorShape((1024, 1024))), (), (shape,), ()
        )
        assert leaf_step_memory_bytes(w_leaf, 2) == 0
        reshard = UnmappedOpCostEstimateKey(
            RepartitionAttrs(0, 8), (shape,),
            (pts([1024, 1024], [8, 1]),), (True,),
        )
        assert leaf_step_memory_bytes(reshard, 2) == 0
        # the consumer: x [64,1024] @ W [1024,1024] with the weight slot
        # sharded 8-way — weight piece 512 KiB x 4 (Adam) + activations
        x = pts([64, 1024])
        w_sharded = pts([1024, 1024], [8, 1])
        out = pts([64, 1024])
        linear = UnmappedOpCostEstimateKey(
            LinearAttrs(out_channels=1024, use_bias=False),
            (x, w_sharded), (out,), (False, True),
        )
        w_piece = 1024 * 1024 * 4 // 8
        act = 64 * 1024 * 4
        assert (
            leaf_step_memory_bytes(linear, 2)
            == 2 * act + 4 * w_piece + 2 * act
        )


# ---------------------------------------------------------------------------
# liveness analysis
# ---------------------------------------------------------------------------


class TestLivenessAnalysis:
    def test_peak_exceeds_resident_and_lands_in_backward(self):
        pcg = _mlp_pcg(width=256, batch=64)
        ana = analyze_memory(pcg, SPEC8)
        for d in ana.per_device.values():
            assert d.peak_bytes > d.resident_bytes > 0
            # deepest liveness is during the backward half of the step
            assert d.peak_tick >= ana.num_ticks // 2
            assert ana.tick_labels[d.peak_tick].startswith("bwd")

    def test_resident_matches_param_accounting(self):
        # 2 weights of 256x256 f32: params + grads whole, the 2 slots at
        # their update shard (the executor cuts a slot over every axis its
        # weight is replicated on: all 8 devices here), plus the one
        # batch — nothing else is whole-step resident
        pcg = _mlp_pcg(width=256, batch=64)
        ana = analyze_memory(pcg, SPEC8, optimizer_state_slots=2)
        w = 2 * 256 * 256 * 4
        batch = 64 * 256 * 4
        assert all(
            d.resident_bytes == 2 * w + 2 * w // 8 + batch
            for d in ana.per_device.values()
        )
        # without a machine the mesh is not known: a slot is cut by its
        # weight's own replica degree alone (1 here), the old accounting
        (alone,) = analyze_memory(
            pcg, None, optimizer_state_slots=2
        ).per_device.values()
        assert alone.resident_bytes == 4 * w + batch

    def test_an_input_is_resident_as_its_one_batch(self):
        """Every device's breakdown books the input's piece once under
        `input_batch`, whole-step resident."""
        from flexflow_tpu.analysis.memory_analysis import CATEGORIES

        assert "input_batch" in CATEGORIES
        pcg = _mlp_pcg(width=256, batch=64)
        ana = analyze_memory(pcg, SPEC8)
        assert all(
            d.peak_breakdown["input_batch"] == 64 * 256 * 4
            for d in ana.per_device.values()
        )

    def test_sharded_plan_cuts_per_device_bytes(self):
        from flexflow_tpu.compiler.unity_algorithm import (
            data_parallel_seed,
            tensor_parallel_seed,
        )

        pcg = _mlp_pcg()
        serial = analyze_memory(pcg, SPEC8).max_peak_bytes()
        tp8 = analyze_memory(
            tensor_parallel_seed(pcg, 8), SPEC8
        ).max_peak_bytes()
        dp8 = analyze_memory(
            data_parallel_seed(pcg, 8), SPEC8
        ).max_peak_bytes()
        # tp shards the weights (the dominant term here); dp does not
        assert tp8 < serial
        assert tp8 < dp8

    def test_mapping_restricts_devices(self):
        from test_static_analysis import _branch_mapping, _branch_pcg

        g = _branch_pcg()
        mapping = _branch_mapping(g)  # branch a on {0,1}, b on {2,3}
        spec4 = MachineSpecification(1, 1, 4, 25.0, 400.0)
        ana = analyze_memory(g, spec4, mapping)
        # all four devices hold something, and the branch devices carry
        # more than nothing (the shared input/add sits on device 0)
        assert ana.per_device[0].peak_bytes > 0
        assert ana.per_device[2].peak_bytes > 0


# ---------------------------------------------------------------------------
# MEM001-MEM003 negative paths (each id pinned on a seeded fixture)
# ---------------------------------------------------------------------------


class TestMemoryRules:
    def test_mem001_aggregate_over_capacity(self):
        pcg = _mlp_pcg(width=512, batch=64)
        ana = analyze_memory(pcg, SPEC8)
        worst_leaf = max(
            leaf_step_memory_bytes(_leaf, 2)
            for _leaf in _leaves(pcg)
        )
        # capacity above every single leaf but below the aggregate peak:
        # only the liveness analysis can reject this plan
        cap = (worst_leaf + ana.max_peak_bytes()) / 2
        assert worst_leaf < cap < ana.max_peak_bytes()
        _, diags = verify_memory(pcg, SPEC8, hbm_bytes=cap)
        ids = rule_ids(errors_of(diags))
        assert "MEM001" in ids
        assert "MEM002" not in ids

    def test_mem002_single_piece_too_large(self):
        pcg = _mlp_pcg(width=512, batch=64)
        _, diags = verify_memory(pcg, SPEC8, hbm_bytes=64 * 1024)
        assert "MEM002" in rule_ids(errors_of(diags))

    def test_mem003_unsharded_optimizer_warning(self):
        pcg = _mlp_pcg(width=512, batch=64)
        ana = analyze_memory(pcg, SPEC8, optimizer_state_slots=2)
        opt = max(
            d.peak_breakdown.get("opt_state", 0)
            for d in ana.per_device.values()
        )
        _, diags = verify_memory(
            pcg, SPEC8, hbm_bytes=opt * 1.5, optimizer_state_slots=2
        )
        assert "MEM003" in rule_ids(diags)  # warning severity
        assert "MEM003" not in rule_ids(errors_of(diags))

    def test_clean_at_generous_capacity(self):
        _, diags = verify_memory(_mlp_pcg(), SPEC8, hbm_bytes=float(2**40))
        assert diags == []

    def test_no_capacity_no_rules(self):
        ana, diags = verify_memory(_mlp_pcg(), SPEC8, hbm_bytes=None)
        assert diags == [] and ana.max_peak_bytes() > 0

    def test_catalog_covers_memory_rules(self):
        from flexflow_tpu.analysis.memory_analysis import MEMORY_RULE_IDS

        assert MEMORY_RULE_IDS == ("MEM001", "MEM002", "MEM003", "MEM005")
        for rid in MEMORY_RULE_IDS:
            assert rid in PCG_RULE_CATALOG


def _leaves(pcg):
    from flexflow_tpu.compiler.machine_mapping.problem_tree import _leaf_key

    return [_leaf_key(pcg, n) for n in pcg.nodes]


# ---------------------------------------------------------------------------
# DP pruner: python/native parity + search/verify agreement
# ---------------------------------------------------------------------------


def _context(budget=0.0):
    from flexflow_tpu.compiler import (
        AnalyticTPUCostEstimator,
        MachineMappingContext,
        make_default_allowed_machine_views,
    )

    return MachineMappingContext(
        AnalyticTPUCostEstimator(SPEC8, peak_flops=5e10, hbm_gbps=10.0),
        make_default_allowed_machine_views(),
        overlap_fraction=0.5,
        memory_budget_bytes=budget,
    )


class TestDPMemoryPruner:
    def test_leaf_prune_python(self):
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingCache,
            get_optimal_machine_mapping_python,
        )
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            get_machine_mapping_problem_tree,
        )

        pcg = _mlp_pcg()
        tree, _ = get_machine_mapping_problem_tree(pcg)
        feasible = get_optimal_machine_mapping_python(
            MachineMappingCache(), _context(0.0), tree, SPEC8
        )
        assert feasible is not None
        # serial fc weights need 1024*1024*4 * 4 = 16 MiB resident: a
        # 4 MiB budget makes the serial plan statically infeasible
        pruned = get_optimal_machine_mapping_python(
            MachineMappingCache(), _context(4 * 2**20), tree, SPEC8
        )
        assert pruned is None

    def test_native_python_parity_with_budget(self):
        """PR-2/6-style exact parity sweep, now with the memory pruner
        armed at several budgets: identical feasibility verdicts and
        bitwise-identical winning costs across every seed template."""
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingCache,
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

        pcg = _mlp_pcg()
        budgets = [0.0, 1 * 2**20, 8 * 2**20, 64 * 2**20]
        outcomes = {}
        for budget in budgets:
            ctx = _context(budget)
            feas = 0
            for label, s in [("serial", pcg)] + list(
                enumerate_seeds(pcg, 8)
            ):
                try:
                    tree, _ = get_machine_mapping_problem_tree(s)
                except ValueError:
                    continue
                nat = try_native_dp(MachineMappingCache(), ctx, tree, SPEC8)
                assert nat is not NATIVE_MISS
                py = get_optimal_machine_mapping_python(
                    MachineMappingCache(), ctx, tree, SPEC8
                )
                assert (nat is None) == (py is None), (label, budget)
                if nat is not None:
                    assert nat.runtime == py.runtime, (label, budget)
                    feas += 1
            outcomes[budget] = feas
        # the budgets actually discriminate: everything feasible
        # unbudgeted, nothing at 1 MiB, a strict subset (the weight-
        # sharded plans) at 8 MiB
        assert outcomes[0.0] > outcomes[8 * 2**20] > outcomes[1 * 2**20] == 0
        assert outcomes[64 * 2**20] == outcomes[0.0]

    def test_search_never_selects_rejected_plan(self):
        """Search/verify agreement (acceptance criterion): a budgeted
        graph_optimize winner always passes `ffcheck --memory` at the
        same capacity — and the budget is load-bearing (the serial plan
        and the dp8 seed are rejected by the verifier at it)."""
        from flexflow_tpu.compiler import OptimizerConfig, graph_optimize
        from flexflow_tpu.compiler.unity_algorithm import (
            data_parallel_seed,
            evaluate_pcg,
        )
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingCache,
        )
        from flexflow_tpu.substitutions import generate_parallelization_rules

        budget = 8 * 2**20
        pcg = _mlp_pcg()
        # the constraint bites: serial is infeasible under the budget...
        assert (
            evaluate_pcg(pcg, _context(budget), SPEC8, MachineMappingCache())
            is None
        )
        # ...and the dp8 seed (replicated weights) fails the verifier
        _, dp_diags = verify_memory(
            data_parallel_seed(pcg, 8), SPEC8, hbm_bytes=budget
        )
        assert errors_of(dp_diags)
        result = graph_optimize(
            pcg,
            _context(budget),
            SPEC8,
            generate_parallelization_rules([2, 4, 8]),
            OptimizerConfig(alpha=1.3, budget=3),
        )
        _, diags = verify_memory(
            result.pcg,
            SPEC8,
            mapping=result.machine_mapping,
            hbm_bytes=budget,
        )
        assert not errors_of(diags), [d.message for d in errors_of(diags)]
        # serial was memory-infeasible: serial_ms records None, never a
        # bare inf that would poison provenance JSON
        assert result.serial_runtime is None

    def test_a_graph_that_is_not_series_parallel_is_priced_under_a_budget(self):
        """A graph with an interior N-shape was refused before PR 62 (and the
        refusal had to be kept apart from a memory diagnosis); it is priced
        on a levelled tree now, under a generous budget like any other."""
        from flexflow_tpu.compiler import OptimizerConfig, graph_optimize
        from flexflow_tpu.substitutions import generate_parallelization_rules
        from test_static_analysis import bad_pcg007_non_sp

        result = graph_optimize(
            bad_pcg007_non_sp(),
            _context(budget=float(2**40)),
            SPEC8,
            generate_parallelization_rules([2]),
            OptimizerConfig(alpha=1.3, budget=2),
        )
        assert result.runtime > 0 and result.machine_mapping


# ---------------------------------------------------------------------------
# ffcheck --memory CLI (schema + exit-code contract)
# ---------------------------------------------------------------------------


def _write_graph(tmp_path, name, pcg):
    from flexflow_tpu.pcg.file_format import pcg_to_json

    p = tmp_path / name
    p.write_text(pcg_to_json(pcg))
    return str(p)


@pytest.mark.filterwarnings("ignore")
def test_ffcheck_memory_cli(tmp_path):
    """--memory: exit 1 + structured MEM diagnostics + one JSON summary
    object per file on an over-capacity graph; exit 0 and a clean summary
    at a generous capacity."""
    path = _write_graph(tmp_path, "big.json", _mlp_pcg())
    proc = subprocess.run(
        [
            sys.executable, FFCHECK, "--memory", "--json",
            "--hbm-gb", "0.005", "--devices-per-node", "8", path,
        ],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
    diag_ids = {d["rule_id"] for d in lines if "rule_id" in d}
    assert {"MEM001", "MEM002"} <= diag_ids
    summaries = [d for d in lines if "memory" in d]
    assert len(summaries) == 1
    s = summaries[0]
    assert s["memory"] == 1  # schema version
    assert s["path"] == path
    assert len(s["devices"]) == 8
    assert all(
        {"device", "peak_bytes", "resident_bytes", "over_capacity",
         "peak_breakdown", "peak_at"} <= set(d)
        for d in s["devices"]
    )
    assert all(d["over_capacity"] for d in s["devices"])

    proc0 = subprocess.run(
        [
            sys.executable, FFCHECK, "--memory", "--json",
            "--hbm-gb", "64", "--devices-per-node", "8", path,
        ],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc0.returncode == 0, proc0.stdout + proc0.stderr
    lines0 = [json.loads(l) for l in proc0.stdout.splitlines() if l]
    assert not any("rule_id" in d for d in lines0)
    (s0,) = [d for d in lines0 if "memory" in d]
    assert not any(d["over_capacity"] for d in s0["devices"])


def test_ffcheck_memory_text_table(tmp_path):
    """Non-JSON mode prints the per-device timeline table."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import ffcheck

        path = _write_graph(tmp_path, "g.json", _mlp_pcg(width=256))
        import contextlib
        import io

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = ffcheck.main(
                ["--memory", "--hbm-gb", "64",
                 "--devices-per-node", "8", path]
            )
        out = buf.getvalue()
        assert rc == 0
        assert "memory timeline" in out
        assert "peak" in out and "bwd" in out
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# compile-time wiring: provenance + XLA cross-check
# ---------------------------------------------------------------------------


@pytest.mark.filterwarnings("ignore")
def test_compile_records_memory_provenance_and_xla_cross_check():
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    cfg = FFConfig(
        batch_size=16, search_budget=1, plan_audit=True, hbm_gb=1.0
    )
    m = FFModel(cfg)
    x = m.create_tensor([16, 64], name="x")
    h = m.dense(x, 64, use_bias=False, name="fc1")
    h = m.relu(h)
    m.dense(h, 8, use_bias=False, name="fc2")
    m.compile(SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy")
    prov = m.search_provenance or {}
    mem = prov.get("memory")
    assert mem is not None, prov.keys()
    peaks = mem["predicted_peak_bytes_per_device"]
    assert peaks and any(v > 0 for v in peaks.values())
    assert mem["capacity_bytes"] == 2**30
    # the winner fits: no MEM errors in the verify summary
    assert prov["verify"]["clean"] is True
    # --plan-audit cross-check: XLA's compiled per-device accounting and
    # the predicted/measured geomean landed beside the prediction
    assert "xla_error" not in mem, mem.get("xla_error")
    assert mem["xla"]["argument_bytes"] > 0
    assert mem["xla_per_device_bytes"] > 0
    assert mem["predicted_over_xla_geomean"] is not None


def test_compile_rejects_impossible_budget():
    """A budget nothing fits in: the search raises (initial PCG
    infeasible) instead of silently searching toward an OOM plan."""
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    cfg = FFConfig(batch_size=16, search_budget=1, hbm_gb=0.00001)
    m = FFModel(cfg)
    x = m.create_tensor([16, 64], name="x")
    m.dense(x, 64, use_bias=False, name="fc")
    with pytest.raises(ValueError, match="no feasible machine mapping"):
        m.compile(SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy")
