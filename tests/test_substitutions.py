"""Substitution engine tests.

Coverage model: reference lib/substitutions/test/src (9 files: pattern match,
shape inference, full substitution apply).
"""

import pytest

from flexflow_tpu.op_attrs import (
    OperatorType,
    ParallelTensorDims,
    ParallelTensorShape,
    ShardParallelDim,
    op_type_of,
)
from flexflow_tpu.op_attrs.ops import LinearAttrs
from flexflow_tpu.pcg import ParallelComputationGraphBuilder
from flexflow_tpu.pcg.parallel_computation_graph import pcg_from_computation_graph
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.substitutions import (
    OperatorAttributePattern,
    PCGPattern,
    Substitution,
    apply_substitution,
    data_parallel_linear_rule,
    find_pattern_matches,
    generate_parallelization_rules,
    head_parallel_attention_rule,
    is_valid_match_for_substitution,
    reduction_parallel_linear_rule,
    tensor_parallel_linear_rule,
    combine_reduction_cancel_rules,
)
from flexflow_tpu.substitutions.rules import data_parallel_attention_rule


def pts(dims, sum_degree=1, discard=1):
    sd = tuple(
        ShardParallelDim(*d) if isinstance(d, tuple) else ShardParallelDim(d, 1)
        for d in dims
    )
    return ParallelTensorShape(ParallelTensorDims(sd, sum_degree, discard))


def mlp_pcg():
    b = ComputationGraphBuilder()
    x = b.create_input([8, 16], name="x")
    h = b.dense(x, 32, use_bias=False, name="fc1")
    h = b.relu(h)
    h = b.dense(h, 8, use_bias=False, name="fc2")
    return pcg_from_computation_graph(b.graph)


class TestPatternMatching:
    def test_linear_pattern_matches_both_dense_layers(self):
        pcg = mlp_pcg()
        p = PCGPattern()
        a = p.add_input()
        w = p.add_input()
        p.add_operator(
            OperatorAttributePattern.for_op_type(OperatorType.LINEAR), [a, w]
        )
        matches = find_pattern_matches(p, pcg)
        assert len(matches) == 2

    def test_field_constraint_narrows(self):
        pcg = mlp_pcg()
        p = PCGPattern()
        a = p.add_input()
        w = p.add_input()
        p.add_operator(
            OperatorAttributePattern.for_op_type(OperatorType.LINEAR, out_channels=32),
            [a, w],
        )
        assert len(find_pattern_matches(p, pcg)) == 1

    def test_chain_pattern(self):
        pcg = mlp_pcg()
        p = PCGPattern()
        a = p.add_input()
        w = p.add_input()
        _, (h,) = p.add_operator(
            OperatorAttributePattern.for_op_type(OperatorType.LINEAR), [a, w]
        )
        p.add_operator(
            OperatorAttributePattern.for_op_type(OperatorType.ELEMENT_UNARY), [h]
        )
        matches = find_pattern_matches(p, pcg)
        assert len(matches) == 1  # only fc1 feeds a relu


class TestApplySubstitution:
    def test_data_parallel_linear(self):
        pcg = mlp_pcg()
        rule = data_parallel_linear_rule(4)
        matches = find_pattern_matches(rule.pattern, pcg)
        assert len(matches) == 2
        m = matches[0]
        assert is_valid_match_for_substitution(pcg, rule, m)
        new_pcg = apply_substitution(pcg, rule, m)
        ops = [op_type_of(new_pcg.op_attrs(n)) for n in new_pcg.topological_ordering()]
        assert OperatorType.REPARTITION in ops
        assert OperatorType.REPLICATE in ops
        assert OperatorType.COMBINE in ops
        # graph grew by 3 (repartition+replicate+combine), same linears
        assert len(new_pcg) == len(pcg) + 3
        # external interface unchanged: all non-parallel tensors still degree-1
        for n in new_pcg.topological_ordering():
            if op_type_of(new_pcg.op_attrs(n)) == OperatorType.LINEAR:
                out = new_pcg.outputs_of(n)[0]
                pass  # shapes checked below

    def test_tensor_parallel_linear_shapes(self):
        pcg = mlp_pcg()
        rule = tensor_parallel_linear_rule(2)
        m = find_pattern_matches(rule.pattern, pcg)[0]
        new_pcg = apply_substitution(pcg, rule, m)
        # the rewritten linear's output is sharded 2-way on out_channels
        linears = [
            n
            for n in new_pcg.topological_ordering()
            if op_type_of(new_pcg.op_attrs(n)) == OperatorType.LINEAR
        ]
        sharded = [
            new_pcg.tensor_shape(new_pcg.outputs_of(n)[0]).shard_degrees()
            for n in linears
        ]
        assert (1, 2) in sharded

    def test_reduction_parallel_linear_sum_degree(self):
        pcg = mlp_pcg()
        rule = reduction_parallel_linear_rule(2)
        m = find_pattern_matches(rule.pattern, pcg)[0]
        new_pcg = apply_substitution(pcg, rule, m)
        sum_degrees = {
            new_pcg.tensor_shape(o).sum_degree
            for n in new_pcg.topological_ordering()
            for o in new_pcg.outputs_of(n)
        }
        assert 2 in sum_degrees  # partial sums exist pre-Reduction

    def test_cancel_rule_roundtrip(self):
        """DP rule then cancellation on the introduced pair shrinks graph."""
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(pts([8, 16]))
        xp = b.parallel_partition(x, 0, 4)
        xc = b.parallel_combine(xp, 0, 4)
        y = b.relu(xc)
        pcg = b.graph
        cancel = combine_reduction_cancel_rules(4, 0)[1]  # repartition->combine
        matches = find_pattern_matches(cancel.pattern, pcg)
        assert len(matches) == 1
        new_pcg = apply_substitution(pcg, cancel, matches[0])
        ops = [op_type_of(new_pcg.op_attrs(n)) for n in new_pcg.topological_ordering()]
        assert OperatorType.REPARTITION not in ops
        assert OperatorType.COMBINE not in ops

    def test_invalid_match_rejected(self):
        """A rule whose interface drops a used output must be rejected."""
        pcg = mlp_pcg()
        rule = data_parallel_linear_rule(4)
        m = find_pattern_matches(rule.pattern, pcg)[0]
        # break the rule: remove the output mapping
        broken = Substitution(
            rule.name, rule.pattern, rule.output_expr, rule.input_mapping, ()
        )
        assert not is_valid_match_for_substitution(pcg, broken, m)

    def test_head_parallel_attention(self):
        b = ComputationGraphBuilder()
        x = b.create_input([2, 16, 32], name="x")
        h = b.multihead_attention(x, x, x, 32, 4, name="attn")
        pcg = pcg_from_computation_graph(b.graph)
        rule = head_parallel_attention_rule(2)
        matches = find_pattern_matches(rule.pattern, pcg)
        assert len(matches) == 1
        new_pcg = apply_substitution(pcg, rule, matches[0])
        ops = [op_type_of(new_pcg.op_attrs(n)) for n in new_pcg.topological_ordering()]
        assert ops.count(OperatorType.REPLICATE) == 3
        assert OperatorType.REDUCTION in ops

    @pytest.mark.parametrize("bias", [False, True])
    def test_data_parallel_attention(self, bias):
        """Each variant matches its own op only, and the biased one
        replicates the two biases beside the weight."""
        b = ComputationGraphBuilder()
        x = b.create_input([8, 16, 32], name="x")
        b.multihead_attention(x, x, x, 32, 4, bias=bias, name="attn")
        pcg = pcg_from_computation_graph(b.graph)
        other = data_parallel_attention_rule(4, bias=not bias)
        assert find_pattern_matches(other.pattern, pcg) == []
        rule = data_parallel_attention_rule(4, bias=bias)
        assert rule.name == (
            "data_parallel_attention_b_4" if bias else "data_parallel_attention_4"
        )
        (m,) = find_pattern_matches(rule.pattern, pcg)
        assert is_valid_match_for_substitution(pcg, rule, m)
        new_pcg = apply_substitution(pcg, rule, m)
        (mha,) = [
            n for n in new_pcg.topological_ordering()
            if op_type_of(new_pcg.op_attrs(n)) == OperatorType.MULTIHEAD_ATTENTION
        ]
        ins = new_pcg.inputs_of(mha)
        assert len(ins) == (6 if bias else 4)
        for v in ins[:3]:
            assert new_pcg.tensor_shape(v).shard_degrees() == (4, 1, 1)
        for v in ins[3:]:
            assert op_type_of(new_pcg.op_attrs(v.node)) == OperatorType.REPLICATE
            assert new_pcg.tensor_shape(v).discard_copy_degree == 4
        (out,) = new_pcg.outputs_of(mha)
        assert new_pcg.tensor_shape(out).shard_degrees() == (4, 1, 1)

    def test_biased_attention_has_no_head_parallel_move(self):
        b = ComputationGraphBuilder()
        x = b.create_input([8, 16, 32], name="x")
        b.multihead_attention(x, x, x, 32, 4, bias=True, name="attn")
        pcg = pcg_from_computation_graph(b.graph)
        rule = head_parallel_attention_rule(2)
        assert find_pattern_matches(rule.pattern, pcg) == []

    def test_generated_rule_set_has_both_attention_variants(self):
        names = [r.name for r in generate_parallelization_rules([2, 4])]
        for k in (2, 4):
            assert names.count(f"data_parallel_attention_{k}") == 1
            assert names.count(f"data_parallel_attention_b_{k}") == 1

    def test_generated_rule_set_nonempty_and_applicable(self):
        pcg = mlp_pcg()
        rules = generate_parallelization_rules([2, 4])
        assert len(rules) > 10
        applicable = 0
        for r in rules:
            for m in find_pattern_matches(r.pattern, pcg):
                if is_valid_match_for_substitution(pcg, r, m):
                    applicable += 1
        assert applicable >= 6  # 3 linear rules x 2 degrees x 2 layers min
