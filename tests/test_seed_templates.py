"""Direct strategy-template constructor tests (compiler/seed_templates.py):
the O(n) seed builders must produce the same class of PCGs the rule-based
construction did — sandwiches on eligible ops, serial fallback on
ineligible ones, cancelled seams."""

import importlib.util
import json
import os

import numpy as np
import pytest

from flexflow_tpu.compiler.unity_algorithm import (
    _cancel_rules,
    _cost_signature,
    _normalize,
    data_parallel_seed,
    greedy_apply,
    max_total_degree,
    parallel_degree_summary,
    sequence_parallel_seed,
    serial_compute_nodes,
    tensor_parallel_seed,
)
from flexflow_tpu.op_attrs import OperatorType, op_type_of
from flexflow_tpu.op_attrs.core import (
    get_parallel_weight_shapes,
    get_weight_shapes,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    CombineAttrs,
    MultiHeadAttentionAttrs,
    ReductionAttrs,
    RepartitionAttrs,
)
from flexflow_tpu.op_attrs.ops.ring_attention import RingAttentionAttrs
from flexflow_tpu.op_attrs.ops.ulysses_attention import UlyssesAttentionAttrs
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    get_reduced_shape,
    lift_to_parallel,
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.parallel_computation_graph import (
    pcg_from_computation_graph,
)
from flexflow_tpu.substitutions.rules import (
    data_parallel_attention_rule,
    data_parallel_layer_norm_rule,
    data_parallel_linear_rule,
    data_parallel_op_rule,
)


def transformer_pcg(batch=16, seq=16, embed=32, heads=4, classes=8):
    b = ComputationGraphBuilder()
    x = b.create_input([batch, seq, embed], name="x")
    attn = b.multihead_attention(x, x, x, embed_dim=embed, num_heads=heads,
                                 name="attn")
    h = b.add(x, attn)
    h = b.layer_norm(h, axes=[-1], name="ln1")
    ff = b.dense(h, 4 * embed, name="ff1")
    ff = b.gelu(ff)
    ff = b.dense(ff, embed, name="ff2")
    h = b.layer_norm(b.add(h, ff), axes=[-1], name="ln2")
    b.dense(h, classes, name="head")
    return pcg_from_computation_graph(b.graph)


def op_types(pcg):
    return [op_type_of(pcg.op_attrs(n)) for n in pcg.topological_ordering()]


class TestDataParallelSeed:
    def test_wraps_whole_graph_at_degree(self):
        seed = data_parallel_seed(transformer_pcg(), 8)
        degrees = parallel_degree_summary(seed)
        assert degrees.get("repartition") == 8
        assert degrees.get("combine") == 8
        assert max_total_degree(seed) == 8
        # interior seams cancelled: exactly one batch Repartition on the
        # input stream (plus none between consecutive wrapped ops)
        reparts = [
            n for n in seed.nodes
            if isinstance(seed.op_attrs(n), RepartitionAttrs)
        ]
        assert len(reparts) == 1

    def test_ineligible_op_stays_serial(self):
        """A batch-dim concat can't shard dim 0; the seed must leave it
        serial instead of failing (the rule-based path's behavior)."""
        b = ComputationGraphBuilder()
        x = b.create_input([8, 16], name="x")
        y = b.create_input([8, 16], name="y")
        cat = b.concat([x, y], axis=0)  # batch concat: axis 0
        b.dense(cat, 8, use_bias=False, name="fc")
        pcg = pcg_from_computation_graph(b.graph)
        seed = data_parallel_seed(pcg, 8)
        # the dense got wrapped; the concat did not
        assert OperatorType.CONCAT in op_types(seed)
        degrees = parallel_degree_summary(seed)
        assert degrees.get("repartition") == 8

    def test_indivisible_batch_leaves_serial(self):
        pcg = transformer_pcg(batch=6)  # 6 % 8 != 0
        seed = data_parallel_seed(pcg, 8)
        assert parallel_degree_summary(seed) == {}


class TestMegatronSeed:
    def test_column_row_alternation(self):
        seed = tensor_parallel_seed(transformer_pcg(), 4)
        # ff1 (32->128) column-parallel: weight repartitioned on dim 1;
        # ff2 (128->32, bias) stays column (bias blocks the row rule);
        # attention head-parallel: Reduction output present
        kinds = parallel_degree_summary(seed)
        assert kinds.get("repartition") == 4
        assert kinds.get("reduction") == 4  # head-parallel attention
        assert max_total_degree(seed) == 4

    def test_row_parallel_on_biasless_contraction(self):
        b = ComputationGraphBuilder()
        x = b.create_input([8, 64], name="x")
        h = b.dense(x, 256, use_bias=False, name="up")
        h = b.relu(h)
        b.dense(h, 64, use_bias=False, name="down")
        pcg = pcg_from_computation_graph(b.graph)
        seed = tensor_parallel_seed(pcg, 4)
        # up=column, relu=channel-sharded, down=row -> one Reduction, and
        # the interior Combine(-1)/Repartition(-1) seams cancel completely
        assert any(
            isinstance(seed.op_attrs(n), ReductionAttrs) for n in seed.nodes
        )
        interior_combines = [
            n for n in seed.nodes
            if isinstance(seed.op_attrs(n), CombineAttrs)
        ]
        assert len(interior_combines) <= 1  # only the terminal one, if any


class TestSequenceParallelSeed:
    def test_ring_retype_and_seq_stream(self):
        seed = sequence_parallel_seed(transformer_pcg(), 8, "ring")
        types = {
            op_type_of(seed.op_attrs(n)).value for n in seed.nodes
        }
        assert "ring_attention" in types
        degrees = parallel_degree_summary(seed)
        assert degrees.get("repartition") == 8

    def test_a2a_requires_head_divisibility(self):
        # heads=4 < sp=8: the attention stays dense MHA, only eligible
        # seq-dim ops shard
        seed = sequence_parallel_seed(transformer_pcg(heads=4), 8, "a2a")
        types = {
            op_type_of(seed.op_attrs(n)).value for n in seed.nodes
        }
        assert "ulysses_attention" not in types

    def test_composes_with_megatron(self):
        tp = tensor_parallel_seed(transformer_pcg(), 2)
        seed = sequence_parallel_seed(tp, 4, "ring")
        assert max_total_degree(seed) == 8


# -- biased attention under batch parallelism --------------------------------


def bert_like_graph(batch=8, seq=16, hidden=32, heads=4, blocks=2, bias=True):
    """Post-LN encoder blocks with BERT's biased attention (the builder's
    graph and its logits, for FFModel.from_computation_graph too)."""
    b = ComputationGraphBuilder()
    x = b.create_input([batch, seq, hidden], name="x")
    h = b.layer_norm(x, axes=[-1], name="ln_emb")
    for i in range(blocks):
        attn = b.multihead_attention(
            h, h, h, hidden, heads, kdim=hidden // heads,
            vdim=hidden // heads, bias=bias, name=f"attn{i}",
        )
        h = b.layer_norm(b.add(h, attn), axes=[-1], name=f"ln1_{i}")
        ff = b.dense(b.gelu(b.dense(h, 4 * hidden, name=f"ff1_{i}")), hidden,
                     name=f"ff2_{i}")
        h = b.layer_norm(b.add(h, ff), axes=[-1], name=f"ln2_{i}")
    return b.graph, b.dense(h, 8, name="head")


def bert_like_pcg(**kw):
    return pcg_from_computation_graph(bert_like_graph(**kw)[0])


def batch_sharded(sizes, k, copy=1):
    return lift_to_parallel_with_degrees(
        TensorShape(tuple(sizes), DataType.FLOAT), 1, copy,
        (k,) + (1,) * (len(sizes) - 1),
    )


ATTENTION_CLASSES = [
    MultiHeadAttentionAttrs, RingAttentionAttrs, UlyssesAttentionAttrs,
]


class TestBiasedAttentionShapeRule:
    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_biases_replicate_over_the_batch_like_the_weight(self, k):
        attrs = MultiHeadAttentionAttrs(32, 4, kdim=8, vdim=8, bias=True)
        x = batch_sharded([8, 16, 32], k)
        w, in_bias, out_bias = get_parallel_weight_shapes(attrs, [x, x, x])
        assert (in_bias.sizes(), out_bias.sizes()) == ((24,), (32,))
        for s in (w, in_bias, out_bias):
            assert s.discard_copy_degree == k and s.sum_degree == 1
            assert set(s.shard_degrees()) == {1}

    @pytest.mark.parametrize("cls", ATTENTION_CLASSES, ids=lambda c: c.__name__)
    def test_degree_one_is_the_serial_shape(self, cls):
        attrs = cls(32, 4, kdim=8, vdim=8, bias=True)
        x = batch_sharded([8, 16, 32], 1)
        assert get_parallel_weight_shapes(attrs, [x, x, x]) == [
            lift_to_parallel(s)
            for s in get_weight_shapes(attrs, [get_reduced_shape(x)] * 3)
        ]

    @pytest.mark.parametrize("cls", ATTENTION_CLASSES, ids=lambda c: c.__name__)
    def test_head_parallel_with_bias_is_rejected(self, cls):
        attrs = cls(32, 4, kdim=8, vdim=8, bias=True)
        x = batch_sharded([8, 16, 32], 1, copy=2)  # copies drive the heads
        with pytest.raises(AssertionError, match="after the Reduction"):
            get_parallel_weight_shapes(attrs, [x, x, x])
        # without a bias the head-parallel weight is as it was
        (w,) = get_parallel_weight_shapes(
            cls(32, 4, kdim=8, vdim=8), [x, x, x]
        )
        assert w.shard_degrees() == (1, 2)


class TestBiasedAttentionDataParallelSeed:
    @pytest.mark.parametrize("k", [2, 4])
    def test_every_mha_is_batch_sharded_with_no_reshard_beside_it(self, k):
        seed = data_parallel_seed(bert_like_pcg(), k)
        mha = [
            n for n in seed.topological_ordering()
            if op_type_of(seed.op_attrs(n)) == OperatorType.MULTIHEAD_ATTENTION
        ]
        assert len(mha) == 2
        for n in mha:
            q, _, _, *weights = seed.inputs_of(n)
            assert seed.tensor_shape(q).shard_degrees() == (k, 1, 1)
            assert len(weights) == 3
            for w in weights:
                assert seed.tensor_shape(w).discard_copy_degree == k
            (out,) = seed.outputs_of(n)
            assert seed.tensor_shape(out).shard_degrees() == (k, 1, 1)
            beside = [v.node for v in seed.inputs_of(n)[:3]] + [
                u.node for u in seed.uses_of(out)
            ]
            assert not any(
                isinstance(seed.op_attrs(m), (CombineAttrs, RepartitionAttrs))
                for m in beside
            )
        assert serial_compute_nodes(seed) == []

    def test_serial_compute_nodes_names_what_a_template_skipped(self):
        pcg = bert_like_pcg(batch=6)  # 6 % 4: nothing can be wrapped
        assert parallel_degree_summary(data_parallel_seed(pcg, 4)) == {}
        names = serial_compute_nodes(data_parallel_seed(pcg, 4))
        assert {"attn0", "attn1", "ff1_0", "head"} <= set(names)
        assert len(names) == 18 and len(set(names)) == 18
        # head-parallel attention refuses a bias: the Megatron template
        # leaves exactly those nodes whole, and now says so
        tp = tensor_parallel_seed(bert_like_pcg(), 4)
        assert {"attn0", "attn1"} <= set(serial_compute_nodes(tp))
        tp = tensor_parallel_seed(bert_like_pcg(bias=False), 4)
        assert not {"attn0", "attn1"} & set(serial_compute_nodes(tp))

    def test_template_equals_the_rules_applied_node_by_node(self):
        k = 4
        pcg = bert_like_pcg()
        rules = [
            data_parallel_attention_rule(k, bias=True),
            data_parallel_linear_rule(k, use_bias=True),
            data_parallel_layer_norm_rule(k),
            data_parallel_op_rule(OperatorType.ELEMENT_UNARY, k),
            data_parallel_op_rule(OperatorType.ELEMENT_BINARY, k, num_inputs=2),
        ]
        by_rules = greedy_apply(pcg, rules, degree_cap=k)
        by_rules = _normalize(greedy_apply(by_rules, _cancel_rules(k)))
        seed = data_parallel_seed(pcg, k)
        assert len(by_rules) == len(seed)
        assert _cost_signature(by_rules) == _cost_signature(seed)


def test_templates_are_priced_whatever_their_size():
    """`max_num_ops` bounds the walk's rewrites; a template larger than it
    (every seed of a 24-block encoder is) still enters the frontier, so the
    result is floored at the data-parallel rewrite and not at what `budget`
    single rewrites reach from the serial graph."""
    from flexflow_tpu.compiler import (
        AnalyticTPUCostEstimator,
        MachineMappingContext,
        OptimizerConfig,
        graph_optimize,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.pcg.machine_view import MachineSpecification
    from flexflow_tpu.substitutions import generate_parallelization_rules

    spec = MachineSpecification(1, 1, 4, 25.0, 400.0)
    ctx = MachineMappingContext(
        AnalyticTPUCostEstimator(spec), make_default_allowed_machine_views()
    )
    pcg = bert_like_pcg(batch=16, seq=128, hidden=256)
    seed = data_parallel_seed(pcg, 4)
    cap = len(pcg) + 6
    assert len(pcg) < cap < len(seed)
    result = graph_optimize(
        pcg, ctx, spec, generate_parallelization_rules([2, 4]),
        OptimizerConfig(budget=2, max_num_ops=cap),
    )
    assert "dp4xtp1xsp1" in result.seed_runtimes
    assert result.runtime <= result.seed_runtimes["dp4xtp1xsp1"]
    assert result.runtime < result.serial_runtime
    assert serial_compute_nodes(result.pcg) == []


@pytest.mark.parametrize(
    "provenance, want",
    [
        ({"search_seconds": 1.0, "serial_compute_nodes": []}, 0),
        ({"serial_compute_nodes": ["attn0", "attn1"]}, 2),
        ({"search_seconds": 1.0}, None),  # a program from before the field
        (None, None),  # one device: no search
    ],
)
def test_serial_compute_nodes_reader(provenance, want):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(
        root, "benchmark", "layer_metrics", "serial_compute_nodes.py"
    )
    spec = importlib.util.spec_from_file_location("serial_compute_nodes", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({"provenance": provenance}) == want
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [
            m for m in json.load(f)["per_layer"]
            if m["name"] == "serial_compute_nodes"
        ]
    assert entry == [{
        "name": "serial_compute_nodes", "unit": reader.UNIT,
        "better": "lower", "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": ["bertlarge_s512_4chip"],
    }]
