"""Model zoo tests (reference: lib/models/test/src/models/* layer-count
invariants, plus forward smoke runs the reference can't do on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.models import (
    BertConfig,
    CandleUnoConfig,
    InceptionV3Config,
    TransformerConfig,
    build_bert,
    build_candle_uno,
    build_inception_v3,
    build_split_test,
    build_transformer,
    get_default_bert_config,
    get_default_candle_uno_config,
    get_default_inception_v3_training_config,
    get_default_transformer_config,
)
from flexflow_tpu.local_execution.training_backing import (
    forward_interpreter,
    init_params,
)
from flexflow_tpu.op_attrs.ops import (
    Conv2DAttrs,
    LinearAttrs,
    MultiHeadAttentionAttrs,
)


def count_ops(cg, attr_cls):
    return sum(
        1
        for n in cg.topological_ordering()
        if isinstance(cg.op_attrs(n), attr_cls)
    )


def test_transformer_default_structure():
    cfg = get_default_transformer_config()
    cg, out = build_transformer(cfg)
    # 6 encoder self-attn + 6 decoder (self + cross) = 18 MHA layers
    assert count_ops(cg, MultiHeadAttentionAttrs) == 18
    # 2 ffn denses per layer x 12 layers + head = 25
    assert count_ops(cg, LinearAttrs) == 25
    assert cg.tensor_shape(out).dims == (
        cfg.batch_size, cfg.sequence_length, cfg.vocab_size
    )


def test_transformer_tiny_forward():
    cfg = TransformerConfig(
        num_features=16, sequence_length=8, batch_size=2, dim_feedforward=32,
        num_heads=2, num_encoder_layers=1, num_decoder_layers=1, vocab_size=11,
    )
    cg, out = build_transformer(cfg)
    params = init_params(cg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 8, 16), jnp.float32)
    env = forward_interpreter(cg, params, {"input": x, "target": x})
    prob = env[out]
    assert prob.shape == (2, 8, 11)
    np.testing.assert_allclose(np.sum(np.asarray(prob), -1), 1.0, rtol=1e-5)


def test_bert_default_structure():
    cfg = get_default_bert_config()
    cg, out = build_bert(cfg)
    assert count_ops(cg, MultiHeadAttentionAttrs) == cfg.num_encoder_layers
    assert count_ops(cg, LinearAttrs) == 2 * cfg.num_encoder_layers + 1
    assert cg.tensor_shape(out).dims == (
        cfg.batch_size, cfg.sequence_length, cfg.vocab_size
    )


def test_bert_rejects_relative_position():
    cfg = BertConfig(position_embedding_type="relative_key")
    with pytest.raises(ValueError):
        build_bert(cfg)


def test_bert_tiny_forward():
    cfg = BertConfig(
        vocab_size=13, hidden_size=16, num_encoder_layers=2, num_heads=2,
        dim_feedforward=32, sequence_length=8, batch_size=2,
    )
    cg, out = build_bert(cfg)
    params = init_params(cg, jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
    env = forward_interpreter(cg, params, {"input": x})
    assert env[out].shape == (2, 8, 13)


def test_candle_uno_default_structure():
    cfg = get_default_candle_uno_config()
    cg, out = build_candle_uno(cfg)
    # 5 tower inputs x 8 feature denses + 4 trunk + 1 regressor = 45
    assert count_ops(cg, LinearAttrs) == 45
    assert cg.tensor_shape(out).dims == (cfg.batch_size, 1)


def test_candle_uno_tiny_forward():
    cfg = CandleUnoConfig(
        batch_size=2,
        dense_layers=(8, 8),
        dense_feature_layers=(8,),
        feature_shapes=(("dose", 1), ("cell.rnaseq", 4), ("drug.descriptors", 5)),
        input_features=(
            ("dose1", "dose"),
            ("cell.rnaseq", "cell.rnaseq"),
            ("drug1.descriptors", "drug.descriptors"),
        ),
        dropout=0.0,
    )
    cg, out = build_candle_uno(cfg)
    params = init_params(cg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    inputs = {
        "dose1": jnp.asarray(rs.randn(2, 1), jnp.float32),
        "cell.rnaseq": jnp.asarray(rs.randn(2, 4), jnp.float32),
        "drug1.descriptors": jnp.asarray(rs.randn(2, 5), jnp.float32),
    }
    env = forward_interpreter(cg, params, inputs)
    assert env[out].shape == (2, 1)


def test_inception_v3_structure():
    cfg = InceptionV3Config(num_classes=10, batch_size=1, aux_logits=True)
    cg, out, aux = build_inception_v3(cfg)
    # the builder shape-checks every module boundary internally; reaching
    # here already validates the topology. 94 conv blocks per torchvision
    # InceptionV3 plus 2 aux-head convs.
    assert count_ops(cg, Conv2DAttrs) == 96
    assert cg.tensor_shape(out).dims == (1, 10)
    assert aux is not None and cg.tensor_shape(aux).dims == (1, 10)


def test_inception_v3_no_aux():
    cfg = InceptionV3Config(num_classes=10, batch_size=1, aux_logits=False)
    cg, out, aux = build_inception_v3(cfg)
    assert aux is None
    assert count_ops(cg, Conv2DAttrs) == 94


def test_split_test_forward():
    cg, out = build_split_test(batch_size=4)
    params = init_params(cg, jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(4, 256), jnp.float32)
    env = forward_interpreter(cg, params, {"input": x})
    assert env[out].shape == (4, 32)


def test_flagship_step_flops_match_graph_count():
    """The flagship's closed-form training FLOPs (the figure chip_smoke.py
    divides by step time) agree with three times the forward FLOPs counted
    op by op from the built graph, at the flagship's own shapes."""
    from flexflow_tpu.kernels.ops import op_forward_flops
    from flexflow_tpu.local_execution.training_backing import (
        split_slot_values,
    )
    from flexflow_tpu.models.flagship import (
        build_flagship_cg,
        flagship_step_flops,
    )

    shapes = dict(batch=64, seq=512, embed=1024, heads=8, layers=12, vocab=32000)
    cg, _ = build_flagship_cg(**shapes)
    forward = 0
    for n in cg.topological_ordering():
        attrs = cg.op_attrs(n)
        slots = [cg.tensor_shape(t) for t in cg.inputs_of(n)]
        outs = [cg.tensor_shape(t) for t in cg.outputs_of(n)]
        data, weights = split_slot_values(attrs, slots)
        if data:  # inputs and weights do no arithmetic
            forward += op_forward_flops(
                attrs, data, outs, weight_shapes=weights or None
            )
    assert flagship_step_flops(**shapes) == pytest.approx(3 * forward, rel=0.02)
