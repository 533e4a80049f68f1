"""Ring attention (sequence parallelism) tests on the 8-device CPU mesh.

Exactness: ring attention must equal dense softmax attention bit-for-bit
(up to fp accumulation order) in both non-causal and causal modes, for
values AND gradients — then the executor/substitution integration.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flexflow_tpu.kernels.ops import _mha_forward
from flexflow_tpu.kernels.ring_attention import ring_mha_forward
from flexflow_tpu.op_attrs.core import OperatorType, op_type_of
from flexflow_tpu.op_attrs.ops import RingAttentionAttrs
from flexflow_tpu.parallel import DistributedTrainingInstance, MachineMesh


def make_inputs(b=2, s=16, e=32, heads=4, seed=0):
    attrs = RingAttentionAttrs(embed_dim=e, num_heads=heads)
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, s, e), jnp.float32)
    kd = attrs.q_proj_size
    per_head = e * kd * 3 + kd * e
    w = jnp.asarray(rs.randn(per_head, heads) * 0.1, jnp.float32)
    return attrs, q, w


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(causal):
    attrs, q, w = make_inputs()
    attrs = RingAttentionAttrs(
        embed_dim=attrs.embed_dim, num_heads=attrs.num_heads, causal=causal
    )
    mm = MachineMesh.for_devices(8)
    dense = _mha_forward(attrs, q, q, q, w, causal=causal)
    ring = jax.jit(
        lambda q_, w_: ring_mha_forward(
            attrs, q_, q_, q_, w_, mm.mesh, P(None, ("d0", "d1"), None)
        )
    )(q, w)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), atol=2e-5)


def test_ring_gradients_match_dense():
    attrs, q, w = make_inputs()
    mm = MachineMesh.for_devices(8)

    def dense_loss(q_, w_):
        return jnp.sum(_mha_forward(attrs, q_, q_, q_, w_) ** 2)

    def ring_loss(q_, w_):
        out = ring_mha_forward(
            attrs, q_, q_, q_, w_, mm.mesh, P(None, ("d0", "d1"), None)
        )
        return jnp.sum(out**2)

    gd_q, gd_w = jax.grad(dense_loss, argnums=(0, 1))(q, w)
    gr_q, gr_w = jax.jit(jax.grad(ring_loss, argnums=(0, 1)))(q, w)
    np.testing.assert_allclose(np.asarray(gr_q), np.asarray(gd_q), atol=5e-4)
    np.testing.assert_allclose(np.asarray(gr_w), np.asarray(gd_w), atol=5e-4)


def test_ring_refuses_an_unsharded_seq():
    """The schedule is for a sharded sequence; a whole one is plain
    attention, which the executor lowers through the op's own dispatch
    (tests/test_flash_attention.py: causal_ring_seq_whole)."""
    attrs, q, w = make_inputs()
    mm = MachineMesh.for_devices(8)
    with pytest.raises(AssertionError, match="whole sequence"):
        ring_mha_forward(attrs, q, q, q, w, mm.mesh, None)


def test_parallel_shape_inference_seq_sharded():
    from flexflow_tpu.op_attrs.core import get_parallel_output_shapes
    from tests.test_parallel_lowering import pts

    attrs = RingAttentionAttrs(embed_dim=32, num_heads=4)
    x = pts([4, 16, 32], [2, 4, 1])
    (out,) = get_parallel_output_shapes(attrs, [x, x, x])
    assert out.shard_degrees() == (2, 4, 1)
    assert out.sum_degree == 1


def test_sequence_parallel_substitution():
    """MHA -> RingAttention rewrite produces a valid seq-sharded PCG."""
    from flexflow_tpu.pcg.parallel_computation_graph import (
        elide_noops,
        pcg_from_computation_graph,
    )
    from flexflow_tpu.pcg.computation_graph_builder import ComputationGraphBuilder
    from flexflow_tpu.substitutions.pcg_pattern import find_pattern_matches
    from flexflow_tpu.substitutions.rules import sequence_parallel_attention_rule
    from flexflow_tpu.substitutions.substitution import apply_substitution

    b = ComputationGraphBuilder()
    x = b.create_input([2, 16, 32], name="x")
    y = b.multihead_attention(x, x, x, 32, 4, name="attn")
    pcg = pcg_from_computation_graph(b.graph)
    rule = sequence_parallel_attention_rule(4)
    matches = find_pattern_matches(rule.pattern, pcg)
    assert matches, "MHA pattern did not match"
    new_pcg = elide_noops(apply_substitution(pcg, rule, matches[0]))
    ring_nodes = [
        n
        for n in new_pcg.topological_ordering()
        if op_type_of(new_pcg.op_attrs(n)) == OperatorType.RING_ATTENTION
    ]
    assert len(ring_nodes) == 1
    (out,) = new_pcg.outputs_of(ring_nodes[0])
    assert new_pcg.tensor_shape(out).shard_degrees()[1] == 4


def test_distributed_training_with_ring_attention():
    """Train a seq-parallel attention PCG end-to-end on the 8-device mesh."""
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.parallel_tensor_shape import (
        ParallelTensorDims,
        ParallelTensorShape,
        ShardParallelDim,
    )
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs
    from flexflow_tpu.pcg.parallel_computation_graph_builder import (
        ParallelComputationGraphBuilder,
    )

    bld = ParallelComputationGraphBuilder()
    x = bld.create_input_tensor(
        ParallelTensorShape(
            ParallelTensorDims(
                (
                    ShardParallelDim(4, 2),  # batch dp=2
                    ShardParallelDim(16, 4),  # seq sp=4
                    ShardParallelDim(32, 1),
                ),
            ),
            DataType.FLOAT,
        ),
        name="x",
    )
    h = bld.ring_attention(x, x, x, 32, 4, causal=True, name="rattn")
    h = bld.layer_norm(bld.add(x, h), axes=[-1], name="ln")
    logits = bld.dense(h, 8, name="head")

    mm = MachineMesh.for_devices(8)
    inst = DistributedTrainingInstance(
        bld.graph,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        SGDOptimizerAttrs(lr=0.05),
        mm,
    )
    params, opt = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    x_v = jnp.asarray(rs.randn(4, 16, 32), jnp.float32)
    y_v = jnp.asarray(rs.randint(0, 8, (4, 16)), jnp.int32)
    xs = inst.input_sharding("x")
    if xs is not None:
        x_v = jax.device_put(x_v, xs)
    ls = inst.label_sharding()
    if ls is not None:
        y_v = jax.device_put(y_v, ls)
    losses = []
    for _ in range(4):
        params, opt, loss, _ = inst.train_step(params, opt, {"x": x_v}, y_v)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


class TestRingComposition:
    """Round-1 weak #7: the ring must compose with head parallelism (the
    seq-parallel and head-parallel rules can stack) and carry qkv/output
    biases."""

    def test_ring_with_head_parallel_matches_dense(self):
        attrs, q, w = make_inputs(s=16, e=32, heads=4)
        mm = MachineMesh.for_devices(8)  # axes d0 x d1 x d2 = 2x2x2
        dense = _mha_forward(attrs, q, q, q, w, causal=attrs.causal)
        ring = jax.jit(
            lambda q_, w_: ring_mha_forward(
                attrs, q_, q_, q_, w_, mm.mesh,
                P(None, ("d0", "d1"), None),  # seq over 4 devices
                w_spec=P(None, "d2"),  # heads over 2 devices
            )
        )(q, w)
        np.testing.assert_allclose(
            np.asarray(ring), np.asarray(dense), atol=2e-5
        )

    def test_ring_with_head_parallel_gradients(self):
        attrs, q, w = make_inputs()
        mm = MachineMesh.for_devices(8)

        def loss_ring(q_, w_):
            out = ring_mha_forward(
                attrs, q_, q_, q_, w_, mm.mesh,
                P(None, ("d0", "d1"), None), w_spec=P(None, "d2"),
            )
            return jnp.sum(out ** 2)

        def loss_dense(q_, w_):
            return jnp.sum(_mha_forward(attrs, q_, q_, q_, w_) ** 2)

        gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1)))(q, w)
        gd = jax.grad(loss_dense, argnums=(0, 1))(q, w)
        for a, b in zip(gr, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)

    def test_ring_with_bias_matches_dense(self):
        e, heads = 32, 4
        attrs = RingAttentionAttrs(embed_dim=e, num_heads=heads, bias=True)
        rs = np.random.RandomState(3)
        q = jnp.asarray(rs.randn(2, 16, e), jnp.float32)
        kd = attrs.q_proj_size
        w = jnp.asarray(rs.randn(e * kd * 3 + kd * e, heads) * 0.1, jnp.float32)
        in_bias = jnp.asarray(rs.randn(3 * kd) * 0.1, jnp.float32)
        out_bias = jnp.asarray(rs.randn(e) * 0.1, jnp.float32)
        mm = MachineMesh.for_devices(8)
        dense = _mha_forward(attrs, q, q, q, w, in_bias) + out_bias
        ring = jax.jit(
            lambda q_, w_, ib, ob: ring_mha_forward(
                attrs, q_, q_, q_, w_, mm.mesh,
                P(None, ("d0", "d1"), None),
                input_bias=ib, output_bias=ob,
            )
        )(q, w, in_bias, out_bias)
        np.testing.assert_allclose(
            np.asarray(ring), np.asarray(dense), atol=2e-5
        )

    def test_ring_bias_and_head_parallel_gradients(self):
        """The riskiest combination: bias + head parallelism, gradients
        through shard_map (a psum placed before the output bias would scale
        it by tp; a mis-spec'd bias would corrupt its gradient)."""
        e, heads = 32, 4
        attrs = RingAttentionAttrs(embed_dim=e, num_heads=heads, bias=True)
        rs = np.random.RandomState(5)
        q = jnp.asarray(rs.randn(2, 16, e), jnp.float32)
        kd = attrs.q_proj_size
        w = jnp.asarray(rs.randn(e * kd * 3 + kd * e, heads) * 0.1, jnp.float32)
        ib = jnp.asarray(rs.randn(3 * kd) * 0.1, jnp.float32)
        ob = jnp.asarray(rs.randn(e) * 0.1, jnp.float32)
        mm = MachineMesh.for_devices(8)

        def loss_ring(q_, w_, ib_, ob_):
            out = ring_mha_forward(
                attrs, q_, q_, q_, w_, mm.mesh,
                P(None, ("d0", "d1"), None), w_spec=P(None, "d2"),
                input_bias=ib_, output_bias=ob_,
            )
            return jnp.sum(out ** 2)

        def loss_dense(q_, w_, ib_, ob_):
            out = _mha_forward(attrs, q_, q_, q_, w_, ib_) + ob_
            return jnp.sum(out ** 2)

        gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2, 3)))(q, w, ib, ob)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2, 3))(q, w, ib, ob)
        for a, b in zip(gr, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
