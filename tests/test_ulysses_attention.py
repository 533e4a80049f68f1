"""Ulysses (all-to-all) sequence-parallel attention on the 8-device mesh.

The second context-parallel strategy beside the ring: numerics must match
dense attention exactly for values and gradients, compose with head
parallelism and biases, and be discoverable by the search via the a2a rule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flexflow_tpu.kernels.ops import _mha_forward
from flexflow_tpu.kernels.ulysses_attention import ulysses_mha_forward
from flexflow_tpu.op_attrs.core import OperatorType, op_type_of
from flexflow_tpu.op_attrs.ops import UlyssesAttentionAttrs
from flexflow_tpu.parallel import DistributedTrainingInstance, MachineMesh


def make_inputs(b=2, s=16, e=32, heads=8, causal=False, seed=0):
    attrs = UlyssesAttentionAttrs(embed_dim=e, num_heads=heads, causal=causal)
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, s, e), jnp.float32)
    kd = attrs.q_proj_size
    w = jnp.asarray(rs.randn(e * kd * 3 + kd * e, heads) * 0.1, jnp.float32)
    return attrs, q, w


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    attrs, q, w = make_inputs(causal=causal)
    mm = MachineMesh.for_devices(8)
    dense = _mha_forward(attrs, q, q, q, w, causal=causal)
    out = jax.jit(
        lambda q_, w_: ulysses_mha_forward(
            attrs, q_, q_, q_, w_, mm.mesh, P(None, ("d0", "d1"), None)
        )
    )(q, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)


def test_ulysses_gradients_match_dense():
    attrs, q, w = make_inputs()
    mm = MachineMesh.for_devices(8)

    def loss_u(q_, w_):
        out = ulysses_mha_forward(
            attrs, q_, q_, q_, w_, mm.mesh, P(None, ("d0", "d1"), None)
        )
        return jnp.sum(out ** 2)

    def loss_d(q_, w_):
        return jnp.sum(_mha_forward(attrs, q_, q_, q_, w_) ** 2)

    gu = jax.jit(jax.grad(loss_u, argnums=(0, 1)))(q, w)
    gd = jax.grad(loss_d, argnums=(0, 1))(q, w)
    for a, b in zip(gu, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_ulysses_with_head_parallel_and_bias():
    e, heads = 32, 8
    attrs = UlyssesAttentionAttrs(embed_dim=e, num_heads=heads, bias=True)
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(2, 16, e), jnp.float32)
    kd = attrs.q_proj_size
    w = jnp.asarray(rs.randn(e * kd * 3 + kd * e, heads) * 0.1, jnp.float32)
    ib = jnp.asarray(rs.randn(3 * kd) * 0.1, jnp.float32)
    ob = jnp.asarray(rs.randn(e) * 0.1, jnp.float32)
    mm = MachineMesh.for_devices(8)
    dense = _mha_forward(attrs, q, q, q, w, ib) + ob
    out = jax.jit(
        lambda q_, w_, ib_, ob_: ulysses_mha_forward(
            attrs, q_, q_, q_, w_, mm.mesh,
            P(None, ("d0", "d1"), None),  # seq over 4 devices
            w_spec=P(None, "d2"),  # heads over 2
            input_bias=ib_, output_bias=ob_,
        )
    )(q, w, ib, ob)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5)


def test_ulysses_refuses_an_unsharded_seq():
    attrs, q, w = make_inputs()
    mm = MachineMesh.for_devices(8)
    with pytest.raises(AssertionError, match="whole sequence"):
        ulysses_mha_forward(attrs, q, q, q, w, mm.mesh, None)


def test_a2a_rule_applies_and_head_divisibility_gates():
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )
    from flexflow_tpu.substitutions import (
        apply_substitution,
        find_pattern_matches,
        is_valid_match_for_substitution,
    )
    from flexflow_tpu.substitutions.rules import (
        sequence_parallel_attention_a2a_rule,
    )

    b = ComputationGraphBuilder()
    x = b.create_input([2, 16, 32], name="x")
    b.multihead_attention(x, x, x, 32, 8)
    pcg = pcg_from_computation_graph(b.graph)

    rule = sequence_parallel_attention_a2a_rule(4)
    matches = find_pattern_matches(rule.pattern, pcg)
    assert matches
    assert is_valid_match_for_substitution(pcg, rule, matches[0])
    new_pcg = apply_substitution(pcg, rule, matches[0])
    ops = {op_type_of(new_pcg.op_attrs(n)) for n in new_pcg.nodes}
    assert OperatorType.ULYSSES_ATTENTION in ops
    assert OperatorType.REPARTITION in ops

    # heads=8 cannot split over degree 16
    assert not find_pattern_matches(
        sequence_parallel_attention_a2a_rule(16).pattern, pcg
    )


def test_ulysses_trains_end_to_end():
    """Distributed instance with a Ulysses node trains on the mesh."""
    from flexflow_tpu.kernels.metrics import METRIC_ACCURACY
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )
    from flexflow_tpu.substitutions import (
        apply_substitution,
        find_pattern_matches,
    )
    from flexflow_tpu.substitutions.rules import (
        sequence_parallel_attention_a2a_rule,
    )

    b = ComputationGraphBuilder()
    x = b.create_input([4, 16, 32], name="x")
    t = b.multihead_attention(x, x, x, 32, 8)
    b.dense(t, 8, use_bias=False, name="head")
    pcg = pcg_from_computation_graph(b.graph)
    rule = sequence_parallel_attention_a2a_rule(4)
    pcg = apply_substitution(pcg, rule, find_pattern_matches(rule.pattern, pcg)[0])

    from flexflow_tpu.core.ffmodel import _find_sink_output

    logit = _find_sink_output(pcg)
    mm = MachineMesh.for_devices(8)
    inst = DistributedTrainingInstance(
        pcg, logit,
        SparseCategoricalCrossEntropyLossAttrs(),
        SGDOptimizerAttrs(lr=0.1),
        mm,
        metrics=frozenset({METRIC_ACCURACY}),
    )
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(4, 16, 32), jnp.float32)
    yv = jnp.asarray(rs.randint(0, 8, (4, 16)), jnp.int32)
    losses = []
    for _ in range(3):
        params, opt_state, loss, _ = inst.train_step(
            params, opt_state, {"x": xv}, yv
        )
        losses.append(float(np.asarray(loss)))
    assert losses[-1] < losses[0], losses


def test_cost_model_distinguishes_ring_from_ulysses():
    """The search can only 'pick either' if their costs differ: the
    schedule-internal comm (ppermutes vs all-to-alls) is priced per op."""
    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
        seq_parallel_attention_comm_ms,
    )
    from flexflow_tpu.op_attrs import (
        ParallelTensorDims,
        ParallelTensorShape,
        ShardParallelDim,
    )
    from flexflow_tpu.op_attrs.ops import RingAttentionAttrs
    from flexflow_tpu.pcg.machine_view import MachineSpecification

    spec = MachineSpecification(1, 1, 8, 1.0, 2.0)
    q = ParallelTensorShape(
        ParallelTensorDims(
            (
                ShardParallelDim(8, 1),
                ShardParallelDim(1024, 4),
                ShardParallelDim(64, 1),
            ),
            1,
            1,
        )
    )
    ring = RingAttentionAttrs(embed_dim=64, num_heads=8)
    uly = UlyssesAttentionAttrs(embed_dim=64, num_heads=8)
    c_ring = seq_parallel_attention_comm_ms(ring, [q, q, q], spec, 0.1, 0.2)
    c_uly = seq_parallel_attention_comm_ms(uly, [q, q, q], spec, 0.1, 0.2)
    assert c_ring > 0 and c_uly > 0
    assert c_ring != c_uly
    # unsharded sequence: both schedules degenerate to dense, zero comm
    q1 = ParallelTensorShape(
        ParallelTensorDims(
            (
                ShardParallelDim(8, 1),
                ShardParallelDim(1024, 1),
                ShardParallelDim(64, 1),
            ),
            1,
            1,
        )
    )
    assert seq_parallel_attention_comm_ms(ring, [q1] * 3, spec, 0.1, 0.2) == 0.0
