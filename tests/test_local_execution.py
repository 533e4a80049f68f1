"""End-to-end single-host training tests (the minimum slice of SURVEY.md §7).

Coverage model: reference lib/local-execution/test/src + the pytorch alignment
tests' numeric-equality idea (tests/align) — here alignment is vs analytic
expectations and loss descent.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import forward as kernel_forward, loss_forward
from flexflow_tpu.local_execution import (
    LocalTrainingBacking,
    ModelTrainingInstance,
)
from flexflow_tpu.local_execution.cost_estimator import LocalCostEstimator
from flexflow_tpu.local_execution.training_backing import init_params, forward_interpreter
from flexflow_tpu.op_attrs import DataType, TensorShape
from flexflow_tpu.op_attrs.ops import (
    LinearAttrs,
    MultiHeadAttentionAttrs,
    SoftmaxAttrs,
)
from flexflow_tpu.op_attrs.ops.loss_functions import (
    LossFunction,
    NonconfigurableLossAttrs,
    SparseCategoricalCrossEntropyLossAttrs,
)
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs, AdamOptimizerAttrs
from flexflow_tpu.kernels.metrics import METRIC_ACCURACY
from flexflow_tpu.kernels.profiling import ProfilingSettings


def make_mlp(batch=16, in_dim=20, hidden=32, classes=5):
    b = ComputationGraphBuilder()
    x = b.create_input([batch, in_dim], name="x")
    h = b.dense(x, hidden, name="fc1")
    h = b.relu(h)
    logits = b.dense(h, classes, name="fc2")
    return b.graph, logits


class TestKernels:
    def test_linear_matches_numpy(self):
        attrs = LinearAttrs(out_channels=4, use_bias=True)
        x = jnp.asarray(np.random.RandomState(0).randn(3, 5), jnp.float32)
        w = jnp.asarray(np.random.RandomState(1).randn(5, 4), jnp.float32)
        bias = jnp.asarray(np.random.RandomState(2).randn(4), jnp.float32)
        (out,) = kernel_forward(attrs, [x], [w, bias])
        np.testing.assert_allclose(out, x @ w + bias, rtol=1e-5)

    def test_mha_shapes_and_finite(self):
        attrs = MultiHeadAttentionAttrs(embed_dim=16, num_heads=4)
        q = jnp.ones((2, 6, 16), jnp.float32)
        w_len = 4 * 16 * 4  # (wq+wk+wv+wo) per head x heads
        w = jnp.asarray(
            np.random.RandomState(0).randn(16 * 4 * 4, 4) * 0.1, jnp.float32
        )
        (out,) = kernel_forward(attrs, [q, q, q], [w])
        assert out.shape == (2, 6, 16)
        assert bool(jnp.isfinite(out).all())

    def test_softmax_rows_sum_to_one(self):
        (out,) = kernel_forward(
            SoftmaxAttrs(-1), [jnp.asarray([[1.0, 2.0, 3.0]])], []
        )
        np.testing.assert_allclose(out.sum(), 1.0, rtol=1e-6)

    def test_scce_loss_matches_manual(self):
        logit = jnp.asarray([[2.0, 1.0, 0.0], [0.0, 2.0, 1.0]])
        label = jnp.asarray([0, 1])
        loss = loss_forward(SparseCategoricalCrossEntropyLossAttrs(), logit, label)
        manual = -np.mean(
            [
                jax.nn.log_softmax(logit[0])[0],
                jax.nn.log_softmax(logit[1])[1],
            ]
        )
        np.testing.assert_allclose(loss, manual, rtol=1e-6)


class TestTrainingInstance:
    def _train(self, optimizer_attrs, steps=30):
        cg, logits = make_mlp()
        inst = ModelTrainingInstance(
            cg,
            logits,
            SparseCategoricalCrossEntropyLossAttrs(),
            optimizer_attrs,
            metrics=frozenset({METRIC_ACCURACY}),
        )
        params, opt_state = inst.initialize(seed=0)
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(16, 20), jnp.float32)
        y = jnp.asarray(rs.randint(0, 5, 16), jnp.int32)
        losses = []
        for _ in range(steps):
            params, opt_state, loss, metrics = inst.train_step(
                params, opt_state, {"x": x}, y
            )
            losses.append(float(loss))
        return losses, metrics

    def test_sgd_loss_decreases(self):
        losses, metrics = self._train(SGDOptimizerAttrs(lr=0.1))
        assert losses[-1] < losses[0] * 0.5, losses
        assert "train_correct" in metrics

    def test_sgd_momentum(self):
        losses, _ = self._train(SGDOptimizerAttrs(lr=0.05, momentum=0.9))
        assert losses[-1] < losses[0] * 0.5

    def test_adam(self):
        losses, _ = self._train(AdamOptimizerAttrs(alpha=0.01))
        assert losses[-1] < losses[0] * 0.5

    def test_overfit_memorizes(self):
        # strong signal: same batch should be nearly memorized
        losses, _ = self._train(AdamOptimizerAttrs(alpha=0.02), steps=150)
        assert losses[-1] < 0.1, losses[-1]


BACKENDS = (
    "flexflow_tpu.local_execution.training_backing.ModelTrainingInstance",
    "flexflow_tpu.parallel.data_parallel.DataParallelTrainingInstance",
    "flexflow_tpu.parallel.executor.DistributedTrainingInstance",
    "flexflow_tpu.parallel.pipeline.PipelinedTrainingInstance",
)


@pytest.mark.parametrize("path", BACKENDS, ids=lambda p: p.rsplit(".", 1)[1])
def test_a_backend_is_one_step_program(path):
    """`train_step` and `compiled_step` are the whole contract of a backend
    towards `fit`: one step program a backend, no K-step window beside it
    and no option that would ask for one (PR 42)."""
    import argparse
    import dataclasses
    import importlib

    from flexflow_tpu.core import FFConfig

    module, name = path.rsplit(".", 1)
    backend = getattr(importlib.import_module(module), name)
    assert callable(backend.train_step) and callable(backend.compiled_step)
    assert callable(backend.initialize) and callable(backend.forward)
    for gone in ("multi_train_step", "compiled_multi_step", "_multi_step"):
        assert not hasattr(backend, gone), gone
    assert "steps_per_dispatch" not in {
        f.name for f in dataclasses.fields(FFConfig)
    }
    parser = argparse.ArgumentParser()
    FFConfig.add_args(parser)
    assert "--steps-per-dispatch" not in parser._option_string_actions


class TestSteppedBacking:
    def test_forward_backward_update_parity(self):
        """Per-op stepped path produces the same gradients as autodiff over
        the whole interpreter."""
        cg, logits = make_mlp(batch=4, in_dim=6, hidden=8, classes=3)
        backing = LocalTrainingBacking(cg)
        backing.execute_init(seed=0)
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(4, 6), jnp.float32)
        y = jnp.asarray(rs.randint(0, 3, 4), jnp.int32)
        backing.execute_forward({"x": x})
        logit_val = backing.env[logits]

        loss_attrs = SparseCategoricalCrossEntropyLossAttrs()

        # loss grad wrt logits
        g = jax.grad(lambda l: loss_forward(loss_attrs, l, y))(logit_val)
        backing.execute_backward({logits: g})

        # reference gradients via autodiff over the full interpreter
        params = dict(backing.params)

        def full_loss(params):
            env = forward_interpreter(cg, params, {"x": x})
            return loss_forward(loss_attrs, env[logits], y)

        expected = jax.grad(full_loss)(params)
        assert set(expected.keys()) == set(backing.param_grads.keys())
        for k in expected:
            np.testing.assert_allclose(
                backing.param_grads[k], expected[k], rtol=1e-4, atol=1e-5
            )

        # update completes (reference left it NOT_IMPLEMENTED)
        old = {k: np.array(v) for k, v in backing.params.items()}
        backing.execute_update(SGDOptimizerAttrs(lr=0.1))
        changed = any(
            not np.allclose(old[k], backing.params[k]) for k in old
        )
        assert changed


class TestCostEstimator:
    def test_linear_cost_positive_and_cached(self):
        est = LocalCostEstimator(ProfilingSettings(warmup_iters=1, measure_iters=2))
        attrs = LinearAttrs(out_channels=32, use_bias=False)
        shape = TensorShape((16, 64))
        c1 = est.estimate_operator_cost(attrs, [shape])
        assert c1.elapsed_ms > 0
        assert c1.mem_bytes > 0
        c2 = est.estimate_operator_cost(attrs, [shape])
        assert c1 == c2  # cache hit returns identical object value

    def test_parallel_op_costs_zero(self):
        from flexflow_tpu.op_attrs.ops import ReplicateAttrs

        est = LocalCostEstimator()
        c = est.estimate_operator_cost(ReplicateAttrs(4), [TensorShape((8, 8))])
        assert c == type(c)(0.0, 0)

    def test_mem_bytes_linear_hand_computed(self):
        # ISSUE 3 satellite: mem accounting must include the activation
        # GRADIENT (live alongside the activation during backward) and the
        # optimizer state (Adam m/v = 2 extra weight-sized slots). Linear
        # [4,8] x [8,16] -> [4,16], f32:
        #   inputs  4*8*4   = 128 B  * 2 (act + grad)
        #   weight  8*16*4  = 512 B  * 4 (w + grad + m + v)
        #   output  4*16*4  = 256 B  * 2 (out + grad)
        est = LocalCostEstimator(
            ProfilingSettings(warmup_iters=1, measure_iters=2),
            optimizer_state_slots=2,
        )
        attrs = LinearAttrs(out_channels=16, use_bias=False)
        c = est.estimate_operator_cost(attrs, [TensorShape((4, 8))])
        assert c.mem_bytes == 128 * 2 + 512 * 4 + 256 * 2

    def test_optimizer_state_slots_of(self):
        from flexflow_tpu.local_execution.cost_estimator import (
            optimizer_state_slots_of,
        )
        from flexflow_tpu.pcg.optimizer import (
            AdamOptimizerAttrs,
            SGDOptimizerAttrs,
        )

        assert optimizer_state_slots_of(AdamOptimizerAttrs(alpha=1e-3)) == 2
        assert optimizer_state_slots_of(SGDOptimizerAttrs(lr=0.1)) == 0
        assert (
            optimizer_state_slots_of(SGDOptimizerAttrs(lr=0.1, momentum=0.9))
            == 1
        )

    def test_mem_bytes_optimizer_slots_scale(self):
        # plain SGD (0 slots) prices the same op lighter than Adam (2)
        attrs = LinearAttrs(out_channels=16, use_bias=False)
        shape = TensorShape((4, 8))
        settings = ProfilingSettings(warmup_iters=1, measure_iters=2)
        sgd = LocalCostEstimator(settings, optimizer_state_slots=0)
        adam = LocalCostEstimator(settings, optimizer_state_slots=2)
        weight_bytes = 8 * 16 * 4
        assert (
            adam.estimate_operator_cost(attrs, [shape]).mem_bytes
            - sgd.estimate_operator_cost(attrs, [shape]).mem_bytes
            == 2 * weight_bytes
        )
