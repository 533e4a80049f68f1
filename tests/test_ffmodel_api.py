"""FFModel user API tests (reference: python interface E2E,
tests/python_interface_test.sh — mnist mlp via flexflow_python — and the
Tensor/Parameter numpy round-trips of flexflow_cffi.py)."""

import numpy as np
import pytest

from flexflow_tpu.core import (
    Activation,
    AdamOptimizer,
    FFConfig,
    FFModel,
    SGDOptimizer,
)


def build_mlp(cfg=None, in_dim=32, hidden=16, classes=4):
    m = FFModel(cfg or FFConfig(batch_size=8, epochs=1, print_freq=0))
    x = m.create_tensor([8, in_dim], name="x")
    t = m.dense(x, hidden, activation=Activation.RELU, name="fc1")
    out = m.dense(t, classes, name="out")
    return m, x, out


class TestBuildCompileFit:
    def test_fit_reduces_loss(self):
        m, x, out = build_mlp()
        m.compile(
            SGDOptimizer(lr=0.1),
            "sparse_categorical_crossentropy",
            metrics=["accuracy"],
        )
        rs = np.random.RandomState(0)
        xs = rs.randn(64, 32).astype(np.float32)
        ys = rs.randint(0, 4, 64)
        # overfit a tiny dataset: accuracy over epochs should rise
        first = m.fit(x=xs, y=ys, epochs=1, shuffle=False, verbose=False)
        last = m.fit(x=xs, y=ys, epochs=30, shuffle=False, verbose=False)
        assert last.accuracy >= first.accuracy
        assert last.accuracy > 0.5

    def test_eval(self):
        m, x, out = build_mlp()
        m.compile(AdamOptimizer(alpha=0.01), "sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        rs = np.random.RandomState(0)
        xs = rs.randn(16, 32).astype(np.float32)
        ys = rs.randint(0, 4, 16)
        perf = m.eval(x=xs, y=ys, batch_size=8)
        assert perf.train_all == 16
        assert 0.0 <= perf.accuracy <= 1.0


ONE_LOOP_BACKENDS = {
    "ModelTrainingInstance": dict(max_devices=1),
    "DataParallelTrainingInstance": dict(),
    "DistributedTrainingInstance": dict(search_budget=2),
    "PipelinedTrainingInstance": dict(
        search_budget=1, pipeline=True, force_strategy_seed="pp2m4xdp4"
    ),
}


@pytest.mark.parametrize("backend", sorted(ONE_LOOP_BACKENDS))
def test_fit_is_one_train_step_a_batch_on_every_backend(backend, monkeypatch):
    """The one fit loop asks a backend for `train_step`, once a batch, each
    time with the next key of the loop's stream, and for nothing else."""
    import jax

    cfg = FFConfig(
        batch_size=16, seed=0, print_freq=0, **ONE_LOOP_BACKENDS[backend]
    )
    m = FFModel(cfg)
    h = m.create_tensor([16, 16], name="x")
    for i in range(4):
        h = m.relu(m.dense(h, 16, name=f"fc{i}"))
    m.compile(
        AdamOptimizer(alpha=1e-2), "sparse_categorical_crossentropy",
        logit_tensor=h,
    )
    assert type(m.instance).__name__ == backend
    calls = []
    train_step = m.instance.train_step

    def counted(params, opt_state, batch, label, rng):
        calls.append(np.asarray(jax.random.key_data(rng)).tolist())
        return train_step(params, opt_state, batch, label, rng)

    monkeypatch.setattr(m.instance, "train_step", counted)
    rs = np.random.RandomState(0)
    xs = rs.randn(64, 16).astype(np.float32)
    ys = rs.randint(0, 16, 64)
    m.fit(xs, ys, epochs=2, shuffle=True, verbose=False)
    assert len(calls) == m._step_count == 2 * 4
    key = jax.random.fold_in(jax.random.PRNGKey(0), 0)
    expected = []
    for _ in range(8):
        key, step_key = jax.random.split(key)
        expected.append(np.asarray(jax.random.key_data(step_key)).tolist())
    assert calls == expected
    for v in jax.tree_util.tree_leaves(m.params):
        assert np.isfinite(np.asarray(v)).all()


class TestTensorRoundTrip:
    def test_get_set_weights(self):
        m, x, out = build_mlp()
        m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
        p = m.get_parameter_by_name("fc1.weight0")
        w = p.get_weights()
        assert w.shape == (32, 16)
        new = np.zeros_like(w)
        p.set_weights(m, new)
        assert np.allclose(p.get_weights(), 0.0)

    def test_tensor_dims(self):
        m, x, out = build_mlp()
        assert x.dims == (8, 32)
        assert out.dims == (8, 4)


class TestSteppedExecution:
    def test_forward_backward_update(self):
        """The legacy per-phase loop: forward / zero_gradients / backward /
        update (flexflow_cffi.py fit's internals, driven manually)."""
        m, x, out = build_mlp()
        m.compile(SGDOptimizer(lr=0.5), "sparse_categorical_crossentropy")
        rs = np.random.RandomState(0)
        xs = rs.randn(8, 32).astype(np.float32)
        ys = rs.randint(0, 4, 8)

        logits0 = m.forward({"x": xs})
        assert logits0.shape == (8, 4)
        before = m.get_parameter_by_name("fc1.weight0").get_weights()
        m.zero_gradients()
        m.backward(ys)
        m.update()
        after = m.get_parameter_by_name("fc1.weight0").get_weights()
        assert not np.allclose(before, after), "update did not change weights"

        # loss should drop after a few steps on the same batch
        def batch_loss():
            lg = m.forward({"x": xs})
            p = np.exp(lg - lg.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            return -np.mean(np.log(p[np.arange(8), ys] + 1e-9))

        l0 = batch_loss()
        for _ in range(10):
            m.zero_gradients()
            m.backward(ys)
            m.update()
        assert batch_loss() < l0


class TestGradAccumulation:
    def test_microbatch_accumulation(self):
        """backward() twice without zero_gradients accumulates weight grads
        (reference zero_gradients semantics)."""
        m, x, out = build_mlp()
        m.compile(SGDOptimizer(lr=0.0), "sparse_categorical_crossentropy")
        rs = np.random.RandomState(0)
        xs = rs.randn(8, 32).astype(np.float32)
        ys = rs.randint(0, 4, 8)
        m.forward({"x": xs})
        m.zero_gradients()
        m.backward(ys)
        g1 = {k: np.asarray(v) for k, v in m._backing.param_grads.items()}
        m.forward({"x": xs})
        m.backward(ys)  # no zero_gradients: should accumulate
        g2 = m._backing.param_grads
        for k in g1:
            assert np.allclose(g2[k], 2 * g1[k], atol=1e-5)


class TestMultiDevice:
    def test_data_parallel_fit(self):
        """--only-data-parallel path on the 8-device CPU mesh."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")
        cfg = FFConfig(batch_size=16, epochs=1, print_freq=0,
                       only_data_parallel=True)
        m = FFModel(cfg)
        x = m.create_tensor([16, 32], name="x")
        t = m.dense(x, 16, activation=Activation.RELU, name="fc1")
        out = m.dense(t, 4, name="out")
        m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
                  metrics=["accuracy"])
        rs = np.random.RandomState(0)
        xs = rs.randn(64, 32).astype(np.float32)
        ys = rs.randint(0, 4, 64)
        perf = m.fit(x=xs, y=ys, epochs=5, shuffle=False, verbose=False)
        assert perf.train_all == 64 * 5

    def test_searched_compile(self):
        """Unity-searched compile on the CPU mesh (search_budget > 0)."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")
        cfg = FFConfig(batch_size=16, epochs=1, print_freq=0, search_budget=2)
        m = FFModel(cfg)
        # deliberately unnamed input: auto-naming must keep the batch binding
        # stable through the Unity rewrite
        x = m.create_tensor([16, 32])
        t = m.dense(x, 16, use_bias=False, name="fc1")
        t = m.relu(t)
        out = m.dense(t, 4, use_bias=False, name="out")
        m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
        rs = np.random.RandomState(0)
        xs = rs.randn(32, 32).astype(np.float32)
        ys = rs.randint(0, 4, 32)
        perf = m.fit(x=xs, y=ys, epochs=2, shuffle=False, verbose=False)
        assert perf.train_all == 64

    def test_mcmc_searched_compile(self):
        """Legacy MCMC search mode end-to-end through FFModel
        (--search-algorithm mcmc; reference strategy_search_task,
        simulator.h:671)."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device")
        cfg = FFConfig(
            batch_size=16, epochs=1, print_freq=0, search_budget=2,
            search_algorithm="mcmc",
        )
        m = FFModel(cfg)
        x = m.create_tensor([16, 32])
        t = m.dense(x, 16, use_bias=False, name="fc1")
        t = m.relu(t)
        m.dense(t, 4, use_bias=False, name="out")
        m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
        prov = m.search_provenance or {}
        assert prov.get("explored", 0) > 0
        assert prov.get("estimated_ms", 0) <= prov.get("serial_ms", 0)
        rs = np.random.RandomState(0)
        xs = rs.randn(32, 32).astype(np.float32)
        ys = rs.randint(0, 4, 32)
        perf = m.fit(x=xs, y=ys, epochs=1, shuffle=False, verbose=False)
        assert perf.train_all == 32


def test_searched_compile_multi_output_graph():
    """Round-1 weak #8: a graph with an auxiliary head (second unconsumed
    output, like Inception's aux classifier) compiles through the searched
    path when the logit layer is named — layer names survive substitutions."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device")
    cfg = FFConfig(batch_size=8, epochs=1, print_freq=0, search_budget=3)
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    trunk = m.relu(m.dense(x, 32, use_bias=False, name="trunk"))
    m.dense(trunk, 4, use_bias=False, name="aux_head")  # unconsumed aux
    logits = m.dense(trunk, 4, use_bias=False, name="main_head")
    m.compile(
        SGDOptimizer(lr=0.1),
        "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance

    assert isinstance(m.instance, DistributedTrainingInstance)
    # the resolved logit has the full [batch, classes] shape
    shape = m.instance.pcg.tensor_shape(m.instance.logit_tensor)
    assert shape.sizes() == (8, 4)
    assert shape.shard_degrees() == (1, 1)
    rs = np.random.RandomState(0)
    perf = m.fit(
        rs.randn(16, 16).astype(np.float32),
        rs.randint(0, 4, 16),
        epochs=1,
        verbose=False,
    )
    assert perf.train_all == 16


def test_searched_logit_not_a_sharded_intermediate():
    """Review repro: when the named logit tensor is also consumed downstream
    and a rule repartitions that consumer, name resolution must not return
    the sharded intermediate — the resolved logit keeps the full shape."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device")
    cfg = FFConfig(batch_size=8, epochs=1, print_freq=0, search_budget=4)
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    logits = m.dense(x, 4, use_bias=False, name="main_head")
    m.relu(logits)  # downstream consumer -> second sink
    m.compile(
        SGDOptimizer(lr=0.1),
        "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance

    if isinstance(m.instance, DistributedTrainingInstance):
        pcg = m.instance.pcg
        shape = pcg.tensor_shape(m.instance.logit_tensor)
        assert shape.sizes() == (8, 4)
        assert all(d == 1 for d in shape.shard_degrees())
        # and it is the head's value, not the downstream relu's
        from flexflow_tpu.op_attrs import OperatorType, op_type_of

        producer = m.instance.logit_tensor.node
        assert op_type_of(pcg.op_attrs(producer)) != OperatorType.ELEMENT_UNARY
