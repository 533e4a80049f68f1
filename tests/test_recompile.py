"""Dynamic recompilation (VERDICT round-1 missing #6).

Reference: RecompileState trigger/alter callbacks checked per iteration
(lib/runtime/src/recompile.h:26-41, recompile_on_condition model.h:107).
Canonical demo: batch-size growth mid-fit.
"""

import numpy as np
import pytest

from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.runtime.recompile import RecompileState, recompile_on_condition


def small_model(batch, seed=0):
    cfg = FFConfig(batch_size=batch, epochs=1, seed=seed, print_freq=0)
    m = FFModel(cfg)
    x = m.create_tensor([batch, 16], name="x")
    t = m.dense(x, 32, use_bias=False, name="fc1")
    t = m.relu(t)
    m.dense(t, 4, use_bias=False, name="out")
    m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
              metrics=["accuracy"])
    return m


def test_recompile_preserves_parameters():
    m = small_model(8)
    before = {k: np.asarray(v) for k, v in m.params.items()}
    m.recompile()
    for k, v in m.params.items():
        np.testing.assert_array_equal(np.asarray(v), before[k])


def test_recompile_on_condition_counts_and_alters():
    m = small_model(8)
    fired = RecompileState(
        trigger_func=lambda ff: ff.config.batch_size < 16,
        alter_func=lambda ff: setattr(ff.config, "batch_size", 16),
    )
    assert recompile_on_condition(m, fired)
    assert fired.recompilations == 1
    assert m.config.batch_size == 16
    # trigger now false: no further recompiles
    assert not recompile_on_condition(m, fired)
    assert fired.recompilations == 1


def test_fit_with_batch_growth():
    """Batch size doubles mid-training; fit rebuilds the iterator and keeps
    training with carried-over weights."""
    m = small_model(8)
    state = RecompileState(
        trigger_func=lambda ff: ff._step_count >= 2
        and ff.config.batch_size == 8,
        alter_func=lambda ff: setattr(ff.config, "batch_size", 16),
    )
    rs = np.random.RandomState(0)
    xs = rs.randn(64, 16).astype(np.float32)
    ys = rs.randint(0, 4, 64)
    perf = m.fit(xs, ys, epochs=2, shuffle=False, verbose=False,
                 recompile_state=state)
    assert state.recompilations == 1
    assert m.config.batch_size == 16
    assert perf.train_all > 0


def test_recompile_before_compile_rejected():
    m = FFModel(FFConfig(batch_size=4))
    with pytest.raises(AssertionError):
        m.recompile()


def test_recompile_state_binds_model_lazily():
    """RecompileState built without ff= (the reference constructor allows
    it) binds the model on the first recompile_on_condition call."""
    m = small_model(8)
    seen = []
    state = RecompileState(
        trigger_func=lambda ff: (seen.append(ff), False)[1],
        alter_func=lambda ff: None,
    )
    assert state.ff is None
    assert not recompile_on_condition(m, state)
    assert state.ff is m
    assert seen == [m]
    assert state.recompilations == 0


def test_recompile_preserves_step_count_and_opt_state():
    """Training progress (step counter, Adam moments) survives a recompile
    when shapes survive — the carry-over the elastic recovery path reuses."""
    from flexflow_tpu.core import AdamOptimizer

    cfg = FFConfig(batch_size=8, seed=0, print_freq=0)
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    t = m.dense(x, 32, use_bias=False, name="fc1")
    m.dense(t, 4, use_bias=False, name="out")
    m.compile(AdamOptimizer(alpha=0.01), "sparse_categorical_crossentropy")
    rs = np.random.RandomState(0)
    m.fit(rs.randn(24, 16).astype(np.float32), rs.randint(0, 4, 24),
          epochs=1, shuffle=False, verbose=False)
    assert m._step_count == 3
    moments_before = {
        k: np.asarray(v) for k, v in m.opt_state["m"].items()
    }
    step_before = int(np.asarray(m.opt_state["step"]))
    m.recompile()
    assert m._step_count == 3
    assert int(np.asarray(m.opt_state["step"])) == step_before
    for k, v in m.opt_state["m"].items():
        np.testing.assert_array_equal(np.asarray(v), moments_before[k])


def test_recompile_carry_over_keeps_scalars_uncommitted():
    """The carry-over must not commit the optimizer step scalar (or any
    uncommitted leaf) to the default device: a device-0-committed scalar
    conflicts with mesh-committed batches inside the next jitted step (the
    old test_fit_with_batch_growth failure mode)."""
    m = small_model(8)
    m.recompile()
    step = m.opt_state["step"]
    assert not getattr(step, "committed", False) or (
        len(step.sharding.device_set) > 1
    )


def test_batch_growth_ends_the_epoch_and_metrics_carry_over():
    """A recompile fired mid-epoch ends that epoch: the iterator is rebuilt
    at the new batch size, no batch is replayed, and the metric totals of
    the steps before it are in what `fit` returns."""
    cfg = FFConfig(batch_size=8, epochs=1, seed=0, print_freq=0)
    m = FFModel(cfg)
    x = m.create_tensor([8, 16], name="x")
    t = m.dense(x, 32, use_bias=False, name="fc1")
    t = m.relu(t)
    m.dense(t, 4, use_bias=False, name="out")
    m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
              metrics=["accuracy"])
    state = RecompileState(
        trigger_func=lambda ff: ff._step_count >= 2
        and ff.config.batch_size == 8,
        alter_func=lambda ff: setattr(ff.config, "batch_size", 16),
    )
    rs = np.random.RandomState(0)
    xs = rs.randn(64, 16).astype(np.float32)
    ys = rs.randint(0, 4, 64)
    perf = m.fit(xs, ys, epochs=2, shuffle=False, verbose=False,
                 recompile_state=state)
    assert state.recompilations == 1
    assert m.config.batch_size == 16
    # 2 steps of 8, then the second epoch whole at 16: 4 steps
    assert m._step_count == 2 + 4
    assert perf.train_all == 2 * 8 + 4 * 16


def test_profile_trace_dir_writes_xla_trace(tmp_path):
    """--profile-trace-dir captures a jax.profiler trace of fit (the Legion
    Prof -lg:prof analogue, SURVEY §5)."""
    import os

    m_cfg = FFConfig(
        batch_size=8, epochs=1, seed=0, print_freq=0,
        profile_trace_dir=str(tmp_path),
    )
    m = FFModel(m_cfg)
    x = m.create_tensor([8, 16], name="x")
    m.dense(x, 4, use_bias=False)
    m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
    rs = np.random.RandomState(0)
    m.fit(rs.randn(16, 16).astype(np.float32), rs.randint(0, 4, 16),
          epochs=1, verbose=False)
    files = [f for _, _, fs in os.walk(tmp_path) for f in fs]
    assert files, "no trace files written"
