"""kernels/profiling.py coverage: the two-point slope measurement, the
noisy fallback (per_iter <= 0), and force_sync on array-free pytrees.

The two-point discipline exists because every timed window pays a FIXED
cost (the first dispatch's ramp and the final sync): per-iter time must come
from the slope between a short and a long run, not a single average. The
slope tests substitute a synthetic _timed_run so the arithmetic is pinned
exactly.
"""

import jax.numpy as jnp
import pytest

from flexflow_tpu.kernels import profiling
from flexflow_tpu.kernels.profiling import (
    ProfilingSettings,
    force_sync,
    profile_fn,
)


class TestTwoPointSlope:
    def test_fixed_latency_cancels(self, monkeypatch):
        # every run costs 0.5 s of fixed latency + 10 ms/iter; a single
        # average would report 510 ms/iter at n1=1 — the slope reports 10
        runs = []

        def fake_timed_run(fn, iters, args, kwargs):
            runs.append(iters)
            return 0.5 + 0.010 * iters

        monkeypatch.setattr(profiling, "_timed_run", fake_timed_run)
        ms = profile_fn(lambda: None, ProfilingSettings(warmup_iters=0))
        assert ms == pytest.approx(10.0)
        # defaults: measure_iters=5 -> short run 1 iter, long run 5
        assert runs == [1, 5]

    def test_window_sizes_follow_measure_iters(self, monkeypatch):
        runs = []

        def fake_timed_run(fn, iters, args, kwargs):
            runs.append(iters)
            return 0.010 * iters

        monkeypatch.setattr(profiling, "_timed_run", fake_timed_run)
        profile_fn(
            lambda: None, ProfilingSettings(warmup_iters=0, measure_iters=20)
        )
        assert runs == [5, 20]
        # degenerate settings still give two distinct window sizes
        runs.clear()
        profile_fn(
            lambda: None, ProfilingSettings(warmup_iters=0, measure_iters=1)
        )
        assert runs == [1, 2]

    def test_noisy_fallback_when_slope_non_positive(self, monkeypatch):
        # long run measured FASTER than the short one (scheduler noise):
        # the slope is negative, so the average of the long run stands
        def fake_timed_run(fn, iters, args, kwargs):
            return 0.5 - 0.010 * iters

        monkeypatch.setattr(profiling, "_timed_run", fake_timed_run)
        ms = profile_fn(lambda: None, ProfilingSettings(warmup_iters=0))
        # t2/n2 = (0.5 - 0.05)/5 s -> 90 ms
        assert ms == pytest.approx(90.0)

    def test_warmup_runs_before_measurement(self, monkeypatch):
        monkeypatch.setattr(
            profiling, "_timed_run", lambda fn, n, a, k: 0.010 * n
        )
        calls = {"n": 0}

        def fn():
            calls["n"] += 1

        profile_fn(fn, ProfilingSettings(warmup_iters=3))
        assert calls["n"] == 3  # only warmup hits fn; runs are synthetic

    def test_real_measurement_is_positive(self):
        x = jnp.ones((64, 64))
        ms = profile_fn(lambda: x @ x, ProfilingSettings())
        assert ms > 0


class TestForceSync:
    def test_empty_pytrees_are_noops(self):
        # no array leaf -> nothing to wait on, no error
        force_sync(None)
        force_sync({})
        force_sync([])
        force_sync(())
        force_sync({"a": None, "b": [1, "x", 2.5]})

    def test_scalar_python_leaves_are_skipped(self):
        force_sync([0, 1.5, "s", True])

    def test_array_pytree_syncs(self):
        out = {"loss": jnp.ones((3,)), "metrics": (jnp.zeros(()), None)}
        force_sync(out)  # completes the wait without error

    def test_zero_size_array_leaf(self):
        # zero-size leaves carry no device work to wait on
        force_sync(jnp.zeros((0,)))
        force_sync({"empty": jnp.zeros((0, 4)), "real": jnp.ones((2,))})


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
