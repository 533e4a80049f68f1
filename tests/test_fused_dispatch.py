"""Fused multi-step dispatch (steps_per_dispatch=K) + input pipeline tests.

Pins the fused execution engine's contract:

1. PARITY — a fused fit (lax.scan over a stacked batch window, RNG split
   inside the scan) reaches allclose-identical params, optimizer state and
   loss trajectory to the per-step loop on the same data, shuffle order and
   RNG stream, on both the DP and searched-PCG backends (K in {1, 4, 8};
   K=1 IS the per-step loop). Dropout in the DP model makes RNG-stream
   parity load-bearing, not incidental.
2. TELEMETRY GRANULARITY — the JSONL event stream still emits exactly one
   event per training step (loss/norm vectors read back once per window and
   re-emitted per step; window wall-clock apportioned equally).
3. HEALTH SEMANTICS — skip_step drops a poisoned step's update INSIDE the
   scan and keeps training (end state identical to the per-step loop);
   raise freezes the window at the trip, localizes the first bad op, and
   leaves params at their pre-trip values with _step_count at the trip.
4. PIPELINE VISIBILITY — the double-buffered producer records a
   host_to_device span and the fused step span carries fused_steps=K.
5. The slow-marked regression: fused K=8 sustains >= 1.3x images/s over
   K=1 on a dispatch-bound proxy on the same host (FF_TPU_FUSED_BASELINE=1
   is the in-process revert switch, mirroring test_search_perf.py).
"""

import os
import tempfile
import time

import jax
import numpy as np
import pytest

from flexflow_tpu.core import FFConfig, FFModel
from flexflow_tpu.observability.health import NonFiniteError
from flexflow_tpu.observability.metrics import read_events
from flexflow_tpu.observability.trace import TraceRecorder, set_recorder
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

BATCH = 16
STEPS_PER_EPOCH = 8
N = BATCH * STEPS_PER_EPOCH


def _data(seed=0):
    rs = np.random.RandomState(seed)
    xv = rs.randn(N, 32).astype(np.float32)
    yv = rs.randint(0, 10, N)
    return xv, yv


def _build(cfg, dropout=True, name_suffix=""):
    m = FFModel(cfg)
    x = m.create_tensor([BATCH, 32], name="x")
    h = m.dense(x, 32, use_bias=False, name="fc1" + name_suffix)
    h = m.relu(h)
    if dropout:
        # stochastic op: parity then proves the in-scan RNG split consumes
        # the identical key stream as the host-side per-step splits
        h = m.dropout(h, 0.1)
    logits = m.dense(h, 10, use_bias=False, name="head" + name_suffix)
    m.compile(
        AdamOptimizerAttrs(alpha=1e-2),
        "sparse_categorical_crossentropy",
        metrics=["accuracy"],
        logit_tensor=logits,
    )
    return m


def _fit(k, metrics_dir=None, budget=-1, dropout=True, epochs=2,
         data_seed=0, health_policy="off", poison_step=None, shuffle=True):
    cfg = FFConfig(
        batch_size=BATCH, seed=0, steps_per_dispatch=k,
        metrics_dir=metrics_dir or "", search_budget=budget,
        health_policy=health_policy, print_freq=0,
    )
    m = _build(cfg, dropout=dropout)
    xv, yv = _data(data_seed)
    if poison_step is not None:
        xv = xv.copy()
        xv[BATCH * poison_step : BATCH * (poison_step + 1)] = np.nan
    perf = m.fit(xv, yv, epochs=epochs, shuffle=shuffle, verbose=False)
    return m, perf


def _assert_state_parity(ref, other, rtol=1e-5, atol=1e-6):
    assert set(ref.params) == set(other.params)
    for key, v in ref.params.items():
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(other.params[key]),
            rtol=rtol, atol=atol, err_msg=f"param {key}",
        )
    ref_leaves = jax.tree_util.tree_leaves(ref.opt_state)
    other_leaves = jax.tree_util.tree_leaves(other.opt_state)
    assert len(ref_leaves) == len(other_leaves)
    for a, b in zip(ref_leaves, other_leaves):
        if hasattr(a, "shape"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=rtol, atol=atol
            )


class TestFusedParity:
    def test_dp_parity_k_1_4_8(self):
        """K in {1, 4, 8} on the DP backend: identical params, opt_state,
        and loss trajectory vs the per-step loop (same data, shuffle order,
        RNG stream). K=1 is the per-step loop itself. The window lengths
        divide (K=4) and equal (K=8) the 8-step epoch."""
        dirs = {k: tempfile.mkdtemp(prefix=f"fffuse{k}_") for k in (1, 4, 8)}
        runs = {k: _fit(k, metrics_dir=dirs[k])[0] for k in (1, 4, 8)}
        losses = {
            k: [e["loss"] for e in read_events(dirs[k])] for k in dirs
        }
        assert len(losses[1]) == STEPS_PER_EPOCH * 2
        for k in (4, 8):
            _assert_state_parity(runs[1], runs[k])
            np.testing.assert_allclose(
                losses[1], losses[k], rtol=1e-5, atol=1e-6,
                err_msg=f"loss trajectory K={k}",
            )

    def test_dp_tail_window_parity(self):
        """K=3 over an 8-step epoch: windows of 3+3+2 — the epoch-end tail
        runs as a smaller window, never spanning the reshuffle."""
        ref, _ = _fit(1)
        fused, _ = _fit(3)
        _assert_state_parity(ref, fused)

    def test_searched_pcg_parity_k8(self):
        """The searched-PCG backend (Unity winner, DistributedTrainingInstance)
        fused at K=8 matches its own per-step loop."""
        ref, _ = _fit(1, budget=2, dropout=False)
        fused, _ = _fit(8, budget=2, dropout=False)
        from flexflow_tpu.parallel.executor import DistributedTrainingInstance

        assert isinstance(ref.instance, DistributedTrainingInstance)
        assert isinstance(fused.instance, DistributedTrainingInstance)
        _assert_state_parity(ref, fused)

    def test_rng_stream_advances_like_per_step(self):
        """After a fused fit the model's future RNG consumption matches the
        per-step loop's: a second fit epoch on each lands on identical
        params (the scan's carry key is the host key, bitwise)."""
        ref, _ = _fit(1, epochs=3)
        fused, _ = _fit(4, epochs=3)
        _assert_state_parity(ref, fused)


class TestFusedTelemetry:
    def test_one_event_per_step_with_apportioned_wallclock(self):
        d = tempfile.mkdtemp(prefix="fffuse_ev_")
        _fit(4, metrics_dir=d, epochs=1)
        events = read_events(d)
        assert [e["step"] for e in events] == list(
            range(1, STEPS_PER_EPOCH + 1)
        )
        for e in events:
            assert e["wallclock_ms"] is not None and e["wallclock_ms"] > 0
            assert e["grad_norm"] is not None
            assert e["tokens_per_s"] is not None
            assert e["skipped"] is False and e["nonfinite"] is False
        # window time is apportioned equally: all 4 steps of one window
        # carry the same wallclock
        assert events[0]["wallclock_ms"] == pytest.approx(
            events[3]["wallclock_ms"]
        )

    def test_verbose_print_reports_from_window_stats(self, capsys):
        """print_freq boundaries inside a fused window report from the
        window's already-read loss vector (no extra device sync, and the
        printed step/loss match the per-step numbering)."""
        cfg = FFConfig(
            batch_size=BATCH, seed=0, steps_per_dispatch=4, print_freq=3,
        )
        m = _build(cfg)
        xv, yv = _data()
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=True)
        out = capsys.readouterr().out
        assert "step 3: loss" in out and "step 6: loss" in out


class TestFusedHealth:
    def test_skip_step_inside_window_matches_per_step(self):
        """A poisoned batch inside a window is skipped INSIDE the scan
        (pre-step params carried forward, later window steps keep
        training): counters, blame, and the end state all match the
        per-step loop on the same poisoned stream."""
        ref, _ = _fit(
            1, health_policy="skip_step", poison_step=5, shuffle=False,
            dropout=False, epochs=1,
        )
        fused, _ = _fit(
            4, health_policy="skip_step", poison_step=5, shuffle=False,
            dropout=False, epochs=1,
        )
        for m in (ref, fused):
            assert m.health_monitor.nonfinite_steps == 1
            assert m.health_monitor.skipped_steps == 1
            assert m.health_monitor.summary()["first_bad_op"] == "fc1"
            assert all(
                np.all(np.isfinite(np.asarray(v))) for v in m.params.values()
            )
        _assert_state_parity(ref, fused)

    def test_raise_freezes_window_and_localizes(self):
        """raise inside a fused window: the scan froze the remaining steps,
        params hold their pre-trip values (identical to where the per-step
        loop stops), _step_count points at the trip, and the blame replay
        names the first bad op."""
        ref_err = fused_err = None
        try:
            _fit(1, health_policy="raise", poison_step=5, shuffle=False,
                 dropout=False, epochs=1)
        except NonFiniteError as e:
            ref_err = e
        assert ref_err is not None
        try:
            _fit(4, health_policy="raise", poison_step=5, shuffle=False,
                 dropout=False, epochs=1)
        except NonFiniteError as e:
            fused_err = e
        assert fused_err is not None
        assert fused_err.report is not None
        assert fused_err.report.op_name == "fc1"

    def test_raise_step_count_and_pre_trip_params(self):
        cfg = FFConfig(
            batch_size=BATCH, seed=0, steps_per_dispatch=4,
            health_policy="raise", print_freq=0,
        )
        m = _build(cfg, dropout=False)
        xv, yv = _data()
        xv = xv.copy()
        xv[BATCH * 5 : BATCH * 6] = np.nan  # step 6, 2nd window's 2nd step
        with pytest.raises(NonFiniteError):
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        assert m._step_count == 6
        # params are the pre-trip values: finite, and identical to a clean
        # 5-step per-step run on the same stream
        ref = _build(
            FFConfig(batch_size=BATCH, seed=0, print_freq=0), dropout=False
        )
        ref.fit(xv[: BATCH * 5], yv[: BATCH * 5], epochs=1, shuffle=False,
                verbose=False)
        _assert_state_parity(ref, m)


class TestInputPipeline:
    def test_host_to_device_span_and_fused_step_span(self):
        m, _ = _fit(4, epochs=1)
        rec = TraceRecorder()
        prev = set_recorder(rec)
        try:
            xv, yv = _data(seed=1)
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        finally:
            set_recorder(prev)
        h2d = rec.spans_named("host_to_device")
        steps = rec.spans_named("step")
        assert len(h2d) == 2  # two K=4 windows over the 8-step epoch
        assert all(s.args.get("steps") == 4 for s in h2d)
        assert len(steps) == 2
        assert all(s.args.get("fused_steps") == 4 for s in steps)
        # one enqueue a window and no wait for the device under `step`
        dispatches = rec.spans_named("dispatch")
        assert len(dispatches) == 2
        assert [
            [c.name for c in rec.children_of(s)] for s in steps
        ] == [["dispatch"], ["dispatch"]]
        # the consumer's waits for its windows: one a window, one that
        # finds the epoch over
        assert len(rec.spans_named("fit/next_batch")) == 3

    def test_windowed_iterator_matches_batch_iterator_order(self):
        """The window stacks are exactly the per-step batches in order
        (shuffle-order parity is what the training parity stands on)."""
        from flexflow_tpu.core.dataloader import (
            BatchIterator,
            WindowedBatchIterator,
        )

        xv, yv = _data()
        mk = lambda: BatchIterator(  # noqa: E731
            {"x": xv}, yv.astype(np.int32), BATCH, shuffle=True, seed=7
        )
        per_step = [
            (np.asarray(b["x"]), np.asarray(l)) for b, l in mk()
        ]
        win_it = WindowedBatchIterator(mk(), 3, keep_host=True)
        stacked = []
        for _, _, host_win, k in win_it:
            for i in range(k):
                stacked.append((host_win[0]["x"][i], host_win[1][i]))
        assert len(stacked) == len(per_step)
        for (xa, ya), (xb, yb) in zip(per_step, stacked):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_prefetch_off_yields_same_windows(self):
        from flexflow_tpu.core.dataloader import (
            BatchIterator,
            WindowedBatchIterator,
        )

        xv, yv = _data()
        mk = lambda: BatchIterator(  # noqa: E731
            {"x": xv}, yv.astype(np.int32), BATCH, shuffle=True, seed=3
        )
        a = [
            (np.asarray(next(iter(w.values()))), k)
            for w, _, _, k in WindowedBatchIterator(mk(), 3, prefetch=True)
        ]
        b = [
            (np.asarray(next(iter(w.values()))), k)
            for w, _, _, k in WindowedBatchIterator(mk(), 3, prefetch=False)
        ]
        assert [k for _, k in a] == [k for _, k in b] == [3, 3, 2]
        for (wa, _), (wb, _) in zip(a, b):
            np.testing.assert_array_equal(wa, wb)


class TestFusedConfig:
    def test_steps_per_dispatch_validated(self):
        cfg = FFConfig(batch_size=BATCH, steps_per_dispatch=0)
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            _build(cfg)

    def test_baseline_env_reverts_to_per_step(self, monkeypatch, capsys):
        monkeypatch.setenv("FF_TPU_FUSED_BASELINE", "1")
        m, _ = _fit(8, epochs=1)
        out = capsys.readouterr().out
        assert "FF_TPU_FUSED_BASELINE" in out
        # the revert really ran the per-step loop: tracing a fresh fit
        # shows 8 un-fused step spans, none carrying fused_steps
        rec = TraceRecorder()
        prev = set_recorder(rec)
        try:
            xv, yv = _data(seed=2)
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        finally:
            set_recorder(prev)
        steps = rec.spans_named("step")
        assert len(steps) == STEPS_PER_EPOCH
        assert all("fused_steps" not in s.args for s in steps)

    def test_cli_flag_round_trip(self):
        import argparse

        p = argparse.ArgumentParser()
        FFConfig.add_args(p)
        args = p.parse_args(["--steps-per-dispatch", "8"])
        cfg = FFConfig.from_args(args)
        assert cfg.steps_per_dispatch == 8


@pytest.mark.slow
def test_fused_k8_speedup_over_per_step():
    """The acceptance bar: fused K=8 sustains >= 1.3x images/s over the
    per-step loop on a dispatch-bound proxy (tiny MLP whose per-step XLA
    program is far cheaper than its dispatch) on the same host.
    FF_TPU_FUSED_BASELINE=1 is the revert switch — the same FFModel/config
    runs both ways in-process."""
    batch, steps = 32, 384
    rs = np.random.RandomState(0)
    xv = rs.randn(batch * steps, 64).astype(np.float32)
    yv = rs.randint(0, 10, batch * steps)

    def run(env_baseline):
        if env_baseline:
            os.environ["FF_TPU_FUSED_BASELINE"] = "1"
        else:
            os.environ.pop("FF_TPU_FUSED_BASELINE", None)
        try:
            cfg = FFConfig(
                batch_size=batch, seed=0, steps_per_dispatch=8, print_freq=0,
            )
            m = FFModel(cfg)
            x = m.create_tensor([batch, 64], name="x")
            h = m.dense(x, 64, use_bias=False, name="fc1")
            h = m.relu(h)
            logits = m.dense(h, 10, use_bias=False, name="head")
            m.compile(
                AdamOptimizerAttrs(alpha=1e-3),
                "sparse_categorical_crossentropy",
                logit_tensor=logits,
            )
            # warmup epoch compiles the step/window programs
            m.fit(xv[: batch * 16], yv[: batch * 16], epochs=1,
                  shuffle=False, verbose=False)
            t0 = time.perf_counter()
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
            elapsed = time.perf_counter() - t0
            return batch * steps / elapsed
        finally:
            os.environ.pop("FF_TPU_FUSED_BASELINE", None)

    per_step_ips = run(env_baseline=True)
    fused_ips = run(env_baseline=False)
    speedup = fused_ips / per_step_ips
    assert speedup >= 1.3, (
        f"fused K=8 speedup {speedup:.2f}x < 1.3x "
        f"(per-step {per_step_ips:.0f} images/s, fused {fused_ips:.0f})"
    )
