"""Elastic training runtime (ISSUE 7): async checkpointing, deterministic
preemption recovery, degraded-grid re-search.

The chaos contract: a run killed mid-epoch via FF_TPU_FAULT_STEP and
resumed with fit(resume=True) produces a BITWISE-identical loss trajectory
(and bitwise final params) to an uninterrupted run — on both the DP and
searched-PCG backends, with dropout in the DP model so the restored RNG
stream position is load-bearing. The degraded-grid contract: shrinking the
device grid after a failure re-runs the machine-mapping search, re-shards
the restored checkpoint onto the new mesh, verifies the new plan, keeps
training, and records the transition in search_provenance["recovery"] + the
JSONL metrics stream.
"""

import os
import tempfile
import threading

import jax
import numpy as np
import pytest

from flexflow_tpu.core import FFConfig, FFModel
from flexflow_tpu.observability.metrics import read_events, read_run_events
from flexflow_tpu.observability.trace import TraceRecorder, set_recorder
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
from flexflow_tpu.runtime.checkpoint import CheckpointError
from flexflow_tpu.runtime.fault import SimulatedFault

BATCH = 16
STEPS_PER_EPOCH = 8
N = BATCH * STEPS_PER_EPOCH


def _data(seed=0):
    rs = np.random.RandomState(seed)
    return rs.randn(N, 32).astype(np.float32), rs.randint(0, 10, N)


def _build(budget=-1, metrics_dir="", ckpt_dir="", every=0,
           dropout=None, sync=False):
    if dropout is None:
        dropout = budget <= 0  # stochastic op on the DP backend only
    cfg = FFConfig(
        batch_size=BATCH, seed=0, print_freq=0,
        search_budget=budget, metrics_dir=metrics_dir,
        checkpoint_dir=ckpt_dir, checkpoint_every_n_steps=every,
        checkpoint_sync=sync,
    )
    m = FFModel(cfg)
    x = m.create_tensor([BATCH, 32], name="x")
    h = m.dense(x, 32, use_bias=False, name="fc1")
    h = m.relu(h)
    if dropout:
        h = m.dropout(h, 0.1)
    logits = m.dense(h, 10, use_bias=False, name="head")
    m.compile(
        AdamOptimizerAttrs(alpha=1e-2),
        "sparse_categorical_crossentropy",
        metrics=["accuracy"],
        logit_tensor=logits,
    )
    return m


def _losses_by_step(metrics_dir):
    """step -> loss over the stream; a resumed run re-emits the steps it
    re-ran, so later events win (they must be identical anyway)."""
    return {
        e["step"]: e["loss"] for e in read_events(metrics_dir) if "step" in e
    }


def _assert_params_bitwise(ref, other):
    assert set(ref.params) == set(other.params)
    for key in ref.params:
        a = np.asarray(ref.params[key])
        b = np.asarray(other.params[key])
        assert np.array_equal(a, b), f"param {key} not bitwise identical"


class TestChaosResume:
    """Kill mid-epoch, resume, compare against uninterrupted: bitwise."""

    @pytest.mark.parametrize(
        "budget", [-1, 2], ids=["dp-per-step", "searched-per-step"]
    )
    def test_kill_and_resume_bitwise_trajectory(self, monkeypatch, budget):
        xv, yv = _data()

        # uninterrupted reference — ALSO checkpointing, so the async writer
        # itself is proven not to perturb the trajectory
        d1, c1 = tempfile.mkdtemp(), tempfile.mkdtemp()
        m1 = _build(budget=budget, metrics_dir=d1, ckpt_dir=c1, every=8)
        m1.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        ref = _losses_by_step(d1)
        assert sorted(ref) == list(range(1, 2 * STEPS_PER_EPOCH + 1))

        # chaos run: fault crosses step 10 (mid-epoch-2), last checkpoint
        # at step 8 -> resume re-runs steps 9..16
        d2, c2 = tempfile.mkdtemp(), tempfile.mkdtemp()
        m2 = _build(budget=budget, metrics_dir=d2, ckpt_dir=c2, every=8)
        monkeypatch.setenv("FF_TPU_FAULT_STEP", "10")
        with pytest.raises(SimulatedFault):
            m2.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        monkeypatch.delenv("FF_TPU_FAULT_STEP")
        steps = sorted(n for n in os.listdir(c2) if n.startswith("step_"))
        assert steps == ["step_8"], (
            "the due snapshot must be durable when the fault propagates"
        )
        # the execution contract rides the checkpoint dir (ISSUE 14)
        assert "exec_contract.json" in os.listdir(c2)

        m2b = _build(budget=budget, metrics_dir=d2, ckpt_dir=c2, every=8)
        m2b.fit(xv, yv, epochs=2, shuffle=True, verbose=False, resume=True)
        got = _losses_by_step(d2)
        assert sorted(got) == sorted(ref)
        for s in ref:
            assert ref[s] == got[s], (
                f"loss at step {s} diverged: {ref[s]} vs {got[s]}"
            )
        _assert_params_bitwise(m1, m2b)
        # opt state too (bitwise down to the Adam moments)
        for a, b in zip(
            jax.tree_util.tree_leaves(m1.opt_state),
            jax.tree_util.tree_leaves(m2b.opt_state),
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_resumed_run_does_not_replay_committed_steps(self, monkeypatch):
        """The resumed fit starts AT the checkpoint: steps <= snapshot are
        not re-emitted (no double training on the same data)."""
        xv, yv = _data()
        d, c = tempfile.mkdtemp(), tempfile.mkdtemp()
        m = _build(metrics_dir=d, ckpt_dir=c, every=8)
        monkeypatch.setenv("FF_TPU_FAULT_STEP", "10")
        with pytest.raises(SimulatedFault):
            m.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        monkeypatch.delenv("FF_TPU_FAULT_STEP")
        before = len(
            [e for e in read_events(d) if "step" in e]
        )  # 10 events (steps 1..10)
        m2 = _build(metrics_dir=d, ckpt_dir=c, every=8)
        m2.fit(xv, yv, epochs=2, shuffle=True, verbose=False, resume=True)
        resumed = [e["step"] for e in read_events(d) if "step" in e][before:]
        assert resumed == list(range(9, 17))  # 9..16, nothing below 9

    def test_sync_checkpoint_path_resumes_identically(self, monkeypatch):
        """checkpoint_sync=True (the blocking A/B baseline) produces the
        same bitwise resume."""
        xv, yv = _data()
        d1 = tempfile.mkdtemp()
        m1 = _build(metrics_dir=d1)
        m1.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        d2, c2 = tempfile.mkdtemp(), tempfile.mkdtemp()
        m2 = _build(metrics_dir=d2, ckpt_dir=c2, every=8, sync=True)
        monkeypatch.setenv("FF_TPU_FAULT_STEP", "10")
        with pytest.raises(SimulatedFault):
            m2.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        monkeypatch.delenv("FF_TPU_FAULT_STEP")
        m2b = _build(metrics_dir=d2, ckpt_dir=c2, every=8, sync=True)
        m2b.fit(xv, yv, epochs=2, shuffle=True, verbose=False, resume=True)
        ref, got = _losses_by_step(d1), _losses_by_step(d2)
        assert ref == got
        _assert_params_bitwise(m1, m2b)


class TestResumeSemantics:
    def test_resume_without_checkpoint_dir_rejected(self):
        m = _build()
        xv, yv = _data()
        with pytest.raises(ValueError, match="resume=True"):
            m.fit(xv, yv, epochs=1, verbose=False, resume=True)

    def test_resume_on_empty_directory_cold_starts(self):
        """resume=True with nothing on disk is a cold start (the idiomatic
        'resume-or-start' entrypoint a preemptible job uses every launch)."""
        c = tempfile.mkdtemp()
        m = _build(ckpt_dir=c, every=4)
        xv, yv = _data()
        m.fit(xv, yv, epochs=1, verbose=False, resume=True)
        assert m._step_count == STEPS_PER_EPOCH

    def test_resume_from_weights_only_checkpoint_rejected(self):
        """save_checkpoint() snapshots carry no RNG/dataloader cursor:
        fit(resume=True) must refuse them loudly rather than silently
        replay data from a wrong position."""
        c = tempfile.mkdtemp()
        m = _build(ckpt_dir=c, every=0)
        m.save_checkpoint(c)
        xv, yv = _data()
        with pytest.raises(CheckpointError, match="resume metadata"):
            m.fit(xv, yv, epochs=1, verbose=False, resume=True)

    def test_resume_with_mismatched_epoch_offset_rejected(self, monkeypatch):
        """A snapshot taken under one epoch_offset must not resume under
        another: the iterator/rng would replay a different shuffle stream
        — silently divergent, never bitwise. Loud error instead."""
        c = tempfile.mkdtemp()
        m = _build(ckpt_dir=c, every=4)
        xv, yv = _data()
        monkeypatch.setenv("FF_TPU_FAULT_STEP", "6")
        with pytest.raises(SimulatedFault):
            m.fit(xv, yv, epochs=1, verbose=False, epoch_offset=1)
        monkeypatch.delenv("FF_TPU_FAULT_STEP")
        m2 = _build(ckpt_dir=c, every=4)
        with pytest.raises(CheckpointError, match="epoch_offset"):
            m2.fit(xv, yv, epochs=1, verbose=False, resume=True)
        # the original offset resumes fine
        m2.fit(xv, yv, epochs=1, verbose=False, resume=True, epoch_offset=1)
        assert m2._step_count == STEPS_PER_EPOCH

    def test_failed_resume_does_not_leak_writer_thread(self):
        """resume_state() raising (weights-only checkpoint) must retire the
        background writer it already started — one leaked daemon thread
        per failed resume-or-start launch adds up on a preemptible job."""
        c = tempfile.mkdtemp()
        m = _build(ckpt_dir=c, every=0)
        m.save_checkpoint(c)
        xv, yv = _data()
        before = {
            t.name for t in threading.enumerate()
            if t.name.startswith("ff-checkpoint-writer")
        }
        for _ in range(3):
            with pytest.raises(CheckpointError):
                m.fit(xv, yv, epochs=1, verbose=False, resume=True)
        after = [
            t for t in threading.enumerate()
            if t.name.startswith("ff-checkpoint-writer")
            and t.name not in before
        ]
        assert after == [], f"leaked writer threads: {after}"

    def test_fit_kwargs_override_config(self):
        """fit(checkpoint_dir=..., checkpoint_every_n_steps=...) wires the
        elastic runtime without config fields."""
        c = tempfile.mkdtemp()
        m = _build()  # no checkpointing configured
        xv, yv = _data()
        m.fit(
            xv, yv, epochs=1, verbose=False,
            checkpoint_dir=c, checkpoint_every_n_steps=4,
        )
        from flexflow_tpu.runtime.checkpoint import CheckpointManager

        assert CheckpointManager(c, backend="npz").all_steps() == [4, 8]


class TestBatchIteratorCursor:
    """The one batch iterator's resume cursor: what `fit(resume=True)`
    stands on."""

    def _it(self):
        from flexflow_tpu.core.dataloader import BatchIterator

        xv, yv = _data()
        return BatchIterator(
            {"x": xv}, yv.astype(np.int32), BATCH, shuffle=True, seed=7
        )

    @staticmethod
    def _epoch(it):
        return [(np.asarray(b["x"]), np.asarray(l)) for b, l in it]

    def test_resume_skip_yields_the_tail_of_the_same_permutation(self):
        whole = self._it()
        first, second = self._epoch(whole), self._epoch(whole)
        resumed = self._it()
        resumed.set_resume_skip(3)
        tail = self._epoch(resumed)
        assert len(tail) == STEPS_PER_EPOCH - 3
        for (xa, ya), (xb, yb) in zip(first[3:], tail):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)
        # the skip is one shot: the next epoch is whole, and the same
        for (xa, ya), (xb, yb) in zip(second, self._epoch(resumed)):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_advance_epochs_burns_the_permutations_of_finished_epochs(self):
        whole = self._it()
        self._epoch(whole)
        second = self._epoch(whole)
        resumed = self._it()
        resumed.advance_epochs(1)
        got = self._epoch(resumed)
        assert len(got) == len(second) == STEPS_PER_EPOCH
        for (xa, ya), (xb, yb) in zip(second, got):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)


class TestCheckpointTrace:
    def test_async_checkpoint_span_on_writer_thread(self):
        """The `checkpoint` span lands on the Chrome trace, on a DIFFERENT
        thread row than the consumer's step spans — the serialization is
        visibly off the critical path, overlapped with the next steps."""
        c = tempfile.mkdtemp()
        m = _build(ckpt_dir=c, every=4)
        xv, yv = _data()
        rec = TraceRecorder()
        prev = set_recorder(rec)
        try:
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        finally:
            set_recorder(prev)
        ckpt_spans = rec.spans_named("checkpoint")
        step_spans = rec.spans_named("step")
        assert len(ckpt_spans) == 2  # steps 4 and 8 on the 8-step epoch
        assert all(s.args.get("mode") == "async" for s in ckpt_spans)
        assert step_spans
        main_tids = {s.tid for s in step_spans}
        assert all(s.tid not in main_tids for s in ckpt_spans)
        assert all(s.tid != threading.get_ident() for s in ckpt_spans)

    def test_sync_checkpoint_span_on_main_thread(self):
        c = tempfile.mkdtemp()
        m = _build(ckpt_dir=c, every=4, sync=True)
        xv, yv = _data()
        rec = TraceRecorder()
        prev = set_recorder(rec)
        try:
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        finally:
            set_recorder(prev)
        ckpt_spans = rec.spans_named("checkpoint")
        assert len(ckpt_spans) == 2
        assert all(s.args.get("mode") == "sync" for s in ckpt_spans)
        assert all(s.tid == threading.get_ident() for s in ckpt_spans)


class TestDegradedGridRecovery:
    def _train_one_epoch(self, budget, mdir, cdir):
        m = _build(budget=budget, metrics_dir=mdir, ckpt_dir=cdir, every=4,
            dropout=False,
        )
        xv, yv = _data()
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        return m, xv, yv

    def test_searched_backend_researches_and_continues(self, monkeypatch):
        """Device failure on the searched backend: the re-entry path
        re-runs the Unity machine-mapping search against the shrunken
        grid, restores the checkpoint onto the new mesh, verifies the new
        plan (FF_TPU_VERIFY on), continues training, and records the
        transition in provenance + the metrics stream."""
        from flexflow_tpu.parallel.executor import DistributedTrainingInstance
        from flexflow_tpu.runtime.recompile import (
            active_num_devices,
            recover_from_grid_change,
        )

        monkeypatch.setenv("FF_TPU_VERIFY", "1")
        mdir, cdir = tempfile.mkdtemp(), tempfile.mkdtemp()
        m, xv, yv = self._train_one_epoch(2, mdir, cdir)
        assert isinstance(m.instance, DistributedTrainingInstance)
        assert active_num_devices(m) == 8
        loss_before = _losses_by_step(mdir)

        rec = recover_from_grid_change(
            m, 4, checkpoint_dir=cdir, reason="simulated_device_failure"
        )
        assert rec["old_grid"]["num_devices"] == 8
        assert rec["new_grid"]["num_devices"] == 4
        assert rec["re_searched"] is True
        assert rec["restored_step"] == STEPS_PER_EPOCH
        assert rec["recovery_seconds"] > 0
        assert active_num_devices(m) == 4
        prov = m.search_provenance
        assert prov["recovery"] is rec
        # the re-searched plan passed static verification for the NEW grid
        assert prov["verify"]["clean"] is True
        # restored params really live on the shrunken mesh
        some_param = next(iter(m.params.values()))
        assert len(some_param.sharding.device_set) <= 4

        # training continues on the degraded grid
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False, epoch_offset=1)
        assert m._step_count == 2 * STEPS_PER_EPOCH
        loss_after = _losses_by_step(mdir)
        assert len(loss_after) == 2 * STEPS_PER_EPOCH
        assert all(np.isfinite(v) for v in loss_after.values())
        assert loss_before.items() <= loss_after.items()

        # and the JSONL metrics stream carries the recovery event
        events = read_run_events(mdir, "recovery")
        assert len(events) == 1
        assert events[0]["new_grid"]["num_devices"] == 4
        assert events[0]["reason"] == "simulated_device_failure"

    def test_dp_backend_recovers_without_search(self):
        """The DP backend has no search to re-run, but the same re-entry
        path re-shards and continues (re_searched records False — the
        decision is in the record either way)."""
        from flexflow_tpu.runtime.recompile import (
            active_num_devices,
            recover_from_grid_change,
        )

        mdir, cdir = tempfile.mkdtemp(), tempfile.mkdtemp()
        m, xv, yv = self._train_one_epoch(-1, mdir, cdir)
        rec = recover_from_grid_change(m, 2, checkpoint_dir=cdir)
        assert rec["re_searched"] is False
        assert active_num_devices(m) == 2
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False, epoch_offset=1)
        assert m._step_count == 2 * STEPS_PER_EPOCH

    def test_recovery_rejects_impossible_grid(self):
        from flexflow_tpu.runtime.recompile import recover_from_grid_change

        mdir, cdir = tempfile.mkdtemp(), tempfile.mkdtemp()
        m, _, _ = self._train_one_epoch(-1, mdir, cdir)
        with pytest.raises(ValueError, match="new_num_devices"):
            recover_from_grid_change(m, 0)
        with pytest.raises(ValueError, match="new_num_devices"):
            recover_from_grid_change(m, len(jax.devices()) + 1)

    def test_max_devices_caps_compile(self):
        """config.max_devices is honored by a fresh compile too (the knob
        the recovery path turns)."""
        cfg = FFConfig(batch_size=BATCH, seed=0, max_devices=2, print_freq=0)
        m = FFModel(cfg)
        x = m.create_tensor([BATCH, 32], name="x")
        logits = m.dense(x, 10, use_bias=False, name="head")
        m.compile(
            AdamOptimizerAttrs(alpha=1e-2),
            "sparse_categorical_crossentropy",
            logit_tensor=logits,
        )
        from flexflow_tpu.runtime.recompile import active_num_devices

        assert active_num_devices(m) == 2
