"""Checkpoint integrity (ISSUE 8): per-leaf checksum manifest, corrupt-
checkpoint quarantine + auto-fallback, and the edge cases a real fleet
hits — zero-length leaves, manifest/file drift, concurrent writer tmp
leftovers, legacy layouts."""

import json
import os
import tempfile

import numpy as np
import pytest

from flexflow_tpu.core import FFConfig, FFModel
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
from flexflow_tpu.runtime.checkpoint import (
    CheckpointCorruptError,
    CheckpointError,
    CheckpointManager,
)
from flexflow_tpu.runtime.integrity import (
    IntegrityViolation,
    build_manifest,
    leaf_digest,
    parse_keys_json,
    verify_and_load_leaves,
)


def _tree(seed=0):
    rs = np.random.RandomState(seed)
    return {
        "w": rs.randn(4, 3).astype(np.float32),
        "b": rs.randn(3).astype(np.float32),
    }


def _save_steps(tmp_path, steps=(4, 8, 12)):
    mgr = CheckpointManager(str(tmp_path), backend="npz")
    for s in steps:
        mgr.save(s, _tree(s), {"step": np.int32(s)})
    return mgr


class TestManifest:
    def test_save_writes_integrity_manifest(self, tmp_path):
        mgr = _save_steps(tmp_path, steps=(1,))
        with open(tmp_path / "step_1" / "keys.json") as f:
            payload = json.load(f)
        assert payload["integrity"] == 1
        keys, leaves = parse_keys_json(payload)
        assert keys == sorted(keys)
        for key in keys:
            digest = leaves[key]
            assert set(digest) == {"crc32", "dtype", "shape", "nbytes"}
        mgr.restore()
        assert mgr.last_restore_report["verified"] is True
        assert mgr.last_restore_report["quarantined"] == []

    def test_leaf_digest_detects_single_bit_flip(self):
        a = np.arange(12, dtype=np.float32)
        d1 = leaf_digest(a)
        b = a.copy()
        b.view(np.uint8)[0] ^= 1
        assert leaf_digest(b)["crc32"] != d1["crc32"]

    def test_verify_and_load_round_trip(self, tmp_path):
        flat = {"a/x": np.ones(3, np.float32), "b": np.zeros(2, np.int32)}
        order = sorted(flat)
        for i, key in enumerate(order):
            np.save(tmp_path / f"arr_{i}.npy", flat[key])
        with open(tmp_path / "keys.json", "w") as f:
            json.dump(build_manifest(order, flat), f)
        got, verified = verify_and_load_leaves(str(tmp_path))
        assert verified
        assert set(got) == set(flat)
        assert np.array_equal(got["a/x"], flat["a/x"])


class TestCorruptionDetection:
    def test_bit_flip_raises_on_explicit_step(self, tmp_path):
        mgr = _save_steps(tmp_path)
        p = tmp_path / "step_12" / "arr_0.npy"
        raw = bytearray(p.read_bytes())
        raw[-1] ^= 0xFF
        p.write_bytes(bytes(raw))
        with pytest.raises(CheckpointCorruptError, match="crc32") as ei:
            mgr.restore(step=12)
        assert ei.value.step == 12
        # explicitly requested: NOT quarantined (the evidence stays put)
        assert (tmp_path / "step_12").exists()

    def test_zero_length_leaf_detected(self, tmp_path):
        """Satellite edge case: a truncated-to-empty .npy leaf is a
        structured corruption, not a raw numpy EOFError."""
        mgr = _save_steps(tmp_path, steps=(4,))
        (tmp_path / "step_4" / "arr_0.npy").write_bytes(b"")
        with pytest.raises(
            CheckpointCorruptError, match="zero-length"
        ) as ei:
            mgr.restore(step=4)
        assert ei.value.leaf is not None

    def test_manifest_listing_missing_leaf_detected(self, tmp_path):
        """Satellite edge case: keys.json names a leaf whose arr_i.npy is
        gone."""
        mgr = _save_steps(tmp_path, steps=(4,))
        os.remove(tmp_path / "step_4" / "arr_1.npy")
        with pytest.raises(
            CheckpointCorruptError, match="missing array file"
        ):
            mgr.restore(step=4)

    def test_unparseable_keys_json_detected(self, tmp_path):
        mgr = _save_steps(tmp_path, steps=(4,))
        (tmp_path / "step_4" / "keys.json").write_text("{not json")
        with pytest.raises(CheckpointCorruptError, match="keys.json"):
            mgr.restore(step=4)

    def test_dtype_drift_detected(self, tmp_path):
        mgr = _save_steps(tmp_path, steps=(4,))
        d = tmp_path / "step_4"
        with open(d / "keys.json") as f:
            payload = json.load(f)
        key0 = payload["keys"][0]
        np.save(
            d / "arr_0.npy",
            np.zeros(payload["leaves"][key0]["shape"], np.float64),
        )
        with pytest.raises(CheckpointCorruptError, match="dtype"):
            mgr.restore(step=4)


class TestAutoFallback:
    def test_latest_corrupt_falls_back_and_quarantines(self, tmp_path):
        mgr = _save_steps(tmp_path, steps=(4, 8, 12))
        (tmp_path / "step_12" / "arr_0.npy").write_bytes(b"")
        step, params, opt, _ = mgr.restore()
        assert step == 8
        assert np.array_equal(params["w"], _tree(8)["w"])
        report = mgr.last_restore_report
        assert report["restored_step"] == 8
        assert [q["step"] for q in report["quarantined"]] == [12]
        # quarantined, not deleted, and no longer counted
        assert (tmp_path / "step_12.corrupt").exists()
        assert mgr.all_steps() == [4, 8]
        assert mgr.latest_step() == 8

    def test_walks_past_multiple_corrupt_steps(self, tmp_path):
        mgr = _save_steps(tmp_path, steps=(4, 8, 12))
        for s in (8, 12):
            (tmp_path / f"step_{s}" / "arr_0.npy").write_bytes(b"x")
        step, _, _, _ = mgr.restore()
        assert step == 4
        assert [q["step"] for q in mgr.last_restore_report["quarantined"]] \
            == [12, 8]

    def test_all_corrupt_raises_structured_error(self, tmp_path):
        mgr = _save_steps(tmp_path, steps=(4, 8))
        for s in (4, 8):
            (tmp_path / f"step_{s}" / "arr_0.npy").write_bytes(b"")
        with pytest.raises(
            CheckpointError, match="survived integrity"
        ) as ei:
            mgr.restore()
        assert not isinstance(ei.value, CheckpointCorruptError)
        assert "8" in str(ei.value) and "4" in str(ei.value)

    def test_corrupt_quarantine_bounded_by_retention(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), backend="npz", max_to_keep=2)
        for s in (1, 2, 3, 4):
            (tmp_path / f"step_{s}.corrupt").mkdir()
        mgr.save(5, _tree())
        corrupt = sorted(
            n for n in os.listdir(tmp_path) if n.endswith(".corrupt")
        )
        assert corrupt == ["step_3.corrupt", "step_4.corrupt"]


class TestConcurrentWriters:
    def test_two_leftover_tmps_for_same_step_gcd(self, tmp_path):
        """Satellite edge case: crashed-writer step_N.tmp leftovers from
        two DEAD writers (unique suffixes AND the legacy bare .tmp name)
        never count as checkpoints and are GC'd by the next save."""
        import subprocess

        proc = subprocess.Popen(["true"])
        proc.wait()  # a pid that verifiably no longer exists
        dead = proc.pid
        mgr = CheckpointManager(str(tmp_path), backend="npz")
        (tmp_path / "step_9.tmp").mkdir()
        (tmp_path / f"step_9.tmp.{dead}_0").mkdir()
        (tmp_path / f"step_9.tmp.{dead}_1").mkdir()
        assert mgr.all_steps() == []
        mgr.save(1, _tree())
        left = sorted(os.listdir(tmp_path))
        assert left == ["step_1"]

    def test_live_foreign_writer_tmp_not_reaped(self, tmp_path):
        """A suffixed tmp whose owning PROCESS is still alive is a write
        in flight (the zombie-beside-restart scenario): GC must leave it
        for that writer's own commit."""
        import subprocess
        import sys as _sys

        proc = subprocess.Popen([_sys.executable, "-c", "input()"],
                                stdin=subprocess.PIPE)
        try:
            mgr = CheckpointManager(str(tmp_path), backend="npz")
            foreign = tmp_path / f"step_9.tmp.{proc.pid}_0"
            foreign.mkdir()
            mgr.save(1, _tree())
            assert foreign.exists(), "reaped a live writer's tmp"
        finally:
            proc.communicate(input=b"\n", timeout=30)

    def test_concurrent_same_step_saves_do_not_collide(self, tmp_path):
        """Two managers saving the SAME step concurrently each build a
        unique tmp dir; both commits succeed and the survivor is a
        complete, verifiable checkpoint."""
        import threading

        a = CheckpointManager(str(tmp_path), backend="npz")
        b = CheckpointManager(str(tmp_path), backend="npz")
        errs = []

        def save(mgr, seed):
            try:
                mgr.save(7, _tree(seed))
            except Exception as e:  # noqa: BLE001 - test collects
                errs.append(e)

        ts = [
            threading.Thread(target=save, args=(m, i))
            for i, m in enumerate((a, b))
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert errs == []
        step, params, _, _ = a.restore()
        assert step == 7
        assert a.last_restore_report["verified"] is True
        # the survivor is one of the two writers' trees, intact
        assert any(
            np.array_equal(params["w"], _tree(i)["w"]) for i in (0, 1)
        )


class TestLegacyLayouts:
    def test_legacy_state_npz_restores_with_one_warning(
        self, tmp_path, capsys
    ):
        """Satellite edge case: a pre-elastic state.npz checkpoint still
        restores — verified-as-legacy, warned exactly once per
        directory."""
        from flexflow_tpu.runtime import integrity as integ
        from flexflow_tpu.runtime.checkpoint import _flatten

        integ._LEGACY_WARNED.clear()
        d = tmp_path / "step_3"
        d.mkdir()
        flat = _flatten({"params": _tree()})
        np.savez(d / "state.npz", **flat)
        (d / "meta.json").write_text(
            json.dumps({"step": 3, "backend": "npz", "extra": {}})
        )
        mgr = CheckpointManager(str(tmp_path), backend="npz")
        step, params, _, _ = mgr.restore()
        assert step == 3
        assert np.array_equal(params["w"], _tree()["w"])
        assert mgr.last_restore_report["legacy"] is True
        assert mgr.last_restore_report["verified"] is False
        err = capsys.readouterr().err
        assert err.count("verified-as-legacy") == 1
        mgr.restore()  # second restore: no second warning
        assert capsys.readouterr().err.count("verified-as-legacy") == 0

    def test_legacy_list_keys_json_restores_with_warning(
        self, tmp_path, capsys
    ):
        from flexflow_tpu.runtime import integrity as integ

        integ._LEGACY_WARNED.clear()
        mgr = _save_steps(tmp_path, steps=(2,))
        kj = tmp_path / "step_2" / "keys.json"
        with open(kj) as f:
            payload = json.load(f)
        kj.write_text(json.dumps(payload["keys"]))  # strip to PR-7 layout
        step, params, _, _ = mgr.restore()
        assert step == 2
        assert np.array_equal(params["w"], _tree(2)["w"])
        assert mgr.last_restore_report["legacy"] is True
        assert "verified-as-legacy" in capsys.readouterr().err


class TestFallbackInFit:
    def _build(self, mdir, cdir):
        cfg = FFConfig(
            batch_size=16, seed=0, print_freq=0,
            metrics_dir=mdir, checkpoint_dir=cdir,
            checkpoint_every_n_steps=4, checkpoint_backend="npz",
        )
        m = FFModel(cfg)
        x = m.create_tensor([16, 32], name="x")
        h = m.dense(x, 32, use_bias=False, name="fc1")
        h = m.relu(h)
        logits = m.dense(h, 10, use_bias=False, name="head")
        m.compile(
            AdamOptimizerAttrs(alpha=1e-2),
            "sparse_categorical_crossentropy",
            logit_tensor=logits,
        )
        return m

    def test_truncated_checkpoint_auto_falls_back_on_resume(self):
        """Acceptance: a truncated newest checkpoint auto-falls back to
        the previous verified step on fit(resume=True), with the
        fallback recorded in search_provenance["recovery"] and the
        metrics JSONL."""
        from flexflow_tpu.observability.metrics import read_run_events

        rs = np.random.RandomState(0)
        xv = rs.randn(128, 32).astype(np.float32)
        yv = rs.randint(0, 10, 128)
        mdir, cdir = tempfile.mkdtemp(), tempfile.mkdtemp()
        m = self._build(mdir, cdir)
        m.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        newest = CheckpointManager(cdir, backend="npz").latest_step()
        assert newest == 16
        with open(os.path.join(cdir, f"step_{newest}", "arr_0.npy"), "w"):
            pass  # truncate
        m2 = self._build(mdir, cdir)
        m2.fit(xv, yv, epochs=2, shuffle=True, verbose=False, resume=True)
        fb = m2.search_provenance["recovery"]["checkpoint_fallback"]
        assert fb["restored_step"] == 12
        assert [q["step"] for q in fb["quarantined"]] == [16]
        assert os.path.isdir(os.path.join(cdir, "step_16.corrupt"))
        events = read_run_events(mdir, "checkpoint_fallback")
        assert len(events) == 1
        assert events[0]["restored_step"] == 12
        # training really continued from the fallback to completion
        assert m2._step_count == 16
