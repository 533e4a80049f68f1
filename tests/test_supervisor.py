"""Fault-domain supervision (ISSUE 8): window watchdog, fault channel,
seeded fault schedules, and the supervised background threads.

The detection contract: a hung dispatch window raises a structured
WindowHangError (with a HangDiagnostic in the metrics JSONL) instead of
blocking forever; a writer thread death surfaces on the training thread
at the next step boundary (or `due()` call) instead of silently or at
final wait(); and every injected fault is deterministic per
(seed, site, step) so chaos runs are reproducible."""

import os
import tempfile
import threading
import time

import numpy as np
import pytest

from flexflow_tpu.core import FFConfig, FFModel
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
from flexflow_tpu.runtime import fault
from flexflow_tpu.runtime.fault import (
    FaultSchedule,
    SimulatedFault,
    inject_boundary_faults,
    inject_nonfinite_fault,
)
from flexflow_tpu.runtime.supervisor import (
    BackgroundFault,
    FaultChannel,
    HangDiagnostic,
    WindowHangError,
    WindowWatchdog,
)

BATCH = 16
STEPS_PER_EPOCH = 8
N = BATCH * STEPS_PER_EPOCH


def _data(seed=0):
    rs = np.random.RandomState(seed)
    return rs.randn(N, 32).astype(np.float32), rs.randint(0, 10, N)


def _build(metrics_dir="", ckpt_dir="", every=0, watchdog_factor=0.0,
           health_policy="off", budget=-1):
    cfg = FFConfig(
        batch_size=BATCH, seed=0, print_freq=0, search_budget=budget,
        metrics_dir=metrics_dir, checkpoint_dir=ckpt_dir,
        checkpoint_every_n_steps=every, checkpoint_backend="npz",
        watchdog_factor=watchdog_factor, health_policy=health_policy,
    )
    m = FFModel(cfg)
    x = m.create_tensor([BATCH, 32], name="x")
    h = m.dense(x, 32, use_bias=False, name="fc1")
    h = m.relu(h)
    logits = m.dense(h, 10, use_bias=False, name="head")
    m.compile(
        AdamOptimizerAttrs(alpha=1e-2),
        "sparse_categorical_crossentropy",
        metrics=["accuracy"],
        logit_tensor=logits,
    )
    return m


class TestFaultChannel:
    def test_post_and_raise_pending(self):
        ch = FaultChannel()
        assert ch.pending() == 0
        ch.raise_pending()  # empty channel is a no-op
        ch.post("writer", OSError("disk gone"))
        assert ch.pending() == 1
        with pytest.raises(BackgroundFault, match="writer") as ei:
            ch.raise_pending()
        assert isinstance(ei.value.original, OSError)
        assert isinstance(ei.value.__cause__, OSError)
        assert ch.pending() == 0
        # history survives the raise (post-mortem evidence)
        assert ch.history == [("writer", "OSError: disk gone")]

    def test_site_filtered_raise(self):
        ch = FaultChannel()
        ch.post("producer", ValueError("a"))
        ch.post("writer", OSError("b"))
        ch.raise_pending(site="missing")  # no match: no-op
        with pytest.raises(BackgroundFault, match="writer"):
            ch.raise_pending(site="writer")
        assert ch.pending() == 1  # the producer fault is still there
        with pytest.raises(BackgroundFault, match="producer"):
            ch.raise_pending()


class TestWindowWatchdog:
    def test_first_window_is_never_timed(self):
        w = WindowWatchdog(2.0, min_budget_ms=10.0, poll_interval_s=0.005)
        try:
            assert w.budget_ms() is None
            w.begin_window(1, 4)
            time.sleep(0.08)  # far beyond min budget: must NOT fire
            assert not w.fired
            w.end_window(4)
            assert w.estimate_ms is not None
        finally:
            w.close()

    def test_budget_from_rolling_estimate_times_factor(self):
        w = WindowWatchdog(10.0, min_budget_ms=1.0)
        try:
            w.begin_window(1, 1)
            time.sleep(0.03)
            w.end_window(1)
            est = w.estimate_ms
            assert est == pytest.approx(30.0, rel=0.8)
            assert w.budget_ms() == pytest.approx(est * 10.0)
        finally:
            w.close()

    def test_fires_and_records_diagnostic(self):
        fired = []
        w = WindowWatchdog(
            1.0, min_budget_ms=30.0, poll_interval_s=0.005,
            on_hang=fired.append,
        )
        try:
            w.begin_window(1, 4)
            w.end_window(4)  # estimate ~0ms -> budget = min_budget 30ms
            w.begin_window(5, 4)
            # the expiry injects WindowHangError into the watched (this)
            # thread asynchronously — the "real hang" path
            with pytest.raises(WindowHangError):
                deadline = time.time() + 5.0
                while time.time() < deadline:
                    time.sleep(0.01)
            assert w.fired
            assert len(fired) == 1
            diag = fired[0]
            assert isinstance(diag, HangDiagnostic)
            assert diag.last_completed_step == 4
            assert diag.window_base_step == 5
            assert diag.window_steps == 4
            assert diag.elapsed_ms >= diag.budget_ms
            d = diag.to_dict()
            assert d["device_kind"]
            assert d["thread_name"]
        finally:
            w.close()

    def test_fires_at_most_once(self):
        fired = []
        w = WindowWatchdog(
            1.0, min_budget_ms=10.0, poll_interval_s=0.005,
            on_hang=fired.append,
        )
        try:
            w.begin_window(1, 1)
            w.end_window(1)
            w.begin_window(2, 1)
            with pytest.raises(WindowHangError):
                time.sleep(0.2)
                time.sleep(0.2)
            time.sleep(0.2)  # plenty of time for a (forbidden) second fire
            assert len(fired) == 1
        finally:
            w.close()

    def test_simulate_hang_requires_armed_deadline(self):
        w = WindowWatchdog(2.0, min_budget_ms=10.0)
        try:
            with pytest.raises(RuntimeError, match="armed watchdog"):
                w.simulate_hang()  # no estimate yet -> no deadline
        finally:
            w.close()

    def test_simulate_hang_raises_structured_error(self):
        """The cooperative hang (fault site `hang`): blocks until the
        deadline fires, then raises WindowHangError carrying the
        diagnostic — on the WATCHED thread itself."""
        w = WindowWatchdog(1.0, min_budget_ms=25.0, poll_interval_s=0.005)
        try:
            w.begin_window(1, 4)
            w.end_window(4)
            w.begin_window(5, 4)
            t0 = time.time()
            with pytest.raises(WindowHangError) as ei:
                w.simulate_hang()
            assert time.time() - t0 < 5.0  # bounded, not forever
            assert ei.value.diagnostic is not None
            assert ei.value.diagnostic.window_base_step == 5
        finally:
            w.close()

    def test_open_spans_in_diagnostic_without_a_recorder(self):
        # a normal job installs no recorder: the diagnostic still names what
        # the hung thread was doing, from `record_span`'s own open list
        from flexflow_tpu.observability.trace import (
            active_recorder,
            open_span_names,
            record_span,
        )

        assert active_recorder() is None
        fired = []
        w = WindowWatchdog(
            1.0, min_budget_ms=20.0, poll_interval_s=0.005,
            on_hang=fired.append,
        )
        try:
            w.begin_window(1, 1)
            w.end_window(1)
            with pytest.raises(WindowHangError):
                with record_span("fit"), record_span("step"):
                    with record_span("dispatch"):
                        w.begin_window(2, 1)
                        deadline = time.time() + 5.0
                        while time.time() < deadline:
                            time.sleep(0.01)
            assert fired and fired[0].trace_spans == ["fit", "step", "dispatch"]
            # the exception left through three spans: the stack is clean
            assert open_span_names(threading.get_ident()) == []
        finally:
            w.close()

    def test_open_span_names_cross_thread(self):
        from flexflow_tpu.observability.trace import (
            open_span_names,
            record_span,
        )

        inside, release = threading.Event(), threading.Event()

        def work():
            with record_span("outer"), record_span("inner"):
                inside.set()
                release.wait(5.0)

        worker = threading.Thread(target=work)
        worker.start()
        try:
            assert inside.wait(5.0)
            assert open_span_names(worker.ident) == ["outer", "inner"]
            assert open_span_names(threading.get_ident()) == []
        finally:
            release.set()
            worker.join(5.0)
        assert not worker.is_alive()
        assert open_span_names(worker.ident) == []


class TestFaultSchedule:
    def test_parse_round_trip(self):
        s = FaultSchedule.parse(
            "seed=7;sites=ckpt_write,nonfinite,hang;rate=0.02"
        )
        assert s.seed == 7
        assert s.sites == {"ckpt_write", "nonfinite", "hang"}
        assert s.rate == 0.02

    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError, match="unknown fault sites"):
            FaultSchedule.parse("seed=1;sites=typo_site;rate=0.5")

    def test_unknown_key_rejected(self):
        with pytest.raises(ValueError, match="unknown fault-spec key"):
            FaultSchedule.parse("seed=1;sites=kill;rat=0.5")

    def test_decisions_are_deterministic_across_instances(self):
        a = FaultSchedule(seed=3, sites=frozenset({"kill"}), rate=0.1)
        b = FaultSchedule.parse("seed=3;sites=kill;rate=0.1")
        assert a.fire_steps("kill", 1, 200) == b.fire_steps("kill", 1, 200)
        assert a.fire_steps("kill", 1, 200)  # rate 0.1 fires in 200 steps

    def test_fire_once_is_one_shot_per_site_step(self):
        s = FaultSchedule(seed=3, sites=frozenset({"kill"}), rate=1.0)
        assert s.fire_once("kill", 5)
        assert not s.fire_once("kill", 5)  # retry of the same step: clean
        assert s.fire_once("kill", 6)
        assert s.fired_log == [("kill", 5), ("kill", 6)]

    def test_the_sites_are_those_of_the_one_loop(self):
        """No producer thread, no `h2d` site: a spec that still names it is
        refused loudly, never run fault-free."""
        assert fault.FAULT_SITES == ("ckpt_write", "nonfinite", "hang", "kill")
        with pytest.raises(ValueError, match="unknown fault sites.*h2d"):
            FaultSchedule.parse("seed=7;sites=ckpt_write,h2d;rate=0.02")

    def test_sites_not_listed_never_fire(self):
        s = FaultSchedule(seed=3, sites=frozenset({"kill"}), rate=1.0)
        assert not s.should_fire("hang", 5)

    def test_find_seed_pins_first_fire_in_range(self):
        seed = fault.find_seed("kill", 0.05, 6, 14)
        s = FaultSchedule(seed=seed, sites=frozenset({"kill"}), rate=0.05)
        fired = s.fire_steps("kill", 1, 14)
        assert fired and 6 <= fired[0] <= 14

    def test_find_seed_candidates(self):
        seed = fault.find_seed(
            "ckpt_write", 0.1, 1, 16, candidates=[8, 12]
        )
        s = FaultSchedule(
            seed=seed, sites=frozenset({"ckpt_write"}), rate=0.1
        )
        assert any(f in (8, 12) for f in s.fire_steps("ckpt_write", 1, 16))

    def test_env_spec_cached_with_state(self, monkeypatch):
        monkeypatch.setenv(fault.FAULT_SPEC_ENV, "seed=1;sites=kill;rate=1.0")
        a = fault.active_schedule()
        assert a is fault.active_schedule()  # same instance: state sticks
        a.fire_once("kill", 1)
        assert fault.active_schedule().fired_log == [("kill", 1)]
        monkeypatch.delenv(fault.FAULT_SPEC_ENV)
        assert fault.active_schedule() is None

    def test_install_overrides_env(self, monkeypatch):
        monkeypatch.setenv(fault.FAULT_SPEC_ENV, "seed=1;sites=kill;rate=1.0")
        mine = FaultSchedule(seed=9, sites=frozenset({"hang"}), rate=0.5)
        fault.install_schedule(mine)
        try:
            assert fault.active_schedule() is mine
        finally:
            fault.install_schedule(None)

    def test_inject_boundary_faults_kill(self):
        s = FaultSchedule(seed=0, sites=frozenset({"kill"}), rate=1.0)
        with pytest.raises(SimulatedFault):
            inject_boundary_faults(s, 4, 8)
        assert s.fired_log[0][0] == "kill"

    def test_inject_boundary_hang_without_watchdog_is_loud(self):
        s = FaultSchedule(seed=0, sites=frozenset({"hang"}), rate=1.0)
        with pytest.raises(RuntimeError, match="watchdog"):
            inject_boundary_faults(s, 0, 1, watchdog=None)


class TestNonfiniteSite:
    """The `nonfinite` chaos site in the one fit loop: the batch the
    firing step is about to consume gets a NaN, and the run-health policy
    owns the reaction."""

    def _schedule(self):
        return FaultSchedule(
            seed=fault.find_seed("nonfinite", 0.08, 6, 14),
            sites=frozenset({"nonfinite"}), rate=0.08,
        )

    def test_poisons_floating_inputs_of_the_firing_step_only(self):
        import jax.numpy as jnp

        sched = FaultSchedule(
            seed=0, sites=frozenset({"nonfinite"}), rate=1.0
        )
        batch = {
            "x": jnp.ones((4, 3), jnp.float32),
            "ids": jnp.ones((4, 3), jnp.int32),
        }
        assert inject_nonfinite_fault(None, 1, batch) is batch
        out = inject_nonfinite_fault(sched, 1, batch)
        x = np.asarray(out["x"])
        assert np.isnan(x[0, 0]) and np.isfinite(x.reshape(-1)[1:]).all()
        assert out["x"].sharding == batch["x"].sharding
        assert out["ids"] is batch["ids"]
        assert np.isfinite(np.asarray(batch["x"])).all()  # not in place
        # one transient a (site, step): a retry of the step runs clean
        assert inject_nonfinite_fault(sched, 1, batch) is batch
        assert sched.fired_log == [("nonfinite", 1)]

    @pytest.mark.parametrize("budget", [-1, 2], ids=["dp", "searched"])
    def test_mid_epoch_nonfinite_raises_at_the_firing_step(self, budget):
        from flexflow_tpu.observability.health import NonFiniteError

        sched = self._schedule()
        fault.install_schedule(sched)
        try:
            m = _build(health_policy="raise", budget=budget)
            xv, yv = _data()
            with pytest.raises(NonFiniteError):
                m.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        finally:
            fault.install_schedule(None)
        (site, step), = sched.fired_log
        assert site == "nonfinite" and 6 <= step <= 14
        assert m._step_count == step
        for v in m.params.values():
            assert np.isfinite(np.asarray(v)).all()

    @pytest.mark.parametrize("budget", [-1, 2], ids=["dp", "searched"])
    def test_mid_epoch_nonfinite_skipped_and_the_run_completes(self, budget):
        from flexflow_tpu.observability.metrics import read_events

        sched = self._schedule()
        fault.install_schedule(sched)
        mdir = tempfile.mkdtemp()
        try:
            m = _build(
                metrics_dir=mdir, health_policy="skip_step", budget=budget
            )
            xv, yv = _data()
            m.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
        finally:
            fault.install_schedule(None)
        assert m._step_count == 2 * STEPS_PER_EPOCH
        skipped = [e["step"] for e in read_events(mdir) if e["skipped"]]
        assert skipped == [step for _, step in sched.fired_log]
        assert m.health_monitor.skipped_steps == len(skipped) >= 1


class TestWriterFailureSurfacing:
    """Satellite: AsyncCheckpointWriter commit failures surface on the
    NEXT due() call, not only at final wait()."""

    def _manager(self, tmp_path):
        from flexflow_tpu.runtime.checkpoint import CheckpointManager

        return CheckpointManager(str(tmp_path), backend="npz")

    def test_transient_commit_failure_absorbed_by_retry(
        self, tmp_path, monkeypatch
    ):
        """The flaky-fs shape from tests/test_retry.py: two transient
        OSErrors on the commit rename are retried and the save lands —
        no error surfaces anywhere."""
        import flexflow_tpu.runtime.checkpoint as ckpt_mod
        from flexflow_tpu.runtime.checkpoint import TrainingCheckpointer

        real_replace = os.replace
        fails = {"n": 2}

        def flaky_replace(src, dst):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise OSError("transient commit")
            return real_replace(src, dst)

        monkeypatch.setattr(ckpt_mod.os, "replace", flaky_replace)
        monkeypatch.setattr(time, "sleep", lambda s: None)
        tc = TrainingCheckpointer(str(tmp_path), every_n_steps=4)
        import jax.numpy as jnp

        tc.snapshot(4, {"w": jnp.zeros(2)}, None, jnp.zeros(2, jnp.uint32),
                    0, 4)
        tc.finalize()
        assert fails["n"] == 0
        assert tc.manager.all_steps() == [4]

    def test_retry_exhausted_failure_surfaces_on_next_due(
        self, tmp_path, monkeypatch
    ):
        """A persistently failing commit exhausts the backoff on the
        writer thread; the NEXT due() raises it as a BackgroundFault
        naming the checkpoint_writer site (one step later, not at
        final wait)."""
        import flexflow_tpu.runtime.checkpoint as ckpt_mod
        from flexflow_tpu.runtime.checkpoint import TrainingCheckpointer

        def dead_replace(src, dst):
            raise OSError("filesystem is gone")

        monkeypatch.setattr(ckpt_mod.os, "replace", dead_replace)
        monkeypatch.setattr(time, "sleep", lambda s: None)
        ch = FaultChannel()
        tc = TrainingCheckpointer(
            str(tmp_path), every_n_steps=4, fault_channel=ch
        )
        import jax.numpy as jnp

        tc.snapshot(4, {"w": jnp.zeros(2)}, None, jnp.zeros(2, jnp.uint32),
                    0, 4)
        deadline = time.time() + 10.0
        while ch.pending() == 0 and time.time() < deadline:
            time.sleep(0.01)
        with pytest.raises(BackgroundFault, match="filesystem is gone"):
            tc.due(7, 8)

    def test_writer_without_channel_keeps_wait_semantics(
        self, tmp_path, monkeypatch
    ):
        """No channel installed (standalone writer use): the original
        surface-at-wait contract still holds, with the raw exception."""
        from flexflow_tpu.runtime.checkpoint import AsyncCheckpointWriter

        mgr = self._manager(tmp_path)

        def boom(*a, **kw):
            raise OSError("disk on fire")

        monkeypatch.setattr(mgr, "_write_host_state", boom)
        w = AsyncCheckpointWriter(mgr)
        import jax.numpy as jnp

        w.submit(1, {"w": jnp.zeros(2)})
        with pytest.raises(OSError, match="disk on fire"):
            w.wait()


class TestWatchdogEndToEnd:
    def test_hang_fires_within_budget_and_lands_in_jsonl(self, monkeypatch):
        """Acceptance: the watchdog fires within budget on a simulated
        hang, the run raises WindowHangError (instead of blocking
        forever), and the HangDiagnostic appears in the metrics JSONL."""
        from flexflow_tpu.observability.metrics import read_run_events

        sched = FaultSchedule(
            seed=fault.find_seed("hang", 0.08, 6, 14),
            sites=frozenset({"hang"}), rate=0.08,
        )
        fault.install_schedule(sched)
        mdir = tempfile.mkdtemp()
        try:
            m = _build(metrics_dir=mdir, watchdog_factor=3.0)
            xv, yv = _data()
            t0 = time.time()
            with pytest.raises(WindowHangError) as ei:
                m.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
            elapsed = time.time() - t0
        finally:
            fault.install_schedule(None)
        diag = ei.value.diagnostic
        assert diag is not None
        assert diag.elapsed_ms >= diag.budget_ms  # fired AT the budget
        assert elapsed < 120.0  # bounded, not forever
        events = read_run_events(mdir, "hang")
        assert len(events) == 1
        assert events[0]["window_base_step"] == diag.window_base_step
        assert events[0]["budget_ms"] == pytest.approx(
            diag.budget_ms, abs=0.01
        )
        assert events[0]["device_kind"]

    def test_watchdog_env_var_arms_without_config(self, monkeypatch):
        """FF_TPU_WATCHDOG supplies the factor when the config field is
        unset (the production knob on an existing launch script)."""
        monkeypatch.setenv("FF_TPU_WATCHDOG", "50.0")
        m = _build()
        sup = m._setup_supervision()
        try:
            assert sup.watchdog is not None
            assert sup.watchdog.factor == 50.0
        finally:
            sup.close()

    def test_no_watchdog_thread_by_default(self):
        m = _build()
        sup = m._setup_supervision()
        try:
            assert sup.watchdog is None
        finally:
            sup.close()

    def test_healthy_run_unaffected_by_watchdog(self):
        """A generous watchdog must not perturb training: same losses as
        an unsupervised run."""
        from flexflow_tpu.observability.metrics import read_events

        xv, yv = _data()
        d1 = tempfile.mkdtemp()
        m1 = _build(metrics_dir=d1)
        m1.fit(xv, yv, epochs=1, shuffle=True, verbose=False)
        d2 = tempfile.mkdtemp()
        m2 = _build(metrics_dir=d2, watchdog_factor=10000.0)
        m2.fit(xv, yv, epochs=1, shuffle=True, verbose=False)
        l1 = {e["step"]: e["loss"] for e in read_events(d1) if "step" in e}
        l2 = {e["step"]: e["loss"] for e in read_events(d2) if "step" in e}
        assert l1 == l2
        for p in m1.params:
            assert np.array_equal(
                np.asarray(m1.params[p]), np.asarray(m2.params[p])
            )
