"""Run-health telemetry tests (ISSUE 3 tentpole): metrics registry, the
per-step JSONL event stream and its pinned schema, the in-jit step
statistics, and the health monitor's warn/skip_step/raise policies with
first-bad-op localization.

The forced-NaN cases are the acceptance bar: a poisoned batch must be
detected, blamed on the earliest bad op by name, and — under skip_step —
dropped without corrupting parameters or optimizer state while training
continues.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.observability.health import (
    HEALTH_POLICIES,
    HealthMonitor,
    NonFiniteError,
    localize_first_nonfinite,
)
from flexflow_tpu.observability.metrics import (
    EVENT_SCHEMA_VERSION,
    STEP_EVENT_FIELDS,
    Histogram,
    MetricsRegistry,
    StepEventLog,
    global_norm,
    read_events,
    step_statistics,
)

BATCH = 16
HIDDEN = 32
CLASSES = 10


def build_model(metrics_dir="", health_policy="off", ndev_config=None,
                **config):
    cfg = FFConfig(
        batch_size=BATCH, seed=0, metrics_dir=metrics_dir,
        health_policy=health_policy, **config,
    )
    m = FFModel(cfg)
    x = m.create_tensor([BATCH, HIDDEN], name="x")
    h = m.dense(x, HIDDEN, name="fc1")
    h = m.relu(h)
    logits = m.dense(h, CLASSES, name="head")
    m.compile(
        SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    return m


def clean_data(steps=4):
    rs = np.random.RandomState(0)
    xv = rs.randn(BATCH * steps, HIDDEN).astype(np.float32)
    yv = rs.randint(0, CLASSES, BATCH * steps)
    return xv, yv


def poisoned_data(steps=4, bad_step=2):
    xv, yv = clean_data(steps)
    lo = BATCH * (bad_step - 1)
    xv[lo:lo + BATCH] = np.nan
    return xv, yv


# ---------------------------------------------------------------------------
# registry / histogram
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        reg.counter("steps").inc()
        reg.counter("steps").inc(2)
        reg.gauge("loss").set(1.5)
        for v in (1.0, 2.0, 3.0, 4.0):
            reg.histogram("ms").observe(v)
        snap = reg.snapshot()
        assert snap["counters"]["steps"] == 3
        assert snap["gauges"]["loss"] == 1.5
        h = snap["histograms"]["ms"]
        assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
        assert h["mean"] == pytest.approx(2.5)
        json.dumps(snap)  # artifact-serializable

    def test_histogram_reservoir_bounds_memory(self):
        h = Histogram(reservoir=8)
        for i in range(1000):
            h.observe(float(i))
        assert h.count == 1000
        assert len(h._samples) == 8
        assert h.percentile(50) is not None


# ---------------------------------------------------------------------------
# in-jit step statistics
# ---------------------------------------------------------------------------


class TestStepStatistics:
    def test_global_norm_matches_numpy(self):
        tree = {"a": jnp.arange(4.0), "b": jnp.ones((2, 3))}
        expected = math.sqrt(sum(float(jnp.sum(v * v)) for v in tree.values()))
        assert float(global_norm(tree)) == pytest.approx(expected, rel=1e-6)

    def test_statistics_inside_jit(self):
        old = {"w": jnp.ones((4,))}
        new = {"w": jnp.ones((4,)) * 1.1}
        grads = {"w": jnp.ones((4,)) * 0.5}

        @jax.jit
        def f(old, new, grads):
            return step_statistics(old, new, grads, jnp.float32(1.0))

        stats = f(old, new, grads)
        assert float(stats["grad_norm"]) == pytest.approx(1.0, rel=1e-5)
        assert float(stats["update_ratio"]) == pytest.approx(0.1, rel=1e-4)
        assert bool(stats["ok"])

    def test_nan_flags_not_ok(self):
        old = {"w": jnp.ones((4,))}
        new = {"w": jnp.full((4,), jnp.nan)}
        stats = step_statistics(old, new, {"w": jnp.full((4,), jnp.nan)},
                                jnp.float32(jnp.nan))
        assert not bool(stats["ok"])

    def test_optimizer_overflow_flags_not_ok(self):
        # finite loss and grads but a non-finite UPDATE (optimizer math
        # overflow): ok must trip, or guard_nonfinite would commit the
        # poisoned params and permanently stall a skip_step run
        old = {"w": jnp.ones((4,))}
        new = {"w": jnp.full((4,), jnp.inf)}
        stats = step_statistics(
            old, new, {"w": jnp.ones((4,))}, jnp.float32(1.0)
        )
        assert not bool(stats["ok"])


# ---------------------------------------------------------------------------
# JSONL event stream + schema stability
# ---------------------------------------------------------------------------

# Frozen copy of the v1 schema. If this assertion fires you changed the
# event format: bump EVENT_SCHEMA_VERSION and update every consumer
# (README "Run health and plan audit", dashboards, this test).
FROZEN_V1_FIELDS = (
    "schema", "step", "loss", "wallclock_ms", "tokens_per_s",
    "grad_norm", "param_norm", "update_ratio", "skipped", "nonfinite",
)


class TestEventSchema:
    def test_schema_is_frozen(self):
        assert EVENT_SCHEMA_VERSION == 1
        assert STEP_EVENT_FIELDS == FROZEN_V1_FIELDS

    def test_fit_emits_schema_conformant_events(self, tmp_path):
        d = str(tmp_path / "metrics")
        m = build_model(metrics_dir=d)
        xv, yv = clean_data()
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        events = read_events(d)
        assert len(events) == 4
        for i, e in enumerate(events):
            assert tuple(e.keys()) == FROZEN_V1_FIELDS
            assert e["schema"] == EVENT_SCHEMA_VERSION
            assert e["step"] == i + 1
            assert e["loss"] is not None and math.isfinite(e["loss"])
            assert e["wallclock_ms"] > 0
            assert e["tokens_per_s"] > 0
            assert e["grad_norm"] > 0
            assert e["param_norm"] > 0
            assert e["update_ratio"] > 0
            assert e["skipped"] is False and e["nonfinite"] is False
        # registry snapshot written on close
        with open(os.path.join(d, "metrics.json")) as f:
            snap = json.load(f)
        assert snap["counters"]["steps_total"] == 4
        assert snap["histograms"]["loss"]["count"] == 4

    def test_event_log_appends_and_counts_skips(self, tmp_path):
        d = str(tmp_path / "m")
        log = StepEventLog(d)
        log.emit(step=1, loss=1.0, wallclock_ms=2.0, tokens_per_s=10.0,
                 grad_norm=0.5, param_norm=3.0, update_ratio=0.01)
        log.emit(step=2, loss=float("nan"), wallclock_ms=2.0,
                 tokens_per_s=10.0, skipped=True, nonfinite=True)
        log.close()
        events = read_events(d)
        assert len(events) == 2
        # non-finite floats serialize as strings (strict-JSON safe)
        assert events[1]["loss"] == "nan"
        snap = log.registry.snapshot()
        assert snap["counters"]["steps_skipped"] == 1
        assert snap["counters"]["nonfinite_steps"] == 1

    def test_multi_fit_accumulates_registry_and_monitor(self, tmp_path):
        # the keras callback loop calls fit once per epoch: events.jsonl
        # appends, so metrics.json and the monitor counters must cover the
        # WHOLE stream, not the last fit
        d = str(tmp_path / "m")
        m = build_model(metrics_dir=d, health_policy="skip_step")
        xv, yv = poisoned_data(steps=2, bad_step=2)
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)  # trips once
        clean_x, clean_y = clean_data(steps=2)
        m.fit(clean_x, clean_y, epochs=1, shuffle=False, verbose=False)
        events = read_events(d)
        assert len(events) == 4
        assert [e["step"] for e in events] == [1, 2, 3, 4]
        with open(os.path.join(d, "metrics.json")) as f:
            snap = json.load(f)
        assert snap["counters"]["steps_total"] == 4
        assert snap["counters"]["steps_skipped"] == 1
        assert m.health_monitor.nonfinite_steps == 1

    def test_verbose_fit_prints_the_loss_every_print_freq_steps(
        self, capsys
    ):
        m = build_model(print_freq=3)
        xv, yv = clean_data(steps=7)
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=True)
        out = capsys.readouterr().out
        printed = [
            int(line.split("step ")[1].split(":")[0])
            for line in out.splitlines() if ": loss " in line
        ]
        assert printed == [3, 6]
        assert "ELAPSED TIME" in out and "samples/s" in out

    def test_no_metrics_dir_means_no_stats_collection(self):
        m = build_model()
        assert m.instance.collect_step_stats is False
        xv, yv = clean_data(steps=1)
        m.fit(xv, yv, epochs=1, verbose=False)
        assert m.instance.last_step_stats is None


# ---------------------------------------------------------------------------
# health monitor policies (the forced-NaN acceptance tests)
# ---------------------------------------------------------------------------


class TestHealthPolicies:
    def test_policy_names_are_pinned(self):
        assert HEALTH_POLICIES == ("off", "warn", "skip_step", "raise")
        with pytest.raises(AssertionError):
            HealthMonitor("explode")

    def test_skip_step_keeps_training_and_params_finite(self, tmp_path):
        d = str(tmp_path / "metrics")
        m = build_model(metrics_dir=d, health_policy="skip_step")
        assert m.instance.guard_nonfinite_updates is True
        xv, yv = poisoned_data(steps=4, bad_step=2)
        params_before = {k: np.asarray(v) for k, v in m.params.items()}
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        # the poisoned update never reached the parameters...
        for k, v in m.params.items():
            assert np.all(np.isfinite(np.asarray(v))), k
        # ...but training did continue past it (later steps updated params)
        assert any(
            not np.allclose(params_before[k], np.asarray(v))
            for k, v in m.params.items()
        )
        mon = m.health_monitor
        assert mon.nonfinite_steps == 1
        assert mon.skipped_steps == 1
        # the monitor names the first bad op: the dense consuming the NaN x
        assert mon.summary()["first_bad_op"] == "fc1"
        # skipped-step accounting lands in the event stream
        events = read_events(d)
        flags = [(e["skipped"], e["nonfinite"]) for e in events]
        assert flags == [
            (False, False), (True, True), (False, False), (False, False),
        ]
        # ONE counter family per fact: the event log's emit() counters are
        # the registry source of truth (the monitor keeps its own attrs)
        with open(os.path.join(d, "metrics.json")) as f:
            snap = json.load(f)
        assert snap["counters"]["steps_skipped"] == 1
        assert snap["counters"]["nonfinite_steps"] == 1

    def test_skip_step_preserves_opt_state(self):
        m = build_model(health_policy="skip_step")
        xv, yv = poisoned_data(steps=1, bad_step=1)
        opt_before = jax.tree_util.tree_map(np.asarray, m.opt_state)
        params_before = {k: np.asarray(v) for k, v in m.params.items()}
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        # the ONLY step was poisoned: params and optimizer state unchanged
        for k, v in m.params.items():
            np.testing.assert_array_equal(params_before[k], np.asarray(v))
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, np.asarray(b)),
            opt_before, m.opt_state,
        )

    def test_raise_names_first_bad_op(self):
        m = build_model(health_policy="raise")
        xv, yv = poisoned_data(steps=2, bad_step=1)
        with pytest.raises(NonFiniteError) as ei:
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        assert ei.value.report is not None
        assert ei.value.report.op_name == "fc1"
        assert ei.value.report.phase == "forward"
        assert "fc1" in str(ei.value)
        # raise guards too: params stayed finite for the post-mortem
        for k, v in m.params.items():
            assert np.all(np.isfinite(np.asarray(v))), k

    @pytest.mark.parametrize(
        "backend,budget",
        [("DataParallelTrainingInstance", -1),
         ("DistributedTrainingInstance", 2)],
        ids=["dp", "searched"],
    )
    def test_raise_stops_at_the_trip_with_the_state_before_it(
        self, backend, budget
    ):
        """`raise` on step 3: the loop stops there, `_step_count` says so,
        and the state is to the bit that of a run of the two steps before
        it (the guarded update never landed)."""
        m = build_model(health_policy="raise", search_budget=budget)
        assert type(m.instance).__name__ == backend
        xv, yv = poisoned_data(steps=4, bad_step=3)
        with pytest.raises(NonFiniteError) as ei:
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        assert ei.value.report.op_name == "fc1"
        assert m._step_count == 3
        ref = build_model(health_policy="raise", search_budget=budget)
        ref.fit(xv[: 2 * BATCH], yv[: 2 * BATCH], epochs=1, shuffle=False,
                verbose=False)
        for k, v in ref.params.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(m.params[k]), err_msg=k
            )
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            ref.opt_state, m.opt_state,
        )

    @pytest.mark.parametrize("budget", [-1, 2], ids=["dp", "searched"])
    def test_skip_step_run_equals_the_run_without_the_batch(self, budget):
        """A skipped step is no step: parameters and optimizer state end
        where a run that never saw the poisoned batch ends."""
        m = build_model(health_policy="skip_step", search_budget=budget)
        xv, yv = poisoned_data(steps=4, bad_step=3)
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        assert m._step_count == 4
        assert m.health_monitor.skipped_steps == 1
        keep = np.r_[0 : 2 * BATCH, 3 * BATCH : 4 * BATCH]
        ref = build_model(health_policy="skip_step", search_budget=budget)
        ref.fit(xv[keep], yv[keep], epochs=1, shuffle=False, verbose=False)
        assert ref.health_monitor.skipped_steps == 0
        for k, v in ref.params.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(m.params[k]), err_msg=k
            )

    def test_warn_continues_without_guard(self, capsys):
        m = build_model(health_policy="warn")
        xv, yv = poisoned_data(steps=2, bad_step=1)
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        assert m.health_monitor.nonfinite_steps >= 1
        assert m.health_monitor.skipped_steps == 0
        out = capsys.readouterr().out
        assert "[flexflow_tpu][health] WARN" in out

    def test_clean_run_trips_nothing(self):
        m = build_model(health_policy="skip_step")
        xv, yv = clean_data()
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        assert m.health_monitor.nonfinite_steps == 0
        assert m.health_monitor.skipped_steps == 0


# ---------------------------------------------------------------------------
# localizer
# ---------------------------------------------------------------------------


class TestLocalizer:
    def test_forward_blame(self):
        m = build_model()
        xv = np.full((BATCH, HIDDEN), np.nan, np.float32)
        report = localize_first_nonfinite(
            m.cg, m.params, {"x": xv},
            logit_tensor=m.instance.logit_tensor,
            label=np.zeros(BATCH, np.int32),
            loss_attrs=m.loss_attrs,
        )
        assert report.phase == "forward"
        assert report.op_name == "fc1"

    def test_bad_parameter_blame(self):
        m = build_model()
        # poison the HEAD weight: fc1/relu stay finite, head trips
        key = next(k for k in m.params if True)
        params = dict(m.params)
        head = m.get_parameter_by_name("head.weight0")
        k = f"n{head.handle.node.idx}"
        params[k] = jnp.full(params[k].shape, jnp.nan, params[k].dtype)
        report = localize_first_nonfinite(
            m.cg, params, {"x": np.zeros((BATCH, HIDDEN), np.float32)},
        )
        assert report.phase == "forward"
        assert report.op_name == "head.weight0"
        assert "parameter value" in report.detail

    def test_clean_replay_reports_unknown(self):
        m = build_model()
        report = localize_first_nonfinite(
            m.cg, m.params, {"x": np.zeros((BATCH, HIDDEN), np.float32)},
            logit_tensor=m.instance.logit_tensor,
            label=np.zeros(BATCH, np.int32),
            loss_attrs=m.loss_attrs,
        )
        assert report.phase == "unknown"
        assert report.op_name is None


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
