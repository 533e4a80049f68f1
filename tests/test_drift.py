"""Drift telemetry tests (ISSUE 18): the shared nearest-rank percentile
convention, the incremental event tail, window aggregation, the
band/run-length drift detector, ReplanAdvisory construction + the frozen
`drift` event schema, monitor thread supervision, and the ffreport CLI
exit contract.

Everything here runs on synthetic event streams — no model compile, no
search — so the whole module stays cheap inside the tier-1 budget. The
end-to-end searched-fit path (advisory fires under an injected slowdown,
candidate matches a cold re-search) is what DRIFT_r18.json records.
"""

import json
import math
import os
import subprocess
import sys
import time

import pytest

from flexflow_tpu.observability.drift import (
    DRIFT_EVENT_FIELDS,
    DRIFT_SCHEMA_VERSION,
    DriftDetector,
    DriftMonitor,
    WindowAggregator,
    WindowStat,
)
from flexflow_tpu.observability.metrics import (
    EVENT_SCHEMA_VERSION,
    Histogram,
    nearest_rank_percentile,
    read_events,
    tail_events,
)
from flexflow_tpu.runtime.supervisor import FaultChannel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# shared percentile convention (satellite 1)
# ---------------------------------------------------------------------------


def _naive_nearest_rank(samples, q):
    """The textbook definition, written independently of the helper."""
    n = len(samples)
    rank = max(1, math.ceil(q / 100.0 * n))  # 1-based nearest rank
    return sorted(samples)[min(rank, n) - 1]


class TestNearestRank:
    def test_matches_textbook_definition_over_grid(self):
        for n in (1, 2, 3, 5, 8, 100):
            samples = [float(i * 3 % n + i) for i in range(n)]
            for q in (0, 1, 25, 50, 75, 90, 99, 100):
                assert nearest_rank_percentile(
                    sorted(samples), q
                ) == _naive_nearest_rank(samples, q), (n, q)

    def test_two_sample_p50_is_lower_sample(self):
        # the case Histogram and serving once disagreed on: nearest-rank
        # p50 of {1, 3} is 1.0 (the lower sample), never the 2.0 mean
        assert nearest_rank_percentile([1.0, 3.0], 50) == 1.0

    def test_empty_is_none(self):
        assert nearest_rank_percentile([], 50) is None

    def test_histogram_routes_through_shared_helper(self):
        h = Histogram()
        for v in (5.0, 1.0, 3.0, 9.0, 7.0):
            h.observe(v)
        for q in (0, 50, 90, 95, 100):
            assert h.percentile(q) == nearest_rank_percentile(
                [1.0, 3.0, 5.0, 7.0, 9.0], q
            )

    def test_serving_summary_uses_same_convention(self):
        # serving's summary() percentiles route through the same helper —
        # pin the import so the subsystems cannot drift apart again
        import inspect

        from flexflow_tpu.serving import engine

        assert "nearest_rank_percentile" in inspect.getsource(engine)


# ---------------------------------------------------------------------------
# incremental event tail (satellite 2)
# ---------------------------------------------------------------------------


def _append(mdir, text):
    with open(os.path.join(mdir, "events.jsonl"), "a") as f:
        f.write(text)


class TestTailEvents:
    def test_missing_file_is_empty_stream(self, tmp_path):
        events, cursor = tail_events(str(tmp_path), 0)
        assert events == [] and cursor == 0

    def test_incremental_cursor(self, tmp_path):
        d = str(tmp_path)
        _append(d, '{"step": 1}\n{"step": 2}\n')
        events, cur = tail_events(d, 0)
        assert [e["step"] for e in events] == [1, 2]
        events2, cur2 = tail_events(d, cur)
        assert events2 == [] and cur2 == cur  # idle poll: stat fast-path
        _append(d, '{"step": 3}\n')
        events3, cur3 = tail_events(d, cur)
        assert [e["step"] for e in events3] == [3] and cur3 > cur

    def test_torn_write_not_consumed_until_complete(self, tmp_path):
        d = str(tmp_path)
        _append(d, '{"step": 1}\n{"step": 2, "wall')  # writer mid-write
        events, cur = tail_events(d, 0)
        assert [e["step"] for e in events] == [1]
        # the torn tail was left alone: completing it yields the event
        _append(d, 'clock_ms": 5.0}\n')
        events2, cur2 = tail_events(d, cur)
        assert events2 == [{"step": 2, "wallclock_ms": 5.0}]
        assert cur2 > cur

    def test_corrupt_complete_line_skipped(self, tmp_path):
        d = str(tmp_path)
        _append(d, '{"step": 1}\nnot json at all\n{"step": 2}\n')
        events, cur = tail_events(d, 0)
        assert [e["step"] for e in events] == [1, 2]
        # the cursor moved PAST the corrupt line — it is never retried
        assert tail_events(d, cur)[0] == []

    def test_truncated_stream_restarts(self, tmp_path):
        d = str(tmp_path)
        _append(d, '{"step": 1}\n{"step": 2}\n')
        _, cur = tail_events(d, 0)
        with open(os.path.join(d, "events.jsonl"), "w") as f:
            f.write('{"step": 9}\n')  # rotation: file shrank
        events, _ = tail_events(d, cur)
        assert [e["step"] for e in events] == [9]


# ---------------------------------------------------------------------------
# window aggregation
# ---------------------------------------------------------------------------


def _step(step, ms, tps=None):
    e = {"schema": 1, "step": step, "wallclock_ms": ms}
    if tps is not None:
        e["tokens_per_s"] = tps
    return e


class TestWindowAggregator:
    def test_windows_of_k_with_means(self):
        agg = WindowAggregator(window_steps=2)
        assert agg.add(_step(1, 10.0)) is None
        w = agg.add(_step(2, 20.0))
        assert isinstance(w, WindowStat)
        assert w.index == 0 and (w.first_step, w.last_step) == (1, 2)
        assert w.mean_ms == 15.0 and w.samples == 2

    def test_lifecycle_and_clockless_events_ignored(self):
        agg = WindowAggregator(window_steps=2)
        assert agg.add({"event": "hang", "step": 7}) is None  # lifecycle
        assert agg.add({"step": 1}) is None  # no wallclock: not a sample
        assert agg.add(_step(2, 4.0)) is None
        w = agg.add(_step(3, 6.0))
        assert w is not None and w.mean_ms == 5.0

    def test_tokens_per_step_derived_from_rate(self):
        agg = WindowAggregator(window_steps=2)
        agg.add(_step(1, 100.0, tps=1000.0))  # 100 tokens in the step
        w = agg.add(_step(2, 100.0, tps=3000.0))  # 300 tokens
        assert w.mean_tokens_per_step == pytest.approx(200.0)


# ---------------------------------------------------------------------------
# drift detection
# ---------------------------------------------------------------------------


def _window(i, ms, tokens=None):
    return WindowStat(
        index=i, first_step=8 * i + 1, last_step=8 * (i + 1),
        mean_ms=ms, mean_tokens_per_step=tokens, samples=8,
    )


def _detector(**kw):
    kw.setdefault("predicted_ms", 10.0)
    kw.setdefault("band", 0.25)
    kw.setdefault("run_length", 2)
    kw.setdefault("warmup_windows", 1)
    kw.setdefault("baseline_windows", 2)
    kw.setdefault("cooldown_windows", 3)
    return DriftDetector(**kw)


def _feed(det, mss, start=0):
    trigs = []
    for j, ms in enumerate(mss):
        t = det.observe(_window(start + j, ms))
        if t is not None:
            trigs.append(t)
    return trigs


class TestDriftDetector:
    def test_healthy_run_never_triggers(self):
        det = _detector()
        # warmup, 2 baseline windows at ratio 1.2, then in-band wobble
        trigs = _feed(det, [90.0, 12.0, 12.0, 13.0, 11.0, 12.5, 12.0])
        assert trigs == []
        assert det.baseline_ratio == pytest.approx(1.2)

    def test_compile_poisoned_baseline_uses_min(self):
        # regression: a compile-heavy window inside the calibration span
        # must not poison the baseline (mean of 22x and 1.2x would make
        # every later healthy window scream "speedup")
        det = _detector()
        trigs = _feed(det, [90.0, 220.0, 12.0, 12.0, 12.0, 12.0, 12.0])
        assert det.baseline_ratio == pytest.approx(1.2)
        assert trigs == []

    def test_slowdown_needs_run_length_consecutive_windows(self):
        det = _detector()
        warm = [90.0, 12.0, 12.0]
        assert _feed(det, warm) == []
        # one mildly out-of-band window, then back in band: the EMA
        # re-enters the band and the run-length counter resets
        assert _feed(det, [20.0, 12.0], start=3) == []
        # sustained out-of-band windows: exactly one trigger
        trigs = _feed(det, [20.0, 20.0], start=5)
        assert len(trigs) == 1 and trigs[0].cause == "slowdown"
        assert trigs[0].drift > 1.25

    def test_cooldown_rearms_after_n_windows(self):
        det = _detector()
        _feed(det, [90.0, 12.0, 12.0])
        trigs = _feed(det, [40.0] * 12, start=3)
        # first trigger after run_length=2, then every cooldown(3)+run(2)
        assert len(trigs) == 3

    def test_speedup_triggers_once_then_reanchors(self):
        det = _detector()
        _feed(det, [90.0, 12.0, 12.0])
        trigs = _feed(det, [5.0] * 12, start=3)
        # sustained speedup advises ONCE; baseline and EMA both re-anchor
        # to the observed new pace instead of re-firing every cooldown
        assert [t.cause for t in trigs] == ["speedup"]
        assert det.baseline_ratio == pytest.approx(0.5)
        assert det.ema_ratio == pytest.approx(0.5)

    def test_batch_growth_classified_by_tokens_trend(self):
        det = _detector()
        warm = [(90.0, 100.0), (12.0, 100.0), (12.0, 100.0)]
        for j, (ms, tok) in enumerate(warm):
            det.observe(_window(j, ms, tokens=tok))
        trigs = []
        for j in range(4):
            t = det.observe(_window(3 + j, 48.0, tokens=400.0))
            if t:
                trigs.append(t)
        assert [t.cause for t in trigs] == ["batch_growth"]

    def test_slowdown_when_tokens_flat(self):
        det = _detector()
        for j, ms in enumerate([90.0, 12.0, 12.0]):
            det.observe(_window(j, ms, tokens=100.0))
        trigs = []
        for j in range(4):
            t = det.observe(_window(3 + j, 48.0, tokens=100.0))
            if t:
                trigs.append(t)
        assert [t.cause for t in trigs] == ["slowdown"]


# ---------------------------------------------------------------------------
# monitor: advisory construction, event emission, supervision
# ---------------------------------------------------------------------------


def _write_steps(mdir, mss, start_step=1, tokens=None):
    lines = []
    for j, ms in enumerate(mss):
        e = {"schema": 1, "step": start_step + j, "wallclock_ms": ms}
        if tokens is not None:
            # constant tokens per step: the rate drops when steps slow
            e["tokens_per_s"] = tokens / ms * 1000.0
        lines.append(json.dumps(e))
    _append(mdir, "".join(line + "\n" for line in lines))


def _monitor(mdir, **kw):
    kw.setdefault("window_steps", 2)
    kw.setdefault("run_length", 2)
    kw.setdefault("warmup_windows", 1)
    kw.setdefault("baseline_windows", 2)
    kw.setdefault("cooldown_windows", 3)
    return DriftMonitor(mdir, 10.0, **kw)


SLOW_STREAM = [90.0] * 2 + [12.0] * 4 + [40.0] * 8  # warmup, baseline, drift


class TestDriftMonitor:
    def test_advisory_arithmetic_fallback_preserves_ranking(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, SLOW_STREAM)
        mon = _monitor(d, seed_runtimes={"dp_only": 8.0, "tp_heavy": 30.0})
        advisories = mon.poll_once()
        assert len(advisories) == 1
        a = advisories[0]
        assert a.cause == "slowdown" and a.repriced is False
        # uniform scaling preserves the seed table's ranking: the seed
        # that was cheaper than the searched plan stays the candidate
        assert a.candidate == "dp_only"
        assert a.candidate_ms == pytest.approx(8.0 * a.ema_ratio)
        assert a.current_ms == pytest.approx(10.0 * a.ema_ratio)
        assert a.predicted_savings_ms == pytest.approx(
            2.0 * a.ema_ratio
        )

    def test_repricer_result_wins_over_fallback(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, SLOW_STREAM)
        calls = []

        def repricer(scale):
            calls.append(scale)
            return {
                "estimated_ms": 33.0,
                "seed_runtimes": {"alt": 44.0},
                "parallel_degrees": {"replicate": 2},
                "research_seconds": 0.01,
            }

        mon = _monitor(d, repricer=repricer)
        (a,) = mon.poll_once()
        assert calls == [pytest.approx(a.ema_ratio)]
        assert a.repriced is True and a.candidate == "searched"
        assert a.current_ms == 33.0
        assert a.parallel_degrees == {"replicate": 2}

    def test_repricer_failure_degrades_and_posts(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, SLOW_STREAM)
        chan = FaultChannel()

        def repricer(scale):
            raise RuntimeError("search exploded")

        mon = _monitor(d, repricer=repricer, channel=chan)
        (a,) = mon.poll_once()
        assert a.repriced is False  # fell back to arithmetic repricing
        assert mon.reprice_errors == 1
        assert chan.pending(DriftMonitor.SITE) == 1

    def test_drift_event_schema_is_frozen(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, SLOW_STREAM)
        mon = _monitor(d)
        mon.poll_once()
        drift_events = [
            e for e in read_events(d) if e.get("event") == "drift"
        ]
        assert len(drift_events) == 1
        e = drift_events[0]
        # the pin: exactly these keys, in order — consumers dispatch on it
        assert tuple(e) == DRIFT_EVENT_FIELDS
        assert e["schema"] == EVENT_SCHEMA_VERSION
        assert e["drift_schema"] == DRIFT_SCHEMA_VERSION
        assert e["cause"] == "slowdown"

    def test_healthy_stream_no_advisories_and_report(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, [90.0] * 2 + [12.0] * 12)
        mon = _monitor(d)
        assert mon.poll_once() == []
        rep = mon.report()
        assert rep["advisories"] == []
        assert rep["windows"] == 7
        assert rep["baseline_ratio"] == pytest.approx(1.2)

    def test_thread_crash_posts_to_channel(self, tmp_path):
        chan = FaultChannel()
        mon = _monitor(str(tmp_path), channel=chan, poll_interval_s=0.01)

        def boom():
            raise RuntimeError("monitor died")

        mon.poll_once = boom
        mon.start()
        deadline = time.time() + 5.0
        while not chan.history and time.time() < deadline:
            time.sleep(0.01)
        mon._stop.set()
        mon._thread.join(timeout=5.0)
        assert chan.history and chan.history[0][0] == DriftMonitor.SITE

    def test_close_drains_stream_synchronously(self, tmp_path):
        d = str(tmp_path)
        mon = _monitor(d, poll_interval_s=60.0).start()
        # events land AFTER start; the poll interval is far away — only
        # close()'s final drain can see them
        _write_steps(d, SLOW_STREAM)
        mon.close()
        assert len(mon.advisories) == 1


# ---------------------------------------------------------------------------
# ffreport CLI exit contract (satellite 6)
# ---------------------------------------------------------------------------


FFREPORT = os.path.join(REPO, "tools", "ffreport.py")


def _run_ffreport(*args):
    return subprocess.run(
        [sys.executable, FFREPORT, *args],
        capture_output=True, text=True, timeout=120,
    )


class TestFFReportCLI:
    def test_malformed_dir_exits_1(self, tmp_path):
        out = _run_ffreport(str(tmp_path))  # exists but has no events
        assert out.returncode == 1

    def test_healthy_dir_exits_0_and_json_roundtrips(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, SLOW_STREAM, tokens=128.0)
        mon = _monitor(d)
        mon.poll_once()
        from flexflow_tpu.observability.metrics import write_provenance

        write_provenance(d, {
            "estimated_ms": 10.0, "search_algorithm": "unity_dp",
            "drift": mon.report(),
        })
        out = _run_ffreport("--json", d)
        assert out.returncode == 0, out.stderr
        sections = {}
        for line in out.stdout.strip().splitlines():
            s = json.loads(line)
            sections[s["section"]] = s
        assert {"health", "throughput", "timeline", "drift", "plan"} <= set(
            sections
        )
        drift = sections["drift"]
        assert drift["verdict"] == "drifting"
        assert drift["last_advisory"]["cause"] == "slowdown"
        assert sections["health"]["steps"] == len(SLOW_STREAM)

    def test_invalid_provenance_exits_1(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, [12.0] * 4)
        with open(os.path.join(d, "provenance.json"), "w") as f:
            f.write("{torn")
        out = _run_ffreport(d)
        assert out.returncode == 1
