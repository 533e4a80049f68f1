"""Fault-domain supervision for the fit loop: window watchdog + fault channel.

PR 7 gave the elastic runtime *recovery* (async checkpoints, bitwise
resume, degraded-grid re-search) but almost no *detection*: a hung
dispatch window blocks the training thread forever, and exceptions on the
background writer thread could die silently or surface only at
teardown. The reference's Legion runtime survives because task failures
are first-class events routed to the mapper (PAPER.md §0); this module is
the JAX-native equivalent — a supervision layer that turns hangs and
thread deaths into structured, recoverable events:

- `FaultChannel` — the shared mailbox background threads (the async
  checkpoint writer, the drift monitor) post their exceptions into; the
  fit loop drains it at every window boundary, so a background failure
  surfaces within one window as a `BackgroundFault` naming the site
  instead of at final `wait()` (or never).
- `WindowWatchdog` — a monitor thread arming a deadline around each
  dispatch window. The budget derives from a rolling (EMA) window-time
  estimate × a configurable factor (`--watchdog-factor` /
  `FF_TPU_WATCHDOG`); the first window is never timed (its wall-clock is
  dominated by XLA compilation, which the estimate cannot predict). On
  expiry the watchdog records a `HangDiagnostic` — last completed step,
  the in-flight window, the live trace-span stack of the watched thread,
  device kind — hands it to `on_hang` (the fit loop writes it to the
  metrics JSONL), and raises a structured `WindowHangError` instead of
  letting the run block forever: cooperatively when the hang site is the
  fault-injection simulation (`runtime/fault.py` site "hang"), and
  best-effort via `PyThreadState_SetAsyncExc` for a real hang blocked at
  Python level (a hang inside a C call surfaces at the next bytecode).

Everything here is off by default: no watchdog thread exists unless a
factor is configured, and the channel is a lock + empty deque check per
window boundary.
"""

from __future__ import annotations

import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple


class BackgroundFault(RuntimeError):
    """A background supervision event: the exception a background
    thread died with, re-raised on the training thread with the fault
    site named. The original exception rides `original` (and
    `__cause__`)."""

    def __init__(self, site: str, original: BaseException) -> None:
        super().__init__(
            f"background thread fault at site {site!r}: "
            f"{type(original).__name__}: {original}"
        )
        self.site = site
        self.original = original


class FaultChannel:
    """Thread-safe mailbox from background threads to the fit loop.

    Background threads `post(site, exc)` and keep running (or die); the
    training thread calls `raise_pending()` at each window boundary and
    gets a `BackgroundFault` chaining the original exception. `history`
    keeps a repr of everything ever posted (diagnostics survive the
    raise)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pending: deque = deque()
        self.history: List[Tuple[str, str]] = []

    def post(self, site: str, exc: BaseException) -> None:
        with self._lock:
            self._pending.append((site, exc))
            self.history.append((site, f"{type(exc).__name__}: {exc}"))

    def pending(self, site: Optional[str] = None) -> int:
        with self._lock:
            if site is None:
                return len(self._pending)
            return sum(1 for s, _ in self._pending if s == site)

    def raise_pending(self, site: Optional[str] = None) -> None:
        """Raise the oldest pending fault (optionally only from `site`)
        as a BackgroundFault; no-op when nothing is pending."""
        with self._lock:
            found = None
            for i, (s, exc) in enumerate(self._pending):
                if site is None or s == site:
                    found = (i, s, exc)
                    break
            if found is None:
                return
            i, s, exc = found
            del self._pending[i]
        raise BackgroundFault(s, exc) from exc


@dataclass
class HangDiagnostic:
    """What the watchdog knew when the deadline expired — enough to file
    a useful bug without a debugger attached to the hung process."""

    last_completed_step: int
    window_base_step: int
    window_steps: int
    budget_ms: float
    elapsed_ms: float
    device_kind: str
    trace_spans: List[str] = field(default_factory=list)
    thread_name: str = ""

    def to_dict(self) -> dict:
        return {
            "last_completed_step": int(self.last_completed_step),
            "window_base_step": int(self.window_base_step),
            "window_steps": int(self.window_steps),
            "budget_ms": round(float(self.budget_ms), 3),
            "elapsed_ms": round(float(self.elapsed_ms), 3),
            "device_kind": self.device_kind,
            "trace_spans": list(self.trace_spans),
            "thread_name": self.thread_name,
        }


class WindowHangError(RuntimeError):
    """A dispatch window exceeded its watchdog budget. `diagnostic` is
    the HangDiagnostic recorded at expiry (None when the error was
    injected asynchronously — read `watchdog.last_diagnostic` then)."""

    def __init__(self, diagnostic: Optional[HangDiagnostic] = None) -> None:
        if diagnostic is None:
            msg = "dispatch window exceeded its watchdog budget"
        else:
            msg = (
                "dispatch window exceeded its watchdog budget: window at "
                f"step {diagnostic.window_base_step} (+{diagnostic.window_steps} steps) "
                f"ran {diagnostic.elapsed_ms:.0f} ms against a "
                f"{diagnostic.budget_ms:.0f} ms budget "
                f"(last completed step {diagnostic.last_completed_step})"
            )
        super().__init__(msg)
        self.diagnostic = diagnostic


def _async_raise(tid: int, exc_type) -> None:
    """Best-effort asynchronous exception into thread `tid` (CPython
    only): the pending exception is raised at the thread's next bytecode
    boundary, which unsticks Python-level waits; a thread blocked inside
    a C call sees it only when the call returns."""
    import ctypes

    set_exc = ctypes.pythonapi.PyThreadState_SetAsyncExc
    res = set_exc(ctypes.c_ulong(tid), ctypes.py_object(exc_type))
    if res > 1:  # multiple threads affected: undo (stale id)
        set_exc(ctypes.c_ulong(tid), None)


class WindowWatchdog:
    """Deadline monitor around dispatch windows.

    `begin_window(step, k)` arms a deadline of
    max(min_budget_ms, estimate_ms * factor) — the estimate is an EMA of
    completed window wall-clocks, so the budget tracks the run's real
    cadence (a 20 ms proxy window and a 250 ms flagship window get
    proportionate budgets from the same factor). `end_window(step)`
    disarms and feeds the estimate. Until the first window completes
    there is no estimate and therefore no deadline: the first window's
    wall-clock is dominated by XLA compilation, which would only ever
    false-trip.

    On expiry the monitor thread records the HangDiagnostic, calls
    `on_hang`, sets the cancel event (unblocking a cooperative
    `simulate_hang` waiter, which then raises `WindowHangError` on the
    training thread itself), and — when no cooperative waiter is
    registered — injects `WindowHangError` into the watched thread
    asynchronously. It fires at most once per fit.
    """

    def __init__(
        self,
        factor: float,
        min_budget_ms: float = 1000.0,
        on_hang: Optional[Callable[[HangDiagnostic], None]] = None,
        poll_interval_s: float = 0.02,
        clock=time.monotonic,
        ema_alpha: float = 0.3,
    ) -> None:
        assert factor > 0, "watchdog factor must be positive (0 = disabled)"
        self.factor = float(factor)
        self.min_budget_ms = float(min_budget_ms)
        self.on_hang = on_hang
        self._poll = float(poll_interval_s)
        self._clock = clock
        self._alpha = float(ema_alpha)
        self.estimate_ms: Optional[float] = None
        self.last_diagnostic: Optional[HangDiagnostic] = None
        self.fired = False
        self._cv = threading.Condition()
        self._cancel = threading.Event()
        self._closed = False
        self._deadline: Optional[float] = None
        self._t0: Optional[float] = None
        self._budget_ms: Optional[float] = None
        self._window: Tuple[int, int] = (0, 0)
        self._last_step = 0
        self._watched_tid: Optional[int] = None
        self._watched_name = ""
        self._cooperative = False
        self._thread = threading.Thread(
            target=self._run, name="ff-watchdog", daemon=True
        )
        self._thread.start()

    # -- fit-loop surface --------------------------------------------------

    def budget_ms(self) -> Optional[float]:
        """The budget the NEXT window would get (None until the rolling
        estimate exists)."""
        if self.estimate_ms is None:
            return None
        return max(self.min_budget_ms, self.estimate_ms * self.factor)

    def begin_window(self, base_step: int, steps: int = 1) -> None:
        """Arm around the window that will advance training to
        `base_step + steps - 1`... i.e. base_step is the first step the
        window computes. Caller thread becomes the watched thread."""
        with self._cv:
            self._window = (int(base_step), int(steps))
            self._watched_tid = threading.get_ident()
            self._watched_name = threading.current_thread().name
            self._t0 = self._clock()
            b = self.budget_ms()
            self._budget_ms = b
            self._deadline = None if b is None else self._t0 + b / 1000.0
            self._cv.notify_all()

    def end_window(self, completed_step: int) -> None:
        """Disarm and feed the rolling estimate with the completed
        window's wall-clock (skipped after a fire: a hang's duration
        must not poison the estimate)."""
        with self._cv:
            if self._t0 is not None and not self.fired:
                dur = (self._clock() - self._t0) * 1000.0
                self.estimate_ms = (
                    dur
                    if self.estimate_ms is None
                    else (1 - self._alpha) * self.estimate_ms + self._alpha * dur
                )
            self._last_step = int(completed_step)
            self._deadline = None
            self._t0 = None
            self._cv.notify_all()

    def simulate_hang(self) -> None:
        """The fault-injection site ("hang", runtime/fault.py): block the
        calling (training) thread exactly like a hung dispatch would,
        until the watchdog deadline fires, then raise the structured
        WindowHangError with the diagnostic. Requires an armed deadline —
        a hang nobody is watching for would block forever, which is the
        failure mode this layer exists to remove."""
        with self._cv:
            if self._deadline is None:
                raise RuntimeError(
                    "simulated hang requires an armed watchdog deadline "
                    "(the first window is never timed; schedule the hang "
                    "after at least one completed window)"
                )
            self._cooperative = True
        try:
            self._cancel.wait()
        finally:
            with self._cv:
                self._cooperative = False
        raise WindowHangError(self.last_diagnostic)

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._deadline = None
            self._cv.notify_all()
        self._thread.join(timeout=5.0)

    # -- monitor thread ----------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._cv:
                if self._closed:
                    return
                deadline = None if self.fired else self._deadline
                now = self._clock()
                if deadline is not None and now >= deadline:
                    self._fire_locked(now)
                    continue
                if deadline is None:
                    # nothing armed: block until begin_window/close
                    # notifies — zero idle wakeups between windows and
                    # after a fire
                    self._cv.wait()
                else:
                    self._cv.wait(
                        min(self._poll, max(deadline - now, 0.0))
                    )

    def _live_spans(self, tid: int) -> List[str]:
        try:
            from flexflow_tpu.observability.trace import open_span_names

            return open_span_names(tid)
        except Exception:
            return []  # diagnostics must never mask the hang itself

    def _fire_locked(self, now: float) -> None:
        """Build + publish the diagnostic (called with self._cv held)."""
        self.fired = True
        base, steps = self._window
        tid = self._watched_tid
        try:
            import jax

            device_kind = jax.default_backend()
        except Exception:
            device_kind = "unknown"
        diag = HangDiagnostic(
            last_completed_step=self._last_step,
            window_base_step=base,
            window_steps=steps,
            budget_ms=self._budget_ms or 0.0,
            elapsed_ms=(now - (self._t0 or now)) * 1000.0,
            device_kind=device_kind,
            trace_spans=self._live_spans(tid) if tid is not None else [],
            thread_name=self._watched_name,
        )
        self.last_diagnostic = diag
        cooperative = self._cooperative
        # publish outside nothing: on_hang may do I/O, but the monitor
        # thread has nothing else to do once fired
        if self.on_hang is not None:
            try:
                self.on_hang(diag)
            except Exception:
                import traceback

                traceback.print_exc(file=sys.stderr)
        print(
            f"[flexflow_tpu] watchdog: {WindowHangError(diag)}",
            file=sys.stderr,
        )
        self._cancel.set()
        if not cooperative and tid is not None:
            _async_raise(tid, WindowHangError)


@dataclass
class FitSupervision:
    """One fit call's supervision bundle: the shared fault channel, the
    optional watchdog, and the active seeded fault schedule (None unless
    FF_TPU_FAULT_SPEC / install_schedule set one)."""

    channel: FaultChannel
    watchdog: Optional[WindowWatchdog] = None
    schedule: Optional[object] = None  # runtime.fault.FaultSchedule

    def close(self) -> None:
        if self.watchdog is not None:
            self.watchdog.close()


__all__ = [
    "BackgroundFault",
    "FaultChannel",
    "FitSupervision",
    "HangDiagnostic",
    "WindowHangError",
    "WindowWatchdog",
]
