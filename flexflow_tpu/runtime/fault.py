"""Fault injection for chaos testing the elastic runtime.

Two generations of trigger, both active:

1. `FF_TPU_FAULT_STEP=N` (PR 7) — the single-kill switch: raise
   `SimulatedFault` as soon as training progress crosses step N, after
   that step's state update has landed, mirroring a preemption that
   kills the process between dispatches. The trigger is a
   CROSSING (prev_step < N <= step), not a threshold, so a resumed run
   restarting below N does not re-raise forever.

2. `FF_TPU_FAULT_SPEC` (this PR) — a seeded *schedule* of faults at named
   sites, e.g.::

       FF_TPU_FAULT_SPEC="seed=7;sites=ckpt_write,nonfinite,hang;rate=0.02"

   Each (site, step) decision is a pure hash of (seed, site, step): the
   same spec fires at the same steps in every process, every run — which
   is what lets the chaos soak (tests/test_chaos_soak.py,
   runtime/chaos.py) assert that a faulted-then-recovered run ends with
   BITWISE-identical final params versus the fault-free run. Sites:

   - `ckpt_write`  one transient `InjectedFault` (an OSError) on the
                   checkpoint commit rename — absorbed by the
                   runtime/retry.py backoff (escalates only if the
                   filesystem really is down).
   - `nonfinite`   the batch the step is about to consume is poisoned
                   with a NaN — the run-health policies
                   (--health-policy raise/skip_step) own the reaction.
   - `hang`        the step boundary blocks like a hung dispatch until
                   the watchdog deadline fires (WindowWatchdog
                   .simulate_hang) — requires an armed watchdog.
   - `kill`        SimulatedFault at the boundary (the FF_TPU_FAULT_STEP
                   preemption, schedule-driven).

   Faults fire at most ONCE per (site, step) per schedule instance
   (`fire_once`), so a retry loop probing the same step sees one
   transient, not a permanent outage. Tests clear the schedule before
   resuming — a real fault does not recur deterministically either.
"""

from __future__ import annotations

import os
import zlib
from typing import FrozenSet, List, Optional, Set, Tuple

FAULT_STEP_ENV = "FF_TPU_FAULT_STEP"
FAULT_SPEC_ENV = "FF_TPU_FAULT_SPEC"

#: The injectable fault sites, in pipeline order (the README taxonomy
#: table documents each site's detection + recovery path).
FAULT_SITES = ("ckpt_write", "nonfinite", "hang", "kill")

#: Soft perturbation sites (ISSUE 18): schedule-driven degradations that
#: do NOT fault the run — they bend its telemetry. Kept out of
#: FAULT_SITES so the chaos-soak recovery matrix (which asserts every
#: fault site recovers to bitwise params) doesn't soak a site that never
#: needs recovering.
#:
#: - `slow`  the step's timed region sleeps FF_TPU_FAULT_SLOW_MS
#:           (default 50) ms — a thermal-throttle / SMT-contention
#:           stand-in that inflates measured step wall-clock without
#:           touching the math; the drift monitor
#:           (observability/drift.py) owns the reaction.
SOFT_SITES = ("slow",)

SLOW_MS_ENV = "FF_TPU_FAULT_SLOW_MS"


class SimulatedFault(RuntimeError):
    """The injected preemption (FF_TPU_FAULT_STEP / schedule site `kill`)."""

    def __init__(self, step: int) -> None:
        super().__init__(
            f"simulated preemption after step {step} ({FAULT_STEP_ENV})"
        )
        self.step = step


class InjectedFault(OSError):
    """A schedule-injected I/O-shaped fault (site `ckpt_write`).
    Subclasses OSError on purpose: the transient-retry machinery
    (runtime/retry.py) must treat it exactly like the real flaky
    filesystem it simulates."""

    def __init__(self, site: str, step: int) -> None:
        super().__init__(
            f"injected {site!r} fault at step {step} ({FAULT_SPEC_ENV})"
        )
        self.site = site
        self.step = step


class FaultSchedule:
    """A seeded, deterministic schedule of faults at named sites.

    The per-(site, step) decision hashes (seed, site, step) into [0, 1)
    and fires below `rate` — no RNG state, no call-order dependence, so
    the schedule is reproducible across processes and resume boundaries.
    `fired_log` records every fault actually injected (site, step), the
    soak harness's evidence that a schedule exercised what it claims.
    """

    def __init__(
        self,
        seed: int = 0,
        sites: FrozenSet[str] = frozenset(),
        rate: float = 0.01,
        spec: str = "",
    ) -> None:
        unknown = sorted(set(sites) - set(FAULT_SITES) - set(SOFT_SITES))
        if unknown:
            raise ValueError(
                f"unknown fault sites {unknown}; known sites: "
                f"{list(FAULT_SITES) + list(SOFT_SITES)}"
            )
        if not 0.0 < rate <= 1.0:
            raise ValueError(f"fault rate must be in (0, 1], got {rate}")
        self.seed = int(seed)
        self.sites = frozenset(sites)
        self.rate = float(rate)
        self.spec = spec or self.canonical_spec()
        self.fired_log: List[Tuple[str, int]] = []
        self._once: Set[Tuple[str, int]] = set()

    def canonical_spec(self) -> str:
        return (
            f"seed={self.seed};sites={','.join(sorted(self.sites))};"
            f"rate={self.rate}"
        )

    @classmethod
    def parse(cls, spec: str) -> "FaultSchedule":
        """Parse `seed=7;sites=a,b;rate=0.02` (order-insensitive; unknown
        keys rejected loudly — a typo'd chaos spec must not silently run
        fault-free)."""
        seed, sites, rate = 0, frozenset(), 0.01
        for part in spec.split(";"):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(f"malformed fault-spec field {part!r}")
            k, v = part.split("=", 1)
            k = k.strip()
            if k == "seed":
                seed = int(v)
            elif k == "sites":
                sites = frozenset(
                    s.strip() for s in v.split(",") if s.strip()
                )
            elif k == "rate":
                rate = float(v)
            else:
                raise ValueError(
                    f"unknown fault-spec key {k!r} (known: seed, sites, "
                    "rate)"
                )
        return cls(seed=seed, sites=sites, rate=rate, spec=spec)

    # -- decisions ---------------------------------------------------------

    def should_fire(self, site: str, step: int) -> bool:
        if site not in self.sites:
            return False
        h = zlib.crc32(f"{self.seed}:{site}:{step}".encode("utf-8"))
        return (h & 0xFFFFFFFF) / 2.0**32 < self.rate

    def fire_once(self, site: str, step: int) -> bool:
        """True exactly the first time a firing (site, step) is asked —
        the injection sites use this so retries of the same step see one
        transient fault, not a permanent outage."""
        if not self.should_fire(site, step):
            return False
        key = (site, int(step))
        if key in self._once:
            return False
        self._once.add(key)
        self.fired_log.append(key)
        return True

    def fire_steps(self, site: str, lo: int, hi: int) -> List[int]:
        """All steps in [lo, hi] where `site` fires (harness planning)."""
        return [s for s in range(lo, hi + 1) if self.should_fire(site, s)]


def find_seed(
    site: str,
    rate: float,
    lo: int,
    hi: int,
    max_seed: int = 100000,
    candidates=None,
) -> int:
    """Smallest seed whose FIRST `site` firing lands inside [lo, hi] (and
    none before lo): the soak harness pins each schedule's fault to a
    step range where a checkpoint already exists, deterministically,
    without storing magic seeds. `candidates` restricts further to steps
    where the site is actually consulted — e.g. `ckpt_write` only runs at
    checkpoint commits, so its fire step must be a checkpoint boundary."""
    for seed in range(max_seed):
        s = FaultSchedule(seed=seed, sites=frozenset({site}), rate=rate)
        fired = s.fire_steps(site, 1, hi)
        if not fired or fired[0] < lo:
            continue
        if candidates is not None and not any(
            f in candidates for f in fired
        ):
            continue
        return seed
    raise ValueError(
        f"no seed < {max_seed} fires {site!r} first inside [{lo}, {hi}] "
        f"at rate {rate}"
    )


# -- process-wide active schedule -------------------------------------------

_INSTALLED: Optional[FaultSchedule] = None
_ENV_CACHE: Tuple[str, Optional[FaultSchedule]] = ("", None)


def install_schedule(schedule: Optional[FaultSchedule]) -> None:
    """Install (or clear, with None) a schedule programmatically — takes
    precedence over FF_TPU_FAULT_SPEC. The soak harness uses this so the
    faulted run and the resume run share a process without env races."""
    global _INSTALLED
    _INSTALLED = schedule


def active_schedule() -> Optional[FaultSchedule]:
    """The installed schedule, else the FF_TPU_FAULT_SPEC one (parsed
    once per distinct spec string so fire-once state survives repeated
    lookups), else None."""
    global _ENV_CACHE
    if _INSTALLED is not None:
        return _INSTALLED
    spec = os.environ.get(FAULT_SPEC_ENV, "")
    if not spec:
        return None
    if _ENV_CACHE[0] != spec:
        _ENV_CACHE = (spec, FaultSchedule.parse(spec))
    return _ENV_CACHE[1]


# -- boundary hooks (the fit loop) ------------------------------------------


def fault_step() -> Optional[int]:
    v = os.environ.get(FAULT_STEP_ENV, "")
    return int(v) if v else None


def maybe_inject_fault(prev_step: int, step: int) -> None:
    """Raise SimulatedFault when [prev_step, step] crossed the configured
    fault step. Called by the fit loop after each completed step — i.e.
    after checkpoint hooks, so a due checkpoint survives the fault."""
    n = fault_step()
    if n is not None and prev_step < n <= step:
        raise SimulatedFault(step)


def inject_nonfinite_fault(
    schedule: Optional[FaultSchedule], step: int, batch: dict
) -> dict:
    """Schedule site `nonfinite` for the batch step `step` is about to
    consume: the first element of every floating input becomes NaN, so the
    run-health policies see a genuinely non-finite step. Returns the batch
    (the same dict when the site does not fire)."""
    if schedule is None or not schedule.fire_once("nonfinite", step):
        return batch
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.runtime.distributed import device_put_global

    poisoned = dict(batch)
    for name, arr in batch.items():
        if jnp.issubdtype(arr.dtype, jnp.floating):
            host = np.array(arr)
            host.reshape(-1)[0] = np.nan
            poisoned[name] = device_put_global(host, arr.sharding)
    return poisoned


def inject_hang_fault(
    schedule: Optional[FaultSchedule],
    prev_step: int,
    step: int,
    watchdog=None,
) -> None:
    """Schedule site `hang` for the window that computed steps
    (prev_step, step]. Fired INSIDE the armed watchdog window (the fit
    loop calls this before disarming): a hung dispatch never reaches the
    window boundary, so neither does the simulation — the boundary's
    checkpoint snapshot correctly does not happen. Blocks via the
    watchdog's cooperative simulation and raises WindowHangError when
    the deadline fires."""
    if schedule is None:
        return
    for s in range(prev_step + 1, step + 1):
        if schedule.fire_once("hang", s):
            if watchdog is None:
                raise RuntimeError(
                    "fault site 'hang' fired but no watchdog is armed "
                    "(set --watchdog-factor / FF_TPU_WATCHDOG so the hang "
                    "is detectable)"
                )
            watchdog.simulate_hang()  # raises WindowHangError


def inject_slow_fault(
    schedule: Optional[FaultSchedule],
    prev_step: int,
    step: int,
    slow_ms: Optional[float] = None,
) -> float:
    """Soft site `slow` for the steps (prev_step, step]: sleep
    FF_TPU_FAULT_SLOW_MS (default 50) ms per firing step. Called INSIDE
    the step's timed region (between dispatch and the health readback)
    so the injected latency lands in the event stream's `wallclock_ms`
    exactly like a thermal throttle would — the drift monitor's
    detection substrate, not a fault. Returns the total ms slept (the
    bench's injected-perturbation accounting)."""
    if schedule is None:
        return 0.0
    import time as _time

    if slow_ms is None:
        slow_ms = float(os.environ.get(SLOW_MS_ENV, "") or 50.0)
    slept = 0.0
    for s in range(prev_step + 1, step + 1):
        if schedule.fire_once("slow", s):
            _time.sleep(slow_ms / 1000.0)
            slept += slow_ms
    return slept


def inject_kill_fault(
    schedule: Optional[FaultSchedule], prev_step: int, step: int
) -> None:
    """Schedule site `kill` at the window boundary. Like
    maybe_inject_fault, runs AFTER the checkpoint hook so a due snapshot
    is durable before the preemption propagates."""
    if schedule is None:
        return
    for s in range(prev_step + 1, step + 1):
        if schedule.fire_once("kill", s):
            raise SimulatedFault(s)


def inject_boundary_faults(
    schedule: Optional[FaultSchedule],
    prev_step: int,
    step: int,
    watchdog=None,
) -> None:
    """Both schedule-driven boundary sites in one call (hang, then
    kill) — the standalone-harness convenience; the fit loop calls the
    two halves separately so the hang rides inside the armed window and
    the kill after the checkpoint hook."""
    inject_hang_fault(schedule, prev_step, step, watchdog=watchdog)
    inject_kill_fault(schedule, prev_step, step)


__all__ = [
    "FAULT_SITES",
    "FAULT_SPEC_ENV",
    "FAULT_STEP_ENV",
    "SLOW_MS_ENV",
    "SOFT_SITES",
    "FaultSchedule",
    "InjectedFault",
    "SimulatedFault",
    "active_schedule",
    "fault_step",
    "find_seed",
    "inject_boundary_faults",
    "inject_hang_fault",
    "inject_kill_fault",
    "inject_nonfinite_fault",
    "inject_slow_fault",
    "install_schedule",
    "maybe_inject_fault",
]
