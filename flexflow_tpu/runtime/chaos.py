"""Seeded chaos-schedule soak harness (run by tests/test_chaos_soak.py;
`soak_schedule` reports one schedule's run as a dict).

The contract being soaked: for EVERY seeded `FaultSchedule` — a ckpt-write
I/O fault, an injected NaN, a simulated hang, a kill+resume
preemption — the run either completes (the fault was absorbed
transparently) or dies with a structured error and, after
`fit(resume=True)`, ends with BITWISE-identical final params and Adam
moments versus the fault-free reference run. That is the strongest
statement "the supervision layer works" can make: detection fires, the
diagnosis is structured, and recovery loses nothing.

The harness is deliberately model-agnostic: callers hand it a
`build(metrics_dir, checkpoint_dir)` factory (DP or searched-PCG backend)
and a reference final state; `soak_schedule` installs
the schedule, runs, recovers, and reports. Seeds are found
deterministically with `fault.find_seed`, so every process derives the
same schedules without storing magic numbers.
"""

from __future__ import annotations

import tempfile
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from flexflow_tpu.runtime import fault as fault_mod
from flexflow_tpu.runtime.fault import FaultSchedule


def final_state(model) -> Tuple[Dict[str, np.ndarray], List[np.ndarray]]:
    """Host copies of (params dict, opt-state leaves) — the bitwise
    comparison payload."""
    import jax

    params = {k: np.asarray(v) for k, v in model.params.items()}
    opt = [
        np.asarray(leaf)
        for leaf in jax.tree_util.tree_leaves(model.opt_state)
    ]
    return params, opt


def states_bitwise(
    a: Tuple[Dict[str, np.ndarray], List[np.ndarray]],
    b: Tuple[Dict[str, np.ndarray], List[np.ndarray]],
) -> Tuple[bool, bool]:
    """(params bitwise-identical, opt-state bitwise-identical)."""
    pa, oa = a
    pb, ob = b
    params_ok = set(pa) == set(pb) and all(
        np.array_equal(pa[k], pb[k]) for k in pa
    )
    opt_ok = len(oa) == len(ob) and all(
        np.array_equal(x, y) for x, y in zip(oa, ob)
    )
    return params_ok, opt_ok


def schedule_for_site(
    site: str,
    total_steps: int,
    checkpoint_every: int,
    rate: float = 0.08,
) -> FaultSchedule:
    """A deterministic single-site schedule whose first firing lands where
    the soak can prove recovery: after the first checkpoint exists and
    before the run ends (for `ckpt_write`, ON a checkpoint boundary that
    is not the final commit; for `hang`, after at least one completed
    window so the watchdog has a rolling estimate)."""
    lo = checkpoint_every + 1
    hi = max(total_steps - 1, lo)
    candidates = None
    if site == "ckpt_write":
        candidates = [
            s
            for s in range(checkpoint_every, total_steps, checkpoint_every)
            if s > checkpoint_every
        ] or [checkpoint_every]
        lo = 1
    seed = fault_mod.find_seed(site, rate, lo, hi, candidates=candidates)
    return FaultSchedule(
        seed=seed, sites=frozenset({site}), rate=rate
    )


def soak_schedule(
    schedule: FaultSchedule,
    build: Callable,
    x,
    y,
    reference: Tuple[Dict[str, np.ndarray], List[np.ndarray]],
    epochs: int = 2,
    dirs: Optional[Tuple[str, str]] = None,
) -> Dict[str, object]:
    """Run one faulted-then-recovered training run under `schedule` and
    compare its final state bitwise against `reference` (the fault-free
    run's `final_state`). `build(metrics_dir, ckpt_dir, watchdog=bool)`
    must return a compiled model; the watchdog is requested only for
    schedules containing the `hang` site — on a contended CPU host the
    window-time estimate is noisy enough that an always-on tight budget
    would false-trip the non-hang runs (a production factor is 10-30x;
    the soak wants a seconds-not-minutes hang wait). Returns the soak
    record (JSON-safe)."""
    mdir, cdir = dirs or (tempfile.mkdtemp(), tempfile.mkdtemp())
    wants_watchdog = "hang" in schedule.sites
    model = build(mdir, cdir, watchdog=wants_watchdog)
    fault_mod.install_schedule(schedule)
    outcome = "completed"
    error_repr = None
    try:
        model.fit(x, y, epochs=epochs, shuffle=True, verbose=False)
    except Exception as e:
        outcome = type(e).__name__
        error_repr = f"{type(e).__name__}: {e}"[:200]
    finally:
        fault_mod.install_schedule(None)
    fired = [list(f) for f in schedule.fired_log]
    resumed = False
    if outcome != "completed":
        # the recovery leg: a fresh process-equivalent resumes from the
        # last durable snapshot with the schedule cleared (a real fault
        # does not recur deterministically either)
        model = build(mdir, cdir, watchdog=False)
        model.fit(
            x, y, epochs=epochs, shuffle=True, verbose=False, resume=True
        )
        resumed = True
    params_ok, opt_ok = states_bitwise(final_state(model), reference)
    return {
        "spec": schedule.canonical_spec(),
        "sites": sorted(schedule.sites),
        "fired": fired,
        "outcome": outcome,
        "error": error_repr,
        "resumed": resumed,
        "bitwise_params": bool(params_ok),
        "bitwise_opt_state": bool(opt_ok),
        "recovered_bitwise": bool(params_ok and opt_ok),
    }


def soak_sites(
    build: Callable,
    x,
    y,
    total_steps: int,
    checkpoint_every: int,
    epochs: int = 2,
    sites: Tuple[str, ...] = fault_mod.FAULT_SITES,
) -> Dict[str, object]:
    """The full per-backend soak: a fault-free reference run, then one
    seeded schedule per site, each required to recover bitwise. Returns
    {"schedules": [...], "n_schedules", "n_fired", "n_bitwise"}."""
    ref_model = build(
        tempfile.mkdtemp(), tempfile.mkdtemp(), watchdog=False
    )
    ref_model.fit(x, y, epochs=epochs, shuffle=True, verbose=False)
    reference = final_state(ref_model)
    records = []
    for site in sites:
        schedule = schedule_for_site(site, total_steps, checkpoint_every)
        records.append(
            soak_schedule(schedule, build, x, y, reference, epochs=epochs)
        )
    return {
        "schedules": records,
        "n_schedules": len(records),
        "n_fired": sum(1 for r in records if r["fired"]),
        "n_bitwise": sum(1 for r in records if r["recovered_bitwise"]),
    }


__all__ = [
    "final_state",
    "schedule_for_site",
    "soak_schedule",
    "soak_sites",
    "states_bitwise",
]
