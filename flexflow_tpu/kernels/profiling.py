"""Measured timing harness (reference: lib/kernels/include/kernels/
profiling.h:10-49 — cudaEvent timing with warmup/measure iters).

Dispatch is asynchronous, so every timed window ends in
`block_until_ready` (SURVEY.md §7 hard part 5). Each window also pays a
fixed cost — the first dispatch's ramp and the final sync — so per-iter time
is taken from the slope between a short and a long run (two-point
measurement), not a single average.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable


@dataclass(frozen=True)
class ProfilingSettings:
    """reference: profiling_settings.struct.toml."""

    warmup_iters: int = 2
    measure_iters: int = 5


def force_sync(out) -> None:
    """Wait until every array of the result pytree is computed (non-array
    leaves are ignored)."""
    import jax

    jax.block_until_ready(out)


def _timed_run(fn, iters, args, kwargs) -> float:
    start = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args, **kwargs)
    force_sync(out)
    return time.perf_counter() - start


def profile_fn(fn: Callable, settings: ProfilingSettings, *args, **kwargs) -> float:
    """Per-iter wall ms of fn(*args) after warmup, with the fixed
    per-window dispatch and sync cost cancelled via two-point measurement."""
    for _ in range(settings.warmup_iters):
        force_sync(fn(*args, **kwargs))
    n1 = max(1, settings.measure_iters // 4)
    n2 = max(n1 + 1, settings.measure_iters)
    t1 = _timed_run(fn, n1, args, kwargs)
    t2 = _timed_run(fn, n2, args, kwargs)
    per_iter = (t2 - t1) / (n2 - n1)
    if per_iter <= 0:
        per_iter = t2 / n2  # noisy fallback
    return per_iter * 1000.0
