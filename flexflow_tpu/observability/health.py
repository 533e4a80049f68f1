"""Run-health monitoring: nonfinite detection, policies, first-bad-op blame.

A production training run has exactly three sane reactions to a non-finite
loss or gradient, and which one is right depends on the run:

- ``warn``      — log and keep going (debugging; the run is disposable).
- ``skip_step`` — drop the poisoned update and continue on the previous
                  parameters (large-batch production runs: one bad batch
                  must not kill a day of training). The guard happens INSIDE
                  the jitted step (metrics.guard_nonfinite), so the skipped
                  update never touches params or optimizer state.
- ``raise``     — stop immediately with the name of the first op whose
                  output went non-finite (CI / experimentation).

The localizer replays the failing step UN-fused, one op at a time, in the
graph's topological order — forward first, then the loss, then the backward
VJP walk — and names the earliest op whose output contains a NaN/Inf. The
fused XLA step can only say "the loss was NaN"; the per-op replay says
"attn3's output was the first non-finite tensor", which is the difference
between re-running with printouts for a day and opening the right kernel.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional

HEALTH_POLICIES = ("off", "warn", "skip_step", "raise")


class NonFiniteError(RuntimeError):
    """Raised by the `raise` policy; carries the localizer's blame report."""

    def __init__(self, message: str, report: Optional["NonFiniteReport"] = None):
        super().__init__(message)
        self.report = report


@dataclass
class NonFiniteReport:
    """Where the step first went non-finite."""

    phase: str            # "forward" | "loss" | "backward" | "unknown"
    op_name: Optional[str]  # layer name (or "n<idx>") of the first bad op
    op_type: Optional[str] = None
    detail: str = ""

    def describe(self) -> str:
        if self.op_name is None:
            return f"non-finite values in {self.phase} (op not localized)"
        return (
            f"first non-finite output at {self.phase} op "
            f"{self.op_name!r} ({self.op_type}){self.detail}"
        )


def _finite(x) -> bool:
    import jax.numpy as jnp
    import numpy as np

    if not hasattr(x, "dtype") or not jnp.issubdtype(x.dtype, jnp.floating):
        return True
    return bool(np.asarray(jnp.all(jnp.isfinite(x))))


def localize_first_nonfinite(
    graph,
    params: Dict[str, object],
    inputs: Dict[str, object],
    logit_tensor=None,
    label=None,
    loss_attrs=None,
    compute_dtype=None,
    rng=None,
) -> NonFiniteReport:
    """Replay one step op-by-op and name the earliest non-finite producer.

    `graph` may be the ComputationGraph or a searched PCG (parallel ops
    interpret as identity, matching the executor's global-view semantics);
    `params` are the live training parameters keyed by param_key, `inputs`
    the batch that tripped the monitor. When `logit_tensor`/`label`/
    `loss_attrs` are given and the forward pass is clean, the loss and the
    reverse-topo VJP walk are checked too. `compute_dtype` is the
    instance's mixed-precision policy: the replay must run at the SAME
    precision as the fused step, or a low-precision overflow/underflow NaN
    stays finite in the replay and the blame degrades to 'unknown'.
    `rng` is the tripped step's PRNG key: with it the replay runs
    train-mode with the same per-op folded keys the fused step used
    (forward_interpreter's fold_in discipline), so train-only ops like
    Dropout compute the same function; without it kernels run in eval
    mode and stochastic-op NaNs cannot be localized."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels import forward as kernel_forward, loss_forward
    from flexflow_tpu.kernels.precision import cast_for_compute
    from flexflow_tpu.local_execution.training_backing import (
        param_key,
        split_slot_values,
    )
    from flexflow_tpu.op_attrs.core import is_parallel_op
    from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs

    params = cast_for_compute(params, compute_dtype)
    inputs = cast_for_compute(
        {k: jnp.asarray(v) for k, v in inputs.items()}, compute_dtype
    )

    def describe(n):
        la = graph.layer_attrs(n)
        name = la.name or param_key(n)
        return name, type(la.attrs).__name__

    # -- forward, one op at a time ------------------------------------------
    env: Dict = {}
    order = graph.topological_ordering()
    for n in order:
        la = graph.layer_attrs(n)
        attrs = la.attrs
        outs = graph.outputs_of(n)
        if isinstance(attrs, InputAttrs):
            key = la.name if la.name in inputs else param_key(n)
            if key not in inputs:
                return NonFiniteReport(
                    "unknown", None, detail=f" (missing input {key!r})"
                )
            env[outs[0]] = jnp.asarray(inputs[key])
        elif isinstance(attrs, WeightAttrs):
            if param_key(n) not in params:
                return NonFiniteReport(
                    "unknown", None, detail=f" (missing param {param_key(n)!r})"
                )
            env[outs[0]] = params[param_key(n)]
            if not _finite(env[outs[0]]):
                name, ot = describe(n)
                return NonFiniteReport("forward", name, ot, " (parameter value)")
        elif is_parallel_op(attrs):
            (src,) = graph.inputs_of(n)
            env[outs[0]] = env[src]
        else:
            slot_vals = [env[v] for v in graph.inputs_of(n)]
            op_rng = (
                jax.random.fold_in(rng, n.idx) if rng is not None else None
            )

            def fn(*xs, a=attrs, r=op_rng):
                data, w = split_slot_values(a, list(xs))
                return kernel_forward(
                    a, data, w, train=rng is not None, rng=r
                )

            results = fn(*slot_vals)
            for o, r in zip(outs, results):
                env[o] = r
            if any(not _finite(r) for r in results):
                name, ot = describe(n)
                return NonFiniteReport("forward", name, ot)

    if logit_tensor is None or label is None or loss_attrs is None:
        return NonFiniteReport("unknown", None, detail=" (forward pass clean)")

    # -- loss ---------------------------------------------------------------
    logit = env.get(logit_tensor)
    if logit is None:
        return NonFiniteReport("unknown", None, detail=" (logit not materialized)")
    lbl = jnp.asarray(label)
    loss = loss_forward(loss_attrs, logit, lbl)
    if not _finite(loss):
        return NonFiniteReport("loss", "loss", type(loss_attrs).__name__)

    # -- backward: reverse-topo per-op VJP ----------------------------------
    grad_env: Dict = {
        logit_tensor: jax.grad(lambda lg: loss_forward(loss_attrs, lg, lbl))(
            logit
        )
    }
    if not _finite(grad_env[logit_tensor]):
        return NonFiniteReport("backward", "loss", type(loss_attrs).__name__)
    for n in reversed(order):
        attrs = graph.op_attrs(n)
        if isinstance(attrs, (InputAttrs, WeightAttrs)):
            continue
        outs = graph.outputs_of(n)
        if not any(o in grad_env for o in outs):
            continue
        out_grads = tuple(
            grad_env.get(o, jnp.zeros_like(env[o])) for o in outs
        )
        in_tensors = graph.inputs_of(n)
        if is_parallel_op(attrs):
            in_grads = out_grads[:1]
        else:
            in_vals = [env[v] for v in in_tensors]
            op_rng = (
                jax.random.fold_in(rng, n.idx) if rng is not None else None
            )

            def op_fn(*xs, a=attrs, r=op_rng):
                data, w = split_slot_values(a, list(xs))
                return tuple(
                    kernel_forward(a, data, w, train=rng is not None, rng=r)
                )

            _, pullback = jax.vjp(op_fn, *in_vals)
            in_grads = pullback(out_grads)
        bad = any(not _finite(g) for g in in_grads)
        for v, g in zip(in_tensors, in_grads):
            grad_env[v] = grad_env[v] + g if v in grad_env else g
        if bad:
            name, ot = describe(n)
            return NonFiniteReport("backward", name, ot)
    return NonFiniteReport("unknown", None, detail=" (replay stayed finite)")


@dataclass
class HealthMonitor:
    """Per-step health policy enforcement over the in-jit step statistics.

    `observe()` is called once per step with the stats dict the jitted step
    produced (metrics.step_statistics). Reading the `ok` flag is the one
    host sync the monitor costs; everything else is host arithmetic. The
    localizer is a zero-arg-free callable (batch, label) -> NonFiniteReport
    installed by the owner (FFModel.fit wires it to the live graph/params).

    The monitor keeps its own trip counters; step-level skipped/nonfinite
    accounting in the metrics registry belongs to StepEventLog.emit (ONE
    counter family per fact — a monitor-side duplicate under a second name
    would leave consumers guessing which to trust).
    """

    policy: str = "off"
    localizer: Optional[Callable] = None
    nonfinite_steps: int = 0
    skipped_steps: int = 0
    last_report: Optional[NonFiniteReport] = None

    def __post_init__(self):
        assert self.policy in HEALTH_POLICIES, (
            f"health policy {self.policy!r} not in {HEALTH_POLICIES}"
        )

    @property
    def active(self) -> bool:
        return self.policy != "off"

    def observe(self, step: int, loss, stats, batch=None, label=None) -> bool:
        """Returns the step's finiteness. Applies the policy on a trip."""
        if not self.active or stats is None:
            return True
        ok = bool(stats["ok"])  # the one host readback
        if ok:
            return True
        self.nonfinite_steps += 1
        report = None
        # Blame the first trip (and every `raise`): the un-fused replay is
        # expensive, and a run that keeps tripping is tripping on the same
        # op. Localization needs the PRE-step parameters, which only the
        # guarded policies (skip_step/raise) preserve — under `warn` the
        # optimizer already applied the poisoned update, so a replay would
        # blame the first NaN weight instead of the op that produced it.
        if (
            self.localizer is not None
            and self.policy in ("skip_step", "raise")
            and (self.policy == "raise" or self.last_report is None)
        ):
            try:
                report = self.localizer(batch, label)
            except Exception as e:  # blame must never mask the trip itself
                report = NonFiniteReport(
                    "unknown", None, detail=f" (localizer failed: {e})"
                )
            self.last_report = report
        where = f": {report.describe()}" if report is not None else ""
        if not where and self.policy == "warn" and self.localizer is not None:
            where = (
                " (first-bad-op localization needs the skip_step/raise "
                "guard; under warn the poisoned update is already applied)"
            )
        msg = (
            f"non-finite loss/gradient at step {step} "
            f"(loss={float(loss)!r}, grad_norm="
            f"{float(stats['grad_norm'])!r}){where}"
        )
        if self.policy == "raise":
            raise NonFiniteError(msg, report)
        if self.policy == "skip_step":
            # params/opt state already guarded inside the jitted step
            self.skipped_steps += 1
            print(f"[flexflow_tpu][health] SKIPPED {msg}")
        else:
            print(f"[flexflow_tpu][health] WARN {msg}")
        return False

    def summary(self) -> Dict[str, object]:
        return {
            "policy": self.policy,
            "nonfinite_steps": self.nonfinite_steps,
            "skipped_steps": self.skipped_steps,
            "first_bad_op": (
                self.last_report.op_name if self.last_report else None
            ),
        }


def record_step_health(
    event_log,
    monitor: Optional[HealthMonitor],
    step: int,
    loss,
    stats,
    *,
    batch=None,
    label=None,
    tokens: Optional[int] = None,
    step_t0: Optional[float] = None,
) -> bool:
    """The per-step telemetry wiring shared by FFModel.fit and
    instance-level training loops (examples/mlp.py): read the step's
    statistics, enforce the health policy, emit the JSONL event. Returns
    the step's finiteness.

    Ordering matters twice here: the wall-clock is captured at the FIRST
    host sync (reading `ok` materializes the step's device work) and
    BEFORE any policy action, so a tripped step's event records the step's
    real time, not the localizer's un-fused replay; and under the `raise`
    policy the event is emitted and the log closed BEFORE the error
    propagates — the crash event is the one that matters."""
    import time

    ok = True
    if stats is not None and (monitor is not None or event_log is not None):
        ok = bool(stats["ok"])  # the step's one host sync
    wall_ms = (
        (time.perf_counter() - step_t0) * 1000.0
        if step_t0 is not None
        else None
    )
    health_err = None
    skipped = False
    if monitor is not None:
        try:
            ok = monitor.observe(step, loss, stats, batch=batch, label=label)
        except NonFiniteError as e:
            ok = False
            health_err = e
        skipped = (not ok) and monitor.policy == "skip_step"
    if event_log is not None:
        event_log.emit(
            step=step,
            loss=loss,
            wallclock_ms=wall_ms,
            tokens_per_s=(
                tokens / max(wall_ms / 1000.0, 1e-9)
                if tokens is not None and wall_ms is not None
                else None
            ),
            grad_norm=stats.get("grad_norm") if stats else None,
            param_norm=stats.get("param_norm") if stats else None,
            update_ratio=stats.get("update_ratio") if stats else None,
            skipped=skipped,
            nonfinite=not ok,
        )
    if health_err is not None:
        if event_log is not None:
            event_log.close()
        raise health_err
    return ok
