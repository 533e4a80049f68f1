"""MLP training example — the minimal end-to-end app.

Equivalent of reference examples/cpp/MLP_Unify/mlp.cc:23-88 (the minimal
train-loop example: 4 dense layers 8192 wide, SGD, synthetic data, prints
ELAPSED TIME / THROUGHPUT after an execution fence) with the same CLI flags
(-e/-b/--lr/--only-data-parallel...).

Run: python examples/mlp.py -e 1 -b 64 --steps 30
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.kernels.metrics import METRIC_ACCURACY
from flexflow_tpu.local_execution import FFConfig, ModelTrainingInstance
from flexflow_tpu.op_attrs import DataType
from flexflow_tpu.op_attrs.ops.loss_functions import (
    SparseCategoricalCrossEntropyLossAttrs,
)
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs


def build_mlp_cg(batch_size: int, in_dim: int, hidden: int, num_hidden: int, classes: int):
    """reference mlp.cc:35-52: input -> N x dense(hidden, relu) -> dense(classes)."""
    b = ComputationGraphBuilder()
    x = b.create_input([batch_size, in_dim], name="x")
    h = x
    for i in range(num_hidden):
        h = b.dense(h, hidden, name=f"fc{i}")
        h = b.relu(h)
    logits = b.dense(h, classes, name="out")
    return b.graph, logits


def main():
    p = argparse.ArgumentParser()
    FFConfig.add_args(p)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--in-dim", type=int, default=1024)
    p.add_argument("--hidden", type=int, default=1024)
    p.add_argument("--num-hidden", type=int, default=4)
    p.add_argument("--classes", type=int, default=10)
    args = p.parse_args()
    cfg = FFConfig.from_args(args)

    cg, logits = build_mlp_cg(
        cfg.batch_size, args.in_dim, args.hidden, args.num_hidden, args.classes
    )
    # run-health telemetry (--metrics-dir / --health-policy): the instance
    # fuses grad/param norms + the nonfinite flag into the jitted step;
    # the loop below emits one JSONL event per step and enforces the policy
    # (observability/{metrics,health}.py — same wiring FFModel.fit does)
    health_on = cfg.health_policy not in ("", "off")
    inst = ModelTrainingInstance(
        cg,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        SGDOptimizerAttrs(lr=cfg.learning_rate, weight_decay=cfg.weight_decay),
        metrics=frozenset({METRIC_ACCURACY}),
        collect_step_stats=bool(cfg.metrics_dir) or health_on,
        guard_nonfinite_updates=cfg.health_policy in ("skip_step", "raise"),
    )
    params, opt_state = inst.initialize(seed=cfg.seed)

    event_log = monitor = None
    if cfg.metrics_dir:
        from flexflow_tpu.observability.metrics import StepEventLog

        event_log = StepEventLog(cfg.metrics_dir)
    inst_params_ref = {"params": params}
    if health_on:
        from flexflow_tpu.observability.health import (
            HealthMonitor,
            localize_first_nonfinite,
        )

        def _localize(batch, label):
            return localize_first_nonfinite(
                cg, inst_params_ref["params"], batch,
                logit_tensor=logits, label=label,
                loss_attrs=inst.loss_attrs,
            )

        monitor = HealthMonitor(cfg.health_policy, localizer=_localize)

    rs = np.random.RandomState(cfg.seed)
    x = jnp.asarray(rs.randn(cfg.batch_size, args.in_dim), jnp.float32)
    y = jnp.asarray(rs.randint(0, args.classes, cfg.batch_size), jnp.int32)

    from flexflow_tpu.kernels.profiling import force_sync

    # warmup/compile (the reference's init_operators + first traced iteration)
    params, opt_state, loss, _ = inst.train_step(params, opt_state, {"x": x}, y)
    force_sync(loss)

    # --profile-trace-dir: an XLA trace of the measured loop, its host spans
    # (step > dispatch) on the host plane; no span waits for the device, so
    # the loop runs as it does untraced
    import contextlib

    trace_ctx = contextlib.nullcontext()
    if cfg.profile_trace_dir:
        trace_ctx = jax.profiler.trace(cfg.profile_trace_dir)

    with trace_ctx:
        start = time.perf_counter()
        for step in range(args.steps):
            step_t0 = (
                time.perf_counter()
                if (event_log is not None or monitor is not None)
                else None
            )
            params, opt_state, loss, metrics = inst.train_step(
                params, opt_state, {"x": x}, y
            )
            if step_t0 is not None:
                # one host sync per step, paid only when telemetry is on —
                # the same shared wiring FFModel.fit uses (event emission,
                # policy enforcement, crash-event-before-raise)
                from flexflow_tpu.observability.health import (
                    record_step_health,
                )

                inst_params_ref["params"] = params
                record_step_health(
                    event_log, monitor, step + 1, loss,
                    inst.last_step_stats, batch={"x": x}, label=y,
                    tokens=cfg.batch_size, step_t0=step_t0,
                )
            if cfg.print_freq and step % cfg.print_freq == 0:
                print(f"step {step}: loss {float(loss):.4f}")
        force_sync(loss)
        # timed INSIDE the session: the profiler's exit writes the trace to
        # disk, which must not count against throughput
        elapsed = time.perf_counter() - start

    num_samples = args.steps * cfg.batch_size
    print(
        f"ELAPSED TIME = {elapsed:.4f}s, "
        f"THROUGHPUT = {num_samples / elapsed:.2f} samples/s"
    )
    if event_log is not None:
        event_log.close()
        print(f"run-health events: {event_log.path}")
    if monitor is not None and monitor.nonfinite_steps:
        print(f"run-health summary: {monitor.summary()}")


if __name__ == "__main__":
    main()
