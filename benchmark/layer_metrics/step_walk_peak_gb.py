"""The peak of the program's own liveness walk over the compiled train
step's schedule, per chip: `flexflow_tpu/observability/step_account.py`,
`account()["walk"]["peak_bytes"]` (arguments, results in allocations of their
own, and every buffer of memory space 0 from the instruction that makes it to
its last reader). An ESTIMATE of XLA's assignment that names its holders:
`held_at_peak`, `kept_for_backward` and `walk_over_xla` (this over
`step_xla_peak_gb`) go to standard error with `step_account.report()`.
Absent where the program has no such module."""

from layer_metrics.step_xla_peak_gb import for_context

LAYER = "lowering and backends"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "step_hbm_gb"


def read(ctx):
    account = for_context(ctx)
    if account is None:
        return None
    return account["walk"]["peak_bytes"] / 1e9
