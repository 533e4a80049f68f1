"""Bytes of the compiled train step's results that XLA laid in memory space 1
(`S(1)` in their layouts: the chip's fast memory, where an operation reads
them without a trip to HBM), summed over the ENTRY computation's
instructions: `flexflow_tpu/observability/step_account.py`, the sum of
`account()["rows"][*]["s1_bytes"]`; by node kind on standard error
(`step_account.report()`). It moved with the shape of the code and not with
what the step computes (PERF.md, PR 61 and PR 63), and time went with it.
Absent where the program has no such module."""

from layer_metrics.step_xla_peak_gb import for_context

LAYER = "lowering and backends"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(ctx):
    account = for_context(ctx)
    if account is None:
        return None
    return sum(row["s1_bytes"] for row in account["rows"]) / 1e9
