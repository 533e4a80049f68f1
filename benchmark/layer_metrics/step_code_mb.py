"""The size of the compiled train step's machine code
(`memory_analysis().generated_code_size_in_bytes`), which set-up loads on
every run after a cell's first: `flexflow_tpu/observability/step_account.py`,
`account()["memory"]["code"]`. Absent where the program has no such
module."""

from layer_metrics.step_xla_peak_gb import for_context

LAYER = "lowering and backends"
UNIT = "MB"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    account = for_context(ctx)
    if account is None:
        return None
    return account["memory"]["code"] / 1e6
