"""Device time per step under the plan's parallel-op nodes (scopes of kind
`parallel_repartition`, `parallel_combine`, `parallel_replicate`,
`parallel_reduction`): what their sharding constraints hold the TensorCore
for, both phases. Mean over chips; absent on one chip."""

from step_anatomy import PARALLEL_PREFIX, ms_per_step

LAYER = "lowering and backends"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(ctx):
    if ctx["chips"] < 2:
        return None
    return ms_per_step(ctx, kind_prefix=PARALLEL_PREFIX)
