"""Serving subsystem (ISSUE 12): forward-only searched plans, a
KV cache with explicit shard/replica degrees, and a continuous-batching
engine under PR-8-style supervision.

Layering (each importable without the ones below it):

- `plan` — the serving SEARCH: forward-only pricing (prefill/decode
  separately, through the PR-9 cost store's `-fwd` family) under a
  ms/token objective, with KV-cache residency making over-capacity
  plans INFEASIBLE in both DPs (MEM005).
- `kv_cache` — the cache as a parallel tensor: degrees bound to the
  plan's sharding, lowered via SNIPPETS-[1]-style regex partition rules.
- `program` — the lowered runtime: one donated prefill program + a
  `lax.scan` fused decode window.
- `engine` — request queue, continuous batching at decode-window
  boundaries, watchdog/FaultChannel replica shedding, JSONL request
  metrics with an SLO-violation counter.
"""

from flexflow_tpu.analysis.memory_accounting import ServingMemorySpec
from flexflow_tpu.serving.engine import (
    RequestRecord,
    ServeRequest,
    ServingEngine,
)
from flexflow_tpu.serving.kv_cache import (
    CacheLayer,
    attention_layers,
    cache_partition_rules,
    cache_shardings,
    init_cache,
    match_partition_rules,
    per_device_cache_bytes,
)
from flexflow_tpu.serving.model import ServingLMConfig, build_serving_lm
from flexflow_tpu.serving.plan import (
    ServingPlan,
    ServingWorkload,
    optimize_serving_plan,
    serving_rules,
    serving_search_context,
)
from flexflow_tpu.serving.program import ServingProgram, init_serving_params

__all__ = [
    "CacheLayer",
    "RequestRecord",
    "ServeRequest",
    "ServingEngine",
    "ServingLMConfig",
    "ServingMemorySpec",
    "ServingPlan",
    "ServingProgram",
    "ServingWorkload",
    "attention_layers",
    "build_serving_lm",
    "cache_partition_rules",
    "cache_shardings",
    "init_cache",
    "init_serving_params",
    "match_partition_rules",
    "optimize_serving_plan",
    "per_device_cache_bytes",
    "serving_rules",
    "serving_search_context",
]
