"""Serving-plan search: forward-only PCGs under a ms/token objective
(ISSUE 12).

Inference re-points the Unity machinery at a forward-only donated
program with a LATENCY objective: the same rewrite lattice and
machine-mapping DPs, but

- ops priced on their FORWARD kernel alone (`forward_only` estimators —
  measured entries land in the PR-9 cost store under a `-fwd`
  fingerprint so they never contaminate training keys),
- PREFILL and DECODE priced separately: two searches over the two
  shapes of the same model ([slots, prompt_len] and [slots, 1]), sharing
  one cost store, combined as
  ``ms/token = decode_ms + prefill_ms / gen_len``
  (each generated token pays one decode dispatch plus its amortized
  share of the prompt's prefill),
- the KV cache priced as residency: the `ServingMemorySpec` rides the
  MachineMappingContext, so a plan whose per-device cache + forward
  residency exceeds `hbm_gb` is INFEASIBLE in both DPs and rejected by
  `evaluate_pcg` with the same MEM005 verdict `ffcheck --memory
  --serving` reports — a budgeted serving search can never select a plan
  ffcheck rejects.

Sequence-parallel attention rules (Ring/Ulysses) are excluded: the
cached-decode runtime does not lower a position-sharded rotating cache
(kv_cache.py notes the accounting is already ahead of the runtime
there).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from flexflow_tpu.analysis.memory_accounting import ServingMemorySpec

__all__ = [
    "ServingPlan",
    "ServingWorkload",
    "optimize_serving_plan",
    "serving_rules",
    "serving_search_context",
]

# rule-name substrings the serving runtime cannot lower (see module doc)
_EXCLUDED_RULE_TOKENS = ("sequence_parallel_attention",)


@dataclass(frozen=True)
class ServingWorkload:
    """The serving regime a plan is searched for."""

    prompt_len: int
    gen_len: int
    max_concurrent: int
    slo_ms_per_token: float = 0.0

    def cache_spec(
        self, max_seq_len: Optional[int] = None, kv_dtype_bytes: int = 4
    ) -> ServingMemorySpec:
        return ServingMemorySpec(
            max_concurrent_seqs=self.max_concurrent,
            max_seq_len=(
                max_seq_len
                if max_seq_len is not None
                else self.prompt_len + self.gen_len
            ),
            kv_dtype_bytes=kv_dtype_bytes,
        )


@dataclass
class ServingPlan:
    """The searched serving plan: separately-searched prefill and decode
    (PCG, mapping) pairs with the combined latency objective."""

    decode: object  # GraphOptimizeResult
    prefill: object  # GraphOptimizeResult
    workload: ServingWorkload
    cache_spec: ServingMemorySpec
    ms_per_token: float = 0.0
    decode_ms: float = 0.0
    prefill_ms: float = 0.0
    provenance: Dict[str, object] = field(default_factory=dict)


def serving_rules(machine_spec):
    """The serving search's rewrite rules: the standard parallelization
    lattice minus the sequence-parallel attention rewrites the cached
    runtime cannot lower."""
    from flexflow_tpu.substitutions.rules import generate_parallelization_rules

    ndev = machine_spec.num_devices
    degrees = [d for d in range(2, ndev + 1) if ndev % d == 0]
    rules = generate_parallelization_rules(degrees)
    return [
        r
        for r in rules
        if not any(tok in r.name for tok in _EXCLUDED_RULE_TOKENS)
    ]


def serving_search_context(
    machine_spec,
    cache_spec: ServingMemorySpec,
    *,
    hbm_gb: float = 0.0,
    cost_store_dir: Optional[str] = None,
    cost_model: str = "analytic",
):
    """A MachineMappingContext for serving searches: forward-only
    pricing, the KV cache in the memory model, measured entries flowing
    through a forward-fingerprinted view of the persistent cost store."""
    import jax

    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
        AnalyticTPUCostEstimator,
        TPUCostEstimator,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingContext,
    )

    # same device-kind-keyed machine constants as FFModel._compile_searched:
    # a serving search priced with TPU numbers but executed on the CPU
    # test mesh would pick plans the emulation cannot afford
    from flexflow_tpu.compiler.machine_constants import machine_constants

    mc = machine_constants()
    peak_flops, hbm_gbps = mc.peak_flops, mc.hbm_gbps
    ici_lat_ms, dcn_lat_ms = mc.ici_latency_ms, mc.dcn_latency_ms
    cost_store = None
    if cost_store_dir:
        import os

        from flexflow_tpu.compiler.cost_store import (
            CostStore,
            forward_fingerprint,
        )

        cost_store = CostStore(
            os.path.join(cost_store_dir, "cost_db.json"),
            fingerprint=forward_fingerprint(),
        )
    if cost_model == "measured":
        from flexflow_tpu.local_execution.cost_estimator import (
            LocalCostEstimator,
        )

        estimator = TPUCostEstimator(
            machine_spec,
            local_cost_estimator=LocalCostEstimator(
                optimizer_state_slots=0,
                cost_store=cost_store,
                forward_only=True,
                serving=cache_spec,
            ),
            ici_latency_ms=ici_lat_ms,
            dcn_latency_ms=dcn_lat_ms,
            emulated_mesh=jax.default_backend() == "cpu",
            cost_store=cost_store,
        )
    else:
        estimator = AnalyticTPUCostEstimator(
            machine_spec,
            peak_flops=peak_flops,
            hbm_gbps=hbm_gbps,
            ici_latency_ms=ici_lat_ms,
            dcn_latency_ms=dcn_lat_ms,
            emulated_mesh=jax.default_backend() == "cpu",
            cost_store=cost_store,
            forward_only=True,
        )
    return MachineMappingContext(
        estimator,
        make_default_allowed_machine_views(),
        overlap_fraction=0.5,
        memory_budget_bytes=(hbm_gb * 2**30 if hbm_gb and hbm_gb > 0 else 0.0),
        optimizer_state_slots=0,
        serving=cache_spec,
    ), cost_store


def optimize_serving_plan(
    model_builder,
    machine_spec,
    workload: ServingWorkload,
    *,
    hbm_gb: float = 0.0,
    budget: int = 4,
    alpha: float = 1.05,
    cost_store_dir: Optional[str] = None,
    cost_model: str = "analytic",
    max_seq_len: Optional[int] = None,
) -> ServingPlan:
    """Search the serving plan. `model_builder(batch, seq_len)` returns
    the (ComputationGraph, logit tensor) of the model at one shape — it
    is called twice, for the prefill shape [max_concurrent, prompt_len]
    and the decode shape [max_concurrent, 1]."""
    from flexflow_tpu.compiler.unity_algorithm import (
        OptimizerConfig,
        graph_optimize,
    )
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    cache_spec = workload.cache_spec(max_seq_len)
    context, cost_store = serving_search_context(
        machine_spec,
        cache_spec,
        hbm_gb=hbm_gb,
        cost_store_dir=cost_store_dir,
        cost_model=cost_model,
    )
    rules = serving_rules(machine_spec)
    cfg = OptimizerConfig(alpha=alpha, budget=budget)

    decode_cg, _ = model_builder(workload.max_concurrent, 1)
    decode = graph_optimize(
        pcg_from_computation_graph(decode_cg), context, machine_spec,
        rules, cfg,
    )
    prefill_cg, _ = model_builder(workload.max_concurrent, workload.prompt_len)
    prefill = graph_optimize(
        pcg_from_computation_graph(prefill_cg), context, machine_spec,
        rules, cfg,
    )
    if cost_store is not None:
        cost_store.save()

    gen = max(workload.gen_len, 1)
    decode_ms = decode.runtime
    prefill_ms = prefill.runtime
    # the latency objective: every generated token pays one decode
    # dispatch plus its amortized share of the prompt's prefill
    ms_per_token = decode_ms + prefill_ms / gen
    provenance: Dict[str, object] = {
        "objective": "ms_per_token",
        "ms_per_token": ms_per_token,
        "decode_ms": decode_ms,
        "prefill_ms": prefill_ms,
        "gen_len": gen,
        "forward_only": True,
        "cost_model": cost_model,
        "hbm_gb": hbm_gb or None,
        "serving": {
            "max_concurrent_seqs": cache_spec.max_concurrent_seqs,
            "max_seq_len": cache_spec.max_seq_len,
            "kv_dtype_bytes": cache_spec.kv_dtype_bytes,
        },
        "excluded_rules": list(_EXCLUDED_RULE_TOKENS),
    }
    for phase, result in (("decode", decode), ("prefill", prefill)):
        telem = result.telemetry or {}
        provenance[phase] = {
            "estimated_ms": result.runtime,
            "serial_ms": result.serial_runtime,
            "explored": result.explored,
            "evaluations": telem.get("evaluations"),
            "infeasible": telem.get("infeasible"),
            "dedup_hits": telem.get("dedup_hits"),
            # whether wiring-blind dedup could have skipped candidates
            # (the A/B-artifact observability satellite, same contract as
            # FFModel.search_provenance)
            "symmetry_dedup": telem.get("symmetry_dedup"),
            "signature_version": telem.get("signature_version"),
        }
    if cost_store is not None:
        provenance["cost_db"] = cost_store.provenance()
    return ServingPlan(
        decode=decode,
        prefill=prefill,
        workload=workload,
        cache_spec=cache_spec,
        ms_per_token=ms_per_token,
        decode_ms=decode_ms,
        prefill_ms=prefill_ms,
        provenance=provenance,
    )
