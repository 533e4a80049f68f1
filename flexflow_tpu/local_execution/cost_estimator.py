"""Measured cost estimation: Unity cost model v2 on TPU.

Reference: lib/local-execution/src/local_cost_estimator.cc:29-92 — build a
one-op graph with the op's *piece* shapes (per-device shard sizes), run
init+fwd+bwd for real, return CostDetails{elapsed_ms, mem_bytes}; parallel ops
cost 0 compute. The comm side (TensorSetMovement) is costed analytically from
the machine spec's ICI/DCN bandwidths (replacing the legacy Simulator's
MachineModel, SURVEY.md §2.8).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.kernels.profiling import ProfilingSettings, profile_fn
from flexflow_tpu.op_attrs.core import (
    OpAttrs,
    get_weight_shapes,
    get_output_shapes,
    is_parallel_op,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    get_piece_shape,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape


@dataclass(frozen=True)
class CostDetails:
    """reference: CostDetails{total_elapsed_time, total_mem_usage}."""

    elapsed_ms: float
    mem_bytes: int


def optimizer_state_slots_of(optimizer_attrs) -> int:
    """Per-weight optimizer-state tensor count of the run's optimizer — the
    memory-model term callers feed LocalCostEstimator so mem_bytes prices
    the optimizer actually in use (Adam m/v = 2, SGD+momentum = 1, plain
    SGD = 0; unknown optimizers price conservatively as Adam-like)."""
    from flexflow_tpu.pcg.optimizer import (
        AdamOptimizerAttrs,
        SGDOptimizerAttrs,
    )

    if isinstance(optimizer_attrs, AdamOptimizerAttrs):
        return 2
    if isinstance(optimizer_attrs, SGDOptimizerAttrs):
        return 1 if optimizer_attrs.momentum > 0.0 else 0
    return 2


class LocalCostEstimator:
    """Measure-by-running per-op cost on a single device.

    Results are memoized on (attrs, piece input shapes) — the reference's
    cost cache keyed by OpCostEstimateKey — and, when a persistent
    `cost_store` (compiler/cost_store.py) is attached, consulted/written
    through it so a leaf measured in ANY past session is never re-timed:
    the cross-session analogue of the reference Simulator's per-op
    cudaEvent caches (simulator.h:161-228).
    """

    def __init__(
        self,
        settings: Optional[ProfilingSettings] = None,
        optimizer_state_slots: int = 2,
        cost_store=None,
        forward_only: bool = False,
        serving=None,
    ) -> None:
        """optimizer_state_slots: per-weight optimizer-state tensors resident
        alongside the weight and its gradient (Adam's m/v = 2, the default
        FFModel optimizer family; SGD-momentum = 1, plain SGD = 0). Part of
        the memory model, so part of the cache key space — one estimator
        instance prices one optimizer regime.

        forward_only (ISSUE 12, serving): measure the op's FORWARD kernel
        only — the regime a serving plan's prefill/decode programs run in.
        A `cost_store` attached to a forward-only estimator must carry a
        forward-marked measurement fingerprint (compiler/cost_store.py
        `forward_fingerprint`) so inference measurements never contaminate
        the training store's fwd+bwd entries. `serving` optionally carries
        the ServingMemorySpec so mem_bytes prices inference residency."""
        self.settings = settings or ProfilingSettings(warmup_iters=2, measure_iters=4)
        self.optimizer_state_slots = optimizer_state_slots
        self.forward_only = bool(forward_only)
        self.serving = serving
        if self.forward_only and cost_store is not None:
            fp = getattr(cost_store, "fingerprint", "")
            assert "fwd" in fp, (
                "a forward-only estimator requires a forward-marked cost "
                "store (CostStore(..., fingerprint=forward_fingerprint())) "
                "— writing inference timings under training keys would "
                "poison every future training search"
            )
        self.cost_store = cost_store
        self._cache: Dict = {}

    def estimate_operator_cost(
        self,
        attrs: OpAttrs,
        piece_input_shapes: Sequence[TensorShape],
        piece_weight_shapes: Optional[Sequence[TensorShape]] = None,
    ) -> CostDetails:
        import math

        from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs

        if isinstance(attrs, InputAttrs):
            # no kernel, but real residency: the step's batch
            from flexflow_tpu.analysis.memory_accounting import estimate_memory

            mem = estimate_memory(attrs, [])
            return CostDetails(0.0, mem.total)
        if is_parallel_op(attrs) or isinstance(attrs, WeightAttrs):
            # no kernel: parallel ops lower to sharding constraints, and
            # weight nodes are value bindings (their bytes are charged at
            # the consuming op's weight slots)
            return CostDetails(0.0, 0)
        inputs = tuple(piece_input_shapes)
        weights = tuple(piece_weight_shapes) if piece_weight_shapes else None
        key = (attrs, inputs, weights)
        if key in self._cache:
            return self._cache[key]
        if self.cost_store is not None:
            # tier 2 of the fallthrough: a measurement from a past session
            # (or a past plan audit) prices the leaf without running it
            hit = self.cost_store.get_op(attrs, inputs, weights)
            if hit is not None:
                cost = CostDetails(hit[0], hit[1])
                self._cache[key] = cost
                return cost
        cost = self._measure(attrs, piece_input_shapes, piece_weight_shapes)
        if self.cost_store is not None and not math.isnan(cost.elapsed_ms):
            # tier 3 writes back so the next session starts warm; inf
            # (unrunnable mapping) is cached as a verdict so the failed
            # jit traces are not re-paid either
            self.cost_store.put_op(
                attrs, inputs, weights, cost.elapsed_ms, cost.mem_bytes
            )
        self._cache[key] = cost
        return cost

    def estimate_operator_cost_parallel(
        self,
        attrs: OpAttrs,
        parallel_input_shapes: Sequence[ParallelTensorShape],
        parallel_output_shapes: Sequence[ParallelTensorShape] = (),
    ) -> CostDetails:
        """Cost one *task* of the op: measure on piece shapes. The leaf key
        carries every incoming slot (data + weights, problem_tree._leaf_key);
        only the data slots feed shape inference — _measure synthesizes
        weights itself. `parallel_output_shapes` matters only for Input
        leaves: their window-buffer residency is the OUTPUT's per-device
        piece (a batch-sharded input stages 1/degree of the batch per
        device), which no input slot carries."""
        from flexflow_tpu.local_execution.training_backing import (
            split_slot_values,
        )
        from flexflow_tpu.op_attrs.ops import InputAttrs

        if isinstance(attrs, InputAttrs) and parallel_output_shapes:
            from flexflow_tpu.analysis.memory_accounting import (
                estimate_memory,
            )

            mem = estimate_memory(
                attrs,
                [],
                output_shapes=[
                    get_piece_shape(s) for s in parallel_output_shapes
                ],
            )
            return CostDetails(0.0, mem.total)
        pieces = [get_piece_shape(s) for s in parallel_input_shapes]
        data, weights = split_slot_values(attrs, pieces)
        return self.estimate_operator_cost(attrs, data, weights or None)

    def _measure(
        self, attrs: OpAttrs, input_shapes, weight_shapes=None
    ) -> CostDetails:
        """Measure with the task's actual weight piece shapes when given (a
        weight-sharded task does less compute); ops whose kernels derive
        sizes from attrs (e.g. MHA's packed head count) reject piece weights,
        so fall back to the synthesized full-weight measurement, and price an
        entirely-unrunnable candidate at infinity rather than crashing the
        search (mirrors AnalyticTPUCostEstimator's inf-on-broken-mapping)."""
        try:
            synth = get_weight_shapes(attrs, list(input_shapes))
        except (AssertionError, IndexError, ValueError, TypeError):
            return CostDetails(float("inf"), 0)
        candidates = []
        if weight_shapes is not None and list(weight_shapes) != list(synth):
            candidates.append(list(weight_shapes))
        candidates.append(list(synth))
        for ws in candidates:
            try:
                return self._measure_with(attrs, list(input_shapes), ws)
            except (AssertionError, IndexError, ValueError, TypeError):
                continue
        return CostDetails(float("inf"), 0)

    def _measure_with(
        self, attrs: OpAttrs, input_shapes, weight_shapes
    ) -> CostDetails:
        import jax
        import jax.numpy as jnp

        from flexflow_tpu.kernels.ops import forward as kernel_forward

        rng = np.random.default_rng(0)

        def make_arr(shape: TensorShape):
            if shape.dtype.is_floating:
                return jnp.asarray(
                    rng.standard_normal(shape.dims), shape.dtype.to_jnp()
                )
            return jnp.asarray(
                rng.integers(0, 2, shape.dims), shape.dtype.to_jnp()
            )

        inputs = [make_arr(s) for s in input_shapes]
        weights = [make_arr(s) for s in weight_shapes]

        def fwd(inputs, weights):
            return kernel_forward(attrs, inputs, weights)

        def fwd_bwd(inputs, weights):
            def scalar(inputs, weights):
                outs = kernel_forward(attrs, inputs, weights)
                return sum(
                    jnp.sum(o) if jnp.issubdtype(o.dtype, jnp.floating) else 0.0
                    for o in outs
                )

            return jax.grad(scalar, argnums=(0, 1))(inputs, weights)

        if self.forward_only:
            # serving regime: the deployed program is the forward pass
            # alone (donated prefill / fused decode), so that is what the
            # plan must be priced on
            elapsed_ms = profile_fn(jax.jit(fwd), self.settings, inputs, weights)
        else:
            jit_fb = jax.jit(fwd_bwd)
            try:
                elapsed_ms = profile_fn(jit_fb, self.settings, inputs, weights)
            except TypeError:
                # Non-differentiable op (int outputs): time forward only.
                jit_f = jax.jit(fwd)
                elapsed_ms = profile_fn(jit_f, self.settings, inputs, weights)

        out_shapes = get_output_shapes(attrs, input_shapes)
        # Training-step residency of this op: activations in + their grads,
        # weights + grads + optimizer slots, outputs + their grads — ONE
        # shared implementation (analysis/memory_accounting.estimate_memory)
        # also read by the DP's feasibility pruner and the static liveness
        # verifier, so the estimator and the verifier cannot drift.
        from flexflow_tpu.analysis.memory_accounting import estimate_memory

        mem = estimate_memory(
            attrs,
            input_shapes,
            weight_shapes,
            out_shapes,
            optimizer_state_slots=self.optimizer_state_slots,
            serving=self.serving,
        )
        return CostDetails(elapsed_ms, mem.total)
