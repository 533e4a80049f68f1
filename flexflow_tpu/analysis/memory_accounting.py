"""Shared training-step memory accounting (ISSUE 10 satellite).

ONE implementation of "how many bytes does this op keep resident on a
device during a training step", read by all three memory consumers so they
cannot drift:

- `LocalCostEstimator` (local_execution/cost_estimator.py) prices
  `CostDetails.mem_bytes` with it,
- the machine-mapping DPs (python + native) prune over-capacity leaves
  with `leaf_step_memory_bytes`,
- the static liveness analysis (`analysis/memory_analysis.py`) builds its
  per-device timelines from the same per-tensor terms.

The model (the round-3/5 accounting, now centralized):

    activations: every data input x2 (the activation AND its gradient are
                 simultaneously live during the op's backward),
    weights:     every weight slot x (2 + optimizer_state_slots)
                 (weight + grad + the optimizer's per-weight state tensors
                 — Adam m/v = 2, SGD+momentum = 1, plain SGD = 0),
    outputs:     every output x2 (out + out-grad),
    input layers (InputAttrs): the step's one batch, x1 (no gradient).

Weight layers (and the pure reshard chains hanging off them) account to
zero here: parameters are STORED in the sharded form the consuming op
reads (the executor's initialize() places them under the post-reshard
sharding from init), so their bytes — value + grad + optimizer slots —
are charged once, at the consuming op's weight slots, whose piece shapes
already reflect that sharding. Charging the unsharded Weight layer would
make every parameter-parallel plan look as heavy as the serial one.

Serving mode (ISSUE 12): passing a `ServingMemorySpec` switches the
accounting to forward-only inference residency — activations / weights /
outputs at x1 (no gradients, no optimizer slots) — and charges each
attention op its per-device share of the persistent KV cache: 2 (K+V) x sequences x max_seq_len x heads x head_dim
x dtype bytes, divided by the op's batch / sequence / head shard degrees
(the cache is a parallel tensor whose degrees are BOUND to the attention
op's own sharding — serving/kv_cache.py lowers the same degrees to
partition rules). This is what makes "max concurrent sequences per
device" a static verdict (MEM005) and over-capacity serving plans
INFEASIBLE in both machine-mapping DPs.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache
from typing import Optional, Sequence


@dataclass(frozen=True)
class ServingMemorySpec:
    """The serving-side memory regime: how many sequences the engine may
    admit concurrently, how long each may grow, and the KV element width.
    Hashable (frozen) so it can ride the leaf-accounting lru_cache and the
    MachineMappingContext."""

    max_concurrent_seqs: int
    max_seq_len: int
    kv_dtype_bytes: int = 4

    def per_seq_cache_bytes(self, num_heads: int, k_dim: int, v_dim: int,
                            num_layers: int = 1) -> int:
        """Unsharded K+V bytes ONE sequence holds across `num_layers`
        attention layers (the unit of the MEM005 admission verdict)."""
        return (
            num_layers
            * self.max_seq_len
            * num_heads
            * (k_dim + v_dim)
            * self.kv_dtype_bytes
        )


def kv_cache_piece_bytes(attrs, q_parallel_shape, w_parallel_shape,
                         serving: "ServingMemorySpec") -> int:
    """Per-device KV-cache residency of ONE attention op under `serving`,
    from the op's parallel shapes — THE shared formula (leaf accounting,
    the liveness analysis, and the serving plan layer all read it, so the
    DP pruner and `ffcheck --memory --serving` cannot drift).

    The cache is a parallel tensor [seqs, heads, max_seq_len, head_dim]
    whose degrees are bound to the attention op's own sharding:
    sequences shard with the op's batch degree (q dim 0), cache positions
    with its sequence degree (q dim 1 — ring/Ulysses attention shards KV
    along seq), heads with the packed weight's head degree (w dim 1)."""
    from flexflow_tpu.op_attrs.ops import MultiHeadAttentionAttrs

    if not isinstance(attrs, MultiHeadAttentionAttrs):
        return 0
    batch_degree = max(q_parallel_shape.shard_dim_at(0).degree, 1)
    seq_degree = 1
    if q_parallel_shape.num_dims >= 3:
        seq_degree = max(q_parallel_shape.shard_dim_at(1).degree, 1)
    head_degree = 1
    if w_parallel_shape is not None and w_parallel_shape.num_dims >= 2:
        head_degree = max(w_parallel_shape.shard_dim_at(1).degree, 1)
    seqs = math.ceil(serving.max_concurrent_seqs / batch_degree)
    positions = math.ceil(serving.max_seq_len / seq_degree)
    # grouped-query attention caches its key/value heads, not the queries'
    heads = math.ceil(attrs.kv_heads / head_degree)
    return (
        seqs
        * positions
        * heads
        * (attrs.k_proj_size + attrs.v_proj_size)
        * serving.kv_dtype_bytes
    )


def update_shard_ways(parallel_shape, num_devices: Optional[int] = None) -> int:
    """How many ways finer than the PCG places it the executor stores this
    weight (its float32 master and each optimizer slot) and computes its
    update (`parallel/sharding.update_partition_spec`, in the PCG's
    terms): the replicas of the weight, if some dimension's piece takes
    them all, else 1. With the mesh's size known the replicas are its
    devices over the weight's shard degrees (under GSPMD every op runs on
    the whole mesh, so an axis the weight does not use holds a copy);
    without it, the view-independent `discard_copy_degree`, which is never
    more."""
    shard = 1
    for d in parallel_shape.shard_degrees():
        shard *= max(int(d), 1)
    if num_devices:
        ways = int(num_devices) // shard
    else:
        ways = int(parallel_shape.discard_copy_degree)
    if ways <= 1:
        return 1
    for size, d in zip(parallel_shape.sizes(), parallel_shape.shard_degrees()):
        if size % (max(int(d), 1) * ways) == 0:
            return ways
    return 1


@dataclass(frozen=True)
class OpStepMemory:
    """Per-category step residency of one op, in bytes (one device's
    share when built from piece shapes)."""

    activations: int = 0  # data inputs
    activation_grads: int = 0  # their gradients (live during backward)
    weights: int = 0
    weight_grads: int = 0
    optimizer_state: int = 0
    outputs: int = 0
    output_grads: int = 0
    input_batch: int = 0  # an input layer's batch (no gradient)
    kv_cache: int = 0  # persistent serving KV cache (ServingMemorySpec)

    @property
    def total(self) -> int:
        return (
            self.activations
            + self.activation_grads
            + self.weights
            + self.weight_grads
            + self.optimizer_state
            + self.outputs
            + self.output_grads
            + self.input_batch
            + self.kv_cache
        )


def estimate_memory(
    attrs,
    input_shapes: Sequence,
    weight_shapes: Optional[Sequence] = None,
    output_shapes: Optional[Sequence] = None,
    optimizer_state_slots: int = 2,
    serving: Optional[ServingMemorySpec] = None,
    kv_cache_bytes: int = 0,
    slot_shard_ways: Optional[Sequence[int]] = None,
) -> OpStepMemory:
    """Step residency of one op from its (piece) TensorShapes.

    `input_shapes` carries the DATA slots only; weight slots go in
    `weight_shapes` (the split_slot_values convention). `output_shapes`
    may be omitted for Input/Weight layers (their outputs are the attrs'
    own shape).

    With `serving` set the regime is forward-only inference: no gradient
    or optimizer terms, plus `kv_cache_bytes` — the caller's per-device
    cache share from `kv_cache_piece_bytes` (this function sees piece TensorShapes only,
    which carry no degrees). `slot_shard_ways[i]` is `update_shard_ways`
    of weight slot i, for the same reason the caller's to give: each
    optimizer slot is resident at 1/ways of its weight's piece. The
    weight term stays the whole piece: the float32 master is stored at
    1/ways too, but the step gathers the copy it computes with, and the
    `weights` term stands for that copy (in the compute dtype it is
    smaller: the term errs high)."""
    from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs

    if isinstance(attrs, InputAttrs):
        out_bytes = (
            sum(s.size_bytes for s in output_shapes)
            if output_shapes
            else attrs.shape.size_bytes
        )
        return OpStepMemory(input_batch=out_bytes)
    if isinstance(attrs, WeightAttrs):
        # charged at the consuming op's weight slots (see module docstring)
        return OpStepMemory()
    in_bytes = sum(s.size_bytes for s in input_shapes)
    w_bytes = sum(s.size_bytes for s in (weight_shapes or ()))
    out_bytes = sum(s.size_bytes for s in (output_shapes or ()))
    if serving is not None:
        return OpStepMemory(
            activations=in_bytes,
            weights=w_bytes,
            outputs=out_bytes,
            kv_cache=max(int(kv_cache_bytes), 0),
        )
    return OpStepMemory(
        activations=in_bytes,
        activation_grads=in_bytes,
        weights=w_bytes,
        weight_grads=w_bytes,
        optimizer_state=max(int(optimizer_state_slots), 0) * sum(
            -(-s.size_bytes // max(int(ways), 1))
            for s, ways in zip(
                weight_shapes or (),
                slot_shard_ways or [1] * len(weight_shapes or ()),
            )
        ),
        outputs=out_bytes,
        output_grads=out_bytes,
    )


# bounded (not maxsize=None): leaf keys are hash-consed per search session
# but this cache outlives the intern table's per-search clears, so a cap
# keeps a long-lived many-search process from accumulating dead leaves
@lru_cache(maxsize=65536)
def leaf_step_memory_bytes(
    leaf,
    optimizer_state_slots: int = 2,
    *,
    serving: Optional[ServingMemorySpec] = None,
) -> int:
    """Per-device step residency of ONE machine-mapping leaf
    (UnmappedOpCostEstimateKey), from its piece shapes — the quantity the
    DP's feasibility pruner compares against the device capacity.

    View-independent by construction: a piece shape depends only on the
    parallel shape's degrees, never on which devices the view picks — so
    the native DP can carry one entry per leaf KEY. A single op whose
    piece residency exceeds the device capacity cannot run under ANY view
    of this sharding (the MEM002 predicate).

    Parallel ops (Combine/Repartition/Replicate/Reduction) on ACTIVATION
    values charge their collective staging: the source piece plus the
    destination piece live simultaneously while the reshard runs — a
    Combine back to degree 1 materializes the FULL tensor per device,
    which is exactly the footprint that makes an unsharded plan
    infeasible. Weight layers and weight-chain reshards charge zero: the
    parameter is stored in its post-reshard form and accounted at the
    consuming op's weight slots (see module docstring).

    With `serving` set the residency is forward-only inference (no grad /
    optimizer terms) and attention leaves additionally charge
    their per-device KV-cache share (`kv_cache_piece_bytes`) — this is
    the predicate both machine-mapping DPs prune serving plans on."""
    from flexflow_tpu.op_attrs.core import (
        get_output_shapes,
        get_weight_shapes,
        is_parallel_op,
    )
    from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs
    from flexflow_tpu.op_attrs.parallel_tensor_shape import get_piece_shape

    from flexflow_tpu.op_attrs.core import is_stage_op

    out_pieces = [get_piece_shape(s) for s in leaf.output_shapes]
    out_bytes = sum(s.size_bytes for s in out_pieces)
    attrs = leaf.op_attrs
    ctx = getattr(leaf, "pipeline", None)  # pcg.pipeline.PipelineLeafContext
    if isinstance(attrs, InputAttrs):
        return out_bytes
    if isinstance(attrs, WeightAttrs):
        return 0
    in_pieces = [get_piece_shape(s) for s in leaf.input_shapes]
    if is_stage_op(attrs):
        # a stage boundary stages ONE microbatch in flight (src piece +
        # dst piece of piece_bytes/M each); the stash of in-flight
        # microbatches is charged at the consuming stage's leaves below
        m = max(getattr(attrs, "num_microbatches", 1), 1)
        total = sum(s.size_bytes for s in in_pieces) + out_bytes
        return -(-total // m)  # ceil
    if is_parallel_op(attrs):
        if all(leaf.weight_inputs) and leaf.weight_inputs:
            # a parameter reshard chain: storage lives (and is charged) at
            # the consuming op's weight slots in its post-reshard form
            return 0
        staging = sum(s.size_bytes for s in in_pieces) + out_bytes
        if ctx is not None and serving is None:
            # an in-region reshard moves one microbatch at a time
            staging = -(-staging // max(ctx.num_microbatches, 1))
        return staging
    from flexflow_tpu.local_execution.training_backing import split_slot_values

    data, weights = split_slot_values(attrs, in_pieces)
    # the slots at their update shard: view-independent like the pieces
    # (the weight's own replica degree, not the mesh's size)
    slot_ways = [
        update_shard_ways(s)
        for s in split_slot_values(attrs, list(leaf.input_shapes))[1]
    ]
    if not weights:
        slot_ways = None
        try:
            weights = get_weight_shapes(attrs, list(data))
        except (AssertionError, IndexError, ValueError, TypeError):
            weights = []
    try:
        outs = out_pieces or get_output_shapes(attrs, list(data))
    except (AssertionError, IndexError, ValueError, TypeError):
        outs = []
    cache_bytes = 0
    if serving is not None:
        cache_bytes = kv_cache_piece_bytes(
            attrs,
            leaf.input_shapes[0] if leaf.input_shapes else None,
            _weight_slot_shape(attrs, leaf.input_shapes),
            serving,
        )
    mem = estimate_memory(
        attrs,
        data,
        weights,
        outs,
        optimizer_state_slots=optimizer_state_slots,
        serving=serving,
        kv_cache_bytes=cache_bytes,
        slot_shard_ways=slot_ways,
    )
    if ctx is not None and serving is None:
        # 1F1B activation stashing (ISSUE 13): inside a pipeline region an
        # op touches one microbatch (piece/M) at a time, and stage s keeps
        # at most min(S-s, M) in-flight microbatch activations stashed for
        # its backward — pipeline's classic per-device HBM win, made
        # visible to the same --hbm-gb pruner the search honors. Gradient
        # terms hold a single microbatch in flight (1/M). Weight-side
        # terms are whole-step resident, unchanged.
        return pipeline_scaled_total(mem, ctx)
    return mem.total


def pipeline_scaled_total(mem: OpStepMemory, ctx) -> int:
    """Apply the 1F1B residency scaling to one op's training accounting:
    activations/outputs x min(S-s, M)/M (the in-flight stash bound),
    activation/output grads x 1/M (one microbatch's backward in flight);
    weights, grads, optimizer state, input batches unchanged."""
    s_total, m = max(ctx.num_stages, 1), max(ctx.num_microbatches, 1)
    keep = max(min(s_total - ctx.stage, m), 1)
    acts = mem.activations + mem.outputs
    grads = mem.activation_grads + mem.output_grads
    fixed = mem.total - acts - grads
    return fixed + -(-acts * keep // m) + -(-grads // m)


def _weight_slot_shape(attrs, input_parallel_shapes):
    """The first WEIGHT-role slot's PARALLEL shape (None when the op has
    none wired) — the head-degree carrier of `kv_cache_piece_bytes`."""
    from flexflow_tpu.op_attrs.core import IncomingTensorRole
    from flexflow_tpu.local_execution.training_backing import slot_roles

    shapes = list(input_parallel_shapes or ())
    for s, role in zip(shapes, slot_roles(attrs, len(shapes))):
        if role == IncomingTensorRole.WEIGHT:
            return s
    return None
