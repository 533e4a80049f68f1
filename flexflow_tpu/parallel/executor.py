"""Distributed training over a searched PCG: the GSPMD global-view executor.

TPU-native analogue of the reference's ModelTrainingInstance + LegionBacking
(include/runtime/model_training_instance.h:14-33,
include/runtime/legion_backing.h:81-102): one jitted train step over a
jax Mesh replaces per-op Legion index launches; sharding constraints derived
from the PCG replace region partitions; XLA-inserted collectives replace NCCL
allreduce + Legion data movement. The whole step (forward + loss + backward +
optimizer update + metrics) is ONE XLA program with donated buffers — the
analogue of Legion trace capture/replay around the training iteration
(SURVEY.md §3.1).

Execution semantics: values are GLOBAL arrays. The four parallel ops are
layout denotations, so they interpret as identity; their effect is realized
by the `with_sharding_constraint` each tensor carries
(Repartition/Combine/Replicate) or by XLA's partial-sum handling of the
producing contraction (Reduction). Correctness therefore never depends on the
searched mapping — only performance does.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.kernels import (
    apply_optimizer,
    compute_metrics,
    context,
    forward as kernel_forward,
    loss_forward,
    make_optimizer_state,
)
from flexflow_tpu.local_execution.training_backing import split_slot_values
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.core import is_parallel_op
from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs
from flexflow_tpu.op_attrs.ops.loss_functions import LossAttrs
from flexflow_tpu.op_attrs.parallel_tensor_shape import get_reduced_shape
from flexflow_tpu.pcg.initializer import initialize
from flexflow_tpu.pcg.machine_view import MachineView
from flexflow_tpu.pcg.optimizer import OptimizerAttrs
from flexflow_tpu.pcg.parallel_computation_graph import ParallelComputationGraph
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.parallel.sharding import pcg_shardings, update_partition_spec
from flexflow_tpu.utils.graph import DataflowOutput, Node


def param_key(n: Node) -> str:
    return f"n{n.idx}"


def _pre_reshard_value(
    pcg: ParallelComputationGraph, t: DataflowOutput
) -> DataflowOutput:
    """Walk back through value-preserving resharding ops (Combine /
    Repartition — pure layout moves). Stops at Reduction/Replicate and any
    compute op (a Reduction's input holds partial sums, not values), and
    never crosses a reshard of the LAST dim: class-sharded logits would
    push the loss's softmax/logsumexp across a sharded class axis, which
    the elementwise loss lowering is not written for (XLA compiles it, at
    pathological cost).

    Contract note (ISSUE 11): the static communication verifier models
    the chain this walk skips as a LEGITIMATELY free lowering
    (`analysis/comm_analysis.trailing_reshard_nodes` re-walks it to
    exempt those movement edges from COMM002). If this walk's stopping
    rules change, the verifier follows automatically — it calls this
    function — but the executor and the verifier must keep consuming the
    SAME pre-reshard tensor, or ffcheck --comm will flag phantom DCE."""
    from flexflow_tpu.op_attrs.ops import CombineAttrs, RepartitionAttrs

    while True:
        attrs = pcg.op_attrs(t.node)
        if isinstance(attrs, CombineAttrs):
            dim = attrs.combine_dim
        elif isinstance(attrs, RepartitionAttrs):
            dim = attrs.repartition_dim
        else:
            return t
        (src,) = pcg.inputs_of(t.node)
        rank = pcg.tensor_shape(src).num_dims
        if dim % rank == rank - 1:
            return t  # class-dim reshard: keep the combined logits
        t = src


def init_pcg_params(
    pcg: ParallelComputationGraph, rng: jax.Array
) -> Dict[str, jnp.ndarray]:
    """Materialize every weight node's GLOBAL value from its initializer
    (same keys/values as the single-host init, so distributed and local runs
    are bit-comparable)."""
    params: Dict[str, jnp.ndarray] = {}
    for n in pcg.topological_ordering():
        if isinstance(pcg.op_attrs(n), WeightAttrs):
            (out,) = pcg.outputs_of(n)
            ta = pcg.tensor_attrs(out)
            assert ta.initializer is not None, f"weight {n} missing initializer"
            key = jax.random.fold_in(rng, n.idx)
            ts = get_reduced_shape(ta.shape)
            params[param_key(n)] = initialize(
                ta.initializer, key, ts.dims, ts.dtype.to_jnp()
            )
    return params


def overlap_lowering_active(flag: Optional[bool] = None) -> bool:
    """Is the fused collective-matmul lowering on? `FF_TPU_OVERLAP_BASELINE=1`
    force-reverts it (the regression test's in-process baseline switch and
    the honest escape hatch for a misbehaving fused kernel); otherwise an
    explicit flag (`--overlap`) wins, else the `FF_TPU_OVERLAP` env var."""
    import os

    if os.environ.get("FF_TPU_OVERLAP_BASELINE"):
        return False
    if flag is not None:
        return bool(flag)
    return os.environ.get("FF_TPU_OVERLAP", "") not in ("", "0")


def pcg_forward_interpreter(
    pcg: ParallelComputationGraph,
    params: Dict[str, jnp.ndarray],
    inputs: Dict[str, jnp.ndarray],
    shardings: Dict[DataflowOutput, Optional[object]],
    *,
    train: bool = False,
    rng: Optional[jax.Array] = None,
    mesh=None,
    barrier_nodes: FrozenSet[Node] = frozenset(),
    overlap_sites: Optional[Dict[Node, str]] = None,
) -> Dict[DataflowOutput, jnp.ndarray]:
    """Global-view evaluation of the PCG with sharding constraints.
    barrier_nodes: same LM-head fusion split as the single-host
    interpreter (local_execution/training_backing.py
    forward_interpreter)."""
    import contextlib

    from flexflow_tpu.kernels.ring_attention import ring_mha_forward

    def constrain(v, o):
        s = shardings.get(o)
        return v if s is None else jax.lax.with_sharding_constraint(v, s)

    # a pallas_call cannot be SPMD-partitioned: on a multi-device mesh no
    # op may emit a bare one. An attention node whose plan shards only batch
    # and/or heads declares its mesh context instead (_try_sharded_flash_mha)
    # and has its kernels mapped over the shards; any other stays pure XLA
    # (sharded via constraints)
    multi_device = mesh is not None and mesh.size > 1
    guard = context.no_flash() if multi_device else contextlib.nullcontext()
    with guard:
        return _interpret(
            pcg, params, inputs, shardings, constrain, train, rng, mesh,
            ring_mha_forward, barrier_nodes, overlap_sites or {},
        )


def _interpret(
    pcg, params, inputs, shardings, constrain, train, rng, mesh,
    ring_mha_forward, barrier_nodes=frozenset(), overlap_sites=None,
):
    overlap_sites = overlap_sites or {}
    env: Dict[DataflowOutput, jnp.ndarray] = {}
    for n in pcg.topological_ordering():
        la = pcg.layer_attrs(n)
        attrs = la.attrs
        outs = pcg.outputs_of(n)
        # everything this node lowers to carries its name in the device
        # trace (observability/trace.py): the kernel, the barrier, a
        # parallel op's or a parameter's sharding constraint, and the
        # shard_map entries (ring / all-to-all attention, sharded flash,
        # pinned reduction, collective matmul) alike
        with trace.node_scope(pcg, n):
            if isinstance(attrs, InputAttrs):
                key = la.name if la.name is not None and la.name in inputs else param_key(n)
                assert key in inputs, f"missing input binding for {la.name or key}"
                env[outs[0]] = constrain(inputs[key], outs[0])
            elif isinstance(attrs, WeightAttrs):
                env[outs[0]] = constrain(params[param_key(n)], outs[0])
            elif is_parallel_op(attrs):
                (src,) = pcg.inputs_of(n)
                env[outs[0]] = constrain(env[src], outs[0])
            elif _seq_parallel(attrs, pcg.inputs_of(n), shardings, mesh):
                # explicit sequence-parallel schedule via shard_map (a sharding
                # constraint alone would make XLA all-gather K/V): ppermute ring
                # for RingAttentionAttrs, heads-for-sequence all-to-all for the
                # Ulysses subclass. Both compose with head parallelism
                # (head-sharded weight) and with qkv/output biases. With the
                # sequence whole the node is plain (causal) attention and
                # takes the lowering below
                from flexflow_tpu.kernels.ulysses_attention import (
                    UlyssesAttentionAttrs,
                    ulysses_mha_forward,
                )

                in_tensors = pcg.inputs_of(n)
                slot_vals = [env[v] for v in in_tensors]
                data_vals, weight_vals = split_slot_values(attrs, slot_vals)
                q_sharding = shardings.get(in_tensors[0])
                q_spec = None if q_sharding is None else q_sharding.spec
                w_sharding = shardings.get(in_tensors[3])
                w_spec = None if w_sharding is None else w_sharding.spec
                fwd = (
                    ulysses_mha_forward
                    if isinstance(attrs, UlyssesAttentionAttrs)
                    else ring_mha_forward
                )
                out = fwd(
                    attrs, *data_vals, weight_vals[0], mesh, q_spec,
                    w_spec=w_spec,
                    input_bias=weight_vals[1] if attrs.bias else None,
                    output_bias=weight_vals[2] if attrs.bias else None,
                )
                env[outs[0]] = constrain(out, outs[0])
            else:
                in_tensors = pcg.inputs_of(n)
                slot_vals = [env[v] for v in in_tensors]
                if n in barrier_nodes:
                    # barrier the DATA slots in place so both the kernel path
                    # (via split_slot_values below) and the pinned-reduction
                    # path (which consumes raw slot_vals) see the fusion split
                    from flexflow_tpu.op_attrs.core import IncomingTensorRole
                    from flexflow_tpu.local_execution.training_backing import (
                        optimization_barrier,
                        slot_roles,
                    )

                    roles = slot_roles(attrs, len(slot_vals))
                    slot_vals = [
                        optimization_barrier(v)
                        if r == IncomingTensorRole.INPUT
                        else v
                        for v, r in zip(slot_vals, roles)
                    ]
                data_vals, weight_vals = split_slot_values(attrs, slot_vals)
                fused_kind = overlap_sites.get(n)
                if fused_kind == "ag_matmul":
                    fused = _try_overlap_ag_matmul(
                        pcg, n, attrs, in_tensors, shardings, mesh, env
                    )
                    if fused is not None:
                        env[outs[0]] = fused
                        continue
                sharded = _try_sharded_flash_mha(
                    attrs, data_vals, weight_vals, in_tensors, shardings, mesh
                )
                if sharded is not None:
                    for o, r in zip(outs, sharded):
                        env[o] = r
                    continue
                sharded = _try_sharded_experts(
                    attrs, slot_vals, in_tensors, shardings, mesh
                )
                if sharded is not None:
                    for o, r in zip(outs, sharded):
                        env[o] = r
                    continue
                pinned = _try_pinned_reduction(
                    pcg, n, attrs, slot_vals, in_tensors, shardings, mesh,
                    ring_overlap=(fused_kind == "matmul_rs"),
                )
                if pinned is not None:
                    env[outs[0]] = pinned
                    continue
                op_rng = jax.random.fold_in(rng, n.idx) if rng is not None else None
                results = kernel_forward(
                    attrs, data_vals, weight_vals, train=train, rng=op_rng
                )
                # compute ops get NO explicit constraint: the PCG's sharding
                # intent is pinned at inputs/weights/parallel-op boundaries and
                # XLA propagates it through the op; constraining every tensor
                # multiplies partitioner work and blocks fusion for no
                # additional information
                for o, r in zip(outs, results):
                    env[o] = r
    return env


def _seq_parallel(attrs, in_tensors, shardings, mesh) -> bool:
    """Does this node's plan shard the sequence of a RingAttentionAttrs (or
    Ulysses) node over more than one device?"""
    from flexflow_tpu.op_attrs.ops.ring_attention import RingAttentionAttrs

    if mesh is None or not isinstance(attrs, RingAttentionAttrs):
        return False
    seq_axes = _entry_names(_spec_entry(shardings.get(in_tensors[0]), 1))
    return _mesh_axes_size(mesh, seq_axes) > 1


def _spec_entry(sharding, i):
    """PartitionSpec entry i of a NamedSharding (None when unconstrained or
    the spec is shorter than the tensor rank)."""
    if sharding is None:
        return None
    spec = sharding.spec
    return spec[i] if i < len(spec) else None


from flexflow_tpu.utils.shard_map_compat import shard_map_compat as _shard_map


def _padded_spec(sharding, rank):
    """Spec entries padded with None to the tensor rank."""
    spec = tuple(sharding.spec)
    return spec + (None,) * (rank - len(spec))


def _entry_names(entry):
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def _mesh_axes_size(mesh, axes) -> int:
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def collect_overlap_sites(pcg, shardings, mesh) -> Dict[Node, str]:
    """Static pattern match for the fused collective-matmul lowerings
    (ROADMAP item 3): compute nodes whose adjacent Combine/Reduction
    movement edge can lower to a `kernels/collective_matmul.py` ring
    instead of a standalone reshard. Returns node -> kind:

    - "ag_matmul": a Linear whose data input is a Combine over a
      non-contraction dim with a sharded producer — the all-gather streams
      chunk-by-chunk around the ring while the matmul consumes chunks.
    - "matmul_rs": a bias-free activation-free Linear/BatchMatmul whose
      partial-sum output feeds a matching Reduction (the pinned-reduction
      shape) — the partial matmul is computed one scatter-chunk per ring
      step, overlapping the reduce-scatter half of the all-reduce.

    Everything checked here is static (specs, degrees, divisibility), so
    the same map drives the lowering, the `fused_edges` trace-span
    attribute, and the plan-audit annotation. The value-level lowering
    re-verifies before fusing and falls back to the serial path on any
    mismatch, so an over-approximation here is safe, never wrong.

    Deliberate contract with the DP: under the switch the executor fuses
    EVERY matched site; the DP's per-edge chosen flag
    (machine_mapping/overlap.py derive_overlap_plan) affects pricing and
    provenance only. Vetoing fusion from that flag would inherit the
    serial model's whole-stage overlap_fraction haircut — which claims
    free hiding for most sub-ms edges on which the fused lowering can
    win (chip: not measured; no benchmark cell runs a fused edge). Both
    sides are recorded (provenance `edges[].chosen` vs
    `executor_fused_edges`), so the divergence is observable, not
    silent."""
    from flexflow_tpu.op_attrs.ops import (
        CombineAttrs,
        LinearAttrs,
        ReductionAttrs,
    )

    sites: Dict[Node, str] = {}
    if mesh is None or mesh.size <= 1:
        return sites
    for n in pcg.topological_ordering():
        attrs = pcg.op_attrs(n)
        outs = pcg.outputs_of(n)
        ins = pcg.inputs_of(n)
        if isinstance(attrs, LinearAttrs) and ins:
            x_t = ins[0]
            pa = pcg.op_attrs(x_t.node)
            if (
                isinstance(pa, CombineAttrs)
                and len(pcg.uses_of(x_t)) == 1
                and len(ins) >= 2
            ):
                (src,) = pcg.inputs_of(x_t.node)
                src_pts = pcg.tensor_shape(src)
                rank = src_pts.num_dims
                g = pa.combine_dim % rank
                s = shardings.get(src)
                if g != rank - 1 and s is not None:
                    x_spec = _padded_spec(s, rank)
                    gather_axes = _entry_names(x_spec[g])
                    sp = _mesh_axes_size(mesh, gather_axes)
                    w_s = shardings.get(ins[1])
                    w_rank = pcg.tensor_shape(ins[1]).num_dims
                    w_spec = (
                        _padded_spec(w_s, w_rank)
                        if w_s is not None
                        else (None,) * w_rank
                    )
                    out_s = shardings.get(outs[0]) if outs else None
                    out_axes = []
                    if out_s is not None:
                        for e in _padded_spec(
                            out_s, pcg.tensor_shape(outs[0]).num_dims
                        ):
                            out_axes.extend(_entry_names(e))
                    reused = set(out_axes)
                    for e in w_spec:
                        reused.update(_entry_names(e))
                    if (
                        sp > 1
                        and src_pts.dims.shard_dims[g].size % sp == 0
                        and w_spec[0] is None
                        and not (reused & set(gather_axes))
                    ):
                        sites[n] = "ag_matmul"
        if isinstance(attrs, LinearAttrs) and outs:
            if attrs.use_bias or attrs.activation is not None:
                continue  # pinned-reduction exactness guard
            out_pts = pcg.tensor_shape(outs[0])
            if out_pts.sum_degree <= 1:
                continue
            uses = pcg.uses_of(outs[0])
            if len(uses) != 1 or not isinstance(
                pcg.op_attrs(uses[0].node), ReductionAttrs
            ):
                continue
            if (
                pcg.op_attrs(uses[0].node).reduction_degree
                != out_pts.sum_degree
            ):
                continue
            s = shardings.get(ins[0]) if ins else None
            if s is None:
                continue
            x_pts = pcg.tensor_shape(ins[0])
            x_spec = _padded_spec(s, x_pts.num_dims)
            sum_axes = _entry_names(x_spec[-1])
            sp = _mesh_axes_size(mesh, sum_axes)
            lead = x_pts.dims.shard_dims[0]
            local_lead = lead.size // max(lead.degree, 1)
            if sp > 1 and local_lead % sp == 0:
                sites[n] = "matmul_rs"
    return sites


def _try_overlap_ag_matmul(pcg, n, attrs, in_tensors, shardings, mesh, env):
    """Fused lowering of `Combine(dim g) -> Linear` (overlap site
    "ag_matmul"): consume the PRE-combine (still sharded) value and run
    the all-gather-then-matmul ring, so the gather streams behind the
    matmul instead of materializing the full activation first. The
    Combine node's own lowering (an identity under a gathered constraint)
    is left without consumers and DCEs away. Returns the Linear's output
    or None to fall back to the serial lowering."""
    from flexflow_tpu.kernels.collective_matmul import all_gather_matmul
    from flexflow_tpu.op_attrs.ops import CombineAttrs

    pa = pcg.op_attrs(in_tensors[0].node)
    if not isinstance(pa, CombineAttrs):
        return None
    (src,) = pcg.inputs_of(in_tensors[0].node)
    s = shardings.get(src)
    if s is None or src not in env:
        return None
    rank = pcg.tensor_shape(src).num_dims
    g = pa.combine_dim % rank
    x_spec = _padded_spec(s, rank)
    if not _entry_names(x_spec[g]):
        return None
    w_s = shardings.get(in_tensors[1])
    w_rank = pcg.tensor_shape(in_tensors[1]).num_dims
    w_spec = (
        _padded_spec(w_s, w_rank) if w_s is not None else (None,) * w_rank
    )
    if w_spec[0] is not None:
        return None  # contraction-sharded weight: partial sums, not ours
    bias = env[in_tensors[2]] if attrs.use_bias else None
    return all_gather_matmul(
        env[src],
        env[in_tensors[1]],
        mesh,
        x_spec,
        w_spec,
        g,
        bias=bias,
        activation=attrs.activation,
    )


def _try_pinned_reduction(
    pcg, n, attrs, slot_vals, in_tensors, shardings, mesh,
    ring_overlap: bool = False,
):
    """Fuse a partial-sum producer with its downstream Reduction into ONE
    shard_map region ending in an explicit psum.

    In global view a sum_degree>1 tensor is invisible to JAX — the producing
    contraction already denotes the full result, so the data movement that
    realizes the PCG's `Reduction` is whatever GSPMD invents (round-3
    verdict weak #3: the plan's priced all-reduce and the executed
    collectives could differ arbitrarily). Here the producer runs per-shard
    on its declared input shardings and the partial sums meet in a psum over
    exactly the contraction axes — the reference Reduction kernel's
    data movement (lib/kernels/src/cuda/ops/reduction_kernels.cu:9-16),
    pinned. Engages only where per-shard execution is exact (bias-free,
    activation-free contractions; local SUM reduce) and the operands'
    contraction axes align; everything else keeps the global-view lowering,
    which is always correct."""
    from flexflow_tpu.op_attrs.ops import BatchMatmulAttrs, LinearAttrs
    from flexflow_tpu.op_attrs.ops.shape_ops import ReduceAttrs, ReduceOpType

    if mesh is None or mesh.size <= 1:
        return None
    outs = pcg.outputs_of(n)
    if len(outs) != 1:
        return None
    out_pts = pcg.tensor_shape(outs[0])
    if out_pts.sum_degree <= 1:
        return None
    if any(pcg.tensor_shape(t).sum_degree > 1 for t in in_tensors):
        return None
    uses = pcg.uses_of(outs[0])
    if len(uses) != 1:
        return None
    red_attrs = pcg.op_attrs(uses[0].node)
    from flexflow_tpu.op_attrs.ops import ReductionAttrs

    if (
        not isinstance(red_attrs, ReductionAttrs)
        or red_attrs.reduction_degree != out_pts.sum_degree
    ):
        return None
    in_shardings = [shardings.get(t) for t in in_tensors]
    if any(s is None for s in in_shardings):
        return None
    from jax.sharding import PartitionSpec as P

    specs = [
        _padded_spec(s, pcg.tensor_shape(t).num_dims)
        for s, t in zip(in_shardings, in_tensors)
    ]
    if isinstance(attrs, LinearAttrs):
        if attrs.use_bias or attrs.activation is not None:
            # a local bias add / activation on partial sums would be wrong;
            # the global-view lowering stays correct for those
            return None
        x_spec, w_spec = specs
        if x_spec[-1] != w_spec[0] or x_spec[-1] is None:
            return None  # misaligned contraction axes: let GSPMD handle it
        sum_axes = _entry_names(x_spec[-1])
        out_spec = P(*x_spec[:-1], w_spec[-1])
    elif isinstance(attrs, BatchMatmulAttrs):
        l_spec, r_spec = specs
        if (
            l_spec[:-2] != r_spec[:-2]
            or l_spec[-1] != r_spec[-2]
            or l_spec[-1] is None
        ):
            return None
        sum_axes = _entry_names(l_spec[-1])
        out_spec = P(*l_spec[:-1], r_spec[-1])
    elif isinstance(attrs, ReduceAttrs) and attrs.op_type == ReduceOpType.SUM:
        if attrs.keepdims:
            return None
        (x_spec,) = specs
        rank = len(x_spec)
        axes = {a % rank for a in attrs.axes}
        sum_axes = tuple(
            x for a in sorted(axes) for x in _entry_names(x_spec[a])
        )
        if not sum_axes:
            return None
        out_spec = P(*[e for i, e in enumerate(x_spec) if i not in axes])
    else:
        return None

    # a mesh axis may not appear twice in one PartitionSpec (nor both shard
    # an output dim and be psum'd): e.g. a retained data dim and the weight's
    # output dim mapped to the same axis. jit would raise at trace time;
    # fall back to the always-correct global-view lowering instead
    axis_names = list(sum_axes)
    for e in out_spec:
        axis_names.extend(_entry_names(e))
    if len(axis_names) != len(set(axis_names)):
        return None

    # fused overlap variant (site kind "matmul_rs"): the partial matmul is
    # computed one scatter-chunk per ring step with the accumulator hop in
    # flight (kernels/collective_matmul.py), then a tiled all-gather
    # rebuilds the full output — an all-reduce whose reduce-scatter half
    # hides behind the matmul. Engages only for the two pure-matmul ops
    # (ReduceAttrs keeps the psum) with a chunkable leading dim.
    # Linear only: a BatchMatmul's rhs carries the same leading batch dims
    # as the lhs, so chunking the lhs leading dim would desynchronize them
    use_ring = (
        ring_overlap
        and isinstance(attrs, LinearAttrs)
        and slot_vals[0].ndim >= 2
    )
    if use_ring:
        sp_ring = 1
        for a in sum_axes:
            sp_ring *= mesh.shape[a]
        lead_shard = 1
        for a in _entry_names(specs[0][0]):
            lead_shard *= mesh.shape[a]
        if (
            sp_ring <= 1
            or (slot_vals[0].shape[0] // lead_shard) % sp_ring != 0
        ):
            use_ring = False

    def local_fn(*local_ins):
        data_vals, weight_vals = split_slot_values(attrs, list(local_ins))
        if use_ring:
            from flexflow_tpu.kernels.collective_matmul import (
                ring_matmul_reduce_scatter_block,
            )

            acc = ring_matmul_reduce_scatter_block(
                data_vals[0], weight_vals[0], mesh, sum_axes, scatter_axis=0
            )
            return jax.lax.all_gather(acc, sum_axes, axis=0, tiled=True)
        (res,) = kernel_forward(attrs, data_vals, weight_vals)
        return jax.lax.psum(res, sum_axes)

    in_specs = tuple(P(*s) for s in specs)
    return _shard_map(local_fn, mesh, in_specs, out_spec)(*slot_vals)


def _attention_shard_axes(attrs, in_tensors, shardings, mesh):
    """(batch_axes, head_axes), the PartitionSpec entries of an attention
    node's batch and head dims, when its plan shards nothing else; None when
    the node is not this lowering's (no multi-device mesh, a sharded
    sequence or embedding dim, operands that disagree on the batch)."""
    from flexflow_tpu.op_attrs.ops import MultiHeadAttentionAttrs

    if (
        mesh is None
        or mesh.size <= 1
        or not isinstance(attrs, MultiHeadAttentionAttrs)
    ):
        return None
    # q/k/v [b, s, e]: batch may be dp-sharded; a sharded seq dim is ring
    # attention's job and a sharded embed dim would make projections partial
    q_sh = shardings.get(in_tensors[0])
    for t in in_tensors[:3]:
        s = shardings.get(t)
        if _spec_entry(s, 1) is not None or _spec_entry(s, 2) is not None:
            return None
        if _spec_entry(s, 0) != _spec_entry(q_sh, 0):
            return None
    # weight [per_head_params, H]: head-parallel shards dim 1
    head_axes = _spec_entry(shardings.get(in_tensors[3]), 1)
    # QK-norm and RoPE act on the fused row, which has no head dim to shard
    if head_axes is not None and (
        attrs.qk_norm or attrs.rope_theta is not None
    ):
        return None
    return _spec_entry(q_sh, 0), head_axes


def _try_sharded_flash_mha(attrs, data_vals, weight_vals, in_tensors,
                           shardings, mesh):
    """Attention under SPMD (SURVEY.md §7 hard-part 4): when the node's plan
    shards only batch and/or heads, declare that to the op's own kernel
    (`flash_mesh`) and run it: `kernels/ops._mha_forward` then makes the
    layout choice it makes on one chip, on the block each device sees. A
    batch-only plan takes the one-chip fused-row dispatch with each Pallas
    call mapped over the batch shards, a head-sharded plan the [b, h, s, d]
    rows kernels; a block no kernel serves stays XLA's dense attention. The
    projections, biases and the output matmul are traced in the global view
    either way (XLA partitions a plain matmul natively and the weight
    gradients stay ordinary HLO for the all-reduce combiner); only the
    attention core is shard_mapped. Returns the node's outputs (the [b, s, e]
    result; a differential node with `kv_outputs` its keys and values
    beside it), or None for a node that is not this lowering's."""
    axes = _attention_shard_axes(attrs, in_tensors, shardings, mesh)
    if axes is None:
        return None
    with context.flash_mesh(mesh, *axes, context.interpret_default()):
        return kernel_forward(attrs, data_vals, weight_vals)


def attention_routes(pcg, shardings, mesh) -> Dict[str, str]:
    """The route `_interpret` lowers each attention node of the plan by,
    under the node's scope name in the device trace (`ff.mha.<name>`), from
    the same static facts the lowering reads (so a plan that falls back to
    dense or to [b, h, s, d] can be read without a trace):

    - "seq_parallel": the ring / all-to-all schedule over a sharded sequence;
    - "fused_row_sharded": the one-chip fused-row kernels per batch shard;
    - "rows_sharded": the [b, h, s, d] kernels per batch and head shard;
    - "dense": XLA's attention, in the global view;
    - on a single device, `mha_core_route`'s own names."""
    from flexflow_tpu.kernels.ops import mha_core_route
    from flexflow_tpu.op_attrs.ops import MultiHeadAttentionAttrs

    routes: Dict[str, str] = {}
    for n in pcg.topological_ordering():
        attrs = pcg.op_attrs(n)
        if not isinstance(attrs, MultiHeadAttentionAttrs):
            continue
        in_tensors = pcg.inputs_of(n)
        q, k, v = in_tensors[:3]
        core_args = (
            attrs, *(pcg.tensor_shape(t).sizes() for t in (q, k, v)),
            q == k == v,
        )
        if _seq_parallel(attrs, in_tensors, shardings, mesh):
            route = "seq_parallel"
        elif mesh is None or mesh.size <= 1:
            route = mha_core_route(*core_args)
        else:
            route = "dense"
            axes = _attention_shard_axes(attrs, in_tensors, shardings, mesh)
            if axes is not None:
                with context.flash_mesh(mesh, *axes, context.interpret_default()):
                    route = mha_core_route(*core_args)
            if route != "dense":
                route = route.replace("_qkv", "") + "_sharded"
        routes[trace.scope_name(pcg, n)] = route
    return routes


def _try_sharded_experts(attrs, slot_vals, in_tensors, shardings, mesh):
    """The Experts op under a batch- and/or expert-sharded plan, per shard
    (a global-view lowering would sort and gather the GLOBAL decisions, which
    XLA can only do by replicating them). Each shard routes ITS tokens at the
    full router width and runs the experts it holds on them:

    - batch shards (the data-parallel rule and template): the capacity, and
      f_e, P_e and Z of the auxiliary scalar, are over the shard's own
      tokens; the shards' scalars are averaged. This is what data-parallel
      MoE training does, and it is not the one-device value.
    - expert shards (Replicate -> Experts -> Reduction): the combine of the
      local experts' rows is a partial sum, summed here; the Reduction node
      that follows is then an identity on the global value.

    Returns the node's outputs, or None to fall back to the global view."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.kernels.moe import experts_forward
    from flexflow_tpu.op_attrs.ops import ExpertsAttrs

    if mesh is None or mesh.size <= 1 or not isinstance(attrs, ExpertsAttrs):
        return None
    x = slot_vals[0]
    x_sh = shardings.get(in_tensors[0])
    # the router's slots and the shared expert's are whole on every shard;
    # the expert tensors shard their leading dim over the expert axes
    roles = attrs.weight_roles()
    first_expert = 1 + roles.index("expert")
    x_spec = (None,) * x.ndim if x_sh is None else _padded_spec(x_sh, x.ndim)
    ep_entry = _spec_entry(shardings.get(in_tensors[first_expert]), 0)
    batch_axes = tuple(a for e in x_spec[:-1] for a in _entry_names(e))
    ep_axes = _entry_names(ep_entry)
    if x_spec[-1] is not None or not (batch_axes or ep_axes):
        return None
    if set(batch_axes) & set(ep_axes):
        return None
    for i, role in enumerate(roles, start=1):
        w, sh = slot_vals[i], shardings.get(in_tensors[i])
        spec = (None,) * w.ndim if sh is None else _padded_spec(sh, w.ndim)
        lead = ep_entry if role == "expert" else None
        if spec[0] != lead or any(e is not None for e in spec[1:]):
            return None
    ep = _mesh_axes_size(mesh, ep_axes)
    if attrs.num_experts % ep:
        return None
    if ep > 1 and (attrs.held_experts or attrs.shared_hidden_size):
        return None  # a held share is one expert shard already
    here = attrs.num_experts // ep

    def local(x, *ws):
        shard = None
        if ep > 1:
            shard = (jax.lax.axis_index(ep_axes) * here, here)
        res = experts_forward(
            attrs, x, list(ws), expert_shard=shard, per_shard=True
        )
        if ep > 1:
            res[0] = jax.lax.psum(res[0], ep_axes)
        if attrs.has_aux and batch_axes:
            res[1] = jax.lax.pmean(res[1], batch_axes)
        return tuple(res)

    in_specs = (
        P(*x_spec),
        *[
            P(ep_entry, *[None] * (w.ndim - 1)) if role == "expert" else P()
            for role, w in zip(roles, slot_vals[1:])
        ],
    )
    out_specs = (P(*x_spec),) + ((P(),) if attrs.has_aux else ())
    return list(_shard_map(local, mesh, in_specs, out_specs)(*slot_vals))


class DistributedTrainingInstance:
    """PCG + machine mapping + loss + optimizer -> sharded jitted train step.

    The searched mapping (GraphOptimizeResult.machine_mapping) refines axis
    placement; without it, degrees map ICI-first.
    """

    def __init__(
        self,
        pcg: ParallelComputationGraph,
        logit_tensor: DataflowOutput,
        loss_attrs: LossAttrs,
        optimizer_attrs: OptimizerAttrs,
        machine_mesh: MachineMesh,
        mapping: Optional[Dict[Node, MachineView]] = None,
        metrics: FrozenSet[str] = frozenset(),
        compute_dtype=None,
        aux_loss_tensors: Sequence[DataflowOutput] = (),
        collect_step_stats: bool = False,
        guard_nonfinite_updates: bool = False,
        overlap: Optional[bool] = None,
    ) -> None:
        self.pcg = pcg
        self.logit_tensor = logit_tensor
        self.loss_attrs = loss_attrs
        self.optimizer_attrs = optimizer_attrs
        self.machine_mesh = machine_mesh
        # the searched per-node views survive on the instance: the static
        # transition verifier (ISSUE 19) reads them back as the old plan's
        # mapping when recompile() verifies the swap
        self.mapping = dict(mapping) if mapping else None
        self.metrics = metrics
        self.compute_dtype = compute_dtype
        # run-health step statistics (same contract as
        # ModelTrainingInstance: fused norms in-jit, last_step_stats on the
        # host side, optional nonfinite guard for skip_step/raise policies)
        self.collect_step_stats = collect_step_stats or guard_nonfinite_updates
        self.guard_nonfinite_updates = guard_nonfinite_updates
        self.last_step_stats = None
        self.aux_loss_tensors = tuple(aux_loss_tensors)
        self.shardings = pcg_shardings(pcg, machine_mesh, mapping)
        # loss/metrics consume the PRE-reshard logits: a searched plan ends
        # in a Combine whose replicated constraint would all-gather the full
        # logits to every device and run loss + backward entry replicated
        # (measured 2.2x step time vs the dedicated DP backend on the dp8
        # plan). Combine/Repartition only move layout, so the loss math is
        # identical on the sharded value and XLA reduces locally + psums.
        self.loss_logit_tensor = _pre_reshard_value(pcg, logit_tensor)
        # same LM-head fusion split as ModelTrainingInstance: barrier the
        # logit producer's inputs so its dX matmul stays un-fused from the
        # upstream norm's backward reductions
        self._barrier_nodes = frozenset({self.loss_logit_tensor.node})
        # fused collective-matmul lowering (--overlap / FF_TPU_OVERLAP,
        # force-reverted by FF_TPU_OVERLAP_BASELINE=1): the static site map
        # is the single source of truth for which edges lower fused — the
        # interpreter consults it, the trace span reports its size
        # (fused_edges), and the plan audit measures those edges as fused
        self.overlap = overlap
        self.overlap_sites: Dict[Node, str] = (
            collect_overlap_sites(pcg, self.shardings, machine_mesh.mesh)
            if overlap_lowering_active(overlap)
            else {}
        )
        # how each attention node will be lowered (attention_routes)
        self.attention_routes = attention_routes(
            pcg, self.shardings, machine_mesh.mesh
        )
        # (params, opt_state) shardings, recorded by initialize(): the
        # step programs hand the new state back under exactly these
        self._state_shardings = None
        # param key -> NamedSharding of the leaves whose master, slots and
        # update are cut finer than the PCG places the weight, and what
        # the rule did in numbers; both set with _state_shardings
        self.update_shardings: Dict[str, object] = {}
        self.update_record: Optional[dict] = None
        self._jit_step = None
        self._jit_fwd = None
        # what the `step` span says of this backend, spelled once
        self._step_span_args = {
            "backend": type(self).__name__,
            "mesh": str(dict(machine_mesh.mesh.shape)),
            "fused_edges": len(self.overlap_sites),
        }

    def _cast_for_compute(self, tree):
        from flexflow_tpu.kernels.precision import cast_for_compute

        return cast_for_compute(tree, self.compute_dtype)

    # -- placement helpers -------------------------------------------------

    def _state_out_shardings(self, n_outputs: int):
        """jit `out_shardings` pinning a step program's first two outputs
        (new params, new optimizer state) to the shardings the state
        arrives with. Left to XLA, a state leaf comes back under an equal
        layout spelled differently (`PartitionSpec()` for the
        `PartitionSpec(None,)` it was placed with), jit keys its cache on
        the spelling, and the second step of every fit recompiles the
        whole program — 31 s on the 12-layer flagship (my chip run,
        PR 21)."""
        if self._state_shardings is None:
            return None
        return (*self._state_shardings, *([None] * (n_outputs - 2)))

    def _weight_sharding(self, n: Node):
        (out,) = self.pcg.outputs_of(n)
        return self.shardings.get(out)

    def input_sharding(self, name: str):
        """NamedSharding of the input layer called `name` (for device_put of
        host batches — the SingleDataLoader equivalent feeds through this)."""
        for n in self.pcg.topological_ordering():
            la = self.pcg.layer_attrs(n)
            if isinstance(la.attrs, InputAttrs) and la.name == name:
                (out,) = self.pcg.outputs_of(n)
                return self.shardings.get(out)
        raise KeyError(name)

    def label_sharding(self):
        """Labels shard like the logits; sparse-categorical labels drop the
        class dim (they are rank-1 lower than the logits)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flexflow_tpu.op_attrs.ops.loss_functions import LossFunction

        s = self.shardings.get(self.loss_logit_tensor)
        if s is None:
            return None
        spec = list(s.spec)
        if self.loss_attrs.loss_type in (
            LossFunction.SPARSE_CATEGORICAL_CROSSENTROPY,
            LossFunction.LOSS_NODES,  # class indices too, for the loss nodes
        ):
            spec = spec[:-1]
        return NamedSharding(self.machine_mesh.mesh, P(*spec))

    def initialize(self, seed: int = 0):
        """Global init + placement onto the mesh. The float32 master of each
        weight and its optimizer slots live at the weight's update sharding
        (`update_partition_spec`: the PCG's sharding of the weight plus every
        mesh axis the PCG replicates it over); the scalar step is
        replicated. What the PCG places is the copy the step computes with:
        the interpreter's constraint on the cast weight gathers it."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        params = init_pcg_params(self.pcg, jax.random.PRNGKey(seed))
        from flexflow_tpu.runtime.distributed import device_put_global

        replicated = NamedSharding(self.machine_mesh.mesh, P())
        weight_shardings = {
            # an unconstrained weight counts as replicated
            param_key(n): self._weight_sharding(n) or replicated
            for n in self.pcg.topological_ordering()
            if isinstance(self.pcg.op_attrs(n), WeightAttrs)
        }
        self._set_state_shardings(weight_shardings, params)
        param_at, opt_at = self._state_shardings
        # every process computes the identical init (same PRNGKey);
        # device_put_global places only the shards this host owns
        placed = {
            k: device_put_global(params[k], param_at[k]) for k in param_at
        }
        # the slots are born at their shardings: a whole zero moment per
        # chip, placed and then cut, would be the start-up's memory peak
        opt_state = jax.jit(
            lambda p: make_optimizer_state(self.optimizer_attrs, p),
            out_shardings=opt_at,
        )(placed)
        return placed, opt_state

    def _set_state_shardings(self, weight_shardings, params) -> None:
        """Record the (params, opt_state) shardings of `params` (arrays or
        shapes) whose PCG placements are `weight_shardings`: each leaf and
        its slots at the leaf's update sharding. `update_shardings` keeps
        the leaves that cuts finer than the PCG does; `update_record` says
        what the rule did, for `search_provenance["update_sharding"]` and
        the static verifiers: how many leaves are cut finer and over which
        axes, which stayed as placed, the bytes a device of one copy of the
        leaves as the PCG places them and as they are stored (the weight,
        and each slot), and the float32 bytes a device gathers for the
        copies the step computes with (what the COMM census holds the
        weights' all-gathers to)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.machine_mesh.mesh
        names = {
            param_key(n): self.pcg.layer_attrs(n).name or param_key(n)
            for n in self.pcg.topological_ordering()
            if isinstance(self.pcg.op_attrs(n), WeightAttrs)
        }
        state_at = dict(weight_shardings)
        self.update_shardings = {}
        axes_count: Dict[str, int] = {}
        whole, placed_bytes, stored_bytes, gathered = [], 0, 0, 0
        for k, s in weight_shardings.items():
            piece = int(
                np.prod(s.shard_shape(params[k].shape), dtype=np.int64)
            ) * params[k].dtype.itemsize
            placed_bytes += piece
            spec, axes = update_partition_spec(
                params[k].shape, s.spec, dict(mesh.shape)
            )
            if not axes:
                whole.append(names.get(k, k))
                stored_bytes += piece
                continue
            state_at[k] = NamedSharding(mesh, P(*spec))
            self.update_shardings[k] = state_at[k]
            axes_count[",".join(axes)] = axes_count.get(",".join(axes), 0) + 1
            stored_bytes += piece // _mesh_axes_size(mesh, axes)
            gathered += piece
        self.update_record = {
            "mesh": dict(mesh.shape),
            "leaves_sharded": len(self.update_shardings),
            "axes": axes_count,
            "leaves_whole": sorted(whole),
            "bytes_per_device_as_placed": placed_bytes,
            "bytes_per_device": stored_bytes,
            "gather_bytes_per_device": gathered,
        }
        opt_shapes = jax.eval_shape(
            lambda p: make_optimizer_state(self.optimizer_attrs, p), params
        )
        replicated = NamedSharding(mesh, P())
        self._state_shardings = (
            state_at,
            {
                name: state_at if isinstance(slot, dict) else replicated
                for name, slot in opt_shapes.items()
            },
        )

    # -- step --------------------------------------------------------------

    def _at_update_shardings(self, grads):
        """Each gradient constrained to where its weight's update is
        computed (where the weight's master and slots live), so that XLA
        reduces it into a shard (a reduce-scatter) and not into a copy a
        chip. No leaf has one on a single chip, or under a plan that shards
        every weight over every axis: the lowered step is then what it was
        without the rule."""
        at = self.update_shardings
        return {
            k: jax.lax.with_sharding_constraint(g, at[k]) if k in at else g
            for k, g in grads.items()
        }

    def loss_fn(self, params, batch_inputs, label, rng=None):
        with trace.step_scope("cast"):
            params = self._cast_for_compute(params)
            batch_inputs = self._cast_for_compute(batch_inputs)
        env = pcg_forward_interpreter(
            self.pcg,
            params,
            batch_inputs,
            self.shardings,
            train=True,
            rng=rng,
            mesh=self.machine_mesh.mesh,
            barrier_nodes=self._barrier_nodes,
            overlap_sites=self.overlap_sites,
        )
        logit = env[self.loss_logit_tensor]
        with trace.step_scope("loss"):
            loss = loss_forward(self.loss_attrs, logit, label)
            for t in self.aux_loss_tensors:
                loss = loss + jnp.sum(env[t].astype(loss.dtype))
        return loss, logit

    def _step(self, params, opt_state, batch_inputs, label, rng):
        trace.count(trace.STEP_TRACE)  # this body runs when JAX traces it
        (loss, logit), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(
            params, batch_inputs, label, rng
        )
        with trace.step_scope("optimizer"):
            new_params, new_opt_state = apply_optimizer(
                self.optimizer_attrs, params, grads, opt_state,
                grads_at=self._at_update_shardings,
            )
        with trace.step_scope("metrics"):
            metric_vals = compute_metrics(self.metrics, logit, label)
        # same shared run-health tail as ModelTrainingInstance._step
        from flexflow_tpu.observability.metrics import finalize_step

        with trace.step_scope("health"):
            new_params, new_opt_state, stats = finalize_step(
                self.collect_step_stats, self.guard_nonfinite_updates,
                params, new_params, grads, loss, opt_state, new_opt_state,
            )
        if stats is None:
            return new_params, new_opt_state, loss, metric_vals
        return new_params, new_opt_state, loss, metric_vals, stats

    def compiled_step(self):
        if self._jit_step is None:
            self._jit_step = jax.jit(
                self._step, donate_argnums=(0, 1),
                out_shardings=self._state_out_shardings(
                    5 if self.collect_step_stats else 4
                ),
            )
        return self._jit_step

    def _record_stats(self, out):
        if self.collect_step_stats:
            self.last_step_stats = out[4]
            return out[:4]
        return out

    def train_step(self, params, opt_state, batch_inputs, label, rng=None):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        # the same span names as ModelTrainingInstance.train_step, so the
        # DP and searched-PCG step programs land on one comparable timeline
        with trace.record_span("step", **self._step_span_args):
            with self.machine_mesh.mesh, trace.record_span("dispatch"):
                out = self.compiled_step()(
                    params, opt_state, batch_inputs, label, rng
                )
        return self._record_stats(out)

    def forward(self, params, batch_inputs):
        if self._jit_fwd is None:

            def fwd(params, batch_inputs):
                env = pcg_forward_interpreter(
                    self.pcg,
                    params,
                    batch_inputs,
                    self.shardings,
                    mesh=self.machine_mesh.mesh,
                    overlap_sites=self.overlap_sites,
                )
                return env[self.logit_tensor]

            self._jit_fwd = jax.jit(fwd)
        with self.machine_mesh.mesh:
            return self._jit_fwd(params, batch_inputs)
