"""ParallelTensorShape (+ MachineView) -> jax PartitionSpec derivation.

This is the TPU-native realization of the reference's FFMapper: where
lib/runtime/src/mapper.cc places each point task of a MachineView on a
processor, here every PCG tensor's shard/sum/discard-copy degrees become a
`PartitionSpec` over the machine mesh and XLA's SPMD partitioner materializes
the data movement the mapper + Legion regions performed.

Axis-assignment policy (what makes the lowering collective-free along a
Megatron-style chain):

- ACTIVATIONS allocate mesh axes to shard dims left-to-right, then the sum
  degree, then the discard-copy degree. So [b/dp, s, h/tp] gets
  dp -> first axes, tp -> next axes, and a replicated activation
  (discard_copy=tp) puts tp on the same axes the consumer's out-dim shard
  will use.
- WEIGHTS allocate their discard-copy degree FIRST, then shard dims. A
  Unity linear weight [in, out/tp] with discard_copy=dp then lands as
  dp -> first axes (replicated over them), tp -> next axes — exactly the
  axes the surrounding activations use, so the matmul partitions cleanly.
- Tensors with sum_degree > 1 (pending partial sums, reference
  `Reduction` inputs) get NO constraint: in global view the producing op
  already denotes the full contraction and XLA keeps/reduces partials
  (psum / reduce-scatter) where profitable.

MachineView integration: a searched view's per-task-dim projections
(INTER_NODE vs INTRA_NODE, reference machine_view_dimension.struct.toml)
select which machine level (DCN vs ICI axes) each nontrivial degree draws
from. Strides/starts affect which concrete chips — placement XLA owns on
TPU — so only the projection axis survives lowering.
"""

from __future__ import annotations

from typing import Dict, Optional

from flexflow_tpu.op_attrs.parallel_tensor_shape import ParallelTensorShape
from flexflow_tpu.op_attrs.ops import WeightAttrs
from flexflow_tpu.pcg.machine_view import MachineView, ProjectionType
from flexflow_tpu.pcg.parallel_computation_graph import ParallelComputationGraph
from flexflow_tpu.parallel.mesh import AxisPool, MachineMesh
from flexflow_tpu.utils.graph import DataflowOutput, Node


def _prefer_inter_flags(pts: ParallelTensorShape, view: Optional[MachineView]):
    """Per-nontrivial-degree INTER preference from the machine view's
    projections, positionally over [shard dims, sum, discard]."""
    degrees = [d for d in pts.shard_degrees() if d > 1]
    if pts.sum_degree > 1:
        degrees.append(pts.sum_degree)
    if pts.discard_copy_degree > 1:
        degrees.append(pts.discard_copy_degree)
    flags = [False] * len(degrees)
    if view is not None and len(view.dimensions) == len(degrees):
        flags = [p == ProjectionType.INTER_NODE for p in view.projections()]
    return flags


def partition_spec_for_shape(
    pts: ParallelTensorShape,
    mm: MachineMesh,
    view: Optional[MachineView] = None,
    is_weight: bool = False,
):
    """PartitionSpec for one tensor, or None when the tensor must stay
    unconstrained (pending-sum activations, or degrees the mesh cannot
    express)."""
    from jax.sharding import PartitionSpec as P

    if not is_weight and pts.sum_degree > 1:
        return None

    pool = AxisPool(mm)
    flags = _prefer_inter_flags(pts, view)
    flag_it = iter(flags)

    entries = [None] * pts.num_dims

    def alloc(degree):
        prefer_inter = next(flag_it, False)
        return pool.allocate(degree, prefer_inter=prefer_inter)

    if is_weight and pts.discard_copy_degree > 1:
        # reserve the replica axes first (see module docstring), tensor
        # stays replicated over them (they do not appear in the spec);
        # the discard-copy degree's projection flag is positionally last
        prefer = flags[-1] if flags else False
        if pool.allocate(pts.discard_copy_degree, prefer_inter=prefer) is None:
            return None

    for i, d in enumerate(pts.shard_degrees()):
        if d == 1:
            continue
        axes = alloc(d)
        if axes is None:
            return None
        entries[i] = axes if len(axes) > 1 else axes[0]

    # non-weight discard-copy degree consumes axes (replication) after shard
    # dims; sum_degree>1 activations already returned None above
    if not is_weight and pts.discard_copy_degree > 1:
        if alloc(pts.discard_copy_degree) is None:
            return None

    return P(*entries)


def pcg_shardings(
    pcg: ParallelComputationGraph,
    mm: MachineMesh,
    mapping: Optional[Dict[Node, MachineView]] = None,
) -> Dict[DataflowOutput, Optional[object]]:
    """NamedSharding (or None = unconstrained) for every tensor in the PCG.

    `mapping` is the searched per-node MachineView dict from
    compiler.unity_algorithm.GraphOptimizeResult; absent entries (or no
    mapping at all) default to ICI-first axis assignment.
    """
    from jax.sharding import NamedSharding

    mapping = mapping or {}
    out: Dict[DataflowOutput, Optional[object]] = {}
    for n in pcg.topological_ordering():
        view = mapping.get(n)
        is_weight = isinstance(pcg.op_attrs(n), WeightAttrs)
        for o in pcg.outputs_of(n):
            spec = partition_spec_for_shape(
                pcg.tensor_shape(o), mm, view, is_weight=is_weight
            )
            out[o] = None if spec is None else NamedSharding(mm.mesh, spec)

    # Weights whose sole consumer chain is resharding ops adopt the
    # POST-chain sharding: searched plans express weight sharding as a
    # Repartition node after a degree-1 weight (rule sandwiches), and
    # placing the parameter replicated at rest only to reshard it every
    # step wastes HBM and defeats the cost model's weight-resident pricing
    # (parallel_op_cost_ms: "sharded parameters live sharded from init").
    from flexflow_tpu.op_attrs.ops import RepartitionAttrs

    for n in pcg.topological_ordering():
        if not isinstance(pcg.op_attrs(n), WeightAttrs):
            continue
        (w,) = pcg.outputs_of(n)
        chain = [w]
        v = w
        while True:
            consumers = pcg.uses_of(v)
            if len(consumers) != 1:
                break
            c = consumers[0].node
            if not isinstance(pcg.op_attrs(c), RepartitionAttrs):
                break
            v = pcg.outputs_of(c)[0]
            chain.append(v)
        if v != w and out.get(v) is not None:
            # the WHOLE chain adopts the final sharding: leaving an
            # intermediate Repartition's own (partial) spec in place would
            # constrain the already-sharded parameter back to the partial
            # layout each step (an all-gather) before re-slicing
            for t in chain:
                out[t] = out[v]
    return out


def update_partition_spec(shape, spec, mesh_shape):
    """Where a weight's update is computed, and its float32 master and
    optimizer slots live: the weight's own partition `spec` plus every mesh
    axis the weight is replicated over, all on the weight's first dimension
    whose local extent those axes divide. Returns (spec entries padded to
    the rank, the axes added); a leaf with no free axis, or one that no
    dimension takes (a vector of odd length), keeps the weight's own spec
    and adds ().

    The weight-update sharding of Xu et al. (arXiv:2004.13336): a gradient
    all-reduce is a reduce-scatter and an all-gather, and Adam between the
    two runs on 1/replicas of the leaf with 1/replicas of its moments."""
    entries = [
        tuple(e) if isinstance(e, (tuple, list)) else ((e,) if e else ())
        for e in tuple(spec)
    ]
    entries += [()] * (len(shape) - len(entries))
    used = {a for e in entries for a in e}
    free = tuple(a for a, n in mesh_shape.items() if n > 1 and a not in used)
    ways = 1
    for a in free:
        ways *= mesh_shape[a]
    added = ()
    if free:
        for d, size in enumerate(shape):
            held = 1
            for a in entries[d]:
                held *= mesh_shape[a]
            if size % (held * ways) == 0:
                entries[d] = entries[d] + free
                added = free
                break
    return (
        tuple(None if not e else (e[0] if len(e) == 1 else e) for e in entries),
        added,
    )
