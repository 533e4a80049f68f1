"""Substitution soundness auditor.

For every registered rule, synthesize a minimal host PCG from the rule's OWN
pattern (concrete attrs satisfying the operator constraints, input shapes
satisfying the tensor constraints, every channel-like size a multiple of
every degree the rule mentions), apply the rule, and check the rewritten
interface is shape/degree-equivalent: each (pattern output, RHS output) pair
in `output_mapping` must carry the SAME ParallelTensorShape before and after
the rewrite. An unsound rule — one whose RHS changes the external parallel
interface — fails here at test time instead of mid-search as a wrong answer
or an XLA crash.

This is strictly stronger than `is_valid_match_for_substitution`, which only
requires RHS shape inference to SUCCEED: a rule that repartitions its output
without combining it back passes validity (the sharded shape infers fine)
but breaks every downstream consumer's expectations; the auditor rejects it
(RULE002).

Catalog:

RULE001 unexercised       no host could be synthesized for the pattern, or
                          the pattern found no match on its own host
                          (warning: the rule is outside the auditable
                          vocabulary, not proven sound)
RULE002 interface-broken  the rewritten interface shape differs from the
                          matched one (error)
RULE003 apply-failed      the rule's RHS fails to apply to its own
                          pattern's shapes (error)
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from flexflow_tpu.analysis.diagnostics import Diagnostic, error, warning
from flexflow_tpu.op_attrs.core import (
    IncomingTensorRole,
    OperatorType,
    get_incoming_tensor_roles,
    get_parallel_output_shapes,
    get_parallel_weight_shapes,
    op_type_of,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    BatchMatmulAttrs,
    BatchNormAttrs,
    BroadcastAttrs,
    CombineAttrs,
    ConcatAttrs,
    Conv2DAttrs,
    DropoutAttrs,
    ElementBinaryAttrs,
    ElementBinaryOpType,
    ElementUnaryAttrs,
    ElementUnaryOpType,
    EmbeddingAttrs,
    InputAttrs,
    LayerNormAttrs,
    RMSNormAttrs,
    LinearAttrs,
    MultiHeadAttentionAttrs,
    NoopAttrs,
    Pool2DAttrs,
    ReductionAttrs,
    RepartitionAttrs,
    ReplicateAttrs,
    SoftmaxAttrs,
)
from flexflow_tpu.op_attrs.ops.conv_ops import FlatAttrs
from flexflow_tpu.op_attrs.ops.moe import ExpertsAttrs
from flexflow_tpu.op_attrs.ops.shape_ops import ReduceAttrs, ReduceOpType
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorDims,
    ParallelTensorShape,
    ShardParallelDim,
    lift_to_parallel,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.pcg.parallel_computation_graph import (
    ParallelComputationGraph,
    ParallelLayerAttrs,
    ParallelTensorAttrs,
)
from flexflow_tpu.substitutions.operator_pattern import (
    ConstraintType,
    OperatorAttributeKey,
    OperatorAttributePattern,
    op_attrs_satisfy_pattern,
)
from flexflow_tpu.substitutions.output_graph import AttrConstant
from flexflow_tpu.substitutions.pcg_pattern import find_pattern_matches
from flexflow_tpu.substitutions.substitution import (
    Substitution,
    apply_substitution,
    match_interface_is_closed,
)
from flexflow_tpu.substitutions.tensor_pattern import (
    TensorAttributeKey,
    TensorConstraintType,
)
from flexflow_tpu.utils.graph import DataflowOutput, GraphInput

RULE_AUDIT_CATALOG: Dict[str, str] = {
    "RULE001": "unexercised: pattern outside the synthesizable vocabulary",
    "RULE002": "interface-broken: rewrite changes the external parallel shape",
    "RULE003": "apply-failed: RHS rejects the rule's own pattern shapes",
}

_AUDIT_SINK_PREFIX = "__audit_out__"
# stands for "any value but None" among a pattern's equalities
_SET = object()


# ---------------------------------------------------------------------------
# constraint introspection
# ---------------------------------------------------------------------------


def _pattern_fields(op_pattern: OperatorAttributePattern):
    """(op_type, {field: eq value}, {field: divisor}) from the constraints."""
    op_type = None
    eq: Dict[str, object] = {}
    div: Dict[str, int] = {}
    for c in op_pattern.constraints:
        if c.key == OperatorAttributeKey.OP_TYPE:
            if c.constraint_type == ConstraintType.EQUAL:
                op_type = c.value
        elif c.constraint_type == ConstraintType.EQUAL:
            eq[c.field_name] = c.value
        elif c.constraint_type == ConstraintType.DIVISIBLE_BY:
            div[c.field_name] = math.lcm(div.get(c.field_name, 1), c.value)
        elif (
            c.constraint_type == ConstraintType.NOT_EQUAL and c.value is None
        ):
            # "this optional field is set": the defaults pick a value for
            # it. Other NOT_EQUAL / NOT_CONTAINS constraints are validated
            # against the defaults later
            eq.setdefault(c.field_name, _SET)
    return op_type, eq, div


def _rule_degree_lcm(sub: Substitution) -> int:
    """lcm of every degree the rule mentions anywhere: tensor-pattern and
    op-pattern divisibility constraints plus the RHS's constant parallel-op
    degrees. Sizing every channel-like dimension as a multiple of this makes
    the synthesized host admit the rule's resharding at full degree (the
    sandwich rules carry their degree ONLY in the RHS constants)."""
    lcm = 1
    pg = sub.pattern.graph
    for gi in pg.graph_inputs:
        lbl = pg.value_label(gi)
        if lbl is None:
            continue
        for c in lbl.constraints:
            if c.constraint_type == TensorConstraintType.DIVISIBLE_BY and isinstance(
                c.value, int
            ):
                lcm = math.lcm(lcm, c.value)
    degree_fields = (
        "repartition_degree",
        "combine_degree",
        "replicate_degree",
        "reduction_degree",
    )
    for pn in pg.nodes:
        for c in pg.node_label(pn).constraints:
            if c.constraint_type == ConstraintType.DIVISIBLE_BY and isinstance(
                c.value, int
            ):
                lcm = math.lcm(lcm, c.value)
            elif (
                c.constraint_type == ConstraintType.EQUAL
                and getattr(c, "field_name", None) in degree_fields
                and isinstance(c.value, int)
            ):
                lcm = math.lcm(lcm, c.value)
    og = sub.output_expr.graph
    for on in og.nodes:
        lbl = og.node_label(on)
        if isinstance(lbl, AttrConstant):
            a = lbl.attrs
            for field in (
                "repartition_degree",
                "combine_degree",
                "replicate_degree",
                "reduction_degree",
            ):
                v = getattr(a, field, None)
                if isinstance(v, int):
                    lcm = math.lcm(lcm, v)
    return lcm


def _gi_divisors(pattern_graph, gi: GraphInput) -> Dict[int, int]:
    """dim index -> lcm of DIM_SIZE DIVISIBLE_BY constraints on this input."""
    out: Dict[int, int] = {}
    lbl = pattern_graph.value_label(gi)
    if lbl is None:
        return out
    for c in lbl.constraints:
        if (
            c.key == TensorAttributeKey.DIM_SIZE
            and c.constraint_type == TensorConstraintType.DIVISIBLE_BY
            and c.dim is not None
            and isinstance(c.value, int)
        ):
            out[c.dim] = math.lcm(out.get(c.dim, 1), c.value)
    return out


def _scale_dims(dims: Tuple[int, ...], divisors: Dict[int, int]):
    dims = list(dims)
    for d, k in divisors.items():
        if -len(dims) <= d < len(dims):
            dims[d] = math.lcm(dims[d], k)
    return tuple(dims)


# ---------------------------------------------------------------------------
# attrs + shape synthesis
# ---------------------------------------------------------------------------


def _default_attrs(op_type: OperatorType, eq: Dict, div: Dict, size: int):
    """Concrete default attrs for `op_type` honoring eq/div constraints,
    channel-like fields sized `size` (a multiple of every rule degree).
    None when the op type is outside the synthesizable vocabulary."""

    def up(base, k=1):
        return math.lcm(base, max(k, 1))

    if op_type == OperatorType.LINEAR:
        return LinearAttrs(
            out_channels=up(size, div.get("out_channels", 1)),
            use_bias=eq.get("use_bias", False),
            activation=eq.get("activation", None),
        )
    if op_type == OperatorType.CONV2D:
        groups = up(eq.get("groups", 1), div.get("groups", 1))
        return Conv2DAttrs(
            out_channels=up(up(size, div.get("out_channels", 1)), groups),
            kernel_h=3,
            kernel_w=3,
            padding_h=1,
            padding_w=1,
            groups=groups,
            use_bias=eq.get("use_bias", False),
        )
    if op_type == OperatorType.EMBEDDING:
        return EmbeddingAttrs(
            num_entries=64,
            out_channels=up(size, div.get("out_channels", 1)),
        )
    if op_type in (
        OperatorType.MULTIHEAD_ATTENTION, OperatorType.RING_ATTENTION
    ):
        from flexflow_tpu.op_attrs.ops import RingAttentionAttrs

        heads = up(size, div.get("num_heads", 1))
        cls = (
            RingAttentionAttrs
            if op_type == OperatorType.RING_ATTENTION
            else MultiHeadAttentionAttrs
        )
        latent = eq.get("kv_latent_rank") is _SET
        return cls(
            embed_dim=heads * 4,
            num_heads=heads,
            kdim=6 if latent else 0,
            vdim=4 if latent else 0,
            bias=eq.get("bias", False),
            qk_norm_eps=1e-5 if eq.get("qk_norm_eps") is _SET else None,
            kv_latent_rank=4 if latent else None,
            shared_key_dim=2 if latent else 0,
            q_latent_rank=4 if eq.get("q_latent_rank") is _SET else None,
        )
    if op_type == OperatorType.RMS_NORM:
        return RMSNormAttrs()
    if op_type == OperatorType.BATCH_NORM:
        return BatchNormAttrs(affine=eq.get("affine", True))
    if op_type == OperatorType.LAYER_NORM:
        # normalize the channel dim of a rank-3 stream; NOT_CONTAINS(axes)
        # constraints in the dim-variant rules hold because only the last
        # axis is normalized
        return LayerNormAttrs(
            axes=(2,), elementwise_affine=eq.get("elementwise_affine", True)
        )
    if op_type == OperatorType.SOFTMAX:
        return SoftmaxAttrs()
    if op_type == OperatorType.DROPOUT:
        return DropoutAttrs(rate=0.1)
    if op_type == OperatorType.POOL2D:
        return Pool2DAttrs(kernel_h=2, kernel_w=2, stride_h=2, stride_w=2)
    if op_type == OperatorType.FLAT:
        return FlatAttrs()
    if op_type == OperatorType.ELEMENT_UNARY:
        return ElementUnaryAttrs(eq.get("op_type", ElementUnaryOpType.RELU))
    if op_type == OperatorType.ELEMENT_BINARY:
        return ElementBinaryAttrs(eq.get("op_type", ElementBinaryOpType.ADD))
    if op_type == OperatorType.CONCAT:
        return ConcatAttrs(axis=eq.get("axis", 1))
    if op_type == OperatorType.BATCH_MATMUL:
        return BatchMatmulAttrs()
    if op_type == OperatorType.REDUCE:
        return ReduceAttrs(
            op_type=eq.get("op_type", ReduceOpType.SUM),
            axes=eq.get("axes", (0,)),
            keepdims=eq.get("keepdims", False),
        )
    if op_type == OperatorType.BROADCAST:
        return BroadcastAttrs(target_dims=())  # pinned to input dims later
    if op_type == OperatorType.EXPERTS:
        lambda_bal = eq.get("lambda_bal")
        if lambda_bal is None:
            lambda_bal = 0.01  # the with_aux pattern pins lambda_bal != 0
        return ExpertsAttrs(
            num_experts=up(size, div.get("num_experts", 1)),
            num_select=2,
            hidden_size=size,
            out_channels=size,
            use_bias=eq.get("use_bias", False),
            lambda_bal=lambda_bal,
            gated=eq.get("gated", False),
            scoring="sigmoid" if eq.get("selection_bias") else "softmax",
            selection_bias=bool(eq.get("selection_bias", False)),
            # the patterns of the shared forms pin the selection bias or
            # the shared expert's gate and ask for a shared width that is
            # not zero
            shared_hidden_size=(
                size if eq.get("selection_bias") or eq.get("shared_gate")
                else 0
            ),
            latent_size=size if eq.get("latent_size") is _SET else None,
            shared_gate=bool(eq.get("shared_gate", False)),
        )
    if op_type == OperatorType.STATE_SPACE:
        from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs

        return StateSpaceAttrs(
            num_heads=2, head_dim=4, state_size=4, num_groups=1, chunk_size=4
        )
    if op_type == OperatorType.GATED_DELTA:
        from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs

        decay = eq.get("decay", "channel")
        return GatedDeltaAttrs(
            num_heads=2, key_dim=4, value_dim=4, gate_rank=2, chunk_size=4,
            num_key_heads=1 if decay == "head" else None, decay=decay,
        )
    if op_type == OperatorType.SHORT_CONV:
        from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs

        return ShortConvAttrs(width=size)
    if op_type == OperatorType.SELECTIVE_SCAN:
        from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs

        return SelectiveScanAttrs(
            channels=size, state_size=4, dt_rank=2,
            memory_output=eq.get("memory_output", False),
        )
    if op_type == OperatorType.LABEL_LOSS:
        from flexflow_tpu.op_attrs.ops import LabelCrossEntropyAttrs

        return LabelCrossEntropyAttrs()
    if op_type == OperatorType.MEAN_LOSS:
        from flexflow_tpu.op_attrs.ops import MeanLossAttrs

        return MeanLossAttrs()
    if op_type == OperatorType.REPARTITION:
        return RepartitionAttrs(
            eq.get("repartition_dim", 0), eq.get("repartition_degree", 2)
        )
    if op_type == OperatorType.COMBINE:
        return CombineAttrs(
            eq.get("combine_dim", 0), eq.get("combine_degree", 2)
        )
    if op_type == OperatorType.REPLICATE:
        return ReplicateAttrs(eq.get("replicate_degree", 2))
    if op_type == OperatorType.REDUCTION:
        return ReductionAttrs(eq.get("reduction_degree", 2))
    if op_type == OperatorType.NOOP:
        return NoopAttrs()
    return None


def _data_shape_table(op_type: OperatorType, size: int, arity: int):
    """Base DATA input dims per op type (weights are derived, never listed).
    None = outside the vocabulary."""
    S = size
    table = {
        OperatorType.LINEAR: ((S, S, S),),
        OperatorType.CONV2D: ((S, S, 8, 8),),
        OperatorType.EMBEDDING: ((S, S),),
        OperatorType.MULTIHEAD_ATTENTION: ((8, S, S), (8, S, S), (8, S, S)),
        OperatorType.RING_ATTENTION: ((8, S, S), (8, S, S), (8, S, S)),
        OperatorType.RMS_NORM: ((S, S, S),),
        OperatorType.BATCH_NORM: ((S, S, 8, 8),),
        OperatorType.LAYER_NORM: ((S, S, S),),
        OperatorType.SOFTMAX: ((S, S),),
        OperatorType.DROPOUT: ((S, S, S),),
        OperatorType.POOL2D: ((S, S, 8, 8),),
        OperatorType.FLAT: ((S, S, 4, 4),),
        OperatorType.ELEMENT_UNARY: ((S, S, S),),
        OperatorType.ELEMENT_BINARY: ((S, S, S), (S, S, S)),
        OperatorType.BATCH_MATMUL: ((S, S, S), (S, S, S)),
        OperatorType.REDUCE: ((S, S, S),),
        OperatorType.BROADCAST: ((S, S, S),),
        OperatorType.EXPERTS: ((S, S),),
        OperatorType.STATE_SPACE: ((S, S, S),),
        OperatorType.GATED_DELTA: ((S, S, S),),
        OperatorType.SHORT_CONV: ((S, S, S),),
        OperatorType.SELECTIVE_SCAN: ((S, S, S),),
        OperatorType.LABEL_LOSS: ((S, S, S), (S, S)),
        OperatorType.MEAN_LOSS: ((S, S),),
        OperatorType.REPARTITION: ((S, S, S),),
        OperatorType.COMBINE: ((S, S, S),),
        OperatorType.REPLICATE: ((S, S, S),),
        OperatorType.REDUCTION: ((S, S, S),),
        OperatorType.NOOP: ((S, S, S),),
    }
    if op_type == OperatorType.CONCAT:
        return tuple((S, S) for _ in range(arity))
    return table.get(op_type)


def _input_label_for_slot(
    consumer_attrs, dims: Tuple[int, ...], dtype: DataType
) -> ParallelTensorShape:
    """Parallel shape of a graph input feeding `consumer_attrs` directly.
    Parallel-op consumers need pre-parallelized inputs (a Combine divides an
    existing shard degree, a Reduction divides an existing sum degree);
    everything else takes a degree-1 lift."""
    shard = [ShardParallelDim(d, 1) for d in dims]
    sum_degree = 1
    if isinstance(consumer_attrs, CombineAttrs):
        d = consumer_attrs.combine_dim % len(dims)
        size = math.lcm(dims[d], consumer_attrs.combine_degree)
        shard[d] = ShardParallelDim(size, consumer_attrs.combine_degree)
    elif isinstance(consumer_attrs, ReductionAttrs):
        sum_degree = consumer_attrs.reduction_degree
    return ParallelTensorShape(
        ParallelTensorDims(tuple(shard), sum_degree, 1), dtype
    )


def _synthesize_host(
    sub: Substitution,
) -> Optional[Tuple[ParallelComputationGraph, Dict]]:
    """Build a host PCG realizing the rule's own pattern, with one Noop
    marker consumer per interface output (so the interface's post-rewrite
    shapes are recoverable and closure is genuinely required). Returns
    (host, pattern value -> host value) or None when the pattern is outside
    the synthesizable vocabulary."""
    from flexflow_tpu.local_execution.training_backing import split_slot_values

    pg = sub.pattern.graph
    topo = pg.topological_ordering()
    size = math.lcm(16, _rule_degree_lcm(sub))

    node_attrs: Dict = {}
    for pn in topo:
        op_type, eq, div = _pattern_fields(pg.node_label(pn))
        if op_type is None:
            return None
        attrs = _default_attrs(op_type, eq, div, size)
        if attrs is None or not op_attrs_satisfy_pattern(
            attrs, pg.node_label(pn)
        ):
            return None
        node_attrs[pn] = attrs

    host = ParallelComputationGraph()
    host_val: Dict = {}  # pattern value (gi or DataflowOutput) -> host value

    def materialize_gi(gi, shape: ParallelTensorShape):
        """Input node carrying `shape` (pre-parallelized for parallel-op
        consumers); a gi bound to several slots must agree on sizes."""
        if gi in host_val:
            existing = host.tensor_shape(host_val[gi])
            return host_val[gi] if existing == shape else None
        _, (v,) = host.add_node(
            ParallelLayerAttrs(
                InputAttrs(TensorShape(shape.sizes(), shape.dtype)),
                f"gi{gi.idx}",
            ),
            [],
            [ParallelTensorAttrs(shape)],
        )
        host_val[gi] = v
        return v

    for pn in topo:
        attrs = node_attrs[pn]
        ins = pg.inputs_of(pn)
        op_type = op_type_of(attrs)
        base = _data_shape_table(op_type, size, len(ins))
        if base is None:
            return None
        roles = get_incoming_tensor_roles(attrs)
        if op_type == OperatorType.CONCAT:
            roles = [IncomingTensorRole.INPUT] * len(ins)
        if len(roles) != len(ins):
            return None
        data_slots = [
            i for i, r in enumerate(roles) if r == IncomingTensorRole.INPUT
        ]
        if len(data_slots) != len(base):
            return None
        # token ids and a loss node's labels are integers
        int_slots = {
            OperatorType.EMBEDDING: (0,), OperatorType.LABEL_LOSS: (1,),
        }.get(op_type, ())
        # required dims per data slot: table defaults scaled by the gi's
        # divisibility constraints; already-produced values keep theirs
        slot_dims: Dict[int, Tuple[int, ...]] = {}
        for slot_pos, dims in zip(data_slots, base):
            v = ins[slot_pos]
            if isinstance(v, GraphInput):
                dims = _scale_dims(dims, _gi_divisors(pg, v))
            elif v in host_val:
                dims = host.tensor_shape(host_val[v]).sizes()
            else:
                return None
            slot_dims[slot_pos] = dims
        # multi-input consistency (attention batch/seq, elementwise
        # equality): unify to the elementwise lcm across slots
        if op_type in (
            OperatorType.MULTIHEAD_ATTENTION,
            OperatorType.RING_ATTENTION,
            OperatorType.ELEMENT_BINARY,
        ):
            ranks = {len(d) for d in slot_dims.values()}
            if len(ranks) != 1:
                return None
            rank = ranks.pop()
            unified = tuple(
                math.lcm(*(d[i] for d in slot_dims.values()))
                for i in range(rank)
            )
            slot_dims = {i: unified for i in slot_dims}
        if isinstance(attrs, BroadcastAttrs):
            attrs = BroadcastAttrs(target_dims=slot_dims[data_slots[0]])
            node_attrs[pn] = attrs
        # materialize data slots (parallel-op consumers get pre-sharded
        # inputs from _input_label_for_slot)
        data_shapes: List[ParallelTensorShape] = []
        for i in data_slots:
            v = ins[i]
            if isinstance(v, GraphInput):
                shape = _input_label_for_slot(
                    attrs, slot_dims[i],
                    DataType.INT32 if data_slots.index(i) in int_slots
                    else DataType.FLOAT,
                )
                if materialize_gi(v, shape) is None:
                    return None
            shape = host.tensor_shape(host_val[v])
            data_shapes.append(shape)
        # weight slots derive their shapes from the data shapes
        try:
            weight_shapes = (
                list(get_parallel_weight_shapes(attrs, data_shapes))
                if len(roles) > len(data_slots)
                else []
            )
        except (AssertionError, IndexError, ValueError, TypeError):
            return None
        w_iter = iter(weight_shapes)
        for i, (v, r) in enumerate(zip(ins, roles)):
            if r != IncomingTensorRole.WEIGHT:
                continue
            try:
                w = next(w_iter)
            except StopIteration:
                return None
            if isinstance(v, GraphInput):
                if materialize_gi(v, w) is None:
                    return None
            elif host.tensor_shape(host_val[v]) != w:
                return None
        # add the pattern node itself
        host_ins = [host_val[v] for v in ins]
        data_vals, _ = split_slot_values(
            attrs, [host.tensor_shape(v) for v in host_ins]
        )
        try:
            out_shapes = get_parallel_output_shapes(attrs, data_vals)
        except (AssertionError, IndexError, ValueError, TypeError):
            return None
        if len(out_shapes) != len(pg.outputs_of(pn)):
            return None
        _, outs = host.add_node(
            ParallelLayerAttrs(attrs, None),
            host_ins,
            [ParallelTensorAttrs(s) for s in out_shapes],
        )
        for po, hv in zip(pg.outputs_of(pn), outs):
            host_val[po] = hv

    # any gi the walk never bound (pattern declares an unused input)
    for gi in pg.graph_inputs:
        if gi not in host_val:
            if (
                materialize_gi(
                    gi,
                    lift_to_parallel(
                        TensorShape((size, size, size), DataType.FLOAT)
                    ),
                )
                is None
            ):
                return None

    # marker consumers on the interface outputs
    for i, (pval, _) in enumerate(sub.output_mapping):
        hv = host_val[pval]
        host.add_node(
            ParallelLayerAttrs(NoopAttrs(), f"{_AUDIT_SINK_PREFIX}{i}"),
            [hv],
            [ParallelTensorAttrs(host.tensor_shape(hv))],
        )
    return host, host_val


# ---------------------------------------------------------------------------
# the audit itself
# ---------------------------------------------------------------------------


@dataclass
class RuleAudit:
    name: str
    status: str  # "ok" | "unsound" | "unexercised"
    diagnostics: List[Diagnostic]
    matches_checked: int = 0


def audit_substitution(sub: Substitution) -> RuleAudit:
    """Audit one rule; see the module docstring for the catalog."""
    synth = _synthesize_host(sub)
    if synth is None:
        return RuleAudit(
            sub.name,
            "unexercised",
            [
                warning(
                    "RULE001",
                    f"rule {sub.name!r}: pattern outside the synthesizable "
                    "vocabulary; soundness not proven",
                    hint="extend the rule_audit shape table for this op type",
                )
            ],
        )
    host, _ = synth
    matches = [
        m
        for m in find_pattern_matches(sub.pattern, host)
        if match_interface_is_closed(host, sub, m)
    ]
    if not matches:
        return RuleAudit(
            sub.name,
            "unexercised",
            [
                warning(
                    "RULE001",
                    f"rule {sub.name!r}: synthesized host produced no "
                    "closed-interface match",
                )
            ],
        )
    diags: List[Diagnostic] = []
    checked = 0
    for match in matches[:4]:  # symmetric patterns repeat; a few suffice
        try:
            new_pcg = apply_substitution(host, sub, match)
        except (AssertionError, KeyError, ValueError) as e:
            diags.append(
                error(
                    "RULE003",
                    f"rule {sub.name!r}: RHS failed to apply to its own "
                    f"pattern's shapes: {type(e).__name__}: {e}",
                    hint="the output expr's shape inference rejects shapes "
                    "the pattern admits",
                )
            )
            continue
        checked += 1
        node_map = match.node_map()
        new_markers = {
            new_pcg.layer_attrs(n).name: n
            for n in new_pcg.nodes
            if (new_pcg.layer_attrs(n).name or "").startswith(
                _AUDIT_SINK_PREFIX
            )
        }
        for i, (pval, _) in enumerate(sub.output_mapping):
            old_shape = host.tensor_shape(
                DataflowOutput(node_map[pval.node], pval.idx)
            )
            marker = new_markers.get(f"{_AUDIT_SINK_PREFIX}{i}")
            if marker is None:
                diags.append(
                    error(
                        "RULE003",
                        f"rule {sub.name!r}: interface output {i} lost its "
                        "consumer during the rewrite",
                    )
                )
                continue
            new_shape = new_pcg.tensor_shape(new_pcg.inputs_of(marker)[0])
            if new_shape != old_shape:
                diags.append(
                    error(
                        "RULE002",
                        f"rule {sub.name!r}: interface output {i} changes "
                        f"shape {old_shape} -> {new_shape}",
                        hint="the RHS must restore the matched interface's "
                        "exact parallel shape (add the missing Combine/"
                        "Reduction or fix the degrees)",
                    )
                )
    status = (
        "unsound"
        if any(d.rule_id in ("RULE002", "RULE003") for d in diags)
        else ("ok" if checked else "unexercised")
    )
    return RuleAudit(sub.name, status, diags, checked)


def audit_rules(
    rules: List[Substitution],
) -> Tuple[List[RuleAudit], List[Diagnostic]]:
    """Audit every rule; returns (per-rule results, flattened diagnostics)."""
    results = [audit_substitution(sub) for sub in rules]
    diags = [d for r in results for d in r.diagnostics]
    return results, diags


def registered_rules_for_grid(num_devices: int) -> List[Substitution]:
    """The rule registry the search registers for an `num_devices`-device
    machine: parallelization rules at every divisor degree plus the fusion
    rules. Single source of truth for ffcheck --audit-rules, the tier-1
    audit test, and the README rule-count claim — three sites that must
    audit the SAME registry."""
    from flexflow_tpu.substitutions.fusion_rules import generate_fusion_rules
    from flexflow_tpu.substitutions.rules import generate_parallelization_rules

    degrees = [d for d in range(2, num_devices + 1) if num_devices % d == 0]
    # enable_pipeline: the stage-partitioning rewrites are opt-in for the
    # SEARCH (flat searches keep their pinned winners) but the audit
    # registry covers the full vocabulary, so a rule that introduces
    # stage ops is soundness-checked like every other rule
    return list(
        generate_parallelization_rules(degrees, enable_pipeline=True)
    ) + list(generate_fusion_rules())
