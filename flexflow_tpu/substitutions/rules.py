"""The generated parallelization rule set seeding the Unity search.

Reference: the reference ships equivalent rules as legacy TASO-style JSON
(graph_subst_3_v2.json era, loaded by lib/substitution-generator
legacy_rules.h:40-55); SURVEY.md §7 step 6 calls for generating them
programmatically instead. Each rule rewrites a single op into a
partition/replicate -> op' -> combine/reduction sandwich that preserves the
op's external parallel interface; redundant resharding pairs introduced at
rule boundaries are cancelled by the combine/repartition cancellation rules.

All Linear rules here match use_bias=False layers (bias variants are a later
widening); degrees are instantiated per machine size by
generate_parallelization_rules.
"""

from __future__ import annotations

from typing import List

from flexflow_tpu.op_attrs.core import OperatorType
from flexflow_tpu.op_attrs.ops import (
    CombineAttrs,
    NoopAttrs,
    RepartitionAttrs,
    ReplicateAttrs,
    ReductionAttrs,
)
from flexflow_tpu.substitutions.operator_pattern import (
    ConstraintType,
    OperatorAttributeConstraint,
    OperatorAttributeKey,
    OperatorAttributePattern,
)
from flexflow_tpu.substitutions.output_graph import (
    AttrConstant,
    CopyAttrsFromMatched,
    OutputGraphExpr,
)
from flexflow_tpu.substitutions.pcg_pattern import PCGPattern
from flexflow_tpu.substitutions.substitution import Substitution
from flexflow_tpu.substitutions.tensor_pattern import (
    TensorAttributeConstraint,
    TensorAttributeKey,
    TensorAttributePattern,
    TensorConstraintType,
)


def _shard_pattern(dim: int, degree: int) -> TensorAttributePattern:
    """Tensor shardable on `dim` by `degree`: dim size divisible, and (for
    positive dims) rank big enough that `dim` is strictly before the last
    (channel/contraction) dim — the generalized sample rules use dim=1 for
    the sequence axis of rank-3 activation streams."""
    cs = [
        TensorAttributeConstraint(
            TensorAttributeKey.DIM_SIZE,
            TensorConstraintType.DIVISIBLE_BY,
            degree,
            dim=dim,
        )
    ]
    if dim >= 0:
        cs.append(
            TensorAttributeConstraint(
                TensorAttributeKey.NUM_DIMS,
                TensorConstraintType.GREATER_EQUAL,
                dim + 2,
            )
        )
    return TensorAttributePattern(tuple(cs))


def _dim_tag(dim: int) -> str:
    return "" if dim == 0 else f"_dim{dim}"


def _linear_pattern(use_bias=False, a_pattern=None, w_pattern=None,
                    any_layout=False):
    """Pattern: a Linear with (activation, weight[, bias]) inputs. A rule
    that shards or fuses the WEIGHT reads it as [in, out]: it matches no
    tied head (`LinearAttrs.weight_transposed`) unless `any_layout`."""
    p = PCGPattern()
    a = p.add_input(a_pattern)
    w = p.add_input(w_pattern)
    extras = [p.add_input()] if use_bias else []
    layout = {} if any_layout else {"weight_transposed": False}
    node, (y,) = p.add_operator(
        OperatorAttributePattern.for_op_type(
            OperatorType.LINEAR, use_bias=use_bias, **layout
        ),
        [a, w, *extras],
    )
    return p, a, w, extras, node, y


def data_parallel_linear_rule(
    degree: int, use_bias: bool = False, dim: int = 0
) -> Substitution:
    """Linear(a, w[, b]) -> Combine_d(Linear(Repartition_d(a), Replicate(w)
    [, Replicate(b)])): sample parallelism on any pre-contraction activation
    dim (dim=0 batch, dim=1 sequence — the latter gives the seq-parallel
    residual stream its Linear segments)."""
    p, a, w, extras, pnode, py = _linear_pattern(
        use_bias, a_pattern=_shard_pattern(dim, degree), any_layout=True
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ow = og.add_input()
    o_extras = [og.add_input() for _ in extras]
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(dim, degree)), [oa])
    _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
    reps = []
    for oe in o_extras:
        _, (er,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [oe])
        reps.append(er)
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, wr, *reps])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(dim, degree)), [y])
    return Substitution(
        f"data_parallel_linear{_dim_tag(dim)}_{'b_' if use_bias else ''}{degree}",
        p,
        og,
        ((a, oa), (w, ow), *zip(extras, o_extras)),
        ((py, out),),
    )


def tensor_parallel_linear_rule(degree: int, use_bias: bool = False) -> Substitution:
    """Linear(a, w[, b]) -> Combine_-1(Linear(Replicate(a), Repartition_1(w)
    [, Repartition_0(b)])): out-channel (parameter) parallelism."""
    p, a, w, extras, pnode, py = _linear_pattern(
        use_bias, w_pattern=TensorAttributePattern.dim_divisible_by(1, degree)
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ow = og.add_input()
    o_extras = [og.add_input() for _ in extras]
    _, (ar,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [oa])
    _, (wp,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [ow])
    parts = []
    for oe in o_extras:
        _, (ep,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oe])
        parts.append(ep)
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ar, wp, *parts])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(-1, degree)), [y])
    return Substitution(
        f"tensor_parallel_linear_{'b_' if use_bias else ''}{degree}",
        p,
        og,
        ((a, oa), (w, ow), *zip(extras, o_extras)),
        ((py, out),),
    )


def reduction_parallel_linear_rule(degree: int) -> Substitution:
    """Linear(a, w) -> Reduction(Linear(Repartition_-1(a), Repartition_0(w))):
    attribute (reduction-dim) parallelism."""
    p, a, w, _, pnode, py = _linear_pattern(
        a_pattern=TensorAttributePattern.dim_divisible_by(-1, degree)
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ow = og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(-1, degree)), [oa])
    _, (wp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [ow])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, wp])
    _, (out,) = og.add_operator(AttrConstant(ReductionAttrs(degree)), [y])
    return Substitution(
        f"reduction_parallel_linear_{degree}",
        p,
        og,
        ((a, oa), (w, ow)),
        ((py, out),),
    )


def head_parallel_attention_rule(degree: int) -> Substitution:
    """MHA(q,k,v,w) -> Reduction(MHA(Repl(q), Repl(k), Repl(v),
    Repartition_heads(w))): head (tensor) parallelism via the reference's
    discard-copy-drives-heads rule (attention.cc:320-353)."""
    p = PCGPattern()
    q = p.add_input()
    k = p.add_input()
    v = p.add_input()
    w = p.add_input()
    pnode, (py,) = p.add_operator(
        # (a node with QK-norm has two more weight slots and cannot match)
        OperatorAttributePattern.for_op_type(
            OperatorType.MULTIHEAD_ATTENTION, bias=False
        ),
        [q, k, v, w],
    )
    og = OutputGraphExpr()
    oq, ok, ov, ow = (og.add_input() for _ in range(4))
    _, (qr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [oq])
    _, (kr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ok])
    _, (vr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ov])
    _, (wp,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [ow])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [qr, kr, vr, wp])
    _, (out,) = og.add_operator(AttrConstant(ReductionAttrs(degree)), [y])
    return Substitution(
        f"head_parallel_attention_{degree}",
        p,
        og,
        ((q, oq), (k, ok), (v, ov), (w, ow)),
        ((py, out),),
    )


def _seq_parallel_attention_rule(
    degree: int, attrs_cls, name: str, extra_div=None
) -> Substitution:
    """Shared builder for the sequence/context-parallel attention rules:
    MHA(q,k,v,w) -> Combine_1(attrs_cls(Part_1(q,k,v), Replicate(w))) —
    the matched MHA retyped to the schedule's attrs class (identical fields
    & weight layout, so trained weights are preserved verbatim)."""
    import dataclasses

    from flexflow_tpu.op_attrs.ops import MultiHeadAttentionAttrs
    from flexflow_tpu.substitutions.output_graph import ComputeAttrsFromMatched

    p = PCGPattern()
    q = p.add_input(TensorAttributePattern.dim_divisible_by(1, degree))
    k = p.add_input(TensorAttributePattern.dim_divisible_by(1, degree))
    v = p.add_input(TensorAttributePattern.dim_divisible_by(1, degree))
    w = p.add_input()
    pnode, (py,) = p.add_operator(
        _attr_pattern(
            OperatorType.MULTIHEAD_ATTENTION,
            # RoPE under a sequence shard needs the shard's global
            # positions, a window its halo of keys (RingAttentionAttrs'
            # shape rule, ROADMAP R7); a rope_scaling comes with a rope_theta;
            # the schedules scale by d ** -0.5 themselves
            eq=dict(
                bias=False, rope_theta=None, window=None, softmax_scale=None
            ),
            div=extra_div,
        ),
        [q, k, v, w],
    )

    def retype(attrs: MultiHeadAttentionAttrs):
        return attrs_cls(
            **{f.name: getattr(attrs, f.name) for f in dataclasses.fields(attrs)}
        )

    og = OutputGraphExpr()
    oq, ok, ov, ow = (og.add_input() for _ in range(4))
    _, (qp_,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [oq])
    _, (kp_,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [ok])
    _, (vp_,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [ov])
    _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
    _, (y,) = og.add_operator(
        ComputeAttrsFromMatched((pnode,), retype), [qp_, kp_, vp_, wr]
    )
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(1, degree)), [y])
    return Substitution(
        f"{name}_{degree}",
        p,
        og,
        ((q, oq), (k, ok), (v, ov), (w, ow)),
        ((py, out),),
    )


def sequence_parallel_attention_rule(degree: int) -> Substitution:
    """Ring flavor: the rewritten kernel rotates K/V blocks around the mesh
    ring — sequence/context parallelism, NEW capability vs the reference
    (SURVEY.md §5)."""
    from flexflow_tpu.op_attrs.ops import RingAttentionAttrs

    return _seq_parallel_attention_rule(
        degree, RingAttentionAttrs, "sequence_parallel_attention"
    )


def _attr_pattern(
    op_type, eq=None, div=None, ne=None, nc=None
) -> OperatorAttributePattern:
    """Op pattern with equality, divisibility, inequality, and
    not-contains constraints."""
    cs = [
        OperatorAttributeConstraint(
            OperatorAttributeKey.OP_TYPE, ConstraintType.EQUAL, op_type
        )
    ]
    for f, v in (eq or {}).items():
        cs.append(
            OperatorAttributeConstraint(
                OperatorAttributeKey.FIELD, ConstraintType.EQUAL, v, field_name=f
            )
        )
    for f, v in (ne or {}).items():
        cs.append(
            OperatorAttributeConstraint(
                OperatorAttributeKey.FIELD,
                ConstraintType.NOT_EQUAL,
                v,
                field_name=f,
            )
        )
    for f, v in (div or {}).items():
        cs.append(
            OperatorAttributeConstraint(
                OperatorAttributeKey.FIELD,
                ConstraintType.DIVISIBLE_BY,
                v,
                field_name=f,
            )
        )
    for f, v in (nc or {}).items():
        cs.append(
            OperatorAttributeConstraint(
                OperatorAttributeKey.FIELD,
                ConstraintType.NOT_CONTAINS,
                v,
                field_name=f,
            )
        )
    return OperatorAttributePattern(tuple(cs))


def _conv_pattern(degree, use_bias, a_pattern=None, div=None, groups=1):
    """Pattern: Conv2D with (input, kernel[, bias]) inputs; groups=None
    leaves the group count unconstrained (divisibility via `div`)."""
    p = PCGPattern()
    a = p.add_input(a_pattern)
    ws = [p.add_input() for _ in range(2 if use_bias else 1)]
    eq = dict(use_bias=use_bias)
    if groups is not None:
        eq["groups"] = groups
    node, (y,) = p.add_operator(
        _attr_pattern(OperatorType.CONV2D, eq=eq, div=div),
        [a, *ws],
    )
    return p, a, ws, node, y


def data_parallel_conv2d_rule(degree: int, use_bias: bool) -> Substitution:
    """Conv2D(x, k[, b]) -> Combine_0(Conv2D(Repartition_0(x), Replicate(k)
    [, Replicate(b)])): sample parallelism (reference conv_2d.cc sample-dim
    rule, lib/op-attrs/src/op-attrs/ops/conv_2d.cc:100-140)."""
    p, a, ws, pnode, py = _conv_pattern(
        degree,
        use_bias,
        a_pattern=TensorAttributePattern.dim_divisible_by(0, degree),
        groups=None,  # sample parallelism is valid for any group count
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ows = [og.add_input() for _ in ws]
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oa])
    reps = []
    for ow in ows:
        _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
        reps.append(wr)
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, *reps])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(0, degree)), [y])
    return Substitution(
        f"data_parallel_conv2d_{'b' if use_bias else 'nb'}_{degree}",
        p,
        og,
        ((a, oa), *zip(ws, ows)),
        ((py, out),),
    )


def channel_parallel_conv2d_rule(
    degree: int, use_bias: bool, grouped: bool = False
) -> Substitution:
    """Conv2D(x, k[, b]) -> Combine_1(Conv2D(Replicate(x), Repartition_0(k)
    [, Repartition_0(b)])): out-channel (parameter) parallelism (reference
    conv_2d.cc replica-partitions-out-channels rule).

    `grouped=True` matches grouped convs (ResNeXt) whose group count splits
    evenly over the shards — each shard owns groups/degree whole groups, so
    the kernel slice stays self-contained; the default variant pins
    groups=1 (a divisibility constraint alone would exclude it: 1 % k != 0)."""
    if grouped:
        p, a, ws, pnode, py = _conv_pattern(
            degree,
            use_bias,
            div=dict(out_channels=degree, groups=degree),
            groups=None,
        )
    else:
        p, a, ws, pnode, py = _conv_pattern(
            degree, use_bias, div=dict(out_channels=degree)
        )
    og = OutputGraphExpr()
    oa = og.add_input()
    ows = [og.add_input() for _ in ws]
    _, (ar,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [oa])
    parts = []
    for ow in ows:
        _, (wp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [ow])
        parts.append(wp)
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ar, *parts])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(1, degree)), [y])
    return Substitution(
        f"channel_parallel_conv2d_{'b' if use_bias else 'nb'}_{degree}",
        p,
        og,
        ((a, oa), *zip(ws, ows)),
        ((py, out),),
    )


def reduction_parallel_conv2d_rule(degree: int) -> Substitution:
    """Conv2D(x, k) -> Reduction(Conv2D(Repartition_1(x), Repartition_1(k))):
    in-channel (attribute) parallelism yielding partial sums (reference
    conv_2d.cc in-channel rule; bias-free like the linear reduction rule)."""
    p, a, ws, pnode, py = _conv_pattern(
        degree,
        use_bias=False,
        a_pattern=TensorAttributePattern.dim_divisible_by(1, degree),
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ow = og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [oa])
    _, (wp,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [ow])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, wp])
    _, (out,) = og.add_operator(AttrConstant(ReductionAttrs(degree)), [y])
    return Substitution(
        f"reduction_parallel_conv2d_{degree}",
        p,
        og,
        ((a, oa), (ws[0], ow)),
        ((py, out),),
    )


def data_parallel_embedding_rule(degree: int) -> Substitution:
    """Embedding(ids, w) -> Combine_0(Embedding(Repartition_0(ids),
    Replicate(w))): sample parallelism (reference embedding.cc:60-85)."""
    p = PCGPattern()
    a = p.add_input(TensorAttributePattern.dim_divisible_by(0, degree))
    w = p.add_input()
    pnode, (py,) = p.add_operator(
        OperatorAttributePattern.for_op_type(OperatorType.EMBEDDING), [a, w]
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ow = og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oa])
    _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, wr])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(0, degree)), [y])
    return Substitution(
        f"data_parallel_embedding_{degree}",
        p,
        og,
        ((a, oa), (w, ow)),
        ((py, out),),
    )


def column_parallel_embedding_rule(degree: int) -> Substitution:
    """Embedding(ids, w) -> Combine_-1(Embedding(Replicate(ids),
    Repartition_1(w))): out-channel (parameter) parallelism — each shard
    holds a column slice of the table (reference embedding.cc:88-111)."""
    p = PCGPattern()
    a = p.add_input()
    w = p.add_input()
    pnode, (py,) = p.add_operator(
        _attr_pattern(OperatorType.EMBEDDING, div=dict(out_channels=degree)),
        [a, w],
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ow = og.add_input()
    _, (ar,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [oa])
    _, (wp,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [ow])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ar, wp])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(-1, degree)), [y])
    return Substitution(
        f"column_parallel_embedding_{degree}",
        p,
        og,
        ((a, oa), (w, ow)),
        ((py, out),),
    )


def _experts_pattern(use_bias, gated, with_aux, div=None, shared=False,
                     latent=False, shared_gate=False):
    """(attribute pattern, weight slots, outputs) of one form of the Experts
    op. The forms differ in their number of weight slots (legacy with and
    without biases, gated; `shared`: the bias-free form with a selection
    bias and a shared expert, two or three more slots; `latent`: the
    projections into and out of a latent space, two more; `shared_gate`: a
    shared expert with its gate and NO selection bias, the softmax-routed
    form, three or four more) and outputs (an
    auxiliary scalar or none), and a pattern has a fixed number of both.
    `with_aux` matches lambda_bal != 0 (with or without a z-loss); a z-loss
    alone has no rule."""
    eq = dict(
        use_bias=use_bias, gated=gated, selection_bias=shared,
        shared_gate=shared_gate,
    )
    ne = {}
    if not with_aux:
        eq.update(lambda_bal=0.0, lambda_z=0.0)
    else:
        ne.update(lambda_bal=0.0)
    if shared or shared_gate:
        ne.update(shared_hidden_size=0)
    else:
        eq.update(shared_hidden_size=0)
    if latent:
        ne.update(latent_size=None)
    pattern = _attr_pattern(
        OperatorType.EXPERTS, eq=eq, div=div, ne=ne or None
    )
    num_w = 4 if gated else (5 if use_bias else 3)
    if shared or shared_gate:
        # the shared expert's matrices, and the selection bias or the gate
        num_w += 1 + (3 if gated else 2)
    if latent:
        num_w += 2
    return pattern, num_w, 2 if with_aux else 1


def _experts_tag(use_bias, gated, with_aux, shared=False, latent=False,
                 shared_gate=False):
    form = "g" if gated else ("b" if use_bias else "nb")
    return (
        f"{form}{'_sh' if shared else ''}{'_shg' if shared_gate else ''}"
        f"{'_lat' if latent else ''}"
        f"{'_aux' if with_aux else ''}"
    )


def data_parallel_state_space_rule(
    degree: int, op_type: OperatorType = OperatorType.STATE_SPACE,
    decay: str = "channel", memory_output: bool = False,
) -> Substitution:
    """StateSpace(x, w...) -> Combine_0(StateSpace(Repartition_0(x),
    Replicate(w)...)): the scan runs along the sequence of each sample by
    itself, so the batch dim shards and nothing else does. The same rule for
    the gated delta-rule mixer (`op_type` GATED_DELTA), whose recurrence
    runs along the sequence as the scan does, and for the short-convolution
    mixer (SHORT_CONV), whose taps read the positions before their own. The
    delta-rule mixer has a rule a form (`decay`), because the forms differ
    in their number of weight slots and a pattern has a fixed number. The
    selective-scan mixer (SELECTIVE_SCAN, a recurrence a channel along the
    sequence) has one a number of outputs (`memory_output`: its memory is a
    second output, combined over the batch as the first is)."""
    from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs
    from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs
    from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs
    from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs

    tag = op_type.value
    if op_type == OperatorType.GATED_DELTA:
        num_weights = GatedDeltaAttrs(2, 4, 4, decay=decay).num_weights
        attr_pattern = _attr_pattern(op_type, eq=dict(decay=decay))
        tag += "_head" if decay == "head" else ""
    elif op_type == OperatorType.SELECTIVE_SCAN:
        num_weights = SelectiveScanAttrs.num_weights
        attr_pattern = _attr_pattern(
            op_type, eq=dict(memory_output=memory_output)
        )
        tag += "_memory" if memory_output else ""
    else:
        num_weights = {
            OperatorType.STATE_SPACE: StateSpaceAttrs,
            OperatorType.SHORT_CONV: ShortConvAttrs,
        }[op_type].num_weights
        attr_pattern = OperatorAttributePattern.for_op_type(op_type)
    outputs = 2 if memory_output else 1
    p = PCGPattern()
    a = p.add_input(_shard_pattern(0, degree))
    ws = [p.add_input() for _ in range(num_weights)]
    pnode, pys = p.add_operator(attr_pattern, [a, *ws], num_outputs=outputs)
    og = OutputGraphExpr()
    oa = og.add_input()
    ows = [og.add_input() for _ in ws]
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oa])
    reps = []
    for ow in ows:
        _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
        reps.append(wr)
    _, ys = og.add_operator(
        CopyAttrsFromMatched(pnode), [ap, *reps], num_outputs=outputs
    )
    outs = [
        og.add_operator(AttrConstant(CombineAttrs(0, degree)), [y])[1][0]
        for y in ys
    ]
    return Substitution(
        f"data_parallel_{tag}_{degree}",
        p,
        og,
        ((a, oa), *zip(ws, ows)),
        tuple(zip(pys, outs)),
    )


def data_parallel_experts_rule(
    degree: int, use_bias: bool, gated: bool = False, with_aux: bool = False,
    shared: bool = False, latent: bool = False, shared_gate: bool = False,
) -> Substitution:
    """Experts(x, gate, w...) -> Combine_0(Experts(Repartition_0(x),
    Replicate(gate), Replicate(w)...)): sample parallelism for the MoE FFN.
    Each batch shard routes its own tokens to every expert: a finite
    capacity, and f_e, P_e and Z of the auxiliary scalar, are over the
    shard's tokens (the shards' scalars are averaged), which is what
    data-parallel MoE training does and not the one-device value. As in the
    expert-parallel rule the auxiliary output is found structurally, not
    interface-mapped."""
    attr_pattern, num_w, num_out = _experts_pattern(
        use_bias, gated, with_aux, shared=shared, latent=latent,
        shared_gate=shared_gate,
    )
    p = PCGPattern()
    a = p.add_input(_shard_pattern(0, degree))
    ws = [p.add_input() for _ in range(num_w)]
    pnode, pouts = p.add_operator(attr_pattern, [a, *ws], num_outputs=num_out)
    og = OutputGraphExpr()
    oa = og.add_input()
    ows = [og.add_input() for _ in ws]
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oa])
    reps = []
    for ow in ows:
        _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
        reps.append(wr)
    _, youts = og.add_operator(
        CopyAttrsFromMatched(pnode), [ap, *reps], num_outputs=num_out
    )
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(0, degree)), [youts[0]])
    return Substitution(
        f"data_parallel_experts_"
        f"{_experts_tag(use_bias, gated, with_aux, shared, latent, shared_gate)}"
        f"_{degree}",
        p,
        og,
        ((a, oa), *zip(ws, ows)),
        ((pouts[0], out),),
    )


def expert_parallel_experts_rule(
    degree: int, use_bias: bool, with_aux: bool = False, gated: bool = False
) -> Substitution:
    """Experts(x, gate, w1[, b1], w2[, b2]) -> Reduction(Experts(Replicate(x),
    Replicate(gate), Repartition_0(w1)[, ...])): expert parallelism — each
    shard owns num_experts/degree experts and contributes a partial sum for
    the tokens it serves (reference: examples/cpp/mixture_of_experts/moe.cc
    via GroupBy/Aggregate; here the fused tpu-native Experts op).

    `with_aux=True` matches the lambda_bal>0 (two-output) form: the
    load-balance aux scalar is unconsumed inside the graph (training adds it
    to the loss), so only the main output is interface-mapped; the RHS op
    emits its own replicated aux, found structurally by the training
    instance. `gated=True` matches the three-matrix form (four weights)."""
    attr_pattern, num_w, num_out = _experts_pattern(
        use_bias, gated, with_aux, div=dict(num_experts=degree)
    )
    p = PCGPattern()
    a = p.add_input()
    ws = [p.add_input() for _ in range(num_w)]
    pnode, pouts = p.add_operator(attr_pattern, [a, *ws], num_outputs=num_out)
    py = pouts[0]
    og = OutputGraphExpr()
    oa = og.add_input()
    ows = [og.add_input() for _ in ws]
    _, (ar,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [oa])
    new_ws = []
    for i, ow in enumerate(ows):
        if i == 0:  # gate table: every shard gates all tokens
            _, (wv,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
        else:  # expert tensors: shard the leading expert dim
            _, (wv,) = og.add_operator(
                AttrConstant(RepartitionAttrs(0, degree)), [ow]
            )
        new_ws.append(wv)
    _, youts = og.add_operator(
        CopyAttrsFromMatched(pnode), [ar, *new_ws], num_outputs=num_out
    )
    _, (out,) = og.add_operator(AttrConstant(ReductionAttrs(degree)), [youts[0]])
    return Substitution(
        f"expert_parallel_experts_{_experts_tag(use_bias, gated, with_aux)}"
        f"_{degree}",
        p,
        og,
        ((a, oa), *zip(ws, ows)),
        ((py, out),),
    )


def branch_parallel_bmm_rule(degree: int) -> Substitution:
    """BatchMatmul(a, w) -> Combine_0(BMM(Repartition_0(a),
    Repartition_0(w))): leading-axis parallelism. On a branch-stacked
    subgraph (compiler/branch_stacking.py) dim 0 is the branch axis, so
    sharding it places each branch's matmul on a disjoint device subset —
    the TPU realization of the reference's disjoint-resource parallel split
    (get_optimal_machine_mapping.cc parallel case + mapper.h:82-126 point
    placement). Equally valid as plain batch parallelism for any BMM."""
    p = PCGPattern()
    a = p.add_input(_shard_pattern(0, degree))
    w = p.add_input(_shard_pattern(0, degree))
    pnode, (py,) = p.add_operator(
        OperatorAttributePattern.for_op_type(OperatorType.BATCH_MATMUL),
        [a, w],
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ow = og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oa])
    _, (wp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [ow])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, wp])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(0, degree)), [y])
    return Substitution(
        f"branch_parallel_bmm_{degree}",
        p,
        og,
        ((a, oa), (w, ow)),
        ((py, out),),
    )


def bmm_batch_parallel_rule(degree: int) -> Substitution:
    """BatchMatmul(a, w) -> Combine_1(BMM(Repartition_1(a), Replicate(w))):
    sample parallelism on the n-rows dim of a BMM whose rhs is a (stacked)
    weight — composes with branch_parallel_bmm_rule so a branch-stacked
    subgraph can use branch x dp hybrids (branch axis on one mesh axis,
    batch on others)."""
    p = PCGPattern()
    a = p.add_input(_shard_pattern(1, degree))
    w = p.add_input()
    pnode, (py,) = p.add_operator(
        OperatorAttributePattern.for_op_type(OperatorType.BATCH_MATMUL),
        [a, w],
    )
    og = OutputGraphExpr()
    oa = og.add_input()
    ow = og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(1, degree)), [oa])
    _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, wr])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(1, degree)), [y])
    return Substitution(
        f"bmm_batch_parallel_{degree}",
        p,
        og,
        ((a, oa), (w, ow)),
        ((py, out),),
    )


def branch_reduce_sum_rule(degree: int) -> Substitution:
    """ReduceSum_axis0(x) -> Reduction(ReduceSum_axis0(Repartition_0(x))):
    the merge half of branch parallelism — each device group sums the
    branches it holds locally, then a Reduction (psum) combines the partial
    sums. Pins the reference Reduction data movement
    (lib/kernels/src/cuda/ops/reduction_kernels.cu:9-16) at the merge site."""
    from flexflow_tpu.op_attrs.ops.shape_ops import ReduceOpType

    p = PCGPattern()
    x = p.add_input(_shard_pattern(0, degree))
    pnode, (py,) = p.add_operator(
        _attr_pattern(
            OperatorType.REDUCE,
            eq=dict(op_type=ReduceOpType.SUM, axes=(0,), keepdims=False),
        ),
        [x],
    )
    og = OutputGraphExpr()
    ox = og.add_input()
    _, (xp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [ox])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [xp])
    _, (out,) = og.add_operator(AttrConstant(ReductionAttrs(degree)), [y])
    return Substitution(
        f"branch_reduce_sum_{degree}",
        p,
        og,
        ((x, ox),),
        ((py, out),),
    )


def data_parallel_attention_rule(
    degree: int, bias: bool = False, qk_norm: bool = False,
    op_type: OperatorType = OperatorType.MULTIHEAD_ATTENTION,
    latent: bool = False, q_latent: bool = False,
) -> Substitution:
    """MHA(q,k,v,w[,bi,bo][,gq,gk]) -> Combine_0(MHA(Repartition_0(q,k,v),
    Replicate(w)[, Replicate(bi), Replicate(bo)][, Replicate(gq),
    Replicate(gk)])): sample parallelism for attention (reference
    attention.cc sample-dim rule). Without this the transformer's searched
    DP plan left every MHA serial, forcing a full reshard at each attention
    boundary. `bias=True` matches the biased op (input and output bias as
    two more weights, replicated like w), `qk_norm=True` the op with
    QK-norm (its two gains likewise); RoPE adds no slot and needs no rule
    of its own. `op_type=RING_ATTENTION` is the same rewrite for the
    program's causal attention, whose sequence dim stays whole here;
    `latent=True` matches latent attention (the latent norm's gain as one
    more weight), `q_latent=True` latent attention with a query rank (the
    query norm's gain as one more after it); a rotary on the shared slice
    adds no slot."""
    p = PCGPattern()
    q = p.add_input(TensorAttributePattern.dim_divisible_by(0, degree))
    k = p.add_input(TensorAttributePattern.dim_divisible_by(0, degree))
    v = p.add_input(TensorAttributePattern.dim_divisible_by(0, degree))
    weights = [
        p.add_input()
        for _ in range(
            1 + 2 * bool(bias) + 2 * bool(qk_norm) + bool(latent)
            + bool(q_latent)
        )
    ]
    ne, eq = {}, dict(bias=bias)
    if qk_norm:
        ne.update(qk_norm_eps=None)
    if latent:
        ne.update(kv_latent_rank=None)
    if q_latent:
        assert latent, "a query rank is latent attention's"
        ne.update(q_latent_rank=None)
    pnode, (py,) = p.add_operator(
        _attr_pattern(
            op_type,
            eq=eq,
            ne=ne or None,
        ),
        [q, k, v, *weights],
    )
    og = OutputGraphExpr()
    oq, ok, ov = (og.add_input() for _ in range(3))
    o_weights = [og.add_input() for _ in weights]
    parts = []
    for oi in (oq, ok, ov):
        _, (xp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oi])
        parts.append(xp)
    reps = []
    for ow in o_weights:
        _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ow])
        reps.append(wr)
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [*parts, *reps])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(0, degree)), [y])
    return Substitution(
        f"data_parallel_"
        f"{'ring_' if op_type == OperatorType.RING_ATTENTION else ''}"
        f"attention_{'b_' if bias else ''}{'qkn_' if qk_norm else ''}"
        f"{'lat_' if latent else ''}{'qlat_' if q_latent else ''}{degree}",
        p,
        og,
        ((q, oq), (k, ok), (v, ov), *zip(weights, o_weights)),
        ((py, out),),
    )


def data_parallel_label_loss_rule(degree: int) -> Substitution:
    """LabelLoss(logits, labels) -> Reduction(LabelLoss(Repartition_0(logits),
    Repartition_0(labels))): sample parallelism for a loss node. Each batch
    shard's scalar is a partial sum of the one mean over the labelled
    positions of all shards (in the global view the op computes exactly
    that), so the output carries sum_degree = degree and a Reduction
    completes it."""
    p = PCGPattern()
    x = p.add_input(_shard_pattern(0, degree))
    y = p.add_input(_shard_pattern(0, degree))
    pnode, (py,) = p.add_operator(
        OperatorAttributePattern.for_op_type(OperatorType.LABEL_LOSS), [x, y]
    )
    og = OutputGraphExpr()
    ox, oy = og.add_input(), og.add_input()
    _, (xp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [ox])
    _, (yp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oy])
    _, (loss,) = og.add_operator(CopyAttrsFromMatched(pnode), [xp, yp])
    _, (out,) = og.add_operator(AttrConstant(ReductionAttrs(degree)), [loss])
    return Substitution(
        f"data_parallel_label_loss_{degree}",
        p,
        og,
        ((x, ox), (y, oy)),
        ((py, out),),
    )


def data_parallel_layer_norm_rule(degree: int, dim: int = 0) -> Substitution:
    """LayerNorm(x, g, b) -> Combine_d(LayerNorm(Repartition_d(x),
    Replicate(g), Replicate(b))): per-sample stats parallelize over any
    non-normalized dim (dim=0 batch, dim=1 sequence). The dim != 0 variants
    additionally require `dim` not be one of the normalized axes (axes are
    stored as non-negative indices)."""
    extra = {}
    if dim != 0:
        extra["nc"] = dict(axes=dim)
    p = PCGPattern()
    a = p.add_input(_shard_pattern(dim, degree))
    g = p.add_input()
    b = p.add_input()
    pnode, (py,) = p.add_operator(
        _attr_pattern(
            OperatorType.LAYER_NORM,
            eq=dict(elementwise_affine=True),
            **extra,
        ),
        [a, g, b],
    )
    og = OutputGraphExpr()
    oa, og_, ob = og.add_input(), og.add_input(), og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(dim, degree)), [oa])
    _, (gr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [og_])
    _, (br,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ob])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, gr, br])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(dim, degree)), [y])
    return Substitution(
        f"data_parallel_layer_norm{_dim_tag(dim)}_{degree}",
        p,
        og,
        ((a, oa), (g, og_), (b, ob)),
        ((py, out),),
    )


def data_parallel_rms_norm_rule(degree: int, dim: int = 0) -> Substitution:
    """RMSNorm(x, g) -> Combine_d(RMSNorm(Repartition_d(x), Replicate(g))):
    the statistic is per position over the last dim, so any other dim
    shards (dim=0 batch, dim=1 sequence)."""
    p = PCGPattern()
    a = p.add_input(_shard_pattern(dim, degree))
    g = p.add_input()
    pnode, (py,) = p.add_operator(
        OperatorAttributePattern.for_op_type(OperatorType.RMS_NORM), [a, g]
    )
    og = OutputGraphExpr()
    oa, og_ = og.add_input(), og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(dim, degree)), [oa])
    _, (gr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [og_])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, gr])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(dim, degree)), [y])
    return Substitution(
        f"data_parallel_rms_norm{_dim_tag(dim)}_{degree}",
        p,
        og,
        ((a, oa), (g, og_)),
        ((py, out),),
    )


def data_parallel_batch_norm_rule(degree: int) -> Substitution:
    """BatchNorm(x, g, b) -> Combine_0(BatchNorm(Repartition_0(x),
    Replicate(g), Replicate(b))): batch stats psum across shards on TPU
    (XLA inserts the collective under GSPMD)."""
    p = PCGPattern()
    a = p.add_input(TensorAttributePattern.dim_divisible_by(0, degree))
    g = p.add_input()
    b = p.add_input()
    pnode, (py,) = p.add_operator(
        OperatorAttributePattern.for_op_type(OperatorType.BATCH_NORM, affine=True),
        [a, g, b],
    )
    og = OutputGraphExpr()
    oa, og_, ob = og.add_input(), og.add_input(), og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oa])
    _, (gr,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [og_])
    _, (br,) = og.add_operator(AttrConstant(ReplicateAttrs(degree)), [ob])
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), [ap, gr, br])
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(0, degree)), [y])
    return Substitution(
        f"data_parallel_batch_norm_{degree}",
        p,
        og,
        ((a, oa), (g, og_), (b, ob)),
        ((py, out),),
    )


def data_parallel_concat_rule(degree: int, arity: int) -> Substitution:
    """Concat_axis1(x...) -> Combine_0(Concat(Repartition_0(x)...)) for
    channel/feature concats (Inception branches, DLRM sparse+dense merge)."""
    p = PCGPattern()
    p_ins = [
        p.add_input(TensorAttributePattern.dim_divisible_by(0, degree))
        for _ in range(arity)
    ]
    pnode, (py,) = p.add_operator(
        _attr_pattern(OperatorType.CONCAT, eq=dict(axis=1)), p_ins
    )
    og = OutputGraphExpr()
    o_ins = [og.add_input() for _ in range(arity)]
    parts = []
    for oi in o_ins:
        _, (xp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, degree)), [oi])
        parts.append(xp)
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), parts)
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(0, degree)), [y])
    return Substitution(
        f"data_parallel_concat{arity}_{degree}",
        p,
        og,
        tuple(zip(p_ins, o_ins)),
        ((py, out),),
    )


def sequence_parallel_attention_a2a_rule(degree: int) -> Substitution:
    """Ulysses flavor: the rewritten kernel all-to-alls heads-for-sequence
    and attends the full sequence locally (second context-parallel strategy;
    requires heads divisible by the degree so the a2a can trade sequence
    shards for head shards)."""
    from flexflow_tpu.op_attrs.ops.ulysses_attention import (
        UlyssesAttentionAttrs,
    )

    return _seq_parallel_attention_rule(
        degree,
        UlyssesAttentionAttrs,
        "sequence_parallel_attention_a2a",
        extra_div=dict(num_heads=degree),
    )


def data_parallel_op_rule(
    op_type: OperatorType, degree: int, num_inputs: int = 1, dim: int = 0
) -> Substitution:
    """Generic shard-dim rule for weightless elementwise-ish ops:
    Op(x...) -> Combine_d(Op(Repartition_d(x)...)). dim=0 is the classic
    batch rule; dim=1 rides the sequence axis of rank-3 streams; dim=-1
    (ELEMENT_UNARY/BINARY/DROPOUT only — never reduction-like ops) shards
    the channel dim so activations between tensor-parallel linears stay
    sharded (the Megatron pattern's activation segment)."""
    p = PCGPattern()
    p_ins = [p.add_input(_shard_pattern(dim, degree)) for _ in range(num_inputs)]
    pnode, (py,) = p.add_operator(
        OperatorAttributePattern.for_op_type(op_type), p_ins
    )
    og = OutputGraphExpr()
    o_ins = [og.add_input() for _ in range(num_inputs)]
    parts = []
    for oi in o_ins:
        _, (xp,) = og.add_operator(AttrConstant(RepartitionAttrs(dim, degree)), [oi])
        parts.append(xp)
    _, (y,) = og.add_operator(CopyAttrsFromMatched(pnode), parts)
    _, (out,) = og.add_operator(AttrConstant(CombineAttrs(dim, degree)), [y])
    return Substitution(
        f"data_parallel_{op_type.value}{_dim_tag(dim)}_{degree}",
        p,
        og,
        tuple(zip(p_ins, o_ins)),
        ((py, out),),
    )


def pipeline_stage_pair_rule(
    num_microbatches: int, use_bias: bool = False
) -> Substitution:
    """Linear(Linear(a, w1), w2) ->
    StageMerge(Linear(StagePartition_1(Linear(StagePartition_0(a), w1)),
    w2)) with S=2 stages and M=`num_microbatches` microbatches (ISSUE 13):
    the minimal substitution that INTRODUCES the pipeline-stage ops, so
    the rewrite walk can cut a chain incrementally and — satellite — so
    the rule auditor exercises stage ops like every other registered rule
    (stage ops are value-identity, so the audited interface shapes are
    unchanged by construction)."""
    from flexflow_tpu.op_attrs.ops import (
        StageMergeAttrs,
        StagePartitionAttrs,
    )

    M = int(num_microbatches)
    p = PCGPattern()
    a = p.add_input(_shard_pattern(0, M))
    w1 = p.add_input()
    w2 = p.add_input()
    b1 = [p.add_input()] if use_bias else []
    b2 = [p.add_input()] if use_bias else []
    lin = OperatorAttributePattern.for_op_type(
        OperatorType.LINEAR, use_bias=use_bias, weight_transposed=False
    )
    n1, (h,) = p.add_operator(lin, [a, w1, *b1])
    n2, (y,) = p.add_operator(lin, [h, w2, *b2])

    og = OutputGraphExpr()
    oa = og.add_input()
    ow1 = og.add_input()
    ow2 = og.add_input()
    ob1 = [og.add_input() for _ in b1]
    ob2 = [og.add_input() for _ in b2]
    _, (sp0,) = og.add_operator(
        AttrConstant(StagePartitionAttrs(2, M, 0)), [oa]
    )
    _, (h1,) = og.add_operator(CopyAttrsFromMatched(n1), [sp0, ow1, *ob1])
    _, (sp1,) = og.add_operator(
        AttrConstant(StagePartitionAttrs(2, M, 1)), [h1]
    )
    _, (y2,) = og.add_operator(CopyAttrsFromMatched(n2), [sp1, ow2, *ob2])
    _, (out,) = og.add_operator(AttrConstant(StageMergeAttrs(2, M)), [y2])
    return Substitution(
        f"pipeline_stage_pair_{'b_' if use_bias else ''}{M}",
        p,
        og,
        ((a, oa), (w1, ow1), (w2, ow2), *zip(b1, ob1), *zip(b2, ob2)),
        ((y, out),),
    )


def combine_reduction_cancel_rules(degree: int, dim: int) -> List[Substitution]:
    """Resharding cancellation: Combine_d(k) . Repartition_d(k) -> Noop and
    Repartition_d(k) . Combine_d(k) -> Noop. These erase the redundant
    resharding pairs the per-op rules introduce at their seams, letting
    parallelism PROPAGATE through chains of ops (the TASO-style closure)."""
    out: List[Substitution] = []

    def mk(first_attrs, second_attrs, tag):
        p = PCGPattern()
        x = p.add_input()
        n1, (mid,) = p.add_operator(
            OperatorAttributePattern.for_op_type(
                first_attrs[0], **first_attrs[1]
            ),
            [x],
        )
        n2, (y,) = p.add_operator(
            OperatorAttributePattern.for_op_type(
                second_attrs[0], **second_attrs[1]
            ),
            [mid],
        )
        og = OutputGraphExpr()
        ox = og.add_input()
        _, (oy,) = og.add_operator(AttrConstant(NoopAttrs()), [ox])
        return Substitution(
            f"{tag}_{dim}_{degree}", p, og, ((x, ox),), ((y, oy),)
        )

    out.append(
        mk(
            (OperatorType.COMBINE, dict(combine_dim=dim, combine_degree=degree)),
            (
                OperatorType.REPARTITION,
                dict(repartition_dim=dim, repartition_degree=degree),
            ),
            "cancel_combine_repartition",
        )
    )
    out.append(
        mk(
            (
                OperatorType.REPARTITION,
                dict(repartition_dim=dim, repartition_degree=degree),
            ),
            (OperatorType.COMBINE, dict(combine_dim=dim, combine_degree=degree)),
            "cancel_repartition_combine",
        )
    )
    return out


def generate_parallelization_rules(
    degrees: List[int],
    max_cancel_dim: int = 3,
    enable_parameter_parallel: bool = True,
    enable_attribute_parallel: bool = True,
    enable_pipeline: bool = False,
    pipeline_microbatches: int = 0,
) -> List[Substitution]:
    """The seed rule set for a machine whose interesting parallel degrees are
    `degrees` (typically divisors of the chip count).

    `enable_parameter_parallel` gates the weight-partitioning rules and
    `enable_attribute_parallel` the reduction-dim rules, mirroring the
    reference's --enable-parameter-parallel / --enable-attribute-parallel
    flags (config.h); data/sample parallelism is always available."""
    rules: List[Substitution] = []
    for k in degrees:
        if k < 2:
            continue
        for use_bias in (True, False):
            rules.append(data_parallel_linear_rule(k, use_bias))
            rules.append(data_parallel_conv2d_rule(k, use_bias))
        rules.append(data_parallel_embedding_rule(k))
        rules.append(data_parallel_batch_norm_rule(k))
        for bias in (False, True):
            rules.append(data_parallel_attention_rule(k, bias))
        for bias in (False, True):
            rules.append(
                data_parallel_attention_rule(
                    k, bias, op_type=OperatorType.RING_ATTENTION
                )
            )
        for op_type in (
            OperatorType.MULTIHEAD_ATTENTION, OperatorType.RING_ATTENTION,
        ):
            rules.append(
                data_parallel_attention_rule(
                    k, False, qk_norm=True, op_type=op_type
                )
            )
            rules.append(
                data_parallel_attention_rule(
                    k, False, op_type=op_type, latent=True
                )
            )
            rules.append(
                data_parallel_attention_rule(
                    k, False, op_type=op_type, latent=True, q_latent=True
                )
            )
        rules.append(data_parallel_label_loss_rule(k))
        rules.append(data_parallel_layer_norm_rule(k))
        rules.append(data_parallel_rms_norm_rule(k))
        rules.append(data_parallel_state_space_rule(k))
        for decay in ("channel", "head"):
            rules.append(
                data_parallel_state_space_rule(
                    k, OperatorType.GATED_DELTA, decay
                )
            )
        rules.append(
            data_parallel_state_space_rule(k, OperatorType.SHORT_CONV)
        )
        for memory in (False, True):
            rules.append(data_parallel_state_space_rule(
                k, OperatorType.SELECTIVE_SCAN, memory_output=memory
            ))
        rules.append(sequence_parallel_attention_rule(k))
        rules.append(sequence_parallel_attention_a2a_rule(k))
        # sequence-axis (dim=1) variants: the seq-parallel residual stream's
        # non-attention segments (Linear/LayerNorm/elementwise ride the
        # sharded seq dim; attention itself needs the ring/a2a rules above)
        for use_bias in (True, False):
            rules.append(data_parallel_linear_rule(k, use_bias, dim=1))
        rules.append(data_parallel_layer_norm_rule(k, dim=1))
        rules.append(data_parallel_rms_norm_rule(k, dim=1))
        rules.append(data_parallel_op_rule(OperatorType.ELEMENT_UNARY, k, dim=1))
        rules.append(
            data_parallel_op_rule(
                OperatorType.ELEMENT_BINARY, k, num_inputs=2, dim=1
            )
        )
        rules.append(data_parallel_op_rule(OperatorType.DROPOUT, k, dim=1))
        # channel-axis (dim=-1) variants: keep activations sharded between
        # tensor-parallel linears (Megatron's activation segment)
        rules.append(data_parallel_op_rule(OperatorType.ELEMENT_UNARY, k, dim=-1))
        rules.append(
            data_parallel_op_rule(
                OperatorType.ELEMENT_BINARY, k, num_inputs=2, dim=-1
            )
        )
        for use_bias in (True, False):
            rules.append(expert_parallel_experts_rule(k, use_bias))
            rules.append(expert_parallel_experts_rule(k, use_bias, with_aux=True))
        for with_aux in (False, True):
            rules.append(
                expert_parallel_experts_rule(k, False, with_aux, gated=True)
            )
            for use_bias, gated in ((True, False), (False, False), (False, True)):
                rules.append(
                    data_parallel_experts_rule(k, use_bias, gated, with_aux)
                )
        # the bias-balanced form: a selection bias and a shared expert
        rules.append(data_parallel_experts_rule(k, False, shared=True))
        # and the same with its experts in a latent space
        rules.append(
            data_parallel_experts_rule(k, False, shared=True, latent=True)
        )
        # the softmax-routed gated form beside a gated shared expert
        rules.append(
            data_parallel_experts_rule(k, False, gated=True, shared_gate=True)
        )
        # branch parallelism over stacked isomorphic branches
        # (compiler/branch_stacking.py): shard the stacked leading axis,
        # merge via local sum + Reduction
        rules.append(branch_parallel_bmm_rule(k))
        rules.append(bmm_batch_parallel_rule(k))
        rules.append(branch_reduce_sum_rule(k))
        rules.append(data_parallel_op_rule(OperatorType.BROADCAST, k))
        if enable_parameter_parallel:
            for use_bias in (True, False):
                rules.append(tensor_parallel_linear_rule(k, use_bias))
            rules.append(head_parallel_attention_rule(k))
            for use_bias in (True, False):
                rules.append(channel_parallel_conv2d_rule(k, use_bias))
                rules.append(
                    channel_parallel_conv2d_rule(k, use_bias, grouped=True)
                )
            rules.append(column_parallel_embedding_rule(k))
        if enable_attribute_parallel:
            rules.append(reduction_parallel_linear_rule(k))
            rules.append(reduction_parallel_conv2d_rule(k))
        for op_type in (
            OperatorType.ELEMENT_UNARY,
            OperatorType.SOFTMAX,
            OperatorType.POOL2D,
            OperatorType.FLAT,
            OperatorType.DROPOUT,
        ):
            rules.append(data_parallel_op_rule(op_type, k))
        rules.append(
            data_parallel_op_rule(OperatorType.ELEMENT_BINARY, k, num_inputs=2)
        )
        for arity in (2, 3, 4):
            rules.append(data_parallel_concat_rule(k, arity))
        for d in (*range(max_cancel_dim), -1):
            rules.extend(combine_reduction_cancel_rules(k, d))
    if enable_pipeline:
        # stage-partitioning moves (ISSUE 13, --pipeline only so flat
        # searches keep their pinned rule counts/winners): the rewrite walk
        # can cut a two-linear chain into a 2-stage region incrementally;
        # the coherent whole-chain cuts come from the pipeline seeds
        for M in sorted({pipeline_microbatches or 4, 2}):
            if M >= 2:
                for use_bias in (False, True):
                    rules.append(pipeline_stage_pair_rule(M, use_bias))
    return rules
