"""Operator type enum + uniform shape-inference dispatch.

Reference: op-attrs/operator_type.enum.toml, pcg_operator_attrs.variant.toml
(30-entry variant), computation_graph_op_attrs.variant.toml, and
incoming_tensor_role.enum.toml. The C++ variant types become a Python union of
attrs dataclasses dispatched by type.

Uniform signatures (shape inference works on *data* inputs; weight shapes are
derived separately, mirroring the reference where the builder creates weight
nodes from get_weight_shapes and IncomingTensorRole):

  get_output_shapes(attrs, inputs)            -> [TensorShape]
  get_weight_shapes(attrs, inputs)            -> [TensorShape]
  get_parallel_output_shapes(attrs, inputs)   -> [ParallelTensorShape]
  get_parallel_weight_shapes(attrs, inputs)   -> [ParallelTensorShape]
"""

from __future__ import annotations

import enum
from typing import Any, List, Sequence, Union

from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.op_attrs.parallel_tensor_shape import ParallelTensorShape
from flexflow_tpu.op_attrs.ops.io import InputAttrs, WeightAttrs, NoopAttrs
from flexflow_tpu.op_attrs.ops.elementwise import (
    ElementUnaryAttrs,
    ElementBinaryAttrs,
    CastAttrs,
    BroadcastAttrs,
)
from flexflow_tpu.op_attrs.ops.linear_ops import (
    LinearAttrs,
    BatchMatmulAttrs,
    EmbeddingAttrs,
)
from flexflow_tpu.op_attrs.ops.conv_ops import (
    Conv2DAttrs,
    Pool2DAttrs,
    FlatAttrs,
    BatchNormAttrs,
)
from flexflow_tpu.op_attrs.ops.norm_ops import (
    LayerNormAttrs,
    RMSNormAttrs,
    SoftmaxAttrs,
    DropoutAttrs,
)
from flexflow_tpu.op_attrs.ops.attention import MultiHeadAttentionAttrs
from flexflow_tpu.op_attrs.ops.ring_attention import RingAttentionAttrs
from flexflow_tpu.op_attrs.ops.ulysses_attention import UlyssesAttentionAttrs
from flexflow_tpu.op_attrs.ops.shape_ops import (
    ConcatAttrs,
    StackAttrs,
    SplitAttrs,
    ReshapeAttrs,
    TransposeAttrs,
    ReverseAttrs,
    GatherAttrs,
    TopKAttrs,
    ReduceAttrs,
)
from flexflow_tpu.op_attrs.ops.parallel_ops import (
    RepartitionAttrs,
    CombineAttrs,
    ReplicateAttrs,
    ReductionAttrs,
    StagePartitionAttrs,
    StageMergeAttrs,
)
from flexflow_tpu.op_attrs.ops.moe import (
    GroupByAttrs,
    AggregateAttrs,
    ExpertsAttrs,
)
from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs
from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs
from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs
from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs
from flexflow_tpu.op_attrs.ops.loss_functions import (
    LabelCrossEntropyAttrs,
    MeanLossAttrs,
)


# mixers and the experts op: their attrs list their own weight slots
# (`num_weights`, `weight_shapes`, `parallel_weight_shapes`)
_OWN_WEIGHT_LIST = (
    ExpertsAttrs, StateSpaceAttrs, GatedDeltaAttrs, ShortConvAttrs,
    SelectiveScanAttrs,
)


class OperatorType(enum.Enum):
    INPUT = "input"
    WEIGHT = "weight"
    NOOP = "noop"
    ELEMENT_UNARY = "element_unary"
    ELEMENT_BINARY = "element_binary"
    CAST = "cast"
    BROADCAST = "broadcast"
    LINEAR = "linear"
    BATCH_MATMUL = "batch_matmul"
    EMBEDDING = "embedding"
    CONV2D = "conv2d"
    POOL2D = "pool2d"
    FLAT = "flat"
    BATCH_NORM = "batch_norm"
    LAYER_NORM = "layer_norm"
    RMS_NORM = "rms_norm"
    SOFTMAX = "softmax"
    DROPOUT = "dropout"
    MULTIHEAD_ATTENTION = "multihead_attention"
    RING_ATTENTION = "ring_attention"  # NEW capability: sequence parallelism
    ULYSSES_ATTENTION = "ulysses_attention"  # NEW: all-to-all seq parallelism
    CONCAT = "concat"
    STACK = "stack"  # NEW: branch-stacking entry (shape_ops.StackAttrs)
    SPLIT = "split"
    RESHAPE = "reshape"
    TRANSPOSE = "transpose"
    REVERSE = "reverse"
    GATHER = "gather"
    TOPK = "topk"
    REDUCE = "reduce"
    GROUP_BY = "group_by"
    AGGREGATE = "aggregate"
    EXPERTS = "experts"  # fused tpu-native MoE FFN (expert parallelism)
    STATE_SPACE = "state_space"  # selective state-space mixer (chunked scan)
    GATED_DELTA = "gated_delta"  # gated delta-rule linear attention (chunked)
    SHORT_CONV = "short_conv"  # double-gated short-convolution mixer
    SELECTIVE_SCAN = "selective_scan"  # Mamba-1 mixer (a recurrence a channel)
    LABEL_LOSS = "label_loss"  # cross-entropy against a label tensor of the graph
    MEAN_LOSS = "mean_loss"  # the mean of a float tensor of the graph, as a loss term
    REPARTITION = "repartition"
    COMBINE = "combine"
    REPLICATE = "replicate"
    REDUCTION = "reduction"
    # pipeline-stage ops (ISSUE 13): temporal parallelism — NOT members of
    # PARALLEL_OP_TYPES (chain-normalization passes must never merge or
    # net-cancel a stage boundary the way they canonicalize reshard chains)
    STAGE_PARTITION = "stage_partition"
    STAGE_MERGE = "stage_merge"


class IncomingTensorRole(enum.Enum):
    INPUT = "input"
    WEIGHT = "weight"


OpAttrs = Union[
    InputAttrs, WeightAttrs, NoopAttrs,
    ElementUnaryAttrs, ElementBinaryAttrs, CastAttrs, BroadcastAttrs,
    LinearAttrs, BatchMatmulAttrs, EmbeddingAttrs,
    Conv2DAttrs, Pool2DAttrs, FlatAttrs, BatchNormAttrs,
    LayerNormAttrs, RMSNormAttrs, SoftmaxAttrs, DropoutAttrs,
    MultiHeadAttentionAttrs, RingAttentionAttrs, UlyssesAttentionAttrs,
    ConcatAttrs, StackAttrs, SplitAttrs, ReshapeAttrs, TransposeAttrs,
    ReverseAttrs, GatherAttrs, TopKAttrs, ReduceAttrs,
    GroupByAttrs, AggregateAttrs, ExpertsAttrs, StateSpaceAttrs,
    GatedDeltaAttrs, ShortConvAttrs, SelectiveScanAttrs,
    LabelCrossEntropyAttrs, MeanLossAttrs,
    RepartitionAttrs, CombineAttrs, ReplicateAttrs, ReductionAttrs,
    StagePartitionAttrs, StageMergeAttrs,
]

_OP_TYPE_BY_ATTRS = {
    InputAttrs: OperatorType.INPUT,
    WeightAttrs: OperatorType.WEIGHT,
    NoopAttrs: OperatorType.NOOP,
    ElementUnaryAttrs: OperatorType.ELEMENT_UNARY,
    ElementBinaryAttrs: OperatorType.ELEMENT_BINARY,
    CastAttrs: OperatorType.CAST,
    BroadcastAttrs: OperatorType.BROADCAST,
    LinearAttrs: OperatorType.LINEAR,
    BatchMatmulAttrs: OperatorType.BATCH_MATMUL,
    EmbeddingAttrs: OperatorType.EMBEDDING,
    Conv2DAttrs: OperatorType.CONV2D,
    Pool2DAttrs: OperatorType.POOL2D,
    FlatAttrs: OperatorType.FLAT,
    BatchNormAttrs: OperatorType.BATCH_NORM,
    LayerNormAttrs: OperatorType.LAYER_NORM,
    RMSNormAttrs: OperatorType.RMS_NORM,
    SoftmaxAttrs: OperatorType.SOFTMAX,
    DropoutAttrs: OperatorType.DROPOUT,
    MultiHeadAttentionAttrs: OperatorType.MULTIHEAD_ATTENTION,
    RingAttentionAttrs: OperatorType.RING_ATTENTION,
    UlyssesAttentionAttrs: OperatorType.ULYSSES_ATTENTION,
    ConcatAttrs: OperatorType.CONCAT,
    StackAttrs: OperatorType.STACK,
    SplitAttrs: OperatorType.SPLIT,
    ReshapeAttrs: OperatorType.RESHAPE,
    TransposeAttrs: OperatorType.TRANSPOSE,
    ReverseAttrs: OperatorType.REVERSE,
    GatherAttrs: OperatorType.GATHER,
    TopKAttrs: OperatorType.TOPK,
    ReduceAttrs: OperatorType.REDUCE,
    GroupByAttrs: OperatorType.GROUP_BY,
    AggregateAttrs: OperatorType.AGGREGATE,
    ExpertsAttrs: OperatorType.EXPERTS,
    StateSpaceAttrs: OperatorType.STATE_SPACE,
    GatedDeltaAttrs: OperatorType.GATED_DELTA,
    ShortConvAttrs: OperatorType.SHORT_CONV,
    SelectiveScanAttrs: OperatorType.SELECTIVE_SCAN,
    LabelCrossEntropyAttrs: OperatorType.LABEL_LOSS,
    MeanLossAttrs: OperatorType.MEAN_LOSS,
    RepartitionAttrs: OperatorType.REPARTITION,
    CombineAttrs: OperatorType.COMBINE,
    ReplicateAttrs: OperatorType.REPLICATE,
    ReductionAttrs: OperatorType.REDUCTION,
    StagePartitionAttrs: OperatorType.STAGE_PARTITION,
    StageMergeAttrs: OperatorType.STAGE_MERGE,
}

PARALLEL_OP_TYPES = frozenset(
    {
        OperatorType.REPARTITION,
        OperatorType.COMBINE,
        OperatorType.REPLICATE,
        OperatorType.REDUCTION,
    }
)


def op_type_of(attrs: OpAttrs) -> OperatorType:
    return _OP_TYPE_BY_ATTRS[type(attrs)]


STAGE_OP_TYPES = frozenset(
    {OperatorType.STAGE_PARTITION, OperatorType.STAGE_MERGE}
)


def is_parallel_op(attrs: OpAttrs) -> bool:
    return op_type_of(attrs) in PARALLEL_OP_TYPES


def is_stage_op(attrs: OpAttrs) -> bool:
    """Pipeline-stage boundary op (StagePartition/StageMerge)? Kept OUT of
    is_parallel_op on purpose: the reshard-chain normalizations
    (merge_parallel_chains / canonicalize_parallel_chains) collapse
    parallel-op chains by their net LAYOUT effect, and a stage boundary is
    layout-identity — they would silently erase the pipeline."""
    return op_type_of(attrs) in STAGE_OP_TYPES


def get_incoming_tensor_roles(attrs: OpAttrs) -> List[IncomingTensorRole]:
    """Role (INPUT vs WEIGHT) of each incoming tensor, in slot order
    (reference: get_linear_incoming_tensor_roles linear.cc:11-23,
    get_attention_incoming_tensor_roles attention.cc:95-108)."""
    I, W = IncomingTensorRole.INPUT, IncomingTensorRole.WEIGHT
    if isinstance(attrs, LinearAttrs):
        return [I, W, W] if attrs.use_bias else [I, W]
    if isinstance(attrs, Conv2DAttrs):
        return [I, W, W] if attrs.use_bias else [I, W]
    if isinstance(attrs, EmbeddingAttrs):
        return [I, W]
    if isinstance(attrs, MultiHeadAttentionAttrs):
        roles = [I, I, I, W]
        if attrs.bias:
            roles += [W, W]
        if attrs.qk_norm:
            roles += [W, W]
        if attrs.latent:
            roles += [W] * (1 + (attrs.q_latent_rank is not None))
        roles += [W] * attrs.num_lambda_weights
        return roles
    if isinstance(attrs, BatchNormAttrs):
        return [I, W, W] if attrs.affine else [I]
    if isinstance(attrs, LayerNormAttrs):
        return [I, W, W] if attrs.elementwise_affine else [I]
    if isinstance(attrs, RMSNormAttrs):
        return [I, W]
    if isinstance(attrs, _OWN_WEIGHT_LIST):
        return [I] + [W] * attrs.num_weights
    n = num_data_inputs(attrs)
    return [I] * n


def num_data_inputs(attrs: OpAttrs) -> int:
    if isinstance(attrs, (InputAttrs, WeightAttrs)):
        return 0
    if isinstance(attrs, LabelCrossEntropyAttrs):
        return 2 + attrs.position_weights
    if isinstance(attrs, (ElementBinaryAttrs, BatchMatmulAttrs, GatherAttrs)):
        return 2
    if isinstance(attrs, GroupByAttrs):
        return 2
    if isinstance(attrs, AggregateAttrs):
        return 2 + attrs.n
    if isinstance(attrs, MultiHeadAttentionAttrs):
        return 3
    if isinstance(attrs, (ConcatAttrs, StackAttrs)):
        return -1  # variadic
    return 1


def num_outputs(attrs: OpAttrs, inputs: Sequence[TensorShape] = ()) -> int:
    if isinstance(attrs, SplitAttrs):
        return len(attrs.sizes)
    if isinstance(attrs, TopKAttrs):
        return 2
    if isinstance(attrs, GroupByAttrs):
        return attrs.n_experts
    if isinstance(attrs, ExpertsAttrs):
        return 2 if attrs.has_aux else 1
    if isinstance(attrs, SelectiveScanAttrs):
        return 2 if attrs.memory_output else 1
    if isinstance(attrs, MultiHeadAttentionAttrs) and attrs.kv_outputs:
        return 3
    return 1


# ---------------------------------------------------------------------------
# Sequential shape inference
# ---------------------------------------------------------------------------


def get_output_shapes(
    attrs: OpAttrs, inputs: Sequence[TensorShape]
) -> List[TensorShape]:
    inputs = list(inputs)
    if isinstance(attrs, (InputAttrs, WeightAttrs)):
        assert not inputs
        return [attrs.output_shape()]
    if isinstance(attrs, SplitAttrs):
        return list(attrs.output_shapes(inputs[0]))
    if isinstance(attrs, TopKAttrs):
        return list(attrs.output_shapes(inputs[0]))
    if isinstance(attrs, GroupByAttrs):
        return list(attrs.output_shapes(inputs[0], inputs[1]))
    if isinstance(attrs, (ExpertsAttrs, SelectiveScanAttrs)):
        return list(attrs.output_shapes(inputs[0]))
    if isinstance(attrs, (RepartitionAttrs, CombineAttrs, ReplicateAttrs, ReductionAttrs)):
        # Parallel ops are identity on sequential shapes.
        return [inputs[0]]
    if isinstance(attrs, MultiHeadAttentionAttrs):
        return list(attrs.output_shapes(*inputs))
    return [attrs.output_shape(*inputs)]


def get_weight_shapes(
    attrs: OpAttrs, inputs: Sequence[TensorShape]
) -> List[TensorShape]:
    """Weight shapes in slot order (after the data inputs' role positions)."""
    inputs = list(inputs)
    if isinstance(attrs, LinearAttrs):
        ws = [attrs.projection_shape(inputs[0])]
        if attrs.use_bias:
            ws.append(attrs.bias_shape(inputs[0]))
        return ws
    if isinstance(attrs, Conv2DAttrs):
        ws = [attrs.kernel_shape(inputs[0])]
        if attrs.use_bias:
            ws.append(attrs.bias_shape(inputs[0]))
        return ws
    if isinstance(attrs, EmbeddingAttrs):
        return [attrs.weight_shape(inputs[0])]
    if isinstance(attrs, MultiHeadAttentionAttrs):
        q, k, v = inputs
        ws = [attrs.weights_shape(q, k, v)]
        if attrs.bias:
            ws += [attrs.input_bias_shape(q, k, v), attrs.output_bias_shape(q, k, v)]
        if attrs.qk_norm:
            ws += [attrs.qk_gain_shape(q, k, v)] * 2
        if attrs.latent:
            ws += [attrs.latent_gain_shape(q)]
            if attrs.q_latent_rank is not None:
                ws += [attrs.q_latent_gain_shape(q)]
        if attrs.differential:
            ws += attrs.lambda_weight_shapes(q)
        return ws
    if isinstance(attrs, BatchNormAttrs) and attrs.affine:
        return [attrs.gamma_shape(inputs[0]), attrs.beta_shape(inputs[0])]
    if isinstance(attrs, LayerNormAttrs) and attrs.elementwise_affine:
        return [attrs.gamma_shape(inputs[0]), attrs.beta_shape(inputs[0])]
    if isinstance(attrs, RMSNormAttrs):
        return [attrs.gamma_shape(inputs[0])]
    if isinstance(attrs, _OWN_WEIGHT_LIST):
        return list(attrs.weight_shapes(inputs[0]))
    return []


def get_default_weight_initializers(attrs: OpAttrs, num_weights: int):
    """Per-weight-slot default initializers (None = builder's generic default:
    glorot for matrices, zero for vectors). Norm scales (gamma) must start at
    one — the reference's batch_norm init_kernel fills gamma with 1
    (initializer_kernels + batch_norm_kernels.cu)."""
    from flexflow_tpu.pcg.initializer import (
        ConstantInitializerAttrs,
        ZeroInitializerAttrs,
    )

    if isinstance(attrs, (BatchNormAttrs, LayerNormAttrs)):
        return [ConstantInitializerAttrs(1.0), ZeroInitializerAttrs()][
            :num_weights
        ]
    if isinstance(attrs, RMSNormAttrs):
        # a zero-centred gain's weight starts at zero (a vector's default)
        one = None if attrs.zero_centered else ConstantInitializerAttrs(1.0)
        return [one][:num_weights]
    if isinstance(attrs, MultiHeadAttentionAttrs) and attrs.differential:
        from flexflow_tpu.pcg.initializer import NormInitializerAttrs

        # the four lambda vectors from N(0, 0.1), the sub-norm's gain one
        # (arXiv:2410.05258, section 2.1): the last five slots
        small = NormInitializerAttrs(seed=0, mean=0.0, stddev=0.1)
        return [None] * (num_weights - 5) + [small] * 4 + [
            ConstantInitializerAttrs(1.0)
        ]
    if isinstance(attrs, MultiHeadAttentionAttrs) and attrs.qk_norm:
        # the two QK-norm gains are the last two slots
        one = (
            None if attrs.qk_norm_zero_centered
            else ConstantInitializerAttrs(1.0)
        )
        return [None] * (num_weights - 2) + [one] * 2
    if isinstance(attrs, MultiHeadAttentionAttrs) and attrs.latent:
        # the latent norm's gain and, with a query rank, the query norm's
        # are the last slots
        gains = 1 + (attrs.q_latent_rank is not None)
        return (
            [None] * (num_weights - gains)
            + [ConstantInitializerAttrs(1.0)] * gains
        )
    if isinstance(attrs, StateSpaceAttrs):
        from flexflow_tpu.pcg.initializer import (
            InverseSoftplusLogUniformInitializerAttrs,
            LogOfUniformInitializerAttrs,
            UniformInitializerAttrs,
        )

        # the published Mamba-2 defaults: the convolution as torch's conv1d
        # (uniform in +-1/sqrt(taps)), dt log-uniform in [1e-3, 1e-1] held
        # over 1e-4 and stored through the inverse of softplus, A uniform
        # in [1, 16] stored as its log, D and the norm's gain one
        bound = float(attrs.conv_kernel) ** -0.5
        conv = UniformInitializerAttrs(min_val=-bound, max_val=bound)
        one = ConstantInitializerAttrs(1.0)
        return [
            None, conv, conv,
            InverseSoftplusLogUniformInitializerAttrs(1e-3, 1e-1, 1e-4),
            LogOfUniformInitializerAttrs(1.0, 16.0), one, one, None,
        ][:num_weights]
    if isinstance(attrs, GatedDeltaAttrs):
        from flexflow_tpu.pcg.initializer import (
            InverseSoftplusLogUniformInitializerAttrs,
            LogOfUniformInitializerAttrs,
            UniformInitializerAttrs,
        )

        # as the public KDA layer starts (recalled; `assumed` in the
        # benchmark's configuration): the convolution as torch's conv1d, the
        # decay's step log-uniform in [1e-3, 1e-1] a key channel through the
        # inverse of softplus, A uniform in [1, 16] a head stored as its
        # log, the gate's bias zero (a vector's own default), the gain one
        bound = float(attrs.conv_kernel) ** -0.5
        if attrs.per_head_decay:
            # as the public Gated DeltaNet layer starts (recalled;
            # `assumed`): dt_bias one, A uniform in (0, 16) a value head
            # stored as its log (the draw held at 1e-3, so that the log is
            # finite), the gain one
            return [
                None, None,
                UniformInitializerAttrs(min_val=-bound, max_val=bound),
                ConstantInitializerAttrs(1.0),
                LogOfUniformInitializerAttrs(1e-3, 16.0),
                ConstantInitializerAttrs(1.0), None,
            ][:num_weights]
        return [
            None, UniformInitializerAttrs(min_val=-bound, max_val=bound), None,
            InverseSoftplusLogUniformInitializerAttrs(1e-3, 1e-1, 1e-4),
            LogOfUniformInitializerAttrs(1.0, 16.0), None, None,
            ConstantInitializerAttrs(1.0), None,
        ][:num_weights]
    if isinstance(attrs, SelectiveScanAttrs):
        from flexflow_tpu.pcg.initializer import (
            InverseSoftplusLogUniformInitializerAttrs,
            LogOfColumnIndexInitializerAttrs,
            UniformInitializerAttrs,
        )

        # the Mamba-1 paper's start: the convolution as torch's conv1d
        # (`conv_silu`'s, as the other scan nodes start it), softplus(b_dt)
        # log-uniform in [1e-3, 1e-1] held over 1e-4, A[c, n] = -(n + 1)
        # stored as its log, D one. W_dt's (uniform in +-dt_rank^-0.5) needs
        # the rank, which the input's width may set: the builder names it
        bound = float(attrs.conv_kernel) ** -0.5
        conv = UniformInitializerAttrs(min_val=-bound, max_val=bound)
        return [
            None, conv, conv, None, None,
            InverseSoftplusLogUniformInitializerAttrs(1e-3, 1e-1, 1e-4),
            LogOfColumnIndexInitializerAttrs(), ConstantInitializerAttrs(1.0),
            None,
        ][:num_weights]
    if isinstance(attrs, ShortConvAttrs):
        from flexflow_tpu.pcg.initializer import UniformInitializerAttrs

        # the convolution as torch's conv1d starts a depthwise kernel
        bound = float(attrs.conv_kernel) ** -0.5
        return [
            None, UniformInitializerAttrs(min_val=-bound, max_val=bound), None,
        ][:num_weights]
    return [None] * num_weights


# ---------------------------------------------------------------------------
# Parallel shape inference
# ---------------------------------------------------------------------------


def get_parallel_output_shapes(
    attrs: OpAttrs, inputs: Sequence[ParallelTensorShape]
) -> List[ParallelTensorShape]:
    inputs = list(inputs)
    if isinstance(attrs, (InputAttrs, WeightAttrs)):
        assert not inputs
        return [attrs.parallel_output_shape()]
    if isinstance(attrs, SplitAttrs):
        return list(attrs.parallel_output_shapes(inputs[0]))
    if isinstance(attrs, TopKAttrs):
        return list(attrs.parallel_output_shapes(inputs[0]))
    if isinstance(attrs, GroupByAttrs):
        return list(attrs.parallel_output_shapes(inputs[0], inputs[1]))
    if isinstance(attrs, (ExpertsAttrs, SelectiveScanAttrs)):
        return list(attrs.parallel_output_shapes(inputs[0]))
    if isinstance(attrs, MultiHeadAttentionAttrs) and attrs.kv_outputs:
        return list(attrs.parallel_output_shapes(*inputs))
    return [attrs.parallel_output_shape(*inputs)]


def get_parallel_weight_shapes(
    attrs: OpAttrs, inputs: Sequence[ParallelTensorShape]
) -> List[ParallelTensorShape]:
    inputs = list(inputs)
    if isinstance(attrs, LinearAttrs):
        ws = [attrs.parallel_projection_shape(inputs[0])]
        if attrs.use_bias:
            ws.append(attrs.parallel_bias_shape(inputs[0]))
        return ws
    if isinstance(attrs, MultiHeadAttentionAttrs):
        q, k, v = inputs
        ws = [attrs.parallel_weights_shape(q, k, v)]
        if attrs.bias:
            ws += [
                attrs.parallel_input_bias_shape(q, k, v),
                attrs.parallel_output_bias_shape(q, k, v),
            ]
        if attrs.qk_norm:
            ws += [attrs.parallel_qk_gain_shape(q, k, v)] * 2
        if attrs.latent:
            ws += [attrs.parallel_latent_gain_shape(q, k, v)]
            if attrs.q_latent_rank is not None:
                ws += [attrs.parallel_q_latent_gain_shape(q, k, v)]
        if attrs.differential:
            ws += attrs.parallel_lambda_weight_shapes(q, k, v)
        return ws
    if isinstance(attrs, Conv2DAttrs):
        ws = [attrs.parallel_kernel_shape(inputs[0])]
        if attrs.use_bias:
            ws.append(attrs.parallel_bias_shape(inputs[0]))
        return ws
    if isinstance(attrs, EmbeddingAttrs):
        return [attrs.parallel_weight_shape(inputs[0])]
    if isinstance(attrs, BatchNormAttrs) and attrs.affine:
        g = attrs.parallel_gamma_shape(inputs[0])
        return [g, g]
    if isinstance(attrs, LayerNormAttrs) and attrs.elementwise_affine:
        g = attrs.parallel_gamma_shape(inputs[0])
        return [g, g]
    if isinstance(attrs, RMSNormAttrs):
        return [attrs.parallel_gamma_shape(inputs[0])]
    if isinstance(attrs, _OWN_WEIGHT_LIST):
        return list(attrs.parallel_weight_shapes(inputs[0]))
    return []
