"""Per-operator attrs + shape inference, grouped by family.

Reference: lib/op-attrs/include/op-attrs/ops/ (30 ops, listed in
pcg_operator_attrs.variant.toml; SURVEY.md §2.2). Every op provides sequential
(TensorShape) and parallel (ParallelTensorShape) output-shape inference; this
build also fills the rules the reference left NOT_IMPLEMENTED
(reshape/transpose/reverse/split/gather/topk/reduce parallel paths).
"""

from flexflow_tpu.op_attrs.ops.io import InputAttrs, WeightAttrs, NoopAttrs
from flexflow_tpu.op_attrs.ops.elementwise import (
    ElementUnaryAttrs,
    ElementBinaryAttrs,
    ElementBinaryOpType,
    ElementUnaryOpType,
    CastAttrs,
    BroadcastAttrs,
)
from flexflow_tpu.op_attrs.ops.linear_ops import (
    LinearAttrs,
    BatchMatmulAttrs,
    EmbeddingAttrs,
    AggregateSpec,
)
from flexflow_tpu.op_attrs.ops.conv_ops import (
    Conv2DAttrs,
    Pool2DAttrs,
    PoolOp,
    FlatAttrs,
    BatchNormAttrs,
)
from flexflow_tpu.op_attrs.ops.norm_ops import (
    LayerNormAttrs,
    RMSNormAttrs,
    SoftmaxAttrs,
    DropoutAttrs,
)
from flexflow_tpu.op_attrs.ops.attention import (
    MultiHeadAttentionAttrs,
    YarnScaling,
)
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
from flexflow_tpu.op_attrs.ops.loss_functions import (
    LossFunction,
    SparseCategoricalCrossEntropyLossAttrs,
    NonconfigurableLossAttrs,
    LossAttrs,
    LabelCrossEntropyAttrs,
    MeanLossAttrs,
)
from flexflow_tpu.op_attrs.ops.moe import (
    GroupByAttrs,
    AggregateAttrs,
    ExpertsAttrs,
    expert_capacity,
)
from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs
from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs
from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs
from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs
