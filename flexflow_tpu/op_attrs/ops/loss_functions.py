"""Loss function attrs (reference: op-attrs/ops/loss_functions/).

LossFunction enum: SCCE, CCE, MSE, MAE, IDENTITY
(loss_function.enum.toml); SCCE carries a replace-labels flag
(sparse_categorical_ce_loss_attrs.struct.toml).
"""

from __future__ import annotations

import enum
import math
from dataclasses import dataclass
from typing import Union

from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    get_reduced_shape,
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape


class LossFunction(enum.Enum):
    CATEGORICAL_CROSSENTROPY = "categorical_crossentropy"
    SPARSE_CATEGORICAL_CROSSENTROPY = "sparse_categorical_crossentropy"
    MEAN_SQUARED_ERROR = "mean_squared_error"
    MEAN_ABSOLUTE_ERROR = "mean_absolute_error"
    IDENTITY = "identity"
    # the step's loss is the sum of the graph's loss nodes and nothing else:
    # no term is taken on the returned logits (a looped model's expected
    # loss over its exits has no "main" exit)
    LOSS_NODES = "loss_nodes"


@dataclass(frozen=True)
class SparseCategoricalCrossEntropyLossAttrs:
    replace_labels: bool = False

    @property
    def loss_type(self) -> LossFunction:
        return LossFunction.SPARSE_CATEGORICAL_CROSSENTROPY


@dataclass(frozen=True)
class NonconfigurableLossAttrs:
    loss_type: LossFunction


LossAttrs = Union[SparseCategoricalCrossEntropyLossAttrs, NonconfigurableLossAttrs]


def loss_attrs_for(fn: LossFunction) -> LossAttrs:
    if fn == LossFunction.SPARSE_CATEGORICAL_CROSSENTROPY:
        return SparseCategoricalCrossEntropyLossAttrs()
    return NonconfigurableLossAttrs(fn)


@dataclass(frozen=True)
class LabelCrossEntropyAttrs:
    """A loss that is a NODE of the graph: the sparse categorical
    cross-entropy of logits [batch..., classes] against an integer label
    tensor of the graph [batch...] (an input like any other, so a step may
    carry more than one set of labels), mean over the positions whose label
    is not negative (a position without a target is given -1 and weighs
    nothing), times `weight`. The output is one scalar [1] that training adds
    to its loss as it does an expert node's auxiliary output
    (`aux_loss_tensors`; found structurally, `core.ffmodel._find_aux_outputs`):
    a multi-token-prediction module's loss is one such node.

    With `position_weights` the node reads a third input, a float tensor of
    the graph [batch...] with a weight a position: `weight * sum_i w_i CE_i /
    n` over the n positions with a label. The weights are differentiated
    like any tensor (a looped model's exit probabilities: the gate learns
    from the losses it weighs)."""

    weight: float = 1.0
    position_weights: bool = False

    def output_shape(
        self, logit: TensorShape, label: TensorShape, *weights: TensorShape
    ) -> TensorShape:
        assert logit.dims[:-1] == label.dims, (
            f"labels {label.dims} do not index the logits' positions "
            f"{logit.dims[:-1]}"
        )
        assert not label.dtype.is_floating, "labels are class indices"
        assert len(weights) == int(self.position_weights), (
            f"{len(weights)} weight tensor(s), position_weights "
            f"{self.position_weights}"
        )
        for w in weights:
            assert w.dims == label.dims and w.dtype.is_floating, (
                f"weights {w.dims} {w.dtype} are no float a position of "
                f"{label.dims}"
            )
        return TensorShape((1,), logit.dtype)

    def parallel_output_shape(
        self, logit: ParallelTensorShape, label: ParallelTensorShape,
        *weights: ParallelTensorShape,
    ) -> ParallelTensorShape:
        """Positions may be sharded (logits, labels and weights alike); the
        classes may not. Each shard's scalar is a partial sum of the whole
        mean: sum_degree is the product of the position degrees."""
        degrees = logit.shard_degrees()
        assert degrees[-1] == 1, "the class dim must be unsharded"
        for other in (label,) + weights:
            assert tuple(degrees[:-1]) == tuple(other.shard_degrees()), (
                "logits, labels and weights are sharded over their "
                "positions alike"
            )
            assert other.sum_degree == 1
            assert logit.discard_copy_degree == other.discard_copy_degree
        assert logit.sum_degree == 1
        unpar = self.output_shape(
            get_reduced_shape(logit), get_reduced_shape(label),
            *(get_reduced_shape(w) for w in weights),
        )
        return lift_to_parallel_with_degrees(
            unpar, math.prod(degrees[:-1]),
            logit.discard_copy_degree, (1,),
        )


@dataclass(frozen=True)
class MeanLossAttrs:
    """A loss node without labels: `weight` times the mean of a float tensor
    of the graph [batch...] (a regulariser built from the graph's own
    elementwise ops: the entropy of a looped model's exit distribution).
    One scalar [1], found and added to the training loss as a
    `LabelCrossEntropyAttrs` node's is, and named in
    `observability/trace.loss_terms()` by its scope."""

    weight: float = 1.0

    def output_shape(self, value: TensorShape) -> TensorShape:
        assert value.dtype.is_floating, "a loss term is a float tensor"
        return TensorShape((1,), value.dtype)

    def parallel_output_shape(
        self, value: ParallelTensorShape
    ) -> ParallelTensorShape:
        """Each shard's scalar is a partial sum of the whole mean."""
        assert value.sum_degree == 1
        return lift_to_parallel_with_degrees(
            self.output_shape(get_reduced_shape(value)),
            math.prod(value.shard_degrees()),
            value.discard_copy_degree, (1,),
        )
