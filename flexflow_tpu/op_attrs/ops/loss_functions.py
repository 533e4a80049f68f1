"""Loss function attrs (reference: op-attrs/ops/loss_functions/).

LossFunction enum: SCCE, CCE, MSE, MAE, IDENTITY
(loss_function.enum.toml); SCCE carries a replace-labels flag
(sparse_categorical_ce_loss_attrs.struct.toml).
"""

from __future__ import annotations

import enum
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
    a multi-token-prediction module's loss is one such node."""

    weight: float = 1.0

    def output_shape(self, logit: TensorShape, label: TensorShape) -> TensorShape:
        assert logit.dims[:-1] == label.dims, (
            f"labels {label.dims} do not index the logits' positions "
            f"{logit.dims[:-1]}"
        )
        assert not label.dtype.is_floating, "labels are class indices"
        return TensorShape((1,), logit.dtype)

    def parallel_output_shape(
        self, logit: ParallelTensorShape, label: ParallelTensorShape
    ) -> ParallelTensorShape:
        """Positions may be sharded (logits and labels alike); the classes
        may not. Each shard's scalar is a partial sum of the whole mean:
        sum_degree is the product of the position degrees."""
        degrees = logit.shard_degrees()
        assert degrees[-1] == 1, "the class dim must be unsharded"
        assert tuple(degrees[:-1]) == tuple(label.shard_degrees()), (
            "logits and labels are sharded over their positions alike"
        )
        assert logit.sum_degree == 1 and label.sum_degree == 1
        assert logit.discard_copy_degree == label.discard_copy_degree
        unpar = self.output_shape(
            get_reduced_shape(logit), get_reduced_shape(label)
        )
        shards = 1
        for d in degrees[:-1]:
            shards *= d
        return lift_to_parallel_with_degrees(
            unpar, shards, logit.discard_copy_degree, (1,)
        )
