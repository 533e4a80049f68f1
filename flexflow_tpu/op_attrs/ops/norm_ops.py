"""LayerNorm, RMSNorm, Softmax, Dropout.

Reference: op-attrs/ops/{layer_norm,softmax,dropout}.h. RMSNorm has no
reference counterpart: it is the norm of the decoders after 2023 (no mean, no
shift; Zhang & Sennrich, arXiv:1910.07467).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    get_reduced_shape,
    lift_to_parallel_with_degrees,
)


from math import prod as _prod


@dataclass(frozen=True)
class LayerNormAttrs:
    axes: Tuple[int, ...]  # normalized axes (non-negative ff indices)
    elementwise_affine: bool = True
    eps: float = 1e-5

    def output_shape(self, input: TensorShape) -> TensorShape:
        return input

    def gamma_shape(self, input: TensorShape) -> TensorShape:
        return TensorShape(
            tuple(input.dims[a] for a in self.axes), input.dtype
        )

    def beta_shape(self, input: TensorShape) -> TensorShape:
        return self.gamma_shape(input)

    def parallel_output_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        assert input.sum_degree == 1, "layernorm over partial sums is invalid"
        for a in self.axes:
            assert input.shard_dim_at(a).degree == 1, (
                f"normalized axis {a} must be unsharded"
            )
        return input

    def parallel_gamma_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        unpar = self.gamma_shape(get_reduced_shape(input))
        non_norm_degrees = _prod(
            d.degree
            for i, d in enumerate(input.dims.shard_dims)
            if i not in self.axes
        )
        return lift_to_parallel_with_degrees(
            unpar,
            1,
            non_norm_degrees * input.discard_copy_degree,
            (1,) * len(self.axes),
        )


@dataclass(frozen=True)
class RMSNormAttrs:
    """x * rsqrt(mean(x^2, last dim) + eps) * gain, the mean of squares
    accumulated in float32 whatever the compute dtype. One weight, the gain
    [channels], which starts at one. Always over the last dim: that is the
    only form the published decoders use, and it keeps every leading dim
    free to shard.

    `zero_centered`: the weight w starts at ZERO and the gain is 1 + w
    (`x * rsqrt(mean(x^2) + eps) * (1 + w)`). The same function of the
    gain, another parameter: a weight decay that is an L2 term on every
    parameter pulls w to zero and so the gain to ONE, where it pulls the
    plain form's gain to zero."""

    eps: float = 1e-5
    zero_centered: bool = False

    def output_shape(self, input: TensorShape) -> TensorShape:
        return input

    def gamma_shape(self, input: TensorShape) -> TensorShape:
        return TensorShape((input.dims[-1],), input.dtype)

    def parallel_output_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        assert input.sum_degree == 1, "rms norm over partial sums is invalid"
        assert input.shard_dim_at(-1).degree == 1, (
            "the normalized (last) dim must be unsharded"
        )
        return input

    def parallel_gamma_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        unpar = self.gamma_shape(get_reduced_shape(input))
        return lift_to_parallel_with_degrees(
            unpar,
            1,
            _prod(input.shard_degrees()) * input.discard_copy_degree,
            (1,),
        )


@dataclass(frozen=True)
class SoftmaxAttrs:
    dim: int = -1

    def output_shape(self, input: TensorShape) -> TensorShape:
        return input

    def parallel_output_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        assert input.sum_degree == 1, "softmax over partial sums is invalid"
        d = self.dim % input.num_dims
        assert input.shard_dim_at(d).degree == 1, "softmax dim must be unsharded"
        return input


@dataclass(frozen=True)
class DropoutAttrs:
    rate: float
    seed: int = 0

    def output_shape(self, input: TensorShape) -> TensorShape:
        return input

    def parallel_output_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        assert input.sum_degree == 1
        return input
