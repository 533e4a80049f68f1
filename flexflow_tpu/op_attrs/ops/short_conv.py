"""ShortConv: the double-gated short-convolution mixer (the `conv` layers of
`lfm2` / `lfm2_moe`, as their model code writes them), one node from the
residual stream's normed input to its mixer output, as `StateSpaceAttrs` is.

    B | C | z = x W_in                 # three parts of `width` columns each
    g = B * z                          # the input gate
    c_t = sum_{j < taps} w_j * g_{t - (taps - 1) + j}   # depthwise, causal
    y = (C * c) W_out                  # the output gate, then the projection

No bias anywhere, no activation after the convolution, zeros before
position 0. `kernels/short_conv.py` lowers it with a written backward that
keeps the node's input and weights and recomputes the chain.

weights (slot order): in_proj [D, 3 * width]; conv weight [taps, width];
out_proj [width, D].

Parallel rule: batch and nothing else. The convolution reads the `taps - 1`
positions before its own, so a sequence shard would need a halo of that many
positions from the shard before it: no such exchange is expressed, and the
shape rule refuses a sharded sequence. A channel shard (column-parallel
`W_in`, the depthwise taps and gates a channel at a time, row-parallel
`W_out` behind a `Reduction`) is sound arithmetic but not expressed either:
`W_in` is ONE [D, 3 * width] matrix whose contiguous column shard is no
channel shard of its three parts, so it would take a [D, 3, width] layout or
three weights and a rule of its own (ROADMAP, Reach, "What the system cannot
run yet" (5), beside the state-space and delta-rule mixers' same gap).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    get_reduced_shape,
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape


@dataclass(frozen=True)
class ShortConvAttrs:
    width: int  # channels of the gates and the convolution
    conv_kernel: int = 3

    num_weights = 3

    def _check(self, input: TensorShape) -> None:
        assert input.num_dims == 3, "short-conv input must be [batch, seq, channel]"

    def output_shape(self, input: TensorShape) -> TensorShape:
        self._check(input)
        return input

    def weight_shapes(self, input: TensorShape) -> List[TensorShape]:
        self._check(input)
        d, dt = input.dims[-1], input.dtype
        return [
            TensorShape((d, 3 * self.width), dt),
            TensorShape((self.conv_kernel, self.width), dt),
            TensorShape((self.width, d), dt),
        ]

    # -- parallel: the batch dim only --------------------------------------

    def _batch_degree(self, input: ParallelTensorShape) -> int:
        assert input.num_dims == 3
        assert input.shard_dim_at(-1).degree == 1, "channel dim must be unsharded"
        assert input.shard_dim_at(-2).degree == 1, (
            f"the convolution reads the {self.conv_kernel - 1} positions "
            "before its own: a sequence shard needs a halo that is not "
            "expressed, so the sequence must be unsharded"
        )
        assert input.sum_degree == 1, "short-conv input must not be a partial sum"
        assert input.discard_copy_degree == 1, (
            "channel-sharded short-conv mixers are not expressed yet"
        )
        return input.shard_dim_at(0).degree

    def parallel_output_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        batch = self._batch_degree(input)
        unpar = self.output_shape(get_reduced_shape(input))
        return lift_to_parallel_with_degrees(unpar, 1, 1, (batch, 1, 1))

    def parallel_weight_shapes(
        self, input: ParallelTensorShape
    ) -> List[ParallelTensorShape]:
        batch = self._batch_degree(input)
        return [
            lift_to_parallel_with_degrees(w, 1, batch, (1,) * w.num_dims)
            for w in self.weight_shapes(get_reduced_shape(input))
        ]
