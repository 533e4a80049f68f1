"""StateSpace: the selective state-space mixer (Mamba-2, Dao & Gu,
arXiv:2405.21060, as the `nemotron_h` / `mamba2` model codes write it), one
node from the residual stream's normed input to its mixer output.

    zxbcdt = x W_in                      # [.., 2*inner + 2*groups*state + heads]
    z | xBC | dt = split(zxbcdt)         # inner | inner + 2*groups*state | heads
    xBC = silu(conv1d_causal_depthwise(xBC; w_conv, b_conv))   # kernel taps
    x | B | C = split(xBC)               # [heads, head_dim] | [groups, state] x 2
    dt = softplus(dt + dt_bias);  A = -exp(A_log)              # per head
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t               # [head_dim, state]
    y_t = H_t C_t + D x_t                                      # head h reads group h // (heads/groups)
    y = rms_norm_grouped(y * silu(z); gain, groups) W_out   # inner / groups features a statistic

inner = heads * head_dim. The recurrence is evaluated in chunks of
`chunk_size` positions (`kernels/ssm.py`), in one of two forms that
`kernels/ssm.scan_route` picks from the shapes, the backend and the trace:
Pallas kernels that keep a chunk in VMEM (a TPU, `chunk_size` and
`state_size` multiples of 128, heads of 64 or 128 whose group fills whole
128-lane tiles; a group of more than 1,024 columns as column blocks of
whole heads that read the group's one B and C), or XLA matmuls with a scan over the chunks' states
(everything else). Both keep only the scan's inputs for the backward and
recompute the chunk-boundary states there; the chunking and the choice of
form change the order of the floating-point sums and nothing else.

weights (slot order): in_proj [D, 2*inner + 2*groups*state + heads];
conv weight [conv_kernel, inner + 2*groups*state]; conv bias [that width];
dt_bias [heads]; A_log [heads]; D [heads]; norm gain [inner];
out_proj [inner, D]. No bias on the projections.

Parallel rule: batch and nothing else. The scan runs along the sequence and
every head reads the whole input row, so the sequence and feature dims stay
whole; weights are replicated over the batch shards. Head- or group-sharded
mixers are not expressed yet (ROADMAP, Reach, "What the system cannot run
yet" (5), where the gated delta-rule mixer's same gap is listed:
`op_attrs/ops/kda.py`).
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
class StateSpaceAttrs:
    num_heads: int
    head_dim: int
    state_size: int
    num_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128
    norm_eps: float = 1e-5

    def __post_init__(self):
        assert self.num_heads % self.num_groups == 0, (
            f"{self.num_heads} heads do not divide into {self.num_groups} groups"
        )

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Features the short convolution runs over: x, B and C."""
        return self.inner + 2 * self.num_groups * self.state_size

    @property
    def in_proj_width(self) -> int:
        return self.inner + self.conv_width + self.num_heads

    num_weights = 8

    def _check(self, input: TensorShape) -> None:
        assert input.num_dims == 3, "state-space input must be [batch, seq, channel]"

    def output_shape(self, input: TensorShape) -> TensorShape:
        self._check(input)
        return input

    def weight_shapes(self, input: TensorShape) -> List[TensorShape]:
        self._check(input)
        d, dt = input.dims[-1], input.dtype
        return [
            TensorShape((d, self.in_proj_width), dt),
            TensorShape((self.conv_kernel, self.conv_width), dt),
            TensorShape((self.conv_width,), dt),
            TensorShape((self.num_heads,), dt),
            TensorShape((self.num_heads,), dt),
            TensorShape((self.num_heads,), dt),
            TensorShape((self.inner,), dt),
            TensorShape((self.inner, d), dt),
        ]

    # -- parallel: the batch dim only --------------------------------------

    def _batch_degree(self, input: ParallelTensorShape) -> int:
        assert input.num_dims == 3
        assert input.shard_dim_at(-1).degree == 1, "channel dim must be unsharded"
        assert input.shard_dim_at(-2).degree == 1, (
            "the scan runs along the sequence: it must be unsharded"
        )
        assert input.sum_degree == 1, "state-space input must not be a partial sum"
        assert input.discard_copy_degree == 1, (
            "head-sharded state-space mixers are not expressed yet"
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
