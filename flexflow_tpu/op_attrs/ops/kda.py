"""GatedDelta: the gated delta-rule linear-attention mixer (the delta rule
of Yang et al., arXiv:2406.06484 / 2412.06464), one node from the residual
stream's normed input to its mixer output, as `StateSpaceAttrs` is. Two
published forms, told apart by `decay`:

**A log-decay for EVERY key channel and a low-rank sigmoid gate** (`decay`
"channel": Kimi Delta Attention, Kimi Linear, arXiv:2510.26692; as many key
heads as value heads):

    q~ | k~ | v~ | f | z | b = x W_in        # h*dk | h*dk | h*dv | rank | rank | h
    q, k, v = silu(conv1d_causal_depthwise(q~ | k~ | v~; w_conv))   # no bias
    q = q / ||q||_2 * dk^-0.5,  k = k / ||k||_2                      # per head
    g_t = -exp(A_log[h]) * softplus(f W_f + dt_bias)     # [h*dk], a log-decay a key channel
    beta_t = sigmoid(b)                                   # one a head
    S' = Diag(exp(g_t)) S_{t-1}                           # S [dk, dv] a head, S_0 = 0
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
    y = [ rms_norm_per_head(o; gain [dv]) * sigmoid(z W_g + b_g) ] W_out

**ONE log-decay a value head and a full-width SiLU gate** (`decay` "head":
Gated DeltaNet, arXiv:2412.06464, as `qwen3_next` writes it), with `num_key_heads` key (and query) heads serving `num_heads` value heads,
value head h reading key head h // (num_heads / num_key_heads); the state
is [dk, dv] a VALUE head:

    q~ | k~ | v~ | z = x W_in        # hk*dk | hk*dk | hv*dv | hv*dv
    b | a = x W_ba                   # hv | hv
    q, k, v as above (conv, SiLU, the two norms per KEY head)
    g_t[h] = -exp(A_log[h]) * softplus(a_t[h] + dt_bias[h])   # one a head
    beta_t[h] = sigmoid(b_t[h])
    the same recurrence with Diag(exp(g_t)) = exp(g_t[h]) I
    y = [ rms_norm_per_head(o; gain [dv]) * silu(z) ] W_out

The recurrence is evaluated in chunks of `chunk_size` positions
(`kernels/kda.py`): inside a chunk the WY / UT form (the inverse of a unit
lower-triangular [chunk, chunk] matrix a head), from chunk to chunk only the
[dk, dv] states. `kernels/kda.scan_route` picks ONE route for the chunks'
operands and the chunk-to-chunk pass alike, from the shapes and the backend:
Pallas kernels on a TPU at lane-sized heads (each `decay` has its own pair
for the operands, `kda_prep_fwd` / `kda_prep_bwd` and `gdn_prep_fwd` /
`gdn_prep_bwd`; the inverse's and the pass's kernels serve both), XLA's
products and a `lax.scan` over the chunks everywhere else;
`kernels/kda.operand_form` names which a node took. The chunking and the
choice of form change the order of the floating-point sums and nothing else.

weights (slot order), the "channel" form: in_proj [D, 2*h*dk + h*dv + 2*rank
+ h]; conv weight [conv_kernel, 2*h*dk + h*dv]; decay up-projection W_f
[rank, h*dk]; dt_bias [h*dk]; A_log [h]; gate up-projection W_g
[rank, h*dv]; gate bias [h*dv]; norm gain [dv]; out_proj [h*dv, D]. The
"head" form: in_proj [D, 2*hk*dk + 2*hv*dv]; W_ba [D, 2*hv]; conv weight
[conv_kernel, 2*hk*dk + hv*dv]; dt_bias [hv]; A_log [hv]; norm gain [dv];
out_proj [hv*dv, D]. No bias on the projections or the convolution.

Parallel rule: batch and nothing else, as the state-space mixer
(`op_attrs/ops/ssm.py`). Head- or sequence-sharded delta-rule mixers are not
expressed yet (ROADMAP, Reach, "What the system cannot run yet" (5), the
same item).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    get_reduced_shape,
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape


@dataclass(frozen=True)
class GatedDeltaAttrs:
    num_heads: int
    key_dim: int  # a head's key (and query) size, the state's rows
    value_dim: int  # a head's value size, the state's columns
    conv_kernel: int = 4
    gate_rank: int = 128  # width of the two low-rank gates' down-projections
    chunk_size: int = 64
    norm_eps: float = 1e-5
    # key (and query) heads; None is one a value head (`num_heads`)
    num_key_heads: Optional[int] = None
    decay: str = "channel"  # a log-decay a key "channel", or one a "head"

    def __post_init__(self):
        q = self.chunk_size
        assert q > 0 and q & (q - 1) == 0, (
            f"chunk_size {q}: the decayed scores are built by halving the "
            "chunk (kernels/kda.decayed_scores), so it is a power of two"
        )
        assert self.decay in ("channel", "head"), (
            f"decay {self.decay!r}: the weight slots are written for the two "
            "published forms"
        )
        assert self.num_heads % self.key_heads == 0, (
            f"{self.num_heads} value heads do not divide over "
            f"{self.key_heads} key heads"
        )
        assert self.key_heads == self.num_heads or self.decay == "head", (
            "the per-channel form's kernels read a value head's own key "
            "block: fewer key heads come with decay='head'"
        )

    @property
    def key_heads(self) -> int:
        return self.num_key_heads or self.num_heads

    @property
    def per_head_decay(self) -> bool:
        return self.decay == "head"

    @property
    def key_width(self) -> int:
        return self.key_heads * self.key_dim

    @property
    def value_width(self) -> int:
        return self.num_heads * self.value_dim

    @property
    def conv_width(self) -> int:
        """Features the short convolution runs over: q, k and v."""
        return 2 * self.key_width + self.value_width

    @property
    def in_proj_width(self) -> int:
        if self.per_head_decay:  # q | k | v | z
            return self.conv_width + self.value_width
        return self.conv_width + 2 * self.gate_rank + self.num_heads

    @property
    def num_weights(self) -> int:
        return 7 if self.per_head_decay else 9

    def _check(self, input: TensorShape) -> None:
        assert input.num_dims == 3, "gated-delta input must be [batch, seq, channel]"

    def output_shape(self, input: TensorShape) -> TensorShape:
        self._check(input)
        return input

    def weight_shapes(self, input: TensorShape) -> List[TensorShape]:
        self._check(input)
        d, dt = input.dims[-1], input.dtype
        if self.per_head_decay:
            return [
                TensorShape((d, self.in_proj_width), dt),
                TensorShape((d, 2 * self.num_heads), dt),
                TensorShape((self.conv_kernel, self.conv_width), dt),
                TensorShape((self.num_heads,), dt),
                TensorShape((self.num_heads,), dt),
                TensorShape((self.value_dim,), dt),
                TensorShape((self.value_width, d), dt),
            ]
        return [
            TensorShape((d, self.in_proj_width), dt),
            TensorShape((self.conv_kernel, self.conv_width), dt),
            TensorShape((self.gate_rank, self.key_width), dt),
            TensorShape((self.key_width,), dt),
            TensorShape((self.num_heads,), dt),
            TensorShape((self.gate_rank, self.value_width), dt),
            TensorShape((self.value_width,), dt),
            TensorShape((self.value_dim,), dt),
            TensorShape((self.value_width, d), dt),
        ]

    # -- parallel: the batch dim only --------------------------------------

    def _batch_degree(self, input: ParallelTensorShape) -> int:
        assert input.num_dims == 3
        assert input.shard_dim_at(-1).degree == 1, "channel dim must be unsharded"
        assert input.shard_dim_at(-2).degree == 1, (
            "the recurrence runs along the sequence: it must be unsharded"
        )
        assert input.sum_degree == 1, "gated-delta input must not be a partial sum"
        assert input.discard_copy_degree == 1, (
            "head-sharded gated-delta mixers are not expressed yet"
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
