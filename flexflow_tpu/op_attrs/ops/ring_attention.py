"""RingAttention: sequence-parallel attention (NEW capability vs reference).

The reference has NO sequence/context parallelism (SURVEY.md §2.12: cuDNN MHA
is whole-sequence; `lib/op-attrs/src/op-attrs/ops/attention.cc:78-84` assumes
full seq per device). This op adds it the Unity way (SURVEY.md §5 design):
the sequence dim of q/k/v may carry a shard degree, and the kernel computes
exact blockwise-softmax attention by rotating K/V blocks around the mesh axis
ring with `lax.ppermute` (Ring Attention; on TPU the rotation rides ICI
neighbor links, overlapping with the per-block matmuls).

Weight layout is IDENTICAL to MultiHeadAttentionAttrs (flat
[per_head_params, num_heads], reference attention.cc:136-170) so the
MHA -> RingAttention substitution preserves trained weights verbatim.
"""

from __future__ import annotations

from dataclasses import dataclass

from flexflow_tpu.op_attrs.ops.attention import MultiHeadAttentionAttrs
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    get_reduced_shape,
    lift_to_parallel_with_degrees,
)


@dataclass(frozen=True)
class RingAttentionAttrs(MultiHeadAttentionAttrs):
    """MHA with a sequence-shardable parallel rule.

    causal=True applies a lower-triangular mask using GLOBAL sequence
    positions (each ring step knows which block offset it holds).
    """

    causal: bool = False

    # -- parallel: seq dim may be sharded --------------------------------

    def _parse_parallel_ring(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ):
        assert q.num_dims == k.num_dims == v.num_dims == 3
        for s in (q, k, v):
            assert s.shard_dim_at(-1).degree == 1, "channel dim must be unsharded"
            assert s.sum_degree == 1, "attention over partial sums is invalid"
        assert (
            q.shard_dim_at(0).degree == k.shard_dim_at(0).degree == v.shard_dim_at(0).degree
        ), "q/k/v batch degrees disagree"
        assert (
            q.shard_dim_at(1).degree == k.shard_dim_at(1).degree == v.shard_dim_at(1).degree
        ), "q/k/v sequence degrees disagree"
        assert (
            q.discard_copy_degree == k.discard_copy_degree == v.discard_copy_degree
        ), "q/k/v discard-copy degrees disagree"
        return (
            q.shard_dim_at(0).degree,
            q.shard_dim_at(1).degree,
            q.discard_copy_degree,
        )

    def parallel_output_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        batch_degree, seq_degree, head_degree = self._parse_parallel_ring(q, k, v)
        self._check_qk_norm_heads(head_degree)
        # a sequence shard would have to rotate by its GLOBAL positions, and
        # the ring and all-to-all schedules project per shard to [b, h, s, d]
        # without the fused-row step QK-norm and RoPE act on (ROADMAP R7)
        assert seq_degree == 1 or not (
            self.qk_norm or self.rope_theta is not None
        ), "attention with rope_theta or qk_norm_eps cannot be sequence-parallel yet"
        assert seq_degree == 1 or not (self.grouped_query or self.latent), (
            "grouped-query and latent attention cannot be sequence-parallel yet"
        )
        assert seq_degree == 1 or self.window is None, (
            "windowed attention cannot be sequence-parallel yet: a shard's "
            f"first queries see the {self.window} - 1 keys before it, a halo "
            "the ring and all-to-all schedules do not carry"
        )
        assert seq_degree == 1 or self.softmax_scale is None, (
            "attention with a stated softmax_scale cannot be sequence-"
            "parallel yet: the ring and all-to-all schedules scale their "
            "scores by d ** -0.5 themselves"
        )
        unpar = self.output_shape(
            get_reduced_shape(q), get_reduced_shape(k), get_reduced_shape(v)
        )
        return lift_to_parallel_with_degrees(
            unpar, head_degree, 1, (batch_degree, seq_degree, 1)
        )

    def parallel_weights_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        batch_degree, seq_degree, head_degree = self._parse_parallel_ring(q, k, v)
        unpar = self.weights_shape(
            get_reduced_shape(q), get_reduced_shape(k), get_reduced_shape(v)
        )
        # weights replicate across batch AND sequence shards; heads shard
        return lift_to_parallel_with_degrees(
            unpar, 1, batch_degree * seq_degree, (1, head_degree)
        )

    def _parse_parallel(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ):
        # (batch, head) for the inherited bias shapes, which follow the
        # batch degree alone: no template shards a biased attention over the
        # sequence yet (sequence_parallel_plan)
        batch_degree, _, head_degree = self._parse_parallel_ring(q, k, v)
        return batch_degree, head_degree
