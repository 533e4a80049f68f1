"""MultiHeadAttention attrs + shape inference.

Reference: op-attrs/ops/attention.h + src/op-attrs/ops/attention.cc.
Inputs q/k/v are [batch, seq, channel] (ff dims -3,-2,-1). Head parallelism is
driven by the inputs' discard_copy_degree: replicated inputs let each replica
compute a slice of heads, whose W^O contributions are partial sums -> the
output has sum_degree = input discard_copy_degree (attention.cc:320-353).

The reference's cuDNN MHA kernel requires the sequence dim unsharded
(attention.cc:78-84 prefill note); this build keeps that PCG-level rule for
the MHA op and adds sequence parallelism as a separate RingAttention op
(ring collective-permute over the ICI mesh; see kernels/ring_attention).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    get_reduced_shape,
    lift_to_parallel_with_degrees,
)


@dataclass(frozen=True)
class YarnScaling:
    """YaRN (arXiv:2309.00071) as `rope_type` `yarn` is computed in the
    public `transformers` code, under its published keys. With f_j =
    theta^(-2j / width) and c(r) = width * ln(original_max_position_embeddings
    / (2 pi r)) / (2 ln theta), `low` = floor(c(beta_fast)) and `high` =
    ceil(c(beta_slow)), clipped to [0, width - 1]: pair j turns at
    f_j * (1 - ramp_j) + (f_j / factor) * ramp_j, ramp_j =
    clip((j - low) / (high - low), 0, 1): the fast pairs below `low` as they
    did, the slow ones from `high` on `factor` times slower, a line between.
    Cosine and sine are both multiplied by `attention_factor` (None:
    0.1 ln(factor) + 1), on q and on k, so the scores carry its square.
    ONE frozen value on the node and not five fields: the five mean nothing
    apart, rules match its absence (`rope_scaling=None`) in one place, and
    another scaling is another value's class."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def __post_init__(self):
        assert self.factor >= 1.0 and self.beta_fast >= self.beta_slow > 0, self
        assert self.original_max_position_embeddings > 0, self

    @property
    def amplitude(self) -> float:
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 0.1 * math.log(self.factor) + 1.0

    def correction_range(self, theta: float, width: int):
        """(low, high): the first pair the ramp touches and the first it
        leaves `factor` times slower, for a rotary `width` columns wide."""

        def c(rotations):
            return width * math.log(
                self.original_max_position_embeddings
                / (rotations * 2.0 * math.pi)
            ) / (2.0 * math.log(theta))

        low = max(math.floor(c(self.beta_fast)), 0)
        high = min(math.ceil(c(self.beta_slow)), width - 1)
        return low, high

    def describe(self, theta: float, width: int) -> str:
        low, high = self.correction_range(theta, width)
        return (
            f"yarn factor={self.factor:g} low={low} high={high} "
            f"amp={self.amplitude:.4f}"
        )


@dataclass(frozen=True)
class MultiHeadAttentionAttrs:
    embed_dim: int
    num_heads: int
    kdim: int = 0  # 0 -> embed_dim / num_heads
    vdim: int = 0
    dropout: float = 0.0
    bias: bool = False
    add_bias_kv: bool = False
    add_zero_attn: bool = False
    # What happens between the projections and the attention core is the
    # op's, because the op owns its projections (ROADMAP D10). Both default
    # to None: a graph without them has the weight slots, rule matches and
    # lowered program it always had.
    # rope_theta: rotary position embedding on q and k, positions 0..s-1,
    # rotate-half pairing (i, i + d/2) within each head, angle
    # pos * theta^(-2j/d).
    rope_theta: Optional[float] = None
    # qk_norm_eps: RMS norm of the projected q and of the projected k over
    # ALL heads' features (the whole [h*d] row, before the split into heads
    # and before RoPE), each with a gain [h*d]: two more weight slots after
    # the biases, q's then k's.
    qk_norm_eps: Optional[float] = None
    # num_kv_heads: grouped-query attention. None is one key/value head a
    # query head, the op as it always was. With fewer, query head h reads
    # key/value head h // (num_heads / num_kv_heads), and the weight is one
    # flat column [wq | wk | wv | wo, 1] holding wq [e, h*d], wk and wv
    # [e, kv*d] (the published, smaller projections) and wo [h*d, e]: a
    # per-head column as in the equal-head layout has no place for a
    # key/value head that several query heads share.
    num_kv_heads: Optional[int] = None
    # kv_latent_rank: latent attention (multi-head latent attention with no
    # position encoding, as `kimi_linear` with `mla_use_nope` writes it). The
    # keys and values come from ONE low-rank row: [c | k_s] = x W_kv_a
    # ([e, rank + shared_key_dim]), [k_n | v] = rms_norm(c; gain [rank])
    # W_kv_b ([rank, h * (kdim - shared_key_dim + vdim)], head-major columns,
    # a head's k_n then its v), and head h's key is [k_n^h | k_s]: its last
    # `shared_key_dim` columns are one slice shared by all the heads. `kdim`
    # is the whole key (and query) width, so kdim != vdim is allowed here.
    # The weight is one flat column [wq | wkv_a | wkv_b | wo, 1] (wq
    # [e, h * kdim], wo [h * vdim, e]), and the latent norm's gain is one
    # more weight slot after it. No bias, QK-norm, output gate or grouped
    # heads. `rope_theta` beside it turns the SHARED slice, once a position
    # before it is broadcast over the heads, and the LAST `shared_key_dim`
    # columns of each head's query (the `deepseek_v3` family's decoupled
    # rotary; `rotary_dim` stays None: the slice's width is the rotary's).
    kv_latent_rank: Optional[int] = None
    shared_key_dim: int = 0
    kv_latent_norm_eps: float = 1e-5
    # qk_norm_per_head: with `qk_norm_eps`, the RMS norm is taken over EACH
    # head's own `kdim` features (q's heads and the key heads alike, before
    # RoPE), with one gain [kdim] for q and one for k that every head
    # shares. The two weight slots are where the whole-row form has them;
    # this form has a row for a key/value head that several query heads
    # share, so it is the one grouped-query heads take.
    qk_norm_per_head: bool = False
    # rotary_dim: with `rope_theta`, the rotary turns only the first
    # `rotary_dim` columns of each head (`partial_rotary_factor` times the
    # head size): pairs (j, j + rotary_dim / 2), angle
    # pos * theta^(-2j / rotary_dim); the head's other columns pass. None
    # turns the whole head, the op as it was.
    rotary_dim: Optional[int] = None
    # output_gate: the query projection is [e, h * 2 * kdim]: a head's
    # 2 * kdim columns are its query, then a gate as wide as its value
    # (kdim == vdim); the context is multiplied by sigmoid(gate) before wo.
    # The flat weight (grouped-query layout: a row for a key/value head
    # that several query heads share) holds the wider wq where wq was.
    output_gate: bool = False
    # qk_norm_zero_centered: the two QK-norm weights start at ZERO and the
    # gains are 1 + w (`RMSNormAttrs.zero_centered`).
    qk_norm_zero_centered: bool = False
    # q_latent_rank: with `kv_latent_rank`, the query comes from a low-rank
    # row of its own: q = rms_norm(x W_q_a; gain [q rank]) W_q_b
    # (`q_lora_rank`). The flat weight holds wq_a [e, q rank] and wq_b
    # [q rank, h * kdim] where wq was, and the query norm's gain is one more
    # weight slot, the LAST (after the latent norm's).
    q_latent_rank: Optional[int] = None
    q_latent_norm_eps: float = 1e-5
    # rope_interleaved: with `rope_theta` on latent attention, the rotary
    # pairs columns (2j, 2j + 1) of the slice (`rope_interleave`), angle
    # pos * theta^(-2j / width); False pairs (j, j + width / 2), rotate-half.
    # The two differ by one fixed permutation of the slice's columns.
    rope_interleaved: bool = False
    # differential: differential attention (arXiv:2410.05258, as the
    # `phi4flash` model code pairs its heads). The `num_heads` query heads of
    # `kdim` pair up into num_heads / 2 differential heads, head j with the
    # queries (2j, 2j + 1); the `num_kv_heads` key/value heads pair up into
    # groups, group g with the keys (2g, 2g + 1) and ONE value
    # [v_2g | v_2g+1], 2 * vdim wide; head j reads group
    # j // (num_heads / num_kv_heads). Two softmax maps over that one value:
    #   a1 = softmax(q1 k1^T / sqrt(kdim) + mask) v, a2 likewise on q2, k2
    #   lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    #   o_j = (1 - lambda_init) * rms_norm(a1 - lambda a2; g, diff_norm_eps)
    # and the num_heads / 2 heads' 2 * vdim columns go through wo. The flat
    # weight is the grouped-query one ([wq | wk | wv | wo, 1]); with `bias`
    # the input bias is one FULL row [h * kdim + kv * kdim + kv * vdim] (a
    # column a projected feature, not one head's shared by all) and the
    # output bias [embed_dim]; after them come five more slots: lq1, lk1,
    # lq2, lk2 [kdim each] and the sub-norm's gain [2 * vdim]. No QK-norm,
    # rotary, gate or latent form beside it.
    differential: bool = False
    lambda_init: float = 0.8
    diff_norm_eps: float = 1e-5
    # window: with a causal mask, query t sees keys t - window + 1 .. t and
    # no others (a sliding window). None: every key before it. A route that
    # cannot honour it raises (`kernels/ops`): there is no silent full
    # attention. Differential nodes and plain causal ones, grouped or equal
    # heads (the causal tile schedule's band on "fused_row", a mask on
    # "dense"); latent nodes are refused here, a sequence shard by
    # `RingAttentionAttrs`' shape rule.
    window: Optional[int] = None
    # kv_outputs: the node's projected keys and values, as projected (bias
    # included, before any padding or repeat), are its SECOND and THIRD
    # outputs, [b, s, kv * kdim] and [b, s, kv * vdim].
    # external_kv: the node's second and third INPUTS are another node's
    # such outputs: it projects its query alone, its flat weight is
    # [wq | wo, 1] and its input bias [h * kdim]. Both differential only.
    kv_outputs: bool = False
    external_kv: bool = False
    # rope_scaling: with `rope_theta`, how the rotary's frequencies and
    # amplitude depart from theta^(-2j / width) and 1 (`YarnScaling`). A
    # field of the NODE, so that two layers of one graph turn differently.
    # None: the rotary as it always was. Plain nodes only (a latent node's
    # rotary on its shared slice takes none yet).
    rope_scaling: Optional[YarnScaling] = None
    # softmax_scale: what the scores q k^T are multiplied by before the
    # softmax. None is kdim ** -0.5, the op as it always was; a model under
    # muP multipliers states its own (`attention_multiplier` 0.015625 = 1/64
    # on heads of 64, not 1/8). A field of the node and no scaling of q in
    # the graph: every core `kernels/ops.mha_core_route` can name for a
    # plain node takes it where it took d ** -0.5 (the kernels fold it into
    # an operand, the dense form multiplies by it). Plain nodes only, causal
    # or not, grouped or equal heads: a latent or a differential node is
    # refused here until one asks (their cores name their own scales), and a
    # sequence shard by `RingAttentionAttrs`' shape rule.
    softmax_scale: Optional[float] = None

    def __post_init__(self):
        assert self.softmax_scale is None or self.softmax_scale > 0, (
            f"softmax_scale {self.softmax_scale} multiplies the scores: a "
            "positive number, or None for kdim ** -0.5"
        )
        assert self.softmax_scale is None or not (
            self.latent or self.differential
        ), (
            "a stated softmax_scale is carried by plain attention nodes: the "
            "latent core names the true key width's scale beside its padded "
            "key and the differential core scales two maps a head, and "
            "neither carries another through yet"
        )
        assert self.rotary_dim is None or (
            self.rope_theta is not None and self.rotary_dim % 2 == 0
            and 0 < self.rotary_dim <= self.q_proj_size
        ), f"rotary_dim {self.rotary_dim} needs rope_theta and an even width"
        assert self.rotary_dim is None or self.kv_latent_rank is None, (
            "latent attention's rotary is as wide as the shared key slice"
        )
        assert not self.qk_norm_zero_centered or self.qk_norm, (
            "qk_norm_zero_centered says how qk_norm_eps norms: it needs one"
        )
        if self.output_gate:
            assert self.num_kv_heads is not None and not self.bias, (
                "the output gate lives in the grouped-query weight layout "
                "(num_kv_heads, equal to num_heads where no head is shared) "
                "and takes no bias"
            )
            assert self.q_proj_size == self.v_proj_size, (
                "a head's gate is as wide as its value, and lies beside its "
                "query: kdim == vdim"
            )
        if self.kv_latent_rank is not None:
            assert not (
                self.bias or self.qk_norm or self.num_kv_heads is not None
                or self.output_gate
            ), "latent attention takes no bias, QK-norm, gate or grouped heads"
            assert self.kdim > self.shared_key_dim >= 0 and self.vdim > 0, (
                "latent attention names its key and value widths"
            )
            assert self.rope_theta is None or (
                self.shared_key_dim > 0 and self.shared_key_dim % 2 == 0
            ), (
                "latent attention's rotary turns the shared key slice and "
                "the query's matching columns: it needs an even shared_key_dim"
            )
        else:
            assert self.shared_key_dim == 0, (
                "a shared key slice comes with kv_latent_rank"
            )
            assert self.q_latent_rank is None and not self.rope_interleaved, (
                "q_latent_rank and rope_interleaved are latent attention's "
                "(kv_latent_rank)"
            )
        assert not self.rope_interleaved or self.rope_theta is not None, (
            "rope_interleaved says how rope_theta pairs columns: it needs one"
        )
        if self.num_kv_heads is not None:
            assert self.num_heads % self.num_kv_heads == 0, (
                f"{self.num_heads} query heads do not divide over "
                f"{self.num_kv_heads} key/value heads"
            )
            assert not self.qk_norm or self.qk_norm_per_head, (
                "QK-norm over the whole row has a gain [num_heads * kdim]: "
                "it has no row for a key/value head that "
                f"{self.num_heads // self.num_kv_heads} query heads share; "
                "grouped-query heads take qk_norm_per_head"
            )
        assert not self.qk_norm_per_head or self.qk_norm, (
            "qk_norm_per_head says how qk_norm_eps norms: it needs one"
        )
        if self.differential:
            assert self.num_kv_heads is not None and not (
                self.qk_norm or self.rope_theta is not None
                or self.output_gate or self.latent
            ), (
                "differential attention lives in the grouped-query weight "
                "layout (num_kv_heads) and takes no QK-norm, rotary, gate "
                "or latent form"
            )
            assert self.num_heads % 2 == 0 and self.num_kv_heads % 2 == 0, (
                "differential heads are pairs of query heads, and their "
                "groups pairs of key/value heads"
            )
            assert not (self.kv_outputs and self.external_kv), (
                "a node hands its own keys and values on, or reads another's"
            )
        else:
            assert not self.kv_outputs and not self.external_kv, (
                "kv_outputs and external_kv are differential nodes'"
            )
        assert self.window is None or self.window >= 1, self.window
        assert self.window is None or not self.latent, (
            "a window on latent attention is not lowered yet: its wide-key "
            "kernels and its dense form have no band"
        )
        assert self.rope_scaling is None or (
            self.rope_theta is not None and not self.latent
        ), (
            "rope_scaling says how rope_theta's frequencies are scaled on a "
            "plain node: it needs one, and latent attention takes none yet"
        )

    @property
    def scale(self) -> float:
        """What the scores are multiplied by: the stated `softmax_scale`,
        or kdim ** -0.5."""
        if self.softmax_scale is not None:
            return float(self.softmax_scale)
        return self.q_proj_size ** -0.5

    @property
    def grouped_query(self) -> bool:
        """Whether the op takes the grouped-query weight layout and path:
        fewer key/value heads than query heads, an output gate (whose
        wider wq has no place in the per-head column), or differential
        attention (whose pairs have none either)."""
        return self.num_kv_heads is not None and (
            self.num_kv_heads != self.num_heads or self.output_gate
            or self.differential
        )

    @property
    def q_columns(self) -> int:
        """Columns of a head in wq: its query, and its gate where it has."""
        return (2 if self.output_gate else 1) * self.q_proj_size

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads if self.grouped_query else self.num_heads

    @property
    def latent(self) -> bool:
        return self.kv_latent_rank is not None

    @property
    def own_key_dim(self) -> int:
        """A head's own key columns (latent attention: without the slice
        the heads share)."""
        return self.q_proj_size - self.shared_key_dim

    @property
    def qk_norm(self) -> bool:
        return self.qk_norm_eps is not None

    @property
    def q_proj_size(self) -> int:
        return self.kdim if self.kdim else self.embed_dim // self.num_heads

    @property
    def k_proj_size(self) -> int:
        return self.q_proj_size

    @property
    def v_proj_size(self) -> int:
        return self.vdim if self.vdim else self.embed_dim // self.num_heads

    def _check_inputs(self, q: TensorShape, k: TensorShape, v: TensorShape) -> None:
        assert q.num_dims == k.num_dims == v.num_dims == 3, "q/k/v must be [b, seq, c]"
        assert q.dims[0] == k.dims[0] == v.dims[0], "batch mismatch"
        assert k.dims[1] == v.dims[1], "kv seq mismatch"
        if self.external_kv:
            kv = self.num_kv_heads
            assert (k.dims[-1], v.dims[-1]) == (
                kv * self.k_proj_size, kv * self.v_proj_size
            ), (
                "external keys and values come as another node projected "
                f"them, [b, s, {kv} * {self.k_proj_size}] and "
                f"[b, s, {kv} * {self.v_proj_size}]; got {k.dims}, {v.dims}"
            )

    def output_shape(self, q: TensorShape, k: TensorShape, v: TensorShape) -> TensorShape:
        self._check_inputs(q, k, v)
        return TensorShape((q.dims[0], q.dims[1], self.embed_dim), q.dtype)

    def output_shapes(self, q: TensorShape, k: TensorShape, v: TensorShape):
        """[the output] and, with `kv_outputs`, the projected keys and
        values beside it."""
        out = self.output_shape(q, k, v)
        if not self.kv_outputs:
            return [out]
        kv = self.num_kv_heads
        return [
            out,
            TensorShape(k.dims[:2] + (kv * self.k_proj_size,), q.dtype),
            TensorShape(v.dims[:2] + (kv * self.v_proj_size,), q.dtype),
        ]

    @property
    def num_lambda_weights(self) -> int:
        """The weight slots differential attention adds after the biases:
        lq1, lk1, lq2, lk2 and the sub-norm's gain."""
        return 5 if self.differential else 0

    def lambda_weight_shapes(self, q: TensorShape):
        vec = TensorShape((self.q_proj_size,), q.dtype)
        return [vec] * 4 + [TensorShape((2 * self.v_proj_size,), q.dtype)]

    def weights_shape(self, q: TensorShape, k: TensorShape, v: TensorShape) -> TensorShape:
        """Flat per-head weight [wq+wk+wv+wo, num_heads]
        (reference attention.cc:136-170)."""
        self._check_inputs(q, k, v)
        if self.latent:
            h, rank = self.num_heads, self.kv_latent_rank
            qr = self.q_latent_rank
            flat = (
                (
                    q.dims[-1] * h * self.q_proj_size if qr is None
                    else q.dims[-1] * qr + qr * h * self.q_proj_size
                )
                + k.dims[-1] * (rank + self.shared_key_dim)
                + rank * h * (self.own_key_dim + self.v_proj_size)
                + h * self.v_proj_size * self.embed_dim
            )
            return TensorShape((flat, 1), q.dtype)
        if self.grouped_query:
            h, kv = self.num_heads, self.num_kv_heads
            flat = (
                q.dims[-1] * h * self.q_columns
                + (0 if self.external_kv else (
                    k.dims[-1] * kv * self.k_proj_size
                    + v.dims[-1] * kv * self.v_proj_size
                ))
                + h * self.v_proj_size * self.embed_dim
            )
            return TensorShape((flat, 1), q.dtype)
        per_head = (
            q.dims[-1] * self.q_proj_size
            + k.dims[-1] * self.k_proj_size
            + v.dims[-1] * self.v_proj_size
            + self.v_proj_size * self.embed_dim
        )
        return TensorShape((per_head, self.num_heads), q.dtype)

    def input_bias_shape(self, q: TensorShape, k: TensorShape, v: TensorShape) -> TensorShape:
        if self.differential:  # one column a projected feature
            kv = 0 if self.external_kv else self.num_kv_heads
            return TensorShape((
                self.num_heads * self.q_proj_size
                + kv * (self.k_proj_size + self.v_proj_size),
            ), q.dtype)
        return TensorShape(
            (self.q_proj_size + self.k_proj_size + self.v_proj_size,), q.dtype
        )

    def output_bias_shape(self, q: TensorShape, k: TensorShape, v: TensorShape) -> TensorShape:
        return TensorShape((self.embed_dim,), q.dtype)

    def qk_gain_shape(self, q: TensorShape, k: TensorShape, v: TensorShape) -> TensorShape:
        """One QK-norm gain (q's and k's have the same shape): the whole
        row's, or with `qk_norm_per_head` one head's."""
        heads = 1 if self.qk_norm_per_head else self.num_heads
        return TensorShape((heads * self.q_proj_size,), q.dtype)

    def latent_gain_shape(self, q: TensorShape) -> TensorShape:
        return TensorShape((self.kv_latent_rank,), q.dtype)

    def q_latent_gain_shape(self, q: TensorShape) -> TensorShape:
        return TensorShape((self.q_latent_rank,), q.dtype)

    # -- parallel ---------------------------------------------------------

    def _parse_parallel(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ):
        assert q.num_dims == k.num_dims == v.num_dims == 3
        for s in (q, k, v):
            assert s.shard_dim_at(-1).degree == 1, "channel dim must be unsharded"
            assert s.shard_dim_at(-2).degree == 1, (
                "MHA requires unsharded sequence; use RingAttention for "
                "sequence parallelism"
            )
            assert s.sum_degree == 1, "MHA over partial sums is invalid"
        assert (
            q.shard_dim_at(0).degree == k.shard_dim_at(0).degree == v.shard_dim_at(0).degree
        ), "q/k/v batch degrees disagree"
        assert (
            q.discard_copy_degree == k.discard_copy_degree == v.discard_copy_degree
        ), "q/k/v discard-copy degrees disagree"
        return q.shard_dim_at(0).degree, q.discard_copy_degree

    def parallel_output_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        batch_degree, head_degree = self._parse_parallel(q, k, v)
        self._check_qk_norm_heads(head_degree)
        unpar = self.output_shape(
            get_reduced_shape(q), get_reduced_shape(k), get_reduced_shape(v)
        )
        return lift_to_parallel_with_degrees(
            unpar, head_degree, 1, (batch_degree, 1, 1)
        )

    def parallel_output_shapes(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ):
        """`output_shapes` lifted: the keys and values a node hands on are
        sharded over the batch (and, for the ring subclass, the sequence) as
        its output is, and are no partial sum."""
        out = self.parallel_output_shape(q, k, v)
        unpar = self.output_shapes(
            get_reduced_shape(q), get_reduced_shape(k), get_reduced_shape(v)
        )
        degrees = out.shard_degrees()
        return [out] + [
            lift_to_parallel_with_degrees(t, 1, 1, degrees[:2] + (1,))
            for t in unpar[1:]
        ]

    def parallel_lambda_weight_shapes(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ):
        """The differential slots, replicated wherever the flat weight is."""
        copies = self.parallel_weights_shape(q, k, v).discard_copy_degree
        return [
            lift_to_parallel_with_degrees(t, 1, copies, (1,))
            for t in self.lambda_weight_shapes(get_reduced_shape(q))
        ]

    def _check_qk_norm_heads(self, head_degree: int) -> None:
        assert not ((self.grouped_query or self.latent) and head_degree > 1), (
            "grouped-query and latent attention keep their projections in "
            "one flat column: they cannot be head-parallel yet"
        )
        assert not (self.qk_norm and head_degree > 1), (
            "QK-norm takes its mean of squares over every head's features: "
            "a head shard would need the other shards' sums, so attention "
            "with qk_norm_eps cannot be head-parallel"
        )

    def parallel_weights_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        batch_degree, head_degree = self._parse_parallel(q, k, v)
        unpar = self.weights_shape(
            get_reduced_shape(q), get_reduced_shape(k), get_reduced_shape(v)
        )
        return lift_to_parallel_with_degrees(
            unpar, 1, batch_degree, (1, head_degree)
        )

    def _parallel_bias_shape(
        self,
        unpar_shape,
        q: ParallelTensorShape,
        k: ParallelTensorShape,
        v: ParallelTensorShape,
    ) -> ParallelTensorShape:
        """A bias is replicated over the batch shards like the weight
        (discard_copy_degree = batch degree, degree 1 keeps the serial
        shape)."""
        batch_degree, head_degree = self._parse_parallel(q, k, v)
        assert head_degree == 1, (
            "biased attention cannot be head-parallel: each head shard's "
            "output is a partial sum, so the output bias must be added once "
            "after the Reduction"
        )
        unpar = unpar_shape(
            get_reduced_shape(q), get_reduced_shape(k), get_reduced_shape(v)
        )
        return lift_to_parallel_with_degrees(unpar, 1, batch_degree, (1,))

    def parallel_input_bias_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        return self._parallel_bias_shape(self.input_bias_shape, q, k, v)

    def parallel_output_bias_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        return self._parallel_bias_shape(self.output_bias_shape, q, k, v)

    def parallel_qk_gain_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        """A QK-norm gain is replicated wherever the flat weight is (batch
        shards, and sequence shards for the ring subclass)."""
        unpar = self.qk_gain_shape(
            get_reduced_shape(q), get_reduced_shape(k), get_reduced_shape(v)
        )
        copies = self.parallel_weights_shape(q, k, v).discard_copy_degree
        return lift_to_parallel_with_degrees(unpar, 1, copies, (1,))

    def parallel_latent_gain_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        """The latent norm's gain, replicated wherever the flat weight is."""
        unpar = self.latent_gain_shape(get_reduced_shape(q))
        copies = self.parallel_weights_shape(q, k, v).discard_copy_degree
        return lift_to_parallel_with_degrees(unpar, 1, copies, (1,))

    def parallel_q_latent_gain_shape(
        self, q: ParallelTensorShape, k: ParallelTensorShape, v: ParallelTensorShape
    ) -> ParallelTensorShape:
        """The query norm's gain, replicated wherever the flat weight is."""
        unpar = self.q_latent_gain_shape(get_reduced_shape(q))
        copies = self.parallel_weights_shape(q, k, v).discard_copy_degree
        return lift_to_parallel_with_degrees(unpar, 1, copies, (1,))
