"""Per-op forward kernels as pure jittable JAX functions.

Reference: lib/kernels/include/kernels/*_kernels.h (init/forward/backward per
op; SURVEY.md §2.4). The TPU design collapses the reference's
init_kernel->PerDeviceState->forward_kernel protocol into stateless pure
functions: XLA compilation replaces cuDNN descriptor setup, and backward comes
from jax.vjp over the forward (numerically the analytic gradients the
reference hand-codes, produced by autodiff).

Uniform signature:
    forward(attrs, inputs, weights, *, train=False, rng=None) -> [outputs]
inputs/weights: lists of jnp arrays in slot order (roles from
op_attrs.get_incoming_tensor_roles).
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.kernels import context
from flexflow_tpu.op_attrs.core import OpAttrs
from flexflow_tpu.op_attrs.ops import (
    BatchMatmulAttrs,
    BatchNormAttrs,
    BroadcastAttrs,
    CastAttrs,
    ConcatAttrs,
    Conv2DAttrs,
    DropoutAttrs,
    ElementBinaryAttrs,
    ElementBinaryOpType,
    ElementUnaryAttrs,
    ElementUnaryOpType,
    EmbeddingAttrs,
    AggregateSpec,
    FlatAttrs,
    GatherAttrs,
    InputAttrs,
    LayerNormAttrs,
    LinearAttrs,
    RMSNormAttrs,
    MultiHeadAttentionAttrs,
    NoopAttrs,
    Pool2DAttrs,
    PoolOp,
    ReduceAttrs,
    RepartitionAttrs,
    CombineAttrs,
    ReplicateAttrs,
    ReductionAttrs,
    StagePartitionAttrs,
    StageMergeAttrs,
    ReshapeAttrs,
    ReverseAttrs,
    SoftmaxAttrs,
    SplitAttrs,
    StackAttrs,
    TopKAttrs,
    TransposeAttrs,
    WeightAttrs,
)
from flexflow_tpu.op_attrs.ops.shape_ops import ReduceOpType


def _apply_activation(activation, x):
    if activation is None:
        return x
    return activation.apply(x)


_UNARY_FNS = {
    ElementUnaryOpType.EXP: jnp.exp,
    ElementUnaryOpType.LOG: jnp.log,
    ElementUnaryOpType.SIN: jnp.sin,
    ElementUnaryOpType.COS: jnp.cos,
    ElementUnaryOpType.IDENTITY: lambda x: x,
    ElementUnaryOpType.RELU: jax.nn.relu,
    ElementUnaryOpType.SIGMOID: jax.nn.sigmoid,
    ElementUnaryOpType.TANH: jnp.tanh,
    ElementUnaryOpType.GELU: jax.nn.gelu,
    ElementUnaryOpType.SILU: jax.nn.silu,
    ElementUnaryOpType.ELU: jax.nn.elu,
    ElementUnaryOpType.RSQRT: lax.rsqrt,
    ElementUnaryOpType.SQRT: jnp.sqrt,
}

_BINARY_FNS = {
    ElementBinaryOpType.ADD: jnp.add,
    ElementBinaryOpType.SUB: jnp.subtract,
    ElementBinaryOpType.MUL: jnp.multiply,
    ElementBinaryOpType.DIV: jnp.divide,
    ElementBinaryOpType.MAX: jnp.maximum,
    ElementBinaryOpType.MIN: jnp.minimum,
    ElementBinaryOpType.POW: jnp.power,
}


def unpack_mha_weights(
    attrs: MultiHeadAttentionAttrs, qsize: int, ksize: int, vsize: int, weight
):
    """Split the reference's flat weight layout [per_head_params, num_heads]
    (attention.cc:136-170: wq|wk|wv|wo concatenated per head) into the four
    projection tensors."""
    H = attrs.num_heads
    kd, vd, e = attrs.q_proj_size, attrs.v_proj_size, attrs.embed_dim
    sizes = [qsize * kd, ksize * kd, vsize * vd, vd * e]
    offs = [0]
    for s in sizes:
        offs.append(offs[-1] + s)
    wq = weight[offs[0]:offs[1], :].reshape(qsize, kd, H)
    wk = weight[offs[1]:offs[2], :].reshape(ksize, kd, H)
    wv = weight[offs[2]:offs[3], :].reshape(vsize, vd, H)
    wo = weight[offs[3]:offs[4], :].reshape(vd, e, H)
    return wq, wk, wv, wo


def mha_project_qkv(attrs: MultiHeadAttentionAttrs, q, k, v, weight, input_bias=None):
    """q/k/v projections -> per-head tensors [b, h, s, d] plus wo."""
    wq, wk, wv, wo = unpack_mha_weights(
        attrs, q.shape[-1], k.shape[-1], v.shape[-1], weight
    )
    qp = jnp.einsum("bsq,qkh->bhsk", q, wq)
    kp = jnp.einsum("btq,qkh->bhtk", k, wk)
    vp = jnp.einsum("btq,qvh->bhtv", v, wv)
    if input_bias is not None:
        kd = attrs.q_proj_size
        qp = qp + input_bias[:kd][None, None, None, :]
        kp = kp + input_bias[kd : 2 * kd][None, None, None, :]
        vp = vp + input_bias[2 * kd :][None, None, None, :]
    return qp, kp, vp, wo


def _bshf_weights(attrs: MultiHeadAttentionAttrs, qsize, ksize, vsize, weight):
    """Projection weights rearranged for the seq-major fused-head layout:
    per-projection [e, h*d] (head-major columns) plus wo as [h*v, e]. The
    lane order here is THE invariant the bshf flash kernels index into —
    one definition shared by the three-matmul and fused-QKV paths."""
    wq, wk, wv, wo = unpack_mha_weights(attrs, qsize, ksize, vsize, weight)
    H = attrs.num_heads
    kd, vd, e = attrs.q_proj_size, attrs.v_proj_size, attrs.embed_dim
    wq2 = jnp.swapaxes(wq, 1, 2).reshape(qsize, H * kd)
    wk2 = jnp.swapaxes(wk, 1, 2).reshape(ksize, H * kd)
    wv2 = jnp.swapaxes(wv, 1, 2).reshape(vsize, H * vd)
    wo2 = jnp.transpose(wo, (2, 0, 1)).reshape(H * vd, e)
    return wq2, wk2, wv2, wo2


def mha_project_qkv_bshf(
    attrs: MultiHeadAttentionAttrs, q, k, v, weight, input_bias=None
):
    """q/k/v projections -> seq-major fused-head tensors [b, s, h*d] plus wo
    pre-arranged as [h*v, e]. Grouped-query heads: k and v come out as their
    `kv_heads` published heads, [b, s, kv*d] (`mha_between` repeats them for
    a core that does not read them in place).

    With heads fused into the minor dim every projection is a PLAIN MATMUL
    ([b,s,e] @ [e, h*d]), whose natural output layout matches
    flash_attention_bshf's operand layout — no physical transpose between
    the projection fusion and the custom call."""
    unpack = unpack_gqa_weights if attrs.grouped_query else _bshf_weights
    wq2, wk2, wv2, wo2 = unpack(
        attrs, q.shape[-1], k.shape[-1], v.shape[-1], weight
    )
    H = attrs.num_heads
    kd, vd = attrs.q_proj_size, attrs.v_proj_size
    qp = q @ wq2
    kp = k @ wk2
    vp = v @ wv2
    if input_bias is not None:
        assert not attrs.grouped_query, "grouped-query attention has no bias yet"
        qp = qp + jnp.tile(input_bias[:kd], H)[None, None, :]
        kp = kp + jnp.tile(input_bias[kd : 2 * kd], H)[None, None, :]
        vp = vp + jnp.tile(input_bias[2 * kd :], H)[None, None, :]
    return qp, kp, vp, wo2


def mha_project_qkv_bshf_fused(
    attrs: MultiHeadAttentionAttrs, x, weight, input_bias=None
):
    """Self-attention projections as ONE matmul into the head-pair
    interleaved layout: qkv[b, s, 3f] where pair-group hp holds
    [q_pair(128) | k_pair(128) | v_pair(128)] (the operand layout of
    flash_attention_bshf_qkv). Returns (qkv, wo2)."""
    e = x.shape[-1]
    wq2, wk2, wv2, wo2 = _bshf_weights(attrs, e, e, e, weight)
    H = attrs.num_heads
    kd, vd = attrs.q_proj_size, attrs.v_proj_size
    assert kd == vd and (H * kd) % 128 == 0 and H % 2 == 0, (H, kd, vd)
    f = H * kd
    wf = jnp.stack(
        [
            wq2.reshape(e, f // 128, 128),
            wk2.reshape(e, f // 128, 128),
            wv2.reshape(e, f // 128, 128),
        ],
        axis=2,
    ).reshape(e, 3 * f)
    qkv = x @ wf
    if input_bias is not None:
        group = jnp.concatenate(
            [
                jnp.tile(input_bias[:kd], 128 // kd),
                jnp.tile(input_bias[kd:2 * kd], 128 // kd),
                jnp.tile(input_bias[2 * kd:], 128 // kd),
            ]
        )
        qkv = qkv + jnp.tile(group, f // 128)[None, None, :]
    return qkv, wo2


def rms_norm(x, gain, eps, zero_centered: bool = False):
    """x * rsqrt(mean(x^2, last) + eps) * gain, the mean of squares in
    float32; the result in x's dtype. `zero_centered`: `gain` is the weight
    w of a gain 1 + w (`RMSNormAttrs.zero_centered`)."""
    x32 = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    if zero_centered:
        return (x32 * scale * (1.0 + gain.astype(jnp.float32))).astype(x.dtype)
    return (x32 * scale * gain.astype(jnp.float32)).astype(x.dtype)


def rope_frequencies(width: int, theta: float, scaling=None):
    """(inv_freq [width / 2] in float32, amplitude) of a rotary `width`
    columns wide: THE place a rotary's frequencies come from (`rope_bshf`,
    `_rope_leading_columns`, `rope_tables`). Without `scaling` pair j turns
    at theta^(-2j / width) and the amplitude is None (no product is
    emitted: the arithmetic of every node from before there was a scaling,
    bit for bit). With a `YarnScaling` the pairs from its `high` on turn
    `factor` times slower, those below its `low` as they did, a line
    between, and cosine and sine are to be multiplied by its amplitude
    (`op_attrs/ops/attention.YarnScaling` has the formula). The ramp is a
    constant of the node; the frequencies are float32, outside any kernel."""
    half = width // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / width)
    if scaling is None:
        return inv_freq, None
    import numpy as np

    low, high = scaling.correction_range(theta, width)
    if low == high:
        high += 0.001  # the public code's guard against a ramp of no width
    ramp = jnp.asarray(
        np.clip((np.arange(half, dtype=np.float32) - low) / (high - low), 0, 1),
        jnp.float32,
    )
    inv_freq = inv_freq * (1.0 - ramp) + (inv_freq / scaling.factor) * ramp
    return inv_freq, scaling.amplitude


def _rope_leading_columns(x, num_heads: int, theta: float, width: int,
                          scaling=None):
    """`rope_bshf` on the first `width` columns of each head alone (pairs
    (j, j + width / 2), angle pos * theta^(-2j / width)); the head's other
    columns pass."""
    b, s, f = x.shape
    half = width // 2
    inv_freq, amplitude = rope_frequencies(width, theta, scaling)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if amplitude is not None:
        cos, sin = cos * amplitude, sin * amplitude
    heads = x.reshape(b, s, num_heads, f // num_heads)
    lo = heads[..., :half].astype(jnp.float32)
    hi = heads[..., half:width].astype(jnp.float32)
    turned = jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin], axis=-1
    ).astype(x.dtype)
    return jnp.concatenate(
        [turned, heads[..., width:]], axis=-1
    ).reshape(b, s, f)


def rope_bshf(x, num_heads: int, theta: float, rotary_dim=None, scaling=None):
    """Rotary position embedding on x [b, s, h*d], positions 0..s-1, each
    d-lane head block rotated by itself with the rotate-half pairing
    (i, i + d/2) and angle pos * theta^(-2j/d). Written on the fused row so
    that the projections' layout is kept: within a block, rotate_half(x) is
    x rolled down by d/2 lanes in the lower half and up in the upper half,
    and a roll of the whole row agrees with the roll of a block wherever
    that half reads from its own block. `rotary_dim` narrower than the head
    turns the head's first columns only (`_rope_leading_columns`). `scaling`
    (`MultiHeadAttentionAttrs.rope_scaling`) changes the frequencies and
    multiplies cosine and sine by its amplitude (`rope_frequencies`)."""
    b, s, f = x.shape
    d = f // num_heads
    if rotary_dim is not None and rotary_dim != d:
        return _rope_leading_columns(x, num_heads, theta, rotary_dim, scaling)
    half = d // 2
    assert d % 2 == 0, f"rotary embedding needs an even head size, got {d}"
    inv_freq, amplitude = rope_frequencies(d, theta, scaling)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    # the order in which these are emitted is part of every rotary cell's
    # lowered program (`sha256_without_locations`): cosine and its tile,
    # then sine
    cos = jnp.cos(angle)
    if amplitude is not None:
        cos = cos * amplitude
    cos = jnp.tile(cos, (1, 2 * num_heads))  # [s, f]
    sin = jnp.sin(angle)
    if amplitude is not None:
        sin = sin * amplitude
    sin = jnp.tile(jnp.concatenate([-sin, sin], axis=-1), (1, num_heads))
    lower = (jnp.arange(f) % d) < half
    x32 = x.astype(jnp.float32)
    rotated = jnp.where(
        lower, jnp.roll(x32, -half, axis=-1), jnp.roll(x32, half, axis=-1)
    )
    return (x32 * cos + rotated * sin).astype(x.dtype)


def mha_row_projections(attrs: MultiHeadAttentionAttrs) -> bool:
    """Whether something acts on the fused-row projections [b, s, h*d]
    between projection and attention core (`mha_between`): QK-norm, RoPE,
    or the repeat of grouped-query key/value heads."""
    return attrs.qk_norm or attrs.rope_theta is not None or attrs.grouped_query


def rope_lane_tables(s: int, d: int, theta: float, scaling=None):
    """(cos, signed sin), float32 `[s, max(d, 128)]`: the rotary of
    `rope_bshf` over whole heads of `d` columns as whole lane tiles a
    POSITION, which every head reads (`kernels/norm_rotary`): a head's lanes
    hold cos | cos and -sin | sin of its pairs' angles, twice where two
    heads of 64 fill a tile. The frequencies, YaRN's ramp and amplitude are
    `rope_frequencies`', as `rope_bshf` takes them."""
    inv_freq, amplitude = rope_frequencies(d, theta, scaling)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if amplitude is not None:
        cos, sin = cos * amplitude, sin * amplitude
    times = 2 * max(128 // d, 1)
    return (
        jnp.tile(cos, (1, times)),
        jnp.tile(jnp.concatenate([-sin, sin], axis=-1), (1, times // 2)),
    )


def _pass_form(attrs: MultiHeadAttentionAttrs):
    """`norm_rotary.PassForm` of what the attrs ask of q's and k's rows."""
    from flexflow_tpu.kernels.norm_rotary import PassForm

    span = (
        "head" if attrs.qk_norm_per_head else "row" if attrs.qk_norm else None
    )
    return PassForm(attrs.q_proj_size, span, attrs.rope_theta is not None)


def between_form(attrs: MultiHeadAttentionAttrs, route: str, s: int,
                 t: int = None):
    """(said, form): which form the norm and the rotary of `mha_between`
    take in a node whose core took `route`, on queries of `s` and keys of
    `t` positions (`s` by default), from what the trace can observe. `said`
    is what the program's counter is told: "pallas"
    (`norm_rotary.norm_rotary`, one Pallas pass each way; `form` is then the
    pass's `PassForm`, which `mha_between` takes) or "xla (<why>)", the plain
    form (`rms_norm`, then `rope_bshf`; `form` None), where

    - `route`: the core is not the "fused_row" one (the `[b, h, s, d]` and
      dense cores turn the rows to heads first anyway), or a `flash_mesh` is
      declared (a bare Pallas call has no partitioning rule);
    - `rotary_dim`: the rotary turns a head's leading columns only;
    - `output_gate`: q is columns cut out of the `[q | gate]` row, and a
      kernel's operand must be a buffer (XLA then writes the cut out: 0.82
      ms of `copy` a step for the 0.37 the pass saved in
      `qwen3next80b_s8192_1chip`, chip runs of PR 65);
    - `head <d>` / `row <lanes>`: `norm_rotary.pass_plan` admits the shapes
      its body is tested on: heads of 64, 128 or 256 columns, and under a
      whole-row norm a row of a power of two of lanes, 4,096 at most.

    A node with neither norm nor rotary has nothing between: (None, None)."""
    from flexflow_tpu.kernels.norm_rotary import HEAD_SIZES, pass_plan

    form = _pass_form(attrs)
    if form.span is None and not form.rotary:
        return None, None
    if route != "fused_row" or context.declared_mesh() is not None:
        return "xla (route)", None
    if attrs.rotary_dim not in (None, form.d):
        return "xla (rotary_dim)", None
    if attrs.output_gate:
        return "xla (output_gate)", None
    if form.d not in HEAD_SIZES:
        return f"xla (head {form.d})", None
    for rows, heads in ((s, attrs.num_heads), (t or s, attrs.kv_heads)):
        if pass_plan(rows, heads * form.d, form) is None:
            return f"xla (row {heads * form.d})", None
    return "pallas", form


def _between_pallas(attrs: MultiHeadAttentionAttrs, form, qp, kp, qk_gains):
    """q and k through `norm_rotary.norm_rotary` in `form`: the gains as
    float32 rows a slab wide (1 + w where `qk_norm_zero_centered`; a head's
    gain twice where two heads of 64 fill a lane tile), the tables a
    position."""
    from flexflow_tpu.kernels.norm_rotary import norm_rotary

    def tables(positions):
        if not form.rotary:
            return None, None
        return rope_lane_tables(
            positions, form.d, attrs.rope_theta, attrs.rope_scaling
        )

    def passed(x, gain, cos, sin):
        if form.span:
            gain = gain.astype(jnp.float32)
            if attrs.qk_norm_zero_centered:
                gain = 1.0 + gain
            if form.span == "head" and form.d < 128:
                gain = jnp.tile(gain, 128 // form.d)
            gain = gain[None, :]
        return norm_rotary(x, gain, cos, sin, form, attrs.qk_norm_eps)

    gains = qk_gains if form.span else (None, None)
    q_tables = tables(qp.shape[1])
    # one pair of tables where the keys are as long as the queries
    k_tables = q_tables if kp.shape[1] == qp.shape[1] else tables(kp.shape[1])
    return passed(qp, gains[0], *q_tables), passed(kp, gains[1], *k_tables)


def mha_between(attrs: MultiHeadAttentionAttrs, qp, kp, vp, qk_gains,
                repeat: bool = True, form=None):
    """What the attrs ask for between projection and attention core, on the
    fused [b, s, h*d] projections: QK-norm over the whole row (or, with
    `qk_norm_per_head`, over each head block by itself), then RoPE on
    each head block, then, with `repeat`, each grouped-query key/value head
    written out for the query heads that read it (head h reads h // group),
    so that the core is the equal-head one and the repeat's transpose sums
    dK and dV over the group: what the head-pair, `[b, h, s, d]` and dense
    cores take. The causal tile schedule indexes the key/value block by
    `h // group` instead (`CausalPlan.group`, wherever the key is as wide as
    the value) and saves the two repeated copies: its caller passes `repeat`
    false and hands the kernels k and v as they lie. With a `form` (the
    `PassForm` `between_form` gave) norm and rotary are ONE Pallas pass each
    way; the plain form below stays what every other node runs, and the
    reference the kernels' tests compare against."""
    if form is not None:
        qp, kp = _between_pallas(attrs, form, qp, kp, qk_gains)
    elif attrs.qk_norm_per_head:
        # a head's own features normed by themselves, one gain [d] for all
        # of q's heads and one for the key heads

        def per_head(x, gain):
            heads = x.reshape(*x.shape[:2], -1, attrs.q_proj_size)
            return rms_norm(
                heads, gain, attrs.qk_norm_eps, attrs.qk_norm_zero_centered
            ).reshape(x.shape)

        qp, kp = per_head(qp, qk_gains[0]), per_head(kp, qk_gains[1])
    elif attrs.qk_norm:
        zc = attrs.qk_norm_zero_centered
        qp = rms_norm(qp, qk_gains[0], attrs.qk_norm_eps, zc)
        kp = rms_norm(kp, qk_gains[1], attrs.qk_norm_eps, zc)
    if attrs.rope_theta is not None and form is None:
        qp = rope_bshf(
            qp, attrs.num_heads, attrs.rope_theta, attrs.rotary_dim,
            attrs.rope_scaling,
        )
        kp = rope_bshf(
            kp, attrs.kv_heads, attrs.rope_theta, attrs.rotary_dim,
            attrs.rope_scaling,
        )
    if repeat and attrs.num_kv_heads not in (None, attrs.num_heads):
        H, KV = attrs.num_heads, attrs.num_kv_heads

        def repeated(x):
            b, t, f = x.shape
            x = x.reshape(b, t, KV, 1, f // KV)
            x = jnp.broadcast_to(x, (b, t, KV, H // KV, f // KV))
            return x.reshape(b, t, H * (f // KV))

        kp, vp = repeated(kp), repeated(vp)
    return qp, kp, vp


def _causal_plan_of(attrs: MultiHeadAttentionAttrs, s: int, itemsize: int = 2):
    """`flash_attention.causal_plan` of the node's fused-row core as
    `_mha_forward` / `_latent_mha_forward` would call it on `s` positions: a
    latent key padded to whole tiles, heads of 64 padded to 128 lanes. None
    where the causal tile schedule has no body for it (no mask, other
    widths) or is not asked (heads of 64 in one tile: the head-pair
    kernels'). `supported` and the blocks are the same at every itemsize:
    the route, which has shapes and no dtype, asks at bf16's."""
    from flexflow_tpu.kernels.flash_attention import (
        bshf_pair_supported,
        causal_plan,
        wide_key_padded,
    )

    H, kv = attrs.num_heads, attrs.kv_heads
    kd, vd = attrs.q_proj_size, attrs.v_proj_size
    if attrs.latent:
        kd, kv = wide_key_padded(kd), H
    elif attrs.differential:
        # a map a query head: its key padded to whole tiles, the pair's
        # value as it lies, keys and values repeated for the heads that
        # share them (the pairing is no `h // group`)
        kd, vd, kv = wide_key_padded(kd), 2 * vd, H
    elif kd == vd == 64:
        if bshf_pair_supported(H, kd, s):
            return None
        kd = vd = 128
    if not getattr(attrs, "causal", False) or kd % 128 or vd % 128:
        return None
    return causal_plan(1, s, H, kv, kd, vd, itemsize, window=attrs.window)


def mha_pads_heads(attrs: MultiHeadAttentionAttrs, s: int) -> bool:
    """Whether the fused-row core runs these heads of 64 as 128-lane heads,
    each padded with 64 zero columns: a causal context of more than one tile,
    which the head-pair kernels refuse (their backward is the single-tile
    one). The d % 128 causal tile schedule takes them as it takes a padded
    latent key (the true head size's scale): on a 128 x 128 matrix unit a
    64-wide contraction or product fills half the array either way, so the
    zero columns cost bytes and no passes. A causal tile schedule for the
    pair kernels would save the padded copies (ROADMAP, Reach)."""
    if not attrs.q_proj_size == attrs.v_proj_size == 64:
        return False
    plan = _causal_plan_of(attrs, s)
    return plan is not None and plan.supported


def _padded_heads(x, kd: int):
    """[b, s, heads * kd] -> [b, s, heads * 128], zero columns after each
    head's own."""
    b, s, f = x.shape
    x = jnp.pad(x.reshape(b, s, f // kd, kd), ((0, 0),) * 3 + ((0, 128 - kd),))
    return x.reshape(b, s, -1)


def _own_columns(ctx, kd: int):
    """`_padded_heads`' inverse on the context: each head's own `kd`."""
    b, s, f = ctx.shape
    return ctx.reshape(b, s, f // 128, 128)[..., :kd].reshape(b, s, -1)


def mha_core_route(
    attrs: MultiHeadAttentionAttrs, q_shape, k_shape, v_shape, fused_qkv: bool
) -> str:
    """The attention core `_mha_forward` lowers these [b, s, e] operands to
    in the current trace, from static facts alone (the shapes, the declared
    `flash_mesh`, the `no_flash` guard, the backend: `kernels/context.py`).
    THE layout rule, for
    one chip and for a mesh alike:

    - "fused_row_qkv": one projection matmul into the head-pair interleaved
      row [b, s, 3*h*d] and `flash_attention_bshf_qkv` on it (d=64
      self-attention, `fused_qkv`: q is k is v);
    - "fused_row": three plain matmuls into [b, s, h*d] rows and
      `flash_attention_bshf` (d % 128 == 0, or d=64 with distinct operands
      or QK-norm / RoPE between projection and core), or, for d=64 under a
      causal mask over more than one tile, the same entry's d % 128 causal
      tile schedule on heads padded to 128 lanes (`mha_pads_heads`);
    - "rows": the per-head [b, h, s, d] projections and `flash_attention`
      (other head sizes; every head-sharded plan, through
      `sharded_flash_attention`);
    - "dense": XLA's attention, below the route's least length
      (`flash_attention.min_seq_for`: measured per kernel family).

    A key wider than its value (kd != vd) has one kernel form: latent
    attention (`attrs.latent`) under a causal mask at more than one causal
    tile (`CausalPlan.supported`) takes "fused_row" on `flash_attention_bshf`,
    its key padded with zero columns to whole 128-lane tiles (192 -> 256)
    and its scale the true width's; every other kd != vd shape takes "rows"
    or "dense", as it always did.

    Under a declared mesh the gates read the block each device sees and the
    fused-row kernels are mapped over the batch shards (`per_batch_shard`);
    a head-sharded plan cannot split a fused row by pairs and takes "rows"."""
    import os

    if os.environ.get("FLEXFLOW_TPU_FLASH", "1") == "0":
        return "dense"
    from flexflow_tpu.kernels.flash_attention import (
        bshf_pair_supported,
        flash_core_supported,
    )

    H, kd, vd = attrs.num_heads, attrs.q_proj_size, attrs.v_proj_size
    b, s, t = q_shape[0], q_shape[1], k_shape[1]
    proj_q = (b, H, s, kd)
    proj_kv = (b, H, t, kd)
    mesh_ctx = context.declared_mesh()
    heads_whole = mesh_ctx is None or mesh_ctx[2] is None
    if attrs.differential:
        # two maps a differential head on the causal tile schedule, or XLA's
        # dense attention with the band as a mask (`_differential_forward`)
        lanes = (b, H, s, 128)
        plan = _causal_plan_of(attrs, s)
        kernel = (
            heads_whole and s == t and plan is not None and plan.supported
            and flash_core_supported(lanes, lanes, lanes, "lane")
        )
        return "fused_row" if kernel else "dense"
    if attrs.latent:
        from flexflow_tpu.kernels.flash_attention import wide_key_padded

        padded = (b, H, s, wide_key_padded(kd))
        plan = _causal_plan_of(attrs, s)
        kernel = (
            heads_whole and plan is not None and plan.supported
            and flash_core_supported(padded, padded, padded, "lane")
        )
        return "fused_row" if kernel else "dense"
    if _banded(attrs, s):
        # a band lives in the causal tile schedule alone (heads of whole
        # lane tiles, or of 64 padded to them, over more than one tile) or
        # is a mask on XLA's attention: the head-pair kernels, the fused qkv
        # row and the [b, h, s, d] kernels have none, and a windowed node is
        # never sent to them
        lanes = (b, H, s, max(kd, 128))
        plan = _causal_plan_of(attrs, s)
        kernel = (
            heads_whole and s == t and kd == vd
            and plan is not None and plan.supported
            and (kd % 128 == 0 or mha_pads_heads(attrs, s))
            and flash_core_supported(lanes, lanes, lanes, "lane")
        )
        return "fused_row" if kernel else "dense"
    # kd % 128: blocks carved from the fused h*d minor dim must be
    # lane-aligned (Pallas requires block minor dims divisible by 128 unless
    # equal to the array dim). d=64 (the reference heads=16 config) rides
    # the HEAD-PAIR bshf kernels (two heads per 128-lane block), so its
    # projections stay plain matmuls too (the per-head [b,h,s,d] entry pays
    # ~27 ms/step of transpose copies); other head dims use the per-head entry
    family = "lane" if kd % 128 == 0 else "pair"  # min_seq_for's families
    if (
        heads_whole
        and kd == vd
        and (kd % 128 == 0 or bshf_pair_supported(H, kd, s))
        and flash_core_supported(proj_q, proj_kv, proj_kv, family)
    ):
        if kd % 128 != 0 and fused_qkv and not mha_row_projections(attrs):
            return "fused_row_qkv"
        return "fused_row"
    lanes = (b, H, s, 128)
    if (
        heads_whole and s == t and mha_pads_heads(attrs, s)
        and flash_core_supported(lanes, lanes, lanes, "lane")
    ):
        return "fused_row"
    if flash_core_supported(proj_q, proj_kv, (b, H, v_shape[1], vd), "rows"):
        return "rows"
    return "dense"


def _banded(attrs: MultiHeadAttentionAttrs, s: int) -> bool:
    """Whether the node's window hides any key of `s` positions from a
    query that the causal mask shows it: a window of `s` or more is none."""
    return attrs.window is not None and attrs.window < s


def _note_route(
    route: str, attrs: MultiHeadAttentionAttrs = None, group: int = 1
) -> None:
    """Note the core the attention node being lowered took, its
    `attention_routes` (`kernels/context.note`; the pinned view
    `observability/trace.attention_routes`): `mha_core_route`'s name,
    followed by ` differential` of a differential node and of any node,
    where it has one, by ` window=<keys>` (`fused_row differential
    window=512`, `fused_row window=1024`), then ` group=<query heads a
    key/value head>` where they are read in place and ` scale=<value>` where
    the node states its scores' scale (`fused_row group=4 scale=0.015625`);
    what its band skips on the kernels is `window_tiles`."""
    if attrs is not None:
        if attrs.differential:
            route += " differential"
        if attrs.window is not None:
            route += f" window={attrs.window}"
    if group > 1:
        route += f" group={group}"
    if attrs is not None and attrs.softmax_scale is not None:
        route += f" scale={attrs.softmax_scale:g}"
    context.note("attention_routes", route)


def _note_scan_blocks(attrs, x) -> None:
    """Note the programs a group's scan goes as in the state-space node
    being lowered, its `scan_column_blocks` (`kernels/context.note`; the
    pinned view `observability/trace.scan_column_blocks`): 1 the group
    whole, 4 a 4,096-column group in blocks of 1,024 on the Pallas kernels,
    0 where the node took `_scan_core` ("xla"), so that a run that fell back
    says so itself."""
    from flexflow_tpu.kernels.ssm import scan_column_blocks

    context.note("scan_column_blocks", scan_column_blocks(
        x.shape[0], attrs.num_heads, attrs.head_dim, attrs.num_groups,
        attrs.state_size, attrs.chunk_size,
    ))


def _note_rotary(attrs: MultiHeadAttentionAttrs) -> None:
    """Note the rotary of the plain attention node being lowered, where it
    has one, its `rotaries` (`kernels/context.note`; the pinned view
    `observability/trace.rotaries`): `default theta=500000`, or with a
    `YarnScaling` `yarn factor=16 low=18 high=35 amp=1.2773` (the first pair
    the ramp touches, the first it leaves `factor` times slower, the
    amplitude on cosine and sine), so that two layers of one graph that turn
    differently say so themselves."""
    if attrs.rope_theta is None:
        return
    width = attrs.rotary_dim or attrs.q_proj_size
    context.note(
        "rotaries",
        f"default theta={attrs.rope_theta:g}" if attrs.rope_scaling is None
        else attrs.rope_scaling.describe(attrs.rope_theta, width)
    )


def _note_between(said) -> None:
    """Note what `between_form` said of the plain attention node being
    lowered, where it has a norm or a rotary, its `between_passes`
    (`kernels/context.note`): `pallas` (norm and rotary of q and of k as ONE
    Pallas pass each way, `norm_rotary_fwd` / `norm_rotary_bwd`) or
    `xla (<why>)` (`rms_norm`, then `rope_bshf`, differentiated by JAX;
    `between_form` lists the reasons), so that a run that fell back says so
    itself."""
    if said is not None:
        context.note("between_passes", said)


def _note_window_tiles(plan, s: int) -> None:
    """Note what the band of the plan being lowered skips, the node's
    `window_tiles` (`kernels/context.note`; the pinned view
    `observability/trace.window_tiles`): the (q block, k block) tiles its
    forward visits and those the causal schedule would, `(visited, causal)`.
    A node whose band is a mask on XLA's dense attention skips nothing and
    notes nothing."""
    from flexflow_tpu.kernels.flash_attention import causal_tile_schedule

    if plan is not None and plan.window is not None:
        context.note("window_tiles", (
            causal_tile_schedule(s, plan.block_q, plan.block_k, plan.window)[0],
            causal_tile_schedule(s, plan.block_q, plan.block_k)[0],
        ))


def _differential_forward(attrs: MultiHeadAttentionAttrs, q, k, v, weights):
    """Differential attention (`MultiHeadAttentionAttrs.differential`) on q
    [b, s, e] and k, v (the same row, or with `external_kv` another node's
    projected keys and values): [out] and, with `kv_outputs`, the projected
    keys and values beside it. Always causal. Parts, each under a scope of
    its own: `qkv` (the projections and their bias), `core` (ONE call of the
    causal tile schedule on the two maps stacked as heads, a map's 64-wide
    key padded to 128 lanes, the pair's 128-wide value as it lies, keys and
    values repeated for the heads that read them, the band in the schedule;
    or XLA's dense attention with the band as a mask), `combine` (the
    subtraction under lambda, the sub-norm a head, its scale) and
    `out_proj`."""
    from flexflow_tpu.kernels.flash_attention import (
        flash_attention_bshf,
        per_batch_shard,
        wide_key_padded,
    )

    assert getattr(attrs, "causal", False), (
        "differential attention is lowered under a causal mask only"
    )
    H, KV = attrs.num_heads, attrs.num_kv_heads
    kd, vd, e = attrs.q_proj_size, attrs.v_proj_size, attrs.embed_dim
    b, s, _ = q.shape
    t = k.shape[1]
    weight, rest = weights[0], list(weights[1:])
    in_bias, out_bias = (rest.pop(0), rest.pop(0)) if attrs.bias else (None, None)
    lq1, lk1, lq2, lk2, gain = rest
    with jax.named_scope("qkv"):
        if attrs.external_kv:
            wq, wo = _unpack_flat(weight, [(q.shape[-1], H * kd), (H * vd, e)])
            qp, kp, vp = q @ wq, k, v
            if in_bias is not None:
                qp = qp + in_bias
        else:
            wq, wk, wv, wo = unpack_gqa_weights(
                attrs, q.shape[-1], k.shape[-1], v.shape[-1], weight
            )
            qp, kp, vp = q @ wq, k @ wk, v @ wv
            if in_bias is not None:
                cut = H * kd, (H + KV) * kd
                qp = qp + in_bias[:cut[0]]
                kp = kp + in_bias[cut[0]:cut[1]]
                vp = vp + in_bias[cut[1]:]
    route = mha_core_route(attrs, q.shape, k.shape, v.shape, False)
    _note_route(route, attrs)
    heads, share = H // 2, H // KV  # differential heads; heads a group
    with jax.named_scope("core"):
        # query head 2j + c (map c of head j) reads key head
        # 2 (j // share) + c and the value of group j // share
        q5 = qp.reshape(b, s, heads, 2, kd)
        k5 = jnp.broadcast_to(
            kp.reshape(b, t, KV // 2, 1, 2, kd), (b, t, KV // 2, share, 2, kd)
        ).reshape(b, t, heads, 2, kd)
        v5 = jnp.broadcast_to(
            vp.reshape(b, t, KV // 2, 1, 1, 2 * vd),
            (b, t, KV // 2, share, 2, 2 * vd),
        ).reshape(b, t, heads, 2, 2 * vd)
        if route == "fused_row":
            _note_window_tiles(_causal_plan_of(attrs, s, q.dtype.itemsize), s)
            pad = ((0, 0),) * 4 + ((0, wide_key_padded(kd) - kd),)
            ctx = per_batch_shard(
                flash_attention_bshf,
                jnp.pad(q5, pad).reshape(b, s, -1),
                jnp.pad(k5, pad).reshape(b, t, -1),
                v5.reshape(b, t, -1),
                num_heads=H, causal=True, scale=kd ** -0.5,
                window=attrs.window,
            ).reshape(b, s, heads, 2, 2 * vd)
        else:
            scores = jnp.einsum("bsjck,btjck->bjcst", q5, k5) * jnp.asarray(
                kd ** -0.5, q5.dtype
            )
            ahead = jnp.arange(s)[:, None] - jnp.arange(t)[None, :]
            keep = ahead >= 0
            if attrs.window is not None:  # the band as a mask
                keep = keep & (ahead < attrs.window)
            scores = jnp.where(keep, scores, jnp.asarray(-1e30, scores.dtype))
            ctx = jnp.einsum(
                "bjcst,btjcv->bsjcv", jax.nn.softmax(scores, axis=-1), v5
            )
    with jax.named_scope("combine"):
        f32 = jnp.float32
        lam = (
            jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32)))
            - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32)))
            + attrs.lambda_init
        )
        diff = ctx[..., 0, :].astype(f32) - lam * ctx[..., 1, :].astype(f32)
        normed = rms_norm(diff, gain, attrs.diff_norm_eps)
        rows = (normed * (1.0 - attrs.lambda_init)).astype(q.dtype)
    with jax.named_scope("out_proj"):
        out = rows.reshape(b, s, heads * 2 * vd) @ wo
        if out_bias is not None:
            out = out + out_bias
    return [out, kp, vp] if attrs.kv_outputs else [out]


def unpack_gqa_weights(
    attrs: MultiHeadAttentionAttrs, qsize: int, ksize: int, vsize: int, weight
):
    """The grouped-query layout: one flat column holding wq [qsize, h*d]
    (with `output_gate` [qsize, h*2*d]: a head's query, then its gate),
    wk [ksize, kv*d], wv [vsize, kv*v] and wo [h*v, e], each row-major with
    head-major columns (`MultiHeadAttentionAttrs.num_kv_heads`)."""
    H, KV = attrs.num_heads, attrs.num_kv_heads
    kd, vd, e = attrs.q_proj_size, attrs.v_proj_size, attrs.embed_dim
    shapes = [
        (qsize, H * attrs.q_columns), (ksize, KV * kd), (vsize, KV * vd),
        (H * vd, e),
    ]
    return _unpack_flat(weight, shapes)


def _unpack_flat(weight, shapes):
    """The row-major matrices of `shapes`, one after the other in one flat
    column."""
    flat, out, at = weight.reshape(-1), [], 0
    for rows, cols in shapes:
        out.append(flat[at:at + rows * cols].reshape(rows, cols))
        at += rows * cols
    return out


def unpack_latent_weights(attrs: MultiHeadAttentionAttrs, esize: int, weight):
    """The latent layout (`MultiHeadAttentionAttrs.kv_latent_rank`): wq
    [e, h*kd] (with `q_latent_rank` wq_a [e, q rank] and wq_b [q rank, h*kd]
    in its place), wkv_a [e, rank + shared], wkv_b [rank, h*(own + vd)] (a
    head's own key columns, then its value's) and wo [h*vd, e]."""
    H, rank = attrs.num_heads, attrs.kv_latent_rank
    kd, vd = attrs.q_proj_size, attrs.v_proj_size
    qr = attrs.q_latent_rank
    query = [(esize, H * kd)] if qr is None else [(esize, qr), (qr, H * kd)]
    return _unpack_flat(weight, query + [
        (esize, rank + attrs.shared_key_dim),
        (rank, H * (attrs.own_key_dim + vd)), (H * vd, attrs.embed_dim),
    ])


def rope_tables(s: int, width: int, theta: float):
    """(cos, sin) [s, width / 2] in float32 of the angles
    pos * theta^(-2j / width), positions 0..s-1: tables of their own (behind
    a barrier), so that a pass over rows a head and position wide reads them
    and does not take a cosine an element."""
    inv_freq, _ = rope_frequencies(width, theta)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    return lax.optimization_barrier((jnp.cos(angle), jnp.sin(angle)))


def rope_halves(x, cos, sin):
    """x [b, s, ..., width] with pair j = columns (j, j + width / 2) turned
    by the tables' angle at its position (axis 1): the products in float32,
    the result in x's dtype."""
    half = x.shape[-1] // 2
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    lo = x[..., :half].astype(jnp.float32)
    hi = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin], axis=-1
    ).astype(x.dtype)


def deinterleaved_columns(w, num_heads: int, start: int, width: int):
    """w [rows, num_heads * d] with columns [start, start + width) of each
    head block reordered evens first, then odds: what turns the pairing
    (2j, 2j + 1) of what the matrix produces into (j, j + width / 2). Done
    to the query's and the key's slice alike it leaves every dot product of
    the two as it was (the public `deepseek_v3` layer does it to q and k)."""
    rows = w.shape[0]
    blocks = w.reshape(rows, num_heads, -1)
    turned = blocks[..., start:start + width]
    return jnp.concatenate([
        blocks[..., :start], turned[..., 0::2], turned[..., 1::2],
        blocks[..., start + width:],
    ], axis=-1).reshape(w.shape)


def _note_latent_form(attrs: MultiHeadAttentionAttrs, route, s, itemsize):
    """Note the form the latent-attention node being lowered took, its
    `latent_attention_forms` (`kernels/context.note`; the pinned view
    `observability/trace.latent_attention_forms`): `query_rank` (None: one
    full-rank query projection), `rotated_columns` (of the shared key slice
    and of each query head; 0: no position encoding), `pairing`
    (`interleaved`: columns (2j, 2j + 1); `halves`: (j, j + width / 2); None)
    and `core` (the forward kernel of the wide-key entry, or `dense`), so
    that a run says itself which node it measured."""
    rope = attrs.rope_theta is not None
    core = "dense"
    if route == "fused_row":
        core = _causal_plan_of(attrs, s, itemsize).fwd_name
    context.note("latent_attention_forms", {
        "query_rank": attrs.q_latent_rank,
        "rotated_columns": attrs.shared_key_dim if rope else 0,
        "pairing": (
            None if not rope
            else "interleaved" if attrs.rope_interleaved else "halves"
        ),
        "core": core,
    })


def _latent_mha_forward(attrs: MultiHeadAttentionAttrs, x, weight, gain, causal,
                        q_gain=None):
    """Latent self-attention on x [b, s, e]: keys and values from one normed
    low-rank row (scope `latent`), head h's key its own columns beside the
    slice all heads share, then the attention core (scope `core`): the
    causal tile kernels where `mha_core_route` says so, on a key padded
    with zero columns, else XLA's dense attention. With `q_latent_rank` the
    query comes from a normed low-rank row of its own (scope `latent` too),
    and with `rope_theta` the shared slice, once a position before the heads
    share it, and each query head's matching columns are turned (scope
    `rows`): halves of the slice against each other, on weights whose slice
    columns `rope_interleaved` puts evens first."""
    from flexflow_tpu.kernels.flash_attention import (
        flash_attention_bshf,
        per_batch_shard,
        wide_key_padded,
    )

    H, rank, shared = attrs.num_heads, attrs.kv_latent_rank, attrs.shared_key_dim
    kd, vd, own = attrs.q_proj_size, attrs.v_proj_size, attrs.own_key_dim
    b, s, e = x.shape
    *wq, wkv_a, wkv_b, wo = unpack_latent_weights(attrs, e, weight)
    rope = attrs.rope_theta is not None
    if rope:
        with jax.named_scope("rows"):
            cos, sin = rope_tables(s, shared, attrs.rope_theta)
            if attrs.rope_interleaved:
                wq[-1] = deinterleaved_columns(wq[-1], H, own, shared)
                wkv_a = deinterleaved_columns(wkv_a, 1, rank, shared)
    with jax.named_scope("latent"):
        low = x @ wkv_a
        c = rms_norm(low[..., :rank], gain, attrs.kv_latent_norm_eps)
        kv = (c @ wkv_b).reshape(b, s, H, own + vd)
        parts = [kv[..., :own]]
        if not rope:
            parts.append(
                jnp.broadcast_to(low[:, :, None, rank:], (b, s, H, shared))
            )
        v = kv[..., own:]
    if rope:
        with jax.named_scope("rows"):  # once a position, before the heads share it
            turned = rope_halves(low[..., rank:], cos, sin)
        with jax.named_scope("latent"):
            parts.append(
                jnp.broadcast_to(turned[:, :, None, :], (b, s, H, shared))
            )

    def project_q(w_last, pad=0):
        """The query [b, s, H * (kd + pad)] on `w_last`, the last query
        matrix: low-rank row and norm first (a full-rank projection is
        booked to the core); the rotary after, in the pass that writes the
        `pad` zero columns a head (without a rotary they are `w_last`'s
        own)."""
        if attrs.q_latent_rank is None:
            with jax.named_scope("core"):
                q = x @ w_last
        else:
            with jax.named_scope("latent"):
                cq = rms_norm(x @ wq[0], q_gain, attrs.q_latent_norm_eps)
                q = cq @ w_last
        if rope:
            with jax.named_scope("rows"):
                heads = q.reshape(b, s, H, kd)
                q = jnp.concatenate(
                    [heads[..., :own], rope_halves(heads[..., own:], cos, sin)]
                    + ([jnp.zeros((b, s, H, pad), q.dtype)] if pad else []),
                    axis=-1,
                ).reshape(b, s, H * (kd + pad))
        return q

    route = mha_core_route(attrs, x.shape, x.shape, x.shape, True)
    _note_route(route)
    _note_latent_form(attrs, route, s, x.dtype.itemsize)
    if route == "fused_row":
        pad = wide_key_padded(kd) - kd
        # zero columns of the WEIGHT are the padded query's zero columns
        # (with a rotary the pass that turns the query writes them)
        wq_last = None if rope else jnp.pad(
            wq[-1].reshape(-1, H, kd), ((0, 0), (0, 0), (0, pad))
        )
        with jax.named_scope("latent"):
            k = jnp.concatenate(
                parts + [jnp.zeros((b, s, H, pad), x.dtype)], axis=-1
            ).reshape(b, s, H * (kd + pad))
        if rope:
            q = project_q(wq[-1], pad)
        else:
            q = project_q(wq_last.reshape(-1, H * (kd + pad)))
        with jax.named_scope("core"):
            ctx = per_batch_shard(
                flash_attention_bshf, q, k, v.reshape(b, s, H * vd),
                num_heads=H, causal=causal, scale=kd ** -0.5,
            )
        return ctx @ wo
    q = project_q(wq[-1]).reshape(b, s, H, kd)
    with jax.named_scope("core"):
        scores = jnp.einsum(
            "bshk,bthk->bhst", q, jnp.concatenate(parts, axis=-1)
        ) / jnp.sqrt(jnp.asarray(kd, q.dtype))
        if causal:
            mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
            scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
        ctx = jnp.einsum("bhst,bthv->bshv", jax.nn.softmax(scores, axis=-1), v)
    return ctx.reshape(b, s, H * vd) @ wo


def _split_output_gate(attrs: MultiHeadAttentionAttrs, qp):
    """(q, gate) of the query projection [b, s, h * q_columns]: with
    `output_gate` a head's columns are its query, then its gate; without,
    (qp, None)."""
    if not attrs.output_gate:
        return qp, None
    b, s, _ = qp.shape
    H, kd = attrs.num_heads, attrs.q_proj_size
    with jax.named_scope("gate"):
        both = qp.reshape(b, s, H, 2 * kd)
        return (
            both[..., :kd].reshape(b, s, H * kd),
            both[..., kd:].reshape(b, s, H * kd),
        )


def _gated_context(ctx, gate):
    """ctx * sigmoid(gate), the sigmoid in float32; ctx where no gate is."""
    if gate is None:
        return ctx
    with jax.named_scope("gate"):
        g = jax.nn.sigmoid(gate.astype(jnp.float32))
        return (ctx.astype(jnp.float32) * g).astype(ctx.dtype)


def _rows_scope(attrs: MultiHeadAttentionAttrs):
    """The scope of the norm-and-rotary pass of a gated node
    (`observability/trace.NODE_PARTS`); other nodes keep the names they
    had."""
    return (
        jax.named_scope("rows") if attrs.output_gate
        else contextlib.nullcontext()
    )


def _mha_forward(
    attrs: MultiHeadAttentionAttrs, q, k, v, weight, input_bias=None,
    causal=False, qk_gains=None,
):
    from flexflow_tpu.kernels.flash_attention import (
        flash_attention,
        flash_attention_bshf,
        flash_attention_bshf_qkv,
        per_batch_shard,
        sharded_flash_attention,
    )

    kd = attrs.q_proj_size
    H = attrs.num_heads
    # QK-norm, RoPE and the grouped-query repeat act on the fused-row
    # projections; a node without them takes the paths it always took
    post = mha_row_projections(attrs)
    route = mha_core_route(attrs, q.shape, k.shape, v.shape, q is k and k is v)
    s = q.shape[1]
    plan = (
        _causal_plan_of(attrs, s, q.dtype.itemsize) if route == "fused_row"
        else None
    )
    # query heads that read one key/value head where it lies
    group = 1 if plan is None else plan.group
    _note_route(route, attrs, group)
    _note_rotary(attrs)
    # the form of the norm and the rotary: asked once, noted and taken
    said, between = (
        between_form(attrs, route, s, k.shape[1]) if post else (None, None)
    )
    _note_between(said)
    banded = _banded(attrs, s)
    if banded and (not causal or route not in ("fused_row", "dense")):
        # `mha_core_route` sends no windowed node here; a caller that forces
        # one gets an error and not a silent full attention
        raise ValueError(
            f"a window of {attrs.window} keys is honoured under a causal mask "
            'on the "fused_row" route (a band in the causal tile schedule) '
            'and on the "dense" one (a mask); this node has '
            f"causal={causal} and took {route!r}, which has no band"
        )
    if route == "fused_row_qkv":
        # self-attention on the head-pair path: ONE fused projection matmul
        # into the interleaved [q_pair|k_pair|v_pair] layout; flash reads
        # the three operands as views of it and the backward returns one
        # fused dqkv (saves two projection launches + two input reads + the
        # gradient combine per layer). The projections stay outside the
        # shard_map of a mesh: XLA partitions a plain matmul over the batch
        # by itself and the weight gradients stay ordinary HLO
        qkv, wo2 = mha_project_qkv_bshf_fused(attrs, q, weight, input_bias)
        ctx = per_batch_shard(
            flash_attention_bshf_qkv, qkv, num_heads=H, causal=causal,
            scale=attrs.softmax_scale,
        )
        return ctx @ wo2
    if route == "fused_row":
        qp, kp, vp, wo2 = mha_project_qkv_bshf(
            attrs, q, k, v, weight, input_bias
        )
        qp, gate = _split_output_gate(attrs, qp)
        pads = mha_pads_heads(attrs, s)
        if banded:
            _note_window_tiles(plan, s)
        if post:
            with _rows_scope(attrs):
                qp, kp, vp = mha_between(
                    attrs, qp, kp, vp, qk_gains, repeat=group == 1,
                    form=between,
                )
        # padded heads and rows read in place came with a scope of their own
        with jax.named_scope("core") if pads or group > 1 else (
            contextlib.nullcontext()
        ):
            if pads:
                qp, kp, vp = (_padded_heads(x, kd) for x in (qp, kp, vp))
            ctx = per_batch_shard(
                flash_attention_bshf, qp, kp, vp, num_heads=H, causal=causal,
                num_kv_heads=H // group, window=attrs.window,
                # padded heads name the TRUE width's scale, a node its own
                scale=attrs.scale if pads else attrs.softmax_scale,
            )
            if pads:
                ctx = _own_columns(ctx, kd)
        return _gated_context(ctx, gate) @ wo2

    if post:
        # the same fused-row projections, then split into heads for the
        # [b, h, s, d] paths below
        qp, kp, vp, wo2 = mha_project_qkv_bshf(
            attrs, q, k, v, weight, input_bias
        )
        qp, gate = _split_output_gate(attrs, qp)
        with _rows_scope(attrs):
            qp, kp, vp = mha_between(attrs, qp, kp, vp, qk_gains)
        vd = attrs.v_proj_size

        def heads(x, d):
            return jnp.swapaxes(x.reshape(*x.shape[:2], H, d), 1, 2)

        qp, kp, vp = heads(qp, kd), heads(kp, kd), heads(vp, vd)
        wo = jnp.transpose(wo2.reshape(H, vd, attrs.embed_dim), (1, 2, 0))
        if gate is not None:
            gate = heads(gate, vd)
    else:
        qp, kp, vp, wo = mha_project_qkv(attrs, q, k, v, weight, input_bias)
        gate = None
    if route == "rows":
        mesh_ctx = context.declared_mesh()
        if mesh_ctx is None:
            ctx = flash_attention(
                qp, kp, vp, causal=causal, scale=attrs.softmax_scale
            )
        else:
            # SPMD trace: a bare pallas_call has no partitioning rule, so
            # flash must go through shard_map
            mesh, batch_axes, head_axes, interpret = mesh_ctx
            ctx = sharded_flash_attention(
                qp, kp, vp, mesh, batch_axes, head_axes,
                causal=causal, interpret=interpret, scale=attrs.softmax_scale,
            )
        return jnp.einsum("bhsv,veh->bse", _gated_context(ctx, gate), wo)
    scores = jnp.einsum("bhsk,bhtk->bhst", qp, kp)
    if attrs.softmax_scale is None:
        scores = scores / jnp.sqrt(jnp.asarray(kd, qp.dtype))
    else:
        scores = scores * jnp.asarray(attrs.softmax_scale, qp.dtype)
    if causal:
        s, t = scores.shape[-2], scores.shape[-1]
        mask = jnp.arange(s)[:, None] >= jnp.arange(t)[None, :]
        if banded:  # the band as a mask
            mask = mask & (
                jnp.arange(s)[:, None] - jnp.arange(t)[None, :] < attrs.window
            )
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
    attn = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,bhtv->bhsv", attn, vp)
    return jnp.einsum("bhsv,veh->bse", _gated_context(ctx, gate), wo)


def forward(
    attrs: OpAttrs,
    inputs: Sequence[jnp.ndarray],
    weights: Sequence[jnp.ndarray] = (),
    *,
    train: bool = False,
    rng: Optional[jax.Array] = None,
) -> List[jnp.ndarray]:
    inputs = list(inputs)
    weights = list(weights)

    if isinstance(attrs, (InputAttrs, WeightAttrs)):
        raise ValueError("input/weight nodes have no kernel; bind their values")

    if isinstance(attrs, NoopAttrs):
        return [inputs[0]]

    if isinstance(attrs, ElementUnaryAttrs):
        x = inputs[0]
        t = attrs.op_type
        if t == ElementUnaryOpType.SCALAR_MULTIPLY:
            return [x * attrs.scalar]
        if t == ElementUnaryOpType.SCALAR_ADD:
            return [x + attrs.scalar]
        if t == ElementUnaryOpType.SCALAR_SUB:
            return [x - attrs.scalar]
        if t == ElementUnaryOpType.SCALAR_TRUE_DIV:
            return [x / attrs.scalar]
        if t == ElementUnaryOpType.POW:
            return [jnp.power(x, attrs.scalar)]
        return [_UNARY_FNS[t](x)]

    if isinstance(attrs, ElementBinaryAttrs):
        return [_BINARY_FNS[attrs.op_type](inputs[0], inputs[1])]

    if isinstance(attrs, CastAttrs):
        return [inputs[0].astype(attrs.dtype.to_jnp())]

    if isinstance(attrs, BroadcastAttrs):
        return [jnp.broadcast_to(inputs[0], attrs.target_dims)]

    if isinstance(attrs, LinearAttrs):
        x = inputs[0]
        if attrs.weight_transposed:  # a tied head: the embedding's matrix
            out = lax.dot_general(
                x, weights[0], (((x.ndim - 1,), (1,)), ((), ()))
            )
        else:
            out = x @ weights[0]
        if attrs.use_bias:
            out = out + weights[1]
        return [_apply_activation(attrs.activation, out)]

    if isinstance(attrs, BatchMatmulAttrs):
        return [jnp.matmul(inputs[0], inputs[1])]

    if isinstance(attrs, EmbeddingAttrs):
        idx = inputs[0]
        table = weights[0]
        out = jnp.take(table, idx, axis=0)
        if attrs.aggr == AggregateSpec.SUM:
            out = out.sum(axis=-2)
        elif attrs.aggr == AggregateSpec.AVG:
            out = out.mean(axis=-2)
        return [out]

    if isinstance(attrs, Conv2DAttrs):
        x = inputs[0]  # NCHW
        kern = weights[0]  # OIHW
        out = lax.conv_general_dilated(
            x,
            kern,
            window_strides=(attrs.stride_h, attrs.stride_w),
            padding=[
                (attrs.padding_h, attrs.padding_h),
                (attrs.padding_w, attrs.padding_w),
            ],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=attrs.groups,
        )
        if attrs.use_bias:
            out = out + weights[1][None, :, None, None]
        return [_apply_activation(attrs.activation, out)]

    if isinstance(attrs, Pool2DAttrs):
        x = inputs[0]
        window = (1, 1, attrs.kernel_h, attrs.kernel_w)
        strides = (1, 1, attrs.stride_h, attrs.stride_w)
        padding = (
            (0, 0),
            (0, 0),
            (attrs.padding_h, attrs.padding_h),
            (attrs.padding_w, attrs.padding_w),
        )
        if attrs.pool_type == PoolOp.MAX:
            out = lax.reduce_window(
                x, -jnp.inf, lax.max, window, strides, padding
            )
        else:
            summed = lax.reduce_window(
                x, 0.0, lax.add, window, strides, padding
            )
            out = summed / (attrs.kernel_h * attrs.kernel_w)
        return [_apply_activation(attrs.activation, out)]

    if isinstance(attrs, FlatAttrs):
        x = inputs[0]
        return [x.reshape(x.shape[0], -1)]

    if isinstance(attrs, BatchNormAttrs):
        x = inputs[0]  # NCHW
        axes = (0, 2, 3) if x.ndim == 4 else (0,)
        mean = x.mean(axis=axes, keepdims=True)
        var = x.var(axis=axes, keepdims=True)
        out = (x - mean) * lax.rsqrt(var + attrs.eps)
        if attrs.affine:
            gamma, beta = weights[0], weights[1]
            shape = (1, -1) + (1,) * (x.ndim - 2)
            out = out * gamma.reshape(shape) + beta.reshape(shape)
        if attrs.relu:
            out = jax.nn.relu(out)
        return [out]

    if isinstance(attrs, LayerNormAttrs):
        x = inputs[0]
        axes = tuple(attrs.axes)
        mean = x.mean(axis=axes, keepdims=True)
        var = x.var(axis=axes, keepdims=True)
        out = (x - mean) * lax.rsqrt(var + attrs.eps)
        if attrs.elementwise_affine:
            gamma, beta = weights[0], weights[1]
            bshape = tuple(
                x.shape[i] if i in axes else 1 for i in range(x.ndim)
            )
            out = out * gamma.reshape(bshape) + beta.reshape(bshape)
        return [out]

    if isinstance(attrs, RMSNormAttrs):
        return [
            rms_norm(inputs[0], weights[0], attrs.eps, attrs.zero_centered)
        ]

    if isinstance(attrs, SoftmaxAttrs):
        return [jax.nn.softmax(inputs[0], axis=attrs.dim)]

    if isinstance(attrs, DropoutAttrs):
        x = inputs[0]
        if not train or attrs.rate == 0.0:
            return [x]
        assert rng is not None, "dropout in train mode needs an rng key"
        keep = 1.0 - attrs.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0)]

    if isinstance(attrs, MultiHeadAttentionAttrs):
        # RingAttentionAttrs subclasses MHA: without a mesh context this is
        # the dense single-device fallback (exact same math; the sharded ring
        # schedule lives in kernels/ring_attention.py and is chosen by the
        # distributed executor)
        from flexflow_tpu.op_attrs.ops.ring_attention import RingAttentionAttrs

        q, k, v = inputs
        if attrs.differential:
            return _differential_forward(attrs, q, k, v, weights)
        input_bias = weights[1] if attrs.bias else None
        causal = isinstance(attrs, RingAttentionAttrs) and attrs.causal
        if attrs.latent:  # self-attention: reads its first input
            return [_latent_mha_forward(
                attrs, q, weights[0], weights[1], causal,
                q_gain=weights[2] if attrs.q_latent_rank is not None else None,
            )]
        out = _mha_forward(
            attrs, q, k, v, weights[0], input_bias, causal=causal,
            qk_gains=weights[-2:] if attrs.qk_norm else None,
        )
        if attrs.bias:
            out = out + weights[2]
        return [out]

    from flexflow_tpu.op_attrs.ops.loss_functions import (
        LabelCrossEntropyAttrs,
        MeanLossAttrs,
    )

    if isinstance(attrs, LabelCrossEntropyAttrs):
        from flexflow_tpu.kernels.loss import label_cross_entropy

        return [label_cross_entropy(attrs, *inputs)]

    if isinstance(attrs, MeanLossAttrs):
        from flexflow_tpu.kernels.loss import mean_loss

        return [mean_loss(attrs, inputs[0])]

    if isinstance(attrs, ConcatAttrs):
        return [jnp.concatenate(inputs, axis=attrs.axis)]

    if isinstance(attrs, StackAttrs):
        # NOT jnp.stack: the branch-parallel plans shard the new leading
        # axis, and XLA's SPMD partitioner miscompiles a concatenate whose
        # concat dim is sharded downstream (jax 0.4.37 CPU: wrong shards
        # reach the consumer; see test_branch_stacking). A dynamic-update-
        # slice build partitions by mask+select and stays correct.
        out = jnp.zeros((len(inputs),) + inputs[0].shape, inputs[0].dtype)
        for i, v in enumerate(inputs):
            out = out.at[i].set(v)
        return [out]

    if isinstance(attrs, SplitAttrs):
        a = attrs.axis % inputs[0].ndim
        offs = []
        acc = 0
        for s in attrs.sizes[:-1]:
            acc += s
            offs.append(acc)
        return list(jnp.split(inputs[0], offs, axis=a))

    if isinstance(attrs, ReshapeAttrs):
        return [inputs[0].reshape(attrs.shape)]

    if isinstance(attrs, TransposeAttrs):
        return [jnp.transpose(inputs[0], attrs.perm)]

    if isinstance(attrs, ReverseAttrs):
        return [jnp.flip(inputs[0], axis=attrs.axis)]

    if isinstance(attrs, GatherAttrs):
        return [jnp.take_along_axis(inputs[0], inputs[1], axis=attrs.dim)]

    if isinstance(attrs, TopKAttrs):
        values, indices = lax.top_k(inputs[0], attrs.k)
        return [values, indices.astype(jnp.int32)]

    if isinstance(attrs, ReduceAttrs):
        x = inputs[0]
        axes = tuple(a % x.ndim for a in attrs.axes)
        fn = {
            ReduceOpType.SUM: jnp.sum,
            ReduceOpType.MEAN: jnp.mean,
            ReduceOpType.MAX: jnp.max,
            ReduceOpType.MIN: jnp.min,
            ReduceOpType.PROD: jnp.prod,
        }[attrs.op_type]
        out = fn(x, axis=axes, keepdims=attrs.keepdims)
        if out.ndim == 0:
            out = out.reshape(1)
        return [out]

    from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs

    if isinstance(attrs, StateSpaceAttrs):
        from flexflow_tpu.kernels.ssm import state_space_forward

        _note_scan_blocks(attrs, inputs[0])
        return [state_space_forward(attrs, inputs[0], weights)]

    from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs

    if isinstance(attrs, GatedDeltaAttrs):
        from flexflow_tpu.kernels.kda import gated_delta_forward

        return [gated_delta_forward(attrs, inputs[0], weights)]

    from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs

    if isinstance(attrs, ShortConvAttrs):
        from flexflow_tpu.kernels.short_conv import short_conv_forward

        return [short_conv_forward(attrs, inputs[0], weights)]

    from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs

    if isinstance(attrs, SelectiveScanAttrs):
        from flexflow_tpu.kernels.selective_scan import selective_scan_forward

        return selective_scan_forward(attrs, inputs[0], weights)

    from flexflow_tpu.op_attrs.ops.moe import (
        AggregateAttrs,
        ExpertsAttrs,
        GroupByAttrs,
    )

    if isinstance(attrs, (GroupByAttrs, AggregateAttrs, ExpertsAttrs)):
        from flexflow_tpu.kernels import moe as moe_kernels

        if isinstance(attrs, GroupByAttrs):
            return moe_kernels.group_by_forward(attrs, inputs[0], inputs[1])
        if isinstance(attrs, AggregateAttrs):
            return [
                moe_kernels.aggregate_forward(
                    attrs, inputs[0], inputs[1], inputs[2:]
                )
            ]
        return moe_kernels.experts_forward(attrs, inputs[0], weights)

    # Parallel ops: local identity; cross-device movement is inserted by the
    # distributed lowering (reference: combine_kernels.cu is a device copy,
    # movement is Legion's job — SURVEY.md §2.4 parallel-op kernels row).
    if isinstance(attrs, (RepartitionAttrs, CombineAttrs, ReplicateAttrs, ReductionAttrs)):
        return [inputs[0]]

    # Stage ops: identity on global values — the microbatch schedule is a
    # lowering choice (parallel/pipeline.py), not a value transformation,
    # so the flat executor stays correct on pipelined PCGs.
    if isinstance(attrs, (StagePartitionAttrs, StageMergeAttrs)):
        return [inputs[0]]

    raise TypeError(f"no kernel for {type(attrs).__name__}")


def _experts_rows(attrs, tokens: int, weight_shapes=None) -> int:
    """Rows the Experts op's grouped matmuls run: the active experts only,
    N*k dispatched decisions (fewer where a finite capacity drops some), not
    E x capacity buffers. With `weight_shapes` (the cost model's per-device
    pieces: the gate table, then [e_local, ...] expert tensors) the piece
    owns e_local / e of the rows, and each of its experts pads its rows to
    whole 128-row MXU tiles, half a tile on average: at a few rows an expert
    that is what the grouped matmul costs, and what expert parallelism
    divides."""
    from flexflow_tpu.op_attrs.ops.moe import expert_capacity

    e = attrs.num_experts
    rows = tokens * attrs.num_select
    if attrs.capacity_factor is not None:
        cap = expert_capacity(
            tokens, e, attrs.num_select, attrs.capacity_factor
        )
        rows = min(rows, e * cap)
    if weight_shapes and len(weight_shapes) > 1:
        e_local = weight_shapes[attrs.weight_roles().index("expert")].dims[0]
        rows = rows * e_local // e + 64 * e_local
    elif attrs.held_experts is not None:
        rows = rows * attrs.num_local_experts // e
    return rows


def op_internal_bytes(attrs: OpAttrs, input_shapes, weight_shapes=None) -> int:
    """Bytes an op writes and reads again INSIDE its node, beyond its
    inputs, weights and outputs (which the cost model counts itself). Only
    the Experts op has any worth counting: its dispatched rows, gathered
    [rows, D], the hidden [rows, H] (twice in the gated form) and the
    experts' output [rows, out], each written once and read once. At the
    published sizes this is more traffic than the expert weights."""
    from flexflow_tpu.op_attrs.ops.moe import ExpertsAttrs
    from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs

    if isinstance(attrs, StateSpaceAttrs):
        # the state-space node's own tensors: the input projection's row,
        # the convolved x | B | C, the scan's y and the gated norm's output
        x = input_shapes[0]
        tokens = int(x.num_elements) // x.dims[-1]
        width = attrs.in_proj_width + attrs.conv_width + 2 * attrs.inner
        return 2 * tokens * width * x.dtype.size_bytes
    from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs

    if isinstance(attrs, GatedDeltaAttrs):
        # the input projection's row, the convolved q | k | v, the two
        # gates' up-projections, the recurrence's o and the gated norm's
        # output
        x = input_shapes[0]
        tokens = int(x.num_elements) // x.dims[-1]
        width = (
            attrs.in_proj_width + attrs.conv_width + attrs.key_width
            + 3 * attrs.value_width
        )
        return 2 * tokens * width * x.dtype.size_bytes
    from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs

    if isinstance(attrs, ShortConvAttrs):
        # the projection's row B | C | z, the gated input, its convolution
        # and the gated row the output projection reads
        x = input_shapes[0]
        tokens = int(x.num_elements) // x.dims[-1]
        return 2 * tokens * 6 * attrs.width * x.dtype.size_bytes
    from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs

    if isinstance(attrs, SelectiveScanAttrs):
        # the projection's row x | z, the convolution's result, the step's
        # pre-activation, the scan's result and the gated row
        x = input_shapes[0]
        tokens = int(x.num_elements) // x.dims[-1]
        return 2 * tokens * 6 * attrs.channels * x.dtype.size_bytes
    if not isinstance(attrs, ExpertsAttrs):
        return 0
    x = input_shapes[0]
    d = x.dims[-1]
    tokens = int(x.num_elements) // d
    rows = _experts_rows(attrs, tokens, weight_shapes)
    forms = 2 if attrs.gated else 1
    latent = attrs.latent_size
    # a dispatched row is as wide as what the experts read and write; the
    # latent image of every token and the combined latent rows besides
    width = (
        (latent or d) + forms * attrs.hidden_size
        + (latent or attrs.out_channels or d)
    )
    per_token = forms * attrs.shared_hidden_size + 2 * (latent or 0)
    return 2 * (rows * width + tokens * per_token) * x.dtype.size_bytes


def op_forward_flops(
    attrs: OpAttrs,
    input_shapes,
    output_shapes,
    weight_shapes=None,
    seq_parallel_degree: int = 1,
) -> int:
    """Analytic forward FLOPs (for MFU accounting and the analytic cost model).

    Matmul-class ops count 2*M*N*K; elementwise ops count one flop per output
    element. `weight_shapes` (per-device weight PIECE shapes) lets the cost
    model credit parameter-sharded pieces: a column-parallel Linear, a
    head-parallel attention, or an expert-parallel Experts op does
    proportionally less local compute than its attrs (out_channels /
    num_heads / num_experts describe the GLOBAL operator) imply. Omitted =
    unsharded weights (the MFU accounting path, which wants global FLOPs).
    """
    import numpy as np

    def nelem(shape):
        return int(np.prod(shape.dims))

    if isinstance(attrs, LinearAttrs):
        x = input_shapes[0]
        batch = nelem(x) // x.dims[-1]
        out_ch = attrs.out_channels
        if weight_shapes:  # [in, out/k] piece of a column-parallel linear
            out_ch = weight_shapes[0].dims[1]
        return 2 * batch * x.dims[-1] * out_ch

    if isinstance(attrs, BatchMatmulAttrs):
        a, b = input_shapes[0], input_shapes[1]
        batch = int(np.prod(a.dims[:-2]))
        return 2 * batch * a.dims[-2] * a.dims[-1] * b.dims[-1]

    if isinstance(attrs, Conv2DAttrs):
        out = output_shapes[0]
        cin = input_shapes[0].dims[1]
        flops = (
            2
            * nelem(out)
            * (cin // attrs.groups)
            * attrs.kernel_h
            * attrs.kernel_w
        )
        if weight_shapes:  # [out/k, in/g, kh, kw] channel-parallel piece
            flops = flops * weight_shapes[0].dims[0] // attrs.out_channels
        return flops

    if isinstance(attrs, MultiHeadAttentionAttrs):
        from flexflow_tpu.op_attrs.ops.ring_attention import RingAttentionAttrs

        q = input_shapes[0]
        b, s, e = q.dims
        kd, vd, H = attrs.q_proj_size, attrs.v_proj_size, attrs.num_heads
        if weight_shapes and not attrs.grouped_query:
            H = weight_shapes[0].dims[1]  # [per-head params, H/k] piece
        # grouped-query heads project kv_heads keys and values, not H
        KV = attrs.kv_heads * H // attrs.num_heads
        # with an output gate a head's query columns come with a gate's
        proj = (
            2 * b * s * e * (attrs.q_columns * H + (kd + vd) * KV)
            + 2 * b * s * vd * attrs.embed_dim * H
        )
        if attrs.latent:
            rank, qr = attrs.kv_latent_rank, attrs.q_latent_rank
            proj = 2 * b * s * (
                (e * kd * H if qr is None else qr * (e + kd * H))
                + e * (rank + attrs.shared_key_dim)
                + rank * H * (attrs.own_key_dim + vd)
                + vd * H * attrs.embed_dim
            )
        scores = 2 * b * H * s * s * kd + 2 * b * H * s * s * vd
        if isinstance(attrs, RingAttentionAttrs) and seq_parallel_degree > 1:
            # the piece sees s/k queries but attends ALL k K/V blocks (ring
            # rotation; Ulysses trades heads for full seq) — per-device
            # score work is (s/k)*s, i.e. k times the (s/k)^2 piece formula
            scores *= seq_parallel_degree
        return proj + scores

    if isinstance(attrs, EmbeddingAttrs):
        return 0

    from flexflow_tpu.op_attrs.ops.moe import ExpertsAttrs

    if isinstance(attrs, ExpertsAttrs):
        x = input_shapes[0]
        d = x.dims[-1]
        n = nelem(x) // d
        h = attrs.hidden_size
        o = attrs.out_channels or d
        rows = _experts_rows(attrs, n, weight_shapes)
        gate = 2 * n * d * attrs.num_experts  # every device gates its tokens
        forms = 2 if attrs.gated else 1
        latent = attrs.latent_size
        mlp = 2 * rows * (forms * (latent or d) * h + h * (latent or o))
        # the shared expert and the latent projections see every token
        hs = attrs.shared_hidden_size
        dense = forms * d * hs + hs * o + (latent or 0) * (d + o)
        return gate + mlp + 2 * n * dense

    from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs

    if isinstance(attrs, StateSpaceAttrs):
        b, s, d = input_shapes[0].dims
        q, p, n = attrs.chunk_size, attrs.head_dim, attrs.state_size
        proj = 2 * b * s * d * (attrs.in_proj_width + attrs.inner)
        # a position's share of the chunked scan: C.B over the chunk once a
        # group, the masked [q, q] x [q, p] product and the state's two
        # [p, n] products a head
        scan = b * s * (
            attrs.num_groups * 2 * q * n
            + attrs.num_heads * (2 * q * p + 4 * p * n)
        )
        return proj + scan + 2 * b * s * attrs.conv_kernel * attrs.conv_width

    from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs

    if isinstance(attrs, GatedDeltaAttrs):
        b, s, d = input_shapes[0].dims
        q, dk, dv = attrs.chunk_size, attrs.key_dim, attrs.value_dim
        proj = 2 * b * s * (
            d * (attrs.in_proj_width + attrs.value_width)
            + (
                # b | a out of the input, or the two low-rank gates up
                d * 2 * attrs.num_heads if attrs.per_head_decay
                else attrs.gate_rank * (attrs.key_width + attrs.value_width)
            )
        )
        # a position's share of a chunk, a head: both decayed score
        # matrices and T's two products over the chunk's rows, and the
        # state's four [dk, dv] products
        scan = b * s * attrs.num_heads * (
            2 * q * (2 * dk + dk + dv) + 2 * q * dv + 4 * 2 * dk * dv
        )
        return proj + scan + 2 * b * s * attrs.conv_kernel * attrs.conv_width

    from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs

    if isinstance(attrs, ShortConvAttrs):
        b, s, d = input_shapes[0].dims
        # the two projections, the taps and the two gates
        return b * s * attrs.width * (
            2 * d * 4 + 2 * attrs.conv_kernel + 2
        )

    from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs

    if isinstance(attrs, SelectiveScanAttrs):
        b, s, d = input_shapes[0].dims
        w, n, rank = attrs.channels, attrs.state_size, attrs.rank_for(d)
        # the four projections, the taps, about 9 operations a state update
        # and the gate
        return b * s * (
            2 * d * 2 * w + 2 * w * (rank + 2 * n) + 2 * rank * w + 2 * w * d
            + w * (2 * attrs.conv_kernel + 9 * n + 2)
        )

    from flexflow_tpu.op_attrs.ops.loss_functions import (
        LabelCrossEntropyAttrs,
        MeanLossAttrs,
    )

    if isinstance(attrs, LabelCrossEntropyAttrs):
        # a maximum, an exponential, two sums and a pick an element of the
        # logits, whose one scalar says nothing of the passes
        return 5 * nelem(input_shapes[0])
    if isinstance(attrs, MeanLossAttrs):
        return nelem(input_shapes[0])

    total = sum(nelem(s) for s in output_shapes)
    return total
