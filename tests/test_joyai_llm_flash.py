"""Latent attention with a low-rank query and a rotary on the shared key slice,
the loss node, and the multi-token-prediction step
(`benchmark/configs/joyai-llm-flash.py`) through the public builder and
`FFModel.compile -> fit`, each part against the plain float32 reference that
lives with the configuration, at toy size on the CPU with seeded weights.
Every tolerance states its reason."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_nemotron_h import (
    BENCH, F32, F32_LOSS, assert_trees_close, bench, rand,
)

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import flash_attention as flash
from flexflow_tpu.kernels import forward as kernel_forward
from flexflow_tpu.kernels.loss import label_cross_entropy
from flexflow_tpu.kernels.moe import experts_forward
from flexflow_tpu.kernels.ops import (
    deinterleaved_columns,
    mha_core_route,
    rope_halves,
    rope_tables,
)
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.core import (
    OperatorType,
    get_default_weight_initializers,
    get_incoming_tensor_roles,
    get_output_shapes,
    get_parallel_output_shapes,
    get_parallel_weight_shapes,
    get_weight_shapes,
    op_type_of,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    ExpertsAttrs,
    LabelCrossEntropyAttrs,
    MultiHeadAttentionAttrs,
    RingAttentionAttrs,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape

CONFIG = os.path.join(BENCH, "configs", "joyai-llm-flash")
ref = bench.load_module(CONFIG + ".py")

# 4 heads of 8 | 4 key and 8 value columns from a latent row of 12, a query
# rank of 20; 4 held of 16 SwiGLU experts of width 24 (top 3) beside a shared
# one; the dense layer, two expert layers and the module
TOY = dict(
    bench.load_json(CONFIG + ".json"),
    hidden_size=32, num_attention_heads=4, num_key_value_heads=4,
    q_lora_rank=20, kv_lora_rank=12, qk_nope_head_dim=8, qk_rope_head_dim=4,
    qk_head_dim=12, v_head_dim=8, intermediate_size=48,
    moe_intermediate_size=24, n_routed_experts=4, num_experts_total=16,
    held_experts_first=4, num_experts_per_tok=3, num_hidden_layers=3,
    vocab_rows_held=96, rope_theta=100.0,
    # ten times the published deviation, as in the other towers' tests: at
    # toy width 0.02 leaves every activation so small that a wrong term
    # would hide inside a tolerance
    initializer_range=0.2,
)
BATCH = 4
ADAM = TOY["training"]

# gradients through the softmax, two norms and five projections in float32
# on the CPU: sums of a few hundred products in another order than the
# reference's, on gradients of up to a hundred
F32_GRADS = dict(rtol=1e-3, atol=1e-3)


# -- the latent node --------------------------------------------------------------


def latent_attrs(sizes=TOY, q_rank=True, rope=True, interleaved=True):
    return RingAttentionAttrs(
        sizes["hidden_size"], sizes["num_attention_heads"],
        kdim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        vdim=sizes["v_head_dim"], causal=True,
        rope_theta=float(sizes["rope_theta"]) if rope else None,
        kv_latent_rank=sizes["kv_lora_rank"],
        shared_key_dim=sizes["qk_rope_head_dim"],
        kv_latent_norm_eps=sizes["rms_norm_eps"],
        q_latent_rank=sizes["q_lora_rank"] if q_rank else None,
        q_latent_norm_eps=sizes["rms_norm_eps"],
        rope_interleaved=rope and interleaved,
    )


def latent_case(attrs, seq, seed=6):
    """(u [b, s, D], the op's weights in slot order)."""
    rs = np.random.RandomState(seed)
    x = TensorShape((BATCH, seq, attrs.embed_dim), DataType.FLOAT)
    shapes = get_weight_shapes(attrs, [x, x, x])
    ws = [rand(rs, *shapes[0].dims, scale=0.3)] + [
        1.0 + rand(rs, *shape.dims, scale=0.2) for shape in shapes[1:]
    ]
    return rand(rs, BATCH, seq, attrs.embed_dim), ws


def program_mla(attrs, u, ws):
    with jax.default_matmul_precision("highest"):
        return kernel_forward(attrs, [u, u, u], ws)[0]


def reference_mla(attrs, u, ws, sizes=TOY):
    """`ref.mla` where the node has both a query rank and the interleaved
    rotary (the published form); the other forms from the same pieces: a
    full-rank query, no rotation."""
    heads, rank = attrs.num_heads, attrs.kv_latent_rank
    nope, rope_w, vd = attrs.own_key_dim, attrs.shared_key_dim, attrs.v_proj_size
    hidden, eps = attrs.embed_dim, sizes["rms_norm_eps"]
    if attrs.q_latent_rank is not None and attrs.rope_interleaved:
        named = {f"m.weight{i}": w for i, w in enumerate(ws)}
        with jax.default_matmul_precision("highest"):
            return jnp.stack([ref.mla(named, "m", row, sizes) for row in u])
    flat = ws[0].reshape(-1)
    qr = attrs.q_latent_rank
    sizes_q = (
        [hidden * heads * (nope + rope_w)] if qr is None
        else [hidden * qr, qr * heads * (nope + rope_w)]
    )
    cuts = np.cumsum([0] + sizes_q + [
        hidden * (rank + rope_w), rank * heads * (nope + vd),
        heads * vd * hidden,
    ])
    pieces = [flat[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    turn = (
        (lambda t: ref.rotary_pairs(t, float(sizes["rope_theta"])))
        if attrs.rope_theta is not None else (lambda t: t)
    )
    assert attrs.rope_theta is None or attrs.rope_interleaved

    def one(row):
        if qr is None:
            q = jnp.einsum(
                "se,ehd->hsd", row,
                pieces[0].reshape(hidden, heads, nope + rope_w),
            )
        else:
            c_q = ref.rms(row @ pieces[0].reshape(hidden, qr), ws[2], eps)
            q = jnp.einsum(
                "sr,rhd->hsd", c_q, pieces[1].reshape(qr, heads, nope + rope_w)
            )
        q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], axis=-1)
        low = row @ pieces[-3].reshape(hidden, rank + rope_w)
        kv = jnp.einsum(
            "sr,rhd->hsd", ref.rms(low[:, :rank], ws[1], eps),
            pieces[-2].reshape(rank, heads, nope + vd),
        )
        k = jnp.concatenate([
            kv[..., :nope],
            jnp.broadcast_to(turn(low[:, rank:])[None], (heads,) + low[:, rank:].shape),
        ], axis=-1)
        ctx = ref.causal_attention(q, k, kv[..., nope:])
        return jnp.einsum("hsd,hde->se", ctx, pieces[-1].reshape(heads, vd, hidden))

    with jax.default_matmul_precision("highest"):
        return jnp.stack([one(row) for row in u])


def test_latent_slots_with_a_query_rank_and_a_rotary():
    attrs = latent_attrs()
    x = TensorShape((2, 16, 32), DataType.FLOAT)
    shapes = get_weight_shapes(attrs, [x, x, x])
    # W_qa 32 x 20 | W_qb 20 x 4*12 | W_kva 32 x (12+4) | W_kvb 12 x 4*(8+8) | W_o 4*8 x 32
    assert shapes[0].dims == (640 + 960 + 512 + 768 + 1024, 1)
    assert [s.dims for s in shapes[1:]] == [(12,), (20,)]  # g_kv, then g_q
    assert len(get_incoming_tensor_roles(attrs)) == 3 + 3
    inits = get_default_weight_initializers(attrs, 3)
    assert inits[0] is None and all(i.value == 1.0 for i in inits[1:])
    # without the rank: Kimi's node, slot for slot
    plain = latent_attrs(q_rank=False, rope=False)
    assert [s.dims for s in get_weight_shapes(plain, [x, x, x])] == [
        (1536 + 512 + 768 + 1024, 1), (12,)
    ]
    assert plain == RingAttentionAttrs(
        32, 4, kdim=12, vdim=8, causal=True, kv_latent_rank=12,
        shared_key_dim=4, kv_latent_norm_eps=TOY["rms_norm_eps"],
        q_latent_norm_eps=TOY["rms_norm_eps"],
    )


def test_new_attributes_are_refused_where_they_mean_nothing():
    with pytest.raises(AssertionError, match="latent attention's"):
        MultiHeadAttentionAttrs(32, 4, q_latent_rank=8)
    with pytest.raises(AssertionError, match="latent attention's"):
        MultiHeadAttentionAttrs(32, 4, rope_theta=1e4, rope_interleaved=True)
    with pytest.raises(AssertionError, match="needs one"):
        RingAttentionAttrs(32, 4, kdim=12, vdim=8, kv_latent_rank=12,
                           shared_key_dim=4, rope_interleaved=True)
    with pytest.raises(AssertionError, match="even shared_key_dim"):
        RingAttentionAttrs(32, 4, kdim=12, vdim=8, kv_latent_rank=12,
                           shared_key_dim=0, rope_theta=1e4)
    with pytest.raises(AssertionError, match="as wide as the shared key slice"):
        RingAttentionAttrs(32, 4, kdim=12, vdim=8, kv_latent_rank=12,
                           shared_key_dim=4, rope_theta=1e4, rotary_dim=4)


@pytest.mark.parametrize("seq", [20, 33])
@pytest.mark.parametrize("rope", [True, False])
@pytest.mark.parametrize("q_rank", [True, False])
def test_latent_node_matches_the_reference(q_rank, rope, seq):
    """Forward, and the gradients of the input and of every weight, with and
    without the query rank and the rotary, at an even and an odd length."""
    attrs = latent_attrs(q_rank=q_rank, rope=rope)
    u, ws = latent_case(attrs, seq)
    np.testing.assert_allclose(
        program_mla(attrs, u, ws), reference_mla(attrs, u, ws), **F32
    )
    cot = rand(np.random.RandomState(8), *u.shape)

    def grads(fn):
        return jax.grad(
            lambda u, ws: jnp.sum(fn(attrs, u, ws) * cot), argnums=(0, 1)
        )(u, ws)

    got, want = grads(program_mla), grads(reference_mla)
    assert all(float(jnp.max(jnp.abs(g))) > 1e-3 for g in want[1])
    assert_trees_close(got, want, **F32_GRADS)


def kimi_node_as_it_was(attrs, x, weight, gain):
    """`kernels/ops._latent_mha_forward` as PR 43 wrote it and PR 52 left it
    (the dense route, which is every CPU's), copied here: what the node with
    neither a query rank nor a rotary has to lower to."""
    from flexflow_tpu.kernels.ops import _unpack_flat, rms_norm

    H, rank, shared = attrs.num_heads, attrs.kv_latent_rank, attrs.shared_key_dim
    kd, vd, own = attrs.q_proj_size, attrs.v_proj_size, attrs.own_key_dim
    b, s, e = x.shape
    wq, wkv_a, wkv_b, wo = _unpack_flat(weight, [
        (e, H * kd), (e, rank + shared), (rank, H * (own + vd)),
        (H * vd, attrs.embed_dim),
    ])
    with jax.named_scope("latent"):
        low = x @ wkv_a
        c = rms_norm(low[..., :rank], gain, attrs.kv_latent_norm_eps)
        kv = (c @ wkv_b).reshape(b, s, H, own + vd)
        parts = [
            kv[..., :own],
            jnp.broadcast_to(low[:, :, None, rank:], (b, s, H, shared)),
        ]
        v = kv[..., own:]
    q = (x @ wq).reshape(b, s, H, kd)
    with jax.named_scope("core"):
        scores = jnp.einsum(
            "bshk,bthk->bhst", q, jnp.concatenate(parts, axis=-1)
        ) / jnp.sqrt(jnp.asarray(kd, q.dtype))
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
        ctx = jnp.einsum("bhst,bthv->bshv", jax.nn.softmax(scores, axis=-1), v)
    return ctx.reshape(b, s, H * vd) @ wo


def test_with_both_off_the_node_lowers_to_the_text_it_had():
    """Defaults leave Kimi's node alone: with neither a query rank nor a
    rotary the node lowers, forward and backward, to the StableHLO of the
    function as it was before this PR, operation for operation."""
    attrs = latent_attrs(q_rank=False, rope=False)
    u, ws = latent_case(attrs, 24)

    def text(fn):
        def both(u, w, g):
            out, vjp = jax.vjp(fn, u, w, g)
            return out, vjp(out)

        return jax.jit(both).lower(u, *ws).as_text()

    now = text(lambda u, w, g: kernel_forward(attrs, [u, u, u], [w, g])[0])
    was = text(lambda u, w, g: kimi_node_as_it_was(attrs, u, w, g))
    assert now == was
    assert "cosine" not in now
    rotated = latent_attrs()
    u, ws = latent_case(rotated, 24)
    assert "cosine" in jax.jit(
        lambda u, *ws: kernel_forward(rotated, [u, u, u], list(ws))[0]
    ).lower(u, *ws).as_text()


def test_scores_depend_on_positions_through_their_difference():
    """Rotating q_r and k_r by their own positions makes a score a function
    of the distance alone: the same rows turned at positions 7 further on
    (the node's own rotary, behind 7 rows of padding) give the same scores."""
    rs = np.random.RandomState(3)
    s, shift, h, w = 12, 7, 2, 8
    q, k = rand(rs, 1, s, h, w), rand(rs, 1, s, w)

    def scores(q, k):
        return jnp.einsum("bshd,btd->bhst", q, k)

    def turned(x, lead):
        rows = jnp.concatenate(
            [jnp.zeros((1, lead) + x.shape[2:]), x], axis=1
        )
        cos, sin = rope_tables(s + lead, w, 50.0)
        return rope_halves(rows, cos, sin)[:, lead:]

    near = scores(turned(q, 0), turned(k, 0))
    far = scores(turned(q, shift), turned(k, shift))
    assert float(jnp.max(jnp.abs(near))) > 0.1
    np.testing.assert_allclose(near, far, rtol=1e-4, atol=1e-4)
    # and without the rotation's position the scores would not be these
    assert float(jnp.max(jnp.abs(scores(q, k) - near))) > 0.1


def test_interleaved_pairs_are_rotate_half_on_permuted_weights():
    """The pairing (2j, 2j + 1) on the weights as published against the
    de-interleaved rotate-half form on weights whose rotary columns are
    permuted evens-first: the same node output."""
    attrs = latent_attrs()
    halves = latent_attrs(interleaved=False)
    u, ws = latent_case(attrs, 20, seed=9)
    hidden, heads, rank, qr = 32, 4, 12, 20
    nope, rope_w = 8, 4
    perm = np.concatenate([np.arange(0, rope_w, 2), np.arange(1, rope_w, 2)])
    flat = np.asarray(ws[0]).reshape(-1).copy()
    a = hidden * qr
    wq_b = flat[a:a + qr * heads * 12].reshape(qr, heads, 12).copy()
    wq_b[..., nope:] = wq_b[..., nope:][..., perm]
    flat[a:a + qr * heads * 12] = wq_b.reshape(-1)
    b = a + qr * heads * 12
    wkv_a = flat[b:b + hidden * 16].reshape(hidden, 16).copy()
    wkv_a[:, rank:] = wkv_a[:, rank:][:, perm]
    flat[b:b + hidden * 16] = wkv_a.reshape(-1)
    permuted = [jnp.asarray(flat).reshape(ws[0].shape)] + ws[1:]
    np.testing.assert_allclose(
        program_mla(halves, u, permuted), program_mla(attrs, u, ws), **F32
    )
    # the two pairings on the SAME weights are different functions
    assert float(jnp.max(jnp.abs(
        program_mla(halves, u, ws) - program_mla(attrs, u, ws)
    ))) > 1e-3


def test_rotary_by_hand_and_the_deinterleaving_of_a_head_block():
    """Halves (j, j + w/2) of a row at position p by p theta^(-2j/w); the
    reordering of a head block's slice columns evens first; and the two
    together against the pairing (2j, 2j + 1) written out, which is what the
    node does to what the published weights produce."""
    rs = np.random.RandomState(0)
    x = np.asarray(rand(rs, 1, 5, 3, 8))
    cos, sin = rope_tables(5, 8, 100.0)
    got = np.asarray(rope_halves(jnp.asarray(x), cos, sin))
    want = x.copy()
    for p in range(5):
        for j in range(4):
            angle = p * 100.0 ** (-2 * j / 8)
            a, b = x[0, p, :, j], x[0, p, :, j + 4]
            want[0, p, :, j] = a * np.cos(angle) - b * np.sin(angle)
            want[0, p, :, j + 4] = b * np.cos(angle) + a * np.sin(angle)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    w = jnp.arange(2 * 3 * 12, dtype=jnp.float32).reshape(2, 36)
    block = np.asarray(deinterleaved_columns(w, 3, 4, 8)).reshape(2, 3, 12)
    np.testing.assert_array_equal(
        block[0, 1], 12 + np.array([0, 1, 2, 3, 4, 6, 8, 10, 5, 7, 9, 11])
    )
    # interleaved pairs of a row = halves of the de-interleaved row, put back
    row = np.asarray(rand(rs, 1, 5, 8))
    perm = np.array([0, 2, 4, 6, 1, 3, 5, 7])
    halves = np.asarray(rope_halves(jnp.asarray(row[..., perm]), cos, sin))
    back = np.empty_like(halves)
    back[..., perm] = halves
    np.testing.assert_allclose(
        back, np.asarray(ref.rotary_pairs(jnp.asarray(row), 100.0)),
        rtol=1e-5, atol=1e-5,
    )


def test_wide_key_route_at_8192_takes_the_forward_with_its_own_limit(monkeypatch, entered):
    attrs = latent_attrs(bench.load_json(CONFIG + ".json"))
    shape = (1, 8192, 2048)
    assert mha_core_route(attrs, shape, shape, shape, True) == "dense"  # the CPU
    entered(context.described_tpu())
    assert mha_core_route(attrs, shape, shape, shape, True) == "fused_row"
    # [8192, 256] and [8192, 128] in bf16, double-buffered, are the budget
    def forward(s, dk, dv):
        plan = flash.causal_plan(1, s, 32, 32, dk, dv, 2)
        return plan.fwd_name, plan.vmem_limit

    limit = flash._CAUSAL_VMEM_LIMIT
    assert forward(8192, 256, 128) == ("flash_fwd_causal_wide_key", limit)
    # every shape that ran before keeps its forward: Kimi's 4,096 positions,
    # LFM2's padded heads of 64 at 8,192
    assert forward(4096, 256, 128) == ("flash_fwd_causal_bshf", None)
    assert forward(8192, 128, 128) == ("flash_fwd_causal_bshf", None)


def test_long_row_forward_matches_the_wide_key_entry(monkeypatch):
    """The forward that names its own limit is the same body: at two causal
    tiles in interpret mode its output and gradients are the folded
    forward's, to the bit."""
    rs = np.random.RandomState(11)
    b, s, h, kd, vd = 1, 1024, 2, 256, 128
    q, k = rand(rs, b, s, h * kd, scale=0.5), rand(rs, b, s, h * kd, scale=0.5)
    v, cot = rand(rs, b, s, h * vd), rand(rs, b, s, h * vd)

    def run():
        return jax.value_and_grad(
            lambda *a: jnp.sum(flash.flash_attention_bshf(
                *a, h, causal=True, scale=192 ** -0.5, interpret=True
            ) * cot), argnums=(0, 1, 2),
        )(q, k, v)

    plain = run()
    monkeypatch.setattr(flash, "_SCOPED_ROWS_BUDGET", 0)
    assert flash.causal_plan(b, s, h, h, kd, vd, 4).fwd_name == (
        "flash_fwd_causal_wide_key"
    )
    assert_trees_close(run(), plain, rtol=0, atol=0)


def test_the_latent_form_is_counted_by_node():
    attrs = latent_attrs()
    u, ws = latent_case(attrs, 20)

    class Graph:
        def layer_attrs(self, n):
            from flexflow_tpu.pcg.computation_graph import LayerAttrs

            return LayerAttrs(attrs, "mla_counted")

    class Node:
        idx = 0

    with trace.node_scope(Graph(), Node()):
        program_mla(attrs, u, ws)
    assert trace.latent_attention_forms()["ff.ring_attention.mla_counted"] == {
        "query_rank": 20, "rotated_columns": 4, "pairing": "interleaved",
        "core": "dense",
    }


# -- the loss node ---------------------------------------------------------------


def plain_masked_cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)


def loss_case(seed=2):
    rs = np.random.RandomState(seed)
    labels = rs.randint(0, 11, (3, 7)).astype(np.int32)
    labels[:, -1] = -1  # a position without a target
    labels[1, 2] = -1
    return rand(rs, 3, 7, 11), jnp.asarray(labels)


def test_loss_node_is_a_masked_mean_times_its_weight():
    logits, labels = loss_case()
    attrs = LabelCrossEntropyAttrs(weight=0.3)
    got = label_cross_entropy(attrs, logits, labels)
    want = plain_masked_cross_entropy(logits, labels)
    assert got.shape == (1,) and got.dtype == jnp.float32
    np.testing.assert_allclose(got[0], 0.3 * want, rtol=1e-6)
    grad = jax.grad(lambda x: label_cross_entropy(attrs, x, labels)[0])(logits)
    want_grad = jax.grad(
        lambda x: 0.3 * plain_masked_cross_entropy(x, labels)
    )(logits)
    np.testing.assert_allclose(grad, want_grad, rtol=1e-5, atol=1e-7)
    # a masked position's row takes no gradient at all
    assert float(jnp.max(jnp.abs(grad[:, -1]))) == 0.0
    # the logits keep their dtype in the backward, the scalar is float32
    half = logits.astype(jnp.bfloat16)
    assert label_cross_entropy(attrs, half, labels).dtype == jnp.float32
    assert jax.grad(
        lambda x: label_cross_entropy(attrs, x, labels)[0]
    )(half).dtype == jnp.bfloat16
    # nothing labelled: zero, not a division by nothing
    none = jnp.full_like(labels, -1)
    assert float(label_cross_entropy(attrs, logits, none)[0]) == 0.0


def test_loss_node_shapes_and_parallel_shapes():
    attrs = LabelCrossEntropyAttrs(0.5)
    assert op_type_of(attrs) == OperatorType.LABEL_LOSS
    logits = TensorShape((4, 8, 16), DataType.FLOAT)
    labels = TensorShape((4, 8), DataType.INT32)
    assert get_output_shapes(attrs, [logits, labels])[0].dims == (1,)
    assert get_weight_shapes(attrs, [logits, labels]) == []
    assert len(get_incoming_tensor_roles(attrs)) == 2
    with pytest.raises(AssertionError, match="do not index"):
        attrs.output_shape(logits, TensorShape((4, 7), DataType.INT32))
    with pytest.raises(AssertionError, match="class indices"):
        attrs.output_shape(logits, TensorShape((4, 8), DataType.FLOAT))
    # batch shards: a partial sum a shard
    out = get_parallel_output_shapes(attrs, [
        lift_to_parallel_with_degrees(logits, 1, 1, (2, 1, 1)),
        lift_to_parallel_with_degrees(labels, 1, 1, (2, 1)),
    ])[0]
    assert out.sum_degree == 2 and out.shard_degrees() == (1,)
    with pytest.raises(AssertionError, match="class dim"):
        get_parallel_output_shapes(attrs, [
            lift_to_parallel_with_degrees(logits, 1, 1, (1, 1, 2)),
            lift_to_parallel_with_degrees(labels, 1, 1, (1, 1)),
        ])
    with pytest.raises(AssertionError, match="sharded over their positions alike"):
        get_parallel_output_shapes(attrs, [
            lift_to_parallel_with_degrees(logits, 1, 1, (2, 1, 1)),
            lift_to_parallel_with_degrees(labels, 1, 1, (1, 1)),
        ])


def test_latent_parallel_shapes_replicate_both_gains():
    attrs = latent_attrs()
    x = lift_to_parallel_with_degrees(
        TensorShape((4, 16, 32), DataType.FLOAT), 1, 1, (2, 1, 1)
    )
    shapes = get_parallel_weight_shapes(attrs, [x, x, x])
    assert [s.sizes() for s in shapes] == [(3904, 1), (12,), (20,)]
    assert all(s.discard_copy_degree == 2 for s in shapes)
    head_parallel = lift_to_parallel_with_degrees(
        TensorShape((4, 16, 32), DataType.FLOAT), 1, 2, (1, 1, 1)
    )
    with pytest.raises(AssertionError, match="cannot be head-parallel"):
        get_parallel_output_shapes(attrs, [head_parallel] * 3)


def test_new_rules_audit_sound():
    from flexflow_tpu.analysis.rule_audit import audit_substitution
    from flexflow_tpu.substitutions.rules import (
        data_parallel_attention_rule,
        data_parallel_label_loss_rule,
        generate_parallelization_rules,
    )

    rules = (
        data_parallel_label_loss_rule(2),
        data_parallel_attention_rule(
            2, False, op_type=OperatorType.RING_ATTENTION, latent=True,
            q_latent=True,
        ),
        data_parallel_attention_rule(
            2, False, op_type=OperatorType.MULTIHEAD_ATTENTION, latent=True,
            q_latent=True,
        ),
    )
    for rule in rules:
        audit = audit_substitution(rule)
        assert audit.status == "ok", (rule.name, audit.diagnostics)
    registered = {r.name for r in generate_parallelization_rules([2])}
    assert {r.name for r in rules} <= registered


# -- the share tied to the model -------------------------------------------------


def experts_attrs(held, sizes=TOY):
    return ExpertsAttrs(
        sizes["num_experts_total"], sizes["num_experts_per_tok"],
        sizes["moe_intermediate_size"], activation=Activation.SILU,
        capacity_factor=None, use_bias=False, gated=True, renormalize=True,
        scoring="sigmoid", selection_bias=True,
        routed_scale=sizes["routed_scaling_factor"],
        shared_hidden_size=sizes["moe_intermediate_size"], held_experts=held,
    )


def test_shares_add_up_to_the_uncut_layer():
    """The model's own split in miniature: all the held ranges of one expert
    layer (4 of 4 experts here, 32 of 8 in the deployment), the shared
    expert counted ONCE, sum to the uncut reference over all 16 experts."""
    rs = np.random.RandomState(5)
    d, e, width = 32, 16, 24
    named = {
        "e.weight0": rand(rs, d, e),
        "e.weight1": rand(rs, e, scale=0.2),  # a bias that moves the choice
        "e.weight2": rand(rs, e, d, width, scale=0.3),
        "e.weight3": rand(rs, e, d, width, scale=0.3),
        "e.weight4": rand(rs, e, width, d, scale=0.3),
        "e.weight5": rand(rs, d, width, scale=0.3),
        "e.weight6": rand(rs, d, width, scale=0.3),
        "e.weight7": rand(rs, width, d, scale=0.3),
    }
    m = rand(rs, 48, d)

    def share_of(first, count):
        ws = [named[f"e.weight{i}"] for i in range(8)]
        for i in (2, 3, 4):
            ws[i] = ws[i][first:first + count]
        return ws

    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(m, *(named[f"e.weight{i}"] for i in (5, 6, 7)))
        parts = [
            experts_forward(experts_attrs((first, 4)), m[None], share_of(first, 4))[0][0]
            - shared
            for first in (0, 4, 8, 12)
        ]
        whole = ref.experts(named, "e", m, TOY, held=(0, 16))[0]
    for part in parts:  # every share is a strict part of the layer
        assert float(jnp.max(jnp.abs(part))) > 1e-3
        assert float(jnp.max(jnp.abs(part - (whole - shared)))) > 1e-3
    np.testing.assert_allclose(sum(parts) + shared, whole, **F32)


def test_a_share_past_its_first_window_agrees_with_the_reference():
    """With 8 of 256 experts held, a seed's initial router sends some nodes'
    share more rows than the first window takes (a quarter over the uniform
    part, the rule every held graph has), and such a node makes a second
    pass. On a router that sends the share (experts 4..7) well over its
    part, two passes give the reference's output and gradients."""
    from flexflow_tpu.kernels.moe import held_window_rows

    assert held_window_rows(65536, 8, 256) == 2560  # the cell's, a node
    rs = np.random.RandomState(9)
    d, e, width, tokens = 32, 16, 24, 256
    ws = [rand(rs, d, e), jnp.zeros((e,)).at[4:8].set(0.5)] + [
        rand(rs, *shape, scale=0.3) for shape in (
            (4, d, width), (4, d, width), (4, width, d), (d, width),
            (d, width), (width, d),
        )
    ]
    m = rand(rs, tokens, d)
    attrs = experts_attrs((4, 4))
    window = held_window_rows(tokens * TOY["num_experts_per_tok"], 4, e)
    named = {f"e.weight{i}": w for i, w in enumerate(ws)}
    drawn = int(jnp.sum(ref.router(named, "e", m, TOY)[0][:, 4:8]))
    assert window < drawn <= 2 * window, (window, drawn)

    def system(m, ws):
        return jnp.sum(jnp.square(experts_forward(attrs, m[None], ws)[0]))

    def reference(m, ws):
        named = {f"e.weight{i}": w for i, w in enumerate(ws)}
        return jnp.sum(jnp.square(ref.experts(named, "e", m, TOY, held=(4, 4))[0]))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(system, argnums=(0, 1))(m, ws)
        want = jax.value_and_grad(reference, argnums=(0, 1))(m, ws)
    # the bias is a buffer: the choice has no gradient on either side
    assert_trees_close(got, want, **F32_GRADS)


# -- the whole tiny step through FFModel ------------------------------------------


def data(seq, seed=0):
    return ref.make_data(np.random.RandomState(seed), TOY, BATCH, seq)


def compiled_model(seq, compute_dtype=None, sizes=TOY, **config):
    builder, logits = ref.build(sizes, BATCH, seq)
    model = FFModel.from_computation_graph(
        builder, logits,
        FFConfig(batch_size=BATCH, seed=7, print_freq=0, **config),
    )
    model.compile(
        AdamOptimizer(
            alpha=ADAM["alpha"], beta1=ADAM["beta1"], beta2=ADAM["beta2"],
            epsilon=ADAM["epsilon"], weight_decay=ADAM["weight_decay"],
        ),
        ADAM["loss"], compute_dtype=compute_dtype,
    )
    return model


def system_loss(model, inputs, labels):
    read = bench.make_loss_reader(model.instance)
    batch, label = bench.place_batch(model.instance, inputs, labels)
    return read(model.params, batch, label)


def step_grads(model, inputs, labels):
    batch, label = bench.place_batch(model.instance, inputs, labels)
    grads = jax.grad(
        lambda p: model.instance.loss_fn(p, batch, label)[0]
    )(model.params)
    return bench.named_parameters(model.instance, grads)


def test_blocks_and_data_are_the_published_ones():
    assert ref.blocks(TOY) == [
        ("0", True), ("1", False), ("2", False), ("mtp", False),
    ]
    assert ref.counts(TOY) == (4, 1, 3, 2)
    inputs, labels = data(10)
    assert sorted(inputs) == sorted(ref.INPUT_NAMES)
    np.testing.assert_array_equal(inputs["mtp_input_ids"], labels)
    np.testing.assert_array_equal(inputs["mtp_labels"][:, :-1], labels[:, 1:])
    assert (inputs["mtp_labels"][:, -1] == -1).all()
    np.testing.assert_array_equal(inputs["input_ids"][:, 1:], labels[:, :-1])


def test_fit_step_matches_reference_adam_step():
    """The whole step (layer 0, two expert layers, the module) before and
    after one `fit` step against the reference's own gradient and Adam step:
    1e-5 is float32 rounding through two forward passes and the update. The
    loss terms are counted apart, the module's expert node among the routing
    counters, and the shared matrices are one weight each."""
    from flexflow_tpu.observability import routing

    seq = 24
    model = compiled_model(seq, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    assert "mtp_embed.weight0" not in named and "mtp_head.weight0" not in named
    assert named["mtp_proj.weight0"].shape == (64, 32)
    before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    assert abs(system_loss(model, inputs, labels) - before) <= F32_LOSS
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(system_loss(model, inputs, labels) - after) <= F32_LOSS
    assert before - after > 100 * F32_LOSS  # the step did something
    terms = trace.loss_terms()
    assert list(terms) == ["ff.loss", "ff.label_loss.mtp_loss"]
    assert terms["ff.label_loss.mtp_loss"]["weight"] == 0.3
    total = sum(t["weight"] * t["mean"] for t in terms.values())
    assert abs(total - before) <= F32_LOSS
    assert all(abs(t["mean"] - np.log(96)) < 1.0 for t in terms.values())
    counted = routing.published()
    assert counted["nodes"] == ["moe1", "moe2", "mtp_moe"]


def test_a_wrong_shift_in_the_data_shows_as_a_disagreement():
    """The reference derives the module's ids and targets from `labels`; a
    batch whose `mtp_labels` are not the labels moved on by one gives the
    system another loss than the reference's."""
    seq = 24
    model = compiled_model(seq, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    before, _ = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    wrong = dict(inputs, mtp_labels=np.roll(inputs["mtp_labels"], 1, axis=1))
    assert abs(system_loss(model, wrong, labels) - before) > 100 * F32_LOSS


def test_weight_zero_gives_the_trunks_gradients_and_none_for_the_module():
    """At lambda 0 the module adds nothing: the trunk's gradients are those
    of the graph built without the module, to the bit, and the module's own
    weights take zero."""
    seq = 16
    inputs, labels = data(seq)
    with_module = compiled_model(seq, sizes=dict(TOY, mtp_loss_weight=0.0),
                                 max_devices=1)
    trunk = compiled_model(seq, sizes=dict(TOY, num_nextn_predict_layers=0),
                           max_devices=1)
    # the same seeded weights in both graphs, by name
    trunk_named = bench.named_parameters(trunk.instance, trunk.params)
    from test_olmoe import weight_keys

    k_trunk, k_with = weight_keys(trunk.instance), weight_keys(with_module.instance)
    trunk.params = {
        k_trunk[name]: with_module.params[k_with[name]] for name in trunk_named
    }
    got = step_grads(with_module, inputs, labels)
    want = step_grads(trunk, {"input_ids": inputs["input_ids"]}, labels)
    for name, grad in want.items():
        np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(grad), name)
    own = [name for name in got if name.startswith("mtp_")]
    assert len(own) >= 8
    for name in own:
        assert float(jnp.max(jnp.abs(got[name]))) == 0.0, name


def test_shared_embedding_and_head_take_the_sum_of_both_uses():
    """The gradient of `embed.weight0` and of `head.weight0` in the step is
    the gradient through the trunk's use plus the gradient through the
    module's: taken one at a time by cutting the other use's path."""
    seq = 16
    sizes = dict(TOY, mtp_loss_weight=1.0)
    inputs, labels = data(seq)
    named_model = compiled_model(seq, sizes=sizes, max_devices=1)
    w = bench.named_parameters(named_model.instance, named_model.params)
    both = step_grads(named_model, inputs, labels)
    rows = (jnp.asarray(inputs["input_ids"]), jnp.asarray(labels))

    def total(shared, detached, which):
        """The reference's loss with `which` use of the shared matrices
        reading `shared` and the other use reading `detached`."""
        def one(row):
            ids, y = row
            names = ("embed.weight0", "head.weight0")
            trunk_w = dict(w, **dict(zip(names, shared if which == 0 else detached)))
            module_w = dict(w, **dict(zip(names, shared if which == 1 else detached)))
            main, _, _ = ref.loss_sums(
                dict(trunk_w, **{k: v for k, v in w.items() if k.startswith("mtp_")}),
                dict(sizes, num_nextn_predict_layers=0), ids, y,
            )
            # the module on the trunk's output, through its own copies
            h = trunk_w["embed.weight0"][ids]
            for tag, dense in ref.blocks(sizes)[:-1]:
                h, _ = ref.run_block(trunk_w, sizes, h, tag, dense)
            eps = sizes["rms_norm_eps"]
            targets = jnp.concatenate([y[1:], jnp.full((1,), -1, y.dtype)])
            cat = jnp.concatenate([
                ref.rms(module_w["embed.weight0"][y], w["mtp_norm_e.weight0"], eps),
                ref.rms(h, w["mtp_norm_h.weight0"], eps),
            ], axis=-1)
            z, _ = ref.run_block(w, sizes, cat @ w["mtp_proj.weight0"], "mtp", False)
            second = ref.cross_entropy_sum(
                ref.rms(z, w["mtp_norm_f.weight0"], eps),
                module_w["head.weight0"], targets,
            )
            return main / (BATCH * seq) + second / (BATCH * (seq - 1))

        return sum(one((rows[0][i], rows[1][i])) for i in range(BATCH))

    shared = (w["embed.weight0"], w["head.weight0"])
    with jax.default_matmul_precision("highest"):
        uses = [
            jax.grad(lambda s: total(s, shared, which))(shared)
            for which in (0, 1)
        ]
    for i, name in enumerate(("embed.weight0", "head.weight0")):
        for use in uses:  # each use moves the matrix by itself
            assert float(jnp.max(jnp.abs(use[i]))) > 1e-4
        np.testing.assert_allclose(
            both[name], uses[0][i] + uses[1][i], **F32_GRADS
        )


def test_bf16_compute_is_inside_its_tolerance_and_outside_float32s():
    """The same graph at bf16 compute: inside 2e-2 (a mean over 96 positions
    averages little rounding away) and outside the float32 bound, so the
    float32 tests above would catch a bf16 path."""
    seq = 24
    model = compiled_model(seq, compute_dtype=jnp.bfloat16, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    before, _ = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    off = abs(system_loss(model, inputs, labels) - before)
    assert 10 * F32_LOSS < off < 2e-2, off


def test_two_devices_train_the_step_on_the_data_parallel_backend():
    """The whole step on two devices, on the data-parallel backend (GSPMD
    over the batch; what `compile` picks without a search budget): the loss
    is the one-device loss, both terms are counted, and a step reduces it.
    Its data flow is no series-parallel graph (two loss terms over one
    stream and one set of labels), which the search's machine mapping
    refused before PR 62 ("seed ... is unmappable"); it prices a levelled
    tree now, and the forced data-parallel seed gives the same loss."""
    from flexflow_tpu.parallel.data_parallel import DataParallelTrainingInstance

    seq = 24
    inputs, labels = data(seq)
    one = compiled_model(seq, max_devices=1)
    two = compiled_model(seq, max_devices=2)
    assert isinstance(two.instance, DataParallelTrainingInstance)
    searched = compiled_model(seq, max_devices=2, search_budget=2,
                              force_strategy_seed="dp2xtp1xsp1")
    named = bench.named_parameters(two.instance, two.params)
    from test_olmoe import weight_keys

    keys = weight_keys(one.instance)
    one.params = {keys[name]: jnp.asarray(np.asarray(w)) for name, w in named.items()}
    first = system_loss(two, inputs, labels)
    assert abs(first - system_loss(one, inputs, labels)) <= F32_LOSS
    keys = weight_keys(searched.instance)
    searched.params = {
        keys[name]: jax.device_put(np.asarray(w), searched.params[keys[name]].sharding)
        for name, w in named.items()
    }
    assert abs(first - system_loss(searched, inputs, labels)) <= F32_LOSS
    two.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert system_loss(two, inputs, labels) < first - 0.01
    assert list(trace.loss_terms()) == ["ff.loss", "ff.label_loss.mtp_loss"]


def two_loss_graph(seq, weight=0.5):
    """A trunk with two heads and no shared weight: the main logits, and a
    second head whose loss is a node of the graph."""
    from flexflow_tpu.pcg import ComputationGraphBuilder

    b = ComputationGraphBuilder()
    ids = b.create_input([BATCH, seq], DataType.INT32, name="input_ids")
    second = b.create_input([BATCH, seq], DataType.INT32, name="second_labels")
    h = b.embedding(ids, 24, 16, name="embed")
    x = b.rms_norm(h, name="norm")
    h = b.add(h, b.multihead_attention(
        x, x, x, 16, 4, kdim=12, vdim=8, causal=True, rope_theta=100.0,
        rope_interleaved=True, kv_latent_rank=12, shared_key_dim=4,
        q_latent_rank=20, name="mla",
    ))
    logits = b.dense(h, 24, use_bias=False, name="head")
    other = b.dense(h, 24, use_bias=False, name="second_head")
    b.label_cross_entropy(other, second, weight=weight, name="second_loss")
    return b, logits


def test_searched_data_parallel_plan_shards_the_latent_node_and_the_loss_node():
    """Through the searched backend with the batch template on two devices:
    the latent node with its two gains and the loss node are sharded over
    the batch (no compute node left serial; the loss node's scalar completed
    by a Reduction), the loss is the one-device loss, a step reduces it."""
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance
    from test_olmoe import weight_keys

    seq = 16
    rs = np.random.RandomState(1)
    inputs = {
        "input_ids": rs.randint(0, 24, (BATCH, seq)).astype(np.int32),
        "second_labels": rs.randint(-1, 24, (BATCH, seq)).astype(np.int32),
    }
    labels = rs.randint(0, 24, (BATCH, seq)).astype(np.int32)

    def compiled(**config):
        builder, logits = two_loss_graph(seq)
        model = FFModel.from_computation_graph(
            builder, logits,
            FFConfig(batch_size=BATCH, seed=3, print_freq=0, **config),
        )
        model.compile(AdamOptimizer(alpha=1e-2), ADAM["loss"])
        return model

    one = compiled(max_devices=1)
    two = compiled(max_devices=2, search_budget=2,
                   force_strategy_seed="dp2xtp1xsp1")
    assert isinstance(two.instance, DistributedTrainingInstance)
    assert two.search_provenance["serial_compute_nodes"] == []
    kinds = {
        op_type_of(two.instance.pcg.op_attrs(n))
        for n in two.instance.pcg.topological_ordering()
    }
    assert OperatorType.LABEL_LOSS in kinds and OperatorType.REDUCTION in kinds
    keys1, keys2 = weight_keys(one.instance), weight_keys(two.instance)
    one.params = {
        keys1[name]: jnp.asarray(np.asarray(two.params[keys2[name]]))
        for name in keys1
    }
    first = system_loss(two, inputs, labels)
    assert abs(first - system_loss(one, inputs, labels)) <= F32_LOSS
    # the second loss is in it: without its labels the loss is another
    none = dict(inputs, second_labels=np.full_like(inputs["second_labels"], -1))
    assert abs(first - system_loss(two, none, labels)) > 0.1
    two.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert system_loss(two, inputs, labels) < first - 0.01


def test_arithmetic_of_the_published_cut_by_hand():
    sizes = bench.load_json(CONFIG + ".json")
    assert ref.counts(sizes) == (6, 1, 5, 2)
    costs = ref.kernel_costs(sizes, 1, 8192)
    pairs = 8192 * 8193 / 2
    assert costs["flash"]["flops"] == 6 * (4 * 192 + 3 * 128) * 2 * pairs * 32
    assert costs["flash"]["bytes"] == 6 * 6 * 2 * 8192 * 32 * (192 + 128)
    mla = (
        2 * 2048 * 1536 + 2 * 1536 * 32 * 192 + 2 * 2048 * 576
        + 2 * 512 * 32 * 256 + 2 * 32 * 128 * 2048
        + (192 + 128) * 2 * pairs * 32 / 8192
    )
    moe = 2 * 2048 * 256 + 6 * 2048 * 768 * (8 * 8 / 256 + 1)
    want = 3.0 * (
        6 * mla + 6 * 2048 * 7168 + 5 * moe + 2 * 4096 * 2048
        + 2 * 2 * 2048 * 16160
    )
    assert ref.flops_per_token(sizes, 8192) == want
    # the file states what was built
    assert sizes["reduced"].keys() == {
        "num_hidden_layers", "n_routed_experts", "vocab_rows_held",
    }
    assert sizes["num_nextn_predict_layers"] == 1
    assert sizes["n_routed_experts"] * 32 == sizes["num_experts_total"]


# -- the benchmark's CPU rehearsal of the cell ------------------------------------


def test_rehearsal_cell_runs_correct_on_the_cpu_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(BENCH, "rehearsal-joyai.json"), "--workload",
         "rehearsal_joyai_s128_1chip", "--seed", "2147483659", "--seconds",
         "1", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], (result["checks"], result["losses"])
    assert result["device"]["platform"] == "cpu"
    # no device trace on the CPU mesh: the four trace readers return nothing
    for name in ("mla_rope_ms", "mla_rope_flash_roofline", "mtp_ms",
                 "joyai_moe_held_ms"):
        assert name not in result["metrics"]
    assert 0.0 < result["metrics"]["joyai_held_rows_pct"]["value"] < 100.0
    assert '"L_mtp"' in done.stderr
