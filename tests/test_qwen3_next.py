"""The Gated DeltaNet / output-gated grouped-query / gated-shared-expert tower
(`benchmark/configs/qwen3-next-80b-a3b.py`) through the public builder and
`FFModel.compile -> fit`, each part against the plain float32 reference that
lives with the configuration, at toy size on the CPU with seeded weights.
Every tolerance states its reason."""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kimi_linear import (
    HEAD_NORM_IDS, HEAD_NORM_SHAPES, assert_head_norms_agree,
    assert_triangular_products_agree, head_norm_case, kernel_corrected,
    rehearsal_choices,
    head_norm_with_gradients, kernel_head_norm, plain_head_norm,
    triangular_case, triangular_products, xla_corrected,
)
from test_nemotron_h import (
    BENCH, F32, F32_LOSS, assert_trees_close, bench, rand,
)

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import forward as kernel_forward
from flexflow_tpu.kernels import kda
from flexflow_tpu.kernels.moe import experts_forward
from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.core import (
    get_default_weight_initializers,
    get_parallel_output_shapes,
    get_parallel_weight_shapes,
    get_weight_shapes,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    ExpertsAttrs,
    MultiHeadAttentionAttrs,
    RingAttentionAttrs,
    RMSNormAttrs,
)
from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.tensor_shape import TensorShape

CONFIG = os.path.join(BENCH, "configs", "qwen3-next-80b-a3b")
ref = bench.load_module(CONFIG + ".py")

# 4 value heads over 2 key heads of 8 | 8 in chunks of 8; 8 query heads over
# 1 key/value head of 16 with a rotary of 4 columns; 4 held of 16 SwiGLU
# experts of width 24 (top 3) beside a gated shared expert of 24; one whole
# period G G G A
TOY = dict(
    bench.load_json(CONFIG + ".json"),
    hidden_size=32, head_dim=16, num_attention_heads=8, num_key_value_heads=1,
    linear_key_head_dim=8, linear_value_head_dim=8, linear_num_key_heads=2,
    linear_num_value_heads=4, gdn_chunk_size=8, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, num_experts=4, num_experts_total=16,
    held_experts_first=4, num_experts_per_tok=3, vocab_rows_held=96,
    # ten times the published deviation, as in the other towers' tests: at
    # toy width 0.02 leaves every activation so small that a wrong term
    # would hide inside a tolerance
    initializer_range=0.2,
)
BATCH = 4
ADAM = TOY["training"]

# gradients through the chunked recurrence, the triangular inverse and two
# projections in float32 on the CPU: sums of a few hundred products in
# another order than the token-by-token recurrence's, on gradients of up to
# a hundred. Measured 3.9e-4 relative (6e-4 absolute) at worst here.
F32_GRADS = dict(rtol=1e-3, atol=1e-3)


# -- the delta rule with one decay a head ----------------------------------------


def gdn_attrs(sizes=TOY):
    return GatedDeltaAttrs(
        sizes["linear_num_value_heads"], sizes["linear_key_head_dim"],
        sizes["linear_value_head_dim"], sizes["linear_conv_kernel_dim"],
        chunk_size=sizes["gdn_chunk_size"], norm_eps=sizes["rms_norm_eps"],
        num_key_heads=sizes["linear_num_key_heads"], decay="head",
    )


def gdn_case(seq, key_heads, seed=1, batch=2):
    """(sizes, u [b, s, D], the op's weights in slot order), with decays
    strong enough that exp(-G) taken from a chunk's start would overflow:
    -exp(A_log) softplus(a + dt_bias) is about -25 a position, -200 over a
    chunk of 8, and float32 ends at e^88."""
    sizes = dict(TOY, linear_num_key_heads=key_heads)
    attrs = gdn_attrs(sizes)
    rs = np.random.RandomState(seed)
    d = sizes["hidden_size"]
    shapes = attrs.weight_shapes(TensorShape((batch, seq, d), DataType.FLOAT))
    ws = [rand(rs, *shape.dims, scale=0.3) for shape in shapes]
    hv = attrs.num_heads
    ws[3] = 1.0 + rand(rs, hv, scale=0.1)  # dt_bias
    ws[4] = jnp.log(jnp.asarray(rs.uniform(15.0, 25.0, hv), jnp.float32))
    ws[5] = 1.0 + rand(rs, attrs.value_dim, scale=0.2)  # the norm's gain
    return sizes, rand(rs, batch, seq, d), ws


def reference_gdn(sizes, u, ws):
    w = {f"g.weight{i}": t for i, t in enumerate(ws)}
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda row: ref.gated_delta_net(w, "g", row, sizes))(u)


def program_gdn(sizes, u, ws):
    with jax.default_matmul_precision("highest"):
        return kernel_forward(gdn_attrs(sizes), [u], ws)[0]


def test_head_decay_slots_and_shapes():
    attrs = GatedDeltaAttrs(
        32, 128, 128, num_key_heads=16, decay="head"
    )
    x = TensorShape((1, 8192, 2048), DataType.FLOAT)
    assert [w.dims for w in get_weight_shapes(attrs, [x])] == [
        (2048, 12288), (2048, 64), (4, 8192), (32,), (32,), (128,),
        (4096, 2048),
    ]
    assert attrs.num_weights == 7 and attrs.key_heads == 16
    # the per-channel form keeps its nine slots and its in-projection
    kimi = GatedDeltaAttrs(32, 128, 128)
    assert kimi.num_weights == 9 and kimi.in_proj_width == 3 * 4096 + 256 + 32
    batch = lift_to_parallel_with_degrees(x, 1, 1, (1, 1, 1))
    (out,) = get_parallel_output_shapes(attrs, [batch])
    assert out.sum_degree == 1
    assert len(get_parallel_weight_shapes(attrs, [batch])) == 7
    # dt_bias from one, the gain from one, A_log a log of a positive draw
    inits = get_default_weight_initializers(attrs, 7)
    assert inits[3].value == 1.0 and inits[5].value == 1.0
    assert inits[4].min_val > 0.0


def test_unwritten_forms_are_refused_and_say_why():
    with pytest.raises(AssertionError, match="two published forms"):
        GatedDeltaAttrs(4, 8, 8, decay="row")
    with pytest.raises(AssertionError, match="come with decay='head'"):
        GatedDeltaAttrs(4, 8, 8, num_key_heads=2)
    with pytest.raises(AssertionError, match="do not divide"):
        GatedDeltaAttrs(4, 8, 8, num_key_heads=3, decay="head")


@pytest.mark.parametrize("key_heads", [4, 2])
@pytest.mark.parametrize("seq", [32, 27])
def test_head_decay_node_matches_the_token_by_token_recurrence(seq, key_heads):
    """Forward, and the gradient of the input and of every weight, at one
    and at two value heads a key head, at a whole number of chunks and not."""
    sizes, u, ws = gdn_case(seq, key_heads)
    cot = rand(np.random.RandomState(2), *u.shape)
    np.testing.assert_allclose(
        program_gdn(sizes, u, ws), reference_gdn(sizes, u, ws), **F32
    )

    def grads(fn):
        return jax.grad(
            lambda u, ws: jnp.sum(fn(sizes, u, ws) * cot), (0, 1)
        )(u, ws)

    assert_trees_close(grads(program_gdn), grads(reference_gdn), **F32_GRADS)


def test_scalar_decay_operands_are_the_per_channel_ones_on_a_broadcast_decay():
    """`head_decay_operands` (one masked product for A, one for P) against
    `chunk_operands` (the levels) given each head's decay on all its key
    channels and each key head repeated for its value heads: the same six
    operands, to float32 rounding of sums taken in another order."""
    rs = np.random.RandomState(3)
    b, hk, group, s, d, chunk = 1, 2, 2, 32, 8, 8
    hv = hk * group
    q = kda._unit(rand(rs, b, hk, s, d), d ** -0.5)
    k = kda._unit(rand(rs, b, hk, s, d), 1.0)
    v = rand(rs, b, hv, s, d)
    g = -30.0 * jnp.asarray(rs.rand(b, hv, s), jnp.float32)
    beta = jnp.asarray(rs.rand(b, hv, s), jnp.float32)
    with jax.default_matmul_precision("highest"):
        scalar = kda.head_decay_operands(q, k, v, g, beta, chunk)
        channel = kda.chunk_operands(
            jnp.repeat(q, group, axis=1), jnp.repeat(k, group, axis=1), v,
            jnp.broadcast_to(g[..., None], (b, hv, s, d)), beta, chunk,
        )
    for got, want in zip(scalar, channel):
        np.testing.assert_allclose(got, want, **F32)


def heads_first(t, heads):
    """[b, s, heads * d] as the model has it -> [b, heads, s, d]."""
    b, s, _ = t.shape
    return jnp.swapaxes(t.reshape(b, s, heads, -1), 1, 2)


def plain_head_operands(q, k, v, g, beta, chunk, key_dim):
    """The "xla" route on the kernels' inputs: q, k, v turned heads first, q
    and k over their 2-norms (`kda._unit`), then `head_decay_operands`."""
    hk, hv = q.shape[-1] // key_dim, g.shape[1]
    return kda.head_decay_operands(
        kda._unit(heads_first(q, hk), key_dim ** -0.5),
        kda._unit(heads_first(k, hk), 1.0), heads_first(v, hv), g, beta, chunk,
    )


# `key_heads` key heads of 128 | 128 in chunks of 64, each read by `group`
# value heads; q, k and v RAW and in the model's layout, [b, s, heads * 128],
# as the convolution leaves them: a key head is one 128-lane column block of
# q and k, a value head one of v (`_HeadPrepBlocks.key_head`,
# `_CorrectedBlocks.value`). 256 positions are four chunks, all in ONE
# program of the kernels; 192 are three, one a program (`_PREP_CHUNKS`),
# and an odd number of chunks a head, which XLA's `unit_lower_inverse`
# inverts where the triangular system's kernels take a head's chunks two by
# two (A a chunk a row from `gdn_prep_fwd`, v turned heads first for XLA's
# form), with one value head and with two; 384 are six, three pairs a head
# and two chunk-heads a program of `kda_corrected_*`; 512 eight, one
# program a head; 100 positions pad to 128 as the node pads them (raw q = k = v = 0,
# beta = 0, a decay all the same). A log-decay of -27 to -30 a position puts
# exp(G_r - G_j) under float32's least (e^-103.3) four positions apart and
# exp(-G) of the textbook form over its greatest inside three.
@pytest.mark.parametrize(
    "key_heads,group,seq,decay",
    [(1, 1, 128, 1.0), (1, 2, 256, 1.0), (1, 4, 128, 1.0), (1, 2, 100, 1.0),
     (1, 1, 192, 1.0), (1, 2, 128, 30.0), (3, 2, 128, 1.0), (1, 2, 192, 1.0),
     (2, 1, 256, 1.0), (1, 1, 384, 1.0), (1, 2, 512, 1.0)],
    ids=["one_value_head", "two_value_heads", "four_value_heads",
         "padded_to_the_chunk", "odd_count_of_chunks", "decay_underflows",
         "three_key_heads_of_two_value_heads", "odd_chunks_a_head",
         "two_key_heads_of_one_value_head", "three_pairs_a_head",
         "eight_chunks_a_program"],
)
def test_scalar_decay_kernels_agree_with_the_xla_operands(
    monkeypatch, key_heads, group, seq, decay
):
    """`head_kernel_operands` (the Pallas kernels in interpret mode:
    `gdn_prep_fwd` and its WRITTEN backward `gdn_prep_bwd`, which normalise q
    and k in VMEM, the triangular inverse, `kda_corrected_*` reading v and
    writing its cotangent in place) against `plain_head_operands`,
    differentiated by JAX, at lane-sized heads in float32: the six operands
    and the cotangents of the raw q, k, v in the model's layout and of g and
    beta. The kernels take every exponent as one sum of log-decays where XLA
    subtracts two running sums (of up to -1,900 in the last case: 1e-4 of an
    exponent), and sum the value heads' cotangents in another order: measured
    1.5e-5 at values of 5, 9e-5 at values of 8 in the underflowing case."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    rs = np.random.RandomState(13)
    b, hk, d, chunk = 1, key_heads, 128, 64
    hv = hk * group
    padded = seq + -seq % chunk
    c = padded // chunk
    real = (jnp.arange(padded) < seq)[:, None]
    q = rand(rs, b, padded, hk * d) * real
    k = rand(rs, b, padded, hk * d) * real
    v = rand(rs, b, padded, hv * d) * real
    g = -decay * jnp.asarray(0.9 + 0.1 * rs.rand(b, hv, padded), jnp.float32)
    beta = jnp.asarray(rs.rand(b, hv, padded), jnp.float32) * real[:, 0]
    cots = [
        rand(rs, b, hv, c, chunk, width) for width in (d, d, d, d, chunk)
    ] + [rand(rs, b, hv, c, 1, d)]

    def run(operands_of):
        def loss(*inputs):
            operands = operands_of(*inputs, chunk, d)
            return sum(
                jnp.sum(o * cot) for o, cot in zip(operands, cots)
            ), operands

        with jax.default_matmul_precision("highest"):
            (_, operands), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True
            )(q, k, v, g, beta)
        # the node's pad drops the padded positions' cotangents of q, k, v
        # (a zero row's inverse root is 1e3: cotangents of thousands there)
        return operands, [t[:, :seq] for t in grads[:3]] + list(grads[3:])

    got, want = run(kda.head_kernel_operands), run(plain_head_operands)
    assert all(
        bool(jnp.all(jnp.isfinite(t))) for t in jax.tree_util.tree_leaves(got)
    )
    for grad in want[1][:3]:  # the raw q, k and v are reached
        assert float(jnp.max(jnp.abs(grad))) > 1e-3
    if decay > 20.0:
        p, gamma = got[0][4], got[0][5]
        far = np.tri(chunk, k=-4, dtype=bool)
        assert not np.any(np.asarray(p)[..., far]) and not np.any(gamma)
        assert float(jnp.max(jnp.abs(p))) > 1e-3  # the diagonal is there
    tol = dict(rtol=2e-4, atol=2e-4) if decay > 20.0 else dict(rtol=1e-4, atol=2e-5)
    assert_trees_close(got, want, **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "lead", [(1, 4, 8), (1, 3, 2), (1, 3, 1), (1, 1, 8), (2, 1, 4)],
    ids=["two_programs_of_sixteen", "three_programs_of_two",
         "odd_count_falls_back", "one_program_of_eight", "two_programs_of_four"],
)
def test_triangular_product_kernels_take_the_value_heads_chunks(
    monkeypatch, lead, dtype
):
    """`kda._kernel_corrected` as `head_kernel_operands` calls it, by chunk
    and VALUE head (`tests/test_kimi_linear.py` has the per-channel form's
    counts and what is compared): thirty-two chunk-heads are two programs
    of `kda_corrected_fwd` / `kda_corrected_bwd`, six are three of two (three
    pairs, one a program of the inverse's kernel), and three heads of one
    chunk keep `_corrected` with `unit_lower_inverse`, bit for bit."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    operands, cots = triangular_case(lead, dtype, seed=29)
    got = triangular_products(kernel_corrected, operands, cots)
    want = triangular_products(xla_corrected, operands, cots)
    if lead[-1] % 2:
        assert_trees_close(got, want, rtol=0, atol=0)
    else:
        assert_triangular_products_agree(got, want, dtype)


@pytest.mark.parametrize(
    "heads,chunks", [(2, 2), (1, 4), (2, 8), (1, 6), (1, 3)],
    ids=["two_a_program", "four_a_program", "eight_a_program",
         "three_pairs_a_head", "odd_chunks_a_head_take_xlas_form"],
)
def test_triangular_product_kernels_read_v_where_the_model_has_it(
    monkeypatch, heads, chunks
):
    """`kda._kernel_corrected` with v [b, s, heads * dv] as the convolution
    leaves it (Qwen3-Next's form: a program's chunk-heads are chunks of ONE
    head, X, A and dA its pairs) against `_corrected` on v heads first: the
    two products and the cotangents of A, K exp(G), v (in the model's
    layout) and beta. An odd number of chunks a head is the ONE thing that
    still takes XLA's form on the route (`triangular_products` "xla")."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    (a, kd, v, beta), cots = triangular_case((1, heads, chunks), jnp.float32, seed=43)

    def as_the_model_has(t):  # [b, h, c, Q, d] -> [b, s, h * d]
        b, h, c, q, d = t.shape
        return jnp.transpose(t, (0, 2, 3, 1, 4)).reshape(b, c * q, h * d)

    got = triangular_products(
        kernel_corrected, (a, kd, as_the_model_has(v), beta), cots
    )
    out, (da, dkd, dv, dbeta) = triangular_products(
        xla_corrected, (a, kd, v, beta), cots
    )
    assert got[1][2].shape == (1, chunks * 64, heads * 128)
    assert_triangular_products_agree(
        got, (out, (da, dkd, as_the_model_has(dv), dbeta)), jnp.float32
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("lead,heads", HEAD_NORM_SHAPES, ids=HEAD_NORM_IDS)
def test_head_norm_kernels_agree_with_the_plain_silu_gated_norm(
    monkeypatch, lead, heads, dtype
):
    """`kda.head_norm_gate` with no bias (interpret mode:
    `head_norm_gate_fwd` and the WRITTEN backward `head_norm_gate_bwd`,
    `tests/test_kimi_linear.py` has the shapes and what is compared) against
    `_head_norm_silu` and JAX's own gradient of it: y and the cotangents of
    o, z and the gain."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    operands, dy = head_norm_case(lead, heads, dtype, biased=False, seed=47)
    got = head_norm_with_gradients(kernel_head_norm, operands, dy, heads)
    want = head_norm_with_gradients(plain_head_norm, operands, dy, heads)
    assert len(got[1]) == 3
    assert_head_norms_agree(got, want, dtype)


@pytest.mark.parametrize("off", ["value_dim_64", "no_flash"])
def test_the_silu_gated_norm_off_the_route_is_the_plain_form(monkeypatch, off):
    """Value heads of 64 and a trace under `no_flash()` leave the node on
    the "xla" route: `_gated_head_norm` is `_head_norm_silu`, bit for bit,
    and no kernel is called."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setattr(kda, "head_norm_gate", None)
    dv = 64 if off == "value_dim_64" else 128
    attrs = GatedDeltaAttrs(
        4, 128, dv, 4, chunk_size=64, norm_eps=1e-6, num_key_heads=2,
        decay="head",
    )
    rs = np.random.RandomState(53)
    o, z = rand(rs, 1, 4, 32, dv), rand(rs, 1, 32, 4 * dv)
    gain = 1.0 + rand(rs, dv, scale=0.1)

    def run():
        route = kda.scan_route(128, dv, 64)
        return route, kda._gated_head_norm(attrs, route, o, z, None, gain)

    if off == "no_flash":
        with context.no_flash():
            route, got = run()
    else:
        route, got = run()
    assert route == "xla"
    in_rows = jnp.swapaxes(o, 1, 2).reshape(1, 32, 4 * dv)
    want = kda._head_norm_silu(in_rows, z, gain, 4, 1e-6)
    assert_trees_close(got, want, rtol=0, atol=0)


@pytest.mark.parametrize(
    "before", [2, 0.5], ids=["read_in_place", "copied_out"]
)
def test_head_norm_kernels_read_z_out_of_a_wider_row(monkeypatch, before):
    """z as the node has it, the LAST heads * dv columns of the input
    projection's row: two whole column blocks before it and the kernels read
    it where it lies (`_NormBlocks.rows(column_block)`), half a block and it
    is copied out first; either way y and the cotangents of o, z and the gain
    are those of the plain form on the slice, and the row's other columns get
    a cotangent of exactly zero."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    heads, dtype = 8, jnp.float32  # two groups of heads a block of rows
    (o, z, _, gain), dy = head_norm_case((1, 64), heads, dtype, biased=False)
    first = int(before * z.shape[-1])
    rs = np.random.RandomState(59)
    row = jnp.concatenate([rand(rs, 1, 64, first), z], axis=-1)

    def from_the_row(o, row, gain):
        return kernel_head_norm(o, row, None, gain, heads, first=first)

    y, vjp = jax.vjp(from_the_row, o, row, gain)
    do, drow, dgain = vjp(dy)
    assert not np.any(np.asarray(drow[..., :first]))
    want = head_norm_with_gradients(plain_head_norm, (o, z, None, gain), dy, heads)
    assert_head_norms_agree((y, [do, drow[..., first:], dgain]), want, dtype)


def kernel_sized_node(seq):
    """(attrs, u, weights, a cotangent): four value heads over two key heads
    of 128 | 128 in chunks of 64, hidden size 32."""
    attrs = GatedDeltaAttrs(
        4, 128, 128, 4, chunk_size=64, norm_eps=1e-6, num_key_heads=2,
        decay="head",
    )
    rs = np.random.RandomState(23)
    b, hidden = 1, 32
    shapes = attrs.weight_shapes(TensorShape((b, seq, hidden), DataType.FLOAT))
    scales = [0.3, 0.5, 0.5, 1.0, 0.3, 0.2, 0.1]
    ws = [rand(rs, *s.dims, scale=k) for s, k in zip(shapes, scales)]
    ws[5] = 1.0 + ws[5]
    return attrs, rand(rs, b, seq, hidden), ws, rand(rs, b, seq, hidden)


def test_the_whole_head_decay_node_on_the_kernels_agrees_with_the_xla_route(
    monkeypatch,
):
    """`gated_delta_forward` with one decay a head over 100 positions (padded
    to two chunks): the "kda" route (every kernel interpreted: the operands',
    the inverse's, the pass's three) against the "xla" route (`no_flash()`):
    the output and the gradients of the input and all seven weights, float32.
    The routes differ where the operands' test says, and the node's
    projections multiply that by a hidden size of 32."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    attrs, u, ws, cot = kernel_sized_node(100)
    routes = []

    def run():
        routes.append(kda.scan_route(128, 128, 64))
        with jax.default_matmul_precision("highest"):
            def loss(u, ws):
                y = kda.gated_delta_forward(attrs, u, ws)
                return jnp.sum(y * cot), y

            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True
            )(u, ws)
        return y, grads

    got = run()
    with context.no_flash():
        want = run()
    assert routes == ["kda", "xla"]
    for grad in jax.tree_util.tree_leaves(want[1]):
        assert float(jnp.max(jnp.abs(grad))) > 1e-3  # every weight is reached
    assert_trees_close(got, want, **F32_GRADS)


def test_the_operands_form_is_counted_by_node(monkeypatch, entered):
    """`observability/trace.kernel_choices("delta_rule_operands")` names the form each
    delta-rule node was lowered with: the scalar form's kernels where
    `scan_route` says "kda", reading q, k and v in place over whole chunks
    (`gdn0`-`gdn2`, as the cell's three nodes) and padded copies of them over
    100 positions, `head_decay_operands` under `no_flash()` and on the plain
    CPU, and the per-channel form's two names likewise."""
    attrs, u, ws, _ = kernel_sized_node(64)
    channel = GatedDeltaAttrs(2, 128, 128, 4, 8, 64, 1e-5)
    monkeypatch.setattr(context, "_CHOICES", {})

    def lowered_as(scope, node=attrs, u=u):
        entered(context.lowering_node(scope))
        if node is attrs:
            jax.eval_shape(lambda u, ws: kda.gated_delta_forward(attrs, u, ws), u, ws)
        else:
            kda.operand_form(node, kda.scan_route(128, 128, 64), 64)
        return trace.kernel_choices("delta_rule_operands")[scope]

    assert lowered_as("ff.kda.on_the_cpu") == "head_xla"
    assert lowered_as("ff.kda.on_the_cpu", channel) == "xla"
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    for name in ("gdn0", "gdn1", "gdn2"):
        assert lowered_as(f"ff.kda.{name}") == "head_kernels_in_place"
    assert lowered_as("ff.kda.padded", u=u[:, :36]) == "head_kernels"
    assert lowered_as("ff.kda.kda0", channel) == "channel_kernels"
    with context.no_flash():
        assert lowered_as("ff.kda.gdn1") == "head_xla"
    assert trace.kernel_choices("delta_rule_operands") == {
        "ff.kda.on_the_cpu": "xla", "ff.kda.gdn0": "head_kernels_in_place",
        "ff.kda.gdn2": "head_kernels_in_place", "ff.kda.padded": "head_kernels",
        "ff.kda.kda0": "channel_kernels", "ff.kda.gdn1": "head_xla",
    }
    # a kernel called by itself, under no node's scope, is not counted
    entered(context.lowering_node(None))
    kda.operand_form(attrs, "kda", 64)
    assert len(trace.kernel_choices("delta_rule_operands")) == 6


def _main_calls(text):
    """{value: defining line} and [(callee, [operands])] of the lowered
    module's `@main`."""
    main = text[text.index("func.func public @main"):]
    main = main[:main.index("\n  }\n")]
    defined, calls = {}, []
    for line in main.splitlines():
        m = re.match(r"\s*(%\w+)(?::\d+)? = (.*)", line)
        if not m:
            continue
        defined[m.group(1)] = m.group(2)
        call = re.match(r"call @(\w+?)(?:_\d+)?\((.*?)\) :", m.group(2))
        if call:
            calls.append((call.group(1), m.group(1), call.group(2).split(", ")))
    return defined, calls


def test_the_lowered_node_hands_the_pieces_from_kernel_to_kernel(monkeypatch, entered):
    """The node lowered for the TPU, forward and backward, at whole chunks:
    q and k reach `gdn_prep_fwd` (forward and recomputed) and `gdn_prep_bwd`,
    and v `kda_corrected_fwd` / `kda_corrected_bwd`, as the very results of
    `conv_silu_fwd` (through the checkpoint's `optimization_barrier` and
    nothing else), and `conv_silu_bwd` takes dq, dk and dv as the very
    results of `gdn_prep_bwd` and `kda_corrected_bwd`: no `transpose`,
    `reshape`, `convert` or `pad` of q, k, v or their cotangents lies
    between the convolution's and the recurrence's kernels (a Pallas operand
    must be a buffer: XLA writes out whatever lies between). Since PR 71 the
    triangular system's arrays go from kernel to kernel the same way, in
    pairs: A is the very result of `gdn_prep_fwd` where `kda_prep_inverse`
    and `kda_corrected_bwd` read it, and dA the very result of
    `kda_corrected_bwd` where `gdn_prep_bwd` reads it (no Diag(beta) A, no
    dn and no un-pairing in XLA)."""
    entered(context.described_tpu())
    attrs, u, ws, cot = kernel_sized_node(128)

    def node(u, ws, cot):
        y, vjp = jax.vjp(
            lambda u, ws: kda.gated_delta_forward(attrs, u, ws), u, ws
        )
        return y, vjp(cot)

    text = jax.jit(node).trace(u, ws, cot).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    defined, calls = _main_calls(text)

    def source(value):
        """(the call that made `value`, which of its results), through the
        checkpoint's barriers."""
        name, _, index = value.partition("#")
        made = defined[name]
        if made.startswith("stablehlo.optimization_barrier"):
            operands = made[len("stablehlo.optimization_barrier "):]
            return source(operands.split(" : ")[0].split(", ")[int(index or 0)])
        callee = re.match(r"call @(\w+?)(?:_\d+)?\(", made)
        return (callee.group(1) if callee else made.split(" ")[0]), int(index or 0)

    took = {"_head_prep_forward": [], "_corrected_forward": []}
    for callee, result, operands in calls:
        if callee in ("_head_prep_forward", "_head_prep_backward"):
            assert [source(o) for o in operands[:2]] == [
                ("_conv_forward", 0), ("_conv_forward", 1)
            ], (callee, operands)
        if callee in ("_corrected_forward", "_corrected_backward"):
            v = operands[3 + (callee == "_corrected_backward")]
            assert source(v) == ("_conv_forward", 2), (callee, operands)
        if callee in ("_pallas_inverse", "_corrected_backward"):
            a = operands[callee == "_corrected_backward"]
            assert source(a) == ("_head_prep_forward", 4), (callee, operands)
        if callee == "_head_prep_backward":
            assert source(operands[7]) == ("_corrected_backward", 0), operands
        if callee in took:
            took[callee].append(result)
        if callee == "_conv_backward":
            assert [source(o) for o in operands[2:5]] == [
                ("_head_prep_backward", 0), ("_head_prep_backward", 1),
                ("_corrected_backward", 3),
            ], operands
    # the forward and the checkpoint's recomputation of it, one backward each
    assert [len(v) for v in took.values()] == [2, 2]
    names = [callee for callee, _, _ in calls]
    for once in ("_conv_forward", "_conv_backward", "_head_prep_backward",
                 "_corrected_backward", "_pallas_inverse"):
        assert names.count(once) == 1, names


def test_the_triangular_products_form_is_counted_by_node(monkeypatch, entered):
    """`observability/trace.kernel_choices("triangular_products")` names the form the
    products around the triangular inverse took in each delta-rule node:
    `kernels` on the "kda" route for both forms of the decay (two chunks a
    head: since PR 71 the kernels take a head's chunks two by two, and
    `triangular_layout` says `pairs` beside it), `xla` under `no_flash()`,
    on the plain CPU and where a head's chunks are odd in number."""
    attrs, u, ws, _ = kernel_sized_node(128)
    channel = GatedDeltaAttrs(2, 128, 128, 4, 8, 64, 1e-5)
    rs = np.random.RandomState(31)
    channel_ws = [
        rand(rs, *s.dims, scale=0.3) for s in channel.weight_shapes(
            TensorShape(u.shape, DataType.FLOAT)
        )
    ]
    monkeypatch.setattr(context, "_CHOICES", {})

    def lowered_as(scope, node=attrs, ws=ws, u=u):
        entered(context.lowering_node(scope))
        jax.eval_shape(lambda u, ws: kda.gated_delta_forward(node, u, ws), u, ws)
        return trace.kernel_choices("triangular_products")[scope]

    assert lowered_as("ff.kda.on_the_cpu") == "xla"
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert lowered_as("ff.kda.gdn0") == "kernels"
    assert lowered_as("ff.kda.kda0", channel, channel_ws) == "kernels"
    with context.no_flash():
        assert lowered_as("ff.kda.gdn1") == "xla"
        assert lowered_as("ff.kda.kda1", channel, channel_ws) == "xla"
    # one value head over three chunks: a count the kernels do not take
    odd = GatedDeltaAttrs(
        1, 128, 128, 4, chunk_size=64, norm_eps=1e-6, num_key_heads=1,
        decay="head",
    )
    odd_u = rand(rs, 1, 192, 32)
    odd_ws = [
        rand(rs, *s.dims, scale=0.3) for s in odd.weight_shapes(
            TensorShape(odd_u.shape, DataType.FLOAT)
        )
    ]
    assert lowered_as("ff.kda.gdn2", odd, odd_ws, odd_u) == "xla"
    # q and k still go to `gdn_prep_*` where they lie; v is turned heads
    # first for XLA's form alone
    assert trace.kernel_choices("delta_rule_operands")["ff.kda.gdn2"] == "head_kernels_in_place"
    assert trace.kernel_choices("triangular_products") == {
        "ff.kda.on_the_cpu": "xla", "ff.kda.gdn0": "kernels",
        "ff.kda.kda0": "kernels", "ff.kda.gdn1": "xla", "ff.kda.kda1": "xla",
        "ff.kda.gdn2": "xla",
    }
    # the layout is the kernels' alone: XLA's form notes none
    assert trace.kernel_choices("triangular_layout") == {
        "ff.kda.gdn0": "pairs", "ff.kda.kda0": "pairs",
    }
    # a kernel called by itself, under no node's scope, is not counted
    entered(context.lowering_node(None))
    kernel_corrected(*triangular_case((1, 1, 2), jnp.float32)[0])
    assert len(trace.kernel_choices("triangular_products")) == 6
    assert len(trace.kernel_choices("triangular_layout")) == 2


def test_the_rehearsal_graphs_nodes_say_how_the_triangular_system_crosses_hbm(
    monkeypatch,
):
    """`trace.kernel_choices("triangular_layout")` names `pairs` for the
    three scalar-decay nodes of the rehearsal graph at lane-sized heads
    (`tests/test_kimi_linear.py` has the helper and the per-channel graph's
    four)."""
    noted = rehearsal_choices(
        monkeypatch, ref, "qwen3next", ADAM, linear_key_head_dim=128,
        linear_value_head_dim=128, gdn_chunk_size=64,
    )
    for node in ("ff.kda.gdn0", "ff.kda.gdn1", "ff.kda.gdn2"):
        assert noted[node]["triangular_products"] == "kernels"
        assert noted[node]["triangular_layout"] == "pairs"
        assert noted[node]["delta_rule_operands"] == "head_kernels_in_place"


def test_the_head_norm_form_is_counted_by_node(monkeypatch, entered):
    """`observability/trace.kernel_choices("head_norms")` names the form the heads' norm
    under its gate took in each delta-rule node: `kernels` on the "kda" route
    for both gates, `xla` under `no_flash()` and on the plain CPU."""
    attrs, u, ws, _ = kernel_sized_node(64)
    channel = GatedDeltaAttrs(2, 128, 128, 4, 8, 64, 1e-5)
    rs = np.random.RandomState(37)
    channel_ws = [
        rand(rs, *s.dims, scale=0.3) for s in channel.weight_shapes(
            TensorShape(u.shape, DataType.FLOAT)
        )
    ]
    monkeypatch.setattr(context, "_CHOICES", {})

    def lowered_as(scope, node=attrs, ws=ws):
        entered(context.lowering_node(scope))
        jax.eval_shape(lambda u, ws: kda.gated_delta_forward(node, u, ws), u, ws)
        return trace.kernel_choices("head_norms")[scope]

    assert lowered_as("ff.kda.on_the_cpu") == "xla"
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert lowered_as("ff.kda.gdn0") == "kernels"
    assert lowered_as("ff.kda.kda0", channel, channel_ws) == "kernels"
    with context.no_flash():
        assert lowered_as("ff.kda.gdn1") == "xla"
        assert lowered_as("ff.kda.kda1", channel, channel_ws) == "xla"
    assert trace.kernel_choices("head_norms") == {
        "ff.kda.on_the_cpu": "xla", "ff.kda.gdn0": "kernels",
        "ff.kda.kda0": "kernels", "ff.kda.gdn1": "xla", "ff.kda.kda1": "xla",
    }
    # a norm called by itself, under no node's scope, is not counted
    entered(context.lowering_node(None))
    o = jnp.ones((1, 4, 8, 128), jnp.float32)
    kda._gated_head_norm(attrs, "xla", o, o.reshape(1, 8, 512), None, ws[5])
    assert len(trace.kernel_choices("head_norms")) == 5


# -- the gated grouped-query attention node --------------------------------------


def attention_attrs(sizes=TOY, **changes):
    fields = dict(
        kdim=sizes["head_dim"], vdim=sizes["head_dim"], causal=True,
        rope_theta=float(sizes["rope_theta"]),
        rotary_dim=ref.rotary_dim(sizes), qk_norm_eps=sizes["rms_norm_eps"],
        qk_norm_per_head=True, qk_norm_zero_centered=True,
        num_kv_heads=sizes["num_key_value_heads"], output_gate=True,
    )
    fields.update(changes)
    return RingAttentionAttrs(
        sizes["hidden_size"], sizes["num_attention_heads"], **fields
    )


def attention_case(attrs, seq=24, seed=3, batch=2):
    """(u, [flat weight, w_q, w_k]) with the zero-centred weights away from
    zero."""
    rs = np.random.RandomState(seed)
    x = TensorShape((batch, seq, attrs.embed_dim), DataType.FLOAT)
    flat = rand(rs, *attrs.weights_shape(x, x, x).dims, scale=0.3)
    gains = [rand(rs, attrs.q_proj_size, scale=0.3) for _ in range(2)]
    return rand(rs, batch, seq, attrs.embed_dim), [flat, *gains]


def plain_attention(attrs, u, ws):
    """The node by hand for any setting of the new attributes, one sequence
    at a time: a query head's columns in wq are its query, then its gate
    where it has one."""
    h, kv, d, e = attrs.num_heads, attrs.kv_heads, attrs.q_proj_size, attrs.embed_dim
    cols = 2 * d if attrs.output_gate else d
    cuts = np.cumsum([0, e * h * cols, e * kv * d, e * kv * d, h * d * e])
    flat = ws[0].reshape(-1)
    wq = flat[cuts[0]:cuts[1]].reshape(e, h, cols)
    wk = flat[cuts[1]:cuts[2]].reshape(e, kv, d)
    wv = flat[cuts[2]:cuts[3]].reshape(e, kv, d)
    wo = flat[cuts[3]:cuts[4]].reshape(h, d, e)
    width = attrs.rotary_dim or d

    def one(row):
        both = jnp.einsum("se,ehd->hsd", row, wq)
        q = ref.zrms(both[..., :d], ws[1], attrs.qk_norm_eps)
        k = ref.zrms(jnp.einsum("se,ehd->hsd", row, wk), ws[2], attrs.qk_norm_eps)
        q, k = (ref.rope(t, attrs.rope_theta, width) for t in (q, k))
        v = jnp.einsum("se,ehd->hsd", row, wv)
        k, v = (jnp.repeat(t, h // kv, axis=0) for t in (k, v))
        ctx = ref.causal_attention(q, k, v)
        if attrs.output_gate:
            ctx = ctx * jax.nn.sigmoid(both[..., d:])
        return jnp.einsum("hsd,hde->se", ctx, wo)

    with jax.default_matmul_precision("highest"):
        return jax.vmap(one)(u)


def program_attention(attrs, u, ws):
    with jax.default_matmul_precision("highest"):
        return kernel_forward(attrs, [u, u, u], ws)[0]


def test_gated_attention_slots_and_refusals():
    attrs = attention_attrs(dict(TOY, hidden_size=2048, head_dim=256,
                                 num_attention_heads=16, num_key_value_heads=2))
    x = TensorShape((1, 8192, 2048), DataType.FLOAT)
    shapes = [w.dims for w in get_weight_shapes(attrs, [x, x, x])]
    # wq with the gates, wk, wv, wo in one column; the two norm weights
    assert shapes == [
        (2048 * 16 * 512 + 2 * 2048 * 2 * 256 + 4096 * 2048, 1), (256,), (256,)
    ]
    # zero-centred norm weights start at zero (a vector's own default)
    assert get_default_weight_initializers(attrs, 3)[1:] == [None, None]
    plain = attention_attrs(qk_norm_zero_centered=False)
    assert get_default_weight_initializers(plain, 3)[1].value == 1.0
    with pytest.raises(AssertionError, match="grouped-query weight layout"):
        MultiHeadAttentionAttrs(32, 4, output_gate=True)
    with pytest.raises(AssertionError, match="needs rope_theta"):
        MultiHeadAttentionAttrs(32, 4, rotary_dim=4)
    with pytest.raises(AssertionError, match="it needs one"):
        MultiHeadAttentionAttrs(32, 4, qk_norm_zero_centered=True)
    # the gate is a batch-parallel node like any grouped-query one, and not
    # head-parallel: its projections lie in one flat column
    two = TensorShape((2, 8192, 2048), DataType.FLOAT)
    batch = lift_to_parallel_with_degrees(two, 1, 1, (2, 1, 1))
    (out,) = get_parallel_output_shapes(attrs, [batch] * 3)
    assert out.shard_dim_at(0).degree == 2
    heads = lift_to_parallel_with_degrees(two, 1, 2, (1, 1, 1))
    with pytest.raises(AssertionError, match="head-parallel"):
        get_parallel_output_shapes(attrs, [heads] * 3)


def test_published_attention_matches_the_reference():
    """Gate on, a rotary of a quarter of the head, 8 query heads on 1
    key/value head, zero-centred norm weights away from zero: the
    configuration's own reference."""
    attrs = attention_attrs()
    u, ws = attention_case(attrs)
    w = {f"a.weight{i}": t for i, t in enumerate(ws)}
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda row: ref.attention(w, "a", row, TOY))(u)
    np.testing.assert_allclose(program_attention(attrs, u, ws), want, **F32)
    np.testing.assert_allclose(plain_attention(attrs, u, ws), want, **F32)


@pytest.mark.parametrize("gate", [True, False])
@pytest.mark.parametrize("rotary", [4, 16])
def test_gate_and_rotary_width_each_by_itself(gate, rotary):
    """The gate on and off, the rotary on a quarter and on the whole head,
    each against the node written by hand; forward and the gradients."""
    attrs = attention_attrs(output_gate=gate, rotary_dim=rotary)
    u, ws = attention_case(attrs, seed=4)
    np.testing.assert_allclose(
        program_attention(attrs, u, ws), plain_attention(attrs, u, ws), **F32
    )
    cot = rand(np.random.RandomState(5), *u.shape)

    def grads(fn):
        return jax.grad(
            lambda u, ws: jnp.sum(fn(attrs, u, ws) * cot), (0, 1)
        )(u, ws)

    assert_trees_close(
        grads(program_attention), grads(plain_attention), **F32_GRADS
    )
    # the whole-head width is the op as it was before the attribute
    if rotary == 16:
        same = attention_attrs(output_gate=gate, rotary_dim=None)
        np.testing.assert_array_equal(
            program_attention(same, u, ws), program_attention(attrs, u, ws)
        )


def test_grouped_flash_entry_reads_a_key_head_in_place(monkeypatch):
    """`flash_attention_bshf` where its plan reads k and v in place (the
    rows' budget set to nothing: these rows are 1 MB), in interpret mode, 4
    query heads on 2 key/value heads of 128 over two causal tiles, against
    dense attention on the repeated heads: forward and the three gradients,
    dk and dv summed over the group. bf16 kernels against a float32 dense form: 2e-2 is a
    few bf16 roundings (2^-9) of values of order one through a sum of 1,024
    terms."""
    from flexflow_tpu.kernels import flash_attention as fa

    rs = np.random.RandomState(6)
    b, s, h, kv, d = 1, 1024, 4, 2, 128
    q = rand(rs, b, s, h * d, scale=0.5).astype(jnp.bfloat16)
    k = rand(rs, b, s, kv * d, scale=0.5).astype(jnp.bfloat16)
    v = rand(rs, b, s, kv * d, scale=0.5).astype(jnp.bfloat16)
    cot = rand(rs, b, s, h * d)

    monkeypatch.setattr(fa, "_SCOPED_ROWS_BUDGET", 0)
    plan = fa.causal_plan(b, s, h, kv, d, d, 2)
    assert (plan.group, plan.fwd_name) == (2, "flash_fwd_causal_grouped")

    def kernels(q, k, v):
        return fa.flash_attention_bshf(
            q, k, v, h, causal=True, num_kv_heads=kv, interpret=True
        )

    def dense(q, k, v):
        qh = q.astype(jnp.float32).reshape(b, s, h, d)
        kh, vh = (
            jnp.repeat(t.astype(jnp.float32).reshape(b, s, kv, d), h // kv, 2)
            for t in (k, v)
        )
        scores = jnp.einsum("bshd,bthd->bhst", qh, kh) * d ** -0.5
        scores = jnp.where(np.tri(s, dtype=bool), scores, -jnp.inf)
        ctx = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, -1), vh)
        return ctx.reshape(b, s, h * d)

    tol = dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(
        kernels(q, k, v).astype(jnp.float32), dense(q, k, v), **tol
    )

    def grads(fn):
        out = jax.grad(
            lambda *ops: jnp.sum(fn(*ops).astype(jnp.float32) * cot), (0, 1, 2)
        )(q, k, v)
        return [t.astype(jnp.float32) for t in out]

    # gradients of sums over up to 1,024 positions and 2 heads of a group
    for got, want in zip(grads(kernels), grads(dense)):
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=1e-1)
    # which shapes take the grouped NAMES: whole rows that leave the causal
    # forward no room in the default scope, read from the shapes alone; the
    # group is read in place at every row length (PR 63)
    monkeypatch.undo()
    big = attention_attrs(dict(TOY, hidden_size=2048, head_dim=256,
                               num_attention_heads=16, num_key_value_heads=2))
    from flexflow_tpu.kernels.ops import _causal_plan_of

    def form(attrs, s):
        plan = _causal_plan_of(attrs, s, 2)
        return plan.group, plan.fwd_name

    assert form(big, 8192) == (8, "flash_fwd_causal_grouped")
    # 8 MB of rows: fits
    assert form(big, 4096) == (8, "flash_fwd_causal_bshf")
    lfm2 = RingAttentionAttrs(2048, 32, kdim=128, vdim=128, causal=True,
                              num_kv_heads=8)
    assert form(lfm2, 8192) == (4, "flash_fwd_causal_bshf")


# -- the zero-centred norm -------------------------------------------------------


def test_zero_centred_norm_is_the_plain_norm_at_one_plus_w():
    rs = np.random.RandomState(7)
    x, w = rand(rs, 3, 5, 16), rand(rs, 16, scale=0.3)
    zero_centred = RMSNormAttrs(1e-6, zero_centered=True)
    got = kernel_forward(zero_centred, [x], [w])[0]
    np.testing.assert_allclose(got, ref.zrms(x, w, 1e-6), **F32)
    np.testing.assert_allclose(
        got, kernel_forward(RMSNormAttrs(1e-6), [x], [1.0 + w])[0], **F32
    )
    # w starts at zero, where the plain form's gain starts at one
    assert get_default_weight_initializers(zero_centred, 1) == [None]
    assert get_default_weight_initializers(RMSNormAttrs(1e-6), 1)[0].value == 1.0
    batch = lift_to_parallel_with_degrees(
        TensorShape((4, 8, 16), DataType.FLOAT), 1, 1, (2, 1, 1)
    )
    assert zero_centred.parallel_gamma_shape(batch).discard_copy_degree == 2


# -- the gated shared expert and the shares --------------------------------------


def experts_attrs(held, sizes=TOY, total=16):
    return ExpertsAttrs(
        total, sizes["num_experts_per_tok"], sizes["moe_intermediate_size"],
        activation=Activation.SILU, capacity_factor=None, use_bias=False,
        gated=True, renormalize=True, scoring="softmax",
        shared_hidden_size=sizes["shared_expert_intermediate_size"],
        shared_gate=True, held_experts=held,
    )


def experts_case(total, seed=8, seq=24):
    rs = np.random.RandomState(seed)
    d, width = TOY["hidden_size"], TOY["moe_intermediate_size"]
    named = {
        "e.weight0": rand(rs, d, total),
        "e.weight1": rand(rs, total, d, width, scale=0.3),
        "e.weight2": rand(rs, total, d, width, scale=0.3),
        "e.weight3": rand(rs, total, width, d, scale=0.3),
        "e.weight4": rand(rs, d, width, scale=0.3),
        "e.weight5": rand(rs, d, width, scale=0.3),
        "e.weight6": rand(rs, width, d, scale=0.3),
        "e.weight7": rand(rs, d, 1, scale=0.5),
    }
    return named, rand(rs, seq, d)


def share_of(named, first, count):
    ws = [named[f"e.weight{i}"] for i in range(8)]
    return [ws[0]] + [w[first:first + count] for w in ws[1:4]] + ws[4:]


def test_shared_gate_slot_and_its_refusal():
    attrs = experts_attrs((0, 4))
    x = TensorShape((2, 24, 32), DataType.FLOAT)
    shapes = [w.dims for w in get_weight_shapes(attrs, [x])]
    assert shapes[-1] == (32, 1) and len(shapes) == 8
    assert attrs.weight_roles()[-4:] == ["shared"] * 4
    with pytest.raises(AssertionError, match="needs one"):
        ExpertsAttrs(4, 2, 8, use_bias=False, shared_gate=True)


def test_gated_shared_expert_matches_the_reference():
    named, m = experts_case(16)
    with jax.default_matmul_precision("highest"):
        got = experts_forward(
            experts_attrs((0, 16)), m[None], share_of(named, 0, 16)
        )[0][0]
        want, _ = ref.experts(named, "e", m, TOY, held=(0, 16))
        ungated = want - ref.experts(named, "e", m, TOY, held=(0, 16),
                                     shared=False)[0]
    np.testing.assert_allclose(got, want, **F32)
    assert float(jnp.max(jnp.abs(ungated))) > 1e-3  # the shared part is there


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The model's own split in miniature, 32 experts in 16 shares of 2 (512
    in 16 of 32 in the deployment): the shares' routed parts, with the gated
    shared expert counted ONCE, add up to the uncut reference layer over all
    32 experts."""
    named, m = experts_case(32, seed=9)
    with jax.default_matmul_precision("highest"):
        parts = [
            experts_forward(
                experts_attrs((first, 2), total=32), m[None],
                share_of(named, first, 2),
            )[0][0]
            for first in range(0, 32, 2)
        ]
        whole, _ = ref.experts(named, "e", m, TOY, held=(0, 32))
        shared = whole - ref.experts(
            named, "e", m, TOY, held=(0, 32), shared=False
        )[0]
    routed = [part - shared for part in parts]
    assert sum(float(jnp.max(jnp.abs(r))) > 1e-3 for r in routed) >= 8
    np.testing.assert_allclose(sum(routed) + shared, whole, **F32)


# -- the rules -------------------------------------------------------------------


def test_new_rules_audit_sound():
    from flexflow_tpu.analysis.rule_audit import audit_substitution
    from flexflow_tpu.op_attrs.core import OperatorType
    from flexflow_tpu.substitutions.rules import (
        data_parallel_experts_rule,
        data_parallel_state_space_rule,
    )

    for rule in (
        data_parallel_state_space_rule(2, OperatorType.GATED_DELTA, "head"),
        data_parallel_state_space_rule(2, OperatorType.GATED_DELTA),
        data_parallel_experts_rule(2, False, gated=True, shared_gate=True),
    ):
        audit = audit_substitution(rule)
        assert audit.status == "ok", (rule.name, audit.diagnostics)


# -- the whole tiny tower through FFModel ----------------------------------------


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


def norms_away_from_zero(model):
    """Every zero-centred weight of the model moved off its zero start, so
    that the L2 term of the step pulls on it."""
    from test_olmoe import weight_keys

    rs = np.random.RandomState(11)
    for name, key in weight_keys(model.instance).items():
        zero_centred = name.startswith("norm") or name in (
            "attn3.weight1", "attn3.weight2"
        )
        if zero_centred:
            model.params[key] = rand(rs, *model.params[key].shape, scale=0.3)


def test_layers_are_the_published_period():
    assert ref.layer_names(TOY) == [(0, "G"), (1, "G"), (2, "G"), (3, "A")]
    assert ref.counts(TOY) == (3, 1, 0, 4)


def test_zero_centred_weights_start_at_zero_and_the_gain_at_one():
    model = compiled_model(24, max_devices=1)
    named = bench.named_parameters(model.instance, model.params)
    for name in ("norm0a", "norm3b", "norm_f"):
        assert float(jnp.max(jnp.abs(named[f"{name}.weight0"]))) == 0.0
    for j in (1, 2):
        assert float(jnp.max(jnp.abs(named[f"attn3.weight{j}"]))) == 0.0
    np.testing.assert_array_equal(named["gdn0.weight5"], 1.0)
    np.testing.assert_array_equal(named["gdn0.weight3"], 1.0)
    assert float(jnp.max(named["gdn1.weight4"])) <= np.log(16.0)


def test_fit_step_matches_reference_adam_step():
    """The four-layer tower's loss before and after one `fit` step against
    the reference's own gradient and Adam step, the zero-centred weights
    away from zero so that the step's L2 term pulls them (to a gain of one,
    not of zero): 1e-5 is float32 rounding through two forward passes and
    the update. The routing counters report the held rows of the model's
    four expert nodes."""
    from flexflow_tpu.observability import routing, trace

    seq = 24
    model = compiled_model(seq, max_devices=1)
    norms_away_from_zero(model)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    assert float(jnp.max(jnp.abs(named["norm2a.weight0"]))) > 0.1
    before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    # the L2 term is felt: the same step on the reference with the decay off
    # ends elsewhere by more than the bound (`fit` donates the parameters,
    # so every reading of `named` comes before it)
    no_decay = dict(ADAM, weight_decay=0.0)
    _, undecayed = ref.reference_losses(named, inputs, labels, TOY, no_decay)
    assert abs(undecayed - after) > 10 * F32_LOSS
    assert abs(system_loss(model, inputs, labels) - before) <= F32_LOSS
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(system_loss(model, inputs, labels) - after) <= F32_LOSS
    assert before - after > 100 * F32_LOSS  # the step did something
    counted = routing.published()
    assert counted["nodes"] == ["moe0", "moe1", "moe2", "moe3"]
    assert list(counted["decisions"]) == [BATCH * seq * 3] * 4  # one step
    assert 0.0 < counted["held_rows_pct"] < 100.0
    assert trace.attention_routes()["ff.ring_attention.attn3"] == "dense"


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


def test_data_parallel_plan_shards_the_new_ops_and_trains():
    """The batch template on four devices through the searched backend: the
    head-decay delta-rule nodes, the gated attention and the experts beside
    their gated shared expert are sharded over the batch (no node left
    serial), the loss is the one-device loss, and a step reduces it."""
    seq = 24
    inputs, labels = data(seq)
    one = compiled_model(seq, max_devices=1)
    four = compiled_model(
        seq, max_devices=4, search_budget=2,
        force_strategy_seed="dp4xtp1xsp1",
    )
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance
    from test_olmoe import weight_keys

    assert isinstance(four.instance, DistributedTrainingInstance)
    assert four.search_provenance["serial_compute_nodes"] == []
    keys1, keys4 = weight_keys(one.instance), weight_keys(four.instance)
    assert set(keys1) == set(keys4)
    one.params = {
        keys1[name]: jnp.asarray(np.asarray(four.params[keys4[name]]))
        for name in keys1
    }
    first = system_loss(four, inputs, labels)
    assert abs(first - system_loss(one, inputs, labels)) <= F32_LOSS
    four.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert system_loss(four, inputs, labels) < first - 0.01


def test_arithmetic_of_the_published_cut_by_hand():
    sizes = bench.load_json(CONFIG + ".json")
    # the graph at the published widths (shapes only, nothing is allocated):
    # its weights add up to the configuration's `parameters.as_built`
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    builder, _ = ref.build(sizes, 1, 8192)
    graph = builder.graph
    built = sum(
        int(np.prod(graph.tensor_attrs(graph.outputs_of(n)[0]).shape.dims))
        for n in graph.topological_ordering()
        if isinstance(graph.op_attrs(n), WeightAttrs)
    )
    assert built == 625_667_136
    assert sizes["parameters"]["as_built"].startswith("625,667,136 ")
    costs = ref.kernel_costs(sizes, 1, 8192)
    tokens = 8192
    half = 32.5  # (64 + 1) / 2 positions of a chunk
    # K K^T and Q K^T at 16 key heads; the scores' product with U, the
    # triangular solve for 128 + 128 columns and three [128, 128] products a
    # state at 32 value heads; three passes, three nodes
    scan = 16 * 2 * 2 * half * 128 + 32 * (
        2 * half * 128 + 2 * half * 256 + 6 * 128 * 128
    )
    assert costs["gdn_scan"]["flops"] == 3 * tokens * 3 * scan
    # q, k at 16 heads and v, o at 32 in bf16; the decay and beta in float32
    assert costs["gdn_scan"]["bytes"] == 3 * tokens * 3 * (
        2 * (2 * 2048 + 2 * 4096) + 8 * 32
    )
    pairs = 8192 * 8193 / 2
    # 2 products forward and 5 backward over the causal half, 16 heads of 256
    assert costs["flash"]["flops"] == 7 * 2 * pairs * 16 * 256
    # q, o (16 heads) and k, v (2 heads) once forward; with do and the three
    # gradients once backward
    assert costs["flash"]["bytes"] == 6 * 2 * tokens * 256 * (16 + 2)
    forward = (
        3 * (2 * 2048 * (12288 + 64 + 4096) + scan)
        + 2 * 2048 * 256 * (3 * 16 + 2 * 2) + 2 * 2 * pairs * 16 * 256 / 8192
        + 4 * (2 * 2048 * 513 + 3 * 2 * 2048 * (512 * 10 * 32 / 512 + 512))
        + 2 * 2048 * 18992
    )
    assert ref.flops_per_token(sizes, 8192) == 3.0 * forward


# -- the benchmark's CPU rehearsal of the cell -----------------------------------


def test_rehearsal_cell_runs_correct_on_the_cpu_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(BENCH, "rehearsal-qwen3next.json"), "--workload",
         "rehearsal_qwen3next_s128_1chip", "--seed", "2147483659", "--seconds",
         "1", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], (result["checks"], result["losses"])
    assert result["device"]["platform"] == "cpu"
    # no device trace on the CPU mesh: the four trace readers return nothing;
    # the routing counter is the program's and reads here too
    for name in ("gdn_ms", "gdn_scan_roofline", "gqa256_flash_roofline",
                 "qwen3next_moe_held_ms"):
        assert name not in result["metrics"]
    assert 0.0 < result["metrics"]["qwen3next_held_rows_pct"]["value"] < 100.0
    assert "qwen3-next reference routing" in done.stderr
    assert '"windows_per_step_by_node"' in done.stderr
