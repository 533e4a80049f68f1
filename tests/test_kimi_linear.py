"""The delta-rule / latent-attention / held-share expert tower
(`benchmark/configs/kimi-linear-48b-a3b.py`) through the public builder and
`FFModel.compile -> fit`, each part against the plain float32 reference that
lives with the configuration, at toy size on the CPU with seeded weights. The
reference's delta rule runs position by position; the program's in chunks
(the WY form, `kernels/kda.py`). Every tolerance states its reason."""

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
from flexflow_tpu.kernels import kda
from flexflow_tpu.kernels.moe import experts_forward
from flexflow_tpu.kernels.ops import mha_core_route
from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    ExpertsAttrs,
    GatedDeltaAttrs,
    RingAttentionAttrs,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape

CONFIG = os.path.join(BENCH, "configs", "kimi-linear-48b-a3b")
ref = bench.load_module(CONFIG + ".py")

# 4 delta-rule heads of 8 (gates 6 wide, chunks of 8 positions); 4 latent
# attention heads whose key is 8 own + 4 shared columns beside a value of 8,
# over a 12-wide latent row; 4 held of 16 SwiGLU experts of width 24 (top 3)
# beside a shared one; the dense layer and one whole period K K M K
TOY = dict(
    bench.load_json(CONFIG + ".json"),
    hidden_size=32, intermediate_size=48, kv_lora_rank=12,
    linear_attn_config={
        "full_attn_layers": [4], "head_dim": 8, "kda_layers": [1, 2, 3, 5],
        "num_heads": 4, "short_conv_kernel_size": 4,
    },
    kda_gate_rank=6, kda_chunk_size=8, num_attention_heads=4,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    moe_intermediate_size=24, num_experts=4, num_experts_total=16,
    held_experts_first=4, num_experts_per_token=3, vocab_rows_held=96,
    # ten times the published deviation, as in the other towers' tests: at
    # toy width 0.02 leaves every activation so small that a wrong term
    # would hide inside a tolerance
    initializer_range=0.2,
)
BATCH = 2
ADAM = TOY["training"]
LA = TOY["linear_attn_config"]

# the chunked form against the recurrence in float32 on the CPU: the WY form
# inverts a unit-triangular [8, 8] matrix a chunk and sums in another order;
# its gradients pass through that inverse twice. Measured 5e-7 / 6e-6 here.
F32_GRADS = dict(rtol=1e-4, atol=1e-4)


# -- the gated delta-rule op -----------------------------------------------------


def delta_attrs(sizes=TOY, chunk=None):
    la = sizes["linear_attn_config"]
    return GatedDeltaAttrs(
        la["num_heads"], la["head_dim"], la["head_dim"],
        la["short_conv_kernel_size"], sizes["kda_gate_rank"],
        chunk or sizes["kda_chunk_size"], sizes["rms_norm_eps"],
    )


def delta_case(seq, seed=1, sizes=TOY, dt_shift=0.0):
    """(u [b, s, D], weights in slot order): the gates and the decay at a
    size where each matters. `dt_shift` is added to `dt_bias`."""
    rs = np.random.RandomState(seed)
    attrs = delta_attrs(sizes)
    d = sizes["hidden_size"]
    shapes = attrs.weight_shapes(TensorShape((BATCH, seq, d), DataType.FLOAT))
    scales = [0.3, 0.5, 0.5, 1.0, 0.3, 0.5, 0.5, 0.2, 0.3]
    ws = [rand(rs, *s.dims, scale=k) for s, k in zip(shapes, scales)]
    ws[3] = ws[3] + dt_shift
    ws[7] = 1.0 + ws[7]
    return rand(rs, BATCH, seq, d), ws


def reference_kda(u, ws, sizes=TOY):
    named = {f"k.weight{i}": w for i, w in enumerate(ws)}
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.kda(named, "k", row, sizes) for row in u])


def program_kda(u, ws, sizes=TOY):
    with jax.default_matmul_precision("highest"):
        return kernel_forward(delta_attrs(sizes), [u], ws)[0]


def test_delta_slots_and_shapes():
    attrs = delta_attrs()
    shapes = attrs.weight_shapes(TensorShape((2, 16, 32), DataType.FLOAT))
    # q | k | v | decay gate | output gate | step: 3 * 32 + 2 * 6 + 4
    assert [s.dims for s in shapes] == [
        (32, 112), (4, 96), (6, 32), (32,), (4,), (6, 32), (32,), (8,),
        (32, 32),
    ]
    assert attrs.num_weights == len(shapes) == 9
    with pytest.raises(AssertionError):
        GatedDeltaAttrs(4, 8, 8, chunk_size=24)  # halved level by level


# 24 positions are three chunks of 8; 21 ends inside a chunk (padded with
# positions that write nothing and decay nothing)
@pytest.mark.parametrize("seq", [24, 21])
def test_chunked_delta_rule_matches_the_recurrence(seq):
    u, ws = delta_case(seq)
    np.testing.assert_allclose(program_kda(u, ws), reference_kda(u, ws), **F32)


def test_delta_rule_gradients_match_the_recurrence():
    """Of x and of every weight, through the written backward of the
    chunk-to-chunk pass and JAX's own of the chunks' operands."""
    u, ws = delta_case(24, seed=2)
    cot = rand(np.random.RandomState(9), *u.shape)

    def loss(fn):
        return jax.grad(
            lambda u, ws: jnp.sum(fn(u, ws) * cot), argnums=(0, 1)
        )(u, ws)

    got, want = loss(program_kda), loss(reference_kda)
    for g in jax.tree_util.tree_leaves(want):
        assert float(jnp.max(jnp.abs(g))) > 1e-3  # every weight is reached
    assert_trees_close(got, want, **F32_GRADS)


def test_a_decay_that_overflows_exp_of_minus_g_stays_finite_and_equal():
    """`dt_bias` + 30: every log-decay is below -10 a position, so over one
    chunk of 8 the textbook K * exp(-G) is exp(80+) per position and
    overflows float32 (3.4e38 = e^88.7) inside the chunk; the program only
    ever takes exp of sums of log-decays, which are <= 0."""
    u, ws = delta_case(24, seed=3, dt_shift=30.0)
    # the log-decays of the first sequence, as the op computes them
    f_up = (u[0] @ ws[0])[:, 96:102] @ ws[2] + ws[3]
    g = -jnp.repeat(jnp.exp(ws[4]), 8)[None, :] * jax.nn.softplus(f_up)
    assert float(jnp.max(jnp.cumsum(-g, axis=0)[7])) > 88.7  # exp(-G) is inf
    got, want = program_kda(u, ws), reference_kda(u, ws)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, **F32)
    grads = jax.grad(lambda u, ws: jnp.sum(program_kda(u, ws) ** 2), (0, 1))(u, ws)
    assert all(
        bool(jnp.all(jnp.isfinite(t))) for t in jax.tree_util.tree_leaves(grads)
    )


def test_scan_route_is_read_from_shapes_and_backend(monkeypatch, entered):
    # the CPU, whatever the shape: the scan over the chunks
    assert kda.scan_route(128, 128, 64) == "xla"
    assert kda.scan_route(8, 8, 8) == "xla"
    # a TPU at the published shape (32 heads of 128, chunks of 64): the
    # Pallas kernels; toy heads stay with XLA there too
    entered(context.described_tpu())
    assert kda.scan_route(128, 128, 64) == "kda"
    assert kda.scan_route(8, 8, 8) == "xla"
    assert kda.scan_route(128, 64, 64) == "xla"
    with context.no_flash():
        assert kda.scan_route(128, 128, 64) == "xla"
    # ONE route decides the chunks' operands and the chunk-to-chunk pass
    # alike: "kda" takes both from the kernels, "xla" the operands from
    # today's `chunk_operands` and the pass from the scan over the chunks
    took = []
    scan = kda.chunk_scan

    def operands_from(name):
        operands = getattr(kda, name)

        def spy(*args):
            took.append(name)
            return operands(*args)
        return spy

    def spy_scan(route, *operands):
        took.append(route)
        return scan("xla", *operands)

    # the scores' kernels take the node's raw inputs: run them (interpreted)
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setattr(kda, "kernel_operands", operands_from("kernel_operands"))
    monkeypatch.setattr(kda, "chunk_operands", operands_from("chunk_operands"))
    monkeypatch.setattr(kda, "chunk_scan", spy_scan)
    attrs = GatedDeltaAttrs(1, 128, 128, 4, 8, 64, 1e-5)
    rs = np.random.RandomState(8)
    shapes = attrs.weight_shapes(TensorShape((1, 64, 16), DataType.FLOAT))
    ws = [rand(rs, *s.dims, scale=0.3) for s in shapes]
    u = rand(rs, 1, 64, 16)
    kda.gated_delta_forward(attrs, u, ws)
    with context.no_flash():
        kda.gated_delta_forward(attrs, u, ws)
    assert took == ["kernel_operands", "kda", "chunk_operands", "xla"]


def test_chunk_kernels_agree_with_the_scan_over_chunks(monkeypatch):
    """The Pallas kernels (interpret mode) against the XLA form of the same
    chunk-to-chunk pass, forward and all six cotangents, at lane-sized heads:
    the same products in the same order, so float32 agrees to the last few
    bits."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert kda.scan_route(128, 128, 64) == "kda"
    rs = np.random.RandomState(5)
    b, h, s, d, chunk = 1, 2, 128, 128, 64

    def unit(t):
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    q, k = unit(rand(rs, b, h, s, d)), unit(rand(rs, b, h, s, d))
    v = rand(rs, b, h, s, d)
    g = -jnp.abs(rand(rs, b, h, s, d, scale=0.3))
    beta = jax.nn.sigmoid(rand(rs, b, h, s))
    cot = rand(rs, b, h, s // chunk, chunk, d)

    def run(route):
        with jax.default_matmul_precision("highest"):
            operands = kda.chunk_operands(q, k, v, g, beta, chunk)
            return jax.value_and_grad(
                lambda *ops: jnp.sum(kda.chunk_scan(route, *ops) * cot),
                argnums=tuple(range(6)),
            )(*operands)

    assert_trees_close(run("kda"), run("xla"), rtol=1e-5, atol=1e-5)


# Two heads of 128 | 128 in chunks of 64. 256 positions and 100 (padded to
# 128) are four and two chunks a head, all of a head's in ONE program of the
# scores' kernels; 192 are three, one a program (`_PREP_CHUNKS`), and on ONE
# head an odd number of chunks, which XLA's `unit_lower_inverse` inverts
# where the inverse's kernel takes them two by two. `dt_bias` 30 lower than
# drawn with `a_log` + 6 puts every log-decay below -10 a position, so that
# exp(-G) of the textbook form overflows inside a chunk of 64 (e^88.7) ten
# times over; `far_gates` draws `dt_bias` around -2 and `a_log` around 1.5, so
# that the softplus is on its bend and both vectors' gradients are sums of
# many terms of one sign and no rounding noise
@pytest.mark.parametrize(
    "seq,dt_shift,a_shift,h",
    [(256, 0.0, 0.0, 2), (192, 0.0, 0.0, 1), (128, 30.0, 0.0, 2),
     (100, 0.0, 0.0, 2), (128, -2.0, 1.5, 2)],
    ids=["plain_decays", "one_chunk_a_program", "decay_overflows_exp_minus_g",
         "padded_to_the_chunk", "far_gates"],
)
def test_operand_kernels_agree_with_the_xla_operands(
    monkeypatch, seq, dt_shift, a_shift, h
):
    """`kernel_operands` (the Pallas kernels in interpret mode: the scores
    forward and WRITTEN backward, which read the RAW q | k | v and decay
    pre-activation in the model's layout and do the gates in VMEM; the
    triangular inverse) against the XLA form (`heads_first` + `_unit` +
    softplus + `chunk_operands`, differentiated by JAX) at lane-sized heads
    in float32: the six operands and the cotangents of qkv, f_up, dt_bias,
    a_log, v and beta. The kernels take every exponent as an exact sum of
    log-decays where XLA subtracts two running sums, and sum a level's
    cotangents in another order: measured 4e-6 at values of 4 here; the
    two vectors' gradients are sums over every position."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    rs = np.random.RandomState(11)
    b, d, chunk = 1, 128, 64
    pad = -seq % chunk

    def heads_first(t):
        # as `_recurrence` pads: a position that writes nothing (beta 0, k
        # 0) and decays nothing (g 0)
        t = jnp.swapaxes(t.reshape(b, seq, h, -1), 1, 2)
        return jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))

    qkv = rand(rs, b, seq, 3 * h * d)
    f_up = rand(rs, b, seq, h * d)
    dt_bias = rand(rs, h * d, scale=0.3) - 1.0 + dt_shift
    a_log = rand(rs, h, scale=0.3) - 1.0 + a_shift
    b_logit = rand(rs, b, seq, h)
    slowest = -jnp.exp(jnp.min(a_log)) * jax.nn.softplus(f_up + dt_bias)
    if dt_shift > 0:
        assert float(jnp.min(jnp.cumsum(-slowest, axis=1)[:, chunk - 1])) > 88.7
    inputs = (qkv, f_up, dt_bias, a_log, b_logit)
    c = (seq + pad) // chunk
    assert c >= 2
    cots = [
        rand(rs, b, h, c, chunk, width) for width in (d, d, d, d, chunk)
    ] + [rand(rs, b, h, c, 1, d)]

    def v_beta(qkv, b_logit):
        v = heads_first(qkv[..., 2 * h * d:])
        return v, jax.nn.sigmoid(heads_first(b_logit))[..., 0]

    def kernels(qkv, f_up, dt_bias, a_log, b_logit):
        return kda.kernel_operands(
            qkv, f_up, dt_bias, a_log, *v_beta(qkv, b_logit), chunk
        )

    def xla(qkv, f_up, dt_bias, a_log, b_logit):
        q = kda._unit(heads_first(qkv[..., :h * d]), d ** -0.5)
        k = kda._unit(heads_first(qkv[..., h * d:2 * h * d]), 1.0)
        g = -jnp.exp(a_log)[:, None, None] * jax.nn.softplus(
            heads_first(f_up) + dt_bias.reshape(h, 1, d)
        )
        # a padded position's g is rate * softplus(dt_bias) here; the op
        # pads AFTER the softplus
        g = g * (jnp.arange(seq + pad) < seq)[:, None]
        v, beta = v_beta(qkv, b_logit)
        return kda.chunk_operands(q, k, v, g, beta, chunk)

    def run(operands_of):
        def loss(*inputs):
            operands = operands_of(*inputs)
            return sum(
                jnp.sum(o * cot) for o, cot in zip(operands, cots)
            ), operands

        with jax.default_matmul_precision("highest"):
            (_, operands), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True
            )(*inputs)
        return operands, grads

    got, want = run(kernels), run(xla)
    assert all(
        bool(jnp.all(jnp.isfinite(t))) for t in jax.tree_util.tree_leaves(got)
    )
    if a_shift:  # dt_bias's and a_log's are more than rounding noise
        assert float(jnp.min(jnp.abs(want[1][3]))) > 1.0
        assert float(jnp.median(jnp.abs(want[1][2]))) > 0.1
    # a_log's gradient is the sum of dg g over a head: it carries dg's error
    # times |g|, which the overflowing case makes 9 and more
    (*got_in, got_a, got_b), (*want_in, want_a, want_b) = got[1], want[1]
    assert_trees_close(
        (got[0], got_in, got_b), (want[0], want_in, want_b),
        rtol=1e-4, atol=1e-5,
    )
    np.testing.assert_allclose(
        got_a, want_a, rtol=1e-4,
        atol=1e-5 * max(1.0, float(jnp.max(-slowest))),
    )


def test_the_whole_node_on_the_kernels_agrees_with_the_xla_route(monkeypatch):
    """`gated_delta_forward` at two heads of 128 | 128 over two chunks: the
    "kda" route (every kernel interpreted: the scores' read the convolved
    q | k and the decay's pre-activation where they lie and do the gates in
    VMEM) against the "xla" route (`no_flash()`): the output and the
    gradients of the input and all nine weights, float32. The routes differ
    where the operands' test says, and the node's projections multiply that
    by a hidden size of 32."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    attrs = GatedDeltaAttrs(2, 128, 128, 4, 8, 64, 1e-5)
    rs = np.random.RandomState(21)
    b, seq, hidden = 1, 128, 32
    shapes = attrs.weight_shapes(TensorShape((b, seq, hidden), DataType.FLOAT))
    scales = [0.3, 0.5, 0.5, 1.0, 0.3, 0.5, 0.5, 0.2, 0.1]
    ws = [rand(rs, *s.dims, scale=k) for s, k in zip(shapes, scales)]
    ws[7] = 1.0 + ws[7]
    u, cot = rand(rs, b, seq, hidden), rand(rs, b, seq, hidden)
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


# The products around the triangular inverse (`kda._kernel_corrected`, PR 54):
# `lead` chunk-heads [b, h, c] of both cells' shape, chunks of 64 at heads of
# 128 | 128. Eight are ONE program of `kda_corrected_fwd` / `kda_corrected_bwd`,
# four one of four, two one of two (`_CORRECTED_HEADS`) and one pair of the
# inverse's kernel; six are three pairs, one a program of the inverse's, a
# count that is no multiple of four; an odd number of chunks a head the
# kernels do not take. Since PR 71 A, the inverse and dA cross HBM two
# chunk-heads to a row (`in_pairs`, `kda._side_by_side`).


def triangular_case(lead, dtype, seed=17):
    """((a, kd, v, beta), (dw, duv)): a strictly lower float32 [.., 64, 64]
    at the size of unit keys' scores, kd float32 and v, dw, duv in `dtype`
    [.., 64, 128], beta in (0, 1)."""
    rs = np.random.RandomState(seed)
    q, d = 64, 128
    a = jnp.asarray(np.tril(rs.randn(*lead, q, q), -1) * 0.2, jnp.float32)
    kd = rand(rs, *lead, q, d)
    v, dw, duv = (rand(rs, *lead, q, d).astype(dtype) for _ in range(3))
    beta = jnp.asarray(rs.rand(*lead, q), jnp.float32)
    return (a, kd, v, beta), (dw, duv)


def triangular_products(products, operands, cots):
    """((w, uv), the cotangents of a, kd, v, beta) of `products`."""
    f32 = jnp.float32

    def loss(*operands):
        out = products(*operands)
        return sum(
            jnp.sum(o.astype(f32) * cot.astype(f32)) for o, cot in zip(out, cots)
        ), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True
        )(*operands)
    return out, grads


def xla_corrected(a, kd, v, beta):
    return kda._corrected(a, kd, v, beta, v.dtype)


def in_pairs(a):
    """[.., c, Q, Q] -> [.., c / 2, Q, 2Q]: chunk 2 p in lanes [0, Q), chunk
    2 p + 1 beside it, as the operands' kernels write A where a head's
    chunks are even in number (`kda._side_by_side`)."""
    return jnp.concatenate([a[..., 0::2, :, :], a[..., 1::2, :, :]], axis=-1)


def kernel_corrected(a, kd, v, beta):
    """`kda._kernel_corrected` as the operands' kernels hand it A: in pairs,
    or a chunk a row where a head's chunks are odd in number; a's cotangent
    comes back [.., c, Q, Q] through `in_pairs`' own transpose."""
    return kda._kernel_corrected(
        a if a.shape[-3] % 2 else in_pairs(a), kd, v, beta
    )


def assert_triangular_products_agree(got, want, dtype):
    """w, uv to float32 rounding, or in bf16 to the last bit but for a value
    in a thousand one ulp off (a float32 sum in another order rounds the
    other way where it lies at a bf16 tie; where the sum's terms cancel, an
    ulp of the float32 terms, 1e-5 at values of 10); every cotangent to 1e-5
    of its largest value (measured 4e-7: the inverse's kernel and the
    products add in another order than XLA's float32 ones)."""
    for g, w in zip(got[0], want[0]):
        assert g.dtype == w.dtype == dtype
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if dtype == jnp.bfloat16:
            assert np.all(np.abs(g - w) <= 2.0 ** -7 * np.abs(w) + 1e-5)
            assert np.mean(g != w) < 1e-3
        else:
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    for g, w in zip(got[1], want[1]):
        assert g.dtype == w.dtype and bool(jnp.all(jnp.isfinite(g)))
        tol = 2.0 ** -7 if w.dtype == jnp.bfloat16 else 1e-5  # v's: an ulp
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.max(np.abs(w)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "lead", [(1, 2, 4), (1, 2, 2), (1, 1, 3), (1, 1, 2), (1, 3, 2), (2, 1, 3)],
    ids=["eight_a_program", "four_a_program", "odd_count_falls_back",
         "two_a_program", "three_pairs_one_a_program",
         "odd_chunks_a_head_fall_back"],
)
def test_triangular_product_kernels_agree_with_xlas_products(
    monkeypatch, lead, dtype
):
    """`kda._kernel_corrected` (interpret mode: the inverse's kernel, then
    `kda_corrected_fwd` and the WRITTEN backward `kda_corrected_bwd`, three
    products a chunk-head) against `_corrected` with `unit_lower_inverse` and
    JAX's own gradient of it (six): T (K exp(G)), T V and the cotangents of
    A, K exp(G), V and beta. A float32 step gives every operand three bf16
    parts, a bf16 step leaves out the terms of v's, dw's and duv's second and
    third, which are exactly zero. An odd number of chunks a head (and so
    every odd number of chunk-heads) keeps XLA's form, bit for bit."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    operands, cots = triangular_case(lead, dtype)
    got = triangular_products(kernel_corrected, operands, cots)
    want = triangular_products(xla_corrected, operands, cots)
    if lead[-1] % 2:
        assert_trees_close(got, want, rtol=0, atol=0)
    else:
        assert float(jnp.max(jnp.abs(got[1][0]))) > 1e-2  # dn is reached
        assert not np.any(np.triu(np.asarray(got[1][0])))
        assert_triangular_products_agree(got, want, dtype)


@pytest.mark.parametrize(
    "lead", [(1, 2, 4), (1, 3, 2), (1, 1, 2)],
    ids=["four_pairs_a_program", "three_pairs_one_a_program", "one_pair"],
)
def test_the_inverses_kernel_agrees_with_unit_lower_inverse(monkeypatch, lead):
    """`kda._pallas_inverse` (interpret mode: A and beta in, Diag(beta) A
    formed in VMEM, the pair written as ONE [Q, 2Q] row block) against
    `unit_lower_inverse` of XLA's Diag(beta) A, laid in pairs: the levels
    are the same and the products add in another order (measured 2e-7 at
    entries of 1)."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    a, _, _, beta = triangular_case(lead, jnp.float32, seed=19)[0]
    got = kda._pallas_inverse(in_pairs(a), beta, True)
    with jax.default_matmul_precision("highest"):
        want = in_pairs(kda.unit_lower_inverse(beta[..., :, None] * a))
    assert got.shape == (*lead[:2], lead[2] // 2, 64, 128)
    assert float(jnp.max(jnp.abs(want))) >= 1.0  # the diagonal is there
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_a_step_of_zero_and_a_padded_position_give_zero_rows(monkeypatch):
    """A beta of exactly 0 and a padded position (k = 0, so A's row and column
    and K exp(G)'s row are 0, beta 0 as `_recurrence` pads it) write nothing:
    their rows of T (K exp(G)) and T V are zero, every gradient is finite and
    agrees with XLA's form."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    (a, kd, v, beta), cots = triangular_case((1, 1, 2), jnp.float32, seed=18)
    still, padded = 5, slice(40, 64)
    beta = beta.at[..., still].set(0.0).at[..., padded].set(0.0)
    a = a.at[..., padded, :].set(0.0).at[..., :, padded].set(0.0)
    kd = kd.at[..., padded, :].set(0.0)
    operands = (a, kd, v, beta)
    got = triangular_products(kernel_corrected, operands, cots)
    for t in got[0]:
        assert not np.any(np.asarray(t)[..., still, :])
        assert not np.any(np.asarray(t)[..., padded, :])
        assert float(jnp.min(jnp.max(jnp.abs(t[..., :still, :]), axis=-1))) > 0
    want = triangular_products(xla_corrected, operands, cots)
    assert_triangular_products_agree(got, want, jnp.float32)


# The heads' norm under its gate (`kda.head_norm_gate`, PR 58): rows of `heads`
# heads of 128 lanes, o HEADS FIRST as the recurrence leaves it. Two rows of a
# hundred positions pad to 128 each, a program of two 64-row steps a batch row;
# 192 positions of eight heads are three blocks of one step (`_NORM_BLOCKS`: 64
# rows divide them, 128 do not) times two groups of `_NORM_HEADS` heads, so the
# partial sums of the gain's and the bias's gradients meet over batch rows,
# over blocks, over groups, over steps and over heads.

HEAD_NORM_SHAPES = [((2, 100), 2), ((1, 192), 8)]
HEAD_NORM_IDS = ["two_heads_positions_padded", "eight_heads_six_programs"]


def head_norm_case(lead, heads, dtype, biased, seed=41):
    """((o, x, bias or None, gain), dy): o, x, dy [*lead, heads * 128]."""
    rs = np.random.RandomState(seed)
    dv = 128
    o, x, dy = (rand(rs, *lead, heads * dv).astype(dtype) for _ in range(3))
    bias = rand(rs, heads * dv, scale=0.3).astype(dtype) if biased else None
    gain = (1.0 + rand(rs, dv, scale=0.1)).astype(dtype)
    return (o, x, bias, gain), dy


def plain_head_norm(o, x, bias, gain, heads, eps=1e-5):
    if bias is None:
        return kda._head_norm_silu(o, x, gain, heads, eps)
    return kda._head_norm_gate(o, x, bias, gain, heads, eps)


def heads_first(o, heads):
    """o [b, s, heads * dv] -> [b, heads, s, dv]."""
    b, s, _ = o.shape
    return jnp.swapaxes(o.reshape(b, s, heads, -1), 1, 2)


def kernel_head_norm(o, x, bias, gain, heads, eps=1e-5, first=0):
    """`kda.head_norm_gate` on o in the model's layout, so that its
    cotangent comes back in that layout too."""
    return kda.head_norm_gate(heads_first(o, heads), x, bias, gain, eps, first)


def head_norm_with_gradients(norm, operands, dy, heads):
    """(y, the cotangents of o, x, (bias,) gain) of `norm`."""
    y, vjp = jax.vjp(lambda *t: norm(*t, heads), *operands)
    return y, [g for g in vjp(dy) if g is not None]


def assert_head_norms_agree(got, want, dtype):
    """y and every cotangent in the operand's dtype: float32 to its
    round-off (the kernels add a head's squares and the partial sums in
    another order: measured 4e-7 of the largest value), bf16 to its last bit
    (a float32 value at a bf16 tie rounds the other way)."""
    for g, w in zip([got[0]] + got[1], [want[0]] + want[1]):
        assert g.shape == w.shape and g.dtype == w.dtype == dtype
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.max(np.abs(w)) > 1e-2  # the operand is reached
        if dtype == jnp.bfloat16:
            assert np.all(np.abs(g - w) <= 2.0 ** -7 * np.abs(w) + 1e-6)
            assert np.mean(g != w) < 1e-2
        else:
            np.testing.assert_allclose(
                g, w, rtol=1e-5, atol=2e-6 * np.max(np.abs(w))
            )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("lead,heads", HEAD_NORM_SHAPES, ids=HEAD_NORM_IDS)
def test_head_norm_kernels_agree_with_the_plain_sigmoid_gated_norm(
    monkeypatch, lead, heads, dtype
):
    """`kda.head_norm_gate` with a bias (interpret mode: `head_norm_gate_fwd`
    and the WRITTEN backward `head_norm_gate_bwd`) against
    `_head_norm_gate` and JAX's own gradient of it: y and the cotangents of
    o, gate_up, gate_bias and the gain."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    operands, dy = head_norm_case(lead, heads, dtype, biased=True)
    got = head_norm_with_gradients(kernel_head_norm, operands, dy, heads)
    want = head_norm_with_gradients(plain_head_norm, operands, dy, heads)
    assert len(got[1]) == 4
    assert_head_norms_agree(got, want, dtype)


@pytest.mark.parametrize("off", ["value_dim_64", "no_flash"])
def test_the_sigmoid_gated_norm_off_the_route_is_the_plain_form(monkeypatch, off):
    """Heads of 64 value features and a trace under `no_flash()` leave the
    node on the "xla" route: `_gated_head_norm` is `_head_norm_gate`, bit for
    bit, and no kernel is called."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setattr(kda, "head_norm_gate", None)
    dv = 64 if off == "value_dim_64" else 128
    attrs = GatedDeltaAttrs(2, 128, dv, 4, 8, 64, 1e-5)
    rs = np.random.RandomState(43)
    o, x = rand(rs, 1, 2, 32, dv), rand(rs, 1, 32, 2 * dv)
    bias, gain = rand(rs, 2 * dv, scale=0.3), 1.0 + rand(rs, dv, scale=0.1)

    def run():
        route = kda.scan_route(128, dv, 64)
        return route, kda._gated_head_norm(attrs, route, o, x, bias, gain)

    if off == "no_flash":
        with context.no_flash():
            route, got = run()
    else:
        route, got = run()
    assert route == "xla"
    in_rows = jnp.swapaxes(o, 1, 2).reshape(1, 32, 2 * dv)
    want = kda._head_norm_gate(in_rows, x, bias, gain, 2, 1e-5)
    assert_trees_close(got, want, rtol=0, atol=0)


# -- latent attention ------------------------------------------------------------


def latent_attrs(sizes=TOY):
    return RingAttentionAttrs(
        sizes["hidden_size"], sizes["num_attention_heads"],
        kdim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
        vdim=sizes["v_head_dim"], kv_latent_rank=sizes["kv_lora_rank"],
        shared_key_dim=sizes["qk_rope_head_dim"],
        kv_latent_norm_eps=sizes["rms_norm_eps"], causal=True,
    )


def latent_case(seq, seed=6, sizes=TOY):
    rs = np.random.RandomState(seed)
    attrs = latent_attrs(sizes)
    d = sizes["hidden_size"]
    x = TensorShape((BATCH, seq, d), DataType.FLOAT)
    flat = attrs.weights_shape(x, x, x)
    return (
        rand(rs, BATCH, seq, d),
        [rand(rs, *flat.dims, scale=0.3), 1.0 + rand(rs, sizes["kv_lora_rank"], scale=0.2)],
    )


def reference_mla(u, ws, sizes=TOY):
    named = {"m.weight0": ws[0], "m.weight1": ws[1]}
    with jax.default_matmul_precision("highest"):
        return jnp.stack([ref.mla(named, "m", row, sizes) for row in u])


def program_mla(u, ws, sizes=TOY):
    with jax.default_matmul_precision("highest"):
        return kernel_forward(latent_attrs(sizes), [u, u, u], ws)[0]


def test_latent_attention_slots():
    attrs = latent_attrs()
    x = TensorShape((2, 16, 32), DataType.FLOAT)
    # Wq 32 x 4*12 | Wkv_a 32 x (12+4) | Wkv_b 12 x 4*(8+8) | Wo 4*8 x 32
    assert attrs.weights_shape(x, x, x).dims == (1536 + 512 + 768 + 1024, 1)
    assert attrs.latent_gain_shape(x).dims == (12,)
    assert attrs.q_proj_size == 12 and attrs.own_key_dim == 8
    # a rotary beside the latent rank is the shared slice's since PR 53
    # (`tests/test_joyai_llm_flash.py`); QK-norm is still refused
    with pytest.raises(AssertionError):
        RingAttentionAttrs(32, 4, kdim=12, vdim=8, kv_latent_rank=12,
                           shared_key_dim=4, qk_norm_eps=1e-5, causal=True)


def test_latent_attention_matches_plain_softmax_attention():
    u, ws = latent_case(20)
    np.testing.assert_allclose(program_mla(u, ws), reference_mla(u, ws), **F32)


def test_latent_attention_gradients_reach_the_shared_key_slice():
    """Of x, the flat weight and the latent norm's gain; the columns of
    Wkv_a that give the slice all heads share take the sum of every head's
    key gradient, and are checked by themselves."""
    u, ws = latent_case(20, seed=7)
    cot = rand(np.random.RandomState(8), *u.shape)

    def grads(fn):
        return jax.grad(
            lambda u, ws: jnp.sum(fn(u, ws) * cot), argnums=(0, 1)
        )(u, ws)

    got, want = grads(program_mla), grads(reference_mla)
    assert_trees_close(got, want, **F32_GRADS)
    a0 = 32 * 4 * 12  # where Wkv_a [32, 12 + 4] starts in the flat column
    shared = np.asarray(want[1][0]).reshape(-1)[a0:a0 + 512].reshape(32, 16)[:, 12:]
    assert np.abs(shared).max() > 1e-3
    np.testing.assert_allclose(
        np.asarray(got[1][0]).reshape(-1)[a0:a0 + 512].reshape(32, 16)[:, 12:],
        shared, **F32_GRADS,
    )


def test_wide_key_route_is_read_from_shapes_and_backend(monkeypatch, entered):
    attrs = latent_attrs(bench.load_json(CONFIG + ".json"))
    shape = (1, 4096, 2304)
    assert mha_core_route(attrs, shape, shape, shape, True) == "dense"  # the CPU
    entered(context.described_tpu())
    # the 192-wide key on the causal tile kernels, padded to 256
    assert mha_core_route(attrs, shape, shape, shape, True) == "fused_row"
    assert flash.wide_key_padded(192) == 256
    # one causal tile or less has no such body
    short = (1, 512, 2304)
    assert mha_core_route(attrs, short, short, short, True) == "dense"
    # and an ordinary kd != vd node never took a fused row
    plain = RingAttentionAttrs(2304, 32, kdim=192, vdim=128, causal=True)
    assert mha_core_route(plain, shape, shape, shape, True) != "fused_row"


def test_wide_key_kernels_match_dense_attention(monkeypatch):
    """`flash_attention_bshf` in interpret mode on a 256-wide padded key
    beside a 128-wide value, two causal tiles: forward and the three
    gradients against XLA's masked softmax at the scale of the TRUE width."""
    rs = np.random.RandomState(11)
    b, s, h, kd, vd = 1, 1024, 2, 256, 128
    q, k = rand(rs, b, s, h * kd, scale=0.5), rand(rs, b, s, h * kd, scale=0.5)
    v, cot = rand(rs, b, s, h * vd), rand(rs, b, s, h * vd)
    scale = 192 ** -0.5

    def dense(q, k, v):
        qh, kh = q.reshape(b, s, h, kd), k.reshape(b, s, h, kd)
        scores = jnp.einsum("bshk,bthk->bhst", qh, kh) * scale
        mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return jnp.einsum("bhst,bthv->bshv", p, v.reshape(b, s, h, vd)).reshape(
            b, s, h * vd
        )

    def kernel(q, k, v):
        return flash.flash_attention_bshf(
            q, k, v, h, causal=True, scale=scale, interpret=True
        )

    def run(fn):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda *a: jnp.sum(fn(*a) * cot), argnums=(0, 1, 2)
            )(q, k, v)

    # the kernels take exp2 of scaled scores and fold row sums by lanes
    assert_trees_close(run(kernel), run(dense), rtol=2e-4, atol=2e-4)


# -- the experts: the gated form, a shared expert, a held share -----------------


def experts_attrs(held, sizes=TOY):
    return ExpertsAttrs(
        sizes["num_experts_total"], sizes["num_experts_per_token"],
        sizes["moe_intermediate_size"], activation=Activation.SILU,
        capacity_factor=None, use_bias=False, gated=True, renormalize=True,
        scoring="sigmoid", selection_bias=True,
        routed_scale=sizes["routed_scaling_factor"],
        shared_hidden_size=sizes["moe_intermediate_size"], held_experts=held,
    )


def experts_case(seed=4, tokens=48, sizes=TOY):
    """(tokens [n, D], the UNCUT layer's weights by reference name)."""
    rs = np.random.RandomState(seed)
    d, e, width = (
        sizes["hidden_size"], sizes["num_experts_total"],
        sizes["moe_intermediate_size"],
    )
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
    return rand(rs, tokens, d), named


def share_of(named, first, count):
    ws = [named[f"e.weight{i}"] for i in range(8)]
    for i in (2, 3, 4):
        ws[i] = ws[i][first:first + count]
    return ws


def reference_experts(m, named, first, count):
    held = {f"e.weight{i}": w for i, w in enumerate(share_of(named, first, count))}
    with jax.default_matmul_precision("highest"):
        return ref.experts(held, "e", m, TOY, held=(first, count))[0]


@pytest.mark.parametrize("held", [(0, 16), (4, 4), (12, 4)], ids=str)
def test_gated_shared_held_experts_forward_and_gradients(held):
    """The first configuration to run the gated form through the held rows
    with a gated shared expert beside them."""
    m, named = experts_case()
    ws = share_of(named, *held)
    cot = rand(np.random.RandomState(2), *m.shape)

    def program(m, ws):
        with jax.default_matmul_precision("highest"):
            return experts_forward(experts_attrs(held), m[None], ws)[0][0]

    def reference(m, ws):
        w = {f"e.weight{i}": t for i, t in enumerate(ws)}
        with jax.default_matmul_precision("highest"):
            return ref.experts(w, "e", m, TOY, held=held)[0]

    np.testing.assert_allclose(program(m, ws), reference(m, ws), **F32)
    trainable = [0, 2, 3, 4, 5, 6, 7]  # not the selection bias, a buffer

    def grads(fn):
        g = jax.grad(lambda m, ws: jnp.sum(fn(m, ws) * cot), (0, 1))(m, ws)
        return g[0], [g[1][i] for i in trainable]

    assert_trees_close(grads(program), grads(reference), **F32_GRADS)


def test_shares_add_up_to_the_uncut_layer():
    """The model's own split in miniature: all the held ranges of one expert
    layer (4 of 4 experts here, 32 of 8 in the deployment), the shared
    expert counted ONCE, sum to the uncut reference over all 16 experts."""
    m, named = experts_case(seed=5)
    with jax.default_matmul_precision("highest"):
        shared = ref.swiglu(m, *(named[f"e.weight{i}"] for i in (5, 6, 7)))
        parts = [
            experts_forward(
                experts_attrs((first, 4)), m[None], share_of(named, first, 4)
            )[0][0] - shared
            for first in (0, 4, 8, 12)
        ]
    whole = reference_experts(m, named, 0, 16)
    for part in parts:  # every share is a strict part of the layer
        assert float(jnp.max(jnp.abs(part))) > 1e-3
        assert float(jnp.max(jnp.abs(part - (whole - shared)))) > 1e-3
    np.testing.assert_allclose(sum(parts) + shared, whole, **F32)


# -- the whole tiny tower through FFModel --------------------------------------


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


def rehearsal_choices(monkeypatch, ref, name, adam, **lane_sized):
    """`trace.kernel_choices()` after the loss of the rehearsal cell
    `benchmark/configs/rehearsal-<name>.json` is traced (nothing runs) on the
    kernels' route: one row of 128 positions, its delta-rule heads
    `lane_sized` to the published 128 | 128 in chunks of 64, which is what
    the route asks of a node (`kda.scan_route`)."""
    from flexflow_tpu.observability import trace

    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setattr(context, "_CHOICES", {})
    sizes = dict(
        bench.load_json(os.path.join(BENCH, "configs", f"rehearsal-{name}.json")),
        **lane_sized,
    )
    builder, logits = ref.build(sizes, 1, 128)
    model = FFModel.from_computation_graph(
        builder, logits, FFConfig(batch_size=1, seed=7, print_freq=0)
    )
    model.compile(
        AdamOptimizer(
            alpha=adam["alpha"], beta1=adam["beta1"], beta2=adam["beta2"],
            epsilon=adam["epsilon"], weight_decay=adam["weight_decay"],
        ),
        adam["loss"],
    )
    inputs, labels = ref.make_data(np.random.RandomState(0), sizes, 1, 128)
    batch, label = bench.place_batch(model.instance, inputs, labels)
    jax.eval_shape(
        lambda p: model.instance.loss_fn(p, batch, label)[0], model.params
    )
    return trace.kernel_choices()


def test_the_rehearsal_graphs_nodes_say_how_the_triangular_system_crosses_hbm(
    monkeypatch,
):
    """`trace.kernel_choices("triangular_layout")` names `pairs` for the four
    delta-rule nodes of the rehearsal graph at lane-sized heads, beside
    `triangular_products` = `kernels` (PR 71: A, the inverse and dA two
    chunk-heads to a 128-lane row); a node on XLA's form notes no layout
    (`tests/test_qwen3_next.py` has the scalar-decay graph's three)."""
    la = dict(
        bench.load_json(os.path.join(BENCH, "configs", "rehearsal-kimi.json"))[
            "linear_attn_config"
        ],
        head_dim=128,
    )
    noted = rehearsal_choices(
        monkeypatch, ref, "kimi", ADAM, linear_attn_config=la, kda_chunk_size=64
    )
    nodes = [f"ff.kda.kda{layer}" for layer in la["kda_layers"]]
    assert len(nodes) == 4
    for node in nodes:
        assert noted[node]["triangular_products"] == "kernels"
        assert noted[node]["triangular_layout"] == "pairs"


def test_layers_are_the_published_period():
    assert ref.layer_names(TOY) == [
        (1, "K", True), (2, "K", False), (3, "K", False), (4, "M", False),
        (5, "K", False),
    ]
    assert ref.counts(TOY) == (4, 1, 1, 4)


def test_fit_step_matches_reference_adam_step():
    """The five-layer tower's loss before and after one `fit` step against
    the reference's own gradient and Adam step: 1e-5 is float32 rounding
    through two forward passes and the update. The selection bias is still
    zero afterwards, and the routing counters report the held rows of this
    model's four expert nodes."""
    from flexflow_tpu.observability import routing

    seq = 24
    model = compiled_model(seq, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    assert abs(system_loss(model, inputs, labels) - before) <= F32_LOSS
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(system_loss(model, inputs, labels) - after) <= F32_LOSS
    assert before - after > 100 * F32_LOSS  # the step did something
    stepped = bench.named_parameters(model.instance, model.params)
    for i in (2, 3, 4, 5):
        assert float(jnp.max(jnp.abs(stepped[f"moe{i}.weight1"]))) == 0.0
    counted = routing.published()
    assert counted["nodes"] == ["moe2", "moe3", "moe4", "moe5"]
    assert list(counted["decisions"]) == [BATCH * seq * 3] * 4  # one step
    assert 0.0 < counted["held_rows_pct"] < 100.0


def test_bf16_compute_is_inside_its_tolerance_and_outside_float32s():
    """The same graph at bf16 compute: inside 2e-2 (a mean over 48 positions
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
    """The batch template on two devices through the searched backend: the
    gated delta-rule op and latent attention are sharded over the batch (no
    node left serial), the loss is the one-device loss, and a step reduces
    it."""
    seq = 24
    inputs, labels = data(seq)
    one = compiled_model(seq, max_devices=1)
    two = compiled_model(
        seq, max_devices=2, search_budget=2,
        force_strategy_seed="dp2xtp1xsp1",
    )
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance
    from test_olmoe import weight_keys

    assert isinstance(two.instance, DistributedTrainingInstance)
    assert two.search_provenance["serial_compute_nodes"] == []
    keys1, keys2 = weight_keys(one.instance), weight_keys(two.instance)
    assert set(keys1) == set(keys2)
    one.params = {
        keys1[name]: jnp.asarray(np.asarray(two.params[keys2[name]]))
        for name in keys1
    }
    first = system_loss(two, inputs, labels)
    assert abs(first - system_loss(one, inputs, labels)) <= F32_LOSS
    two.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert system_loss(two, inputs, labels) < first - 0.01


def test_arithmetic_of_the_published_cut_by_hand():
    sizes = bench.load_json(CONFIG + ".json")
    # 32 heads x (two score matrices 2*32.5*256 + the system's solve
    # 2*32.5*256 + scores x values 2*32.5*128 + three state products 6*128*128)
    assert ref.kda_scan_flops_per_token(sizes) == 32 * (
        16640 + 16640 + 8320 + 98304
    )
    # q, k, v, o in bf16, the log-decays and beta in float32
    assert ref.kda_row_bytes(sizes) == 4 * 4096 * 2 + 4096 * 4 + 32 * 4
    costs = ref.kernel_costs(sizes, 1, 4096)
    assert costs["kda_scan"]["bytes"] == 4 * 4096 * 3 * 49280
    pairs = 4096 * 4097 / 2
    assert costs["flash"]["flops"] == (4 * 192 + 3 * 128) * 2 * pairs * 32
    assert costs["flash"]["bytes"] == 6 * 2 * 4096 * 32 * (192 + 128)


# -- the benchmark's CPU rehearsal of the cell ---------------------------------


def test_rehearsal_cell_runs_correct_on_the_cpu_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(BENCH, "rehearsal-kimi.json"), "--workload",
         "rehearsal_kimi_s128_1chip", "--seed", "2147483659", "--seconds",
         "1", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], (result["checks"], result["losses"])
    assert result["device"]["platform"] == "cpu"
    # no device trace on the CPU mesh: the three trace readers return nothing
    for name in ("kda_ms", "kda_scan_roofline", "mla_flash_roofline"):
        assert name not in result["metrics"]
    assert "kimi-linear reference routing" in done.stderr
