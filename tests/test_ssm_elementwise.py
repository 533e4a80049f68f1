"""The state-space node's two elementwise stages, `kernels/ssm.conv_silu` and
`kernels/ssm.gated_group_norm`, against the plain forms they replaced (PR 41),
which live here now as the references: the short causal depthwise convolution
with SiLU behind it, and the gated grouped RMS norm with its runs taken as
slices and glued back by a `concatenate`. The new forms carry a written
backward (`jax.custom_vjp`) and the same arithmetic: float32 accumulation, a
rounding to the step's dtype where the references round. On the CPU, at the
toy widths and at the widths the scan's kernels take."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from flexflow_tpu.kernels.ssm import conv_silu, gated_group_norm

TAPS = 4
BATCH = 2
# the convolution's width (x, B and C) and the norm's (x alone) at
# `tests/test_nemotron_h.py`'s TOY (4 heads of 8, 2 groups, state 16) and
# KERNEL_TOY (4 heads of 64, 2 groups, state 128)
CONV_WIDTHS = {"toy": 96, "kernels": 768}
# positions: whole chunks of 8, no multiple of the chunk, fewer than the taps
LENGTHS = [40, 13, 3]
# (inner, groups): TOY, KERNEL_TOY, one run (the Super cell's cut), eight
# (TwoTower's)
NORMS = [(32, 2), (256, 2), (256, 1), (256, 8)]


def reference_conv_silu(x, weight, bias):
    """`causal_depthwise_conv` and the SiLU after it as `state_space_forward`
    had them before PR 41: y_t = bias + sum_k weight[k] * x_{t - (taps - 1)
    + k} over a padded float32 copy of x, rounded to x's dtype; SiLU of that
    in float32, rounded again."""
    taps, s = weight.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32)
    for k in range(taps):
        y = y + w[k] * lax.dynamic_slice_in_dim(padded, k, s, axis=1)
    y = y.astype(x.dtype)
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype)


def reference_gated_group_norm(y, z, gain, groups, eps):
    """`gated_group_norm` before PR 41: rms_norm(y * silu(z)) with the mean
    of squares over each of `groups` equal runs of the last dim, in float32,
    every run normalised on its own slice and the runs concatenated."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    width = g.shape[-1] // groups
    runs = [g[..., k * width:(k + 1) * width] for k in range(groups)]
    g = jnp.concatenate(
        [
            run * lax.rsqrt(jnp.mean(jnp.square(run), axis=-1, keepdims=True) + eps)
            for run in runs
        ],
        axis=-1,
    )
    return (g * gain.astype(jnp.float32)).astype(y.dtype)


def rand(rs, *shape, dtype, scale=1.0):
    return jnp.asarray(rs.randn(*shape) * scale, jnp.float32).astype(dtype)


def conv_case(width, seq, dtype, seed=0):
    rs = np.random.RandomState(seed)
    return (
        rand(rs, BATCH, seq, width, dtype=dtype),
        rand(rs, TAPS, width, dtype=dtype, scale=0.5),
        rand(rs, width, dtype=dtype, scale=0.5),
    ), rand(rs, BATCH, seq, width, dtype=dtype)


def norm_case(inner, seq, dtype, seed=1):
    rs = np.random.RandomState(seed)
    return (
        rand(rs, BATCH, seq, inner, dtype=dtype),
        rand(rs, BATCH, seq, inner, dtype=dtype),
        1.0 + rand(rs, inner, dtype=dtype, scale=0.3),
    ), rand(rs, BATCH, seq, inner, dtype=dtype)


def gradients(fn, operands, cot):
    def loss(*operands):
        return jnp.sum(fn(*operands).astype(jnp.float32) * cot.astype(jnp.float32))

    return jax.grad(loss, argnums=tuple(range(len(operands))))(*operands)


def assert_gradients_agree(got, want, dtype):
    """float32: the two sides add the same float32 products in another
    order, 1e-6 of the largest entry. bf16: each side rounds its float32
    result to bf16 once, so they differ by a bf16 unit in the last place
    (2 ** -8 of the entry) where the float32 sums fall on two sides of a
    rounding boundary, and by nothing else."""
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        top = np.max(np.abs(w))
        assert top > 1e-3  # every operand is reached
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6 * top)
        else:
            np.testing.assert_allclose(g, w, rtol=2.0 ** -7, atol=2.0 ** -8 * top)


DTYPES = [jnp.float32, jnp.bfloat16]
DTYPE_IDS = ["float32", "bf16"]


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("seq", LENGTHS)
@pytest.mark.parametrize("widths", list(CONV_WIDTHS))
def test_conv_silu_forward_is_the_references_to_the_bit(widths, seq, dtype):
    operands, _ = conv_case(CONV_WIDTHS[widths], seq, dtype)
    got = conv_silu(*operands)
    want = reference_conv_silu(*operands)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("seq", LENGTHS)
@pytest.mark.parametrize("widths", list(CONV_WIDTHS))
def test_conv_silu_gradients_are_the_references(widths, seq, dtype):
    """The input's, the taps' and the bias's gradient under a random
    cotangent. In bf16 the reference's transposed casts round `dy silu'(a)`
    to bf16 between SiLU and the convolution; the written backward rounds it
    there too, once."""
    operands, cot = conv_case(CONV_WIDTHS[widths], seq, dtype)
    got = gradients(conv_silu, operands, cot)
    want = gradients(reference_conv_silu, operands, cot)
    assert_gradients_agree(got, want, dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("seq", [40, 13])
@pytest.mark.parametrize("inner,groups", NORMS)
def test_gated_group_norm_forward_is_within_a_unit_of_the_references(
    inner, groups, seq, dtype
):
    """The same products; a run's squares may be added in another order in
    float32: 1e-6 relative there, at most one bf16 unit in the last place
    after the rounding."""
    operands, _ = norm_case(inner, seq, dtype)
    got = gated_group_norm(*operands, groups, 1e-5)
    want = reference_gated_group_norm(*operands, groups, 1e-5)
    assert got.dtype == want.dtype
    rtol = 1e-6 if dtype == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=rtol, atol=1e-7,
    )


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("seq", [40, 13])
@pytest.mark.parametrize("inner,groups", NORMS)
def test_gated_group_norm_gradients_are_the_references(inner, groups, seq, dtype):
    """y's, z's and the gain's gradient under a random cotangent: the
    written backward against JAX's own transpose of the reference."""
    operands, cot = norm_case(inner, seq, dtype)
    got = gradients(
        lambda y, z, gain: gated_group_norm(y, z, gain, groups, 1e-5),
        operands, cot,
    )
    want = gradients(
        lambda y, z, gain: reference_gated_group_norm(y, z, gain, groups, 1e-5),
        operands, cot,
    )
    assert_gradients_agree(got, want, dtype)


def test_written_backwards_keep_no_float32_tensor_of_the_rows():
    """What the forward hands the backward, read from the jaxpr of
    `jax.vjp`: the operands, and for the norm the runs' reciprocal roots, a
    [rows, 1] column each; no float32 tensor of the convolution's or the
    norm's width in a bf16 step, and not the pre-activation."""
    (x, w, b), _ = conv_case(96, 40, jnp.bfloat16)
    (y, z, gain), _ = norm_case(256, 40, jnp.bfloat16)

    def kept(fn, *operands):
        _, vjp = jax.vjp(fn, *operands)
        return [leaf for leaf in jax.tree_util.tree_leaves(vjp) if hasattr(leaf, "shape")]

    conv_kept = kept(conv_silu, x, w, b)
    assert sorted(t.shape for t in conv_kept) == sorted([x.shape, w.shape, b.shape])
    assert all(t.dtype == jnp.bfloat16 for t in conv_kept)
    norm_kept = kept(lambda y, z, g: gated_group_norm(y, z, g, 8, 1e-5), y, z, gain)
    wide = [t for t in norm_kept if t.shape[-1:] == (256,) and t.ndim == 3]
    assert len(wide) == 2 and all(t.dtype == jnp.bfloat16 for t in wide)
    assert [t.shape for t in norm_kept if t.dtype == jnp.float32] == 8 * [(BATCH, 40, 1)]
