"""The state-space node's two elementwise stages, `kernels/ssm.conv_silu` and
`kernels/ssm.gated_group_norm`, against the plain forms they replaced (PR 41),
which live here now as the references: the short causal depthwise convolution
with SiLU behind it, and the gated grouped RMS norm with its runs taken as
slices and glued back by a `concatenate`. The new forms carry a written
backward (`jax.custom_vjp`) and the same arithmetic: float32 accumulation, a
rounding to the step's dtype where the references round. On the CPU, at the
toy widths and at the widths the scan's kernels take.

Since PR 59 `conv_silu` has a second route, the Pallas kernels
`conv_silu_fwd` / `conv_silu_bwd` (`kernels/ssm.conv_route`): run here in
interpret mode at the five cells' call shapes cut in rows, against the plain
route of the same function as the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import ssm
from flexflow_tpu.kernels.ssm import conv_silu, gated_group_norm
from flexflow_tpu.observability import trace

TAPS = 4
BATCH = 2
# the convolution's width (x, B and C) and the norm's (x alone) at
# `tests/test_nemotron_h.py`'s TOY (4 heads of 8, 2 groups, state 16) and
# KERNEL_TOY (4 heads of 64, 2 groups, state 128)
CONV_WIDTHS = {"toy": 96, "kernels": 768}
# positions: whole chunks of 8, no multiple of the chunk, fewer than the taps
LENGTHS = [40, 13, 3]
# `conv_silu`'s call in each cell that has one, cut in rows: (the columns of
# the projection's row x is, the convolution's first column in it, its width,
# whether it has a bias, the column pieces the caller takes the result in:
# the head-decay delta-rule node's q | k | v). Batch 2, so that a halo that
# crossed a sequence would show.
CELL_CALLS = {
    "qwen3next": (12288, 0, 8192, False, (2048, 2048, 4096)),
    "kimi": (12576, 0, 12288, False, None),
    "twotower": (10304, 4096, 6144, True, None),
    "super": (2320, 1024, 1280, True, None),
    "phi4flash": (10240, 0, 5120, True, None),
}
# (widths or cell, positions) of every convolution case; the kernels take
# 128 positions as one block of two steps and 96 as three blocks of one
CONV_CASES = (
    [(w, s) for w in CONV_WIDTHS for s in LENGTHS]
    + [(c, 128) for c in CELL_CALLS]
    + [("qwen3next", 96), ("twotower", 96)]
)
CONV_IDS = [f"{w}-{s}" for w, s in CONV_CASES]
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


def conv_pair(widths, seq, dtype, monkeypatch):
    """(the function under test, the reference, operands, cotangent) of a
    convolution case. At the toy widths: `conv_silu` as the CPU runs it
    against the reference above. At a cell's call: the kernels in interpret
    mode against the plain route, both under `jax.jit` (XLA's CPU compiler
    contracts `y + w * x` into one rounding where it compiles the whole
    expression, in the interpreted kernel as in the jitted plain form, and
    not where the operations run one by one)."""
    if widths in CONV_WIDTHS:
        operands, cot = conv_case(CONV_WIDTHS[widths], seq, dtype)
        return conv_silu, reference_conv_silu, operands, cot
    row, first, width, biased, pieces = CELL_CALLS[widths]
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert ssm.conv_route(first, width, seq, TAPS, pieces) == "kernels"
    rs = np.random.RandomState(2)
    operands = (
        rand(rs, BATCH, seq, row, dtype=dtype),
        rand(rs, TAPS, width, dtype=dtype, scale=0.5),
    ) + ((rand(rs, width, dtype=dtype, scale=0.5),) if biased else ())

    def conv(x, weight, bias=None):
        if pieces is None:
            return conv_silu(x, weight, bias, first)
        return jnp.concatenate(
            conv_silu(x, weight, bias, first, pieces=pieces), axis=-1
        )

    def plain(*operands):
        with context.no_flash():
            return conv(*operands)

    return (
        jax.jit(conv), jax.jit(plain), operands,
        rand(rs, BATCH, seq, width, dtype=dtype),
    )


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
@pytest.mark.parametrize("widths,seq", CONV_CASES, ids=CONV_IDS)
def test_conv_silu_forward_is_the_references_to_the_bit(
    widths, seq, dtype, monkeypatch
):
    conv, reference, operands, _ = conv_pair(widths, seq, dtype, monkeypatch)
    got = conv(*operands)
    want = reference(*operands)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


@pytest.mark.parametrize("dtype", DTYPES, ids=DTYPE_IDS)
@pytest.mark.parametrize("widths,seq", CONV_CASES, ids=CONV_IDS)
def test_conv_silu_gradients_are_the_references(widths, seq, dtype, monkeypatch):
    """The input's, the taps' and the bias's gradient under a random
    cotangent. In bf16 the reference's transposed casts round `dy silu'(a)`
    to bf16 between SiLU and the convolution; the written backward rounds it
    there too, once, on both routes. At a cell's call the input's gradient
    is the whole row's: zeros outside the convolution's columns."""
    conv, reference, operands, cot = conv_pair(widths, seq, dtype, monkeypatch)
    got = gradients(conv, operands, cot)
    want = gradients(reference, operands, cot)
    assert_gradients_agree(got, want, dtype)
    if widths in CELL_CALLS:
        _, first, width, *_ = CELL_CALLS[widths]
        outside = np.array(got[0], np.float32)
        outside[..., first:first + width] = 0.0
        assert not outside.any()


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


def test_conv_route_takes_the_kernels_only_where_they_apply(monkeypatch):
    """`conv_route` from what the trace can observe: the plain form on the
    CPU; with interpret mode opted in, the kernels at every cell's call, and
    the plain form again for a width or a first column off the 128-lane
    tile, a sequence no block divides, taps that reach back over more than a
    sublane tile, under `no_flash()` and under a declared `flash_mesh`."""
    calls = [(first, width, 4096, TAPS) for _, first, width, *_ in CELL_CALLS.values()]
    assert {ssm.conv_route(*call) for call in calls} == {"xla"}
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert {ssm.conv_route(*call) for call in calls} == {"kernels"}
    assert ssm.conv_route(0, 768, 64, TAPS) == "kernels"
    assert ssm.conv_route(0, 96, 64, TAPS) == "xla"
    assert ssm.conv_route(0, 8192 + 64, 64, TAPS) == "xla"
    assert ssm.conv_route(64, 768, 64, TAPS) == "xla"
    assert ssm.conv_route(0, 768, 40, TAPS) == "xla"
    assert ssm.conv_route(0, 768, 64, 10) == "xla"
    with context.no_flash():
        assert ssm.conv_route(0, 768, 64, TAPS) == "xla"
    with context.flash_mesh(None, ("data",), None):
        assert ssm.conv_route(0, 768, 64, TAPS) == "xla"
    assert ssm.conv_route(0, 768, 64, TAPS) == "kernels"
    # the widest column block that divides both the first column and the
    # width, the largest block of positions that divides the sequence
    assert ssm._conv_plan(4096, 6144, 4096) == (1024, 512)
    assert ssm._conv_plan(1024, 1280, 4096) == (1024, 256)
    assert ssm._conv_plan(0, 768, 96) == (32, 256)
    # and every column piece the result is taken in; none that does: plain
    assert ssm._conv_plan(0, 1280, 64, (1024, 128, 128)) == (64, 128)
    assert ssm.conv_route(0, 768, 64, TAPS, (512, 192, 64)) == "xla"


def test_the_convolutions_form_is_counted_by_node(monkeypatch, entered):
    """`observability/trace.kernel_choices("conv_forms")` names the form
    `conv_silu` took in each node that has one: `kernels` where `conv_route` says so, `xla` on
    the plain CPU and under `no_flash()`; a call under no node's scope is
    not counted."""
    (x, w, b), _ = conv_case(256, 64, jnp.bfloat16)
    monkeypatch.setattr(context, "_CHOICES", {})

    def lowered_as(scope, bias=b[:128]):
        entered(context.lowering_node(scope))
        jax.eval_shape(lambda x, w: conv_silu(x, w, bias, 128), x, w[:, :128])
        return trace.kernel_choices("conv_forms")[scope]

    assert lowered_as("ff.ssm.on_the_cpu") == "xla"
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert lowered_as("ff.ssm.m0") == "kernels"
    assert lowered_as("ff.kda.gdn0", None) == "kernels"
    with context.no_flash():
        assert lowered_as("ff.selective_scan.s0") == "xla"
    assert trace.kernel_choices("conv_forms") == {
        "ff.ssm.on_the_cpu": "xla", "ff.ssm.m0": "kernels",
        "ff.kda.gdn0": "kernels", "ff.selective_scan.s0": "xla",
    }
    entered(context.lowering_node(None))
    conv_silu(x, w, b)
    assert len(trace.kernel_choices("conv_forms")) == 4
