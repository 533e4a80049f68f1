"""The norm and the rotary of a plain attention node's fused row as ONE
Pallas pass each way (`kernels/ops.mha_between` where
`kernels/ops.between_form` says "pallas").

x is q or k `[b, s, heads * d]` in the model's dtype, as the projection's
matmul left it; y = rotary(round(norm(x) * gain)):

- the norm's span is one head's d columns (`span` "head": gain `[d]`, shared
  by the heads), the whole row ("row": gain `[heads * d]`) or nothing (None);
  float32 inside, its result rounded to x's dtype as `kernels/ops.rms_norm`
  returns it;
- the rotary is the rotate-half one over the whole head,
  `t * cos + rotate_half(t) * sin` with the sign of `rotate_half` in the
  sine's table, float32, rounded once on the way out. The tables are float32
  `[s, max(d, 128)]` a POSITION (`kernels/ops.rope_lane_tables`), read by
  row block with an index map that stands still across the head axis.

**One body, whose size does not follow the head count.** A program holds a
block of rows by `width` lanes and walks it `step` rows at a time; every
operation of a step is on the whole `[step, width]` slab: one reduction over
its lanes for the norm's statistics, one roll (two and a select where the
slab holds more than one head) for rotate-half, the tables repeated across
the slab's heads in VMEM. What the slab is decides the span, not a loop:

- span "head" and None: `width` is ONE head (d 128 or 256) or one lane
  tile of two heads of 64 (each half's statistics under a mask),
  the heads on the grid's third axis;
- span "row": `width` is the whole row.

The backward reads dy and x, recomputes the inverse root, turns dy back
(rotate-half's permutation is its own transpose), rounds where JAX's
transpose of the plain form rounds (the cotangent of the norm's result, to
x's dtype) and takes the norm's backward in `kda.head_norm_gate`'s form.
With r the inverse root, n = x r, dz = rotary^T(dy) and dn = dz gain:

    dx = r (dn - n mean_span(dn n))        dgain = sum dz n

the gain's sum as eight sublanes of partial sums a program, which XLA adds
up. Kept for the backward: x, the gain's row and the tables; the tables
alone where there is no norm (a rotary is linear). Each kernel call sits in
a module-level `jax.jit` (`_forward`, `_backward`), so q and k of one shape,
and every node of a step with those shapes, are traced once and lowered as
one function of the module (once more inside a `jax.checkpoint`, whose
tracing context is its own).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.kernels.context import interpret_default

_LANES = 128
# elements of a step's slab (rows a step = this over the slab's lanes, 16 at
# least). Kernels alone, q and k of a node, forward / backward ms (my chip
# run, PR 66): 1,024 rows of one lane tile a step 0.236 / 0.375 (Mellum2's
# pair, 6.8 ms in the plain form), 0.213 / 0.165 (Ouro's), 0.413 / 0.604
# (OLMoE's), 0.321 / 0.605 (LFM2's); 512 rows 0.258 / 0.379, 0.216 / 0.172,
# 0.418 / 0.632, 0.357 / 0.586; 256 rows 0.306 / 0.410 and 128 rows 0.514 /
# 0.630 for Mellum2's
_STEP = 1024 * _LANES
# rows a program: the largest that divides the (padded) rows and fits
# `_BLOCK` elements of x (same run: 0.5, 1 and 2 M elements within 2% at
# OLMoE's and LFM2's, 1 M 10% ahead of 0.5 M at Mellum2's); rows are padded
# with zeros to whole `_PAD`s
_BLOCKS = (4096, 2048, 1024, 512, 256, 128, 64)
_BLOCK = 1024 * 1024
_PAD = 64
# the shapes the body is tested on, and the only ones `pass_plan` admits:
# heads of these sizes (two heads of 64 a lane tile) and, for the "row" span,
# a row of a power of two of lanes, at most the widest that one slab of 16
# rows holds. Every slab is then a power of two of lanes wide, so a step (a
# power of two of rows, 16 at least) divides its block
HEAD_SIZES = (64, 128, 256)
_ROW_SPAN_LANES = 4096


class PassForm(NamedTuple):
    """What the pass does to a row of heads of `d` columns: the norm's
    `span` ("head", "row" or None) and whether a `rotary` turns each head."""

    d: int
    span: Optional[str]
    rotary: bool


class PassPlan(NamedTuple):
    pad: int  # rows of zeros appended
    block: int  # rows a program
    width: int  # lanes a program
    step: int  # rows a step of the program's walk


def pass_plan(s: int, f: int, form: PassForm) -> Optional[PassPlan]:
    """The blocks of the pass over `[b, s, f]`, or None where the body has
    no slab for the form: heads of another size than `HEAD_SIZES`, an odd
    number of heads of 64, a "row" span over a row that is no power of two
    of lanes or wider than `_ROW_SPAN_LANES`, nothing to do. An admitted
    plan's `step` is a multiple of 16 that divides its `block`: the walk
    covers every row and the backward's sums add whole registers."""
    d, span, rotary = form
    if d not in HEAD_SIZES or f % max(d, _LANES):
        return None
    if span is None and not rotary:
        return None
    if span == "row" and (f & (f - 1) or f > _ROW_SPAN_LANES):
        return None
    width = f if span == "row" else max(d, _LANES)
    pad = -s % _PAD
    block = next(
        n for n in _BLOCKS
        if (s + pad) % n == 0 and (n * width <= _BLOCK or n == _PAD)
    )
    step = min(block, max(16, _STEP // width))
    if block % step or step % 16:
        return None
    return PassPlan(pad, block, width, step)


def _span_mean(t, form: PassForm):
    """The mean of t `[rows, width]` over the norm's span, a value a row
    (`[rows, 1]`: the slab is the span) or a lane (two heads of 64 in one
    lane tile, each half its own)."""
    if form.span == "row" or form.d == t.shape[1]:
        return jnp.mean(t, axis=-1, keepdims=True)
    lower = lax.broadcasted_iota(jnp.int32, t.shape, 1) < form.d
    lo = jnp.sum(jnp.where(lower, t, 0.0), axis=-1, keepdims=True)
    hi = jnp.sum(jnp.where(lower, 0.0, t), axis=-1, keepdims=True)
    return jnp.where(lower, lo, hi) / form.d


def _rotate_half(t, d: int):
    """rotate_half's PERMUTATION of the slab's heads (its sign is in the
    sine's table): lane j of a head's lower half reads j + d / 2, of its
    upper half j - d / 2. Where the slab is one head the two are one roll."""
    width, half = t.shape[1], d // 2
    if width == d:
        return pltpu.roll(t, half, 1)
    lane = lax.broadcasted_iota(jnp.int32, t.shape, 1)
    lower = lax.rem(lane, d) < half
    return jnp.where(
        lower, pltpu.roll(t, width - half, 1), pltpu.roll(t, half, 1)
    )


def _refs(refs, form: PassForm, x: bool = True):
    """(x or None, gain or None, (cos, sin) or None, the other refs) of a
    kernel's refs: x (where the kernel reads it: `x`), then the gain where
    there is a norm, the tables where there is a rotary."""
    refs = list(refs)
    x_ref = refs.pop(0) if x else None
    gain_ref = refs.pop(0) if form.span else None
    tables = (refs.pop(0), refs.pop(0)) if form.rotary else None
    return x_ref, gain_ref, tables, refs


def _slab_tables(tables_ref, rows, width: int):
    """(cos, sin) `[step, width]` of the step's rows: the tables' lanes
    repeated across the slab's heads."""
    if tables_ref is None:
        return None
    times = width // tables_ref[0].shape[1]
    return tuple(
        pltpu.repeat(t[rows, :], times, 1) if times > 1 else t[rows, :]
        for t in tables_ref
    )


def _forward_kernel(*refs, form: PassForm, eps: float, step: int):
    """refs: x [rows, width], (gain [1, width] float32), (cos, sin
    [rows, lanes] float32); y [rows, width]."""
    x_ref, gain_ref, tables_ref, (y_ref,) = _refs(refs, form)
    f32 = jnp.float32
    width = x_ref.shape[1]

    def walk(i, _):
        rows = pl.ds(pl.multiple_of(i * step, step), step)
        t = x_ref[rows, :].astype(f32)
        if form.span:
            root = lax.rsqrt(_span_mean(t * t, form) + eps)
            t = (t * root * gain_ref[:]).astype(y_ref.dtype).astype(f32)
        if form.rotary:
            cos, sin = _slab_tables(tables_ref, rows, width)
            t = t * cos + _rotate_half(t, form.d) * sin
        y_ref[rows, :] = t.astype(y_ref.dtype)

    lax.fori_loop(0, x_ref.shape[0] // step, walk, None)


def _backward_kernel(*refs, form: PassForm, eps: float, step: int):
    """refs: (x, gain where there is a norm), (cos, sin) as the forward's,
    dy [rows, width]; dx [rows, width], (the gain's partial sums [8, width]
    float32). A rotary alone is linear: its backward reads no x."""
    x_ref, gain_ref, tables_ref, (dy_ref, dx_ref, *dgain_ref) = _refs(
        refs, form, x=bool(form.span)
    )
    f32 = jnp.float32
    width = dy_ref.shape[1]

    def walk(i, dgain):
        rows = pl.ds(pl.multiple_of(i * step, step), step)
        dz = dy_ref[rows, :].astype(f32)
        if form.rotary:
            cos, sin = _slab_tables(tables_ref, rows, width)
            dz = dz * cos + _rotate_half(dz * sin, form.d)
        if not form.span:
            dx_ref[rows, :] = dz.astype(dx_ref.dtype)
            return dgain
        # the cotangent of the norm's rounded result, in x's dtype as JAX's
        # transpose of the plain form hands it on
        dz = dz.astype(dx_ref.dtype).astype(f32)
        x = x_ref[rows, :].astype(f32)
        root = lax.rsqrt(_span_mean(x * x, form) + eps)
        n = x * root
        dn = dz * gain_ref[:]
        along = _span_mean(dn * n, form)
        dx_ref[rows, :] = (root * (dn - n * along)).astype(dx_ref.dtype)
        # the rows added eight apart: whole registers added, no sublane
        # leaves its place
        return dgain + jnp.sum((dz * n).reshape(step // 8, 8, width), axis=0)

    dgain = lax.fori_loop(
        0, dy_ref.shape[0] // step, walk,
        jnp.zeros((8, width), f32) if form.span else None,
    )
    if form.span:
        dgain_ref[0][:] = dgain


class _Blocks:
    """The BlockSpecs over the grid (batch row, block of rows, slab of
    lanes) of the pass over x [b, s, f]: the lane axis innermost, so that a
    table's block stands still while the heads of its rows go by."""

    def __init__(self, x, form: PassForm, plan: PassPlan):
        b, s, f = x.shape
        _, block, width, _ = plan
        self.grid = (b, s // block, f // width)
        self.rows = pl.BlockSpec(
            (None, block, width), lambda bi, ri, hi: (bi, ri, hi)
        )
        lanes = max(form.d, _LANES)
        self.table = pl.BlockSpec((block, lanes), lambda bi, ri, hi: (ri, 0))
        self.gain = pl.BlockSpec((1, width), lambda bi, ri, hi: (0, 0))
        # a program's partial sums [b, blocks, slabs, 8, width]
        self.gain_sums = pl.BlockSpec(
            (None, None, None, 8, width), lambda bi, ri, hi: (bi, ri, hi, 0, 0)
        )
        self.gain_sums_shape = jax.ShapeDtypeStruct(
            (*self.grid, 8, width), jnp.float32
        )
        # the gain where there is a norm, the tables where a rotary
        self.constants = (
            [self.gain] * bool(form.span) + [self.table] * (2 * form.rotary)
        )
        # the backward's three blocks of rows and the two tables', twice for
        # the pipeline's two buffers, and room for the body's slabs
        self.params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=max(
                2 * (3 * block * width * x.dtype.itemsize + 8 * block * lanes)
                + 16 * 1024 * 1024,
                32 * 1024 * 1024,
            ),
        )


def _padded(rows, tables, pad: int):
    """The `[b, s, f]` rows and the `[s, lanes]` tables with `pad` rows of
    zeros appended (a zero row adds nothing to a sum and is cut off again)."""
    if not pad:
        return rows, tables
    return (
        [jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in rows],
        [jnp.pad(t, ((0, pad), (0, 0))) for t in tables],
    )


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _forward(x, gain, cos, sin, form, eps, interpret):
    s = x.shape[1]
    plan = pass_plan(s, x.shape[2], form)
    (x,), tables = _padded([x], [cos, sin] * form.rotary, plan.pad)
    at = _Blocks(x, form, plan)
    y = pl.pallas_call(
        functools.partial(_forward_kernel, form=form, eps=eps, step=plan.step),
        grid=at.grid,
        in_specs=[at.rows] + at.constants,
        out_specs=at.rows,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=at.params,
        interpret=interpret,
        name="norm_rotary_fwd",
    )(x, *[gain] * bool(form.span), *tables)
    return y[:, :s] if plan.pad else y


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _backward(x, gain, cos, sin, dy, form, eps, interpret):
    """(dx, the gain row's cotangent or None) from dy; `x` and `gain` are
    None where there is no norm."""
    s = dy.shape[1]
    plan = pass_plan(s, dy.shape[2], form)
    normed = bool(form.span)
    (dy, *kept), tables = _padded(
        [dy] + [x] * normed, [cos, sin] * form.rotary, plan.pad
    )
    at = _Blocks(dy, form, plan)
    dx, *dgain = pl.pallas_call(
        functools.partial(_backward_kernel, form=form, eps=eps, step=plan.step),
        grid=at.grid,
        in_specs=[at.rows] * normed + at.constants + [at.rows],
        out_specs=[at.rows] + [at.gain_sums] * normed,
        out_shape=[jax.ShapeDtypeStruct(dy.shape, dy.dtype)]
        + [at.gain_sums_shape] * normed,
        compiler_params=at.params,
        interpret=interpret,
        name="norm_rotary_bwd",
    )(*kept, *[gain] * normed, *tables, dy)
    if plan.pad:
        dx = dx[:, :s]
    if not normed:
        return dx, None
    return dx, jnp.sum(dgain[0], axis=(0, 1, 2, 3))[None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def norm_rotary(x, gain, cos, sin, form: PassForm, eps: float):
    """y [b, s, f] of x [b, s, f]: the rms norm over `form.span` under the
    float32 gain row `gain` ([1, width of a slab]: a head's gain, twice for
    two heads of 64, or the row's; None without a norm), rounded to x's
    dtype, then the rotary of the float32 lane tables `cos`, `sin`
    [s, max(d, 128)] (None without a rotary); float32 inside, x's dtype out:
    `kernels/ops.rms_norm` then `rope_bshf`, as the kernels
    `norm_rotary_fwd` and, its WRITTEN backward, `norm_rotary_bwd`.
    `pass_plan` must admit the form."""
    return _forward(x, gain, cos, sin, form, eps, interpret_default())


def _norm_rotary_vjp_fwd(x, gain, cos, sin, form, eps):
    # a rotary alone is linear: its backward needs no x
    kept = (x if form.span else None, gain, cos, sin)
    return norm_rotary(x, gain, cos, sin, form, eps), kept


def _norm_rotary_vjp_bwd(form, eps, kept, dy):
    dx, dgain = _backward(*kept, dy, form, eps, interpret_default())
    # the tables are constants of the node: nothing reads their cotangent
    return dx, dgain, None, None


norm_rotary.defvjp(_norm_rotary_vjp_fwd, _norm_rotary_vjp_bwd)
