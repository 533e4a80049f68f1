"""The state-space mixer's kernels (`StateSpaceAttrs`): the short causal
depthwise convolution with its SiLU (`conv_silu`), the selective scan in its
chunked ("SSD") form, and the gated grouped RMS norm (`gated_group_norm`).

The recurrence, per head h (group g = h // (heads / groups)) and position t:

    H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t (x) B_t        H in R^{P x N}
    y_t = H_t C_t + D_h x_t

is evaluated over chunks of Q positions (Dao & Gu, arXiv:2405.21060, section
6). With a_t = dt_t A_h <= 0 and La the inclusive running sum of a within a
chunk, for positions i, j of one chunk:

    y_i  = sum_{j <= i} exp(La_i - La_j) (C_i . B_j) dt_j x_j    intra-chunk
         + exp(La_i) H_in C_i                                     inter-chunk
    H_out = exp(La_Q) H_in + sum_j exp(La_Q - La_j) dt_j x_j (x) B_j

Everything inside a chunk is a matrix product ([Q, N] x [N, Q] once a GROUP,
since the heads of a group share B and C; [Q, Q] x [Q, P] and [P, Q] x [Q, N]
a head), and only the [heads, P, N] states go from chunk to chunk, one
after the other over the s / Q chunks. Nothing is approximated: the
decays are exponentials of float32 differences of float32 running sums,
masked BEFORE the exponential (La_i - La_j is positive above the diagonal
and may overflow), and the states are float32. The matrix products take
operands in the input's dtype (bf16 in a bf16 step) and accumulate in
float32.

Two forms of these chunks exist, and `scan_route` picks one from what the
trace can observe (shapes and the facts of `kernels/context.py`); nothing but
the order of the floating-point sums differs between them:

- **The Pallas kernels** (`ssd_fwd_chunk`, `ssd_states_chunk`,
  `ssd_bwd_chunk`; "ssd" / "ssd_sharded"): on a TPU, at chunk and state
  sizes that are multiples of 128 lanes and heads of 64 or 128 whose group
  fills whole 128-lane tiles. One program is one (batch row, group, chunk),
  or where a group is wider than `_MAX_GROUP_COLUMNS` one of its column
  blocks of whole heads, each reading the group's one B and C
  (`_column_blocks`);
  C.B, the running-sum differences, the masked exponentials, the products
  and the state update of a chunk live in VMEM and only x, dt, B, C and y
  (and their gradients) cross HBM; the [r * P, N] states ride a VMEM scratch
  from chunk to chunk. The D x skip is added in the kernel. The backward is
  a `jax.custom_vjp`: it keeps the scan's INPUTS (x, dt, B, C, D and the
  [b, s, heads] float32 running sums, a 64th of x), recomputes the state
  every chunk starts from in a first pass (`ssd_states_chunk`, a transient
  of one layer) and walks the chunks last to first in a second, carrying the
  state's gradient in VMEM. The running sums, and their transpose in the
  backward, are XLA's `cumsum` outside the kernels, in float32.
- **The XLA form** (`_scan_core`; "xla"): everything else: the CPU, toy
  widths, a trace that admits no bare Pallas call. Plain batched matmuls and
  a sequential `lax.scan` over the chunks' states, with the D x skip added
  after it. The backward is JAX's own transpose of it under
  `jax.checkpoint`: kept are the scan's inputs, and the chunk-boundary
  states and everything inside the chunks are recomputed; the largest
  tensors are the [chunks, heads, Q, Q] decay masks in HBM, alive in one
  layer's backward at a time.

In neither form does a state per position ever exist.

The two elementwise stages around the scan, `conv_silu` and
`gated_group_norm`, each carry a WRITTEN backward (`jax.custom_vjp`),
because what JAX's own transpose keeps and writes is the cost (PR 41; a node
at [4096, 6144] / [4096, 4096] moved 3.1 GB through these passes where 0.8
would do). Kept for the backward are their operands in the step's dtype (x,
the taps and the bias; y, z and the gain) and the norm's reciprocal roots,
one [rows, 1] column a run: no float32 tensor of the row's width, not the
pre-activation, not the gate. The backwards recompute those in float32
where they need them. Float32 accumulation throughout; a rounding to the
step's dtype only where the forward rounds (after the convolution, after
SiLU, after the norm) and, in the convolution's backward, once between
SiLU's derivative and the mirrored convolution, where the transposed casts
round too.

`gated_group_norm` is plain JAX in one form for every route. `conv_silu`
has two behind its one `custom_vjp`, and `conv_route` picks one from what the
trace can observe, for every caller alike (this node, `kernels/kda.py`'s two
delta-rule nodes, `kernels/selective_scan.py`; told by node in
`trace.kernel_choices("conv_forms")`):

- **The Pallas kernels** (`conv_silu_fwd`, `conv_silu_bwd`; "kernels", PR
  59): on a TPU, where the trace admits a bare Pallas call, the
  convolution's first column in the projection's row and its width are whole
  128-lane tiles and the sequence divides into the kernels' blocks. One pass
  over the rows each way: a program reads a block of positions by columns IN
  PLACE out of the projection's row (every caller hands `conv_silu` the row
  and the first column, not a slice: a kernel's operand must be a buffer,
  and a slice made for it is a copy), reaches the three positions before it
  through a halo of one sublane tile, and in the backward recomputes the
  pre-activation, forms ds = round(dy silu'(a)) in VMEM and writes dx, with
  the taps' and the bias's sums in one float32 block that stays in VMEM
  along the rows. ds never reaches HBM.
- **The plain form** ("xla"): everything else, and what the kernels are
  tested against. The same arithmetic as XLA's fusions of `_conv_taps` over
  a slice of the row: two passes forward and back.

The node's parts go under scopes of their own inside the node's
(`ff.ssm.<name>/scan`, `/conv`, `/norm`; `observability/trace.NODE_PARTS`),
the backward's as the transpose of each, so a trace reader can tell the
scan, the convolution and the norm from the projections.

Two `optimization_barrier`s say what XLA's fusion heuristics get wrong here
(my chip runs, PR 41). The convolution's PLAIN backward recomputes the
pre-activation from x behind one, or XLA shares the forward's and has the
forward WRITE it for the backward (the kernels need none: a custom call
shares nothing). The norm's result passes one, or XLA
recomputes the normalised rows (a sigmoid and the runs' selects an element)
inside every tile of the two matmuls that read them, the output projection
and its weight gradient: 1.9 ms a step of `twotower30b_s4096_1chip` in the
matmuls' rows for 0.5 saved in the norm's.
"""

from __future__ import annotations

import functools
import itertools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.kernels import context
from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs


def _shifted(x, taps: int, mirrored: bool = False):
    """The `taps` shifted copies of x [b, s, f] that a causal convolution
    reads, in float32: x_{t - (taps - 1) + k} for k = 0 .. taps - 1, zeros
    before the first position; `mirrored`, x_{t + (taps - 1) - k}, zeros
    after the last. x is padded in its own dtype and converted slice by
    slice INSIDE the fusion that reads them, so no padded float32 copy of x
    ever exists."""
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (0, taps - 1) if mirrored else (taps - 1, 0), (0, 0)))
    return [
        lax.dynamic_slice_in_dim(
            padded, taps - 1 - k if mirrored else k, s, axis=1
        ).astype(jnp.float32)
        for k in range(taps)
    ]


def _conv_taps(x, weight, bias, mirrored: bool = False):
    """bias + sum_k weight[k] * x_{t - (taps - 1) + k} in float32: the
    causal depthwise conv1d of the model codes (`groups = channels`,
    `padding = taps - 1` cut back to s); `mirrored`, its transpose in x.
    Four shifted multiply-adds either way. `bias` None is a convolution
    without one: the sum starts at the first tap's product."""
    w = weight.astype(jnp.float32)
    y = None if bias is None else bias.astype(jnp.float32)
    for k, x_k in enumerate(_shifted(x, len(weight), mirrored)):
        y = w[k] * x_k if y is None else y + w[k] * x_k
    return y


def _silu_slope(a):
    """(silu(a), silu'(a)) of a float32 a: s = sigmoid(a), a s and
    s (1 + a (1 - s))."""
    sig = jax.nn.sigmoid(a)
    return a * sig, sig * (1.0 + a * (1.0 - sig))


def conv_silu(x, weight, bias, first: int = 0, pieces=None):
    """silu(causal_depthwise_conv(.)) of the `weight.shape[1]` columns of x
    [b, s, .] from `first` on (every caller's x is a projection's row, and
    the convolution reads a column slice of it): weight [taps, f], bias [f]
    or None (the gated delta-rule mixer's convolutions have none, and none
    is made for them) -> [b, s, f] in x's dtype. The convolution accumulates
    in float32 and is rounded to x's dtype, SiLU is taken of that in float32
    and rounded again: one pass over x, one output.

    Two routes behind the one `custom_vjp` (`_conv_silu`), `conv_route`'s
    choice made here once for the forward and the backward and noted as the
    node's `conv_forms` (`kernels/context.note`; every node with a
    `conv_silu`: the state-space, selective-scan and gated delta-rule
    mixers): `kernels`, the Pallas kernels `conv_silu_fwd` / `conv_silu_bwd`
    (the section below), which read x's columns in place, or `xla`, the
    plain form here with its written backward, which slices them, so that a
    run that fell back says so itself.

    The backward is written because JAX's own keeps the wrong things: the
    transpose of `w[k] * slice(pad(float32(x)))` keeps the padded float32
    copy of x, and hands the input's gradient back as one float32
    [b, s, f] tensor a tap, added up in a further pass (1,240 MB a node at
    [4096, 6144] for a least of 150; described-chip compile, PR 41). Kept
    here: x, weight, bias. The backward recomputes the pre-activation
    beside SiLU's derivative, rounds `dy silu'(a)` to x's dtype ONCE (where
    the transposed casts rounded it too), and takes the input's gradient as
    the mirrored convolution of that and the weight's and the bias's as
    reductions over the same operands. x's other columns get a zero
    cotangent.

    `pieces`, the widths of the column pieces the caller cuts the result
    into (the delta-rule node's q | k | v), makes the result the tuple of
    those pieces, each an output of the `custom_vjp` of its own: their
    cotangents then come back apart and the backward's kernel reads each
    where its producer left it, where XLA otherwise writes their `[rows, f]`
    sum-of-pads for the kernel's sake (a kernel's operand must be a
    buffer). The kernels' column block divides every piece
    (`_conv_plan`)."""
    pieces = None if pieces is None else tuple(pieces)
    width = weight.shape[1]
    route = conv_route(first, width, x.shape[1], len(weight), pieces)
    context.note("conv_forms", route)
    if route == "xla":
        # the plain form takes its own columns and keeps no more of the row
        x, first = x[..., first:first + width], 0
    return _conv_silu(x, weight, bias, first, route, pieces)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_silu(x, weight, bias, first, route, pieces):
    """`conv_silu` on `route`, which the forward and the backward share: a
    backward traced after a `no_flash()` has closed still takes the route
    its forward took. On "kernels" x is the projection's row and `first` the
    convolution's first column in it; on "xla" x is the convolution's own
    columns."""
    return _conv_silu_fwd(x, weight, bias, first, route, pieces)[0]


def _conv_silu_fwd(x, weight, bias, first, route, pieces):
    kept = (x, weight, bias)
    if route == "kernels":
        ys = _conv_forward(
            x, weight, bias, first, pieces or weight.shape[1:],
            context.interpret_default(),
        )
        return (ys[0] if pieces is None else ys), kept
    a = _conv_taps(x, weight, bias).astype(x.dtype)
    y = jax.nn.silu(a.astype(jnp.float32)).astype(x.dtype)
    if pieces is None:
        return y, kept
    ends = list(itertools.accumulate(pieces))
    return tuple(y[..., e - n:e] for n, e in zip(pieces, ends)), kept


def _conv_silu_bwd(first, route, pieces, kept, dy):
    x, weight, bias = kept
    if route == "kernels":
        dys = (dy,) if pieces is None else tuple(dy)
        return _conv_backward(
            x, weight, bias, dys, first, context.interpret_default()
        )
    if pieces is not None:
        dy = jnp.concatenate(dy, axis=-1)
    f32 = jnp.float32
    # the forward computes the same pre-activation: without the barrier XLA
    # shares it, which is the forward WRITING it for the backward to read
    x = lax.optimization_barrier(x)
    a = _conv_taps(x, weight, bias).astype(x.dtype).astype(f32)
    ds = (dy.astype(f32) * _silu_slope(a)[1]).astype(x.dtype)
    dx = _conv_taps(ds, weight, jnp.zeros((), f32), mirrored=True)
    dsf = ds.astype(f32)
    dw = jnp.stack(
        [jnp.sum(dsf * x_k, axis=(0, 1)) for x_k in _shifted(x, len(weight))]
    )
    db = None if bias is None else jnp.sum(dsf, axis=(0, 1))
    dx, dw = dx.astype(x.dtype), dw.astype(weight.dtype)
    return dx, dw, None if bias is None else db.astype(bias.dtype)


_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


# ---------------------------------------------------------------------------
# the convolution, the Pallas form: one pass over the rows each way
# ---------------------------------------------------------------------------
#
# A program is one block of `rows` positions by `cols` columns of one batch
# row, read IN PLACE out of the projection's row (the BlockSpec starts at
# column block `first / cols`). It walks the block `_CONV_ROWS` positions a
# step (a block of fewer in one) and a 128-lane tile at a time, float32 in
# vector registers. A tap's
# shifted copy of the step's rows is a sublane roll of [8 rows before, the
# step's rows]: the 8 rows before are the tile the loop read a step ago,
# and for a block's first step the HALO: a second BlockSpec on the same
# array for the one sublane tile that ends where the block starts, zeros in
# the sequence's first block (the convolution is causal a sequence: nothing
# crosses a batch row).
#
# The backward, `_conv_silu_bwd` above in one pass: with a the rounded
# pre-activation and ds = round(dy silu'(a)),
#
#     dx_t = sum_k w[k] ds_{t + (taps - 1) - k}
#     dw[k] = sum_t ds_t x_{t - (taps - 1) + k}        db = sum_t ds_t
#
# dx reads ds of the rows AFTER its own, so the steps go last to first and
# carry the first 8 rows of the step after; a block's last step starts from
# the ds of the 8 positions after the block, recomputed from halos of x and
# dy there (zeros past the sequence's end). ds never reaches HBM. dy may
# come as the column pieces the caller cut y into (`conv_silu`'s `pieces`):
# every piece is an operand, a program reads the one its column block lies
# in, and the others' BlockSpecs stand still meanwhile (an index that does
# not change is not fetched again). y's pieces are outputs the same way,
# and a window that stands still is written back once, by the program that
# filled it: true of ONE core walking the column blocks in order, so with
# pieces the column axis is "arbitrary" and no chip may split it over cores
# (`_ConvBlocks`). The taps'
# and the bias's sums go to ONE float32 [taps (+ 1), 8, cols] block that
# stays in VMEM along the row axis (the grid's last, sequential), eight
# sublanes of partial sums a column that XLA adds up after.

_CONV_ROWS = 64  # positions a step at most (whole bf16 sublane tiles)
_CONV_BLOCKS = (1024, 512, 256, 128, 64, 32)  # positions a program
_CONV_COLUMNS = (512, 256, 128)  # columns a program
_HALO = 8  # a float32 sublane tile: the rows a roll can reach back over


def _conv_plan(first: int, width: int, seq: int, pieces=None):
    """(positions, columns) a program, or None where the kernels do not
    apply: the largest block of `_CONV_BLOCKS` that divides the sequence,
    the widest of `_CONV_COLUMNS` that divides the convolution's first
    column in its row, its width and every one of the column `pieces` the
    caller takes the result in (a piece is whole column blocks)."""
    rows = next((n for n in _CONV_BLOCKS if seq % n == 0), None)
    whole = (first, width) + tuple(pieces or ())
    cols = next(
        (n for n in _CONV_COLUMNS if all(w % n == 0 for w in whole)), None
    )
    return None if rows is None or cols is None else (rows, cols)


def conv_route(
    first: int, width: int, seq: int, taps: int, pieces=None
) -> str:
    """Which form `conv_silu` takes, from what the trace can observe:

    - "kernels": `conv_silu_fwd` / `conv_silu_bwd`, where the backend is a
      TPU (or the CPU with interpret mode opted in) and the trace admits a
      bare Pallas call (`kernels/context.admits_bare_pallas_call`), the
      convolution's first column, its width and
      the `pieces` its result is taken in are whole 128-lane tiles, the
      sequence divides into the plan's blocks (`_conv_plan`) and the taps
      reach back over no more than one float32 sublane tile;
    - "xla": everything else, the plain form."""
    if _conv_plan(first, width, seq, pieces) is None or taps - 1 > _HALO:
        return "xla"
    admitted = context.admits_bare_pallas_call(context.interpret_default())
    return "kernels" if admitted else "xla"


def _eight_apart(t):
    """[rows, w] float32 -> [8, w]: the rows added eight apart (whole
    registers added, no sublane leaves its place)."""
    return functools.reduce(
        jnp.add, [t[i:i + 8, :] for i in range(0, t.shape[0], 8)]
    )


def _reached(window, taps: int, mirrored: bool = False):
    """The `taps` shifted copies of a step's rows, as `_shifted` orders
    them, from `window` [8 + rows, 128] float32 (the 8 rows before, then the
    step's): x_{t - (taps - 1) + k}; `mirrored`, from [rows + 8, 128] (the
    step's, then the 8 after): x_{t + (taps - 1) - k}."""
    n = window.shape[0]
    kept = slice(0, n - _HALO) if mirrored else slice(_HALO, n)

    def shifted(back):
        if not back:
            return window
        return pltpu.roll(window, n - back if mirrored else back, 0)

    return [shifted(taps - 1 - k)[kept] for k in range(taps)]


def _taps_sum(w, bias, shifted):
    """bias + sum_k w[k] shifted[k], in `_conv_taps`'s order."""
    y = bias
    for k, x_k in enumerate(shifted):
        y = w[k:k + 1] * x_k if y is None else y + w[k:k + 1] * x_k
    return y


def _last_rows(t):
    return t.astype(jnp.float32)[t.shape[0] - _HALO:]


def _lane_tiles(cols: int):
    return [slice(c, c + _LANES) for c in range(0, cols, _LANES)]


def _halo_before(before_ref, lanes):
    """The 8 rows before a block, float32: zeros in a sequence's first."""
    rows = _last_rows(before_ref[:, lanes])
    return jnp.where(pl.program_id(2) == 0, jnp.zeros_like(rows), rows)


def _step_rows(x_ref) -> int:
    return min(_CONV_ROWS, x_ref.shape[0])


def _window_before(x_ref, halo, i, lanes):
    """(where step i's rows start, [8 + rows a step, 128] float32: those
    rows of the block behind the 8 rows before them, `halo` for the block's
    first step)."""
    tile = 32 // x_ref.dtype.itemsize  # rows of x's sublane tile
    step = _step_rows(x_ref)
    at = pl.multiple_of(i * step, step)
    before = pl.multiple_of(jnp.maximum(at - tile, 0), tile)
    prev = _last_rows(x_ref[pl.ds(before, tile), lanes])
    return at, jnp.concatenate(
        [
            jnp.where(i == 0, halo, prev),
            x_ref[pl.ds(at, step), lanes].astype(jnp.float32),
        ],
        axis=0,
    )


def _conv_silu_fwd_kernel(
    x_ref, before_ref, w_ref, *refs, taps: int, starts: tuple
):
    """refs: x [rows, cols], the sublane tile of x before it, the taps
    [taps, cols] float32, (the bias [1, cols] float32); y's pieces
    [rows, cols] (`starts`: the column block each begins at), of which a
    program writes the one its column block lies in."""
    b_ref, y_refs = refs[:-len(starts)], refs[-len(starts):]
    f32 = jnp.float32
    rows = _step_rows(x_ref)
    tiles = _lane_tiles(x_ref.shape[1])
    halos = [_halo_before(before_ref, lanes) for lanes in tiles]

    def walk(y_ref):
        def step(i, _):
            for lanes, halo in zip(tiles, halos):
                at, window = _window_before(x_ref, halo, i, lanes)
                a = _taps_sum(
                    w_ref[:, lanes], b_ref[0][:, lanes] if b_ref else None,
                    _reached(window, taps),
                ).astype(x_ref.dtype)
                y_ref[pl.ds(at, rows), lanes] = jax.nn.silu(
                    a.astype(f32)
                ).astype(y_ref.dtype)

        lax.fori_loop(0, x_ref.shape[0] // rows, step, None)

    if len(y_refs) == 1:
        return walk(y_refs[0])
    # one walk a piece, so that no step of the loop holds a branch; the
    # pieces a program does not write keep what their blocks hold, and
    # those blocks stand still (`piece` in `_ConvBlocks`)
    column_block = pl.program_id(1)
    ends = starts[1:] + (pl.num_programs(1),)
    for y_ref, start, end in zip(y_refs, starts, ends):
        pl.when((column_block >= start) & (column_block < end))(
            functools.partial(walk, y_ref)
        )


def _conv_silu_bwd_kernel(
    x_ref, before_ref, after_ref, *refs, taps: int, starts: tuple
):
    """refs: x [rows, cols] with the sublane tiles of x before and after it,
    dy's pieces [rows, cols] (`starts`: the column block each begins at),
    the tile after each, the taps, (the bias); dx [rows, cols], the taps'
    (and the bias's) partial sums [taps (+ 1), 8, cols] float32, one block
    for all of a column block's programs."""
    n = len(starts)
    dy_refs, dy_after_refs = refs[:n], refs[n:2 * n]
    w_ref, *b_ref, dx_ref, dw_ref = refs[2 * n:]

    column_block = pl.program_id(1)

    def of_piece(piece_refs, rows, lanes):
        # the rows of the piece this program's column block lies in
        out = piece_refs[0][rows, lanes]
        for ref, start in zip(piece_refs[1:], starts[1:]):
            out = jnp.where(column_block >= start, ref[rows, lanes], out)
        return out

    f32 = jnp.float32
    dtype = x_ref.dtype
    rows, step_rows = x_ref.shape[0], _step_rows(x_ref)
    tiles = _lane_tiles(x_ref.shape[1])
    last = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        dw_ref[:] = jnp.zeros(dw_ref.shape, f32)

    def slopes(window, dy, lanes):
        """(ds = round(dy silu'(a)) in float32, the taps' copies of x)."""
        shifted = _reached(window, taps)
        a = _taps_sum(
            w_ref[:, lanes], b_ref[0][:, lanes] if b_ref else None, shifted
        ).astype(dtype).astype(f32)
        return (dy * _silu_slope(a)[1]).astype(dtype).astype(f32), shifted

    def after(tile_after):  # the 8 rows after the block: zeros past the end
        rows_after = tile_after.astype(f32)[:_HALO]
        return jnp.where(last, jnp.zeros_like(rows_after), rows_after)

    halos = [_halo_before(before_ref, lanes) for lanes in tiles]
    tile = before_ref.shape[0]
    ds_after = tuple(
        slopes(
            jnp.concatenate(
                [
                    _last_rows(x_ref[rows - tile:rows, lanes]),
                    after(after_ref[:, lanes]),
                ],
                axis=0,
            ),
            after(of_piece(dy_after_refs, slice(None), lanes)), lanes,
        )[0]
        for lanes in tiles
    )

    def step(j, ds_next):
        i = rows // step_rows - 1 - j
        heads = []
        for lanes, halo, ds_n in zip(tiles, halos, ds_next):
            at, window = _window_before(x_ref, halo, i, lanes)
            here = pl.ds(at, step_rows)
            ds, shifted = slopes(
                window, of_piece(dy_refs, here, lanes).astype(f32), lanes
            )
            dx_ref[here, lanes] = _taps_sum(
                w_ref[:, lanes], None,
                _reached(jnp.concatenate([ds, ds_n], axis=0), taps, True),
            ).astype(dx_ref.dtype)
            for k, x_k in enumerate(shifted):
                dw_ref[k, :, lanes] += _eight_apart(ds * x_k)
            if b_ref:
                dw_ref[taps, :, lanes] += _eight_apart(ds)
            heads.append(ds[:_HALO])
        return tuple(heads)

    lax.fori_loop(0, rows // step_rows, step, ds_after)


class _ConvBlocks:
    """The BlockSpecs over the grid (batch row, column block, block of
    positions) of a convolution of x [b, s, .] from column `first` on, in a
    dtype of `itemsize` bytes, its result and its cotangent in column pieces
    of the widths `pieces` (one where the caller cut none)."""

    def __init__(self, b, s, first, pieces, taps, itemsize):
        width = sum(pieces)
        rows, cols = _conv_plan(first, width, s, pieces)
        tile = 32 // itemsize  # rows of the dtype's sublane tile
        self.grid = (b, width // cols, s // rows)
        tiles, last = rows // tile, s // tile - 1
        # the column block each piece begins at, the blocks it holds
        self.blocks = [n // cols for n in pieces]
        self.starts = tuple(itertools.accumulate([0] + self.blocks[:-1]))

        def block(first=0):
            return pl.BlockSpec(
                (None, rows, cols),
                lambda bi, ci, ri: (bi, ri, first // cols + ci),
            )

        def before(first=0):
            return pl.BlockSpec(
                (None, tile, cols),
                lambda bi, ci, ri: (
                    bi, jnp.maximum(ri * tiles - 1, 0), first // cols + ci
                ),
            )

        def after(first=0):
            return pl.BlockSpec(
                (None, tile, cols),
                lambda bi, ci, ri: (
                    bi, jnp.minimum((ri + 1) * tiles, last), first // cols + ci
                ),
            )

        def piece(start, blocks, spec):
            """`spec` (`block` or `after`) for a piece of y or dy that holds
            the convolution's column blocks `start .. start + blocks`: its
            own blocks while the grid is there; before that its first and
            after that its last, standing still. For a piece of y that
            relies on an output's window being written back when its index
            moves or the grid ends, and so on ONE core walking the column
            blocks in order: with more than one piece the column axis is
            "arbitrary" (`params`), or a second core would end on windows of
            pieces it never wrote and write them back."""
            whole = spec()
            if len(pieces) == 1:
                return whole
            shape, index = whole.block_shape, whole.index_map
            rows_last = self.grid[2] - 1

            def held(bi, ci, ri):
                ri = jnp.where(
                    ci < start, 0, jnp.where(ci >= start + blocks, rows_last, ri)
                )
                return index(bi, jnp.clip(ci - start, 0, blocks - 1), ri)

            return pl.BlockSpec(shape, held)

        self.block, self.before, self.after = block, before, after
        self.pieces = lambda spec: [
            piece(i, n, spec) for i, n in zip(self.starts, self.blocks)
        ]
        self.taps = pl.BlockSpec((taps, cols), lambda bi, ci, ri: (0, ci))
        self.bias = pl.BlockSpec((1, cols), lambda bi, ci, ri: (0, ci))

        def sums(n):
            return pl.BlockSpec(
                (None, n, 8, cols), lambda bi, ci, ri: (bi, 0, 0, ci)
            )

        self.sums = sums
        self.params = pltpu.CompilerParams(
            dimension_semantics=(
                "parallel",
                "parallel" if len(pieces) == 1 else "arbitrary",
                "arbitrary",
            )
        )


def _conv_rows(weight, bias):
    """The taps and the bias as the kernels take them: float32 rows."""
    f32 = jnp.float32
    return [weight.astype(f32)] + (
        [] if bias is None else [bias.astype(f32)[None, :]]
    )


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _conv_forward(x, weight, bias, first, pieces, interpret):
    """`conv_silu`'s y as a kernel, in the column pieces of widths `pieces`,
    each an output of the kernel."""
    b, s, _ = x.shape
    taps = len(weight)
    at = _ConvBlocks(b, s, first, pieces, taps, x.dtype.itemsize)
    return tuple(pl.pallas_call(
        functools.partial(_conv_silu_fwd_kernel, taps=taps, starts=at.starts),
        grid=at.grid,
        in_specs=[at.block(first), at.before(first), at.taps]
        + [at.bias] * (bias is not None),
        out_specs=at.pieces(at.block),
        out_shape=[jax.ShapeDtypeStruct((b, s, n), x.dtype) for n in pieces],
        compiler_params=at.params,
        interpret=interpret,
        name="conv_silu_fwd",
    )(x, x, *_conv_rows(weight, bias)))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _conv_backward(x, weight, bias, dys, first, interpret):
    """The cotangents of (x, weight, bias) from y's, which comes as the
    tuple of its column pieces (one where the caller cut none)."""
    b, s, _ = x.shape
    taps, width = weight.shape
    biased = bias is not None
    pieces = tuple(dy.shape[-1] for dy in dys)
    at = _ConvBlocks(b, s, first, pieces, taps, x.dtype.itemsize)
    dx, sums = pl.pallas_call(
        functools.partial(_conv_silu_bwd_kernel, taps=taps, starts=at.starts),
        grid=at.grid,
        in_specs=[at.block(first), at.before(first), at.after(first)]
        + at.pieces(at.block) + at.pieces(at.after)
        + [at.taps] + [at.bias] * biased,
        out_specs=[at.block(), at.sums(taps + biased)],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, width), x.dtype),
            jax.ShapeDtypeStruct((b, taps + biased, 8, width), jnp.float32),
        ],
        compiler_params=at.params,
        interpret=interpret,
        name="conv_silu_bwd",
    )(x, x, x, *dys, *dys, *_conv_rows(weight, bias))
    sums = jnp.sum(sums, axis=(0, 2))
    # x's other columns get a zero cotangent
    after = x.shape[-1] - first - width
    if first or after:
        dx = jnp.pad(dx, ((0, 0), (0, 0), (first, after)))
    return (
        dx, sums[:taps].astype(weight.dtype),
        sums[taps].astype(bias.dtype) if biased else None,
    )


def _run_of_column(ndim: int, inner: int, groups: int):
    """Which of `groups` equal runs each of `inner` columns lies in, shaped
    to broadcast against a [.., inner] tensor of `ndim` dims."""
    shape = (1,) * (ndim - 1) + (inner,)
    return lax.broadcasted_iota(jnp.int32, shape, ndim - 1) // (inner // groups)


def _run_sums(t, groups: int):
    """[.., inner] float32 -> `groups` [.., 1] columns: the sum over each of
    `groups` equal runs of the last dim, every run a masked sum over the
    whole row. XLA fuses `t`'s producers into the one reduction that gives
    all of them, so `t` itself never exists. A run is never a dimension of
    its own (the reshape re-tiles the row on a TPU: two float32 copies a
    pass, PR 33) and never a slice either: slices of a float32 product are
    what makes XLA write the product to HBM first (PR 41)."""
    if groups == 1:
        return [jnp.sum(t, axis=-1, keepdims=True)]
    run_of = _run_of_column(t.ndim, t.shape[-1], groups)
    return [
        jnp.sum(jnp.where(run_of == k, t, 0.0), axis=-1, keepdims=True)
        for k in range(groups)
    ]


def _over_runs(columns, inner: int):
    """`groups` [.., 1] columns -> [.., inner] (or a column that broadcasts
    to it): each run's value on its own columns, by selects, inside whatever
    fusion reads it. The runs are never glued back together: a
    `concatenate` of [rows, width] pieces is `groups` in-place updates of a
    float32 [rows, inner] tensor in HBM (PR 41)."""
    run_of = _run_of_column(columns[0].ndim, inner, len(columns))
    out = columns[-1]
    for k in reversed(range(len(columns) - 1)):
        out = jnp.where(run_of == k, columns[k], out)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_group_norm(y, z, gain, groups: int, eps: float):
    """rms_norm(y * silu(z)) with the mean of squares over each of `groups`
    equal runs of the last dim, in float32; the result in y's dtype. Two
    passes over y and z: the runs' sums of squares (`_run_sums`), then the
    normalised rows, written once in y's dtype.

    The backward is written because JAX's own keeps float32 [rows, inner]
    products and re-assembles the runs' gradients with a `concatenate`
    (490 + 520 MB a node at [4096, 4096] in 8 runs for leasts of 100 and
    168; described-chip compile, PR 41). Kept here: y, z, gain and the
    runs' reciprocal roots, [rows, 1] each; the gate is recomputed."""
    return _gated_group_norm_fwd(y, z, gain, groups, eps)[0]


def _gated_group_norm_fwd(y, z, gain, groups, eps):
    f32 = jnp.float32
    inner = y.shape[-1]
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    roots = [
        lax.rsqrt(total / (inner // groups) + eps)
        for total in _run_sums(jnp.square(g), groups)
    ]
    out = (g * _over_runs(roots, inner) * gain.astype(f32)).astype(y.dtype)
    # written once: without the barrier XLA recomputes the normalised rows
    # (the gate's sigmoid, the runs' selects) inside every tile of the two
    # matmuls that read them, the output projection and its weight gradient
    return lax.optimization_barrier(out), (y, z, gain, roots)


def _gated_group_norm_bwd(groups, eps, kept, d_out):
    """With g = y silu(z), r a run's reciprocal root, n = g r and
    d n = d_out gain: d g = r d n - g r^3 mean_run(d n g). One reduction
    for the runs' sums of d n g, then d y and d z from y, z and d_out."""
    y, z, gain, roots = kept
    f32 = jnp.float32
    inner = y.shape[-1]
    yf, zf, df = y.astype(f32), z.astype(f32), d_out.astype(f32)
    gate, slope = _silu_slope(zf)
    g = yf * gate
    dn = df * gain.astype(f32)
    pulls = [
        total * (r * r * r) / (inner // groups)
        for total, r in zip(_run_sums(dn * g, groups), roots)
    ]
    spread = _over_runs(roots, inner)
    dg = spread * dn - _over_runs(pulls, inner) * g
    d_gain = jnp.sum(df * g * spread, axis=tuple(range(y.ndim - 1)))
    dy = dg * gate
    dz = dg * yf * slope
    return dy.astype(y.dtype), dz.astype(z.dtype), d_gain.astype(gain.dtype)


gated_group_norm.defvjp(_gated_group_norm_fwd, _gated_group_norm_bwd)


def _scan_core(x, dt, a_log, b_mat, c_mat, chunk: int):
    """x [b, s, h, p]; dt [b, s, h] float32 (after softplus); a_log [h];
    b_mat, c_mat [b, s, g, n]. Returns y [b, s, h, p] float32 WITHOUT the
    D x skip. s must be a multiple of `chunk`.

    Every product is written as a plain batched matmul: the chunk, group
    and head dims lead and a chunk's positions are the rows, so the
    operands are transposed ONCE here, under this node's scope, and XLA has
    no layout left to repair with copies of its own (which carry no scope:
    17 ms a step of them in the first trace of PR 32)."""
    b, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    q, c = chunk, s // chunk
    r = h // g
    dtype = x.dtype
    f32 = jnp.float32

    def by_head(t):  # [b, s, h, ...] -> [b, c, g, r, q, ...]
        t = t.reshape(b, c, q, g, r, *t.shape[3:])
        return jnp.moveaxis(t, 2, 4)

    def by_group(t):  # [b, s, g, n] -> [b, c, g, q, n]
        return jnp.moveaxis(t.reshape(b, c, q, g, n), 2, 3)

    a = by_head(dt * (-jnp.exp(a_log.astype(f32))))  # [b, c, g, r, q], <= 0
    la = jnp.cumsum(a, axis=-1)  # inclusive running sum within the chunk
    xdt = by_head((x.astype(f32) * dt[..., None]).astype(dtype))
    bm, cm = by_group(b_mat), by_group(c_mat)

    # -- inside the chunks --------------------------------------------------
    # C_i . B_j, once a group
    cb = jnp.einsum("bcgin,bcgjn->bcgij", cm, bm, preferred_element_type=f32)
    # exp(La_i - La_j) for j <= i, per head; masked before the exponential
    diff = la[..., :, None] - la[..., None, :]
    causal = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))  # [b, c, g, r, i, j]
    m = (cb[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bcgrij,bcgrjp->bcgrip", m, xdt, preferred_element_type=f32)

    # -- the chunks' own contributions to the state --------------------------
    to_end = jnp.exp(la[..., -1:] - la)  # [b, c, g, r, q], exponent <= 0
    xdt_end = (xdt.astype(f32) * to_end[..., None]).astype(dtype)
    s_chunk = jnp.einsum(
        "bcgrjp,bcgjn->bcgrpn", xdt_end, bm, preferred_element_type=f32
    )

    # -- from chunk to chunk: only the states --------------------------------
    def carry(state, inputs):
        decay_c, s_c = inputs  # [b, g, r], [b, g, r, p, n]
        return state * decay_c[..., None, None] + s_c, state

    _, h_in = lax.scan(
        carry,
        jnp.zeros((b, g, r, p, n), f32),
        (jnp.moveaxis(jnp.exp(la[..., -1]), 1, 0), jnp.moveaxis(s_chunk, 1, 0)),
    )
    h_in = jnp.moveaxis(h_in, 0, 1)  # the state each chunk starts from
    y_inter = jnp.einsum(
        "bcgin,bcgrpn->bcgrip", cm, h_in.astype(dtype),
        preferred_element_type=f32,
    )
    y = y + y_inter * jnp.exp(la)[..., None]
    return jnp.moveaxis(y, 4, 2).reshape(b, s, h, p)


# ---------------------------------------------------------------------------
# the same chunks as Pallas kernels: everything of a chunk stays in VMEM
# ---------------------------------------------------------------------------
#
# One program is one (batch row, group, chunk). It reads the chunk's x rows of
# the group's r = heads / groups heads straight from the [b, s, heads * P] row
# layout (a head is a P-lane slice of the [Q, r * P] tile), B and C [Q, N],
# and dt and the running sum La as [Q, r] columns (La as [r, Q] rows too, so
# that La_i - La_j needs no transpose). The chunk axis is the last grid axis
# and sequential: the group's [r * P, N] float32 states ride a VMEM scratch
# from chunk to chunk (from the last chunk to the first in the backward).
# Products with a [Q, Q] mask run per head; the products with the state run
# once a group over all r * P rows or columns. The D x skip is added here.

_NN = (((1,), (0,)), ((), ()))  # [m, k] x [k, n]
_NT = (((1,), (1,)), ((), ()))  # [m, k] x [n, k]
_TN = (((0,), (0,)), ((), ()))  # [k, m] x [k, n]

# r * P columns a program holds at most: the [r * P, N] state and a few
# [Q, r * P] float32 tiles have to fit the scoped VMEM. 1,024 is what was
# compiled for the described v5e, forward and backward, at Q = N = 128 (the
# backward's five scratch tiles are 2 MB there and its double-buffered
# blocks 2.5 MB more, of 16 MB) and at Q = 256, N = 128 (twice the tiles,
# four times the [Q, Q] masks: PR 68, compiled and run). A group of at most
# this many columns is ONE program a chunk, as it always was (16 heads of 64
# in one group, 8 heads of 64 in each of 8). A wider group (64 heads of 64 in
# ONE group, 4,096 columns: PR 68) goes as COLUMN BLOCKS of whole heads
# (`_column_blocks`): every block is a program of its own over the chunks,
# reads the group's one B and C [Q, N], its own heads' dt and La, and carries
# its own [columns, N] state from chunk to chunk. The recurrence couples no
# two heads, so the forward needs nothing else; in the backward every block
# holds a PART of dB and dC (a sum over the group's heads), written as
# float32 partials and added up once after the kernel. Blocks of 1,024 and
# not fewer columns: at 4,096 columns and Q = 256 the node's forward and
# backward took 6.32 ms in blocks of 1,024 and 6.40 in blocks of 512 (the
# scan alone 1.88 and 2.02: C.B and the reads of B and C once a block; my
# chip run, PR 68).
_MAX_GROUP_COLUMNS = 1024
_LANES = 128


def _column_blocks(columns: int) -> int:
    """How many programs a group of `columns` = r * P columns (whole
    128-lane tiles) goes as: 1 up to `_MAX_GROUP_COLUMNS` (the group whole,
    today's program); beyond, the fewest equal blocks of whole 128-lane
    tiles (so of whole heads of 64 or 128) of at most that many columns."""
    if columns <= _MAX_GROUP_COLUMNS:
        return 1
    width = next(
        w for w in range(_MAX_GROUP_COLUMNS, 0, -_LANES) if columns % w == 0
    )
    return columns // width


def _mm(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _end_decay_columns(la_end, p: int):
    """exp(La_Q) of every head as a [P, r] block of equal rows: a head's
    column scales its [P, N] state. Mosaic broadcasts a value along sublanes
    or along lanes, not both at once, so a [1, 1] decay cannot meet a state;
    the select is what lays the [1, r] row out over P real rows (a plain
    `broadcast_to` stays a replicated layout, which the column slice then
    aborts on; jax 0.9.0)."""
    shape = (p, la_end.shape[1])
    rows = lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.exp(jnp.where(rows >= 0, la_end, 0.0))


def _causal(q: int):
    rows = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return rows >= cols


def _decay_mask(lac, lar_ref, hh: int, causal):
    """exp(La_i - La_j) for j <= i of head `hh`, float32 [Q, Q]: masked
    before the exponential."""
    diff = lac[:, hh:hh + 1] - lar_ref[hh:hh + 1, :]
    return jnp.exp(jnp.where(causal, diff, -jnp.inf))


def _heads_of_tile(p: int):
    """(k, lane_head): the k = 128 / P heads a 128-lane tile of the
    [Q, r * P] row holds, and each lane's head within the tile, [1, 128]."""
    return _LANES // p, lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // p


def _over_lanes(cols, first: int, k: int, lane_head):
    """Columns `first .. first + k` of cols [Q, r], each laid over its head's
    P lanes of a tile: [Q, 128]."""
    out = cols[:, first + k - 1:first + k]
    for i in reversed(range(k - 1)):
        out = jnp.where(lane_head == i, cols[:, first + i:first + i + 1], out)
    return out


def _ssd_fwd_kernel(
    x_ref, b_ref, c_ref, dtc_ref, lac_ref, lar_ref, d_ref, out_ref,
    state, xe_ref, *inter, heads: int, p: int, states_only: bool,
):
    """The forward of one chunk. `states_only` is the backward's first pass:
    it writes the state the chunk STARTS from and skips what only y needs.
    The row is walked a 128-lane tile (128 / P heads) at a time, so that
    every elementwise pass fills its vregs; a head's masked product is taken
    over the whole tile (the MXU is 128 columns wide either way) and kept on
    the head's own lanes."""
    f32 = jnp.float32
    q = x_ref.shape[0]
    dtype = x_ref.dtype
    k, lane_head = _heads_of_tile(p)

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[:] = jnp.zeros_like(state)

    lac, dtc = lac_ref[:], dtc_ref[:]
    la_end = lac[q - 1:q, :]  # [1, r]
    to_end = jnp.exp(la_end - lac)  # [Q, r], exponent <= 0
    bm = b_ref[:]
    h_in = state[:]
    if states_only:
        out_ref[:] = h_in
    else:
        (inter_ref,) = inter
        cm = c_ref[:]
        cb = _mm(cm, bm, _NT)  # C_i . B_j, once a group
        inter_ref[:] = _mm(cm, h_in.astype(dtype), _NT)  # [Q, r * P]
        e_la = jnp.exp(lac)
        causal = _causal(q)
    for t in range(heads // k):
        tile = pl.ds(t * _LANES, _LANES)
        x = x_ref[:, tile].astype(f32)
        xdt = (x * _over_lanes(dtc, t * k, k, lane_head)).astype(dtype)
        xe_ref[:, tile] = (
            xdt.astype(f32) * _over_lanes(to_end, t * k, k, lane_head)
        ).astype(dtype)
        if states_only:
            continue
        y = None
        for i in range(k):
            decay = _decay_mask(lac, lar_ref, t * k + i, causal)
            y_i = _mm((cb * decay).astype(dtype), xdt, _NN)
            y = y_i if y is None else jnp.where(lane_head == i, y_i, y)
        y = y + inter_ref[:, tile] * _over_lanes(e_la, t * k, k, lane_head)
        out_ref[:, tile] = (y + d_ref[:, tile] * x).astype(out_ref.dtype)
    s_chunk = _mm(xe_ref[:], bm, _TN)  # [r * P, N]
    decay_end = _end_decay_columns(la_end, p)
    for hh in range(heads):
        rows = slice(hh * p, (hh + 1) * p)
        state[pl.ds(hh * p, p), :] = (
            h_in[rows] * decay_end[:, hh:hh + 1] + s_chunk[rows]
        )


def _ssd_bwd_kernel(
    x_ref, dy_ref, b_ref, c_ref, dtc_ref, lac_ref, lar_ref, d_ref, hin_ref,
    dx_ref, db_ref, dc_ref, ddtc_ref, dlac_ref, dlar_ref, dd_ref,
    dstate, xe_ref, dye_ref, dxe_ref, inter_ref, *, heads: int, p: int,
):
    """The backward of one chunk, the chunks visited last to first: `dstate`
    carries the gradient of the state the chunk ENDS in. Writes dx, dB and
    dC (summed over the group's heads here), per head and position the
    gradient of dt through x dt and the gradient of La (a column, and a row
    for the part that sums over a mask's rows); d_skip's gradient adds up
    over the chunks in its resident output block. Tiles as in the forward."""
    f32 = jnp.float32
    q = x_ref.shape[0]
    dtype = x_ref.dtype
    k, lane_head = _heads_of_tile(p)

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[:] = jnp.zeros_like(dstate)
        dd_ref[:] = jnp.zeros_like(dd_ref)

    lac, dtc = lac_ref[:], dtc_ref[:]
    la_end = lac[q - 1:q, :]
    to_end = jnp.exp(la_end - lac)
    e_la = jnp.exp(lac)
    bm, cm = b_ref[:], c_ref[:]
    h_in, dh = hin_ref[:], dstate[:]
    cb = _mm(cm, bm, _NT)
    dxe_ref[:] = _mm(bm, dh.astype(dtype), _NT)  # d(x dt to_end), [Q, r * P]
    inter_ref[:] = _mm(cm, h_in.astype(dtype), _NT)
    causal = _causal(q)
    decay_end = _end_decay_columns(la_end, p)
    last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    lane = lax.broadcasted_iota(jnp.int32, lac.shape, 1)
    dg = jnp.zeros((q, q), f32)
    ddt_cols = jnp.zeros(lac.shape, f32)
    dla_cols = jnp.zeros(lac.shape, f32)

    def of_head(i, tile_value):  # a head's lanes of a tile, zero elsewhere
        if k == 1:
            return tile_value
        return jnp.where(
            lane_head == i, tile_value, jnp.zeros_like(tile_value)
        )

    for t in range(heads // k):
        tile = pl.ds(t * _LANES, _LANES)
        x = x_ref[:, tile].astype(f32)
        dy = dy_ref[:, tile]
        dyf = dy.astype(f32)
        dt_t, to_end_t, e_la_t = (
            _over_lanes(cols, t * k, k, lane_head)
            for cols in (dtc, to_end, e_la)
        )
        xdt = (x * dt_t).astype(dtype)
        xe = xdt.astype(f32) * to_end_t
        xe_ref[:, tile] = xe.astype(dtype)
        dxe = dxe_ref[:, tile]
        dye = dyf * e_la_t
        dye_ref[:, tile] = dye.astype(dtype)
        e_end = dxe * xe
        through_la = dye * inter_ref[:, tile] - e_end
        dxdt = None
        masks = []
        for i in range(k):
            decay = _decay_mask(lac, lar_ref, t * k + i, causal)
            m = cb * decay
            dm = _mm(of_head(i, dy), xdt, _NT)  # [Q, Q]
            dg = dg + dm * decay
            masks.append(dm * m)  # d La_i - d La_j of every pair
            dxdt_i = _mm(m.astype(dtype), dy, _TN)
            dxdt = dxdt_i if dxdt is None else jnp.where(
                lane_head == i, dxdt_i, dxdt
            )
        dxdt = dxdt + dxe * to_end_t
        through_dt = dxdt * x
        for i, w in enumerate(masks):
            hh = t * k + i
            rows = slice(hh * p, (hh + 1) * p)
            to_last = jnp.sum(of_head(i, e_end), keepdims=True) + jnp.sum(
                dh[rows] * h_in[rows] * decay_end[:, hh:hh + 1], keepdims=True
            )
            dla = (
                jnp.sum(w, axis=1, keepdims=True)
                + jnp.sum(of_head(i, through_la), axis=1, keepdims=True)
                + jnp.where(last, to_last, 0.0)  # [Q, 1] against [1, 1]
            )
            dla_cols = jnp.where(lane == hh, dla, dla_cols)
            dlar_ref[hh:hh + 1, :] = -jnp.sum(w, axis=0, keepdims=True)
            ddt_cols = jnp.where(
                lane == hh,
                jnp.sum(of_head(i, through_dt), axis=1, keepdims=True),
                ddt_cols,
            )
            dstate[pl.ds(hh * p, p), :] = dh[rows] * decay_end[:, hh:hh + 1]
        dx_ref[:, tile] = (
            dxdt * dt_t + d_ref[:, tile] * dyf
        ).astype(dx_ref.dtype)
        dd_ref[:, tile] += jnp.sum(dyf * x, axis=0, keepdims=True)
    dlac_ref[:] = dla_cols
    ddtc_ref[:] = ddt_cols
    dgc = dg.astype(dtype)
    dye_all = dye_ref[:]
    dc_ref[:] = (
        _mm(dgc, bm, _NN) + _mm(dye_all, h_in.astype(dtype), _NN)
    ).astype(dc_ref.dtype)
    db_ref[:] = (
        _mm(dgc, cm, _TN) + _mm(xe_ref[:], dh.astype(dtype), _NN)
    ).astype(db_ref.dtype)
    dstate[:] += _mm(dye_all, cm, _TN)


def _by_group(t, groups: int):
    """[b, s, heads] -> [b, groups, s, heads / groups]."""
    b, s, h = t.shape
    return jnp.moveaxis(t.reshape(b, s, groups, h // groups), 2, 1)


def _per_head_operands(dt, la, groups: int):
    """(dt and La as [b, groups, s, r] columns, La as [b, groups, r, s]
    rows) of the [b, s, heads] float32 arrays."""
    lac = _by_group(la, groups)
    return _by_group(dt, groups), lac, jnp.swapaxes(lac, 2, 3)


def _from_group(t):
    """[b, groups, s, r] -> [b, s, groups * r]."""
    b, g, s, r = t.shape
    return jnp.moveaxis(t, 1, 2).reshape(b, s, g * r)


class _Blocks:
    """The BlockSpecs of the kernels' operands over the grid (batch, program,
    chunk); `reverse` visits the chunks last to first. A program is a group,
    or where a group is wider than `_MAX_GROUP_COLUMNS` one of its `blocks`
    column blocks (`_column_blocks`): x, dt, La, D and the states are its
    own columns' or heads', B and C its group's."""

    def __init__(self, x, b_mat, dt, groups: int, chunk: int, reverse: bool):
        b, s, hp = x.shape
        self.blocks = blocks = _column_blocks(hp // groups)
        self.programs = programs = groups * blocks
        self.grid = (b, programs, s // chunk)
        self.r = dt.shape[2] // programs
        self.rp, self.n = hp // programs, b_mat.shape[2] // groups
        self.p = self.rp // self.r
        last = s // chunk - 1
        at = (lambda ci: last - ci) if reverse else (lambda ci: ci)
        rp, n, r = self.rp, self.n, self.r
        # [b, s, programs * width]: the chunk's rows, the program's columns
        self.x = pl.BlockSpec(
            (None, chunk, rp), lambda bi, gi, ci: (bi, at(ci), gi)
        )
        # [b, s, groups * n] read, and [b, s, programs * n] written (dB and
        # dC: a program's own part where a group goes as blocks)
        self.bc_part = pl.BlockSpec(
            (None, chunk, n), lambda bi, gi, ci: (bi, at(ci), gi)
        )
        self.bc = self.bc_part if blocks == 1 else pl.BlockSpec(
            (None, chunk, n), lambda bi, gi, ci: (bi, at(ci), gi // blocks)
        )
        # [b, programs, s, r] and [b, programs, r, s]
        self.col = pl.BlockSpec(
            (None, None, chunk, r), lambda bi, gi, ci: (bi, gi, at(ci), 0)
        )
        self.row = pl.BlockSpec(
            (None, None, r, chunk), lambda bi, gi, ci: (bi, gi, 0, at(ci))
        )
        # [1, programs * rp], and [b, 1, programs * rp] resident over the
        # chunks
        self.skip = pl.BlockSpec((1, rp), lambda bi, gi, ci: (0, gi))
        self.sums = pl.BlockSpec(
            (None, 1, rp), lambda bi, gi, ci: (bi, 0, gi)
        )
        # [b, programs, chunks, rp, n]
        self.state = pl.BlockSpec(
            (None, None, None, rp, n),
            lambda bi, gi, ci: (bi, gi, at(ci), 0, 0),
        )


_SEQUENTIAL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


# jitted, so that the layers of a model that call a kernel at one shape share
# ONE trace and ONE Mosaic lowering of its body: a `pallas_call` is traced
# anew at every call, three kernels a layer, and a process lowers its step
# more than once (warm `setup_s` of the four-layer cell 38.3-38.9 s without
# this, 34.0-36.9 with, the XLA form 32.0-34.9; my chip runs, PR 33). The
# operations keep the scope of the call site they are inlined into.
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _ssd_forward(x, dt, la, b_mat, c_mat, d_row, groups, chunk, interpret,
                 states_only=False):
    """y [b, s, h * P] in x's dtype, or with `states_only` the float32 state
    every chunk starts from, [b, programs, chunks, r * P, N] (a program a
    group, or a column block of one: `_Blocks`)."""
    f32 = jnp.float32
    (b, s, _), q = x.shape, chunk
    at = _Blocks(x, b_mat, dt, groups, chunk, reverse=False)
    scratch = [pltpu.VMEM((at.rp, at.n), f32), pltpu.VMEM((q, at.rp), x.dtype)]
    if states_only:
        out_shape = jax.ShapeDtypeStruct(
            (b, at.programs, s // q, at.rp, at.n), f32
        )
        out_spec = at.state
    else:
        out_shape, out_spec = jax.ShapeDtypeStruct(x.shape, x.dtype), at.x
        scratch.append(pltpu.VMEM((q, at.rp), f32))
    return pl.pallas_call(
        functools.partial(
            _ssd_fwd_kernel, heads=at.r, p=at.p, states_only=states_only
        ),
        grid=at.grid,
        in_specs=[at.x, at.bc, at.bc, at.col, at.col, at.row, at.skip],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_SEQUENTIAL_CHUNKS,
        interpret=interpret,
        name="ssd_states_chunk" if states_only else "ssd_fwd_chunk",
    )(x, b_mat, c_mat, *_per_head_operands(dt, la, at.programs), d_row)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _ssd_backward(x, dy, dt, la, b_mat, c_mat, d_row, h_in, groups, chunk,
                  interpret):
    """(dx, d dt through x dt, d La, dB, dC, d d_row) of `_ssd_forward`."""
    f32 = jnp.float32
    (b, s, hp), q = x.shape, chunk
    at = _Blocks(x, b_mat, dt, groups, chunk, reverse=True)
    dtc, lac, lar = _per_head_operands(dt, la, at.programs)
    small = jax.ShapeDtypeStruct(lac.shape, f32)
    # dB and dC sum over a group's heads: where the group goes as column
    # blocks every block writes its own part, in float32, and the parts are
    # added once below (blocks * 2 * [s, N] float32 written and read again:
    # 16 MB a node at 4 blocks of 4,096 positions, beside the 100 MB of x,
    # dy and dx)
    part = (b, s, at.programs * at.n)
    dx, db, dc, ddtc, dlac, dlar, dd = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, heads=at.r, p=at.p),
        grid=at.grid,
        in_specs=[at.x, at.x, at.bc, at.bc, at.col, at.col, at.row, at.skip,
                  at.state],
        out_specs=[at.x, at.bc_part, at.bc_part, at.col, at.col, at.row,
                   at.sums],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(part, b_mat.dtype if at.blocks == 1 else f32),
            jax.ShapeDtypeStruct(part, c_mat.dtype if at.blocks == 1 else f32),
            small, small,
            jax.ShapeDtypeStruct(lar.shape, f32),
            jax.ShapeDtypeStruct((b, 1, hp), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((at.rp, at.n), f32),
            pltpu.VMEM((q, at.rp), x.dtype),
            pltpu.VMEM((q, at.rp), x.dtype),
            pltpu.VMEM((q, at.rp), f32),
            pltpu.VMEM((q, at.rp), f32),
        ],
        compiler_params=_SEQUENTIAL_CHUNKS,
        interpret=interpret,
        name="ssd_bwd_chunk",
    )(x, dy, b_mat, c_mat, dtc, lac, lar, d_row, h_in)
    dla = _from_group(dlac) + _from_group(jnp.swapaxes(dlar, 2, 3))
    if at.blocks > 1:
        db, dc = (
            jnp.sum(t.reshape(b, s, groups, at.blocks, at.n), axis=3)
            .reshape(b, s, groups * at.n).astype(like.dtype)
            for t, like in ((db, b_mat), (dc, c_mat))
        )
    return dx, _from_group(ddtc), dla, db, dc, jnp.sum(dd, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ssd_scan(groups, chunk, interpret, x, dt, la, b_mat, c_mat, d_row):
    """The chunked recurrence with the D x skip on row layouts: x
    [b, s, h * P], dt and la [b, s, h] float32 (la the running sum of dt A
    within each chunk), b_mat and c_mat [b, s, g * N], d_row [1, h * P]
    float32 (D repeated over a head's columns) -> y like x. s is a multiple
    of `chunk`. What the backward keeps is these operands."""
    return _ssd_forward(x, dt, la, b_mat, c_mat, d_row, groups, chunk, interpret)


def _ssd_scan_fwd(groups, chunk, interpret, *operands):
    return _ssd_scan(groups, chunk, interpret, *operands), operands


def _ssd_scan_bwd(groups, chunk, interpret, operands, dy):
    x, dt, la, b_mat, c_mat, d_row = operands
    h_in = _ssd_forward(*operands, groups, chunk, interpret, True)
    return _ssd_backward(
        x, dy, dt, la, b_mat, c_mat, d_row, h_in, groups, chunk, interpret
    )


_ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


def scan_column_blocks(batch, heads, head_dim, groups, state, chunk) -> int:
    """The column blocks a group's scan goes as on the Pallas kernels (1: the
    group whole), 0 where `scan_route` names "xla": what the program's
    counter keeps of a state-space node (`kernels/ops._note_scan_blocks`)."""
    if scan_route(batch, heads, head_dim, groups, state, chunk) == "xla":
        return 0
    return _column_blocks(heads // groups * head_dim)


def scan_route(batch, heads, head_dim, groups, state, chunk) -> str:
    """Which form `selective_scan` takes, from what the trace can observe:

    - "ssd": the Pallas kernels, where the backend is a TPU (or the CPU with
      interpret mode opted in, `context.interpret_default`), the tiles fill whole
      vregs (chunk and state multiples of 128 lanes, a group's r * P columns
      whole 128-lane tiles of heads of 64 or 128) and fit VMEM (a group of
      at most `_MAX_GROUP_COLUMNS` columns whole, a wider one as the column
      blocks `_column_blocks` cuts it into), and the trace admits a bare
      Pallas call (`context.admits_bare_pallas_call`);
    - "ssd_sharded": the same under a declared `context.flash_mesh` with
      whole heads whose batch axes divide the batch: the kernels mapped over
      the batch shards, as the attention kernels are (a group that goes as column
      blocks has not been mapped over a mesh yet and takes "xla" there);
    - "xla": everything else, `_scan_core`."""
    from flexflow_tpu.kernels import flash_attention as flash

    columns = heads // groups * head_dim
    if (
        chunk % 128 or state % 128 or columns % 128
        or head_dim not in (64, 128)
    ):
        return "xla"
    blocks = _column_blocks(columns)
    ctx = context.declared_mesh()
    if ctx is None:
        admitted = context.admits_bare_pallas_call(context.interpret_default())
        return "ssd" if admitted else "xla"
    mesh, batch_axes, head_axes, interpret = ctx
    if head_axes is not None or batch % flash._axes_size(mesh, batch_axes):
        return "xla"
    if blocks > 1:
        return "xla"
    return "ssd_sharded" if context.on_tpu(interpret) else "xla"


def _ssd_scan_routed(route, groups, chunk, *operands):
    if route == "ssd":
        return _ssd_scan(groups, chunk, context.interpret_default(), *operands)
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.utils.shard_map_compat import shard_map_compat

    mesh, batch_axes, _, interpret = context.declared_mesh()
    rows = P(batch_axes, None, None)
    return shard_map_compat(
        functools.partial(_ssd_scan, groups, chunk, interpret),
        mesh, (rows,) * 5 + (P(None, None),), rows,
    )(*operands)


def selective_scan(x, dt, a_log, b_mat, c_mat, d_skip, chunk: int):
    """The recurrence of the module docstring: x [b, s, h, p], dt [b, s, h]
    (float32, after softplus), a_log and d_skip [h], b_mat and c_mat
    [b, s, g, n] -> y [b, s, h, p] in x's dtype, by the form `scan_route`
    names. A sequence that is no multiple of `chunk` is padded at its end
    with dt = 0 and x = 0, which changes no earlier position."""
    b, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    f32 = jnp.float32
    pad = -s % chunk
    if pad:
        def padded(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

        x_, dt_, b_, c_ = padded(x), padded(dt), padded(b_mat), padded(c_mat)
    else:
        x_, dt_, b_, c_ = x, dt, b_mat, c_mat
    route = scan_route(b, h, p, g, n, chunk)
    with jax.named_scope("scan"):
        if route == "xla":
            core = jax.checkpoint(_scan_core, static_argnums=(5,))
            y = core(x_, dt_, a_log, b_, c_, chunk)[:, :s]
            y = y + d_skip.astype(f32)[:, None] * x.astype(f32)
            return y.astype(x.dtype)
        full = s + pad
        a = dt_ * (-jnp.exp(a_log.astype(f32)))
        la = jnp.cumsum(a.reshape(b, full // chunk, chunk, h), axis=2)
        y = _ssd_scan_routed(
            route, g, chunk,
            x_.reshape(b, full, h * p), dt_, la.reshape(b, full, h),
            b_.reshape(b, full, g * n), c_.reshape(b, full, g * n),
            jnp.repeat(d_skip.astype(f32), p)[None],
        )
        return y[:, :s].reshape(b, s, h, p)


def state_space_forward(
    attrs: StateSpaceAttrs, u: jnp.ndarray, weights: Sequence[jnp.ndarray]
) -> jnp.ndarray:
    """u [b, s, D] -> [b, s, D]; weights in `StateSpaceAttrs` slot order."""
    w_in, w_conv, b_conv, dt_bias, a_log, d_skip, gain, w_out = weights
    b, s, _ = u.shape
    heads, p = attrs.num_heads, attrs.head_dim
    g, n = attrs.num_groups, attrs.state_size
    inner = attrs.inner
    zxbcdt = u @ w_in
    z = zxbcdt[..., :inner]
    dt = zxbcdt[..., inner + attrs.conv_width:]
    with jax.named_scope("conv"):
        xbc = conv_silu(zxbcdt, w_conv, b_conv, inner)
    x = xbc[..., :inner].reshape(b, s, heads, p)
    b_mat = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
    c_mat = xbc[..., inner + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    y = selective_scan(x, dt, a_log, b_mat, c_mat, d_skip, attrs.chunk_size)
    with jax.named_scope("norm"):
        y = gated_group_norm(
            y.reshape(b, s, inner), z, gain, attrs.num_groups, attrs.norm_eps
        )
    return y @ w_out
