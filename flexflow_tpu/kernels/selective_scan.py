"""The selective-scan mixer's kernels (`SelectiveScanAttrs`, the Mamba-1 layer
of arXiv:2312.00752): a recurrence a CHANNEL, each channel with its own step
and an N-wide state whose every column has its own decay. For channel c and
state column n, over the positions t of one sequence:

    dt_t[c]   = softplus(r_t[c] + b_dt[c])
    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    m_t[c]    = sum_n C_t[n] s_t[c, n] + D[c] x_t[c]          A = -exp(A_log)

with s_{-1} = 0, so every exponent is <= 0. As a linear attention this is one
head a channel with an N-wide key and a 1-wide value: it fills no matrix
unit, and the chunked matmul forms of `ssm.py` and `kda.py` do not carry
over. It is a pass over time on the vector unit, and `scan_route` picks one
of two forms of it from what the trace can observe:

- **The Pallas kernels** (`s6_scan_fwd`, `s6_scan_bwd`; "pallas"): on a TPU
  (or the interpreter where a test opts in), channels in whole 128-lane
  tiles, a state of whole 8-sublane tiles, no mesh declared. The state lies
  TRANSPOSED, [N, channels]: channels along the lanes, the N columns down
  the sublanes, float32. One program is one (batch row, block of channels,
  chunk of `chunk` positions), the chunks of a row one after the other with
  the block's [N, channels] state in a VMEM scratch between them. Inside a
  program the block is walked `sub` channels at a time with that slice of
  the state in registers for the whole chunk; x and r are read as aligned
  [rows, sub] tiles (`rows` positions: a packed bf16 tile is 16), m is
  written the same way, and B_t and C_t arrive as [N, 128] tiles (a column
  broadcast along the lanes, made by XLA once a call: [s, N, 128], a
  fortieth of a [s, channels, N] tensor at 5,120 channels), so that a step
  is sublane broadcasts, multiplies, one exponential a state element and
  one sublane sum. x, r, B and C are read once a pass and m written once;
  no [t, channels, N] tensor exists anywhere.
  The backward is WRITTEN (`jax.custom_vjp`) and recomputes: the forward
  also writes the state every chunk STARTS from ([s / chunk, N, channels]
  float32: 10 MB a layer at 4,096 positions of 5,120 channels), and the
  backward walks the chunks last to first; in each it runs the chunk's steps
  forward once more from that state, keeping the `chunk` states of its `sub`
  channels in VMEM, then runs time backwards over them carrying the state's
  cotangent (a_{t+1} g_{t+1}) in registers and from chunk to chunk in a
  scratch. Dividing the state back out (s_{t-1} = (s_t - b_t) / a_t) was
  not taken: a decay near zero makes it a division by nothing. dB and dC
  are sums over channels, which lie along the lanes: the kernel folds them
  to [N, 128] lane partials a position and XLA takes the last sum; dA_log
  accumulates in its [N, channels] output block over the chunks; dD and
  db_dt are XLA's reductions over the kernel's operands and results.
- **`lax.scan`** ("scan"): the CPU, widths that fill no tile, a trace that
  admits no bare Pallas call (`kernels/context.admits_bare_pallas_call`). One
  `lax.scan` over the chunks of a `jax.checkpoint`ed `lax.scan` over a
  chunk's positions: JAX's own transpose, which keeps the chunk-edge states
  and recomputes inside. Float32 throughout.

Both take their operands in the step's dtype and compute in float32; the
state is float32 in both. The projections and the gate stay XLA's around
the scan (`selective_scan_forward`); the convolution is `ssm.conv_silu` on
the x half of W_in's row, read in place, in the form `ssm.conv_route` picks
(since PR 59 its own two kernels where they apply). Each goes under a scope
of its own inside the node's (`in_proj`, `conv`, `scan`, `gate`, `out_proj`;
`observability/trace.NODE_PARTS`).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.kernels import context
from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs

# positions a program takes (and a kept state apart), channels a register
# walk takes forward and backward (the backward carries two states' worth)
_CHUNK = 128
_SUB_FWD = 512
_SUB_BWD = 256
_VMEM_LIMIT = 64 * 1024 * 1024
_LANES = 128


def _softplus(z):
    """log(1 + exp(z)) of a float32 z without an overflow: the identity
    from 20 on, where the difference is below float32's last bit."""
    return jnp.where(z > 20.0, z, jnp.log1p(jnp.exp(jnp.minimum(z, 20.0))))


def scan_route(channels: int, state: int) -> str:
    """"pallas" or "scan" (module docstring), from static facts alone."""
    if channels % _LANES or state % 8:
        return "scan"
    admitted = context.admits_bare_pallas_call(context.interpret_default())
    return "pallas" if admitted else "scan"


# ---------------------------------------------------------------------------
# the lax.scan form
# ---------------------------------------------------------------------------


def _scan_reference(x, r, dt_bias, a_log, b_mat, c_mat, d_skip, chunk):
    """The recurrence as written, one `lax.scan` step a position, float32."""
    f32 = jnp.float32
    b, s, w = x.shape
    a = -jnp.exp(a_log.astype(f32))  # [w, n]
    pad = -s % chunk

    def chunks(t):
        t = jnp.pad(t.astype(f32), ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(t.reshape(b, -1, chunk, t.shape[-1]), 0, 2)

    def step(state, row):
        x_t, r_t, b_t, c_t = row  # [b, w], [b, w], [b, n], [b, n]
        dt = _softplus(r_t + dt_bias.astype(f32))
        state = (
            jnp.exp(dt[..., None] * a) * state
            + (dt * x_t)[..., None] * b_t[:, None, :]
        )
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def one_chunk(state, rows):
        return lax.scan(step, state, rows)

    _, y = lax.scan(
        one_chunk, jnp.zeros((b, w, a.shape[-1]), f32),
        tuple(chunks(t) for t in (x, r, b_mat, c_mat)),
    )
    # [chunks, chunk, b, w] -> [b, s, w]
    y = jnp.moveaxis(y.reshape(-1, b, w), 0, 1)[:, :s]
    return (y + d_skip.astype(f32) * x.astype(f32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# the Pallas form
# ---------------------------------------------------------------------------


def _rows_of(dtype) -> int:
    """Positions of one aligned tile of `dtype`: 8 sublanes of 32 bits."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _widen(tile, sub: int):
    """An [N, 128] lane-broadcast tile as [N, sub]."""
    return tile if sub == _LANES else jnp.concatenate(
        [tile] * (sub // _LANES), axis=1
    )


def _fold(t, sub: int):
    """[N, sub] -> [N, 128]: the sum of its 128-lane column groups."""
    out = t[:, :_LANES]
    for k in range(1, sub // _LANES):
        out = out + t[:, k * _LANES:(k + 1) * _LANES]
    return out


def _fwd_kernel(x_ref, r_ref, b_ref, c_ref, a_ref, d_ref, bias_ref, y_ref,
                *rest, sub: int, rows: int):
    """One chunk of one block of channels, forward (module docstring).
    `rest` is (the chunk-start state's output block,)? and the scratch."""
    f32 = jnp.float32
    state = rest[-1]
    chunk, width = x_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[:] = jnp.zeros_like(state)

    if len(rest) == 2:
        rest[0][:] = state[:]

    def walk(ci, _):
        cols = pl.ds(pl.multiple_of(ci * sub, sub), sub)
        a = a_ref[:, cols]
        d_skip, bias = d_ref[:, cols], bias_ref[:, cols]
        row_id = lax.broadcasted_iota(jnp.int32, (rows, sub), 0)

        def group(gi, h):
            r0 = pl.multiple_of(gi * rows, rows)
            xs = x_ref[pl.ds(r0, rows), cols].astype(f32)
            dts = _softplus(r_ref[pl.ds(r0, rows), cols].astype(f32) + bias)
            out = jnp.zeros((rows, sub), f32)
            for j in range(rows):
                dt, x = dts[j:j + 1], xs[j:j + 1]
                b_t = _widen(b_ref[r0 + j].astype(f32), sub)
                c_t = _widen(c_ref[r0 + j].astype(f32), sub)
                h = jnp.exp(dt * a) * h + (dt * x) * b_t
                y = jnp.sum(c_t * h, axis=0, keepdims=True) + d_skip * x
                out = jnp.where(row_id == j, y, out)
            y_ref[pl.ds(r0, rows), cols] = out.astype(y_ref.dtype)
            return h

        state[:, cols] = lax.fori_loop(0, chunk // rows, group, state[:, cols])
        return 0

    lax.fori_loop(0, width // sub, walk, 0)


def _bwd_kernel(x_ref, r_ref, b_ref, c_ref, a_ref, d_ref, bias_ref, dy_ref,
                h0_ref, dx_ref, dr_ref, db_ref, dc_ref, da_ref, carry, kept,
                *, sub: int, rows: int):
    """One chunk of one block of channels, backward: the chunks arrive last
    first. `carry` [N, width] is a_{t+1} g_{t+1} from the chunk after this
    one, `kept` [chunk, N, sub] the state BEFORE each step of the channels
    being walked."""
    f32 = jnp.float32
    chunk, width = x_ref.shape
    groups = chunk // rows

    @pl.when(pl.program_id(2) == 0)
    def _():
        carry[:] = jnp.zeros_like(carry)
        da_ref[:] = jnp.zeros_like(da_ref)

    db_ref[:] = jnp.zeros_like(db_ref)
    dc_ref[:] = jnp.zeros_like(dc_ref)

    def walk(ci, _):
        cols = pl.ds(pl.multiple_of(ci * sub, sub), sub)
        a = a_ref[:, cols]
        d_skip, bias = d_ref[:, cols], bias_ref[:, cols]
        row_id = lax.broadcasted_iota(jnp.int32, (rows, sub), 0)

        def load(r0):
            xs = x_ref[pl.ds(r0, rows), cols].astype(f32)
            z = r_ref[pl.ds(r0, rows), cols].astype(f32) + bias
            return xs, z

        def again(gi, h):
            r0 = pl.multiple_of(gi * rows, rows)
            xs, z = load(r0)
            dts = _softplus(z)
            for j in range(rows):
                kept[r0 + j] = h
                dt, x = dts[j:j + 1], xs[j:j + 1]
                b_t = _widen(b_ref[r0 + j].astype(f32), sub)
                h = jnp.exp(dt * a) * h + (dt * x) * b_t
            return h

        lax.fori_loop(0, groups, again, h0_ref[:, cols])

        def back(gi, state):
            g_next, da = state
            r0 = pl.multiple_of((groups - 1 - gi) * rows, rows)
            xs, z = load(r0)
            dts = _softplus(z)
            dys = dy_ref[pl.ds(r0, rows), cols].astype(f32)
            dx_out = jnp.zeros((rows, sub), f32)
            ddt_out = jnp.zeros((rows, sub), f32)
            for j in reversed(range(rows)):
                dt, x, dy = dts[j:j + 1], xs[j:j + 1], dys[j:j + 1]
                b_t = _widen(b_ref[r0 + j].astype(f32), sub)
                c_t = _widen(c_ref[r0 + j].astype(f32), sub)
                before = kept[r0 + j]
                decay = jnp.exp(dt * a)
                h = decay * before + (dt * x) * b_t
                g = c_t * dy + g_next
                dc_ref[r0 + j] += _fold(dy * h, sub)
                db_ref[r0 + j] += _fold(g * (dt * x), sub)
                into_b = jnp.sum(g * b_t, axis=0, keepdims=True)
                through_a = g * before * decay
                da = da + through_a * dt
                ddt = jnp.sum(through_a * a, axis=0, keepdims=True) + x * into_b
                dx = dt * into_b + d_skip * dy
                dx_out = jnp.where(row_id == j, dx, dx_out)
                ddt_out = jnp.where(row_id == j, ddt, ddt_out)
                g_next = decay * g
            # softplus'(z) = sigmoid(z)
            dr = ddt_out * jax.nn.sigmoid(z)
            dx_ref[pl.ds(r0, rows), cols] = dx_out.astype(dx_ref.dtype)
            dr_ref[pl.ds(r0, rows), cols] = dr.astype(dr_ref.dtype)
            return g_next, da

        g_next, da = lax.fori_loop(
            0, groups, back, (carry[:, cols], jnp.zeros((a.shape[0], sub), f32))
        )
        carry[:, cols] = g_next
        da_ref[:, cols] += da
        return 0

    lax.fori_loop(0, width // sub, walk, 0)


def _sub_of(width: int, most: int) -> int:
    """The widest register walk of at most `most` channels that divides the
    block."""
    sub = most
    while width % sub:
        sub //= 2
    return sub


def _layout(x, r, dt_bias, a_log, b_mat, c_mat, d_skip, chunk):
    """The kernels' operands: the sequence padded to whole chunks (a padded
    position comes after every real one and its output is dropped), B and C
    as lane-broadcast [b, s, N, 128] tiles, A, D and the bias transposed to
    [., channels] float32 rows."""
    f32 = jnp.float32
    s = x.shape[1]
    rows = _rows_of(x.dtype)
    chunk = min(chunk, -(-s // rows) * rows)
    pad = -s % chunk

    def padded(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    def tiles(t):
        t = padded(t).astype(x.dtype)
        return jnp.broadcast_to(t[..., None], t.shape + (_LANES,))

    return (
        padded(x), padded(r), tiles(b_mat), tiles(c_mat),
        -jnp.exp(a_log.astype(f32)).T, d_skip.astype(f32)[None],
        dt_bias.astype(f32)[None],
    ), chunk, rows


def _specs(width: int, n: int, chunk: int, reverse_of: int = None):
    """BlockSpecs of a (batch row, channel block, chunk) program: a
    [chunk, width] tile of a [b, s, channels] operand, a [chunk, N, 128] tile
    of B or C, and an [N or 1, width] row of a per-channel constant. With
    `reverse_of` chunks the time axis is walked from its last chunk."""
    def at(ti):
        return ti if reverse_of is None else reverse_of - 1 - ti

    tile = pl.BlockSpec((None, chunk, width), lambda bi, wi, ti: (bi, at(ti), wi))
    lanes = pl.BlockSpec(
        (None, chunk, n, _LANES), lambda bi, wi, ti: (bi, at(ti), 0, 0)
    )

    def const(height):
        return pl.BlockSpec((height, width), lambda bi, wi, ti: (0, wi))

    state = pl.BlockSpec(
        (None, None, n, width), lambda bi, wi, ti: (bi, at(ti), 0, wi)
    )
    return tile, lanes, const, state


def _params(interpret):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )


def _scan_fwd_call(operands, chunk, rows, interpret, keep_states):
    x = operands[0]
    b, s, w = x.shape
    n = operands[4].shape[0]
    tile, lanes, const, state = _specs(w, n, chunk)
    out_specs = [tile]
    out_shape = [jax.ShapeDtypeStruct((b, s, w), x.dtype)]
    if keep_states:
        out_specs.append(state)
        out_shape.append(
            jax.ShapeDtypeStruct((b, s // chunk, n, w), jnp.float32)
        )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sub=_sub_of(w, _SUB_FWD), rows=rows),
        name="s6_scan_fwd",
        interpret=interpret,
        compiler_params=_params(interpret),
        grid=(b, 1, s // chunk),
        in_specs=[tile, tile, lanes, lanes, const(n), const(1), const(1)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, w), jnp.float32)],
    )(*operands)


def _scan_bwd_call(operands, dy, h0, chunk, rows, interpret):
    x = operands[0]
    b, s, w = x.shape
    n = operands[4].shape[0]
    f32 = jnp.float32
    sub = _sub_of(w, _SUB_BWD)
    tile, lanes, const, state = _specs(w, n, chunk, reverse_of=s // chunk)
    partial_shape = jax.ShapeDtypeStruct((b, s, n, _LANES), f32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, sub=sub, rows=rows),
        name="s6_scan_bwd",
        interpret=interpret,
        compiler_params=_params(interpret),
        grid=(b, 1, s // chunk),
        in_specs=[
            tile, tile, lanes, lanes, const(n), const(1), const(1), tile, state,
        ],
        out_specs=[
            tile, tile, lanes, lanes,  # dB's and dC's lane partials
            pl.BlockSpec((None, n, w), lambda bi, wi, ti: (bi, 0, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, w), x.dtype),
            jax.ShapeDtypeStruct((b, s, w), x.dtype),
            partial_shape, partial_shape,
            jax.ShapeDtypeStruct((b, n, w), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, w), f32), pltpu.VMEM((chunk, n, sub), f32),
        ],
    )(*operands, dy, h0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _pallas_scan(chunk, interpret, x, r, dt_bias, a_log, b_mat, c_mat, d_skip):
    operands, chunk, rows = _layout(
        x, r, dt_bias, a_log, b_mat, c_mat, d_skip, chunk
    )
    (y,) = _scan_fwd_call(operands, chunk, rows, interpret, False)
    return y[:, :x.shape[1]]


def _pallas_scan_fwd(chunk, interpret, *args):
    x = args[0]
    operands, chunk_, rows = _layout(*args, chunk)
    y, h0 = _scan_fwd_call(operands, chunk_, rows, interpret, True)
    return y[:, :x.shape[1]], (args, h0)


def _pallas_scan_bwd(chunk, interpret, kept, dy):
    args, h0 = kept
    x, r, dt_bias, a_log, b_mat, c_mat, d_skip = args
    f32 = jnp.float32
    s = x.shape[1]
    operands, chunk_, rows = _layout(*args, chunk)
    pad = operands[0].shape[1] - s
    dy_ = jnp.pad(dy, ((0, 0), (0, pad), (0, 0)))
    dx, dr, db, dc, da = _scan_bwd_call(
        operands, dy_, h0, chunk_, rows, interpret
    )
    dx, dr = dx[:, :s], dr[:, :s]
    # A = -exp(A_log): dA/dA_log = A; the kernel's rows are [N, channels]
    da_log = (jnp.sum(da, axis=0) * operands[4]).T
    return (
        dx, dr,
        jnp.sum(dr.astype(f32), axis=(0, 1)).astype(dt_bias.dtype),
        da_log.astype(a_log.dtype),
        jnp.sum(db[:, :s], axis=-1).astype(b_mat.dtype),
        jnp.sum(dc[:, :s], axis=-1).astype(c_mat.dtype),
        jnp.sum(
            dy.astype(f32) * x.astype(f32), axis=(0, 1)
        ).astype(d_skip.dtype),
    )


_pallas_scan.defvjp(_pallas_scan_fwd, _pallas_scan_bwd)


def selective_scan(x, r, dt_bias, a_log, b_mat, c_mat, d_skip,
                   chunk: int = _CHUNK, route: str = None,
                   interpret: bool = None):
    """The recurrence of the module docstring: x and r [b, s, channels] (r
    the step before its bias and softplus), dt_bias and d_skip [channels],
    a_log [channels, N], b_mat and c_mat [b, s, N] -> m [b, s, channels] in
    x's dtype, by the form `scan_route` names (or `route`, and the kernels
    in `interpret` mode, for a test), noted as the node's
    `selective_scan_routes` (`kernels/context.note`)."""
    route = route or scan_route(x.shape[-1], a_log.shape[-1])
    context.note("selective_scan_routes", route)
    with jax.named_scope("scan"):
        if route == "scan":
            return _scan_reference(
                x, r, dt_bias, a_log, b_mat, c_mat, d_skip, chunk
            )
        if interpret is None:
            interpret = context.interpret_default()
        return _pallas_scan(
            chunk, interpret, x, r, dt_bias, a_log, b_mat, c_mat, d_skip
        )


def selective_scan_forward(
    attrs: SelectiveScanAttrs, u: jnp.ndarray, weights: Sequence[jnp.ndarray]
):
    """u [b, s, D] -> [out [b, s, D]] and, with `memory_output`, the scan's
    result before its gate [b, s, channels] beside it; weights in
    `SelectiveScanAttrs` slot order."""
    from flexflow_tpu.kernels.ssm import conv_silu

    w_in, w_conv, b_conv, w_x, w_dt, dt_bias, a_log, d_skip, w_out = weights
    width, n = attrs.channels, attrs.state_size
    rank = attrs.rank_for(u.shape[-1])
    with jax.named_scope("in_proj"):
        xz = u @ w_in
        z = xz[..., width:]
    with jax.named_scope("conv"):
        x = conv_silu(xz, w_conv, b_conv)
    with jax.named_scope("in_proj"):
        low = x @ w_x
        r = low[..., :rank] @ w_dt
        b_mat, c_mat = low[..., rank:rank + n], low[..., rank + n:]
    m = selective_scan(x, r, dt_bias, a_log, b_mat, c_mat, d_skip)
    with jax.named_scope("gate"):
        gated = (
            m.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        ).astype(u.dtype)
    with jax.named_scope("out_proj"):
        out = gated @ w_out
    return [out, m] if attrs.memory_output else [out]
