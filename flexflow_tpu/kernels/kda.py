"""The gated delta-rule mixer's kernels (`GatedDeltaAttrs`; Kimi Delta
Attention, arXiv:2510.26692): the recurrence, per head, with a [dk, dv] state
S, a log-decay g_t <= 0 for EVERY key channel and a step beta_t in (0, 1):

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

evaluated over chunks of Q positions. With G the inclusive running sum of g
inside a chunk, S_0 the state the chunk starts from and u_j = beta_j (v_j -
S'_j^T k_j) the rule's corrected values, for positions r, j of one chunk:

    A_rj = sum_c k_rc k_jc exp(G_rc - G_jc)   j <  r     (key against key)
    P_rj = sum_c q_rc k_jc exp(G_rc - G_jc)   j <= r     (query against key)
    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp(G)) S_0)
    O   = (Q * exp(G)) S_0 + P U
    S_Q = Diag(exp(G_Q)) S_0 + (K * exp(G_Q - G))^T U

so that with T = (I + Diag(beta) A)^-1 Diag(beta), a [Q, Q] matrix a head
(the WY / UT form), U = T V - (T (K * exp(G))) S_0: everything that does not
read the state is computed for all chunks at once (`chunk_operands`), and
only U, O and the state's update go from chunk to chunk (`chunk_scan`).
Nothing is approximated. The running sums, every decay, the triangular
inverse and the states are float32; the matrix products take operands in the
input's dtype (bf16 in a bf16 step) and accumulate in float32.

**`exp` is only ever taken of a sum of log-decays, which is <= 0.** The
scores need exp(G_r - G_j) INSIDE the contraction over the key channels, so
they are a matrix product only of factors taken relative to a common
reference row: (k_r exp(G_r - G_ref)) . (k_j exp(G_ref - G_j)) with
j < ref <= r, both exponents sums of g and so <= 0. `decayed_scores` gets every
pair of a chunk that way by halving: at each of log2(Q) levels the chunk is
cut into blocks of 2m rows, the reference is the first row of a block's later
half, and the level gives the pairs (r in the later half, j in the earlier
half of the same block); the levels' patterns tile the strict lower triangle.
The textbook K * exp(-G) (one reference, the chunk's start) overflows float32
once a channel's decay over a chunk passes e^-88; here no exponent is ever
positive, whatever the decay. The inverse of the unit lower-triangular
I + Diag(beta) A goes up the same levels (`unit_lower_inverse`): with X the
inverse of the diagonal blocks so far and L the level's off-diagonal part,
X <- X - X L X is exact block elimination, two [Q, Q] products a level.

Two forms of the chunk-to-chunk pass exist, and `scan_route` picks one from
what the trace can observe (shapes, backend, `no_flash`, `flash_mesh`):

- **The Pallas kernels** (`kda_fwd_chunk`, `kda_states_chunk`,
  `kda_bwd_chunk`; "kda"): on a TPU at heads whose key and value sizes are
  multiples of 128 lanes. One program is one (batch row, head, chunk); the
  chunk axis is sequential and the head's [dv, dk] float32 state (held
  transposed, so that the end-of-chunk decay is a row that broadcasts along
  sublanes) rides a VMEM scratch from chunk to chunk, last to first in the
  backward.
- **The XLA form** ("xla"): everything else (the CPU, toy widths, a trace
  that admits no bare Pallas call, a declared mesh): the same products as a
  `lax.scan` over the chunks.

Both are one `jax.custom_vjp` (`chunk_scan`) with a WRITTEN backward: it
recomputes the state every chunk starts from in a first pass and walks the
chunks last to first in a second, carrying the state's gradient. The whole
recurrence (normalisation, gates, `chunk_operands`, `chunk_scan`) runs under
one `jax.checkpoint`, so what a step keeps of the node for its backward is
the op's inputs to it (the convolved q | k | v, the two gates' pre-activations
and the step's logits) and nothing a chunk computes; the chunk operands are
recomputed there, and differentiated by JAX but for the triangular inverse,
whose backward is written too (`unit_lower_inverse`). In neither form does a state
per position ever exist.

The node's parts go under scopes of their own inside the node's
(`ff.kda.<name>/scan`, `/prep`, `/gates`, `/conv`, `/norm`;
`observability/trace.NODE_PARTS`).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.kernels.ssm import conv_silu
from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs

# added under the root of a head's sum of squares before q and k are
# normalised (the public layer's l2norm; `assumed` in the benchmark's file)
L2_EPS = 1e-6
_HIGHEST = lax.Precision.HIGHEST


def _level_blocks(q: int, m: int):
    """For the level whose blocks are 2m rows: (is the row in its block's
    later half [Q], do two rows share a block [Q, Q])."""
    pos = np.arange(q)
    block = pos // (2 * m)
    return (pos % (2 * m)) >= m, block[:, None] == block[None, :]


def decayed_scores(rows, cols, gc):
    """For each of the n operands a stacked in `rows` [.., n, Q, K]:
    sum_c a_rc cols_jc exp(Gc_rc - Gc_jc) for j < r and zero elsewhere,
    float32 [.., n, Q, Q]. `cols` is [.., Q, K], `gc` the inclusive running
    sum of the log-decays within the chunk, float32 [.., Q, K]; Q is a power
    of two. Every exponent is a sum of log-decays and so <= 0 (module
    docstring): a row at or after its level's reference decays FROM it, a
    row before it decays TO it. The n operands share each level's decays and
    its column factor and meet it in ONE product of n * Q rows."""
    q, width = gc.shape[-2:]
    lead = gc.shape[:-2]
    dtype = cols.dtype
    f32 = jnp.float32
    out = jnp.zeros(rows.shape[:-1] + (q,), f32)
    rows, cols = rows.astype(f32), cols.astype(f32)
    m = q // 2
    while m >= 1:
        later, same_block = _level_blocks(q, m)
        ref = gc.reshape(lead + (q // (2 * m), 2 * m, width))[..., m, :]
        ref = jnp.repeat(ref, 2 * m, axis=-2)  # each row's reference
        toward = gc - ref
        e = jnp.exp(jnp.where(later[:, None], toward, -toward))
        col_f = jnp.where(later[:, None], 0.0, cols * e)
        row_f = jnp.where(later[:, None], rows * e[..., None, :, :], 0.0)
        pairs = jnp.einsum(
            "...nrk,...jk->...nrj", row_f.astype(dtype), col_f.astype(dtype),
            preferred_element_type=f32,
        )
        out = out + jnp.where(same_block, pairs, 0.0)
        m //= 2
    return out


@jax.custom_vjp
def unit_lower_inverse(n):
    """(I + n)^-1 of a strictly lower-triangular n, float32 [.., Q, Q], Q a
    power of two, by block elimination up the levels (module docstring).
    Entries of n on or above the diagonal are not read. The backward is
    WRITTEN: with X the inverse, dn = -X^T dX X^T below the diagonal, two
    products where differentiating the levels costs four a level."""
    q = n.shape[-1]
    x = jnp.broadcast_to(jnp.eye(q, dtype=n.dtype), n.shape)
    m = 1
    while m < q:
        later, same_block = _level_blocks(q, m)
        level = same_block & later[:, None] & ~later[None, :]
        below = jnp.where(level, n, 0.0)
        step = jnp.matmul(
            jnp.matmul(x, below, precision=_HIGHEST), x, precision=_HIGHEST
        )
        x = x - step
        m *= 2
    return x


def _unit_lower_inverse_fwd(n):
    x = unit_lower_inverse(n)
    return x, x


def _unit_lower_inverse_bwd(x, dx):
    xt = jnp.swapaxes(x, -1, -2)
    dn = jnp.matmul(
        jnp.matmul(xt, dx, precision=_HIGHEST), xt, precision=_HIGHEST
    )
    return (jnp.where(np.tri(x.shape[-1], k=-1, dtype=bool), -dn, 0.0),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunk_operands(q, k, v, g, beta, chunk: int):
    """What `chunk_scan` reads, for all chunks at once: q, k [b, h, s, dk]
    (normalised), v [b, h, s, dv], g [b, h, s, dk] float32 log-decays,
    beta [b, h, s] float32; s a multiple of `chunk`. Returns, by chunk
    ([b, h, c, Q, .]): Q exp(G) and T (K exp(G)) [., dk], T V [., dv],
    K exp(G_Q - G) [., dk], the inclusive decayed scores P [., Q] in the
    inputs' dtype, and exp(G_Q) [b, h, c, 1, dk] float32."""
    b, h, s, dk = q.shape
    dtype = q.dtype
    f32 = jnp.float32

    def by_chunk(t):
        return t.reshape(b, h, s // chunk, chunk, *t.shape[3:])

    q5, k5, v5 = by_chunk(q), by_chunk(k), by_chunk(v)
    gc = jnp.cumsum(by_chunk(g), axis=3)
    beta5 = by_chunk(beta)
    from_start = jnp.exp(gc)
    g_end = gc[..., -1:, :]
    qf, kf = q5.astype(f32), k5.astype(f32)
    qd = (qf * from_start).astype(dtype)
    kd = kf * from_start
    ke = (kf * jnp.exp(g_end - gc)).astype(dtype)
    scores = decayed_scores(jnp.stack([q5, k5], axis=-3), k5, gc)
    p, a = scores[..., 0, :, :], scores[..., 1, :, :]
    # a position reads its own key undecayed
    own = jnp.sum(qf * kf, axis=-1)
    p = p + own[..., None] * jnp.eye(chunk, dtype=f32)
    t = unit_lower_inverse(beta5[..., :, None] * a) * beta5[..., None, :]
    w = jnp.matmul(t, kd, precision=_HIGHEST).astype(dtype)
    uv = jnp.matmul(t, v5.astype(f32), precision=_HIGHEST).astype(dtype)
    return qd, w, uv, ke, p.astype(dtype), jnp.exp(g_end)


# ---------------------------------------------------------------------------
# from chunk to chunk, the XLA form: a scan over the chunks
# ---------------------------------------------------------------------------


def _chunks_first(t):
    return jnp.moveaxis(t, 2, 0)


def _xla_forward(qd, w, uv, ke, p, gamma, states_only: bool):
    """o [b, h, c, Q, dv], or with `states_only` the float32 state every
    chunk starts from, [b, h, c, dk, dv]."""
    b, h, _, _, dk = qd.shape
    dtype, f32 = uv.dtype, jnp.float32

    def step(state, chunk):
        qd_c, w_c, uv_c, ke_c, p_c, gam_c = chunk
        sd = state.astype(dtype)
        u = uv_c.astype(f32) - jnp.einsum(
            "bhqk,bhkv->bhqv", w_c, sd, preferred_element_type=f32
        )
        ud = u.astype(dtype)
        after = state * jnp.swapaxes(gam_c, -1, -2) + jnp.einsum(
            "bhqk,bhqv->bhkv", ke_c, ud, preferred_element_type=f32
        )
        if states_only:
            return after, state
        o = jnp.einsum(
            "bhqk,bhkv->bhqv", qd_c, sd, preferred_element_type=f32
        ) + jnp.einsum("bhqj,bhjv->bhqv", p_c, ud, preferred_element_type=f32)
        return after, o.astype(dtype)

    _, out = lax.scan(
        step, jnp.zeros((b, h, dk, uv.shape[-1]), f32),
        tuple(_chunks_first(t) for t in (qd, w, uv, ke, p, gamma)),
    )
    return jnp.moveaxis(out, 0, 2)


def _xla_backward(qd, w, uv, ke, p, gamma, states, do):
    """The cotangents of `_xla_forward`'s six operands, the chunks visited
    last to first with the gradient of the state a chunk ENDS in carried.
    With U = T V - W S_0 recomputed from the chunk's incoming state:
    dU = P^T dO + K_e dS_Q; dS_0 = Q_d^T dO + Diag(gamma) dS_Q - W^T dU."""
    b, h, _, _, dk = qd.shape
    dtype, f32 = uv.dtype, jnp.float32

    def mm(spec, x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=f32)

    def step(d_after, chunk):
        qd_c, w_c, uv_c, ke_c, p_c, gam_c, state, do_c = chunk
        sd, dsd = state.astype(dtype), d_after.astype(dtype)
        ud = (uv_c.astype(f32) - mm("bhqk,bhkv->bhqv", w_c, sd)).astype(dtype)
        du = mm("bhqj,bhqv->bhjv", p_c, do_c) + mm("bhqk,bhkv->bhqv", ke_c, dsd)
        dud = du.astype(dtype)
        d_state = (
            mm("bhqk,bhqv->bhkv", qd_c, do_c)
            + d_after * jnp.swapaxes(gam_c, -1, -2)
            - mm("bhqk,bhqv->bhkv", w_c, dud)
        )
        grads = (
            mm("bhqv,bhkv->bhqk", do_c, sd).astype(qd.dtype),
            (-mm("bhqv,bhkv->bhqk", dud, sd)).astype(w.dtype),
            dud,
            mm("bhqv,bhkv->bhqk", ud, dsd).astype(ke.dtype),
            mm("bhqv,bhjv->bhqj", do_c, ud).astype(p.dtype),
            jnp.sum(d_after * state, axis=-1)[..., None, :],
        )
        return d_state, grads

    _, grads = lax.scan(
        step, jnp.zeros((b, h, dk, uv.shape[-1]), f32),
        tuple(_chunks_first(t) for t in (qd, w, uv, ke, p, gamma, states, do)),
        reverse=True,
    )
    return tuple(jnp.moveaxis(t, 0, 2) for t in grads)


# ---------------------------------------------------------------------------
# from chunk to chunk, the Pallas form: the state stays in VMEM
# ---------------------------------------------------------------------------
#
# One program is one (batch row, head, chunk) on [b, h, s, .] operands (the
# chunk's Q rows; P as [b, h, s, Q], exp(G_Q) as [b, h, c, 1, dk]). The chunk
# axis is the last grid axis and sequential. The state is held TRANSPOSED,
# [dv, dk] float32: exp(G_Q) scales its key channels, which are then lanes,
# and a [1, dk] row broadcasts along sublanes for nothing.

_NN = (((1,), (0,)), ((), ()))  # [m, k] x [k, n]
_NT = (((1,), (1,)), ((), ()))  # [m, k] x [n, k]
_TN = (((0,), (0,)), ((), ()))  # [k, m] x [k, n]
_LANES = 128


def _mm(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _kda_fwd_kernel(
    qd_ref, w_ref, uv_ref, ke_ref, p_ref, gam_ref, out_ref, state,
    *, states_only: bool,
):
    """The forward of one chunk; `states_only` is the backward's first pass,
    which writes the state the chunk STARTS from."""
    f32 = jnp.float32
    dtype = uv_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[:] = jnp.zeros_like(state)

    before = state[:]
    sd = before.astype(dtype)
    ud = (uv_ref[:].astype(f32) - _mm(w_ref[:], sd, _NT)).astype(dtype)
    if states_only:
        out_ref[:] = before
    else:
        out_ref[:] = (
            _mm(qd_ref[:], sd, _NT) + _mm(p_ref[:], ud, _NN)
        ).astype(out_ref.dtype)
    state[:] = before * gam_ref[:] + _mm(ud, ke_ref[:], _TN)


def _kda_bwd_kernel(
    qd_ref, w_ref, uv_ref, ke_ref, p_ref, gam_ref, s0_ref, do_ref,
    dqd_ref, dw_ref, duv_ref, dke_ref, dp_ref, dgam_ref, dstate,
):
    """The backward of one chunk, the chunks visited last to first: `dstate`
    carries the gradient of the (transposed) state the chunk ENDS in. The
    products are `_xla_backward`'s."""
    f32 = jnp.float32
    dtype = uv_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        dstate[:] = jnp.zeros_like(dstate)

    before, d_after = s0_ref[:], dstate[:]
    sd, dsd = before.astype(dtype), d_after.astype(dtype)
    do, w = do_ref[:], w_ref[:]
    ud = (uv_ref[:].astype(f32) - _mm(w, sd, _NT)).astype(dtype)
    dud = (_mm(p_ref[:], do, _TN) + _mm(ke_ref[:], dsd, _NT)).astype(dtype)
    dqd_ref[:] = _mm(do, sd, _NN).astype(dqd_ref.dtype)
    dw_ref[:] = (-_mm(dud, sd, _NN)).astype(dw_ref.dtype)
    duv_ref[:] = dud
    dke_ref[:] = _mm(ud, dsd, _NN).astype(dke_ref.dtype)
    dp_ref[:] = _mm(do, ud, _NT).astype(dp_ref.dtype)
    dgam_ref[:] = jnp.sum(d_after * before, axis=0, keepdims=True)
    dstate[:] = (
        _mm(do, qd_ref[:], _TN) + d_after * gam_ref[:] - _mm(dud, w, _TN)
    )


class _Blocks:
    """The BlockSpecs over the grid (batch, head, chunk); `reverse` visits
    the chunks last to first."""

    def __init__(self, qd, uv, p, reverse: bool):
        b, h, c, q, dk = qd.shape
        dv = uv.shape[-1]
        self.grid = (b, h, c)
        self.dk, self.dv = dk, dv
        at = (lambda ci: c - 1 - ci) if reverse else (lambda ci: ci)

        def rows(width):  # [b, h, s, width]: the chunk's rows
            return pl.BlockSpec(
                (None, None, q, width), lambda bi, hi, ci: (bi, hi, at(ci), 0)
            )

        def whole(*tile):  # [b, h, c, *tile]: one tile a chunk
            return pl.BlockSpec(
                (None, None, None) + tile,
                lambda bi, hi, ci: (bi, hi, at(ci), 0, 0),
            )

        self.key, self.value, self.scores = rows(dk), rows(dv), rows(q)
        self.gamma, self.state = whole(1, dk), whole(dv, dk)


_SEQUENTIAL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _rows(t):
    """[b, h, c, Q, w] -> [b, h, s, w]."""
    b, h, c, q, width = t.shape
    return t.reshape(b, h, c * q, width)


# jitted for the reason `kernels/ssm._ssd_forward` is: the layers that call a
# kernel at one shape share ONE trace and ONE Mosaic lowering of its body
@functools.partial(jax.jit, static_argnums=(6, 7))
def _pallas_forward(qd, w, uv, ke, p, gamma, interpret, states_only=False):
    """`_xla_forward` as kernels; the states come out TRANSPOSED,
    [b, h, c, dv, dk]."""
    f32 = jnp.float32
    b, h, c, _, _ = qd.shape
    at = _Blocks(qd, uv, p, reverse=False)
    if states_only:
        out_shape = jax.ShapeDtypeStruct((b, h, c, at.dv, at.dk), f32)
        out_spec = at.state
    else:
        out_shape = jax.ShapeDtypeStruct(_rows(uv).shape, uv.dtype)
        out_spec = at.value
    out = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, states_only=states_only),
        grid=at.grid,
        in_specs=[at.key, at.key, at.value, at.key, at.scores, at.gamma],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((at.dv, at.dk), f32)],
        compiler_params=_SEQUENTIAL_CHUNKS,
        interpret=interpret,
        name="kda_states_chunk" if states_only else "kda_fwd_chunk",
    )(_rows(qd), _rows(w), _rows(uv), _rows(ke), _rows(p), gamma)
    return out if states_only else out.reshape(uv.shape)


@functools.partial(jax.jit, static_argnums=(8,))
def _pallas_backward(qd, w, uv, ke, p, gamma, states, do, interpret):
    f32 = jnp.float32
    at = _Blocks(qd, uv, p, reverse=True)

    def like(t):
        return jax.ShapeDtypeStruct(_rows(t).shape, t.dtype)

    dqd, dw, duv, dke, dp, dgam = pl.pallas_call(
        _kda_bwd_kernel,
        grid=at.grid,
        in_specs=[at.key, at.key, at.value, at.key, at.scores, at.gamma,
                  at.state, at.value],
        out_specs=[at.key, at.key, at.value, at.key, at.scores, at.gamma],
        out_shape=[
            like(qd), like(w), like(uv), like(ke), like(p),
            jax.ShapeDtypeStruct(gamma.shape, f32),
        ],
        scratch_shapes=[pltpu.VMEM((at.dv, at.dk), f32)],
        compiler_params=_SEQUENTIAL_CHUNKS,
        interpret=interpret,
        name="kda_bwd_chunk",
    )(_rows(qd), _rows(w), _rows(uv), _rows(ke), _rows(p), gamma, states,
      _rows(do))
    return (
        dqd.reshape(qd.shape), dw.reshape(w.shape), duv.reshape(uv.shape),
        dke.reshape(ke.shape), dp.reshape(p.shape), dgam,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def chunk_scan(route, qd, w, uv, ke, p, gamma):
    """The chunk-to-chunk pass on `chunk_operands`' results: o
    [b, h, c, Q, dv] in their dtype, by the form `route` names ("kda" or
    "xla", `scan_route`). What the backward keeps is these operands."""
    if route == "xla":
        return _xla_forward(qd, w, uv, ke, p, gamma, False)
    return _pallas_forward(qd, w, uv, ke, p, gamma, _interpret())


def _chunk_scan_fwd(route, *operands):
    return chunk_scan(route, *operands), operands


def _chunk_scan_bwd(route, operands, do):
    if route == "xla":
        states = _xla_forward(*operands, True)
        return _xla_backward(*operands, states, do)
    states = _pallas_forward(*operands, _interpret(), True)
    return _pallas_backward(*operands, states, do, _interpret())


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)


def _interpret() -> bool:
    from flexflow_tpu.kernels import flash_attention as flash

    return flash.interpret_default()


def scan_route(key_dim: int, value_dim: int, chunk: int) -> str:
    """Which form `chunk_scan` takes, from what the trace can observe:

    - "kda": the Pallas kernels, where the backend is a TPU (or the CPU with
      interpret mode opted in, `interpret_default`), a head's key and value
      sizes are multiples of 128 lanes (the published 128 / 128) and its
      chunk of whole sublane tiles, and the trace admits a bare Pallas call
      (not under `no_flash()`, no declared `flash_mesh`: a sharded form as
      `kernels/ssm` has is not written yet, ROADMAP Reach (5));
    - "xla": everything else, the scan over the chunks."""
    from flexflow_tpu.kernels import flash_attention as flash

    if key_dim % _LANES or value_dim % _LANES or chunk % 16:
        return "xla"
    if flash.current_flash_mesh() is not None:
        return "xla"
    if getattr(flash._tls, "disabled", False):
        return "xla"
    return "kda" if flash._backend_ok(flash.interpret_default()) else "xla"


# ---------------------------------------------------------------------------
# the node
# ---------------------------------------------------------------------------


def _unit(t, scale: float):
    """t [.., d] over its own 2-norm, times `scale`, in float32 and back."""
    tf = t.astype(jnp.float32)
    root = lax.rsqrt(jnp.sum(tf * tf, axis=-1, keepdims=True) + L2_EPS)
    return (tf * (root * scale)).astype(t.dtype)


def _recurrence(attrs: GatedDeltaAttrs, route: str, qkv, f_up, dt_bias, a_log,
                b_logit):
    """qkv [b, s, 2*h*dk + h*dv] after the convolution, f_up [b, s, h*dk] the
    decay's pre-activation, b_logit [b, s, h] -> o [b, s, h*dv]."""
    f32 = jnp.float32
    b, s, _ = qkv.shape
    h, dk, dv, chunk = (
        attrs.num_heads, attrs.key_dim, attrs.value_dim, attrs.chunk_size
    )
    kw = attrs.key_width
    pad = -s % chunk

    def heads_first(t, width):
        # [b, s, h * width] -> [b, h, s + pad, width]; a padded position has
        # beta = 0 and g = 0: it writes nothing and decays nothing
        t = jnp.swapaxes(t.reshape(b, s, h, width), 1, 2)
        return jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else t

    with jax.named_scope("gates"):
        q = _unit(heads_first(qkv[..., :kw], dk), dk ** -0.5)
        k = _unit(heads_first(qkv[..., kw:2 * kw], dk), 1.0)
        v = heads_first(qkv[..., 2 * kw:], dv)
        rate = -jnp.exp(a_log.astype(f32))[:, None, None]
        g = rate * jax.nn.softplus(
            heads_first(f_up, dk).astype(f32)
            + dt_bias.astype(f32).reshape(h, 1, dk)
        )
        beta = jax.nn.sigmoid(heads_first(b_logit, 1).astype(f32))[..., 0]
    with jax.named_scope("prep"):
        operands = chunk_operands(q, k, v, g, beta, chunk)
    with jax.named_scope("scan"):
        o = chunk_scan(route, *operands)
    o = o.reshape(b, h, s + pad, dv)[:, :, :s]
    return jnp.swapaxes(o, 1, 2).reshape(b, s, h * dv)


def _head_norm_gate(o, gate_up, gate_bias, gain, heads: int, eps: float):
    """rms_norm of each head's dv features of o (gain [dv], shared by the
    heads) times sigmoid(gate_up + gate_bias), float32 inside, o's dtype
    out."""
    f32 = jnp.float32
    b, s, width = o.shape
    of = o.astype(f32).reshape(b, s, heads, width // heads)
    root = lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) + eps)
    normed = (of * root * gain.astype(f32)).reshape(b, s, width)
    gate = jax.nn.sigmoid(gate_up.astype(f32) + gate_bias.astype(f32))
    return (normed * gate).astype(o.dtype)


def gated_delta_forward(
    attrs: GatedDeltaAttrs, u: jnp.ndarray, weights: Sequence[jnp.ndarray]
) -> jnp.ndarray:
    """u [b, s, D] -> [b, s, D]; weights in `GatedDeltaAttrs` slot order."""
    w_in, w_conv, w_f, dt_bias, a_log, w_g, b_g, gain, w_out = weights
    cw, rank = attrs.conv_width, attrs.gate_rank
    proj = u @ w_in
    with jax.named_scope("conv"):
        qkv = conv_silu(proj[..., :cw], w_conv, None)
    with jax.named_scope("gates"):
        f_up = proj[..., cw:cw + rank] @ w_f
        g_up = proj[..., cw + rank:cw + 2 * rank] @ w_g
    route = scan_route(attrs.key_dim, attrs.value_dim, attrs.chunk_size)
    o = jax.checkpoint(functools.partial(_recurrence, attrs, route))(
        qkv, f_up, dt_bias, a_log, proj[..., cw + 2 * rank:]
    )
    with jax.named_scope("norm"):
        y = jax.checkpoint(
            functools.partial(
                _head_norm_gate, heads=attrs.num_heads, eps=attrs.norm_eps
            )
        )(o, g_up, b_g, gain)
    return y @ w_out
