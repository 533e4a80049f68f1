"""The state-space mixer's kernels (`StateSpaceAttrs`): the short causal
depthwise convolution, the selective scan in its chunked ("SSD") form, and
the gated grouped RMS norm.

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
a head), and only the [heads, P, N] states go from chunk to chunk, in a
sequential `lax.scan` over the s / Q chunks. Nothing is approximated: the
decays are exponentials of float32 differences of float32 running sums,
masked BEFORE the exponential (La_i - La_j is positive above the diagonal
and may overflow), and the states are float32. The matrix products take
operands in the input's dtype (bf16 in a bf16 step) and accumulate in
float32.

The backward pass is JAX's own transpose of this form under `jax.checkpoint`:
what is kept from the forward is the scan's INPUTS (x, dt, B, C: a few bytes a
feature and position); the chunk-boundary states and everything inside the
chunks are recomputed from them when the gradient is taken. No state per
position ever exists: the largest tensors are the [chunks, heads, Q, Q] decay
masks, alive in one layer's backward at a time.

The scan's device operations go under a `scan` scope inside the node's
(`ff.ssm.<name>/scan`), so a trace reader can tell it from the projections.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs


def causal_depthwise_conv(x, weight, bias):
    """x [b, s, f], weight [taps, f], bias [f]: y_t = bias + sum_k
    weight[k] * x_{t - (taps - 1) + k}, zeros before the first position (the
    causal conv1d of the model codes, `groups = channels`, `padding =
    taps - 1` cut back to s). Four shifted multiply-adds in float32."""
    taps = weight.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32)
    for k in range(taps):
        y = y + w[k] * lax.dynamic_slice_in_dim(padded, k, s, axis=1)
    return y.astype(x.dtype)


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """rms_norm(y * silu(z)) with the mean of squares over each of `groups`
    equal runs of the last dim, in float32; the result in y's dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return (g.reshape(shape) * gain.astype(jnp.float32)).astype(y.dtype)


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


def selective_scan(x, dt, a_log, b_mat, c_mat, d_skip, chunk: int):
    """The recurrence of the module docstring: x [b, s, h, p], dt [b, s, h]
    (float32, after softplus), a_log and d_skip [h], b_mat and c_mat
    [b, s, g, n] -> y [b, s, h, p] in x's dtype. A sequence that is no
    multiple of `chunk` is padded at its end with dt = 0 and x = 0, which
    changes no earlier position."""
    s = x.shape[1]
    pad = -s % chunk
    if pad:
        def padded(t):
            return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

        x_, dt_, b_, c_ = padded(x), padded(dt), padded(b_mat), padded(c_mat)
    else:
        x_, dt_, b_, c_ = x, dt, b_mat, c_mat
    with jax.named_scope("scan"):
        core = jax.checkpoint(_scan_core, static_argnums=(5,))
        y = core(x_, dt_, a_log, b_, c_, chunk)[:, :s]
        y = y + d_skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        return y.astype(x.dtype)


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
    xbc = zxbcdt[..., inner:inner + attrs.conv_width]
    dt = zxbcdt[..., inner + attrs.conv_width:]
    xbc = causal_depthwise_conv(xbc, w_conv, b_conv)
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(u.dtype)
    x = xbc[..., :inner].reshape(b, s, heads, p)
    b_mat = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
    c_mat = xbc[..., inner + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
    y = selective_scan(x, dt, a_log, b_mat, c_mat, d_skip, attrs.chunk_size)
    y = gated_group_norm(
        y.reshape(b, s, inner), z, gain, attrs.num_groups, attrs.norm_eps
    )
    return y @ w_out
