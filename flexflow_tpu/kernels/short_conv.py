"""The double-gated short-convolution mixer (`ShortConvAttrs`):

    B | C | z = x W_in;  g = B * z;  c = causal_depthwise_conv(g; w);
    y = (C * c) W_out

as XLA matmuls and one elementwise chain between them (scope `conv`: the
input gate, the taps, the output gate), with a written backward.

Why written: the chain's own transpose keeps what `kernels/ssm.conv_silu`'s
did before it was written (a padded float32 copy of the convolution's input
and one float32 gradient a tap, PR 41), and the projection's row
[tokens, 3 * width] besides, three times the node's input. Kept here: the
node's input x and its three weights, nothing else. The backward recomputes
the projection's row and the chain from them (one more input projection a
step, which `kernel_costs` of the benchmark does not count as least work),
rounds each gate's gradient to the compute dtype once, and takes the
weights' gradients as reductions over the same operands.

Every product of the chain is taken in float32 and rounded to x's dtype
where a tensor is written: g, c, the gated row, and their gradients.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.kernels.ssm import _conv_taps, _shifted
from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs


def _chain(row, w):
    """(B, C, z, g, c) of the projection's row [b, s, 3 * width]: the three
    parts, the gated input and its convolution, each in the row's dtype."""
    f32 = jnp.float32
    width = row.shape[-1] // 3
    b_, c_, z = (row[..., j * width:(j + 1) * width] for j in range(3))
    g = (b_.astype(f32) * z.astype(f32)).astype(row.dtype)
    return b_, c_, z, g, _conv_taps(g, w, None).astype(row.dtype)


@jax.custom_vjp
def gated_short_conv(x, w_in, w, w_out):
    """x [b, s, D], w_in [D, 3 * width], w [taps, width], w_out [width, D]
    -> [b, s, D] in x's dtype."""
    return _forward(x, w_in, w, w_out)[0]


def _forward(x, w_in, w, w_out):
    row = x @ w_in
    with jax.named_scope("conv"):
        _, c_, _, _, c = _chain(row, w)
        gated = (c_.astype(jnp.float32) * c.astype(jnp.float32)).astype(x.dtype)
    return gated @ w_out, (x, w_in, w, w_out)


def _backward(kept, dy):
    x, w_in, w, w_out = kept
    f32 = jnp.float32
    # the forward computes the same row: without the barrier XLA shares it,
    # which is the forward WRITING [tokens, 3 * width] for the backward
    x = lax.optimization_barrier(x)
    row = x @ w_in
    d_gated = dy @ w_out.T
    with jax.named_scope("conv"):
        b_, c_, z, g, c = _chain(row, w)
        gated = (c_.astype(f32) * c.astype(f32)).astype(x.dtype)
        d32 = d_gated.astype(f32)
        d_c = (d32 * c_.astype(f32)).astype(x.dtype)
        d_cgate = (d32 * c.astype(f32)).astype(x.dtype)
        d_g = _conv_taps(d_c, w, None, mirrored=True)
        d_c32 = d_c.astype(f32)
        d_w = jnp.stack(
            [jnp.sum(d_c32 * g_k, axis=(0, 1)) for g_k in _shifted(g, len(w))]
        )
        d_row = jnp.concatenate(
            [
                (d_g * z.astype(f32)).astype(x.dtype), d_cgate,
                (d_g * b_.astype(f32)).astype(x.dtype),
            ],
            axis=-1,
        )
    tokens = x.shape[0] * x.shape[1]
    d_w_out = gated.reshape(tokens, -1).T @ dy.reshape(tokens, -1)
    d_w_in = x.reshape(tokens, -1).T @ d_row.reshape(tokens, -1)
    return (
        d_row @ w_in.T, d_w_in.astype(w_in.dtype), d_w.astype(w.dtype),
        d_w_out.astype(w_out.dtype),
    )


gated_short_conv.defvjp(_forward, _backward)


def short_conv_forward(attrs: ShortConvAttrs, x, weights):
    w_in, w, w_out = weights
    assert w_in.shape[-1] == 3 * attrs.width and len(w) == attrs.conv_kernel, (
        w_in.shape, w.shape, attrs,
    )
    return gated_short_conv(x, w_in, w, w_out)
