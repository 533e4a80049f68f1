"""Ulysses (all-to-all) sequence-parallel attention kernel.

Schedule (DeepSpeed-Ulysses; see op_attrs/ops/ulysses_attention.py): each
device projects its local sequence block, all-to-alls heads-for-sequence so
it holds ALL positions for a head slice, attends the full sequence locally
(the tuned Pallas flash kernel applies directly; the ring schedule gets its
own flash path via kernels/ring_flash.py, whose kernels carry the online
softmax state across ring steps), and all-to-alls back before the output
projection. Composes with head (tensor) parallelism exactly like the ring:
weights head-sliced over the tp axes, output projection psummed across them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from flexflow_tpu.kernels import context
from flexflow_tpu.op_attrs.ops.ulysses_attention import UlyssesAttentionAttrs


def _attend_full_seq(qp, kp, vp, causal: bool, interpret: bool):
    """Attention on full-sequence per-head blocks [b, h, s, d]; flash when
    the local block qualifies, dense einsums otherwise."""
    from flexflow_tpu.kernels.flash_attention import (
        _flash_shape_ok,
        _min_seq_default,
        flash_attention,
    )

    b, h, s, d = qp.shape
    if (
        kp.shape == qp.shape == vp.shape
        and context.on_tpu(allow_interpret=interpret)
        and _flash_shape_ok(qp.shape, _min_seq_default())
    ):
        return flash_attention(qp, kp, vp, causal=causal, interpret=interpret)
    scale = 1.0 / np.sqrt(d)
    scores = (
        jnp.einsum(
            "bhsk,bhtk->bhst", qp, kp, preferred_element_type=jnp.float32
        )
        * scale
    )
    if causal:
        t = kp.shape[2]
        mask = jnp.arange(s)[:, None] >= jnp.arange(t)[None, :]
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
    attn = jax.nn.softmax(scores, axis=-1).astype(vp.dtype)
    return jnp.einsum(
        "bhst,bhtv->bhsv", attn, vp, preferred_element_type=jnp.float32
    ).astype(qp.dtype)


def ulysses_mha_shard_fn(
    attrs: UlyssesAttentionAttrs, axis_names, sp: int,
    head_axes=None, tp: int = 1, interpret: bool = False,
):
    from flexflow_tpu.kernels.ops import mha_project_qkv
    from flexflow_tpu.kernels.ring_attention import _local_attrs

    local = _local_attrs(attrs, tp)

    def a2a_seq_to_heads(x):
        # [b, h_loc, s_blk, d] -> [b, h_loc/sp, s, d]
        return lax.all_to_all(
            x, axis_names, split_axis=1, concat_axis=2, tiled=True
        )

    def a2a_heads_to_seq(x):
        # [b, h_loc/sp, s, d] -> [b, h_loc, s_blk, d]
        return lax.all_to_all(
            x, axis_names, split_axis=2, concat_axis=1, tiled=True
        )

    def fn(q_blk, k_blk, v_blk, weight, input_bias=None, output_bias=None):
        qp, kp, vp, wo = mha_project_qkv(
            local, q_blk, k_blk, v_blk, weight, input_bias
        )
        ctx = _attend_full_seq(
            a2a_seq_to_heads(qp),
            a2a_seq_to_heads(kp),
            a2a_seq_to_heads(vp),
            attrs.causal,
            interpret,
        )
        ctx = a2a_heads_to_seq(ctx)
        out = jnp.einsum("bhsv,veh->bse", ctx, wo)
        if tp > 1:
            out = lax.psum(out, head_axes)
        if output_bias is not None:
            out = out + output_bias
        return out

    return fn


def ulysses_mha_forward(
    attrs: UlyssesAttentionAttrs,
    q,
    k,
    v,
    weight,
    mesh,
    q_spec,
    w_spec=None,
    input_bias=None,
    output_bias=None,
):
    """Global-view entry for the all-to-all schedule (contract identical to
    ring_mha_forward; plumbing shared via seq_parallel_mha_forward)."""
    from flexflow_tpu.kernels.ring_attention import seq_parallel_mha_forward

    interpret = context.interpret_default()

    def factory(attrs_, axis_names, sp, head_axes, tp):
        assert (attrs_.num_heads // max(tp, 1)) % sp == 0, (
            f"{attrs_.num_heads // max(tp, 1)} local heads do not split "
            f"over sp={sp}"
        )
        return ulysses_mha_shard_fn(
            attrs_, axis_names, sp, head_axes, tp, interpret
        )

    return seq_parallel_mha_forward(
        factory, attrs, q, k, v, weight, mesh, q_spec,
        w_spec=w_spec, input_bias=input_bias, output_bias=output_bias,
    )
