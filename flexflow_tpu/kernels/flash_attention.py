"""Pallas (Mosaic) flash attention for TPU.

The TPU-native replacement for the reference's cuDNN MHA core
(lib/kernels/src/cuda/ops/attention_kernels.cu; SURVEY.md §2.4): blockwise
softmax attention that never materializes the [s, s] score matrix. Each grid
cell owns one (batch*head, q-block) tile held in VMEM; K/V blocks stream
through the MXU with an online (max, sum-exp, weighted-V) accumulator in f32.
The backward pass is the standard flash recomputation: forward saves only the
per-row logsumexp, backward rebuilds P blockwise to form dQ (one kernel) and
dK/dV (a second kernel, looping q-blocks per kv-block).

Layout notes (guide: /opt/skills/guides/pallas_guide.md): q blocks are
(block_q, d) with d the head dim (lane-dim aligned), lse/delta tiles are
(1, block_q) so the last dim stays 128-aligned; matmuls pass
preferred_element_type=f32 so bf16 inputs still accumulate in f32 on the MXU.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.kernels import context

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _exp2_probs(z, in_dtype):
    """exp2 of normalized (<= 0) f32 scores. bf16 kernel inputs compute
    bf16 probabilities — they feed a bf16 matmul anyway and the exp is the
    kernel's VPU bottleneck; ~0.4% relative error on values in (0, 1].
    Accumulators stay f32 either way."""
    if in_dtype == jnp.bfloat16:
        return jnp.exp2(z.astype(jnp.bfloat16))
    return jnp.exp2(z)


def _row_max(scores):
    """Row max over the LANE (minor) dim. Cross-lane reductions are the
    VPU's slow direction (the r4 finding that moved every rowSUM onto the
    MXU); max has no MXU contraction, but an elementwise maximum tree over
    128-wide lane slices leaves only a single 128-wide cross-lane max.
    (A [..., s//128, 128] reshape expresses the same fold, but Mosaic
    rejects that shape cast on matmul-output layouts.)"""
    s = scores.shape[-1]
    if s % 128 or s == 128:
        return scores.max(axis=-1)
    m = scores[..., 0:128]
    for j in range(1, s // 128):
        m = jnp.maximum(m, scores[..., j * 128:(j + 1) * 128])
    return m.max(axis=-1)


LOG2E = 1.4426950408889634  # log2(e): scores are scaled into the base-2
# domain so the online softmax uses exp2 — the TPU transcendental unit
# computes pow2 natively; exp costs an extra multiply per element, which is
# pure VPU overhead in a kernel whose non-matmul time is exp-dominated.
# lse is stored base-2 (m2 + log2 l); every consumer is in this module.


def _one_block_attn_3d(q, kb, vb, causal, row_offset, in_dtype):
    """Single-k-block attention body of the batched ([bb, bq, d]) forward
    kernel of heads of 128 (_fwd_kernel_b; the d=64 head pairs left it for
    _fwd_kernel_pair, at the end of this file, which runs at half its
    time): scores -> mask -> row max -> exp2 -> MXU rowsum ->
    o = (p@v)/l, plus the base-2 lse row. `q` arrives pre-scaled by
    scale*LOG2E (the scale folds into the [bb, bq, d] operand — a
    post-matmul scalar multiply is a full [bq, s] f32 VPU pass). The
    rowsum runs as p @ ones[s, 1]: the [bb, bq, 1] result divides acc
    directly (the [1, bb, bq] ones-on-the-left form needs a [0] squeeze
    whose layout cast Mosaic rejects outside a loop)."""
    block_q = q.shape[1]
    s = kb.shape[1]
    scores = jax.lax.dot_general(
        q, kb, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    if causal:
        rows = row_offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, s), 0
        )
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, s), 1)
        scores = jnp.where((rows >= cols)[None, :, :], scores, NEG_INF)
    m = _row_max(scores)
    p = _exp2_probs(scores - m[..., None], in_dtype)
    l = jax.lax.dot_general(
        p, jnp.ones((s, 1), p.dtype),
        (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc = jax.lax.dot_general(
        p.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return acc / l, m + jnp.log2(l[..., 0])


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k, scale, pid_axis=1
):
    # q_ref: [block_q, d]; k_ref/v_ref: [s, d]; o_ref: [block_q, d];
    # lse_ref: [1, block_q]
    qi = pl.program_id(pid_axis)
    block_q, d = q_ref.shape
    s = k_ref.shape[0]
    nk = s // block_k
    scale2 = scale * LOG2E  # base-2 domain (see LOG2E note)
    # scale folded into the [block_q, d] operand: a post-matmul scalar
    # multiply is a full [block_q, s] f32 VPU pass per k block
    q = q_ref[:] * jnp.asarray(scale2, q_ref.dtype)

    if nk == 1:
        # single k block: no online carry (see _fwd_kernel_b)
        kb = k_ref[:]
        vb = v_ref[:]
        scores = (
            jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, s), 0
            )
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, s), 1)
            scores = jnp.where(rows >= cols, scores, NEG_INF)
        m = _row_max(scores)
        p = _exp2_probs(scores - m[:, None], q_ref.dtype)
        # rowsum as p @ ones[s, 1] (see _fwd_kernel_b)
        l = jax.lax.dot_general(
            p, jnp.ones((s, 1), p.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[:] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, :] = m + jnp.log2(l[:, 0])
        return

    acc = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        kb = k_ref[pl.ds(j * block_k, block_k), :]
        vb = v_ref[pl.ds(j * block_k, block_k), :]
        scores = (
            jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            scores = jnp.where(rows >= cols, scores, NEG_INF)
        m_new = jnp.maximum(m, _row_max(scores))
        p = _exp2_probs(scores - m_new[:, None], q_ref.dtype)
        alpha = jnp.exp2(m - m_new)
        # rowsum(p) on the MXU (see _fwd_kernel_b)
        psum = jax.lax.dot_general(
            jnp.ones((1, p.shape[-1]), p.dtype), p,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )[0]
        l = l * alpha + psum
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l

    # causal: only kv blocks touching rows <= (qi+1)*block_q - 1 contribute
    # (block_q and block_k may differ)
    bound = (
        jnp.minimum(pl.cdiv((qi + 1) * block_q, block_k), nk) if causal else nk
    )
    acc, m, l = jax.lax.fori_loop(0, bound, body, (acc, m, l))
    o_ref[:] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, :] = m + jnp.log2(l)  # base-2 lse


def _fwd(q, k, v, causal, block_q, block_k, interpret=False, scale=None):
    bh, s, d = q.shape
    nq = s // block_q
    scale = 1.0 / (d**0.5) if scale is None else scale
    bb = _batch_block(bh, block_q, block_k, s, d, q.dtype.itemsize)
    if bb > 1:
        # batch-fold BB (batch*head) rows per program: at d=64 (the
        # reference heads=16 config) the one-row-per-program grid pays
        # ~25k kernel launches per step; the folded grid reuses the
        # batched bshf kernel on the [bh, s, d] layout (a block whose
        # minor dim EQUALS the array's d is legal at any d)
        kernel = functools.partial(
            _fwd_kernel_b, causal=causal, block_k=block_k, scale=scale,
            pid_axis=1,
        )
        o, lse = pl.pallas_call(
            kernel,
            name="flash_fwd_rows_folded",
            interpret=interpret,
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")
            ),
            grid=(bh // bb, nq),
            in_specs=[
                pl.BlockSpec((bb, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((bb, s, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((bb, s, d), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bb, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((bb, 1, block_q), lambda b, i: (b, 0, i)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
            ],
        )(q, k, v)
        return o, lse.reshape(bh, s)
    kernel = functools.partial(
        _fwd_kernel, causal=causal, block_k=block_k, scale=scale
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd_rows",
        interpret=interpret,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
    )(q, k, v)
    return o, lse.reshape(bh, s)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, causal, block_k, scale, pid_axis=1,
):
    qi = pl.program_id(pid_axis)
    block_q, d = q_ref.shape
    s = k_ref.shape[0]
    nk = s // block_k
    scale2 = scale * LOG2E
    # scale folded into the [block_q, d] q operand (see _fwd_kernel)
    q = q_ref[:] * jnp.asarray(scale2, q_ref.dtype)
    do = do_ref[:]
    lse = lse_ref[0, :]  # base-2 (see _fwd_kernel)
    delta = delta_ref[0, :]

    def body(j, dq):
        kb = k_ref[pl.ds(j * block_k, block_k), :]
        vb = v_ref[pl.ds(j * block_k, block_k), :]
        scores = (
            jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            scores = jnp.where(rows >= cols, scores, NEG_INF)
        p = jnp.exp2(scores - lse[:, None])
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # scale folds into the [block_k, d] operand, not an [q, k] pass
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds.astype(kb.dtype), kb * jnp.asarray(scale, kb.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    bound = (
        jnp.minimum(pl.cdiv((qi + 1) * block_q, block_k), nk) if causal else nk
    )
    dq = jax.lax.fori_loop(0, bound, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, causal, block_q, scale, pid_axis=1,
):
    ki = pl.program_id(pid_axis)
    block_k, d = k_ref.shape
    s = q_ref.shape[0]
    nq = s // block_q
    scale2 = scale * LOG2E
    kb = k_ref[:]
    vb = v_ref[:]

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[pl.ds(i * block_q, block_q), :]
        dob = do_ref[pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q)]  # base-2
        delta = delta_ref[0, pl.ds(i * block_q, block_q)]
        scores = (
            jax.lax.dot_general(
                qb * jnp.asarray(scale2, qb.dtype), kb,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        )
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            scores = jnp.where(rows >= cols, scores, NEG_INF)
        p = jnp.exp2(scores - lse[:, None])
        dv = dv + jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        # scale folds into the [block_q, d] operand, not an [q, k] pass
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(
            ds.astype(qb.dtype), qb * jnp.asarray(scale, qb.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    start = ki * block_k // block_q if causal else 0
    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, nq, body, (dk, dv))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _delta_rows(do, o, interpret=False):
    """delta[bh, 1, s] = rowsum(do * o) for the [bh, s, d] layout, via the
    same VMEM-tiled kernel as the bshf path."""
    bh, s, d = do.shape
    bb = _delta_fold_cap(bh, s, d, do.dtype.itemsize)
    return pl.pallas_call(
        _delta_kernel,
        name="flash_delta_rows",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        grid=(bh // bb,),
        in_specs=[
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, 1, s), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
    )(do, o)


def _bwd_rows_fused(q, k, v, o, lse, do, causal, interpret=False, scale=None):
    """Batch-folded fused backward for the [bh, s, d] layout (s == block):
    the d=64 reference config otherwise pays one kernel launch per
    (batch, head) row."""
    bh, s, d = q.shape
    scale = 1.0 / (d**0.5) if scale is None else scale
    lse3 = lse.reshape(bh, 1, s)
    delta3 = _delta_rows(do, o, interpret)
    bb = _batch_block(bh, s, s, s, d, q.dtype.itemsize, fused_bwd=True)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel_b, causal=causal, scale=scale),
        name="flash_bwd_fused_rows",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        grid=(bh // bb,),
        in_specs=[
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, 1, s), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, 1, s), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((bb, s, d), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv


def _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret=False, scale=None):
    bh, s, d = q.shape
    if s <= block_q and s <= block_k:
        return _bwd_rows_fused(q, k, v, o, lse, do, causal, interpret, scale)
    nq = s // block_q
    nk = s // block_k
    scale = 1.0 / (d**0.5) if scale is None else scale
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse3 = lse.reshape(bh, 1, s)
    delta3 = delta.reshape(bh, 1, s)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, block_k=block_k, scale=scale
        ),
        name="flash_bwd_dq_rows",
        interpret=interpret,
        grid=(bh, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, s, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((None, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
    )(q, k, v, do, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, block_q=block_q, scale=scale
        ),
        name="flash_bwd_dkv_rows",
        interpret=interpret,
        grid=(bh, nk),
        in_specs=[
            pl.BlockSpec((None, s, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, s, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, s), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, 1, s), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom VJP)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, scale=None):
    o, _ = _fwd(q, k, v, causal, block_q, block_k, interpret, scale)
    return o


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, scale=None):
    o, lse = _fwd(q, k, v, causal, block_q, block_k, interpret, scale)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, block_q, block_k, interpret, scale, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, causal, block_q, block_k, interpret, scale)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _clamp_block(block: int, s: int) -> int:
    """Largest power-of-two-halving of `block` that divides s (any gated
    s is a multiple of 128, so this terminates at or above 128)."""
    blk = min(block, s)
    while s % blk != 0:
        blk //= 2
    return blk


def flash_attention(
    q, k, v, *, causal: bool = False, block_q: int = None, block_k: int = None,
    interpret: bool = False, scale: float = None,
):
    """Blockwise attention on [b, h, s, d] per-head tensors (`scale`: the
    scores' multiplier where it is not d ** -0.5).

    Requires s divisible by the block sizes; callers gate on
    flash_attention_supported(). Default blocks are 1024 (clamped to s,
    overridable via FLEXFLOW_TPU_FLASH_BLOCK_Q/K): measured on the bench
    chip, 1024x1024 runs the s=2048 forward in ~2.4ms vs 12.5ms at 128x128
    (and 4.7ms for XLA's fused dense attention) — small q-tiles leave the
    MXU idle between K/V streams.
    """
    b, h, s, d = q.shape
    dq0, dk0 = _default_blocks()
    bq = _clamp_block(block_q if block_q is not None else dq0, s)
    bk = _clamp_block(block_k if block_k is not None else dk0, s)
    assert s % bq == 0 and s % bk == 0 and bq >= 1, (
        f"seq {s} must divide into blocks ({bq}, {bk}); "
        "gate callers on flash_attention_supported"
    )
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)
    o = _flash(qf, kf, vf, causal, bq, bk, interpret, scale)
    return o.reshape(b, h, s, d)


# ---------------------------------------------------------------------------
# [b, s, h*d] (seq-major, heads fused into the minor dim) layout variant
# ---------------------------------------------------------------------------
#
# With this layout the QKV projections are PLAIN MATMULS
# ([b,s,e] @ [e, h*d] -> [b,s,h*d]) whose natural output layout matches the
# custom call's operand layout exactly, and the output projection is again a
# plain matmul ([b,s,h*d] @ [h*d, e]). With the [b,h,s,d] entry the profiler
# shows ~14 ms/step of pure layout-copy ops on the headline bench; this
# variant removes them. Per-head blocks are carved out of the minor dim at
# offset head*d (block sizes stay (block_q, d), kernels unchanged).


def _delta_fold_cap(rows: int, s: int, width: int, itemsize: int) -> int:
    """Batch fold for the delta kernels: the per-row VMEM residency is two
    double-buffered input blocks plus the f32 product tile, within an 8 MB
    budget (shared by all three delta variants so the constants cannot
    drift apart)."""
    per_row = s * width * (4 * itemsize + 4)
    bb = max(1, (8 * 1024 * 1024) // per_row)
    bb = min(bb, rows)
    while rows % bb != 0:
        bb -= 1
    return bb


def _batch_block(
    b: int, block_q: int, block_k: int, s: int, d: int, itemsize: int,
    fused_bwd: bool = False, bwd_blocks: int = 7,
) -> int:
    """Batch rows folded into ONE kernel program (bshf path).

    At [512, 64]-shaped per-head tiles a program's compute is sub-µs while
    its fixed launch cost is ~2.5µs: folding BB batch rows per program
    divides the launch count by BB. The budget keeps the per-program VMEM
    residency in bounds where the [s, s] tiles dominate it (K/V blocks of
    the full local sequence, q/out/acc tiles: all scale with BB), and
    _MAX_FOLD bounds BB where they do not (short sequences; see there).
    Override via FLEXFLOW_TPU_FLASH_BATCH_BLOCK (1 = the old
    one-row-per-program grid).
    """
    import os

    env = os.environ.get("FLEXFLOW_TPU_FLASH_BATCH_BLOCK")
    if env is not None:
        bb = int(env)
    elif fused_bwd:
        # _bwd_fused_kernel_b holds ~3 f32 [s, s] tiles (scores, p/ds, dp)
        # and bwd_blocks [s, d] blocks per batch row (7 = q/k/v/do in +
        # dq/dk/dv out; the pair backwards stream o too and pass 8)
        budget = 16 * 1024 * 1024
        score = 3 * block_q * block_k * 4
        resident = bwd_blocks * s * d * itemsize
        bb = min(_MAX_FOLD, max(1, budget // max(1, score + resident)))
    else:
        budget = 12 * 1024 * 1024  # VMEM bytes per program
        score = 2 * block_q * block_k * 4  # f32 scores + exp tile
        resident = (2 * s + 2 * block_q) * d * itemsize  # k+v, q+out
        acc = block_q * d * 4
        bb = min(_MAX_FOLD, max(1, budget // max(1, score + resident + acc)))
    bb = min(bb, b)
    while b % bb != 0:
        bb -= 1
    return max(bb, 1)


def _fwd_kernel_b(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k, scale,
    pid_axis=2,
):
    """Batch-blocked _fwd_kernel: refs carry a leading batch dim; matmuls
    run batched on the MXU; one program serves BB batch rows."""
    qi = pl.program_id(pid_axis)
    bb, block_q, d = q_ref.shape
    s = k_ref.shape[1]
    nk = s // block_k
    scale2 = scale * LOG2E
    # scale folded into the [bb, block_q, d] operand (see _fwd_kernel)
    q = q_ref[:] * jnp.asarray(scale2, q_ref.dtype)

    if nk == 1:
        # single k block (s <= block_k): no online carry — the alpha
        # rescale and running max/sum are pure VPU overhead when there is
        # nothing to carry across
        o, lse = _one_block_attn_3d(
            q, k_ref[:], v_ref[:], causal, qi * block_q, q_ref.dtype
        )
        o_ref[:] = o.astype(o_ref.dtype)
        lse_ref[:, 0, :] = lse
        return

    acc = jnp.zeros((bb, block_q, d), jnp.float32)
    m = jnp.full((bb, block_q), NEG_INF, jnp.float32)
    l = jnp.zeros((bb, block_q), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        kb = k_ref[:, pl.ds(j * block_k, block_k), :]
        vb = v_ref[:, pl.ds(j * block_k, block_k), :]
        scores = (
            jax.lax.dot_general(
                q, kb, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
        )
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            scores = jnp.where(
                (rows >= cols)[None, :, :], scores, NEG_INF
            )
        m_new = jnp.maximum(m, _row_max(scores))
        p = _exp2_probs(scores - m_new[..., None], q_ref.dtype)
        alpha = jnp.exp2(m - m_new)
        # rowsum(p) as an MXU contraction against ones: a cross-LANE
        # reduction on the VPU is the slow direction (same trick as the
        # delta kernels)
        psum = jax.lax.dot_general(
            jnp.ones((1, p.shape[-1]), p.dtype), p,
            (((1,), (2,)), ((), ())),
            preferred_element_type=jnp.float32,
        )[0]
        l = l * alpha + psum
        acc = acc * alpha[..., None] + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l

    bound = (
        jnp.minimum(pl.cdiv((qi + 1) * block_q, block_k), nk) if causal else nk
    )
    acc, m, l = jax.lax.fori_loop(0, bound, body, (acc, m, l))
    o_ref[:] = (acc / l[..., None]).astype(o_ref.dtype)
    lse_ref[:, 0, :] = m + jnp.log2(l)


def _fwd_pair_call(
    name, operands, b, s, f, h, causal, block_q, block_k, interpret, dtype,
    qkv_index_maps, scale=None,
):
    """Shared pallas_call of the head-pair forwards: `operands` are the q/k/v
    arrays (three distinct, or the same fused-QKV array thrice),
    qkv_index_maps their minor-block index maps and `name` the kernel's
    name in the device trace."""
    d = f // h
    assert 2 * d == 128 and h % 2 == 0, (d, h)
    nq = s // block_q
    scale = 1.0 / (d**0.5) if scale is None else scale
    bb = _batch_block(b, block_q, block_k, s, 128, dtype.itemsize)
    kernel = functools.partial(
        _pair_fwd_kernel(s, block_q, block_k), causal=causal, scale=scale,
        d=d,
    )
    q_map, k_map, v_map = qkv_index_maps
    o, lse = pl.pallas_call(
        kernel,
        name=name,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        grid=(b // bb, h // 2, nq),
        in_specs=[
            pl.BlockSpec((bb, block_q, 128), q_map),
            pl.BlockSpec((bb, s, 128), k_map),
            pl.BlockSpec((bb, s, 128), v_map),
        ],
        out_specs=[
            pl.BlockSpec((bb, block_q, 128), lambda bi, hp, i: (bi, i, hp)),
            pl.BlockSpec(
                (bb, 2, 1, block_q), lambda bi, hp, i: (bi, hp, 0, i)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, f), dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
    )(*operands)
    return o, lse


def _fwd_bshf_pair(q, k, v, h, causal, block_q, block_k, interpret=False,
                   scale=None):
    """d=64 entry: blocks hold a PAIR of heads (128 lanes) — see
    _fwd_kernel_pair."""
    b, s, f = q.shape
    return _fwd_pair_call(
        "flash_fwd_pair", (q, k, v), b, s, f, h, causal, block_q, block_k,
        interpret, q.dtype,
        (
            lambda bi, hp, i: (bi, i, hp),
            lambda bi, hp, i: (bi, 0, hp),
            lambda bi, hp, i: (bi, 0, hp),
        ), scale,
    )


def _bwd_bshf_pair_fused(q, k, v, o, lse, do, h, causal, interpret=False,
                         scale=None):
    b, s, f = q.shape
    d = f // h
    scale = 1.0 / (d**0.5) if scale is None else scale
    bb = _batch_block(
        b, s, s, s, 128, q.dtype.itemsize, fused_bwd=True, bwd_blocks=8,
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel_pair, causal=causal, scale=scale, d=d
        ),
        name="flash_bwd_fused_pair",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        grid=(b // bb, h // 2),
        in_specs=[
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, 2, 1, s), lambda bi, hp: (bi, hp, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, f), q.dtype),
            jax.ShapeDtypeStruct((b, s, f), k.dtype),
            jax.ShapeDtypeStruct((b, s, f), v.dtype),
        ],
    )(q, k, v, o, do, lse)
    return dq, dk, dv


def _fwd_bshf_pair_qkv(qkv, h, causal, block_q, block_k, interpret=False,
                       scale=None):
    """Fused-QKV head-pair forward: qkv is ONE interleaved [b, s, 3f]
    array, laid out per pair-group hp as 384 lanes of
    [q_pair(128) | k_pair(128) | v_pair(128)]. The kernel is the ordinary
    _fwd_kernel_pair — the three operands are just three BlockSpec views
    into the same array, so a single projection matmul feeds flash with
    no slicing copy."""
    b, s, f3 = qkv.shape
    return _fwd_pair_call(
        "flash_fwd_pair_qkv", (qkv, qkv, qkv), b, s, f3 // 3, h, causal,
        block_q, block_k, interpret, qkv.dtype,
        (
            lambda bi, hp, i: (bi, i, 3 * hp),
            lambda bi, hp, i: (bi, 0, 3 * hp + 1),
            lambda bi, hp, i: (bi, 0, 3 * hp + 2),
        ), scale,
    )


def _bwd_bshf_pair_fused_qkv(qkv, o, lse, do, h, causal, interpret=False,
                             scale=None):
    b, s, f3 = qkv.shape
    f = f3 // 3
    d = f // h
    scale = 1.0 / (d**0.5) if scale is None else scale
    bb = _batch_block(
        b, s, s, s, 128, qkv.dtype.itemsize, fused_bwd=True, bwd_blocks=8,
    )
    return pl.pallas_call(
        functools.partial(
            _bwd_fused_kernel_pair_qkv, causal=causal, scale=scale, d=d
        ),
        name="flash_bwd_fused_pair_qkv",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        grid=(b // bb, h // 2),
        in_specs=[
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, 3 * hp)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, 3 * hp + 1)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, 3 * hp + 2)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, s, 128), lambda bi, hp: (bi, 0, hp)),
            pl.BlockSpec((bb, 2, 1, s), lambda bi, hp: (bi, hp, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, s, 384), lambda bi, hp: (bi, 0, hp)),
        out_shape=jax.ShapeDtypeStruct((b, s, f3), qkv.dtype),
    )(qkv, qkv, qkv, o, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _flash_bshf_qkv(qkv, h, causal, block_q, block_k, interpret, scale=None):
    o, _ = _fwd_bshf_pair_qkv(qkv, h, causal, block_q, block_k, interpret, scale)
    return o


def _flash_bshf_qkv_fwd(qkv, h, causal, block_q, block_k, interpret, scale=None):
    o, lse = _fwd_bshf_pair_qkv(qkv, h, causal, block_q, block_k, interpret, scale)
    return o, (qkv, o, lse)


def _flash_bshf_qkv_bwd(h, causal, block_q, block_k, interpret, scale, res, do):
    qkv, o, lse = res
    s = qkv.shape[1]
    # pair mode ships the fused single-tile backward only; the entry gate
    # restricts shapes to s <= block
    assert s <= block_q and s <= block_k, (s, block_q, block_k)
    return (
        _bwd_bshf_pair_fused_qkv(qkv, o, lse, do, h, causal, interpret, scale),
    )


_flash_bshf_qkv.defvjp(_flash_bshf_qkv_fwd, _flash_bshf_qkv_bwd)


def flash_attention_bshf_qkv(
    qkv, num_heads: int, *, causal: bool = False, interpret: bool = False,
    scale: float = None,
):
    """Head-pair (d=64) flash attention on ONE interleaved [b, s, 3*f]
    projection array (per pair-group: [q_pair | k_pair | v_pair], 384
    lanes). One fused projection matmul feeds this entry and one fused
    dqkv gradient flows back — no per-operand slicing or concat in either
    direction. Callers gate on bshf_pair_supported(). Returns [b, s, f]."""
    b, s, f3 = qkv.shape
    assert f3 % 3 == 0 and (f3 // 3) % num_heads == 0
    d = f3 // 3 // num_heads
    dq0, dk0 = _default_blocks()
    bq = _clamp_block(dq0, s)
    bk = _clamp_block(dk0, s)
    assert 2 * d == 128 and num_heads % 2 == 0 and s <= bq and s <= bk, (
        d, num_heads, s, bq, bk,
    )
    return _flash_bshf_qkv(qkv, num_heads, causal, bq, bk, interpret, scale)


def _fwd_bshf(q, k, v, h, causal, block_q, block_k, interpret=False,
              scale=None):
    b, s, f = q.shape
    d = f // h
    if d % 128 != 0:
        return _fwd_bshf_pair(
            q, k, v, h, causal, block_q, block_k, interpret, scale
        )
    nq = s // block_q
    scale = 1.0 / (d**0.5) if scale is None else scale
    bb = _batch_block(b, block_q, block_k, s, d, q.dtype.itemsize)
    kernel = functools.partial(
        _fwd_kernel_b, causal=causal, block_k=block_k, scale=scale,
        pid_axis=2,
    )
    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd_bshf",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        grid=(b // bb, h, nq),
        in_specs=[
            pl.BlockSpec((bb, block_q, d), lambda bi, hi, i: (bi, i, hi)),
            pl.BlockSpec((bb, s, d), lambda bi, hi, i: (bi, 0, hi)),
            pl.BlockSpec((bb, s, d), lambda bi, hi, i: (bi, 0, hi)),
        ],
        out_specs=[
            pl.BlockSpec((bb, block_q, d), lambda bi, hi, i: (bi, i, hi)),
            pl.BlockSpec(
                (bb, None, 1, block_q), lambda bi, hi, i: (bi, hi, 0, i)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, f), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
    )(q, k, v)
    return o, lse


def _bwd_fused_kernel_b(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    *, causal, scale,
):
    """Batch-blocked _bwd_fused_kernel (see _fwd_kernel_b)."""
    bb, s, d = q_ref.shape
    scale2 = scale * LOG2E
    q = q_ref[:]
    kb = k_ref[:]
    vb = v_ref[:]
    do = do_ref[:]
    lse = lse_ref[:, 0, :]  # base-2
    delta = delta_ref[:, 0, :]
    # scale folded into the [bb, s, d] operand (see _fwd_kernel); plain q
    # stays for the dk contraction below
    scores = (
        jax.lax.dot_general(
            q * jnp.asarray(scale2, q.dtype), kb,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
    )
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
        scores = jnp.where((rows >= cols)[None, :, :], scores, NEG_INF)
    p = _exp2_probs(scores - lse[..., None], q_ref.dtype)
    pb = p.astype(do.dtype)
    dv_ref[:] = jax.lax.dot_general(
        pb, do, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, vb, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    # ds = p * (dp - delta) * scale, minimizing [s, s]-sized VPU passes:
    # the dp-delta difference casts to the probs dtype before the multiply
    # (same precision policy as _exp2_probs), and the 1/sqrt(d) scale folds
    # into the [s, d] matmul operands instead of an [s, s] pass
    if p.dtype == jnp.float32:
        ds = (p * (dp - delta[..., None])).astype(kb.dtype)
    else:
        ds = p * (dp - delta[..., None]).astype(p.dtype)
    dq_ref[:] = jax.lax.dot_general(
        ds, kb * jnp.asarray(scale, kb.dtype),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).astype(dq_ref.dtype)
    dk_ref[:] = jax.lax.dot_general(
        ds, q * jnp.asarray(scale, q.dtype),
        (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ).astype(dk_ref.dtype)


def _bwd_pair_core(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, outs, causal, scale, d,
):
    """Shared body of the head-pair fused backwards (see _fwd_kernel_pair).

    delta (rowsum of do*o per half) is computed INLINE as an MXU
    contraction against a ones column — do and o are already resident in
    VMEM here, so a separate delta launch (one more full HBM pass over do
    and o per step) is saved. The [d, 1] ones-on-the-right form yields
    [bb, s, 1] directly, broadcastable against dp without the squeeze
    whose layout cast Mosaic rejects.

    outs: ((dq_ref, off), (dk_ref, off), (dv_ref, off)) — three separate
    refs at offset 0, or the fused-QKV variant's single interleaved ref at
    offsets 0/128/256."""
    bb, s, _ = q_ref.shape
    scale2 = scale * LOG2E
    (dq_ref, dq_off), (dk_ref, dk_off), (dv_ref, dv_off) = outs
    for h2 in range(2):
        sl = pl.ds(h2 * d, d)
        q = q_ref[:, :, sl]
        kb = k_ref[:, :, sl]
        vb = v_ref[:, :, sl]
        do = do_ref[:, :, sl]
        lse = lse_ref[:, h2, 0, :]
        if do_ref.dtype == jnp.float32:
            prod = do.astype(jnp.float32) * o_ref[:, :, sl].astype(jnp.float32)
        else:
            prod = do * o_ref[:, :, sl]
        delta_col = jax.lax.dot_general(
            prod, jnp.ones((d, 1), prod.dtype),
            (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bb, s, 1]
        # scale folded into the [bb, s, d] half (see _bwd_fused_kernel_b)
        scores = (
            jax.lax.dot_general(
                q * jnp.asarray(scale2, q.dtype), kb,
                (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
        )
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
            scores = jnp.where((rows >= cols)[None], scores, NEG_INF)
        p = _exp2_probs(scores - lse[..., None], q_ref.dtype)
        pb = p.astype(do.dtype)
        dv_ref[:, :, pl.ds(dv_off + h2 * d, d)] = jax.lax.dot_general(
            pb, do, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(
            do, vb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        # see _bwd_fused_kernel_b: minimize [s, s] VPU passes, fold scale
        # into the [s, d] operands
        if p.dtype == jnp.float32:
            ds = (p * (dp - delta_col)).astype(kb.dtype)
        else:
            ds = p * (dp - delta_col).astype(p.dtype)
        dq_ref[:, :, pl.ds(dq_off + h2 * d, d)] = jax.lax.dot_general(
            ds, kb * jnp.asarray(scale, kb.dtype),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).astype(dq_ref.dtype)
        dk_ref[:, :, pl.ds(dk_off + h2 * d, d)] = jax.lax.dot_general(
            ds, q * jnp.asarray(scale, q.dtype),
            (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ).astype(dk_ref.dtype)


def _bwd_fused_kernel_pair(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
    dq_ref, dk_ref, dv_ref, *, causal, scale, d,
):
    _bwd_pair_core(
        q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
        ((dq_ref, 0), (dk_ref, 0), (dv_ref, 0)), causal, scale, d,
    )


def _bwd_fused_kernel_pair_qkv(
    qkv_q_ref, qkv_k_ref, qkv_v_ref, o_ref, do_ref, lse_ref,
    dqkv_ref, *, causal, scale, d,
):
    """Fused-QKV head-pair backward: the three 128-lane q/k/v views come
    from the SAME interleaved [b, s, 3f] array and the three gradients
    land in ONE contiguous [bb, s, 384] block — no concat, no extra HBM
    pass (see flash_attention_bshf_qkv)."""
    _bwd_pair_core(
        qkv_q_ref, qkv_k_ref, qkv_v_ref, o_ref, do_ref, lse_ref,
        ((dqkv_ref, 0), (dqkv_ref, 128), (dqkv_ref, 256)), causal, scale, d,
    )


def _delta_kernel(do_ref, o_ref, delta_ref):
    # do/o: [bb, s, d] per-head slices; delta: [bb, 1, s]. Product in the
    # storage dtype, accumulation in f32 (same policy as _exp2_probs). The
    # rowsum runs as an MXU contraction against a ones vector — cross-LANE
    # reductions on the VPU dominated this kernel.
    d = do_ref.shape[-1]
    if do_ref.dtype == jnp.float32:
        prod = do_ref[:].astype(jnp.float32) * o_ref[:].astype(jnp.float32)
    else:
        prod = do_ref[:] * o_ref[:]
    ones = jnp.ones((1, d), prod.dtype)
    res = jax.lax.dot_general(
        ones, prod, (((1,), (2,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [1, bb, s]
    delta_ref[:, 0, :] = res[0]


def _delta_bshf(do, o, b, s, h, d, interpret=False, block=None,
                name="flash_delta_bshf"):
    """delta[b,h,1,s] = sum_d do*o per head, in the (1, block) lse tiling.

    A Pallas kernel instead of the XLA multiply+reduce: the XLA version
    materialized the full [b,s,h*d] f32 product in a layout inherited from
    the flash custom call's operands and then paid a layout-normalizing
    copy per layer (~0.9 ms/layer of pure HBM traffic on the headline
    bench); here the product lives only in VMEM tiles. The fold cap
    budgets this kernel's own residency: two [bb, s, d] input blocks,
    double-buffered by the pipeline (the 16 MB scoped-VMEM limit trips at
    seq 2048 otherwise). With `block`, a tile of that many positions of one
    batch row a program: the whole-row blocks ask for 48 MB of VMEM at
    8,192 positions of 256 columns (`CausalPlan.delta_block`)."""
    if block is None:
        bb = _delta_fold_cap(b, s, d, do.dtype.itemsize)
        grid = (b // bb, h)
        tile = pl.BlockSpec((bb, s, d), lambda bi, hi: (bi, 0, hi))
        stat = pl.BlockSpec((bb, None, 1, s), lambda bi, hi: (bi, hi, 0, 0))
    else:
        grid = (b, h, s // block)
        tile = pl.BlockSpec((1, block, d), lambda bi, hi, j: (bi, j, hi))
        stat = pl.BlockSpec(
            (1, None, 1, block), lambda bi, hi, j: (bi, hi, 0, j)
        )
    return pl.pallas_call(
        _delta_kernel,
        name=name,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid)
        ),
        grid=grid,
        in_specs=[tile, tile],
        out_specs=stat,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
    )(do, o)


def _bwd_bshf_fused(q, k, v, o, lse, do, h, causal, interpret=False,
                    scale=None):
    """Fused single-block backward for the bshf layout (s == block)."""
    b, s, f = q.shape
    d = f // h
    scale = 1.0 / (d**0.5) if scale is None else scale
    delta4 = _delta_bshf(do, o, b, s, h, d, interpret)
    bb = _batch_block(b, s, s, s, d, q.dtype.itemsize, fused_bwd=True)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel_b, causal=causal, scale=scale),
        name="flash_bwd_fused_bshf",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        grid=(b // bb, h),
        in_specs=[
            pl.BlockSpec((bb, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((bb, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((bb, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((bb, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((bb, None, 1, s), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((bb, None, 1, s), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((bb, s, d), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((bb, s, d), lambda bi, hi: (bi, 0, hi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, f), q.dtype),
            jax.ShapeDtypeStruct((b, s, f), k.dtype),
            jax.ShapeDtypeStruct((b, s, f), v.dtype),
        ],
    )(q, k, v, do, lse, delta4)
    return dq, dk, dv


def _bwd_onepass_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dkp_ref, dvp_ref, acc_ref, *, scale, nk,
):
    """One-pass tiled backward: dq, dk and dv from a SINGLE (q-block,
    k-block) tile visit — 5 matmuls per tile where the dq/dkv kernel pair
    pays 7 (both recompute scores and dp). dq accumulates in an f32 VMEM
    scratch across the innermost k grid dim; dk/dv are written as
    per-q-block partials reduced by the caller (nq is small — the fused
    single-tile kernel owns the s <= block case). Non-causal only: every
    tile of its grid is live. Causal calls take _bwd_causal_kernel, which
    also visits a tile once and skips the dead ones (PR 29)."""
    ki = pl.program_id(3)
    block_q, d = q_ref.shape
    scale2 = scale * LOG2E
    q = q_ref[:]
    kb = k_ref[:]
    vb = v_ref[:]
    do = do_ref[:]
    lse = lse_ref[0, :]
    delta = delta_ref[0, :]
    scores = jax.lax.dot_general(
        q * jnp.asarray(scale2, q.dtype), kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    p = _exp2_probs(scores - lse[:, None], q_ref.dtype)
    dvp_ref[:] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dvp_ref.dtype)
    dp = jax.lax.dot_general(
        do, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if p.dtype == jnp.float32:
        ds = p * (dp - delta[:, None])
    else:
        ds = p * (dp - delta[:, None]).astype(p.dtype)
    dkp_ref[:] = jax.lax.dot_general(
        ds.astype(q.dtype), q * jnp.asarray(scale, q.dtype),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dkp_ref.dtype)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        ds.astype(kb.dtype), kb * jnp.asarray(scale, kb.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _fin():
        dq_ref[:] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_bshf_onepass(q, k, v, o, lse, do, h, causal, block_q, block_k,
                      interpret=False, scale=None):
    assert not causal
    b, s, f = q.shape
    d = f // h
    nq = s // block_q
    nk = s // block_k
    scale = 1.0 / (d**0.5) if scale is None else scale
    delta4 = _delta_bshf(do, o, b, s, h, d, interpret)
    dq, dkp, dvp = pl.pallas_call(
        functools.partial(_bwd_onepass_kernel, scale=scale, nk=nk),
        name="flash_bwd_onepass_bshf",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")
        ),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bi, hi, i, j: (bi, i, hi)),
            pl.BlockSpec((None, block_k, d), lambda bi, hi, i, j: (bi, j, hi)),
            pl.BlockSpec((None, block_k, d), lambda bi, hi, i, j: (bi, j, hi)),
            pl.BlockSpec((None, block_q, d), lambda bi, hi, i, j: (bi, i, hi)),
            pl.BlockSpec(
                (None, None, 1, block_q), lambda bi, hi, i, j: (bi, hi, 0, i)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q), lambda bi, hi, i, j: (bi, hi, 0, i)
            ),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda bi, hi, i, j: (bi, i, hi)),
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, i, j: (i, bi, j, hi)
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, i, j: (i, bi, j, hi)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, f), q.dtype),
            jax.ShapeDtypeStruct((nq, b, s, f), k.dtype),
            jax.ShapeDtypeStruct((nq, b, s, f), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(q, k, v, do, lse, delta4)
    dk = dkp.astype(jnp.float32).sum(axis=0).astype(k.dtype)
    dv = dvp.astype(jnp.float32).sum(axis=0).astype(v.dtype)
    return dq, dk, dv


def _bwd_bshf(q, k, v, o, lse, do, h, causal, block_q, block_k,
              interpret=False, scale=None):
    b, s, f = q.shape
    d = f // h
    nq = s // block_q
    nk = s // block_k
    scale = 1.0 / (d**0.5) if scale is None else scale
    delta4 = _delta_bshf(do, o, b, s, h, d, interpret)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, block_k=block_k, scale=scale,
            pid_axis=2,
        ),
        name="flash_bwd_dq_bshf",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        grid=(b, h, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bi, hi, i: (bi, i, hi)),
            pl.BlockSpec((None, s, d), lambda bi, hi, i: (bi, 0, hi)),
            pl.BlockSpec((None, s, d), lambda bi, hi, i: (bi, 0, hi)),
            pl.BlockSpec((None, block_q, d), lambda bi, hi, i: (bi, i, hi)),
            pl.BlockSpec((None, None, 1, block_q), lambda bi, hi, i: (bi, hi, 0, i)),
            pl.BlockSpec((None, None, 1, block_q), lambda bi, hi, i: (bi, hi, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda bi, hi, i: (bi, i, hi)),
        out_shape=jax.ShapeDtypeStruct((b, s, f), q.dtype),
    )(q, k, v, do, lse, delta4)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, block_q=block_q, scale=scale,
            pid_axis=2,
        ),
        name="flash_bwd_dkv_bshf",
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        grid=(b, h, nk),
        in_specs=[
            pl.BlockSpec((None, s, d), lambda bi, hi, j: (bi, 0, hi)),
            pl.BlockSpec((None, block_k, d), lambda bi, hi, j: (bi, j, hi)),
            pl.BlockSpec((None, block_k, d), lambda bi, hi, j: (bi, j, hi)),
            pl.BlockSpec((None, s, d), lambda bi, hi, j: (bi, 0, hi)),
            pl.BlockSpec((None, None, 1, s), lambda bi, hi, j: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, 1, s), lambda bi, hi, j: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda bi, hi, j: (bi, j, hi)),
            pl.BlockSpec((None, block_k, d), lambda bi, hi, j: (bi, j, hi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, f), k.dtype),
            jax.ShapeDtypeStruct((b, s, f), v.dtype),
        ],
    )(q, k, v, do, lse, delta4)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_bshf(q, k, v, h, causal, block_q, block_k, interpret,
                explicit=False, scale=None):
    o, _ = _fwd_bshf(q, k, v, h, causal, block_q, block_k, interpret, scale)
    return o


def _flash_bshf_fwd(q, k, v, h, causal, block_q, block_k, interpret,
                    explicit=False, scale=None):
    o, lse = _fwd_bshf(q, k, v, h, causal, block_q, block_k, interpret, scale)
    return o, (q, k, v, o, lse)


def _flash_bshf_bwd(h, causal, block_q, block_k, interpret, explicit, scale,
                    res, do):
    q, k, v, o, lse = res
    s = q.shape[1]
    d = q.shape[2] // h
    if d % 128 != 0:
        # pair mode only ships the fused single-tile backward; the entry
        # gate restricts pair shapes to s <= block
        assert s <= block_q and s <= block_k, (s, block_q, block_k)
        return _bwd_bshf_pair_fused(
            q, k, v, o, lse, do, h, causal, interpret, scale
        )
    if s <= block_q and s <= block_k:
        # whole sequence in one tile: one fused kernel instead of two
        # (single scores/exp computation, q/k/v/do read once)
        return _bwd_bshf_fused(q, k, v, o, lse, do, h, causal, interpret, scale)
    # backward tiles get their own block budget (unless the caller passed
    # explicit blocks): the dq/dkv kernels hold more live tiles than the
    # forward, so the forward-optimal blocks (e.g. K = full seq at 2048,
    # riding the single-block fast path) blow the 16 MB scoped-VMEM limit
    # in the backward
    bwd_bq, bwd_bk = _bwd_blocks(block_q, block_k, s, explicit)
    if not causal and s // bwd_bq <= 2:
        # one-pass dq+dk+dv (5 matmuls/tile vs the 7 the kernel pair
        # pays); its dk/dv partials cost nq extra gradient-sized HBM
        # buffers, so large nq keeps the constant-memory kernel pair
        return _bwd_bshf_onepass(
            q, k, v, o, lse, do, h, causal, bwd_bq, bwd_bk, interpret, scale
        )
    return _bwd_bshf(
        q, k, v, o, lse, do, h, causal, bwd_bq, bwd_bk, interpret, scale
    )


_flash_bshf.defvjp(_flash_bshf_fwd, _flash_bshf_bwd)


def _bwd_blocks(
    block_q: int, block_k: int, s: int, explicit: bool, causal: bool = False
) -> Tuple[int, int]:
    """Backward-pass block sizes: explicit caller blocks verbatim, else
    _CAUSAL_BLOCK squared for a causal call (measured there) and (2048, 512)
    otherwise: the non-causal one-pass backward at batch 4, seq 2048, 16
    heads of 128 takes 2.20 ms a call at (2048, 512) against 2.61 at
    (1024, 1024) (my chip run, PR 29; the MFU pair this note used to quote
    is in no record). Its scores tile (bq*bk*4B) stays within scoped VMEM
    for any s at this shape."""
    if not explicit:
        block_q, block_k = (
            (_CAUSAL_BLOCK, _CAUSAL_BLOCK) if causal else (2048, 512)
        )
    return _clamp_block(block_q, s), _clamp_block(block_k, s)


def _default_blocks() -> Tuple[int, int]:
    """Benchmark-tunable default block sizes (FLEXFLOW_TPU_FLASH_BLOCK_Q/K).
    Applied by every flash entry (per-head, bshf, sharded)."""
    import os

    out = []
    for var in ("FLEXFLOW_TPU_FLASH_BLOCK_Q", "FLEXFLOW_TPU_FLASH_BLOCK_K"):
        val = int(os.environ.get(var, "1024"))
        # power of two: _clamp_block halves until the block divides seq, so
        # e.g. 768 would silently degrade to a 1-row block
        if val <= 0 or (val & (val - 1)) != 0:
            raise ValueError(
                f"{var} must be a positive power-of-two block size, got {val}"
            )
        out.append(val)
    return out[0], out[1]


def _bshf_blocks(s: int, d: int, causal: bool, block_q, block_k):
    """(block_q, block_k, explicit) of a bshf call on `s` positions of
    `d`-wide heads: the caller's blocks where it names them (`explicit`),
    else the env's, else the measured ones of the d % 128 kernels."""
    import os

    dq0, dk0 = _default_blocks()
    bq = _clamp_block(block_q if block_q is not None else dq0, s)
    bk = _clamp_block(block_k if block_k is not None else dk0, s)
    explicit = block_q is not None or block_k is not None
    # explicit caller blocks and the env sweep knobs opt out of both rules
    swept = explicit or "FLEXFLOW_TPU_FLASH_BLOCK_Q" in os.environ or (
        "FLEXFLOW_TPU_FLASH_BLOCK_K" in os.environ)
    if not swept and d % 128 == 0 and causal:
        bq = bk = _clamp_block(_CAUSAL_BLOCK, s)  # the causal tile schedule
    elif not swept and d % 128 == 0 and s <= 2048:
        # non-causal: one K tile as long as the sequence, no online softmax:
        # 1.37 against 1.48 ms at 1024 x 1024 (b 4, s 2048; my chip run, PR 29)
        bk = s
        if s == 2048:
            bq = min(bq, 256)  # scores tile bq*s*4B within scoped VMEM
    assert s % bq == 0 and s % bk == 0 and bq >= 1, (
        f"seq {s} must divide into blocks ({bq}, {bk}); "
        "gate callers on flash_attention_supported"
    )
    return bq, bk, explicit


def flash_attention_bshf(
    q, k, v, num_heads: int, *, causal: bool = False,
    block_q: int = None, block_k: int = None, interpret: bool = False,
    num_kv_heads: int = None, scale: float = None, window: int = None,
):
    """Blockwise attention on [b, s, num_heads*d] seq-major tensors.

    Same kernels as flash_attention, blocked so plain-matmul QKV projections
    feed the custom call without a layout copy. Returns [b, s, num_heads*d].

    Under a causal mask over more than one tile, heads of whole 128-lane
    tiles run the causal tile schedule as `causal_plan` lays it out, and
    there alone the key may be wider than the value (q, k
    [b, s, num_heads * dk], v [b, s, num_heads * dv]) and k and v hold the node's own `num_kv_heads` heads, read in place by
    the query heads that share them (`CausalPlan.group`: where the key is as
    wide as the value; a caller whose pairing is no `h // group` writes them
    out a query head and names no `num_kv_heads`).
    `window` (keys a query sees, itself included) is honoured there alone,
    as a band in the tile schedule; anywhere else it is an error, not a
    silent full attention. `scale` is the scores' multiplier where it is
    not dk ** -0.5 (a key padded with zero columns names its TRUE width's; a
    node states its own, `MultiHeadAttentionAttrs.softmax_scale`): every
    body takes it. -> [b, s, num_heads * dv]."""
    b, s, f = q.shape
    kv = num_heads if num_kv_heads is None else num_kv_heads
    assert f % num_heads == 0 and v.shape[-1] % kv == 0
    d, dv = f // num_heads, v.shape[-1] // kv
    bq, bk, explicit = _bshf_blocks(s, d, causal, block_q, block_k)
    if causal and d % 128 == 0 and dv % 128 == 0 and s > min(bq, bk):
        # more than one tile: skip the dead ones
        plan = causal_plan(
            b, s, num_heads, kv, d, dv, q.dtype.itemsize, block_q, block_k,
            window,
        )
        assert num_heads == kv * plan.group, (num_heads, kv, plan.group)
        assert k.shape == (b, s, kv * d) and v.shape == (b, s, kv * dv), (
            q.shape, k.shape, v.shape
        )
        return _flash_causal(q, k, v, num_heads, plan, interpret, scale)
    if window is not None and window < s:
        raise ValueError(
            f"a window of {window} keys over {s} positions needs the causal "
            "tile schedule (a causal mask over more than one tile of heads "
            f"of whole 128-lane tiles); these operands (causal={causal}, "
            f"d={d} | {dv}) take another body, which has no band"
        )
    assert q.shape == k.shape == v.shape and kv == num_heads, (
        f"flash_attention_bshf is self-attention-shaped: {q.shape} vs "
        f"{k.shape} / {v.shape} (the K/V BlockSpecs use q's seq length)"
    )
    if d % 128 != 0:
        # head-pair mode (d=64): fused-backward only — callers gate on
        # bshf_pair_supported
        assert 2 * d == 128 and num_heads % 2 == 0 and s <= bq and s <= bk, (
            d, num_heads, s, bq, bk,
        )
    return _flash_bshf(
        q, k, v, num_heads, causal, bq, bk, interpret, explicit, scale
    )


def bshf_pair_supported(num_heads: int, d: int, s: int) -> bool:
    """Can the d=64 head-pair bshf path run these shapes? (s must fit one
    block: the pair backward ships only the fused single-tile kernel.)"""
    bq, bk = _default_blocks()
    return (
        2 * d == 128
        and num_heads % 2 == 0
        and s <= _clamp_block(bq, s)
        and s <= _clamp_block(bk, s)
    )


def _min_seq_default() -> int:
    """Least (local) sequence length of a kernel route that no measurement
    has placed: MIN_SEQ_UNMEASURED, 512 (overridable for benchmarking and
    tests via FLEXFLOW_TPU_FLASH_MIN_SEQ). The ring and all-to-all sequence-
    parallel kernels read it as their least local block; the routes of
    `ops.mha_core_route` read `min_seq_for`, whose table (at the end of this
    file) holds what was measured, route by route."""
    return min_seq_for()


def _flash_shape_ok(shape: Tuple[int, ...], min_seq: int) -> bool:
    b, h, s, d = shape
    return b >= 1 and h >= 1 and s % 128 == 0 and s >= min_seq and d % 8 == 0


def flash_attention_supported(
    q_shape: Tuple[int, ...], k_shape, v_shape, min_seq: int = None
) -> bool:
    """Static gate: TPU backend, self-attention-shaped, block-aligned, and
    at least `min_seq` long: the length from which the caller's route beats
    XLA's dense attention, which keeps the [b, h, s, s] probabilities in
    HBM for the backward (`min_seq_for`; _min_seq_default for a caller that
    names no route)."""
    if context.bare_calls_refused() or not context.on_tpu():
        return False
    if len(q_shape) != 4:
        return False
    if min_seq is None:
        min_seq = _min_seq_default()
    return (
        k_shape == q_shape
        and v_shape == q_shape
        and _flash_shape_ok(q_shape, min_seq)
    )


# ---------------------------------------------------------------------------
# SPMD composition: shard_map wrapper
# ---------------------------------------------------------------------------


def _axes_size(mesh, axes) -> int:
    """Total device count of a PartitionSpec entry (None | name | tuple)."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def sharded_flash_supported(
    q_shape: Tuple[int, ...],
    mesh,
    batch_axes,
    head_axes,
    min_seq: int = None,
    interpret: bool = False,
) -> bool:
    """Can flash run per-device under shard_map, with the batch dim sharded
    over `batch_axes` and heads over `head_axes`? Gates on the LOCAL block
    shape each device will see (SURVEY.md §7 hard-part 4: pallas_call has no
    SPMD partitioning rule, so the kernel must be mapped per-shard)."""
    if not context.on_tpu(allow_interpret=interpret):
        return False
    if len(q_shape) != 4:
        return False
    b, h, s, d = q_shape
    db = _axes_size(mesh, batch_axes)
    dh = _axes_size(mesh, head_axes)
    if b % db != 0 or h % dh != 0:
        return False
    if min_seq is None:
        min_seq = _min_seq_default()
    return _flash_shape_ok((b // db, h // dh, s, d), min_seq)


def flash_core_supported(q_shape, k_shape, v_shape, family=None) -> bool:
    """The static gate of the trace a kernel of `family` (`min_seq_for`)
    would be emitted into: flash_attention_supported on the [b, h, s, d]
    shapes, or under a `flash_mesh` on the block each device sees."""
    least = min_seq_for(family)
    ctx = context.declared_mesh()
    if ctx is None:
        return flash_attention_supported(q_shape, k_shape, v_shape, least)
    mesh, batch_axes, head_axes, interpret = ctx
    return k_shape == q_shape == v_shape and sharded_flash_supported(
        q_shape, mesh, batch_axes, head_axes, least, interpret=interpret
    )


def per_batch_shard(entry, *rows, **kwargs):
    """entry(*rows, **kwargs) on [b, s, f] fused-row operands, the way the
    current trace admits it: called as it is with no mesh declared; under a
    `flash_mesh` whose heads are whole, mapped over the batch shards with
    the mesh's interpret flag, so that each device runs the one-chip kernel
    on its own sequences. Attention is independent over the batch: the body
    needs no collective."""
    ctx = context.declared_mesh()
    if ctx is None:
        return entry(*rows, **kwargs)
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.utils.shard_map_compat import shard_map_compat

    mesh, batch_axes, head_axes, interpret = ctx
    assert head_axes is None, "a fused row has no head dimension to shard"
    spec = P(batch_axes, None, None)
    f = functools.partial(entry, interpret=interpret, **kwargs)
    return shard_map_compat(f, mesh, (spec,) * len(rows), spec)(*rows)


def sharded_flash_attention(
    q, k, v, mesh, batch_axes, head_axes, *,
    causal: bool = False, interpret: bool = False, scale: float = None,
):
    """Flash attention composed with SPMD sharding: each device runs the
    Pallas kernel on its local [b/dp, h/tp, s, d] block. Attention is
    embarrassingly parallel over batch and heads, so the body needs no
    collectives; shard_map reshards inputs to the declared specs if the
    producing computation laid them out differently."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.utils.shard_map_compat import shard_map_compat

    spec = P(batch_axes, head_axes, None, None)
    f = functools.partial(
        flash_attention, causal=causal, interpret=interpret, scale=scale
    )
    # replication (vma) checking can't see through a pallas_call's out_shape;
    # the body is elementwise-parallel over b/h so the specs are exact
    wrapped = shard_map_compat(
        f, mesh, (spec, spec, spec), spec
    )
    return wrapped(q, k, v)


# ---------------------------------------------------------------------------
# the causal tile schedule (d % 128 == 0 bshf calls of more than one tile)
# ---------------------------------------------------------------------------
#
# Half of a causal call's (q block, k block) tiles hold no pair the mask
# keeps. These kernels visit the live ones only, mask only those on the
# diagonal, and the backward visits each once. They live at the end of the
# file, behind flash_attention_bshf's rule, so that nothing the other entry
# points lower moves (the serialized kernel bodies carry line numbers).

# Block edge, forward and backward, when neither the caller nor the env
# names one. Measured at batch 4, 16 heads of 128, bf16, a call (forward /
# backward with its delta kernel; my chip runs, PR 29): seq 2048, 512 x 512
# 1.27 / 1.78 ms, 1024 x 1024 1.40 / 1.94, 256 x 256 1.43 / 2.58, against
# 1.45 / 3.20 for the kernels this replaces (single k block; dq + dkv at
# (2048, 512)); seq 4096, 3.45 / 5.41, 3.71 / 5.53, 4.15 / 8.74 against
# 3.84 / 8.90. Unequal edges (256 or 1024 by 512) are no better.
_CAUSAL_BLOCK = 512
# flash_bwd_causal_bshf keeps q, do, dq and the f32 dq accumulator resident
# as whole [s, d] rows (2 KB a position at d = 128, bf16) next to its
# [block_k, block_q] f32 tiles; v5e has 128 MiB of VMEM, 16 of it scoped to
# a kernel by default.
_CAUSAL_VMEM_LIMIT = 64 * 1024 * 1024
# The kernels keep a (batch, head)'s keys and values resident as whole rows,
# double-buffered: 2 * s * (dk + dv) * itemsize bytes in the forward. That is
# 8 MB at 8,192 positions of 128 bf16 columns and 16 MB, the whole default
# scope, at heads of 256 (PR 51); a padded key row [s, 256] beside a value row
# [s, 128] at 8,192 positions is 12 MB, exactly this budget, and the forward
# does not compile inside the default scope there (a described-chip compile of
# the latent node at [1, 8192, 2048], 32 heads of 192 | 128: "Scoped allocation
# with size 48.08M and limit 48.00M exceeded", PR 53). From the budget on the
# forward takes one batch row a program and asks for the room the backward
# always asked for (`_CAUSAL_VMEM_LIMIT`); `causal_plan` is where it is read.
_SCOPED_ROWS_BUDGET = 12 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class CausalPlan:
    """What the causal tile schedule's three `pallas_call`s (`_fwd_causal`,
    `_bwd_causal`, `_delta_bshf`) are built from, beside the operands: a
    value of static facts (`causal_plan`), not a setting. With `window` the
    schedule is a BAND: query t sees keys t - window + 1 .. t, the tiles
    wholly under the band are visited by neither pass, and the forward and
    backward carry names of their own (`*_window`); without one every field
    and every kernel is what it was before there was a band."""

    # s is whole `_CAUSAL_BLOCK`s and more than one tile: what a caller asks
    # before it promises a key wider than its value, a scale or padded heads,
    # which have no other body
    supported: bool
    block_q: int
    block_k: int
    bwd_block_q: int
    bwd_block_k: int
    fold: int  # batch rows a forward program takes (`_batch_block`, or 1)
    vmem_limit: Optional[int]  # the forward's; the backward always names one
    # query heads that read one key/value head WHERE IT LIES in
    # [b, s, kv * d] rows (the backward writes a query head's own dk and dv
    # and they are summed over the group after): heads over key/value heads
    # wherever the key is as wide as the value; 1: k and v hold a head a
    # query head
    group: int
    delta_block: Optional[int]  # positions a delta program takes; None: rows
    fwd_name: str
    bwd_name: str
    delta_name: str
    # keys a query sees, itself included (a sliding window); None: all before
    window: Optional[int] = None


def causal_plan(
    b: int, s: int, num_heads: int, num_kv_heads: int, dk: int, dv: int,
    itemsize: int, block_q: int = None, block_k: int = None,
    window: int = None,
) -> CausalPlan:
    """The plan of a causal call on `s` positions of `num_heads` heads with
    `dk`-wide keys and `dv`-wide values (multiples of 128 lanes), the node's
    k and v holding `num_kv_heads` heads. Two independent facts. The ROWS:
    those that fit the default scope fold batch rows under it, with a
    whole-row delta; longer ones take one row a program under
    `_CAUSAL_VMEM_LIMIT`, and with dk == dv a delta kernel by tiles under
    the grouped kernels' names (heads of 256 with a key/value head a query
    head too: a group of 1); a wide key keeps the folded form's backward and
    whole-row delta. Each is what its shapes ran with before there was a
    plan (PR 29, 43, 51, 53). The GROUP: a key as wide as its value is read
    in place by the `num_heads // num_kv_heads` query heads that share it,
    at every row length, folded rows included; a wide key (a latent node,
    which has a key/value head a query head anyway) keeps a group of 1.
    `window` (keys a query sees, itself included) makes the schedule a band:
    the same blocks, fold and delta, the forward and backward under
    `<name>_window`; a window of `s` or more is no window."""
    assert dk % 128 == 0 and dv % 128 == 0, (dk, dv)
    assert window is None or window >= 1, window
    if window is not None and window >= s:
        window = None
    assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
    bq, bk, explicit = _bshf_blocks(s, dk, True, block_q, block_k)
    bwd_bq, bwd_bk = _bwd_blocks(bq, bk, s, explicit, causal=True)
    tiled = s > min(bq, bk)
    long_rows = tiled and 2 * s * (dk + dv) * itemsize >= _SCOPED_ROWS_BUDGET
    grouped = long_rows and dk == dv  # the long rows' delta and names
    banded = "" if window is None else "_window"
    return CausalPlan(
        supported=tiled and s % _CAUSAL_BLOCK == 0,
        block_q=bq, block_k=bk, bwd_block_q=bwd_bq, bwd_block_k=bwd_bk,
        fold=1 if long_rows else _batch_block(b, bq, bk, s, dk, itemsize),
        vmem_limit=_CAUSAL_VMEM_LIMIT if long_rows else None,
        group=num_heads // num_kv_heads if tiled and dk == dv else 1,
        delta_block=bwd_bq if grouped else None,
        fwd_name=(
            "flash_fwd_causal_grouped" if grouped
            else "flash_fwd_causal_wide_key" if long_rows
            else "flash_fwd_causal_bshf"
        ) + banded,
        bwd_name=(
            "flash_bwd_causal_grouped" if grouped else "flash_bwd_causal_bshf"
        ) + banded,
        delta_name="flash_delta_grouped" if grouped else "flash_delta_bshf",
        window=window,
    )


def _causal_k_range(qi, block_q, block_k, window=None):
    """(full, live) for q block `qi` under the causal mask: k blocks
    [0, full) lie wholly on or under the diagonal and take no mask,
    [full, live) straddle it, and those from `live` on are dead (every
    column after the block's last row). Plain integer arithmetic: `qi` is a
    Python int (causal_tile_schedule) or a kernel's program id.

    With `window` (query t sees keys t - window + 1 .. t) the answer is
    (dead_before, masked_low, full, live): k blocks before `dead_before`
    hold no key the block's FIRST query still sees and are not visited,
    [dead_before, masked_low) straddle the band's lower edge (some pair is
    `window` or more apart: the block's LAST query no longer sees the
    block's first key) and take the mask, and `full` and `live` are the
    causal ones. A window shorter than a tile puts `masked_low` past
    `full`: every visited block is then masked."""
    full, live = (
        (qi * block_q + 1) // block_k, pl.cdiv((qi + 1) * block_q, block_k)
    )
    if window is None:
        return full, live
    first, last = qi * block_q, (qi + 1) * block_q - 1
    dead_before = _at_least_zero(first - window + 1) // block_k
    # block j is straddled when its first key j * block_k < last - window + 1
    masked_low = pl.cdiv(_at_least_zero(last - window + 1), block_k)
    return dead_before, masked_low, full, live


def _causal_q_range(ki, block_q, block_k, window=None, s=None):
    """The transpose of _causal_k_range, (start, full) for k block `ki`:
    q blocks before `start` are dead, [start, full) straddle the diagonal,
    and those from `full` on take no mask.

    With `window`, (start, full, masked_high, end) over the `s` positions:
    q blocks from `masked_high` on straddle the band's lower edge (their
    last query no longer sees the block's first key) and take the mask, and
    those from `end` on see none of the block's keys and are not visited."""
    start, full = (
        (ki * block_k) // block_q, pl.cdiv((ki + 1) * block_k - 1, block_q)
    )
    if window is None:
        return start, full
    first, last = ki * block_k, (ki + 1) * block_k - 1
    blocks = s // block_q
    # q block i is straddled when its last query (i + 1) * block_q - 1 >=
    # first + window, and dead when its first query i * block_q >=
    # last + window
    masked_high = _at_most(blocks, (first + window) // block_q)
    end = _at_most(blocks, pl.cdiv(last + window, block_q))
    return start, full, masked_high, end


def _larger(a, b):
    """max of two block indices: Python ints (`causal_tile_schedule`) or a
    kernel's traced values."""
    both = isinstance(a, int) and isinstance(b, int)
    return max(a, b) if both else jnp.maximum(a, b)


def _smaller(a, b):
    both = isinstance(a, int) and isinstance(b, int)
    return min(a, b) if both else jnp.minimum(a, b)


def _at_least_zero(x):
    return _larger(x, 0)


def _at_most(cap: int, x):
    return _smaller(x, cap)


def _banded_loops(lo, unmasked_from, masked_from, hi):
    """The three runs of a banded pass over blocks [lo, hi): masked up to
    `unmasked_from`, unmasked up to `masked_from`, masked to the end; the
    unmasked run is empty where the two edges' masked runs meet or cross
    (a window shorter than a tile). ((start, stop, masked), ...)."""
    first_stop = _smaller(_larger(unmasked_from, lo), hi)
    second_stop = _smaller(_larger(masked_from, first_stop), hi)
    return (
        (lo, first_stop, True), (first_stop, second_stop, False),
        (second_stop, hi, True),
    )


def causal_tile_schedule(s: int, block_q: int, block_k: int, window=None):
    """(live, diagonal, total) (q block, k block) tiles of a causal call:
    the tiles the kernels visit, those of them that are masked, and the
    grid a non-causal call visits. (2048, 512, 512) -> (10, 4, 16). The
    kernels take their loop bounds from the same two range functions. With
    `window` the visited tiles are the band's (the tiles wholly under it
    are not counted) and the masked ones those on either of its edges:
    (4096, 512, 512, window=512) -> (15, 15, 64), against (36, 8, 64)
    without."""
    blocks = range(s // block_q)
    total = (s // block_q) * (s // block_k)
    if window is None:
        ranges = [_causal_k_range(qi, block_q, block_k) for qi in blocks]
        live = sum(hi for _, hi in ranges)
        diagonal = live - sum(full for full, _ in ranges)
        return live, diagonal, total
    live = masked = 0
    for qi in blocks:
        dead, low, full, hi = _causal_k_range(qi, block_q, block_k, window)
        for start, stop, is_masked in _banded_loops(dead, low, full, hi):
            live += stop - start
            masked += (stop - start) * is_masked
    return live, masked, total


def _causal_mask(scores, q0, k0, q_axis, window=None):
    """NEG_INF where a key comes after its query (or, with `window`, lies
    `window` or more positions before it): `scores` is a [..., n, m]
    tile whose `q_axis` (-2 or -1) runs over queries from position q0 and
    whose other minor axis runs over keys from k0."""
    n, m = scores.shape[-2:]
    q_ids = q0 + jax.lax.broadcasted_iota(jnp.int32, (n, m), 2 + q_axis)
    k_ids = k0 + jax.lax.broadcasted_iota(jnp.int32, (n, m), -1 - q_axis)
    keep = q_ids >= k_ids
    if window is not None:
        keep = keep & (q_ids - k_ids < window)
    return jnp.where(keep[(None,) * (scores.ndim - 2)], scores, NEG_INF)


# The causal forward holds a tile's scores TRANSPOSED, [block_k, block_q], as
# _fwd_kernel_pair and the causal backward hold theirs: the maximum and the
# sums run down the sublanes, so m, alpha, l and lse are [1, block_q] rows
# along the lanes, where lse_ref stores them, and go back over a tile as
# sublane broadcasts; p is the stationary operand of v^T @ p and the
# accumulator is [dv, block_q], turned once a q block. Forward alone at the
# eight cells' call shapes, bf16, device time from a trace (6 calls a form in
# one process; my chip runs, PR 56), ms a call and (us a live tile):
#   b x s, heads, dk | dv, rows a program   [queries, keys]   transposed
#   1 x 8192, 32, 256 | 128, 1 (64 MB)      7.042 (1.618)     6.167 (1.417)
#   1 x 4096, 32, 256 | 128, 1              2.088 (1.812)     1.701 (1.477)
#   4 x 2048, 16, 128 | 128, 2              1.122 (1.754)     0.814 (1.272)
#   4 x 4096, 16, 128 | 128, 2              3.293 (1.429)     2.700 (1.172)
#   2 x 8192, 32, 128 | 128, 1             10.968 (1.260)    10.750 (1.235)
#   1 x 8192, 16 over 2, 256 | 256, 1       4.531 (2.082)     4.136 (1.901)
#   1 x 4096, 32, 128 | 128, 1              1.670 (1.450)     1.489 (1.293)
#   1 x 4096, 4, 128 | 128, 1               0.212 (1.473)     0.189 (1.315)
# "[queries, keys]" was this body until PR 56: a cross-lane maximum a tile,
# m_new and alpha turned from lanes to sublanes to be broadcast, the sums
# carried as 128 lane partials. What it lost was mostly AROUND the k loop,
# once a q block (the fold of the partials, the accumulator's quotient by a
# lane-major l, the carry's spills): 1.8 us a q block against 0.56, while a
# tile inside the loop costs about what it did (fitted from the pairs of
# lengths above: 1.40 -> 1.35 us at 256 | 128, 1.05 -> 1.17 at 128 | 128 and
# one row a program, 1.02 -> 1.05 at two): the maximum down the sublanes
# needs the WHOLE tile before the first exponential (0.22-0.26 us a tile,
# timed by leaving it out), where the old body's rows were independent
# chains woven through both matmuls. So the exponentials, the sums and
# v^T @ p are taken _FWD_KEY_CHUNK keys at a time once the tile's maximum is
# known: a chunk's matmul runs beside the next chunk's exponentials. One
# chunk of 512: 6.530 / 0.832 / 11.336 ms a call on the first, third and
# fifth shape above (slower than the old body on the fifth); chunks of 128,
# in another call: 6.49 / 0.823 / 11.38. Not kept: two k tiles an iteration
# with both score products first (6.875 / 0.876 / 11.195: slower on the chip
# though 7-16% SHORTER by the compiler's bundle count); the q block in two
# chunks of queries beside the key chunks (6.054 / 0.813 / 10.83: within 2%
# either way, for a carry a chunk); a tree for the maximum and the sums
# (within 2% either way).
_FWD_KEY_CHUNK = 256


def _fwd_causal_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, scale, window=None,
):
    """The online softmax of a q block on the causal tile schedule: k blocks
    wholly under the diagonal run the unmasked body, the (at most
    cdiv(block_q, block_k)) blocks on it the masked one, and dead blocks
    are outside both loops (_causal_k_range). Scores [bb, block_k, block_q]
    float32 (see above), f32 maximum, sums and accumulator, _exp2_probs, MXU
    operands in the inputs' dtype, base-2 lse. The keys may be wider than
    the values (a padded latent key): the accumulator is as wide as a
    value."""
    qi = pl.program_id(2)
    bb, block_q, _ = q_ref.shape
    d = v_ref.shape[-1]
    chunk = math.gcd(_FWD_KEY_CHUNK, block_k)
    scale2 = scale * LOG2E
    # scale folded into the [bb, block_q, d] operand (see _fwd_kernel)
    q = q_ref[:] * jnp.asarray(scale2, q_ref.dtype)

    def body(j, carry, masked=False):
        acc, m, l = carry
        kb = k_ref[:, pl.ds(j * block_k, block_k), :]
        scores = jax.lax.dot_general(
            kb, q, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if masked:
            scores = _causal_mask(
                scores, qi * block_q, j * block_k, -1, window
            )
        m_new = jnp.maximum(m, scores.max(axis=1, keepdims=True))
        alpha = jnp.exp2(m - m_new)
        l = l * alpha
        acc = acc * alpha
        for k0 in range(0, block_k, chunk):
            vb = v_ref[:, pl.ds(j * block_k + k0, chunk), :]
            p = _exp2_probs(scores[:, k0:k0 + chunk, :] - m_new, q_ref.dtype)
            l = l + p.astype(jnp.float32).sum(axis=1, keepdims=True)
            acc = acc + jax.lax.dot_general(
                vb, p.astype(vb.dtype), (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [bb, d, block_q]: (p^T v)^T
        return acc, m_new, l

    carry = (
        jnp.zeros((bb, d, block_q), jnp.float32),
        jnp.full((bb, 1, block_q), NEG_INF, jnp.float32),
        jnp.zeros((bb, 1, block_q), jnp.float32),
    )
    if window is None:
        full, live = _causal_k_range(qi, block_q, block_k)
        carry = jax.lax.fori_loop(0, full, body, carry)
        acc, m, l = jax.lax.fori_loop(
            full, live, functools.partial(body, masked=True), carry
        )
    else:
        # the band: blocks wholly under it are outside all three loops
        for start, stop, masked in _banded_loops(
            *_causal_k_range(qi, block_q, block_k, window)
        ):
            carry = jax.lax.fori_loop(
                start, stop, functools.partial(body, masked=masked), carry
            )
        acc, m, l = carry
    o_ref[:] = jnp.swapaxes(acc / l, 1, 2).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log2(l)  # base-2 lse


def _kv_head(group: int):
    """The key/value head query head `hi` reads, as a block index."""
    return (lambda hi: hi) if group == 1 else (lambda hi: hi // group)


def _fwd_causal(q, k, v, h, plan: CausalPlan, interpret, scale):
    """_fwd_bshf's grid and blocks (k and v resident as whole rows, batch
    rows folded as the plan says), on the kernel that skips. q
    [b, s, h * d], k [b, s, kv * d], v [b, s, kv * dv]; `scale` where it is
    not d ** -0.5 (a key padded with zero columns)."""
    b, s, f = q.shape
    d, dv = f // h, v.shape[-1] // (h // plan.group)
    bb, block_q, kv_head = plan.fold, plan.block_q, _kv_head(plan.group)

    def tile(width):
        return pl.BlockSpec((bb, block_q, width), lambda bi, hi, i: (bi, i, hi))

    def row(width):
        return pl.BlockSpec(
            (bb, s, width), lambda bi, hi, i: (bi, 0, kv_head(hi))
        )

    return pl.pallas_call(
        functools.partial(
            _fwd_causal_kernel, block_k=plan.block_k,
            scale=1.0 / (d**0.5) if scale is None else scale,
            **({} if plan.window is None else {"window": plan.window}),
        ),
        name=plan.fwd_name,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=plan.vmem_limit,
        ),
        grid=(b // bb, h, s // block_q),
        in_specs=[tile(d), row(d), row(dv)],
        out_specs=[
            tile(dv),
            pl.BlockSpec(
                (bb, None, 1, block_q), lambda bi, hi, i: (bi, hi, 0, i)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
    )(q, k, v)


def _bwd_causal_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_acc, *, block_q, scale, window=None,
):
    """Causal backward on the tile schedule: one program per (batch, head,
    k block), k innermost, visiting each live (q block, k block) tile ONCE.
    Scores, exp2 and dp are computed once a tile and dv, dk, dq all come
    from them: 5 matmuls where the dq/dkv kernel pair pays 7. q blocks
    before the k block are never visited, the blocks on the diagonal are
    masked and the rest are not (_causal_q_range). dk/dv accumulate in f32
    across the q blocks of this k block; dq accumulates in f32 in `dq_acc`,
    the whole [s, d] row of this (batch, head), across the k grid dim and is
    written once with the last k block, so nothing gradient-sized goes
    through HBM. q, do, lse and delta stay resident as whole rows (their
    block index does not change along k). Tiles are held transposed,
    [block_k, block_q]: lse and delta then broadcast as rows and only dq
    contracts over a tile's major dim (1.78 against 1.93 ms a call at seq
    2048 for the [block_q, block_k] form; my chip run, PR 29).
    Probabilities and ds are f32 as in the kernel pair; only the MXU
    operands are cast."""
    ki = pl.program_id(2)
    block_k, d = k_ref.shape
    s = q_ref.shape[0]
    scale2 = scale * LOG2E
    kb = k_ref[:]
    vb = v_ref[:]
    # scale folds into the [block_k, d] operand once a program
    k_scaled = kb * jnp.asarray(scale, kb.dtype)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def body(i, carry, masked=False):
        dk, dv = carry
        rows = pl.ds(i * block_q, block_q)
        qb = q_ref[rows, :]
        dob = do_ref[rows, :]
        scores = jax.lax.dot_general(
            kb, qb * jnp.asarray(scale2, qb.dtype),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if masked:
            scores = _causal_mask(
                scores, i * block_q, ki * block_k, -1, window
            )
        p = jnp.exp2(scores - lse_ref[:, rows])  # base-2 lse, [1, block_q]
        dv = dv + jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            vb, dob, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta_ref[:, rows])).astype(qb.dtype)
        dk = dk + jax.lax.dot_general(
            ds, qb * jnp.asarray(scale, qb.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_acc[rows, :] += jax.lax.dot_general(
            ds, k_scaled, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    ranges = _causal_q_range(ki, block_q, block_k, window, s)
    zero = jnp.zeros((block_k, d), jnp.float32)
    # a value may be narrower than its key (a padded wide key)
    dv0 = zero if v_ref.shape == zero.shape else jnp.zeros(v_ref.shape, jnp.float32)
    if window is None:
        start, full = ranges
        carry = jax.lax.fori_loop(
            start, full, functools.partial(body, masked=True), (zero, dv0)
        )
        dk, dv = jax.lax.fori_loop(full, s // block_q, body, carry)
    else:
        # the band: q blocks that see none of these keys any more are
        # outside all three loops
        carry = (zero, dv0)
        for lo, hi, masked in _banded_loops(*ranges):
            carry = jax.lax.fori_loop(
                lo, hi, functools.partial(body, masked=masked), carry
            )
        dk, dv = carry
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _fin():
        dq_ref[:] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_causal(q, k, v, o, lse, do, h, plan: CausalPlan, interpret, scale):
    b, s, f = q.shape
    kv = h // plan.group
    d, vd = f // h, v.shape[-1] // kv
    block_q, block_k = plan.bwd_block_q, plan.bwd_block_k
    delta4 = _delta_bshf(
        do, o, b, s, h, vd, interpret, plan.delta_block, plan.delta_name
    )
    # k and v are read by the head the group shares; dk and dv are written
    # a query head
    own, shared = _kv_head(1), _kv_head(plan.group)

    def row(width):
        return pl.BlockSpec((None, s, width), lambda bi, hi, j: (bi, 0, hi))

    def col(width, head):
        return pl.BlockSpec(
            (None, block_k, width), lambda bi, hi, j: (bi, j, head(hi))
        )

    stat = pl.BlockSpec((None, None, 1, s), lambda bi, hi, j: (bi, hi, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_causal_kernel, block_q=block_q,
            scale=1.0 / (d**0.5) if scale is None else scale,
            **({} if plan.window is None else {"window": plan.window}),
        ),
        name=plan.bwd_name,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_CAUSAL_VMEM_LIMIT,
        ),
        grid=(b, h, s // block_k),
        in_specs=[
            row(d), col(d, shared), col(vd, shared), row(vd), stat, stat,
        ],
        out_specs=[row(d), col(d, own), col(vd, own)],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, f), q.dtype),
            jax.ShapeDtypeStruct((b, s, f), k.dtype),
            jax.ShapeDtypeStruct((b, s, h * vd), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32)],
    )(q, k, v, do, lse, delta4)
    if plan.group == 1:
        return dq, dk, dv

    def over_group(t):
        t = t.reshape(b, s, kv, plan.group, -1).astype(jnp.float32)
        return jnp.sum(t, axis=3).reshape(b, s, -1).astype(k.dtype)

    return dq, over_group(dk), over_group(dv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_causal(q, k, v, h, plan, interpret, scale):
    o, _ = _fwd_causal(q, k, v, h, plan, interpret, scale)
    return o


def _flash_causal_fwd(q, k, v, h, plan, interpret, scale):
    o, lse = _fwd_causal(q, k, v, h, plan, interpret, scale)
    return o, (q, k, v, o, lse)


def _flash_causal_bwd(h, plan, interpret, scale, res, do):
    q, k, v, o, lse = res
    return _bwd_causal(q, k, v, o, lse, do, h, plan, interpret, scale)


_flash_causal.defvjp(_flash_causal_fwd, _flash_causal_bwd)


# ---------------------------------------------------------------------------
# where the kernels start: the least length per route, the fold, and the
# head-pair forward of one key tile
# ---------------------------------------------------------------------------
#
# At the end of the file for the reason the causal schedule is: a cell's
# lowered text holds the line of every frame above (what is compared since
# PR 32, `sha256_without_locations`, does not).

# Most batch rows one program of the bshf kernels folds. _batch_block's
# budgets were fitted on [512, 64] tiles, where the f32 [s, s] tiles are the
# residency, and there they allow 4. At s = 128 the double-buffered blocks
# and the lane-padded 64-wide halves outweigh the tiles: the compiler's
# stack is 710 KB a row against 327,680 B budgeted, and the 32 rows the
# budget allows ask for 22.72 MB of the 16 MB scoped VMEM (19.36 MB for 16
# rows at s = 256). Measured, head-pair forward / fused backward, ms a call
# at 8,192 positions, 16 heads of 64, bf16 (my chip runs, PR 31): s = 128,
# 2 rows 0.572 / 0.395, 4 rows 0.391 / 0.292, 8 rows 0.335 / 0.327, 16 rows
# 0.578 / 0.305; s = 256, 2 rows 0.582 / 0.389, 4 rows 0.491 / 0.355, 8
# rows 0.442 / 0.324. From 512 on the budget binds and its answer is what
# the chip chose for the head-pair forward that holds p stationary
# (_fwd_kernel_pair; ms a call, device time, my chip runs, PR 35): on
# [24, 512, 3072] 2 rows 0.396, 4 rows (the budget's) 0.395, 8 rows ask
# for more than the 16 MB of scoped VMEM; on [8, 1024, 3072] 1 row (the
# budget's) 0.482, 2 rows with the queries in two chunks 0.466.
_MAX_FOLD = 8

# The least (local) sequence length at which each kernel family of
# `ops.mha_core_route` takes over from XLA's dense attention, which writes
# the [b, h, s, s] probabilities to HBM and reads them back in the backward.
# Whole cells at 8,192 tokens a step on one v5e, dense -> kernels (my chip
# runs, PR 31; `tokens_per_s`, `step_hbm_gb`):
#   "pair" (d = 64 head-pair kernels; bert-large-uncased, 24 layers):
#     seq 128: 57,630 -> 62,333 (+8.2%), 10.484 -> 9.442 GB, before the
#     transposed forward below; seq 256: step 150.4 -> 134.9 ms (-10.3%),
#     busy MFU 57.63 -> 64.31%, 11.276 -> 9.442 GB;
#   "lane" (d % 128 == 0 fused rows; cerebras-gpt-1.3b, 8 layers, causal):
#     seq 256: 44,318 -> 44,664 and 44,333 -> 44,646 (+0.8%, +0.7%), 12.200
#     -> 11.651 GB; seq 128: 44,919 -> 44,931 (even), so 256;
#   "rows" (per-head [b, h, s, d] entry; 16 heads of 96, hidden 1536, 12
#     layers): seq 256: 53,941 -> 53,010 (-1.7%); seq 128: 55,883 -> 53,695
#     (-3.9%): it loses, and keeps MIN_SEQ_UNMEASURED.
# The ring and all-to-all sequence-parallel kernels (ring_flash.py,
# ulysses_attention.py) read MIN_SEQ_UNMEASURED too: no cell runs them.
MIN_SEQ_UNMEASURED = 512
_MIN_SEQ = {"pair": 128, "lane": 256, "rows": MIN_SEQ_UNMEASURED}


def min_seq_for(family=None) -> int:
    """The least sequence length of a kernel family: FLEXFLOW_TPU_FLASH_MIN_SEQ
    where set (benchmarking and tests: one length for every family), else
    what was measured for it (_MIN_SEQ), else MIN_SEQ_UNMEASURED. A key wider
    than its value (kd != vd: latent attention's 192 | 128) reads the "lane"
    family's length and, besides, needs more than one causal tile
    (`CausalPlan.supported`): below that it takes XLA's dense attention."""
    import os

    env = os.environ.get("FLEXFLOW_TPU_FLASH_MIN_SEQ")
    if env is not None:
        return int(env)
    return _MIN_SEQ.get(family, MIN_SEQ_UNMEASURED)


# The head-pair forward has ONE body, for one key tile (every call the
# entries make: the pair path's gate is s <= block, the fused backward's
# limit). It holds its scores TRANSPOSED, [keys, queries]: the softmax's
# maximum runs down the sublanes and lse comes out along the lanes, as
# lse_ref stores it; and p is the STATIONARY operand of the second matmul,
# so what the compiler turns is the [128, queries] result and not every
# [keys, queries] tile of p. Forward, ms a call on bf16 [rows, s, 3072], 16
# heads of 64, device time (8 calls a program; my chip runs, PR 35; the fold
# is _batch_block's: 8 / 8 / 4 / 4 / 1 rows a program):
#                          queries x keys    p streams    p stationary
#   [64, 128]   non-causal   0.324             0.172        0.168
#   [32, 256]   non-causal   0.430             0.186        0.172
#   [24, 512]   non-causal   0.750             0.445        0.395
#   [16, 512]   non-causal   0.509             0.303        0.269
#   [8, 1024]   non-causal   0.761             0.519        0.482
#   [24, 512]   causal       0.746             0.396        0.295
#   [8, 1024]   causal       0.760             0.491        0.409
# "queries x keys" was the single-tile branch of an online-softmax body:
# three score-sized matmuls (the row sum was p @ ones[s, 1]) and a
# cross-lane maximum; the seq-512 cells ran it at 17.7 ms a step. "p
# streams" was PR 31's transposed body (p^T @ [v | 1]), which ran under
# 512. Under ~0.2 ms a call this probe reads the host, not the chip: in the
# device trace of bertlarge_s128_1chip the 24 calls on [64, 128] take 3.64
# ms a step with p streaming and 2.43 with p stationary (+0.9% tokens/s).
# There is no body for more than one tile: the pair backward is one tile.


def _pair_causal_chunk(s: int) -> int:
    """Queries a causal program of _fwd_kernel_pair takes at a time: the
    keys past a chunk's last query are never read. The largest of 256 and
    128 that divides s (the pair path's gate is s % 128 == 0). [24, 512]
    causal, 4 rows a program: one chunk of 512 0.363 ms, chunks of 256
    0.295, of 128 0.313; [8, 1024] causal, 2 rows: 512 0.358, 256 0.368.
    In chunks of 128: [24, 384] 0.174, [12, 640] 0.240, [8, 896] 0.498
    (0.219 / 0.301 / 0.377 without a mask: at one row a program seven
    chunks cost more than they skip; no cell has such a length). Without a
    mask there is nothing to skip and one chunk is best (a phase of rows x
    queries under 1,024 columns leaves its latency exposed: [24, 512], 2
    rows, 512 queries 0.396, 256 queries 0.456). My chip runs, PR 35."""
    assert s % 128 == 0, s
    return 256 if s % 256 == 0 else 128


def _fwd_kernel_pair(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale, d,
):
    """Head-PAIR forward for d=64: the refs carry TWO heads side by side in
    a 128-lane block (Pallas cannot carve 64-wide blocks out of a fused h*d
    dim, but a 128-wide block holding a pair is legal), and the softmax runs
    per 64-lane half. Keeps the projections plain matmuls at the reference
    heads=16 / d=64 config — the per-head [b,h,s,d] layout pays ~27 ms/step
    of transpose copies. One key tile, scores held [keys, queries]:
    [v | 1]^T @ p gives o transposed, [2d, queries], with the row sums in
    the rows the column of ones makes (the MXU is 128 wide either way), so
    the sum that divides o and lse both lie along the lanes, where the
    maximum already is, and nothing is summed on the VPU. What is turned is
    the [2d, queries] quotient of BOTH heads, once, into the pair's
    128-lane block of o. f32 scores, _exp2_probs, f32 sums on the MXU,
    bf16 p."""
    bb, s, _ = q_ref.shape
    scale2 = scale * LOG2E
    cq = _pair_causal_chunk(s) if causal else s
    for c in range(s // cq):
        qs = pl.ds(c * cq, cq)
        t = (c + 1) * cq  # the keys this chunk reads: all s without a mask
        halves = []
        for h2 in range(2):
            sl = pl.ds(h2 * d, d)
            # scale folded into the [bb, cq, d] half (see _fwd_kernel)
            q = q_ref[:, qs, sl] * jnp.asarray(scale2, q_ref.dtype)
            vb = v_ref[:, :t, sl]
            scores = jax.lax.dot_general(
                k_ref[:, :t, sl], q, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [bb, t, cq]
            if causal:
                keys = jax.lax.broadcasted_iota(jnp.int32, (t, cq), 0)
                queries = c * cq + jax.lax.broadcasted_iota(
                    jnp.int32, (t, cq), 1
                )
                scores = jnp.where((queries >= keys)[None], scores, NEG_INF)
            m = scores.max(axis=1)
            p = _exp2_probs(scores - m[:, None, :], q_ref.dtype)
            acc = jax.lax.dot_general(
                jnp.concatenate([vb, jnp.ones_like(vb)], -1),
                p.astype(vb.dtype),
                (((1,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [bb, 2d, cq]: (p^T v)^T over the row sums
            l = acc[:, d:d + 1, :]
            lse_ref[:, h2, 0, qs] = m + jnp.log2(l[:, 0, :])
            halves.append(acc[:, :d, :] / l)
        o_ref[:, qs, :] = jnp.swapaxes(
            jnp.concatenate(halves, axis=1), 1, 2
        ).astype(o_ref.dtype)


def _pair_fwd_kernel(s: int, block_q: int, block_k: int):
    """The head-pair forward body for a call's shape: there is one, and it
    takes one key tile."""
    assert block_q == s == block_k, (s, block_q, block_k)
    return _fwd_kernel_pair


# ---------------------------------------------------------------------------
# a key wider than its value (latent attention: 192 | 128)
# ---------------------------------------------------------------------------
#
# The causal tile kernels above take a value narrower than the key (the
# forward's accumulator and the backward's dv are as wide as v). A 192-wide
# key is no whole number of 128-lane tiles, so the caller pads q and k with
# zero columns to `wide_key_padded` (256) and names the scale of the TRUE
# width: zero columns add nothing to a score. The other form, a second score
# term over the 64 columns the heads share (two more operands, a dk_s summed
# over the heads), contracts 128 + 128 MXU columns too once the slice is
# padded to a tile, and was not built (PERF.md section 6, PR 43).


def wide_key_padded(kd: int) -> int:
    """The least multiple of 128 lanes that holds a `kd`-wide key."""
    return -(-kd // 128) * 128
