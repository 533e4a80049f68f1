"""Loss kernels (reference: lib/kernels/include/kernels/loss_function_kernels.h,
lib/runtime/src/loss_functions.cc:33-108).

The reference computes loss *gradients* directly in CUDA with scale 1/batch
(2/volume for MSE). Here the loss is a scalar forward function and autodiff
produces identical gradients: mean-reduction over the batch gives the 1/batch
scale; MSE as mean of squared error gives 2/volume on the backward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.op_attrs.ops.loss_functions import (
    LabelCrossEntropyAttrs,
    LossAttrs,
    MeanLossAttrs,
    LossFunction,
    NonconfigurableLossAttrs,
    SparseCategoricalCrossEntropyLossAttrs,
)


@jax.custom_vjp
def _fused_scce(logit: jnp.ndarray, label: jnp.ndarray) -> jnp.ndarray:
    """Sparse categorical cross-entropy that never materializes the
    [batch..., num_classes] log-prob tensor in f32.

    The naive jax.nn.log_softmax path makes XLA write (and re-read on the
    backward pass) a full-precision log-prob array — for a [64,512,32000]
    LM head that is 4.2 GB of pure HBM traffic per step. Here the forward
    keeps only the per-row logsumexp (f32, [batch...]) and the backward
    emits (softmax - onehot) * g/N directly in the logit dtype."""
    return _scce_fwd_impl(logit, label)[0]


def _row_lse(logit):
    """logsumexp over the classes in float32, [batch...]."""
    lf = logit.astype(jnp.float32)
    m = jnp.max(lf, axis=-1)
    return m + jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))


def _picked(logit, label):
    """The labelled logit of every row, float32. Gathered from the ORIGINAL
    logits: a gather operand cannot fuse, so gathering from the f32
    conversion made XLA materialize the full [batch..., classes] array in
    f32 (4.2 GB on the [64,512,32000] LM head); the picked values are exact
    in the storage dtype and the subtraction happens in f32 anyway."""
    return jnp.take_along_axis(logit, label[..., None], axis=-1)[
        ..., 0
    ].astype(jnp.float32)


def _probs_minus_onehot(logit, label, lse):
    """softmax(logit) - onehot(label) in the logit dtype END-TO-END:
    computing f32 probabilities first made XLA materialize a full-precision
    [batch..., classes] fusion output (4.2 GB on the [64,512,32000] LM
    head, ~12 ms/step of pure HBM traffic) that the weight-grad matmuls
    then re-read. The normalized scores are exact in f32 up to the cast;
    p in bf16 has ~0.4% relative error on a value in (0, 1], far below
    gradient noise. A negative label matches no class."""
    z = (logit.astype(jnp.float32) - lse[..., None]).astype(logit.dtype)
    p = jnp.exp(z)
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, logit.shape, logit.ndim - 1)
        == label[..., None]
    )
    return p - onehot.astype(p.dtype)


def _scce_fwd_impl(logit, label):
    lse = _row_lse(logit)
    label = label.astype(jnp.int32)
    loss = jnp.mean(lse - _picked(logit, label))
    return loss, (logit, label, lse)


def _scce_bwd(res, g):
    logit, label, lse = res
    diff = _probs_minus_onehot(logit, label, lse)
    dlogit = diff * jnp.asarray(g / lse.size, diff.dtype)
    return dlogit.astype(logit.dtype), None


_fused_scce.defvjp(_scce_fwd_impl, _scce_bwd)


@jax.custom_vjp
def _fused_masked_scce(logit: jnp.ndarray, label: jnp.ndarray) -> jnp.ndarray:
    """`_fused_scce` as a mean over the positions whose label is not
    negative (a position without a target weighs nothing; with none at all
    the loss is zero): the same two passes over the logits in their own
    dtype, no float32 [batch..., classes] array kept."""
    return _masked_scce_fwd(logit, label)[0]


def _masked_scce_fwd(logit, label):
    lse = _row_lse(logit)
    label = label.astype(jnp.int32)
    valid = label >= 0
    count = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)
    rows = jnp.where(valid, lse - _picked(logit, jnp.maximum(label, 0)), 0.0)
    return jnp.sum(rows) / count, (logit, label, lse, count)


def _masked_scce_bwd(res, g):
    logit, label, lse, count = res
    diff = _probs_minus_onehot(logit, label, lse)
    scale = jnp.where(label >= 0, g / count, 0.0).astype(diff.dtype)
    return (diff * scale[..., None]).astype(logit.dtype), None


_fused_masked_scce.defvjp(_masked_scce_fwd, _masked_scce_bwd)


@jax.custom_vjp
def _fused_weighted_scce(
    logit: jnp.ndarray, label: jnp.ndarray, weight: jnp.ndarray
) -> jnp.ndarray:
    """`_fused_masked_scce` with a weight a position, `sum_i w_i CE_i / n`
    over the n positions with a label: the same two passes over the logits
    in their own dtype, and a gradient to the weights (each position's own
    cross-entropy over n, kept from the forward as one float a position)."""
    return _weighted_scce_fwd(logit, label, weight)[0]


def _weighted_scce_fwd(logit, label, weight):
    lse = _row_lse(logit)
    label = label.astype(jnp.int32)
    valid = label >= 0
    count = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)
    rows = jnp.where(valid, lse - _picked(logit, jnp.maximum(label, 0)), 0.0)
    loss = jnp.sum(rows * weight.astype(jnp.float32)) / count
    return loss, (logit, label, lse, count, rows, weight)


def _weighted_scce_bwd(res, g):
    logit, label, lse, count, rows, weight = res
    diff = _probs_minus_onehot(logit, label, lse)
    scale = jnp.where(
        label >= 0, g * weight.astype(jnp.float32) / count, 0.0
    ).astype(diff.dtype)
    dweight = (g * rows / count).astype(weight.dtype)  # rows are 0 off-label
    return (diff * scale[..., None]).astype(logit.dtype), None, dweight


_fused_weighted_scce.defvjp(_weighted_scce_fwd, _weighted_scce_bwd)


def label_cross_entropy(
    attrs: LabelCrossEntropyAttrs, logit: jnp.ndarray, label: jnp.ndarray,
    weight: jnp.ndarray = None,
) -> jnp.ndarray:
    """The node's scalar [1]: `attrs.weight` times the mean cross-entropy
    over the positions with a label (each under its own `weight`, where the
    node has position weights), through the fused form. The unweighted mean
    goes to the step's loss-term counter (`observability/trace.loss_terms`),
    with the weights' mean over the same positions as the term's `mass`."""
    from flexflow_tpu.observability import trace

    if weight is None:
        mean = _fused_masked_scce(logit, label)
        trace.record_loss_term(attrs.weight, mean)
    else:
        mean = _fused_weighted_scce(logit, label, weight)
        valid = label >= 0
        mass = jnp.sum(
            jnp.where(valid, weight.astype(jnp.float32), 0.0)
        ) / jnp.maximum(jnp.sum(valid), 1)
        trace.record_loss_term(
            attrs.weight, mean, mass=jax.lax.stop_gradient(mass)
        )
    return (mean * attrs.weight).reshape(1)  # float32, whatever the logits are


def mean_loss(attrs: MeanLossAttrs, value: jnp.ndarray) -> jnp.ndarray:
    """The node's scalar [1]: `attrs.weight` times the float32 mean of
    `value`; the unweighted mean goes to the loss-term counter."""
    from flexflow_tpu.observability import trace

    mean = jnp.mean(value.astype(jnp.float32))
    trace.record_loss_term(attrs.weight, mean)
    return (mean * attrs.weight).reshape(1)


def loss_forward(attrs: LossAttrs, logit: jnp.ndarray, label: jnp.ndarray) -> jnp.ndarray:
    """Scalar loss. logit: [batch..., num_classes] (or arbitrary for MSE/MAE);
    label: int labels [batch...] for SCCE, one-hot/dense for others."""
    fn = attrs.loss_type
    if fn == LossFunction.SPARSE_CATEGORICAL_CROSSENTROPY:
        # fused path: loss math in f32 without a materialized log-prob array
        return _fused_scce(logit, label)
    # loss math runs in f32 regardless of the compute dtype (bf16 logits
    # would lose the log-softmax tail)
    if jnp.issubdtype(logit.dtype, jnp.floating) and logit.dtype != jnp.float32:
        logit = logit.astype(jnp.float32)
    if fn == LossFunction.CATEGORICAL_CROSSENTROPY:
        logprobs = jax.nn.log_softmax(logit, axis=-1)
        return -jnp.mean(jnp.sum(label * logprobs, axis=-1))
    if fn == LossFunction.MEAN_SQUARED_ERROR:
        return jnp.mean(jnp.square(logit - label))
    if fn == LossFunction.MEAN_ABSOLUTE_ERROR:
        return jnp.mean(jnp.abs(logit - label))
    if fn == LossFunction.IDENTITY:
        return jnp.mean(logit)
    if fn == LossFunction.LOSS_NODES:
        # the caller adds the graph's loss nodes to this; the logits are
        # some node's input already and no term of their own
        return jnp.zeros((), jnp.float32)
    raise ValueError(f"unknown loss {fn}")


def loss_grad_scale(attrs: LossAttrs, batch_size: int, volume: int) -> float:
    """The scale the reference applies in loss_backward_task
    (loss_functions.cc:54-108): 1/batch, or 2/volume for MSE."""
    if attrs.loss_type == LossFunction.MEAN_SQUARED_ERROR:
        return 2.0 / volume
    return 1.0 / batch_size
