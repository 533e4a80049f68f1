"""MoE kernels: the fused Experts op by sorted dispatch and grouped matmuls,
and the frozen one-hot GroupBy / Aggregate parity ops.

Reference: the legacy CUDA Group_by/Aggregate kernels scatter tokens into
per-expert buffers with atomics (examples/cpp/mixture_of_experts/moe.cu era
ops). `group_by_forward` / `aggregate_forward` keep that composition alive
with one-hot dispatch matrices and einsums (the GShard/Mesh-TF formulation),
which is fine at the toy sizes the parity ops run at and nowhere else: the
one-hot tensor is [N*k, E, capacity] float32, 68.7 GB at a published size
(16,384 tokens, 8 of 64 experts, capacity factor 1).

`experts_forward` therefore dispatches by index, the way MegaBlocks and the
dropless decoders do: sort the N*k routing decisions by expert (stable, so a
decision's rank within its expert is "earlier tokens first"), gather the
token rows in that order, run the experts' matrices as grouped matmuls (rows
[N*k, D] against [E, D, H] with E group sizes) in the compute dtype with
float32 accumulation, and combine each token's k rows with the router's
weights. The grouped matmul is `_grouped_matmul`: on the chip the Pallas
kernels `gmm` / `tgmm` of `jax.experimental.pallas.ops.tpu.megablox`, elsewhere
`jax.lax.ragged_dot`. The row count is
always N*k, so every shape is static and nothing is dropped unless the attrs
state a capacity, in which case the decisions ranked past it get weight zero.
Neither the gather nor the combine needs a scatter in either direction: both
are row permutations, and the transpose of a permutation is the gather by its
inverse (`_take_rows`).
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from flexflow_tpu.op_attrs.ops.moe import (
    AggregateAttrs,
    ExpertsAttrs,
    GroupByAttrs,
    expert_capacity,
)


def dispatch_mask(assign: jnp.ndarray, n_experts: int, capacity: int) -> jnp.ndarray:
    """One-hot dispatch tensor D[n, e, c] for flattened routing decisions.

    assign: [N] int expert index per routing decision (row-major over
    (token, select) so earlier tokens win capacity, matching the reference
    GroupBy's first-come scatter order). D[n, e, c] = 1 iff decision n goes
    to expert e at buffer position c; decisions past capacity are dropped.
    """
    onehot = jax.nn.one_hot(assign, n_experts, dtype=jnp.int32)  # [N, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1  # position within expert
    keep = (pos >= 0) & (pos < capacity)
    posc = jnp.clip(pos, 0, capacity - 1)
    d = jax.nn.one_hot(posc, capacity, dtype=jnp.int32)  # [N, E, cap]
    return (d * keep[..., None].astype(jnp.int32)).astype(jnp.float32)


def group_by_forward(
    attrs: GroupByAttrs, data: jnp.ndarray, assign: jnp.ndarray
) -> List[jnp.ndarray]:
    """data [B, D], assign [B, k] -> n_experts buffers [cap, D]."""
    b, k = assign.shape
    cap = expert_capacity(data.shape[0], attrs.n_experts, k, attrs.alpha)
    d = dispatch_mask(assign.reshape(-1), attrs.n_experts, cap)  # [B*k, E, c]
    data_rep = jnp.repeat(data, k, axis=0)  # decision (b, j) carries data[b]
    grouped = jnp.einsum("nec,nd->ecd", d, data_rep.astype(jnp.float32))
    grouped = grouped.astype(data.dtype)
    return [grouped[e] for e in range(attrs.n_experts)]


def aggregate_forward(
    attrs: AggregateAttrs,
    gate_preds: jnp.ndarray,
    gate_assign: jnp.ndarray,
    exp_preds: Sequence[jnp.ndarray],
) -> jnp.ndarray:
    """Weighted un-dispatch: [B, k] gates + n x [cap, D] -> [B, D]."""
    b, k = gate_assign.shape
    cap = exp_preds[0].shape[0]
    d = dispatch_mask(gate_assign.reshape(-1), attrs.n, cap)  # [B*k, E, c]
    combine = d * gate_preds.reshape(-1)[:, None, None].astype(d.dtype)
    stacked = jnp.stack(list(exp_preds)).astype(jnp.float32)  # [E, cap, D]
    out = jnp.einsum("nec,ecd->nd", combine, stacked)  # [B*k, D]
    out = out.reshape(b, k, -1).sum(axis=1)
    return out.astype(exp_preds[0].dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, inverse, fan):
    """x[index] for index = permutation // fan (each row of x appears `fan`
    times). The transpose of that gather is a scatter-add; with the
    permutation's inverse in hand it is a gather and a sum over the `fan`
    copies instead, which is what the backward pass computes."""
    del inverse, fan
    return x[index]


def _take_rows_fwd(x, index, inverse, fan):
    return x[index], inverse


def _take_rows_bwd(fan, inverse, g):
    gx = g[inverse].reshape(-1, fan, *g.shape[1:]).sum(axis=1)
    return gx.astype(g.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


# (rows, contraction, columns) tile of the megablox kernels. Measured on a v5e
# at the OLMoE shapes (131,072 rows, 2048 x 1024 and back, 64 groups, forward
# and backward of the gated expert): 41.8 ms, against 43.1 at 256 rows, 46.9 at
# a 512 contraction tile, 509 at the library's default (128, 128, 128) and
# 56.3 for XLA's own `ragged-dot` kernel, which also loses the node's scope in
# the device trace; 1024 rows or a 2048 contraction tile do not fit VMEM
# (my chip run, PR 26).
_GMM_TILE = (512, 1024, 1024)


def _pallas_allowed(per_shard: bool) -> bool:
    """On the chip, and in a per-device program: the body of a shard_map, or
    a trace that is no global-view SPMD program (the data-parallel backend's
    jit, the searched executor's), where a Pallas call has no partitioning
    rule. The signals the flash attention path reads."""
    from flexflow_tpu.kernels import flash_attention as flash

    return flash._backend_ok() and (
        per_shard
        or (
            flash.current_flash_mesh() is None
            and not getattr(flash._tls, "disabled", False)
        )
    )


def _grouped_matmul(rows, w, group_sizes, pallas: bool):
    """rows [M, K] (sorted by group) x w [G, K, N] -> [M, N] in rows' dtype,
    float32 accumulation; `pallas`: `_pallas_allowed`."""
    w = w.astype(rows.dtype)
    (m, k), n = rows.shape, w.shape[-1]
    tm, tk, tn = _GMM_TILE
    if pallas and m % tm == 0 and k % 128 == 0 and n % 128 == 0:
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(
            rows, w, group_sizes, rows.dtype, (tm, min(tk, k), min(tn, n))
        )
    return lax.ragged_dot(
        rows, w, group_sizes, preferred_element_type=rows.dtype
    )


def route(attrs: ExpertsAttrs, x2: jnp.ndarray, gate_w: jnp.ndarray):
    """The router, in float32 whatever x2's dtype: (logits [N, E],
    probabilities [N, E], selected experts [N, k], their weights [N, k])."""
    logits = x2.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, attrs.num_select)
    if attrs.renormalize:
        topv = topv / topv.sum(axis=-1, keepdims=True)
    return logits, probs, topi, topv


def experts_forward(
    attrs: ExpertsAttrs,
    x: jnp.ndarray,
    weights: Sequence[jnp.ndarray],
    expert_shard=None,
    per_shard: bool = False,
) -> List[jnp.ndarray]:
    """Fused MoE FFN. x [.., D]; weights per ExpertsAttrs slot order.

    expert_shard: None, or (first expert, experts here) when the expert
    tensors in `weights` are one expert-parallel shard's slice: routing is
    at the full router width and the output is this shard's experts' part
    of the combine (the caller sums the parts).
    per_shard: called from the body of a shard_map, a per-device program
    whatever the enclosing trace."""
    gate_w, rest = weights[0], list(weights[1:])
    w1 = rest.pop(0)
    w3 = rest.pop(0) if attrs.gated else None
    b1 = rest.pop(0) if attrs.use_bias else None
    w2 = rest.pop(0)
    b2 = rest.pop(0) if attrs.use_bias else None

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    n = x2.shape[0]
    e, k = attrs.num_experts, attrs.num_select
    logits, probs, topi, topv = route(attrs, x2, gate_w)

    # -- dispatch: decisions in (token, select) order, sorted by expert ----
    flat_e = topi.reshape(-1).astype(jnp.int32)  # [N*k]
    order = jnp.argsort(flat_e, stable=True)
    inverse = jnp.argsort(order)  # decision -> its row after the sort
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    if attrs.capacity_factor is not None:
        cap = expert_capacity(n, e, k, attrs.capacity_factor)
        first_row = jnp.cumsum(counts) - counts
        rank = jnp.arange(n * k, dtype=jnp.int32) - first_row[flat_e[order]]
        kept = (rank < cap)[inverse].reshape(n, k)
        topv = jnp.where(kept, topv, 0.0)

    if expert_shard is None:
        group_sizes = counts
    else:
        # rows of the experts before and after this shard's form one group
        # each around its own; their two "experts" are zero matrices, so the
        # rows cost their FLOPs and contribute nothing
        lo, here = expert_shard
        before = jnp.sum(jnp.where(jnp.arange(e) < lo, counts, 0))
        mine = lax.dynamic_slice(counts, (lo,), (here,))
        after = n * k - before - jnp.sum(mine)
        group_sizes = jnp.concatenate(
            [before[None], mine, after[None]]
        ).astype(jnp.int32)

        def pad(w):
            zero = jnp.zeros((1,) + w.shape[1:], w.dtype)
            return jnp.concatenate([zero, w, zero])

        w1, w2 = pad(w1), pad(w2)
        w3 = None if w3 is None else pad(w3)
        b1 = None if b1 is None else pad(b1)
        b2 = None if b2 is None else pad(b2)

    pallas = _pallas_allowed(per_shard)

    def grouped(rows, w):
        return _grouped_matmul(rows, w, group_sizes, pallas)

    def bias_rows(b, dtype):
        # each row's expert's bias, as a grouped matmul of a column of ones:
        # its transpose is a grouped matmul too, where that of `b[expert]`
        # is a scatter-add whose colliding updates combine in schedule order
        ones = jnp.ones((n * k, 1), dtype)
        return grouped(ones, b[:, None, :])

    rows = _take_rows(x2, order // k, inverse, k)
    with jax.named_scope("grouped_matmul"):
        h = grouped(rows, w1)
        if b1 is not None:
            h = h + bias_rows(b1, h.dtype)
        if attrs.activation is not None:
            h = attrs.activation.apply(h)
        if w3 is not None:
            h = h * grouped(rows, w3)
        y = grouped(h, w2)
        if b2 is not None:
            y = y + bias_rows(b2, y.dtype)
    # -- combine: each token's k rows, weighted by the router in float32 ---
    y = _take_rows(y, inverse, order, 1)
    y = y.reshape(n, k, y.shape[-1]).astype(jnp.float32)
    out = jnp.einsum("nk,nko->no", topv, y)
    out = out.reshape(*lead, out.shape[-1]).astype(x.dtype)

    if not attrs.has_aux:
        return [out]
    aux = jnp.zeros((), jnp.float32)
    if attrs.lambda_bal > 0:
        # gated: f_e = tokens that chose e / N; legacy: decisions / (N k)
        # (ExpertsAttrs docstring). Counts carry no gradient.
        frac = counts.astype(jnp.float32) / (n if attrs.gated else n * k)
        aux += attrs.lambda_bal * e * jnp.sum(
            lax.stop_gradient(frac) * probs.mean(axis=0)
        )
    if attrs.lambda_z > 0:
        aux += attrs.lambda_z * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))
        )
    # float32 out of the node whatever the compute dtype: 0.01 x LB is about
    # 0.08, whose bf16 rounding (3e-4) is the size of a loss tolerance
    return [out, aux.reshape(1)]
