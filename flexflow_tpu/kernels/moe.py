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
kernels `gmm` / `tgmm` of `jax.experimental.pallas.ops.tpu.megablox`, the
forward, the input gradient and the weight gradient each with a tile chosen
from that call's own shape (`_gmm_tile`), elsewhere `jax.lax.ragged_dot`.
The row count is
always N*k, so every shape is static and nothing is dropped unless the attrs
state a capacity, in which case the decisions ranked past it get weight zero.
Neither the gather nor the combine needs a scatter in either direction: both
are row permutations, and the transpose of a permutation is the gather by its
inverse (`_take_rows`).

A call that has a SHARE of the experts' matrices (an op that holds one,
`ExpertsAttrs.held_experts`: one chip's share under expert parallelism,
without the exchange; or one expert-parallel shard inside a `shard_map`,
`expert_shard`) routes over all of them and takes `_held_rows_forward`
instead: it touches only the rows routed to its experts, a window of them at
a time, so its cost follows the rows that do work and not N*k. A window's
rows go back to their tokens as a sum over each token's own rows
(`held_rows_sum`, a Pallas kernel of row copies) where the rows are bf16 on
the chip, as XLA's scatter-add elsewhere; there, too, what runs between the
grouped matmuls and that sum stops at the share's last row and not at the
window's (`_window_stages`).

Where the experts live in a latent space (`ExpertsAttrs.latent_size`) the
rows that are sorted, gathered, multiplied and combined are the tokens'
latent images `x w_down`, in every form above; the router and the shared
expert read x itself, and `w_up` meets the combined float32 rows once.

On the routing path no value is fetched, counted or sent back one element
at a time: the chosen scores are picked from the `[N, E]` scores, and the
decisions counted per expert, by comparison with an iota and a reduction
(`_pick_columns`, `_count_keys`), and the ranking gives indices only.

The node's parts go under scopes of their own inside the node's
(`ff.experts.<name>/router`, `/latent`, `/routed`, `/shared`;
`observability/trace.NODE_PARTS`), so a trace reader can tell them apart.
"""

from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.kernels import context
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


# -- the grouped matmul: megablox's `gmm` / `tgmm`, a tile a call ------------
#
# A grouped matmul is three kernel calls, named here as megablox names a
# call's own sides, rows [m, k] x matrices [g, k, n]:
#   forward          gmm   rows [m, K] x w [g, K, N]        k = K, n = N
#   input_gradient   gmm   g [m, N] x w^T (`transpose_rhs`)  k = N, n = K
#   weight_gradient  tgmm  rows^T [K, m] x g [m, N]          [g, K, N] out
# and a (tm, tk, tn) tile means blocks [tm, tk] x [tk, tn] -> [tm, tn] in the
# two `gmm` calls and [tm, tk]^T x [tm, tn] -> [tk, tn] in `tgmm`.
_CALLS = ("forward", "input_gradient", "weight_gradient")

# Bytes of VMEM a tile may ask for, as Pallas lays a call out: every operand
# and result block twice (the pipeline's two buffers) and the float32
# accumulator once, operands of two bytes. The v5e's compiler allows a kernel
# 16 MB with its own temporaries: (512, 2048, 1024), 16 MB by this count,
# is refused at 16.58 (PR 26; described-chip compile, PR 48), and every tile
# of up to 14.75 MB tried compiled and ran. A quarter is left to it.
_TILE_BYTES = 12 * 2**20


def _whole_tiles(size: int, tile: int) -> int:
    """`size` rounded up to whole tiles: what a side costs, since megablox
    runs a partial block as a whole one."""
    return -(-size // tile) * tile


def _exact_tiles(size: int) -> List[int]:
    """The multiples of 128 no larger than `size` that end it in the least
    padding, largest first: 1,536, 768, 512, 384, 256, 128 for 1,536; 640,
    384, 128 for 1,856 (1,920: it is no multiple of 128)."""
    tiles = range(size - size % 128, 0, -128)
    least = min(_whole_tiles(size, t) for t in tiles)
    return [t for t in tiles if _whole_tiles(size, t) == least]


def _tile_bytes(call: str, tm: int, tk: int, tn: int) -> int:
    if call == "weight_gradient":  # [tm, tk]^T x [tm, tn] -> [tk, tn]
        return 4 * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    return 4 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def _gmm_tile(m: int, k: int, n: int, groups: int, call: str):
    """The megablox (tm, tk, tn) for one of a grouped matmul's `_CALLS` on
    rows [m, k] and `groups` matrices [k, n] (the call's own sides), or None
    where the kernels do not take the shape. Read from the shape alone; what
    each clause is worth was measured on a v5e, kernels alone, at the five
    sparse cells' shapes (PERF.md section 6, PR 48; the times here are one
    `gmm` call of `lfm2moe24b_s8192_1chip`, 10,240 rows x 2048 x 1536):

    - the contraction and the columns in tiles that end them in no padding
      (`_exact_tiles`): megablox runs a partial block as a whole one, so a
      1,024-wide tile computes 2,048 of a 1,536-wide side: 553 us at
      (512, 1024, 1024), 417 at (512, 1024, 768);
    - the row tile the largest of 512, 256, 128 of which a group holds four
      (`m // groups` rows; 128 where it holds none): every group that does
      not start on a tile's boundary visits one tile more, 1.24 of the rows
      at 2,048 a group and 512 a tile, 1.47 at 1,024 a group, 1.23 at 256;
    - a `gmm` under 512 rows a tile holds the contraction whole where that
      fits `_TILE_BYTES`: the group's weight block then stays in VMEM across
      its row tiles, where a 256-row tile would fetch it again at 256 FLOPs a
      byte, the chip's balance: 350 us at (256, 2048, 768), 444 at
      (256, 1024, 768). Kimi's 128-row call on 2304 x 1024: 84 us whole, 114
      at 768, 134 at the 1,024 that pads 2,304 to 3,072;
    - otherwise (`tgmm`, whose contraction is the row tile, and 512 rows a
      tile) the largest block that fits, the squarer of two as large: at
      OLMoE's 131,072 rows x 2048 x 1024 that is (512, 1024, 1024) in all
      three calls, the tile measured there against 256 rows, a 512
      contraction tile, the library's (128, 128, 128), 12 times slower, and
      XLA's own `ragged-dot` (PR 26)."""
    assert call in _CALLS, call
    if m % 128 or min(k, n) < 128 or k % 64 or n % 64:
        return None
    tm = next((t for t in (512, 256) if m % t == 0 and m // groups >= 4 * t), 128)
    whole = call != "weight_gradient" and tm < 512
    tk, tn = max(
        (
            (tk, tn) for tk in _exact_tiles(k) for tn in _exact_tiles(n)
            if _tile_bytes(call, tm, tk, tn) <= _TILE_BYTES
        ),
        key=lambda t: (whole and t[0] == k, t[0] * t[1], min(t)),
    )
    return tm, tk, tn


def _gmm_tiles(m: int, k: int, n: int, groups: int):
    """The three tiles of a grouped matmul rows [m, k] x [groups, k, n], in
    the order of `_CALLS`, or None where the kernels do not take the shape:
    the input gradient contracts over the forward's columns."""
    tiles = (
        _gmm_tile(m, k, n, groups, "forward"),
        _gmm_tile(m, n, k, groups, "input_gradient"),
        _gmm_tile(m, k, n, groups, "weight_gradient"),
    )
    return None if None in tiles else tiles


def _pallas_allowed(per_shard: bool) -> bool:
    """On the chip, and in a per-device program: the body of a shard_map, or
    a trace that is no global-view SPMD program (the data-parallel backend's
    jit, the searched executor's), where a Pallas call has no partitioning
    rule (`kernels/context.admits_bare_pallas_call`)."""
    return context.on_tpu() if per_shard else context.admits_bare_pallas_call()


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm(rows, w, group_sizes, group_offset, tiles, interpret=False):
    """megablox's grouped matmul with a tile a call (`tiles`: `_gmm_tiles`),
    where `megablox.ops.gmm`'s own `custom_vjp` hands the forward's to all
    three. Operands in rows' dtype, float32 accumulation, the residuals the
    library's: rows, matrices, sizes. `group_offset`: see `_grouped_matmul`."""
    # the backend MODULE's function: the package's `gmm` is `ops.gmm`
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(
        rows, w, group_sizes, rows.dtype, tiles[0], group_offset,
        interpret=interpret,
    )


def _gmm_fwd(rows, w, group_sizes, group_offset, tiles, interpret):
    out = _gmm(rows, w, group_sizes, group_offset, tiles, interpret)
    return out, (rows, w, group_sizes, group_offset)


def _gmm_bwd(tiles, interpret, kept, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    rows, w, group_sizes, group_offset = kept
    g_rows = gmm(
        g, w, group_sizes, rows.dtype, tiles[1], group_offset,
        transpose_rhs=True, interpret=interpret,
    )
    g_w = tgmm(
        rows.swapaxes(0, 1), g, group_sizes, w.dtype, tiles[2], group_offset,
        w.shape[0], interpret=interpret,
    )
    return g_rows, g_w, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped_matmul(rows, w, group_sizes, pallas: bool):
    """rows [M, K] (sorted by group) x w [G, K, N] -> [M, N] in rows' dtype,
    float32 accumulation; `pallas`: `_pallas_allowed`. `group_sizes` has G
    entries, or G + 1: the last is then the rows' rest (`_held_window`),
    which meets no matrix and comes back zero. The kernels do not visit it
    (a group offset of zero into G + 1 sizes; megablox then fills it with
    zeros, a select over all M rows); XLA's `ragged_dot` is given a matrix
    of zeros for it.

    G sizes that add up to LESS than M (`_held_window` where its row stages
    stop at the share's last row, `_window_stages`): the kernels visit the
    same row tiles and nothing is filled, so the rows past the groups' last
    are NOT WRITTEN, here and in the backward's input gradient, and hold
    whatever the buffer held (NaN in interpret mode). That is sound where
    every reader of them stops at the same row: `gmm` and `tgmm` (bounded by
    the sizes; in the one tile the last group shares with the rest `gmm`
    stores a group's rows under a select and `tgmm` selects both operands'
    rows by group before it contracts over them, so no product meets a row
    past the group's last), and the kernels of `_live_rows_call`."""
    w = w.astype(rows.dtype)
    rest = group_sizes.shape[0] - w.shape[0]
    assert rest in (0, 1), (group_sizes.shape, w.shape)
    tiles = _gmm_tiles(rows.shape[0], *w.shape[1:], w.shape[0]) if pallas else None
    if tiles is not None:
        offset = jnp.zeros((), jnp.int32) if rest else None
        return _gmm(rows, w, group_sizes, offset, tiles, context.interpret_default())
    if rest:
        w = jnp.concatenate([w, jnp.zeros((1,) + w.shape[1:], w.dtype)])
    return lax.ragged_dot(
        rows, w, group_sizes, preferred_element_type=rows.dtype
    )


def _note_tiles(matrices, m: int, pallas: bool) -> None:
    """Note which tiles the grouped matmuls of the expert node being
    lowered take, its `grouped_matmul_tiles` (`kernels/context.note`): asked
    of `_gmm_tiles` as `_grouped_matmul` asks, here because the window
    functions are jitted and traced once for every node of one shape.
    `matrices`: name -> [G, K, N] (`w1`, `w3`, `w2`), `m`: rows a call.
    The value is `{"<matrix>/<call>": entry}`: for each of the node's
    matrices and each of a grouped matmul's three calls (`forward`,
    `input_gradient`, `weight_gradient`) the call's `shape` (rows,
    contraction, columns, in the kernel's own names), the `tile` it was
    given and `padded_over_true`, the contraction and column sides in whole
    tiles over their true size (the row side is the data's). A node on XLA's
    `ragged_dot` notes nothing."""
    entries = {}
    for name, w in matrices.items():
        groups, k, n = w.shape
        tiles = _gmm_tiles(m, k, n, groups) if pallas else None
        if tiles is None:
            continue
        shapes = ((m, k, n), (m, n, k), (m, k, n))
        for call, shape, tile in zip(_CALLS, shapes, tiles):
            entries[f"{name}/{call}"] = {
                "shape": shape, "tile": tile,
                "padded_over_true": round(
                    _whole_tiles(shape[1], tile[1]) * _whole_tiles(shape[2], tile[2])
                    / (shape[1] * shape[2]), 4
                ),
            }
    if entries:
        context.note("grouped_matmul_tiles", entries)


def _note_held_sums(pallas: bool, attrs, held: int, n: int, window: int, dtype, sites, ws):
    """The forms (`_held_sum_form`) of a held share's two sums of a window's
    rows over their tokens, the forward's output and the backward's gradient
    of x2, in that order, noted as the node's `held_row_sums` as well
    (`kernels/context.note`) for the reason `_note_tiles` has, with the rows
    each row stage of the window runs over (`_window_stages`).
    `held`: the share's experts; `n`: the tokens; `dtype`: the rows';
    `sites`: name -> (row width, the sum's dtype); `ws`: the share's
    matrices. The value is `{"forward": entry, "backward": entry, "stages":
    {...}}`: for the forward's sum of a window's output rows over their
    tokens and for the backward's sum of the rows' cotangent, the `form`
    (`pallas`: the kernel `held_rows_sum`; `xla`: a scatter-add), the
    window's rows (`window_rows`), the row's `width` and `dtype`, the sum's
    (`sum_dtype`) and the tokens a program of the kernel (`token_tile`, None
    on `xla`); under `stages`, for each row stage of a window between the
    grouped matmuls and the sums (`rows_in`, `zero_fill`, `elementwise`,
    `lanes`) `live` where it stops at the share's last row and `window`
    where it runs over the whole pass, so that a trace says whether a cell's
    passes cost their rows or their size."""
    k, entries = attrs.num_select, {}
    for name, (width, sum_dtype) in sites.items():
        form = _held_sum_form(pallas, n, k, width, dtype)
        entries[name] = {
            "form": form, "window_rows": window, "width": width,
            "dtype": jnp.dtype(dtype).name,
            "sum_dtype": jnp.dtype(sum_dtype).name,
            "token_tile": _held_sum_tile(n, k, width) if form == "pallas" else None,
        }
    forms = tuple(entry["form"] for entry in entries.values())
    context.note(
        "held_row_sums",
        dict(entries, stages=_window_stages(pallas, forms, attrs, held, n * k, ws))
    )
    return forms


# XLA's TPU gather and scatter move one element at a time: 8.8-10.2 ns each
# on a v5e whatever the element (a float of `[4096, 512]`, a one into 9
# bins; PERF.md section 5). Comparing an index with an iota and reducing is
# dense VPU work that XLA keeps in one fusion each way, 1.0 ps a compared
# element at the widest router here (512; the others 9 to 128), and exact at
# any width (PERF.md section 6, PR 40, has the widths at which the two tie).


def _pick_columns(values: jnp.ndarray, index: jnp.ndarray) -> jnp.ndarray:
    """values[n, index[n, j]]: [N, E], [N, k] -> [N, k], the k columns of a
    row distinct: `sum_e where(index[n, j] == e, values[n, e], 0)`, one term
    of which is not zero, so the sum is the picked value to the bit, and
    autodiff's transpose `sum_j where(index[n, j] == e, g[n, j], 0)` is the
    gather's scatter-add to the bit because a row's columns are distinct."""
    e = values.shape[-1]
    hit = index[:, :, None] == lax.broadcasted_iota(index.dtype, (1, 1, e), 2)
    return jnp.sum(jnp.where(hit, values[:, None, :], 0), axis=-1)


def _count_keys(keys: jnp.ndarray, bins: int) -> jnp.ndarray:
    """How many of `keys` [M] fall in each of `bins` bins, [bins] int32:
    `sum_i (keys[i] == b)`, so a key outside the bins is counted in none."""
    hit = keys[None, :] == lax.broadcasted_iota(keys.dtype, (bins, 1), 0)
    return jnp.sum(hit, axis=1, dtype=jnp.int32)


def route(attrs: ExpertsAttrs, x2: jnp.ndarray, gate_w: jnp.ndarray,
          select_bias=None):
    """The router, in float32 whatever x2's dtype: (logits [N, E],
    probabilities or sigmoid scores [N, E], selected experts [N, k], their
    weights [N, k]). `select_bias` [E] (sigmoid scoring) moves the choice
    and nothing else, and takes no gradient. The ranking gives indices only
    (no JVP of `top_k` is built); the weights are picked from the scores by
    `_pick_columns`."""
    logits = x2.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    if attrs.scoring == "sigmoid":
        scores = choice = jax.nn.sigmoid(logits)
        if select_bias is not None:
            choice = scores + select_bias.astype(jnp.float32)
    else:
        scores = choice = jax.nn.softmax(logits, axis=-1)
    _, topi = lax.top_k(lax.stop_gradient(choice), attrs.num_select)
    topv = _pick_columns(scores, topi)
    if attrs.renormalize:
        # a buffer of their own, as the gather's were: fused into the pick,
        # the sum over k adds its terms in another order
        topv = lax.optimization_barrier(topv)
    if attrs.scoring == "sigmoid":
        if attrs.renormalize:
            topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
        return logits, scores, topi, topv * attrs.routed_scale
    if attrs.renormalize:
        topv = topv / topv.sum(axis=-1, keepdims=True)
    if attrs.routed_scale != 1.0:
        topv = topv * attrs.routed_scale
    return logits, scores, topi, topv


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
    of the combine (the caller sums the parts), as for an op that holds a
    share (`attrs.held_experts`).
    per_shard: called from the body of a shard_map, a per-device program
    whatever the enclosing trace."""
    gate_w, rest = weights[0], list(weights[1:])
    select_bias = rest.pop(0) if attrs.selection_bias else None
    w_down = rest.pop(0) if attrs.latent_size else None
    w1 = rest.pop(0)
    w3 = rest.pop(0) if attrs.gated else None
    b1 = rest.pop(0) if attrs.use_bias else None
    w2 = rest.pop(0)
    b2 = rest.pop(0) if attrs.use_bias else None
    w_up = rest.pop(0) if attrs.latent_size else None
    shared = rest  # ws1[, ws3], ws2 of the shared expert, or nothing

    x2 = x.reshape(-1, x.shape[-1])
    n = x2.shape[0]
    e, k = attrs.num_experts, attrs.num_select
    with jax.named_scope("router"):
        logits, probs, topi, topv = route(attrs, x2, gate_w, select_bias)
    # the rows the experts read: x's own, or their latent image
    z2 = x2
    if w_down is not None:
        with jax.named_scope("latent"):
            z2 = x2 @ w_down.astype(x2.dtype)
    pallas = _pallas_allowed(per_shard)
    with jax.named_scope("routed"):
        flat_e = topi.reshape(-1).astype(jnp.int32)  # [N*k]
        if attrs.held_experts is not None or expert_shard is not None:
            assert None in (attrs.held_experts, expert_shard), (
                "a held share is not sharded again"
            )
            experts = {"w1": w1, "w3": w3, "b1": b1, "w2": w2, "b2": b2}
            out, here, windows = _held_rows_forward(
                attrs, attrs.held_experts or expert_shard, z2, flat_e, topv,
                {name: w for name, w in experts.items() if w is not None},
                pallas,
            )
            if attrs.held_experts is not None:
                from flexflow_tpu.observability import routing

                routing.record(here, n * k, windows)
            counts = None
            if attrs.lambda_bal > 0:
                counts = _count_keys(flat_e, e)
        else:
            out, counts = _all_rows_forward(
                attrs, z2, flat_e, topv, w1, w3, b1, w2, b2, pallas
            )
    if w_up is not None:
        # once, on the combined rows: float32 in, float32 out, the operands
        # of the product in the compute dtype like every other's
        with jax.named_scope("latent"):
            out = jnp.dot(
                out.astype(x2.dtype), w_up.astype(x2.dtype),
                preferred_element_type=jnp.float32,
            )
    return _finish(attrs, out, x, shared, counts, probs, logits)


def _all_rows_forward(attrs, x2, flat_e, topv, w1, w3, b1, w2, b2, pallas):
    """The routed part of a call that has every expert's matrices: (sum over
    a token's k decisions of weight * expert(x) [N, out] float32, decisions
    per expert [E]). `x2` [N, D]: the rows the experts read; `flat_e` [N*k]:
    the chosen experts in (token, select) order."""
    (n, k), e = topv.shape, attrs.num_experts
    # -- dispatch: decisions in (token, select) order, sorted by expert ----
    order = jnp.argsort(flat_e, stable=True)
    inverse = jnp.argsort(order)  # decision -> its row after the sort
    counts = _count_keys(flat_e, e)
    if attrs.capacity_factor is not None:
        cap = expert_capacity(n, e, k, attrs.capacity_factor)
        first_row = jnp.cumsum(counts) - counts
        rank = jnp.arange(n * k, dtype=jnp.int32) - first_row[flat_e[order]]
        kept = (rank < cap)[inverse].reshape(n, k)
        topv = jnp.where(kept, topv, 0.0)

    _note_tiles(
        {name: w for name, w in (("w1", w1), ("w3", w3), ("w2", w2)) if w is not None},
        n * k, pallas,
    )

    def grouped(rows, w):
        return _grouped_matmul(rows, w, counts, pallas)

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
    return jnp.einsum("nk,nko->no", topv, y), counts


def _finish(attrs, out, x, shared, counts, probs, logits):
    """The node's outputs from its routed part `out` [N, out] float32: the
    shared expert added, x's leading dims and dtype back, and the auxiliary
    scalar where the attrs ask for one (`counts` [E]: decisions per expert
    of the whole router)."""
    n, e = probs.shape
    out = _add_shared_expert(attrs, out, x, shared)
    out = out.reshape(*x.shape[:-1], out.shape[-1]).astype(x.dtype)

    if not attrs.has_aux:
        return [out]
    aux = jnp.zeros((), jnp.float32)
    if attrs.lambda_bal > 0:
        # gated: f_e = tokens that chose e / N; legacy: decisions / (N k)
        # (ExpertsAttrs docstring). Counts carry no gradient.
        frac = counts.astype(jnp.float32) / (
            n if attrs.gated else n * attrs.num_select
        )
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


def _add_shared_expert(attrs: ExpertsAttrs, out, x, shared):
    """out [N, out] float32 plus the shared expert's dense path on every
    token of x [.., D] (`shared`: ws1[, ws3], ws2[, w_sg]; nothing where
    the op has none), times sigmoid(x w_sg) where it has a gate
    (`ExpertsAttrs.shared_gate`)."""
    if not shared:
        return out
    with jax.named_scope("shared"), jax.named_scope("shared_expert"):
        x2 = x.reshape(-1, x.shape[-1])
        w_sg = shared[-1] if attrs.shared_gate else None
        ws2 = shared[-2] if attrs.shared_gate else shared[-1]
        hs = x2 @ shared[0].astype(x2.dtype)
        if attrs.activation is not None:
            hs = attrs.activation.apply(hs)
        if attrs.gated:
            hs = hs * (x2 @ shared[1].astype(x2.dtype))
        y = (hs @ ws2.astype(x2.dtype)).astype(jnp.float32)
        if w_sg is not None:
            # one logit a token: a [N, D] x [D, 1] product accumulated in
            # float32, the sigmoid in float32
            logit = jnp.matmul(
                x2, w_sg.astype(x2.dtype), preferred_element_type=jnp.float32
            )
            y = y * jax.nn.sigmoid(logit)
        return out + y


# -- the held rows back to their tokens: a sum, not a scatter-add ------------
#
# XLA's TPU scatter-add takes a window's rows one after another, because two
# of them may land on one token: 217 ns a row on a v5e against 22 for the
# gather of as many (PERF.md section 5). `held_rows_sum` turns the movement
# round: the window's rows are listed in TOKEN order (one sort of `window`
# decisions with their window row as payload, `_token_order`: the inverse of
# the window's part of the share's sort), a program takes a tile of tokens,
# and every row of the tile is one DMA from HBM into a VMEM scratch, all of
# a tile's copies in flight together. No token has two writers and nothing
# is read that is not held, so the scalar work follows the held rows and not
# the N k decisions.
#
# Mosaic copies whole (8, 128) tiles, and a row of a `[window, width]` array
# is an eighth (bf16: a sixteenth) of each of its tiles. `held_rows_lanes`
# therefore writes the rows once more with a row's 128-lane groups as
# SUBLANES, so that a row is tiles of its own that lie together in HBM, and
# packs two bf16 columns into one 32-bit word on the way (a row's first half
# of the groups in the low halves, the second in the high): half the bytes
# of the float32 rows XLA's scatter-add was given, in HBM and on the wire.
# A bf16 is the upper half of its float32, so the sum takes a word apart
# with a shift and a mask and adds in float32.

# Bytes of VMEM the sum's slots may take: one a decision of a token tile, so
# that a tile whose every decision is held fits whatever the router does.
# The accumulator and the out block's two buffers come on top; the limit
# handed to the compiler is the sum with a quarter to spare.
_HELD_SUM_SLOT_BYTES = 4 * 2**20
# Entries a trip of the sum's three loops over a tile's rows (copy, wait, add).
_HELD_SUM_UNROLL = 8


def _word_groups(width: int):
    """(pairs, sublanes): the 128-lane groups of a row `width` wide come in
    `pairs` pairs (group p with group p + pairs, the last alone where they
    are odd), a 128-word sublane each, a row in `sublanes` whole 8-sublane
    tiles of them."""
    pairs = -(-width // 256)
    return pairs, _whole_tiles(pairs, 8)


def _held_sum_tile(n: int, k: int, width: int):
    """Tokens a program of `held_rows_sum` for `n` tokens of `k` decisions
    and rows `width` wide: the largest power of two from 512 down to 16 that
    divides n and whose `k` slots a token fit `_HELD_SUM_SLOT_BYTES`; None
    where there is none, or the width is not whole 128-lane tiles."""
    if width % 128:
        return None
    slot = 4 * 128 * _word_groups(width)[1]
    return next(
        (
            tile for tile in (512, 256, 128, 64, 32, 16)
            if n % tile == 0 and k * tile * slot <= _HELD_SUM_SLOT_BYTES
        ),
        None,
    )


def _held_sum_form(pallas: bool, n: int, k: int, width: int, dtype) -> str:
    """Which form the sum of a window's rows over their tokens takes:
    "pallas" (`held_rows_sum`) where `_pallas_allowed`, the rows are bf16
    and the shape has a tile, "xla" (the scatter-add) elsewhere: the CPU
    mesh, a global-view SPMD trace, float32 compute, an odd width."""
    return (
        "pallas"
        if pallas and dtype == jnp.bfloat16 and _held_sum_tile(n, k, width)
        else "xla"
    )


def _token_order(decision, valid, decisions: int):
    """A window's rows in token order: (`decision` [window] sorted ascending
    with the masked rows' last, as `decisions`; the window row of each
    [window]). A decision is `token * k + j`, so a token's rows lie together
    with j ascending."""
    window = decision.shape[0]
    return lax.sort(
        (
            jnp.where(valid, decision, decisions),
            jnp.arange(window, dtype=jnp.int32),
        ),
        num_keys=1,
    )


def _live_tiles(live, tm: int):
    """What a kernel over a window's row tiles is given so that it stops at
    the share's last row: (`live` as the int32 [1] scalar prefetch, the row
    block index of grid step i). The tiles past the `live`-th row all name
    the last live tile, so the pipeline fetches nothing new for them and
    writes nothing back, and the kernel's body skips them (`pl.when`): their
    rows of the result are NOT WRITTEN (`_grouped_matmul` says who may read
    them)."""
    live = jnp.asarray(live, jnp.int32).reshape(1)

    def block(i, live_ref):
        return jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0) // tm), 0

    return live, block


def _write_lanes(part, out_ref, tm: int, width: int):
    """out_ref [tm * sublanes, 128] uint32 from `part(g)`, the rows' g-th
    128-lane group as float32 [tm, 128]: row r's p-th word sublane at `r *
    sublanes + p`, group p's bf16 bits in the low halves and group p +
    pairs' in the high."""
    groups = width // 128
    pairs, sublanes = _word_groups(width)

    def bits(g):  # a bf16 is its float32's upper half
        return pltpu.bitcast(part(g), jnp.uint32)

    for p in range(pairs):
        words = bits(p) >> 16
        if p + pairs < groups:
            words = words | bits(p + pairs)
        out_ref[pl.ds(p, tm, stride=sublanes), :] = words


def _held_rows_lanes_kernel(rows_ref, out_ref):
    """rows_ref [tm, width] bf16 -> out_ref (`_write_lanes`)."""
    tm, width = rows_ref.shape
    _write_lanes(
        lambda g: rows_ref[:, g * 128:(g + 1) * 128].astype(jnp.float32),
        out_ref, tm, width,
    )


def _live_rows_lanes_kernel(live_ref, rows_ref, *refs):
    """`_held_rows_lanes_kernel` of the tiles up to the `live_ref[0]`-th row,
    the others left as they are; `refs`: `out_ref`, after a second
    `[tm, width]` operand where the rows are a sum of two (added in bf16, as
    XLA adds two cotangents: the float32 sum rounded)."""
    *add_ref, out_ref = refs
    tm, width = rows_ref.shape

    def part(g):
        rows = rows_ref[:, g * 128:(g + 1) * 128].astype(jnp.float32)
        if add_ref:
            rows = rows + add_ref[0][:, g * 128:(g + 1) * 128].astype(jnp.float32)
            rows = rows.astype(jnp.bfloat16).astype(jnp.float32)
        return rows

    @pl.when(pl.program_id(0) * tm < live_ref[0])
    def _():
        _write_lanes(part, out_ref, tm, width)


@functools.partial(jax.jit, static_argnums=(1,))
def held_rows_lanes(rows, interpret: bool = False, live=None, addend=None):
    """rows [window, width] bf16 as uint32 [window * sublanes, 128]
    (`_word_groups`): a row is `sublanes` sublanes of words that lie
    together, which one DMA can take. The sublanes past a row's last pair
    are not written, and nothing reads them. With `live` (an int32 scalar)
    neither are the tiles of rows past the `live`-th (`_live_tiles`), which
    `held_rows_sum` never asks for, and the rows are `rows + addend` where
    there is one."""
    window, width = rows.shape
    sublanes = _word_groups(width)[1]
    tm = 256 if window % 256 == 0 else 128
    assert window % tm == 0 and width % 128 == 0, rows.shape
    assert rows.dtype == jnp.bfloat16, rows.dtype
    if live is None:
        assert addend is None
        return pl.pallas_call(
            _held_rows_lanes_kernel,
            out_shape=jax.ShapeDtypeStruct((window * sublanes, 128), jnp.uint32),
            grid=(window // tm,),
            in_specs=[pl.BlockSpec((tm, width), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((tm * sublanes, 128), lambda i: (i, 0)),
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
            interpret=interpret,
            name="held_rows_lanes",
        )(rows)
    live, block = _live_tiles(live, tm)
    operands = (rows,) if addend is None else (rows, addend)
    return pl.pallas_call(
        _live_rows_lanes_kernel,
        out_shape=jax.ShapeDtypeStruct((window * sublanes, 128), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(window // tm,),
            in_specs=[pl.BlockSpec((tm, width), block) for _ in operands],
            out_specs=pl.BlockSpec((tm * sublanes, 128), block),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="held_rows_lanes",
    )(live, *operands)


def _held_rows_sum_kernel(tokens_ref, rows_ref, starts_ref, *refs):
    """One tile of tokens. `tokens_ref` / `rows_ref` [window] (SMEM): the
    window's rows in token order, token and window row; `starts_ref`: the
    first entry of each tile, and one past the last's; `refs`: `weight_ref`
    [window] float32 (SMEM, by window row) where the rows are weighted, then
    `src_ref` [window * sublanes, 128] uint32 (`held_rows_lanes`), which
    stays in HBM, `out_ref` [tile, width], the slots `buf_ref` [k * tile *
    sublanes, 128], one an entry, the float32 accumulator `acc_ref` [tile *
    2 * sublanes, 128] (a token's low halves, then its high halves) and the
    copies' semaphore. A 128-lane group of the result is every `2 *
    sublanes`-th sublane of the accumulator."""
    *weight_ref, src_ref, out_ref, buf_ref, acc_ref, sem = refs
    i = pl.program_id(0)
    tile, width = out_ref.shape
    pairs, sublanes = _word_groups(width)
    lo, hi = starts_ref[i], starts_ref[i + 1]

    def row_copy(row, slot):
        return pltpu.make_async_copy(
            src_ref.at[pl.ds(pl.multiple_of(row * sublanes, 8), sublanes)],
            buf_ref.at[pl.ds(pl.multiple_of(slot * sublanes, 8), sublanes)],
            sem,
        )

    def begin(at):
        row_copy(rows_ref[at], at - lo).start()

    def wait(at):
        row_copy(0, 0).wait()  # every copy is one row: any stands for all

    def add(at):
        words = buf_ref[pl.ds(pl.multiple_of((at - lo) * sublanes, 8), sublanes), :]
        parts = (words << 16, words & jnp.uint32(0xFFFF0000))
        r = (tokens_ref[at] - i * tile) * 2 * sublanes
        for half, part in enumerate(parts):
            part = pltpu.bitcast(part, jnp.float32)
            if weight_ref:
                part = weight_ref[0][rows_ref[at]] * part
            here = pl.ds(pl.multiple_of(r + half * sublanes, 8), sublanes)
            acc_ref[here, :] += part

    def each(entry):
        # entry(at) for at in [lo, hi): `_HELD_SUM_UNROLL` a trip, so that the
        # scalar unit overlaps one entry's loads with the next's, then the rest
        trips = (hi - lo) // _HELD_SUM_UNROLL

        def trip(j, carry):
            for u in range(_HELD_SUM_UNROLL):
                entry(lo + j * _HELD_SUM_UNROLL + u)
            return carry

        def one(at, carry):
            entry(at)
            return carry

        lax.fori_loop(0, trips, trip, 0)
        lax.fori_loop(lo + trips * _HELD_SUM_UNROLL, hi, one, 0)

    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
    each(begin)
    each(wait)
    each(add)
    for g in range(width // 128):
        at_sublane = g if g < pairs else sublanes + g - pairs
        out_ref[:, g * 128:(g + 1) * 128] = acc_ref[
            pl.ds(at_sublane, tile, stride=2 * sublanes), :
        ].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def held_rows_sum(src, decisions_sorted, rows_sorted, weight, n: int, k: int,
                  width: int, dtype, interpret: bool = False):
    """[n, width] in `dtype`: token t's row the float32 sum, j ascending, of
    the rows `weight[r] * src[r]`, r = `rows_sorted[i]`, whose
    `decisions_sorted[i] // k` is t (`_token_order`'s pair; an entry of `n *
    k` or more is no row). `src`: `held_rows_lanes` of bf16 rows `width`
    wide; `weight` [window] float32, or None for the rows as they are."""
    sublanes = _word_groups(width)[1]
    tile = _held_sum_tile(n, k, width)
    assert tile and src.dtype == jnp.uint32, (src.shape, src.dtype, n, k)
    tokens = decisions_sorted // k
    bounds = jnp.arange(0, n + 1, tile, dtype=jnp.int32)
    starts = jnp.sum(tokens[None, :] < bounds[:, None], axis=1, dtype=jnp.int32)
    scalars = (tokens, rows_sorted, starts) + (() if weight is None else (weight,))
    slots, accumulator = k * tile * sublanes, 2 * tile * sublanes
    vmem = 4 * 128 * (slots + accumulator) + 2 * tile * width * jnp.dtype(dtype).itemsize
    return pl.pallas_call(
        _held_rows_sum_kernel,
        out_shape=jax.ShapeDtypeStruct((n, width), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n // tile,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, width), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((slots, 128), jnp.uint32),
                pltpu.VMEM((accumulator, 128), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem * 5 // 4,
        ),
        interpret=interpret,
        name="held_rows_sum",
    )(*scalars, src)


# -- a window's row stages stop at the share's last row -----------------------
#
# A window is a static `[window, .]` buffer and a share fills a part of it:
# 23% of the decisions reach Mellum2's 16 held experts where the pass holds
# 56% (PERF.md section 6, PR 61). `gmm` and `tgmm` visit the share's row
# tiles alone (`group_sizes`), `held_rows_sum` its rows alone; what ran
# between them ran over all `window` rows: megablox's zero fill of the rows
# no matrix met, the mask of the gathered rows, the activation and its
# backward, the weighted cotangent, the lane rewrites. The share's rows are
# a PREFIX of the window (`order` puts them first), so "the share's rows
# alone" is one scalar, `live`, and each of those stages is a Pallas kernel
# over row tiles that skips the tiles past it (`_live_tiles`) or is not run
# at all (the fills and the mask), where `_window_stages` says the pass is
# large enough for it. The buffers keep their shapes; the rows
# past `live` are not written and not read (`_grouped_matmul`). What still
# runs over the window is XLA's gathers of whole rows (`x2[token]`, and the
# cotangent's `g_out[token]` in the backward: 14-17 ns a row, where one DMA
# a row costs `held_rows_sum` 32-37) and its index arithmetic.

# Bytes of VMEM the blocks of one `_live_rows_call` may take, both of the
# pipeline's buffers counted; the limit handed to the compiler is twice that
# and 4 MB, for the float32 temporaries of a body.
_LIVE_BLOCK_BYTES = 12 * 2**20


def _live_rows_call(body, name: str, live, operands, results, over=None):
    """`body(*blocks)` -> a block a result, over the row tiles of `operands`
    ([window, w] arrays) up to the `live`-th row: one Pallas kernel `name`
    whose program takes `[tm, w]` of every operand and writes `[tm, w]` of
    every result (`results`: (width, dtype) each), the tiles past `live`
    skipped (`_live_tiles`). `over`: operand -> the result written over it
    (an operand of the result's width and dtype that nothing reads after
    this call: XLA then gives the result no buffer of its own, 170 MB of
    Mellum2's compiled step, and copies the operand where something does);
    such a result's rows past `live` hold what the operand held."""
    window = operands[0].shape[0]
    row_bytes = sum(
        a.shape[1] * a.dtype.itemsize for a in operands
    ) + sum(w * jnp.dtype(d).itemsize for w, d in results)
    tm = next(
        (t for t in (512, 256) if window % t == 0
         and 2 * t * row_bytes <= _LIVE_BLOCK_BYTES), 128
    )
    live, block = _live_tiles(live, tm)

    def kernel(live_ref, *refs):
        ins, outs = refs[:len(operands)], refs[len(operands):]

        @pl.when(pl.program_id(0) * tm < live_ref[0])
        def _():
            values = body(*(ref[...] for ref in ins))
            for ref, value in zip(outs, values):
                ref[...] = value.astype(ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((window, w), d) for w, d in results],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(window // tm,),
            in_specs=[pl.BlockSpec((tm, a.shape[1]), block) for a in operands],
            out_specs=[pl.BlockSpec((tm, w), block) for w, _ in results],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * tm * row_bytes + 4 * 2**20,
        ),
        # the live count is input 0
        input_output_aliases={1 + i: o for i, o in (over or {}).items()},
        interpret=context.interpret_default(),
        name=name,
    )(live, *operands)


def _expert_hidden(attrs, h1, h3, c1):
    """What an expert's second matrix reads, from the first's product `h1`
    (`c1`: its bias rows, or None) and, where the expert is gated, the
    third's `h3` (else None): a row at a time, in the rows' dtype."""
    h = h1 if c1 is None else h1 + c1
    if attrs.activation is not None:
        h = attrs.activation.apply(h)
    return h if h3 is None else h * h3


def _hidden_blocks(attrs, present, blocks):
    """`_expert_hidden` of the operands that are there, in float32:
    `present` says which of (h1, h3, c1) `blocks` holds, in that order. A
    stage kernel computes its tile in float32 and rounds once, where it
    stores, as XLA's fusion of the same chain does on the chip (a v5e's VPU
    has no bf16 arithmetic, and Mosaic there takes neither a bf16 `logistic`
    nor a bf16 comparison)."""
    blocks = (block.astype(jnp.float32) for block in blocks)
    return _expert_hidden(attrs, *(next(blocks) if p else None for p in present))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _live_hidden(attrs, live, h1, h3, c1):
    """`_expert_hidden` of the rows up to the `live`-th, a kernel each way
    (`experts_hidden_fwd`, `experts_hidden_bwd`: the same expressions on a
    tile of rows and their `jax.vjp`, in float32, each result rounded once
    to the rows' dtype where it is stored: `_hidden_blocks`)."""
    present = tuple(a is not None for a in (h1, h3, c1))
    given = [a for a in (h1, h3, c1) if a is not None]
    return _live_rows_call(
        lambda *blocks: (_hidden_blocks(attrs, present, blocks),),
        "experts_hidden_fwd", live, given, [(h1.shape[1], h1.dtype)],
    )[0]


def _live_hidden_fwd(attrs, live, h1, h3, c1):
    return _live_hidden(attrs, live, h1, h3, c1), (live, h1, h3, c1)


def _live_hidden_bwd(attrs, kept, g):
    live, *operands = kept
    present = tuple(a is not None for a in operands)
    given = [a for a in operands if a is not None]

    def body(g, *blocks):
        return jax.vjp(
            lambda *blocks: _hidden_blocks(attrs, present, blocks), *blocks
        )[1](g.astype(jnp.float32))

    # each operand's gradient over the operand: its last reader is this call
    grads = iter(_live_rows_call(
        body, "experts_hidden_bwd", live, [g, *given],
        [(a.shape[1], a.dtype) for a in given],
        over={1 + j: j for j in range(len(given))},
    ))
    return (None,) + tuple(next(grads) if p else None for p in present)


_live_hidden.defvjp(_live_hidden_fwd, _live_hidden_bwd)


def _masked_rows(x2, token, valid):
    """A window's token rows, `x2[token]` with the masked rows zero."""
    return jnp.where(valid[:, None], x2[token], 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _window_rows(x2, token, valid, live, decisions_sorted, rows_sorted, k, readers):
    """A window's token rows whose transpose is no scatter-add: with the
    window's rows in token order (`_token_order`) it is `held_rows_sum` of
    the rows' cotangent, summed in float32 and rounded once to x2's dtype.
    `valid` [window]: `_masked_rows`; None: `x2[token]` as gathered, for
    readers that stop at the `live`-th row, and so does the transpose.
    The rows come `readers` times over (one array), so that two readers'
    cotangents arrive apart and are added where they are rewritten
    (`held_rows_lanes`), not in a pass of their own."""
    del live, decisions_sorted, rows_sorted, k
    rows = x2[token] if valid is None else _masked_rows(x2, token, valid)
    return (rows,) * readers


def _window_rows_fwd(x2, token, valid, live, decisions_sorted, rows_sorted, k, readers):
    rows = _window_rows(
        x2, token, valid, live, decisions_sorted, rows_sorted, k, readers
    )
    return rows, (live, decisions_sorted, rows_sorted, x2.shape[0])


def _window_rows_bwd(k, readers, kept, g):
    live, decisions_sorted, rows_sorted, n = kept
    assert readers in (1, 2), readers
    interpret = context.interpret_default()
    g_x2 = held_rows_sum(
        held_rows_lanes(g[0], interpret, live, *g[1:]), decisions_sorted,
        rows_sorted, None, n, k, g[0].shape[1], g[0].dtype, interpret,
    )
    return g_x2, None, None, None, None, None


_window_rows.defvjp(_window_rows_fwd, _window_rows_bwd)


def held_window_rows(
    decisions: int, held: int, experts: int, factor: Optional[float] = None
) -> int:
    """Rows one pass of `_held_rows_forward` takes: a quarter more than a
    uniform router sends the held experts (`factor` times what it sends them
    where the attrs give a `held_window_factor`), in whole 128-row tiles, and
    never more than there are decisions."""
    expected = -(-decisions * held // experts)
    rows = expected + expected // 4 if factor is None else math.ceil(
        expected * factor
    )
    return min(decisions, max(128, -(-rows // 128) * 128))


def _window_stages(pallas: bool, forms, attrs, held: int, decisions: int, ws) -> dict:
    """Which rows each row stage of a held window runs over, read from what
    the window is given: "live", the share's rows alone, or "window", XLA's
    forms over the whole pass. `rows_in`: the mask of the gathered rows
    ("live": none is needed; the gather itself is XLA's over the window
    either way); `zero_fill`: megablox's fill of the rows no matrix met
    ("live": not run); `elementwise`: the activation, the gate's product and
    the weighted cotangent; `lanes`: `held_rows_lanes`.

    "live" where the forward's sum takes the kernel (`_held_sum_form`: on
    the chip, bf16 rows of whole lane tiles), every matrix's grouped matmul
    has its tiles (`_gmm_tiles`) AND the pass (`held_window_rows`, whatever
    rule or attribute sized it) is at least half again the rows a uniform
    router sends the share's `held` experts of `decisions`. That size is
    where the stages were measured to pay (PERF.md section 6, PR 61, a
    v5e): Mellum2's step +1.8 and +2.1% `tokens_per_s` at passes of 1.5
    times the share and +5.9 to +6.2% at its own 2.25, and at a quarter
    over, the rule of every other held graph, -0.75 to +0.56% over six
    cells, under the 1% the benchmark resolves. What the condition guards
    is the compiled step's BYTES: with the stages live at a quarter over,
    `step_hbm_gb` read -3.7 to +2.6% with no pattern in the pass, the
    widths or the tokens (the same LFM2 graph: +2.57% at two sequences a
    batch, over the benchmark's bound of 1%, and -2.0% at one). The part
    that moves is in none of XLA's buffers (they move by 13 MB): it is a
    term of the executable's temporary bytes that no dump of the compiler
    lists, 435 MB of LFM2's step before and 710 after, which follows
    whether an XLA operation or another kernel reads a kernel's result.
    Nothing a trace can read predicts it, so a pass whose gain the
    benchmark cannot resolve keeps XLA's forms, the parent program to the
    letter, and a graph whose passes grow past the size takes its compiles
    first (1.25 to 2.25 times in Mellum2's step: -1.2 to +0.35%)."""
    window = held_window_rows(
        decisions, held, attrs.num_experts, attrs.held_window_factor
    )
    uniform = -(-decisions * held // attrs.num_experts)
    live = pallas and forms[0] == "pallas" and 2 * window >= 3 * uniform and all(
        _gmm_tiles(window, *w.shape[1:], w.shape[0]) for w in ws.values()
        if w.ndim == 3
    )
    stages = dict.fromkeys(
        ("rows_in", "zero_fill", "elementwise", "lanes"),
        "live" if live else "window",
    )
    if forms[1] != "pallas":
        stages["rows_in"] = "window"  # the scatter-add's mask stays
    return stages


def _held_window(t, order, counts, x2, flat_w, ws, attrs, pallas: bool, forms):
    """The t-th window of a share's rows (`_held_rows_forward`: `order` the
    decisions with the share's first, `counts` [held] its decisions per
    expert, `forms` the two sums' forms), as a dict: `decision` and `token`
    [window], the router's `weight` [window] float32, the experts' outputs
    `y` [window, out], `valid` [window] (the share has a `t * window + i`-th
    row), their count `live` where the row stages stop at it
    (`_window_stages`, else None) and `by_token`, the window's rows in token
    order (`_token_order`'s pair where a sum takes the kernel, else None). A
    row's weight is zero where it is not valid, so a window past the share's
    last row adds nothing; what `y` holds there is zeros on the "window"
    stages and NOT WRITTEN on the "live" ones (`_grouped_matmul`)."""
    held, (n, decisions) = counts.shape[0], (x2.shape[0], order.shape[0])
    k = decisions // n
    window = held_window_rows(
        decisions, held, attrs.num_experts, attrs.held_window_factor
    )
    stages = _window_stages(pallas, forms, attrs, held, decisions, ws)
    bounded = stages["elementwise"] == "live"

    total = jnp.sum(counts)
    ends = jnp.cumsum(counts)
    lo = t * window
    at = lo + jnp.arange(window, dtype=jnp.int32)
    valid = at < total
    live = jnp.clip(total - lo, 0, window) if bounded else None
    if bounded:
        # `order[at]`, the last entry again past the end: a slice, where an
        # indexed read of `window` scalars is 7 ns each (PERF.md section 5)
        decision = lax.dynamic_slice(
            jnp.concatenate([order, jnp.broadcast_to(order[-1:], (window,))]),
            (lo,), (window,),
        )
    else:
        decision = order[jnp.minimum(at, decisions - 1)]
    token = decision // k
    by_token = None
    if "pallas" in forms:
        by_token = _token_order(decision, valid, decisions)
    readers = 2 if bounded and "w3" in ws else 1
    if forms[1] == "pallas":
        rows = _window_rows(
            x2, token, None if bounded else valid, live, *by_token, k, readers
        )
    else:
        rows = (_masked_rows(x2, token, valid),) * readers
    sizes = jnp.clip(
        jnp.minimum(ends, lo + window) - jnp.maximum(ends - counts, lo),
        0, window,
    )
    # `held` matrices for held + 1 sizes: the last is the window's rest
    rest = jnp.concatenate(
        [sizes, (window - jnp.sum(sizes))[None]]
    ).astype(jnp.int32)

    def grouped(rows, w):
        return _grouped_matmul(
            rows, w, sizes.astype(jnp.int32) if bounded else rest, pallas
        )

    def bias_rows(b):
        # each row's expert's bias, as a grouped matmul of a column of
        # ones (see `experts_forward`), the rest's rows zero; the select
        # changes no value and keeps the rows past the share's last out of
        # the bias's gradient where the stages leave them unwritten
        rows = _grouped_matmul(
            jnp.ones((window, 1), b.dtype), b[:, None, :], rest, pallas
        )
        return jnp.where(valid[:, None], rows, 0) if bounded else rows

    with jax.named_scope("grouped_matmul"):
        h = grouped(rows[0], ws["w1"])
        c1 = bias_rows(ws["b1"]) if "b1" in ws else None
        if bounded:
            h3 = grouped(rows[-1], ws["w3"]) if "w3" in ws else None
            h = _live_hidden(attrs, live, h, h3, c1)
        else:
            h = _expert_hidden(attrs, h, None, c1)
            if "w3" in ws:
                h = h * grouped(rows[-1], ws["w3"])
        y = grouped(h, ws["w2"])
        if "b2" in ws:
            y = y + bias_rows(ws["b2"])
    return {
        "decision": decision, "token": token, "valid": valid, "y": y,
        "weight": jnp.where(valid, flat_w[decision], 0.0),
        "by_token": by_token, "live": live,
    }


def _live_cotangents(live, y, weight, g_rows):
    """(the cotangent of `y` [window, out], the weights' [window] float32)
    under `sum(weight[:, None] * float32(y) * g_rows)`, `g_rows` [window,
    out] float32, for the rows up to the `live`-th: `weight * g_rows`
    rounded to y's dtype and the row sums of `g_rows * float32(y)`, one
    kernel (`experts_cotangent`) where XLA wrote the products out between
    its passes. A [window] vector goes in and comes out a 128-lane tile
    wide, every lane the same."""
    out = y.shape[1]

    def body(y, g_rows, weight):
        g_weight = jnp.sum(g_rows * y.astype(jnp.float32), axis=1, keepdims=True)
        return weight[:, :1] * g_rows, jnp.broadcast_to(g_weight, weight.shape)

    g_y, g_weight = _live_rows_call(
        body, "experts_cotangent", live,
        [y, g_rows, jnp.broadcast_to(weight[:, None], (y.shape[0], 128))],
        [(out, y.dtype), (128, jnp.float32)],
        over={0: 0},  # y's cotangent over y
    )
    return g_y, g_weight[:, 0]


# The two functions of the window index `t` that `_held_rows_forward` calls,
# each from two sites: straight-line for window 0 and from the body of the
# loop over the windows after it. Jitted, so that both sites, and every layer
# that calls them at one shape, share ONE trace and ONE Mosaic lowering of
# `gmm` / `tgmm`: a `pallas_call` is traced anew at every call site unless
# its caller is jitted (PERF.md, PR 33). The operations keep the scope of
# the call site they are inlined into.
@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _held_window_add(out, t, order, counts, x2, flat_w, ws, attrs, pallas, forms):
    """`out` [N, out] float32 with window t's rows added to their tokens:
    each token's own rows summed (`held_rows_sum`) and the sums added, or
    XLA's scatter-add (`forms[0]`). The first window's `out` is a constant
    zero, which XLA folds either way."""
    w = _held_window(t, order, counts, x2, flat_w, ws, attrs, pallas, forms)
    if forms[0] == "pallas":
        interpret = context.interpret_default()
        return out + held_rows_sum(
            held_rows_lanes(w["y"], interpret, w["live"]), *w["by_token"],
            w["weight"], x2.shape[0], order.shape[0] // x2.shape[0],
            out.shape[1], out.dtype, interpret,
        )
    return out.at[w["token"]].add(
        w["weight"][:, None] * w["y"].astype(jnp.float32)
    )


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _held_window_grads(g_out, t, order, counts, x2, flat_w, ws, attrs, pallas, forms):
    """Window t's part of the gradients of (x2, flat_w, ws) under the
    cotangent `g_out` [N, out] float32 of the share's output: the window
    recomputed from its inputs and differentiated by itself (x2's through
    `_window_rows`'s written transpose where `forms[1]` is the kernel).
    Where the row stages stop at the share's last row the weighted sum's
    own transpose is written out (`_live_cotangents`), the same products
    and sums as autodiff's, for the rows that are there."""

    def window(x2, flat_w, ws):
        return _held_window(t, order, counts, x2, flat_w, ws, attrs, pallas, forms)

    def window_dot(x2, flat_w, ws):
        w = window(x2, flat_w, ws)
        return jnp.sum(
            w["weight"][:, None] * w["y"].astype(jnp.float32) * g_out[w["token"]]
        )

    def outputs(x2, ws):
        w = window(x2, flat_w, ws)
        return w.pop("y"), w

    stages = _window_stages(
        pallas, forms, attrs, counts.shape[0], order.shape[0], ws
    )
    if stages["elementwise"] != "live":
        return jax.grad(window_dot, argnums=(0, 1, 2))(x2, flat_w, ws)
    y, transpose, w = jax.vjp(outputs, x2, ws, has_aux=True)
    g_y, g_weight = _live_cotangents(
        w["live"], y, w["weight"], g_out[w["token"]]
    )
    g_x2, g_ws = transpose(g_y)
    g_w = jnp.zeros_like(flat_w).at[w["decision"]].add(
        jnp.where(w["valid"], g_weight, 0.0)
    )
    return g_x2, g_w, g_ws


def _held_rows_forward(attrs, share, x2, flat_e, topv, ws, pallas: bool):
    """The routed part of a call that has the matrices of experts `first ..
    first + held - 1` (`share`) of the E its router chose among: sum over a
    token's decisions that landed on one of them of weight * expert(x),
    [N, out] float32; the other decisions add nothing. Also the decisions
    per expert of the share, [held], and the windows the call ran (int32
    scalar). `flat_e` [N*k]: the chosen experts in (token, select) order;
    `ws`: the share's `w1`, `w2` and, where the attrs have them, `w3`, `b1`,
    `b2`.

    Only the rows that do work are touched. The decisions are sorted with
    the share's first, by local expert (one stable sort of N k small keys),
    and taken in windows of `held_window_rows` rows: gather the window's
    token rows, run the grouped matmuls over the share's groups (`gmm` /
    `tgmm` visit those groups' row tiles), add the weighted results to
    their tokens (`held_rows_sum`, or a scatter-add: `_held_sum_form`).
    A window is a static buffer and the share's rows are a prefix of it.
    On the chip (`_window_stages`: "live") every stage between the gather
    and the sum stops at the share's last row, as the kernels at either end
    do: the rows past it are NOT WRITTEN in any intermediate (the gathered
    rows' are some token's, the rest whatever the buffer held) and may be
    read by nothing but a per-row operation that is itself not read there:
    `gmm`, `tgmm` and `held_rows_sum` take the share's rows alone, the
    stage kernels (`_live_rows_call`, `held_rows_lanes`) skip the tiles
    past them and work a row at a time in the one tile the last row
    shares, XLA's only touches are the bias rows' add (a row at a time)
    and selects under `valid` (the bias's and the weights' gradients).
    Nothing sums over rows but `tgmm`, which selects its operands' rows by
    group first. Elsewhere ("window": the CPU mesh, a global-view SPMD
    trace, float32 compute, widths the kernels do not take, a pass under
    half again its uniform share) the rest of a
    window is zeros: megablox fills what no matrix met, the gathered rows
    are masked, and XLA's passes run over the whole window.
    A uniform router fills less than
    one window, so the first window is straight-line code and its results
    ARE the accumulators: nothing is zero-filled and nothing added to the
    fill. A router that sends this share more takes the windows after the
    first in a loop whose trip count is the data's, each added to what the
    first gave, so nothing is dropped that the attrs' capacity keeps and no
    later window without a row of the share is run. (A share that no
    decision reached still runs the first, whose every row is masked.) The
    backward pass is the same: each window recomputed from x and the
    routing and differentiated by itself (`_held_window_grads`), the first
    window's gradients the accumulator of the later ones', so what the
    forward keeps is its inputs."""
    first, held = share
    n, k = topv.shape
    decisions = n * k
    window = held_window_rows(
        decisions, held, attrs.num_experts, attrs.held_window_factor
    )
    local = flat_e - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)  # the share's decisions first
    counts = _count_keys(key, held + 1)
    flat_w = topv.reshape(-1)
    if attrs.capacity_factor is not None:
        # a decision's rank within its expert is "earlier tokens first"
        cap = expert_capacity(n, attrs.num_experts, k, attrs.capacity_factor)
        first_row = jnp.cumsum(counts) - counts
        rank = jnp.arange(decisions, dtype=jnp.int32) - first_row[key[order]]
        kept = jnp.zeros((decisions,), bool).at[order].set(rank < cap)
        flat_w = jnp.where(kept, flat_w, 0.0)
    counts = counts[:held]

    def windows(counts):
        return (jnp.sum(counts) + window - 1) // window

    # the first window's index and the loop's first, typed as the loop gives
    # its own (int32, not weakly), so that the two call sites of a window
    # function share its one trace; numpy's, because a device array made
    # while tracing is a constant the step's executable takes as an argument
    zeroth, after = np.int32(0), np.int32(1)

    def forward(order, counts, x2, flat_w, ws):
        def add(t, out):
            return _held_window_add(
                out, t, order, counts, x2, flat_w, ws, attrs, pallas, forms
            )

        zero = jnp.zeros((n, ws["w2"].shape[-1]), jnp.float32)
        return lax.fori_loop(after, windows(counts), add, add(zeroth, zero))

    @jax.custom_vjp
    def routed(order, counts, x2, flat_w, ws):
        return forward(order, counts, x2, flat_w, ws)

    def routed_fwd(order, counts, x2, flat_w, ws):
        return forward(order, counts, x2, flat_w, ws), (
            order, counts, x2, flat_w, ws
        )

    def routed_bwd(kept, g_out):
        order, counts, x2, flat_w, ws = kept

        def grads(t, forms):
            return _held_window_grads(
                g_out, t, order, counts, x2, flat_w, ws, attrs, pallas, forms
            )

        # a later window's gradient of x2 stays a scatter-add, which XLA does
        # in place on the running sum; the kernel's result would be a buffer
        # of its own beside it, and in the loop's body that cost the compiled
        # step 72 to 274 MB (PERF.md section 6, PR 50)
        later = (forms[0], "xla")

        def add(t, so_far):
            return jax.tree_util.tree_map(jnp.add, so_far, grads(t, later))

        g_x2, g_w, g_ws = lax.fori_loop(
            after, windows(counts), add, grads(zeroth, forms)
        )
        return None, None, g_x2, g_w, g_ws

    routed.defvjp(routed_fwd, routed_bwd)
    ws = {name: w.astype(x2.dtype) for name, w in ws.items()}
    _note_tiles(
        {name: w for name, w in ws.items() if w.ndim == 3}, window, pallas
    )
    forms = _note_held_sums(
        pallas, attrs, held, n, window, x2.dtype,
        {"forward": (ws["w2"].shape[-1], jnp.float32),
         "backward": (x2.shape[-1], x2.dtype)},
        ws,
    )
    ran = jnp.maximum(windows(counts), 1)  # the first runs whatever the counts
    return routed(order, counts, x2, flat_w, ws), counts, ran
