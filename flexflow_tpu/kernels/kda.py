"""The gated delta-rule mixer's kernels (`GatedDeltaAttrs`): the
recurrence, per value head, with a [dk, dv] state S, a step beta_t in (0, 1)
and a log-decay g_t <= 0, one for EVERY key channel (Kimi Delta Attention,
arXiv:2510.26692; written out below) or ONE a head (Gated DeltaNet,
arXiv:2412.06464; "One decay a head" further down):

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

Two forms of the recurrence exist, and `scan_route` picks one from what the
trace can observe (shapes and the facts of `kernels/context.py`); the ONE route
decides the chunks' operands and the chunk-to-chunk pass alike:

- **The Pallas kernels** ("kda"): on a TPU at heads whose key and value sizes
  are multiples of 128 lanes.
  *The operands* (`kernel_operands`): `kda_prep_fwd` computes every decay and
  the decayed scores of eight chunks a program in VMEM, so that nothing a
  level computes reaches HBM, and `kda_prep_bwd` is its WRITTEN backward
  (`chunk_scores`, one `jax.custom_vjp`). Both take the node's inputs RAW
  and in the MODEL's layout: a head of 128 key channels is one 128-lane
  column block of the convolved q | k | v [b, s, .] and of the decay's
  pre-activation, read where the convolution and the projection left it, so
  no heads-first copy of q, k or the pre-activation exists; the two
  normalisations (rounded as `_unit` rounds them) and
  g = -exp(a_log) softplus(f_up + dt_bias) happen in VMEM per chunk
  (`_raw_gates`), g never reaches HBM, and the backward recomputes them from
  the same raw inputs (what the node's checkpoint holds anyway), carries its
  cotangents through the norm and the softplus, writes those of q, k and
  the pre-activation in the model's layout and a program's part of
  `dt_bias`'s and `a_log`'s. Every exponent is the product of a constant 0/1
  matrix with g (the set of positions whose log-decays it sums), so it is
  <= 0 by construction, float32-exact in three bf16 passes, and its transpose
  is the way back to g. *The triangular system* (`corrected_products`, one
  `jax.custom_vjp` for both forms of the decay): its float32 [Q, Q] arrays
  cross HBM TWO chunk-heads to a row, [.., c / 2, Q, 2Q] (whole 128-lane
  rows at Q = 64: `_side_by_side`), from the kernel that makes each to the
  kernel that reads it: A from the operands' kernel, the inverse X from
  `kda_prep_inverse` (`unit_lower_inverse`'s levels on the pair as it lies,
  n = Diag(beta) A formed in VMEM), dA from `kda_corrected_bwd` back into
  the operands' backward. `kda_corrected_fwd` takes X, beta, K exp(G) and V
  of sixteen chunk-heads a program and writes T (K exp(G)) and T V,
  T = X Diag(beta); `kda_corrected_bwd` is the WRITTEN backward of the
  whole system from X, A and the two cotangents dW, dU. With Y_w = X^T dW,
  Y_u = X^T dU and M = Y_w (K exp(G))^T + Y_u V^T, a [Q, Q] matrix:
  d(K exp(G)) = Diag(beta) Y_w, dV = Diag(beta) Y_u, dn = -M T^T under the
  diagonal for the inverse's input n, dA = Diag(beta) dn, and dbeta =
  diag(M) (T's column scaling) + sum_j dn_rj A_rj (n's rows). dn is
  `unit_lower_inverse`'s dn = -X^T dX X^T with X^T dX X^T = (X^T dT) T^T
  substituted (equally -(Y_w W^T + Y_u U^T), W and U the forward's results,
  which the backward never forms): THREE products a chunk-head where
  differentiating `_corrected` runs six, and n, dn and the cotangents of T
  and X exist nowhere in HBM. Every product keeps every term `HIGHEST`
  keeps: a float32 operand goes in three bf16 parts, one that IS bf16 (V,
  dW, dU in a bf16 step) in one, its other parts being exactly zero
  (`_split_product`). The whole of `_corrected` with `unit_lower_inverse`
  stays XLA's where the kernels do not take the call: an odd number of
  chunks a head (the system's kernels take a head's chunks two by two; A
  is then a chunk a row) and everything on the "xla" route.
  `trace.kernel_choices("triangular_products")` says which, by node, and
  `kernel_choices("triangular_layout")` `pairs` beside `kernels`.
  *The pass* (`kda_fwd_chunk`, `kda_states_chunk`, `kda_bwd_chunk`): one
  program is one (batch row, head, chunk); the chunk axis is sequential and
  the head's [dv, dk] float32 state (held transposed, so that the
  end-of-chunk decay is a row that broadcasts along sublanes) rides a VMEM
  scratch from chunk to chunk, last to first in the backward.
- **The XLA form** ("xla"): everything else (the CPU, toy widths, a trace
  that admits no bare Pallas call, a declared mesh): `chunk_operands`, which
  JAX differentiates but for the triangular inverse, and the same products
  of the pass as a `lax.scan` over the chunks. It is what the kernels are
  tested against.

The pass is one `jax.custom_vjp` (`chunk_scan`) with a WRITTEN backward in
both forms: it recomputes the state every chunk starts from in a first pass
and walks the chunks last to first in a second, carrying the state's
gradient. The whole recurrence (normalisation, gates, the operands,
`chunk_scan`) runs under one `jax.checkpoint`, so what a step keeps of the
node for its backward is the op's inputs to it (the convolved q | k | v, the
two gates' pre-activations and the step's logits) and ONE thing a chunk
computes: the triangular inverse (`_KEPT`, 16 KB a chunk), whose ten products
a chunk would otherwise run again. Everything else is recomputed there. In
neither form does a state per position ever exist.

**One decay a head** (`GatedDeltaAttrs.decay` "head"). exp(G_r - G_j) is one
number a pair of positions and leaves the contraction over the key channels:

    A = (K K^T) * M  (j < r),   P = (Q K^T) * M  (j <= r),   M_rj = exp(G_r - G_j)

one [Q, Q] product each and a mask, every exponent still a sum of log-decays
and so <= 0; none of `decayed_scores`' levels is needed. `num_key_heads` key
heads may serve more value heads: K K^T and Q K^T are taken once a KEY head
and meet the value heads' M through a group axis, so a key head is indexed
(value head h reads key head h // group) and never repeated; Q exp(G),
K exp(G), K exp(G_Q - G), the inverse and the states are a value head's.
`head_decay_operands` is that form in XLA (JAX differentiates it but for the
triangular inverse): the "xla" route's, and what the kernels are tested
against; it takes q, k and v HEADS FIRST, q and k normalised (`_unit`), which
`_recurrence_head_decay` does under `gates` on that route alone. On the
"kda" route the operands are `head_kernel_operands`, and every kernel that
reads q, k or v reads it IN THE MODEL'S LAYOUT, where the convolution's
kernel left its three pieces ([b, s, hk * dk] twice and [b, s, hv * dv]): a
head of 128 channels is ONE 128-lane column block of the program's rows
(`_HeadPrepBlocks.key_head`, `_CorrectedBlocks.value`), so no heads-first
copy of q, k or v and no float32 pass of a norm exists, forward, recomputed
or backward. `gdn_prep_fwd` takes q and k RAW, puts them over their own
2-norm in VMEM (`_unit_root`: float32, one rounding to the input's dtype,
q times dk ** -0.5), computes the two products once a chunk of a KEY head
and, for each value head that reads it, the mask and the three decay vectors,
eight chunks a program, and writes Q exp(G), K exp(G_Q - G), P, exp(G_Q) and
K exp(G) heads first and by chunk, as `chunk_scan` reads them, and the
strictly lower A two chunks to a row (`_side_by_side`); `gdn_prep_bwd` is
its WRITTEN backward (`head_chunk_scores`, one `jax.custom_vjp`), which
recomputes the norms, the products and the decays
from the raw q, k and g, sums the value heads' cotangents of the normalised q
and k in the program, takes them through the norm in float32
(`_unit_cotangent`) and writes dq and dk as column blocks of [b, s, hk * dk],
and takes the exponents' back to g. `kda_corrected_fwd` reads v and
`kda_corrected_bwd` writes dv the same way, a program's chunk-heads being
chunks of one head. The three cotangents are what `conv_silu_bwd` reads, with
nothing between. M, Q K^T, K K^T, the normalised q and k and the decays never
reach HBM. A sequence that is not whole chunks is padded in the model's
layout first (a copy of each piece; a padded position has q = k = v = 0).
The kernels share helpers with the per-channel form's (`_bf16_parts`, `_mm`,
`_unit_root`, `_unit_cotangent`) and no body: there the exponent is one a key
channel and needs the levels, here one number a pair of positions and no
level. The triangular system is the per-channel form's own, kernels and
fallback (`_kernel_corrected`: both forms hand it A, K exp(G) and beta by
chunk and value head, the per-channel form v likewise, this one v in the
model's layout; XLA's fallback turns it heads first itself). Either way what
comes back is what `chunk_operands` returns, exp(G_Q) written over the dk
lanes, so `chunk_scan` and its kernels take it as they are. Which form a node
took is `operand_form`'s answer (the attrs' `decay`, `scan_route`'s route and
whether the sequence is whole chunks, nothing else), counted by node in
`trace.kernel_choices("delta_rule_operands")`.

**The gated norm** (`_gated_head_norm`, the node's last part before W_out):
the rms norm of each head's dv features of the recurrence's o under a gate,
silu(z) with one decay a head and sigmoid(g_up + b_g) with one a key channel.
On the "kda" route it is `head_norm_gate`, ONE `jax.custom_vjp` for both
gates over two kernels: `head_norm_gate_fwd` reads a block of rows of four
heads of o, HEADS FIRST as the recurrence leaves it, and of the gate's
operand where the projection left it (z in place, a column block of the
input projection's row), in the model's dtype, and writes y, one pass;
`head_norm_gate_bwd`, the WRITTEN backward, reads the same blocks and dy,
recomputes the roots and the gate in VMEM and writes do and the operand's
cotangent, with a program's partial sums of the gain's (and the bias's)
gradient as one float32 tile that XLA adds up after. What the backward keeps
is the operands (o, z or g_up and b_g, the gain), alive anyway; no float32
of the rows' width and no root crosses HBM, and no forward is run again. On
the "xla" route it is the plain `_head_norm_silu` / `_head_norm_gate` under a
checkpoint of its own, differentiated by JAX: what the kernels are tested
against. `trace.kernel_choices("head_norms")` says which, by node.

The node's parts go under scopes of their own inside the node's
(`ff.kda.<name>/scan`, `/prep`, `/gates`, `/conv`, `/norm`;
`observability/trace.NODE_PARTS`). On the "kda" route `gates` holds what the
scores' kernels do not take: with a decay a key channel the two rank-128 gate
matmuls (`f_up`, `g_up`), beta's sigmoid, v's heads-first copy (and dv's
back) and the small reductions; with one decay a head the `W_ba` matmul and
its backward, beta's sigmoid and g's softplus on [b, hv, s], and no copy of
q, k or v. On the "xla" route it also holds the norms of q and k, the
softplus and the heads-first copies of q, k, v and the pre-activation.
`norm` holds the two kernels above and nothing else on the "kda" route, and
on the "xla" route o's copy to the model's layout with XLA's fusions of the
plain form, forward, recomputed and backward. `conv` is
`kernels/ssm.conv_silu` on both, and it chooses its own form
(`ssm.conv_route`, from the widths, the sequence and the trace;
`trace.kernel_choices("conv_forms")`): since PR 59 the kernels `conv_silu_fwd`
/ `conv_silu_bwd`, which read the q | k | v columns in place out of the input
projection's row (the node hands it the row, not a slice), wherever the
"kda" route runs and the sequence divides into their blocks, else its plain
form.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.kernels import context
from flexflow_tpu.kernels.ssm import _eight_apart, conv_silu
from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs

# added under the root of a head's sum of squares before q and k are
# normalised (the public layer's l2norm; `assumed` in the benchmark's file)
L2_EPS = 1e-6
_HIGHEST = lax.Precision.HIGHEST
# the one value of the recurrence that the node's checkpoint keeps, and the
# policy that says so: ONE object for every node, because JAX caches what it
# splits a jitted kernel call into under a checkpoint by the policy's
# identity, and lowers each kernel once a step program only where the nodes
# share it (a policy a node lowered `kda_prep_inverse` once a node)
_KEPT = "kda_triangular_inverse"
_KEEP_INVERSE = jax.checkpoint_policies.save_only_these_names(_KEPT)


def _level_blocks(q: int, m: int):
    """For the level whose blocks are 2m rows: (is the row in its block's
    later half [Q], do two rows share a block [Q, Q])."""
    pos = np.arange(q)
    block = pos // (2 * m)
    return (pos % (2 * m)) >= m, block[:, None] == block[None, :]


def _level_mask(q: int, m: int):
    """[Q, Q]: the pairs (r in the later half, j in the earlier half of the
    same block of 2m rows), which the level gives."""
    later, same_block = _level_blocks(q, m)
    return same_block & later[:, None] & ~later[None, :]


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
    # the first level's X is the identity, and I L I = L: no product
    x = jnp.eye(q, dtype=n.dtype) - jnp.where(_level_mask(q, 1), n, 0.0)
    m = 2
    while m < q:
        below = jnp.where(_level_mask(q, m), n, 0.0)
        step = jnp.matmul(
            jnp.matmul(x, below, precision=_HIGHEST), x, precision=_HIGHEST
        )
        x = x - step
        m *= 2
    return x


def _unit_lower_inverse_fwd(n):
    """The node's checkpoint keeps the inverse (`_KEPT`) where it recomputes
    everything else, 16 KB a chunk for ten products."""
    x = checkpoint_name(unit_lower_inverse(n), _KEPT)
    return x, x


def _unit_lower_inverse_bwd(x, dx):
    xt = jnp.swapaxes(x, -1, -2)
    dn = jnp.matmul(
        jnp.matmul(xt, dx, precision=_HIGHEST), xt, precision=_HIGHEST
    )
    return (jnp.where(np.tri(x.shape[-1], k=-1, dtype=bool), -dn, 0.0),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _corrected(a, kd, v5, beta5, dtype):
    """T (K exp(G)) and T V in `dtype`, T = (I + Diag(beta) A)^-1 Diag(beta):
    a [.., Q, Q] and kd [.., Q, dk] float32, v5 [.., Q, dv], beta5 [.., Q].
    XLA's form, which JAX differentiates but for the inverse
    (`corrected_products` is the kernels')."""
    t = unit_lower_inverse(beta5[..., :, None] * a) * beta5[..., None, :]
    w = jnp.matmul(t, kd, precision=_HIGHEST).astype(dtype)
    uv = jnp.matmul(t, v5.astype(jnp.float32), precision=_HIGHEST).astype(dtype)
    return w, uv


def chunk_operands(q, k, v, g, beta, chunk: int):
    """What `chunk_scan` reads, for all chunks at once: q, k [b, h, s, dk]
    (normalised), v [b, h, s, dv], g [b, h, s, dk] float32 log-decays,
    beta [b, h, s] float32; s a multiple of `chunk`. Returns, by chunk
    ([b, h, c, Q, .]): Q exp(G) and T (K exp(G)) [., dk], T V [., dv],
    K exp(G_Q - G) [., dk], the inclusive decayed scores P [., Q] in the
    inputs' dtype, and exp(G_Q) [b, h, c, 1, dk] float32. The XLA form, which
    JAX differentiates but for the triangular inverse."""
    b, h, s, dk = q.shape
    dtype = q.dtype
    f32 = jnp.float32

    def by_chunk(t):
        return t.reshape(b, h, s // chunk, chunk, *t.shape[3:])

    q5, k5, v5 = by_chunk(q), by_chunk(k), by_chunk(v)
    gc = jnp.cumsum(by_chunk(g), axis=3)
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
    w, uv = _corrected(a, kd, v5, by_chunk(beta), dtype)
    return qd, w, uv, ke, p.astype(dtype), jnp.exp(g_end)


def head_decay_operands(q, k, v, g, beta, chunk: int):
    """`chunk_operands` for ONE log-decay a value head (module docstring,
    "One decay a head"): q, k [b, hk, s, dk] (normalised), v [b, hv, s, dv],
    g and beta [b, hv, s] float32, hv a multiple of hk; value head h reads
    key head h // (hv / hk). Returns what `chunk_operands` returns, by chunk
    and VALUE head."""
    b, hk, s, dk = q.shape
    hv = v.shape[1]
    group, c = hv // hk, s // chunk
    dtype, f32 = q.dtype, jnp.float32

    def by_chunk(t):
        return t.reshape(*t.shape[:2], c, chunk, *t.shape[3:])

    def by_group(t):  # [b, hv, c, ..] -> [b, hk, group, c, ..]
        return t.reshape(b, hk, group, *t.shape[2:])

    def by_value_head(t):
        return t.reshape(b, hv, *t.shape[3:])

    q5, k5 = by_chunk(q), by_chunk(k)
    gc = jnp.cumsum(by_chunk(g), axis=3)  # [b, hv, c, Q]
    g_end = gc[..., -1:]
    # exp(G_r - G_j) on and under the diagonal, 0 above it: every exponent
    # that is taken is a sum of log-decays
    m = jnp.exp(jnp.where(
        np.tri(chunk, dtype=bool), gc[..., :, None] - gc[..., None, :],
        -jnp.inf,
    ))
    qk, kk = (
        jnp.einsum("bhcrk,bhcjk->bhcrj", t, k5, preferred_element_type=f32)
        for t in (q5, k5)
    )
    # one product a KEY head meets its value heads' decays
    p = by_value_head(by_group(m) * qk[:, :, None])
    a = by_value_head(by_group(m) * kk[:, :, None])
    qf, kf = q5.astype(f32)[:, :, None], k5.astype(f32)[:, :, None]
    from_start = by_group(jnp.exp(gc))[..., None]
    to_end = by_group(jnp.exp(g_end - gc))[..., None]
    qd = by_value_head(qf * from_start).astype(dtype)
    kd = by_value_head(kf * from_start)
    ke = by_value_head(kf * to_end).astype(dtype)
    w, uv = _corrected(a, kd, by_chunk(v), by_chunk(beta), dtype)
    gamma = jnp.broadcast_to(
        jnp.exp(g_end)[..., None], (b, hv, c, 1, dk)
    )
    return qd, w, uv, ke, p.astype(dtype), gamma


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
    return _pallas_forward(qd, w, uv, ke, p, gamma, context.interpret_default())


def _chunk_scan_fwd(route, *operands):
    return chunk_scan(route, *operands), operands


def _chunk_scan_bwd(route, operands, do):
    if route == "xla":
        states = _xla_forward(*operands, True)
        return _xla_backward(*operands, states, do)
    states = _pallas_forward(*operands, context.interpret_default(), True)
    return _pallas_backward(*operands, states, do, context.interpret_default())


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)


# ---------------------------------------------------------------------------
# the chunks' operands, the Pallas form: nothing a level computes reaches HBM
# ---------------------------------------------------------------------------
#
# One program is `n` chunks of one (batch row, head), unrolled: the chunks are
# independent, so every grid axis is parallel and the scheduler has n product
# chains to interleave. Every exponent is a SUM of the chunk's log-decays over
# a set of positions, so it is a product of a constant 0/1 matrix with g, and
# <= 0 by construction: for the pair (r, j) of a level with reference `ref`,
# the row's set is (ref, r] and the column's (j, ref] (`_prep_tables`). The
# matrix is exact in bf16 and g is split into three bf16 parts that add up to
# it exactly (`_bf16_parts`), so three MXU passes give the float32 sums, and
# their transpose gives the log-decays' gradient from the exponents'.

# the decays a chunk needs before its levels': from the chunk's start (the
# inclusive running sum G) and to its end (G_Q - G)
_FROM_START, _TO_END, _FIRST_LEVEL = 0, 1, 2
_PARTS = 3  # bf16 parts of a float32 operand


@functools.lru_cache(maxsize=None)
def _prep_tables(q: int):
    """(sums, sums_t, owner) of a chunk of q positions. A piece is the [q, q]
    0/1 matrix whose product with g is one set of exponents: from the start,
    to the end, then a level each, the widest first. `sums`
    [pieces * q, 3 q] bf16 stacks the pieces down its rows, each written
    three times side by side (once a bf16 part of g, which are stacked down
    the contraction: ONE product gives every exponent); `sums_t`
    [q, pieces * 3 q] lays the pieces' transposes side by side the same way
    (one product takes every exponent's cotangent back to g). `owner` [q, q]
    int32 names which level gives the pair (r, j), j < r, with `levels` on
    the diagonal and `levels + 1` above it."""
    pos = np.arange(q)
    r, i = pos[:, None], pos[None, :]
    pieces = [i <= r, i > r]
    owner = np.zeros((q, q), np.int32)
    m, level = q // 2, 0
    while m >= 1:
        ref = (pos // (2 * m)) * (2 * m) + m
        low, high = np.minimum(pos, ref)[:, None], np.maximum(pos, ref)[:, None]
        pieces.append((low < i) & (i <= high))
        owner[_level_mask(q, m)] = level
        m //= 2
        level += 1
    owner[r == i] = level
    owner[r < i] = level + 1
    sums = np.concatenate([np.tile(p, (1, _PARTS)) for p in pieces], axis=0)
    sums_t = np.concatenate([np.tile(p.T, (1, _PARTS)) for p in pieces], axis=1)
    return sums.astype(jnp.bfloat16), sums_t.astype(jnp.bfloat16), owner


def _bf16_parts(x):
    """Three bf16 arrays that add up to the float32 x exactly."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf16)
    return hi, mid, (rest - mid.astype(f32)).astype(bf16)


def _stacked_parts(x):
    """`_bf16_parts` of x [q, .] stacked down the rows [3 q, .]: against a
    0/1 table written three times side by side they give the table's float32
    product with x, every term `HIGHEST` keeps, in one product."""
    return jnp.concatenate(_bf16_parts(x), axis=0)


def _side_by_side(chunks: int) -> int:
    """How many of a program's chunks lay their float32 [Q, Q] of the
    triangular system (A, its cotangent) side by side along the lanes: two
    where the program's chunks go two by two, so that a row of the array in
    HBM is 2 Q = 128 whole lanes ([.., c / 2, Q, 2 Q]: chunk 2 p in lanes
    [0, Q), chunk 2 p + 1 in [Q, 2 Q), as `kda_prep_inverse` holds a pair);
    one where a head's chunks are odd in number ([.., c, Q, Q], which only
    XLA's `_corrected` reads: `_kernel_corrected`)."""
    return 2 - chunks % 2


def _beside(i, half: int, q: int):
    """Where chunk `side * i + half`'s [Q, Q] lies in a program's block of
    chunks side by side: row block i (a loop index), lanes `half` (static)."""
    return pl.ds(pl.multiple_of(i * q, q), q), slice(half * q, (half + 1) * q)


def _each_chunk(chunks: int, one_chunk, carry=None):
    """`one_chunk(c, i, half, carry)` -> carry for each of a program's
    `chunks`, unrolled, `_side_by_side(chunks)` to a row: c = side * i +
    half, i the loop's index and `half` static (`_beside`). The body is
    traced once a half."""
    side = _side_by_side(chunks)

    def one_row(i, carry):
        for half in range(side):
            carry = one_chunk(side * i + half, i, half, carry)
        return carry

    return lax.fori_loop(0, chunks // side, one_row, carry, unroll=True)


def _chunk_decays(sums_ref, g, q: int):
    """piece -> exp of that piece's sums of the chunk's log-decays g [q, dk],
    float32 [q, dk]; every exponent is <= 0."""
    decays = jnp.exp(_mm(sums_ref[:], _stacked_parts(g), _NN))
    return lambda piece: decays[piece * q:(piece + 1) * q, :]


def _raw_gates(q_ref, k_ref, f_ref, bias_ref, rate_ref, rows, scale: float):
    """What the "xla" route's `gates` computes, for one chunk's rows of one
    head read where the model left them: q and k [Q, dk] over their own
    2-norm (`_unit_root`: float32, ROUNDED to the input's dtype; q times
    `scale`) and the float32 log-decays g = rate * softplus(f_up + dt_bias);
    then what the backward takes besides: the two inverse roots [Q, 1] and
    the log of the softplus's slope."""
    qn, q_root = _unit_root(q_ref[rows, :], scale)
    kn, k_root = _unit_root(k_ref[rows, :], 1.0)
    pre = f_ref[rows, :].astype(jnp.float32) + bias_ref[:]
    soft = jax.nn.softplus(pre)
    # the softplus's slope as JAX's own rule takes it: exp(x - softplus(x))
    return qn, kn, rate_ref[:] * soft, (q_root, k_root, pre - soft)


def _kda_prep_fwd_kernel(
    sums_ref, owner_ref, q_ref, k_ref, f_ref, bias_ref, rate_ref,
    qd_ref, ke_ref, p_ref, gam_ref, a_ref, kd_ref, *, chunks: int, scale: float,
):
    """Q exp(G), K exp(G_Q - G), P, exp(G_Q), the strictly lower key-against-
    key scores A (`_side_by_side`) and K exp(G) (float32, for the triangular
    system) of each of the program's chunks, from the RAW q, k and decay
    pre-activation of the head (`_raw_gates`)."""
    f32 = jnp.float32
    dtype = q_ref.dtype
    q = owner_ref.shape[0]
    levels = sums_ref.shape[0] // q - _FIRST_LEVEL
    owner = owner_ref[:]

    def one_chunk(c, i, half, _):
        rows = pl.ds(pl.multiple_of(c * q, q), q)
        qn, kn, g, _ = _raw_gates(
            q_ref, k_ref, f_ref, bias_ref, rate_ref, rows, scale
        )
        qf, kf = qn.astype(f32), kn.astype(f32)
        decay = _chunk_decays(sums_ref, g, q)
        from_start = decay(_FROM_START)
        qd_ref[rows, :] = (qf * from_start).astype(dtype)
        kd_ref[rows, :] = kf * from_start
        ke_ref[rows, :] = (kf * decay(_TO_END)).astype(dtype)
        gam_ref[c] = from_start[q - 1:q, :]
        # a position reads its own key undecayed
        p = jnp.where(owner == levels, _mm(qn, kn, _NT), 0.0)
        a = jnp.zeros((q, q), f32)
        for level in range(levels):
            e = decay(_FIRST_LEVEL + level)
            kz = (kf * e).astype(dtype)
            mine = owner == level
            p = jnp.where(mine, _mm((qf * e).astype(dtype), kz, _NT), p)
            a = jnp.where(mine, _mm(kz, kz, _NT), a)
        p_ref[rows, :] = p.astype(dtype)
        a_ref[_beside(i, half, q)] = a

    _each_chunk(chunks, one_chunk)


def _unit_cotangent(x, root, dy, scale: float):
    """The cotangent of x [Q, d] from dy, that of `_unit(x, scale)` (straight
    through its rounding, as `astype` is): with r = `root` the inverse root,
    scale r (dy - r^2 x (x . dy)), float32."""
    xf = x.astype(jnp.float32)
    along = jnp.sum(xf * dy, axis=-1, keepdims=True)
    return (scale * root) * (dy - (root * root * along) * xf)


def _kda_prep_bwd_kernel(
    sums_ref, sums_t_ref, owner_ref, q_ref, k_ref, f_ref, bias_ref, rate_ref,
    dqd_ref, dke_ref, dp_ref, dgam_ref, da_ref, dkd_ref,
    dq_ref, dk_ref, df_ref, dbias_ref, dalog_ref, dex_ref,
    *, chunks: int, scale: float,
):
    """The cotangents of the RAW q, k and decay pre-activation (in the model's
    layout, as they were read) from those of `_kda_prep_fwd_kernel`'s six
    results, the gates and every decay recomputed. A level's pairs are
    (q e) (k e)^T and (k e) (k e)^T under its mask, so its backward is the
    masked cotangents against k e (the rows' factor) and their transposes
    against q e | k e (the columns'). What reaches a piece's exponents is kept
    in bf16 parts in `dex_ref` [pieces * 3 q, dk] and goes back to g through
    the transposed sums in one product at the chunk's end; g's cotangent
    never leaves VMEM: through the softplus it is the pre-activation's
    (`df_ref`), and the program's sums of that and of dg g are its part of
    `dt_bias`'s and `a_log`'s ([1, dk] each, summed by the caller)."""
    f32 = jnp.float32
    dtype = q_ref.dtype
    q = owner_ref.shape[0]
    levels = sums_ref.shape[0] // q - _FIRST_LEVEL
    owner = owner_ref[:]

    def one_chunk(c, i, half, sums):
        rows = pl.ds(pl.multiple_of(c * q, q), q)
        qn, kn, g, (q_root, k_root, log_slope) = _raw_gates(
            q_ref, k_ref, f_ref, bias_ref, rate_ref, rows, scale
        )
        qf, kf = qn.astype(f32), kn.astype(f32)
        decay = _chunk_decays(sums_ref, g, q)

        def to_g(piece, d_exponent):
            dex_ref[pl.ds(piece * _PARTS * q, _PARTS * q), :] = _stacked_parts(
                d_exponent
            )

        from_start, to_end = decay(_FROM_START), decay(_TO_END)
        dqd, dkd = dqd_ref[rows, :].astype(f32), dkd_ref[rows, :]
        dke = dke_ref[rows, :].astype(f32)
        dq = dqd * from_start
        dk = dkd * from_start + dke * to_end
        to_g(_FROM_START, (dqd * qf + dkd * kf) * from_start)
        to_g(_TO_END, dke * kf * to_end)
        dp, da = dp_ref[rows, :].astype(f32), da_ref[_beside(i, half, q)]
        own = jnp.where(owner == levels, dp, 0.0).astype(dtype)
        dq = dq + _mm(own, kn, _NN)
        dk = dk + _mm(own, qn, _NN)
        for level in range(levels):
            e = decay(_FIRST_LEVEL + level)
            qe, ke = qf * e, kf * e
            qz, kz = qe.astype(dtype), ke.astype(dtype)
            mine = owner == level
            dp_l = jnp.where(mine, dp, 0.0).astype(dtype)
            da_l = jnp.where(mine, da, 0.0).astype(dtype)
            dqz = _mm(dp_l, kz, _NN)
            dkz = _mm(da_l, kz, _NN) + _mm(
                jnp.concatenate([dp_l, da_l], axis=0),
                jnp.concatenate([qz, kz], axis=0), _TN,
            )
            dq = dq + dqz * e
            dk = dk + dkz * e
            to_g(_FIRST_LEVEL + level, dqz * qe + dkz * ke)
        dq_ref[rows, :] = _unit_cotangent(
            q_ref[rows, :], q_root, dq, scale
        ).astype(dq_ref.dtype)
        dk_ref[rows, :] = _unit_cotangent(
            k_ref[rows, :], k_root, dk, 1.0
        ).astype(dk_ref.dtype)
        # exp(G_Q) is the decay from the start at the last position, and G_Q
        # sums EVERY position's g
        dg = (
            _mm(sums_t_ref[:], dex_ref[:], _NN)
            + dgam_ref[c] * from_start[q - 1:q, :]
        )
        dpre = dg * rate_ref[:] * jnp.exp(log_slope)
        df_ref[rows, :] = dpre.astype(df_ref.dtype)
        dbias, dalog = sums
        return (
            dbias + jnp.sum(dpre, axis=0, keepdims=True),
            dalog + jnp.sum(dg * g, axis=0, keepdims=True),
        )

    zero = jnp.zeros(bias_ref.shape, f32)
    dbias_ref[:], dalog_ref[:] = _each_chunk(chunks, one_chunk, (zero, zero))


_PARALLEL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel")
)
# chunks a program of the two kernels above (the largest that divides the
# sequence's): independent product chains for the scheduler to interleave and
# one grid step's fixed cost shared
_PREP_CHUNKS = (8, 4, 2, 1)


def _table(t):
    """The BlockSpec of a constant table that every program reads whole."""
    return pl.BlockSpec(t.shape, lambda *_: (0,) * t.ndim)


class _PrepBlocks:
    """The BlockSpecs over the grid (batch, head, group of n chunks). The
    inputs lie as the model has them, [b, s, heads * dk]: a head of dk = 128
    key channels is ONE 128-lane column block (`head`; k's lie `heads`
    blocks after q's), so no heads-first copy of q, k or the decay's
    pre-activation exists; a row of the per-channel vectors [1, heads * dk]
    is cut the same way. The results are [b, h, s, .], A and its cotangent
    (`beside`) `side` chunks side by side, [b, h, s / side, side * Q]."""

    def __init__(self, b: int, h: int, s: int, dk: int, q: int):
        c = s // q
        n = self.chunks = next(n for n in _PREP_CHUNKS if c % n == 0)
        side = self.side = _side_by_side(n)
        self.grid = (b, h, c // n)

        def rows(width, count=n * q):
            return pl.BlockSpec(
                (None, None, count, width), lambda bi, hi, gi: (bi, hi, gi, 0)
            )

        self.beside = rows(side * q, n * q // side)

        def head(first):
            return pl.BlockSpec(
                (None, n * q, dk), lambda bi, hi, gi: (bi, gi, first + hi)
            )

        self.key, self.scores, self.head = rows(dk), rows(q), head(0)
        self.gamma = pl.BlockSpec(
            (None, None, n, 1, dk), lambda bi, hi, gi: (bi, hi, gi, 0, 0)
        )
        channels = pl.BlockSpec((1, dk), lambda bi, hi, gi: (0, hi))
        # of `_raw_inputs`: q and k out of q | k | v, f_up, dt_bias, the rate
        self.raw = [head(0), head(h), head(0), channels, channels]
        # a program's part of a per-channel sum, [b, groups, 1, heads * dk]
        self.partial = pl.BlockSpec(
            (None, None, 1, dk), lambda bi, hi, gi: (bi, gi, 0, hi)
        )


def _raw_inputs(qkv, f_up, dt_bias, a_log):
    """What the two kernels read of the node's inputs (`_PrepBlocks.raw`):
    qkv twice (q's and k's column blocks), f_up, and dt_bias and
    -exp(a_log) a key channel as float32 rows [1, heads * dk]."""
    f32 = jnp.float32
    rate = jnp.repeat(-jnp.exp(a_log.astype(f32)), dt_bias.size // a_log.size)
    return qkv, qkv, f_up, dt_bias.astype(f32)[None, :], rate[None, :]


@functools.partial(jax.jit, static_argnums=(4, 5))
def _prep_forward(qkv, f_up, dt_bias, a_log, chunk, interpret):
    """The kernel on the convolved q | k | v [b, s, 2 * h * dk + h * dv] (its
    q and k columns are read), the decay's pre-activation f_up [b, s, h * dk],
    dt_bias [h * dk] and a_log [h]; by chunk out ([b, h, c, Q, .]): qd, ke,
    p, gamma as `chunk_operands` has them, then A (`_side_by_side`:
    [b, h, c / 2, Q, 2 Q] where the chunks are even in number, [., Q, Q]
    otherwise) and K exp(G) [., Q, dk], float32."""
    f32 = jnp.float32
    b, s, width = f_up.shape
    h = a_log.shape[0]
    dk = width // h
    c = s // chunk
    at = _PrepBlocks(b, h, s, dk, chunk)
    sums, _, owner = _prep_tables(chunk)

    def rows(width, dtype):
        return jax.ShapeDtypeStruct((b, h, s, width), dtype)

    qd, ke, p, gamma, a, kd = pl.pallas_call(
        functools.partial(
            _kda_prep_fwd_kernel, chunks=at.chunks, scale=dk ** -0.5
        ),
        grid=at.grid,
        in_specs=[_table(sums), _table(owner), *at.raw],
        out_specs=[at.key, at.key, at.scores, at.gamma, at.beside, at.key],
        out_shape=[
            rows(dk, qkv.dtype), rows(dk, qkv.dtype), rows(chunk, qkv.dtype),
            jax.ShapeDtypeStruct((b, h, c, 1, dk), f32),
            jax.ShapeDtypeStruct((b, h, s // at.side, at.side * chunk), f32),
            rows(dk, f32),
        ],
        compiler_params=_PARALLEL_CHUNKS,
        interpret=interpret,
        name="kda_prep_fwd",
    )(jnp.asarray(sums), jnp.asarray(owner),
      *_raw_inputs(qkv, f_up, dt_bias, a_log))

    def by_chunk(t):
        return t.reshape(b, h, -1, chunk, t.shape[-1])

    return by_chunk(qd), by_chunk(ke), by_chunk(p), gamma, by_chunk(a), by_chunk(kd)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _prep_backward(qkv, f_up, dt_bias, a_log, cotangents, chunk, interpret):
    """The cotangents of `_prep_forward`'s four inputs; v's columns of qkv's
    are zero."""
    f32 = jnp.float32
    b, s, width = f_up.shape
    h = a_log.shape[0]
    dk = width // h
    at = _PrepBlocks(b, h, s, dk, chunk)
    sums, sums_t, owner = _prep_tables(chunk)
    dqd, dke, dp, dgam, da, dkd = cotangents
    partial = jax.ShapeDtypeStruct((b, at.grid[2], 1, width), f32)
    dq, dkey, df, dbias, dalog = pl.pallas_call(
        functools.partial(
            _kda_prep_bwd_kernel, chunks=at.chunks, scale=dk ** -0.5
        ),
        grid=at.grid,
        in_specs=[
            _table(sums), _table(sums_t), _table(owner), *at.raw,
            at.key, at.key, at.scores, at.gamma, at.beside, at.key,
        ],
        out_specs=[at.head, at.head, at.head, at.partial, at.partial],
        out_shape=[
            jax.ShapeDtypeStruct(f_up.shape, qkv.dtype),
            jax.ShapeDtypeStruct(f_up.shape, qkv.dtype),
            jax.ShapeDtypeStruct(f_up.shape, f_up.dtype),
            partial, partial,
        ],
        scratch_shapes=[pltpu.VMEM((sums_t.shape[1], dk), jnp.bfloat16)],
        compiler_params=_PARALLEL_CHUNKS,
        interpret=interpret,
        name="kda_prep_bwd",
    )(jnp.asarray(sums), jnp.asarray(sums_t), jnp.asarray(owner),
      *_raw_inputs(qkv, f_up, dt_bias, a_log),
      _rows(dqd), _rows(dke), _rows(dp), dgam, _rows(da), _rows(dkd))
    dv = jnp.zeros((b, s, qkv.shape[-1] - 2 * width), qkv.dtype)
    return (
        jnp.concatenate([dq, dkey, dv], axis=-1), df,
        jnp.sum(dbias, axis=(0, 1, 2)).astype(dt_bias.dtype),
        jnp.sum(dalog.reshape(-1, h, dk), axis=(0, 2)).astype(a_log.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def chunk_scores(qkv, f_up, dt_bias, a_log, chunk: int):
    """What the chunks' operands take from q, k and the log-decays alone, as
    Pallas kernels with a WRITTEN backward (`_prep_forward`'s inputs and
    results): the kernels read the node's RAW inputs in the model's layout
    and normalise q and k and take the softplus in VMEM. What the backward
    keeps is those inputs: it recomputes the gates and every decay."""
    return _prep_forward(qkv, f_up, dt_bias, a_log, chunk, context.interpret_default())


def _chunk_scores_fwd(qkv, f_up, dt_bias, a_log, chunk):
    return chunk_scores(qkv, f_up, dt_bias, a_log, chunk), (
        qkv, f_up, dt_bias, a_log
    )


def _chunk_scores_bwd(chunk, kept, cotangents):
    return _prep_backward(*kept, cotangents, chunk, context.interpret_default())


chunk_scores.defvjp(_chunk_scores_fwd, _chunk_scores_bwd)


# The triangular system's float32 [Q, Q] arrays (A, Diag(beta) A, the inverse
# X, the cotangents dn and dA) cross HBM TWO chunk-heads to a row,
# [.., c / 2, Q, 2 Q]: chunk-head 2 p in lanes [0, Q), 2 p + 1 in [Q, 2 Q)
# (`_side_by_side`), whole 128-lane rows at Q = 64 where a [rows, 64] float32
# array is padded to twice its bytes and streams at a fifth of the HBM's rate.
# The operands' kernels write A so and read dA so, `kda_prep_inverse` reads A
# and writes X so, `kda_corrected_fwd` / `kda_corrected_bwd` read X and A and
# write dA so; Diag(beta) A, dn, Diag(beta) dn and beta's share of dn exist in
# VMEM alone, and no XLA pass stands between two of the kernels. beta
# [.., c, Q] IS that layout when read two chunk-heads a row, [.., c / 2, 2 Q].
#
# The triangular inverse as a kernel (`unit_lower_inverse`'s levels, its
# products float32 with every term `HIGHEST` keeps) works on the pair as it
# lies ([Q, 2Q]: a float32 [Q, Q] fills half of each vreg), the right-hand
# operand of a product block-diagonal [2Q, 2Q], so that one MXU pass
# multiplies both chunks at full depth and width and every vector operation
# works on whole vregs.


def _pair_product(lhs, rhs, first):
    """[A_a B_a | A_b B_b] of lhs = [A_a | A_b] and rhs = [B_a | B_b], float32
    [Q, 2Q]; `first` [Q, 2Q] marks the first chunk's lanes. With a_1 + a_2 +
    a_3 the bf16 parts of an operand, the six terms a_i b_j, i + j <= 4."""
    q = lhs.shape[0]
    l1, l2, l3 = _bf16_parts(lhs)
    r1, r2, r3 = _bf16_parts(jnp.concatenate(
        [jnp.where(first, rhs, 0.0), jnp.where(first, 0.0, rhs)], axis=0
    ))
    by_r1 = _mm(jnp.concatenate([l1, l2, l3], axis=0), r1, _NN)
    by_r2 = _mm(jnp.concatenate([l1, l2], axis=0), r2, _NN)
    small = (by_r1[2 * q:] + by_r2[q:]) + _mm(l1, r3, _NN)
    return by_r1[:q] + ((by_r1[q:2 * q] + by_r2[:q]) + small)


def _down_the_rows(steps, diagonal):
    """[Q, 1] of steps [1, Q]: a chunk-head's beta lies along the lanes (T's
    columns) and scales ROWS here; one term and zeros a sum, so exact."""
    return jnp.sum(jnp.where(diagonal, steps, 0.0), axis=1, keepdims=True)


def _kda_inverse_kernel(owner_ref, eye_ref, a_ref, beta_ref, x_ref, *,
                        pairs: int):
    """(I + Diag(beta) A)^-1 of each of the program's pairs of chunk-heads,
    up the levels from the narrowest: a and x [pairs Q, 2Q], beta
    [pairs, 2Q] (the section's comment); `owner_ref` and `eye_ref` [Q, 2Q]
    hold `_prep_tables`' owner and the identity for both chunks of a pair."""
    q = owner_ref.shape[0]
    narrowest = q.bit_length() - 2
    owner = owner_ref[:]
    first = lax.broadcasted_iota(jnp.int32, (q, 2 * q), 1) < q
    diagonal = owner[:, :q] == narrowest + 1

    def one_pair(c, _):
        rows = pl.ds(pl.multiple_of(c * q, q), q)
        beta = beta_ref[pl.ds(c, 1), :]
        n = a_ref[rows, :] * jnp.where(
            first, _down_the_rows(beta[:, :q], diagonal),
            _down_the_rows(beta[:, q:], diagonal),
        )
        # the first level's X is the identity, and I L I = L: no product
        x = eye_ref[:] - jnp.where(owner == narrowest, n, 0.0)
        for level in range(narrowest - 1, -1, -1):
            below = jnp.where(owner == level, n, 0.0)
            x = x - _pair_product(_pair_product(x, below, first), x, first)
        x_ref[rows, :] = x

    lax.fori_loop(0, pairs, one_pair, None, unroll=True)


@functools.partial(jax.jit, static_argnums=(2,))
def _pallas_inverse(a, beta, interpret):
    """`unit_lower_inverse` of Diag(beta) A, pairs in and pairs out: a
    [.., c / 2, Q, 2Q] float32 and beta [.., c, Q], four pairs a program
    where they divide."""
    q = a.shape[-2]
    count = a.size // (2 * q * q)
    pairs = next(p for p in (4, 2, 1) if count % p == 0)
    owner = np.tile(_prep_tables(q)[2], (1, 2))
    eye = np.tile(np.eye(q, dtype=np.float32), (1, 2))
    rows = pl.BlockSpec((pairs * q, 2 * q), lambda i: (i, 0))

    x = pl.pallas_call(
        functools.partial(_kda_inverse_kernel, pairs=pairs),
        grid=(count // pairs,),
        in_specs=[
            _table(owner), _table(eye), rows,
            pl.BlockSpec((None, pairs, 2 * q), lambda i: (i, 0, 0)),
        ],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((count * q, 2 * q), a.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="kda_prep_inverse",
    )(jnp.asarray(owner), jnp.asarray(eye), a.reshape(count * q, 2 * q),
      beta.reshape(-1, pairs, 2 * q))
    return x.reshape(a.shape)


# The two products around the inverse, W = T (K exp(G)) and U = T V with
# T = X Diag(beta), and the WRITTEN backward of the whole triangular system
# as kernels: `n` chunk-heads a program, unrolled, every operand of one
# chunk-head in VMEM, X, A and dA a pair of chunk-heads a row block (a lane
# half of a block in VMEM is a chunk-head's: `_beside`). With Y_w = X^T dW,
# Y_u = X^T dU and M = Y_w Kd^T + Y_u V^T, a [Q, Q] matrix, the cotangents are
#
#     dKd = Diag(beta) Y_w     dV = Diag(beta) Y_u
#     dn = -M T^T              strictly under the diagonal
#     dA = Diag(beta) dn       (n = Diag(beta) A)
#     dbeta = diag(M) + sum_j dn_rj A_rj      (T's columns, then n's rows)
#
# dn `unit_lower_inverse`'s dn = -X^T dX X^T with dX = dT Diag(beta),
# dT = dW Kd^T + dU V^T, so that X^T dX X^T = (X^T dT) T^T = M T^T; it is
# -(Y_w W^T + Y_u U^T) with W = T Kd and U = T V, which the backward never
# forms. Three products a chunk-head (Y, M, dn) where differentiating
# `_corrected` runs six, and dT, dX, X^T dX and dn reach HBM nowhere. Every
# product is float32 with every term `HIGHEST` keeps (`_split_product`).


def _parts(x):
    """The bf16 arrays that add up to x exactly: x itself where it is bf16,
    three of anything wider (`_bf16_parts`)."""
    if x.dtype == jnp.bfloat16:
        return (x,)
    return _bf16_parts(x.astype(jnp.float32))


def _split_product(lhs, rhs, dims):
    """The float32 product of two operands given as their bf16 parts
    (`_parts`), `dims` one of `_NN`, `_NT`, `_TN`: with l_1 + l_2 + l_3 and
    r_1 + r_2 + r_3 the parts, the six terms l_i r_j, i + j <= 4, as
    `_pair_product` takes them; an operand that IS bf16 has one part and the
    terms of its others, exactly zero, are left out. The terms are ONE
    product, stacked along the contraction from the smallest up: a [Q, Q]
    operand's six fill three whole passes of the matrix unit's 128-deep
    contraction where one a term fills half of six, and the product's own
    float32 accumulation adds them."""
    terms = sorted(
        ((i, j) for i in range(len(lhs)) for j in range(len(rhs))
         if i + j < _PARTS),
        key=lambda term: -sum(term),
    )
    lhs_axis, rhs_axis = {_NN: (1, 0), _NT: (1, 1), _TN: (0, 0)}[dims]
    return _mm(
        jnp.concatenate([lhs[i] for i, _ in terms], axis=lhs_axis),
        jnp.concatenate([rhs[j] for _, j in terms], axis=rhs_axis), dims,
    )


def _kda_corrected_fwd_kernel(x_ref, beta_ref, kd_ref, v_ref, w_ref, uv_ref,
                              *, heads: int):
    """W = T Kd and U = T V, T = X Diag(beta), of each of the program's
    chunk-heads: x [n / 2 Q, 2Q] (pairs) and kd [n Q, dk] float32, v
    [n Q, dv], beta [n / 2, 2Q] (a chunk-head's steps along the lanes, as
    T's columns lie)."""
    q = x_ref.shape[1] // 2

    def one(c, i, half, _):
        rows = pl.ds(pl.multiple_of(c * q, q), q)
        pair, lanes = _beside(i, half, q)
        t = _parts(x_ref[pair, lanes] * beta_ref[pl.ds(i, 1), lanes])
        w_ref[rows, :] = _split_product(
            t, _parts(kd_ref[rows, :]), _NN
        ).astype(w_ref.dtype)
        uv_ref[rows, :] = _split_product(
            t, _parts(v_ref[rows, :]), _NN
        ).astype(uv_ref.dtype)

    _each_chunk(heads, one)


def _kda_corrected_bwd_kernel(
    x_ref, a_ref, beta_ref, kd_ref, v_ref, dw_ref, duv_ref,
    da_ref, dbeta_ref, dkd_ref, dv_ref, yw_ref, yu_ref, m_ref, *, heads: int,
):
    """The cotangents of A (through n = Diag(beta) A and X = (I + n)^-1),
    beta (through T's columns and n's rows), Kd and V from those of W and U,
    of each of the program's chunk-heads (the section's comment): one sweep
    over the chunk-heads a product, Y and M between the sweeps in VMEM
    (`yw_ref`, `yu_ref` [n Q, .] and `m_ref` [n Q, Q] float32), so that a
    sweep is `heads` short independent chains and not one long one a
    chunk-head."""
    q = x_ref.shape[1] // 2
    r = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    diagonal, below = r == j, j < r

    def rows_of(c):
        return pl.ds(pl.multiple_of(c * q, q), q)

    def steps_of(i, half):  # [1, Q]: T's columns
        return pl.ds(i, 1), _beside(i, half, q)[1]

    def from_the_left(c, i, half, _):  # Y = X^T dW | X^T dU
        rows = rows_of(c)
        by_row = _down_the_rows(beta_ref[steps_of(i, half)], diagonal)
        xs = _parts(x_ref[_beside(i, half, q)])
        y_w = _split_product(xs, _parts(dw_ref[rows, :]), _TN)
        y_u = _split_product(xs, _parts(duv_ref[rows, :]), _TN)
        yw_ref[rows, :], yu_ref[rows, :] = y_w, y_u
        dkd_ref[rows, :] = by_row * y_w
        dv_ref[rows, :] = (by_row * y_u).astype(dv_ref.dtype)

    def against_the_operands(c, i, half, _):  # M = Y_w Kd^T + Y_u V^T
        rows = rows_of(c)
        m = _split_product(
            _parts(yw_ref[rows, :]), _parts(kd_ref[rows, :]), _NT
        ) + _split_product(_parts(yu_ref[rows, :]), _parts(v_ref[rows, :]), _NT)
        m_ref[rows, :] = m
        dbeta_ref[steps_of(i, half)] = jnp.sum(
            jnp.where(diagonal, m, 0.0), axis=0, keepdims=True
        )

    def from_the_right(c, i, half, _):  # dn = -M T^T, and what n hands on
        pair = _beside(i, half, q)
        beta = beta_ref[steps_of(i, half)]
        dn = jnp.where(below, -_split_product(
            _parts(m_ref[rows_of(c), :]), _parts(x_ref[pair] * beta), _NT
        ), 0.0)
        da_ref[pair] = _down_the_rows(beta, diagonal) * dn
        by_row = jnp.sum(dn * a_ref[pair], axis=1, keepdims=True)
        dbeta_ref[steps_of(i, half)] += jnp.sum(
            jnp.where(diagonal, by_row, 0.0), axis=0, keepdims=True
        )

    for sweep in (from_the_left, against_the_operands, from_the_right):
        _each_chunk(heads, sweep)


# chunk-heads a program of the two kernels above: the largest that divides
# their number, which is even (X, A and dA hold them two by two), or, where
# v is read in the model's layout, the chunks of one head
_CORRECTED_HEADS = (16, 8, 4, 2)


class _CorrectedBlocks:
    """The BlockSpecs over the one grid axis, `n` chunk-heads a program: what
    a chunk-head has, [count * Q, width], its rows; X, A and dA
    [count / 2 * Q, 2Q] the rows of the program's pairs (`pairs`); beta and
    its cotangent [count / n, n / 2, 2Q] a program's rows. With `in_place` =
    (heads, chunks a head) v and its cotangent lie as the MODEL has them,
    [b, s, heads * dv] (`value`): a program's chunk-heads are then chunks of
    ONE head, n the largest that divides a head's chunks, and a head of
    dv = 128 value channels one 128-lane column block of their rows."""

    def __init__(self, count: int, q: int, dk: int, dv: int, itemsize: int,
                 in_place=None):
        self.in_place = in_place is not None
        heads, chunks = in_place or (1, count)
        n = self.heads = next(n for n in _CORRECTED_HEADS if chunks % n == 0)
        self.dv = dv
        self.grid = (count // n,)
        self.block_rows = n * q
        self.pairs = pl.BlockSpec((n // 2 * q, 2 * q), lambda i: (i, 0))
        self.steps = pl.BlockSpec((None, n // 2, 2 * q), lambda i: (i, 0, 0))
        programs = chunks // n  # of one head
        self.value = pl.BlockSpec(
            (None, n * q, dv),
            lambda i: (i // (heads * programs), i % programs,
                       i // programs % heads),
        ) if in_place else self.rows(dv)
        # the backward's blocks (x, a and da two chunk-heads to a row of at
        # least 128 lanes; kd, dkd float32; v, dw, duv, dv in the model's
        # dtype), twice for the pipeline's two buffers, and as much again
        # for Y, M and what the body holds
        block = n * q * (
            3 * 4 * max(2 * q, _LANES) // 2 + 2 * 4 * dk
            + itemsize * (dk + 3 * dv)
        )
        self.between = [
            pltpu.VMEM((n * q, width), jnp.float32) for width in (dk, dv, q)
        ]
        self.params = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(4 * block, 16 * 1024 * 1024),
        )

    def rows(self, width: int):
        return pl.BlockSpec((self.block_rows, width), lambda i: (i, 0))

    def taken(self, v):
        """v as the kernels take it: where it lies, or its rows flattened."""
        return v if self.in_place else _flat(v)

    def steps_taken(self, beta):
        """beta [.., c, Q] as the kernels take it, a program's pairs' rows."""
        return beta.reshape(self.grid[0], self.heads // 2, -1)


def _flat(t):
    """[.., Q, w] -> [chunk-heads * Q, w] (pairs: [pairs * Q, 2Q])."""
    return t.reshape(-1, t.shape[-1])


def _corrected_blocks(beta, kd, v):
    """`_CorrectedBlocks` of beta [b, h, c, Q], kd [b, h, c, Q, dk] and v, by
    chunk and head [b, h, c, Q, dv] or in the model's layout [b, s, h * dv],
    which the kernels then read in place."""
    heads, chunks, q = beta.shape[-3:]
    in_place = None if v.ndim == kd.ndim else (heads, chunks)
    dv = v.shape[-1] // heads if in_place else v.shape[-1]
    return _CorrectedBlocks(
        beta.size // q, q, kd.shape[-1], dv, v.dtype.itemsize, in_place
    )


@functools.partial(jax.jit, static_argnums=(4,))
def _corrected_forward(x, beta, kd, v, interpret):
    """(T Kd in v's dtype [.., Q, dk], T V [.., Q, dv]) of x
    [.., c / 2, Q, 2Q] (pairs) and kd [.., Q, dk] float32, beta [.., Q] and
    v ([.., Q, dv], or [b, s, h * dv] where the model has it:
    `_corrected_blocks`), as a kernel."""
    q, dk = beta.shape[-1], kd.shape[-1]
    count = beta.size // q
    at = _corrected_blocks(beta, kd, v)
    dv = at.dv
    w, uv = pl.pallas_call(
        functools.partial(_kda_corrected_fwd_kernel, heads=at.heads),
        grid=at.grid,
        in_specs=[at.pairs, at.steps, at.rows(dk), at.value],
        out_specs=[at.rows(dk), at.rows(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((count * q, dk), v.dtype),
            jax.ShapeDtypeStruct((count * q, dv), v.dtype),
        ],
        compiler_params=at.params,
        interpret=interpret,
        name="kda_corrected_fwd",
    )(_flat(x), at.steps_taken(beta), _flat(kd), at.taken(v))
    return w.reshape(kd.shape), uv.reshape(*kd.shape[:-1], dv)


@functools.partial(jax.jit, static_argnums=(7,))
def _corrected_backward(x, a, beta, kd, v, dw, duv, interpret):
    """The cotangents of (a, beta, kd, v), x the inverse of I + Diag(beta) a,
    from those of `_corrected_forward`'s results; a's in pairs as a and x
    are, v's in v's layout."""
    f32 = jnp.float32
    q, dk = beta.shape[-1], kd.shape[-1]
    count = beta.size // q
    at = _corrected_blocks(beta, kd, v)
    dv = at.dv
    da, dbeta, dkd, dvalue = pl.pallas_call(
        functools.partial(_kda_corrected_bwd_kernel, heads=at.heads),
        grid=at.grid,
        in_specs=[at.pairs, at.pairs, at.steps, at.rows(dk), at.value,
                  at.rows(dk), at.rows(dv)],
        out_specs=[at.pairs, at.steps, at.rows(dk), at.value],
        out_shape=[
            jax.ShapeDtypeStruct((count // 2 * q, 2 * q), f32),
            jax.ShapeDtypeStruct((at.grid[0], at.heads // 2, 2 * q), f32),
            jax.ShapeDtypeStruct((count * q, dk), f32),
            jax.ShapeDtypeStruct(
                v.shape if at.in_place else (count * q, dv), v.dtype
            ),
        ],
        scratch_shapes=at.between,
        compiler_params=at.params,
        interpret=interpret,
        name="kda_corrected_bwd",
    )(_flat(x), _flat(a), at.steps_taken(beta), _flat(kd), at.taken(v),
      _flat(dw), _flat(duv))
    return (
        da.reshape(a.shape), dbeta.reshape(beta.shape).astype(beta.dtype),
        dkd.reshape(kd.shape), dvalue.reshape(v.shape),
    )


@jax.custom_vjp
def corrected_products(a, beta, kd, v):
    """(T Kd, T V) in v's dtype, T = (I + Diag(beta) A)^-1 Diag(beta), as
    kernels with a WRITTEN backward (the section's comment): a
    [.., c / 2, Q, 2Q] strictly lower in pairs (`_side_by_side`) and kd
    [.., Q, dk] float32, v [.., Q, dv], beta [.., c, Q] float32. What the
    backward keeps is the inverse, in pairs (`_KEPT`: under the node's
    checkpoint the inverse's kernel runs once, this forward twice), beside a,
    beta, kd and v."""
    return _corrected_products_fwd(a, beta, kd, v)[0]


def _corrected_products_fwd(a, beta, kd, v):
    interpret = context.interpret_default()
    x = checkpoint_name(_pallas_inverse(a, beta, interpret), _KEPT)
    return _corrected_forward(x, beta, kd, v, interpret), (x, a, beta, kd, v)


def _corrected_products_bwd(kept, cotangents):
    return _corrected_backward(*kept, *cotangents, context.interpret_default())


corrected_products.defvjp(_corrected_products_fwd, _corrected_products_bwd)


def _kernel_corrected(a, kd, v, beta5):
    """`_corrected` on the "kda" route, `dtype` v's: the kernels where the
    operands' kernel wrote A in pairs (`_side_by_side`: a head's chunks are
    even in number), XLA's form with `unit_lower_inverse` where it wrote
    A [.., Q, Q] a chunk; which, noted as the node's `triangular_products`
    (`kernels/context.note`): `kernels` (the inverse, T (K exp(G)), T V and
    the triangular system's whole backward from `kda_prep_inverse`,
    `kda_corrected_fwd` / `kda_corrected_bwd`, with `triangular_layout`
    `pairs` noted beside it: how A, X and dA cross HBM) or `xla`
    (`_corrected`, differentiated by JAX: the "xla" route, and an odd number
    of chunks a head here). v is [b, h, c, Q, dv], or [b, s, h * dv] as the
    model has it, which the kernels read in place and only XLA's form turns
    heads first."""
    if a.shape[-1] == a.shape[-2]:
        context.note("triangular_products", "xla")
        if v.ndim != kd.ndim:
            b, h, c, q = beta5.shape
            v = jnp.transpose(v.reshape(b, c, q, h, -1), (0, 3, 1, 2, 4))
        return _corrected(a, kd, v, beta5, v.dtype)
    context.note("triangular_products", "kernels")
    context.note("triangular_layout", "pairs")
    return corrected_products(a, beta5, kd, v)


# what a padded position's decay pre-activation reads: its softplus, and so
# the position's log-decay, is exactly 0 (exp(-1e30) is) and so is its sigmoid
_NO_DECAY = -1e30


def kernel_operands(qkv, f_up, dt_bias, a_log, v, beta, chunk: int):
    """`chunk_operands` on the "kda" route, from the node's RAW q, k
    (qkv [b, s, 2 * h * dk + h * dv], the convolution's result as it lies)
    and decay pre-activation f_up [b, s, h * dk] beside v [b, h, s', dv] and
    beta [b, h, s'] as `chunk_operands` takes them, s' = s padded to the
    chunk: the gates, the decays and the scores from one kernel, the
    triangular inverse from another, its two products with K exp(G) and V
    from a third (`_kernel_corrected`)."""
    b, h, s, dv = v.shape
    c = s // chunk
    pad = s - f_up.shape[1]
    if pad:
        # a padded position has k = 0 (raw zeros stay zeros) and g = 0
        qkv = jnp.pad(qkv, ((0, 0), (0, pad), (0, 0)))
        f_up = jnp.pad(
            f_up, ((0, 0), (0, pad), (0, 0)), constant_values=_NO_DECAY
        )
    qd, ke, p, gamma, a, kd = chunk_scores(qkv, f_up, dt_bias, a_log, chunk)
    w, uv = _kernel_corrected(
        a, kd, v.reshape(b, h, c, chunk, dv), beta.reshape(b, h, c, chunk)
    )
    return qd, w, uv, ke, p, gamma


# ---------------------------------------------------------------------------
# the chunks' operands for ONE decay a head, the Pallas form
# ---------------------------------------------------------------------------
#
# One program is `n` chunks of one (batch row, KEY head) with ALL the value
# heads that read it: q and k are loaded once, RAW and where the model has
# them (a key head's 128-lane column block of [b, s, hk * dk]), normalised in
# VMEM, and Q K^T, K K^T taken once a chunk, then met by each value head's
# mask M_rj = exp(G_r - G_j). The chunks are a `fori_loop` that is unrolled:
# the body is traced once, the value heads written out inside it. No level is
# needed and no exponent table: with the chunk's log-decays g a ROW [1, Q]
# (positions along the lanes, as [b, hv, s] lies), L_ri = g_i for i <= r and
# 0 elsewhere is a broadcast and a select, and
#
#     G_r - G_j = sum_i L_ri [i > j]        one product with a 0/1 matrix
#     G_r       = sum_i L_ri                a sum along the lanes
#     G_Q - G_r = sum_i (g_i - L_ri)        likewise
#
# each a SUM of log-decays over a set of positions and so <= 0. The product
# takes L in three bf16 parts (`_bf16_parts`: float32-exact), and its
# transpose takes the exponents' cotangent back to g the same way. M, Q K^T,
# K K^T, the normalised q and k and the decay vectors never leave VMEM.


def _head_decays(g_row, tri, after):
    """Of one chunk of one value head, from its log-decays g_row [1, Q]:
    (G_r - G_j [Q, Q], valid where j <= r; G_r [Q, 1]; G_Q - G_r [Q, 1]),
    float32 and <= 0 (the section's comment). `tri` [Q, Q] is i <= r and
    `after` [Q, Q] bf16 is [i > j]."""
    q = tri.shape[0]

    def lower(row):  # L_ri = row_i where i <= r, 0 elsewhere
        return jnp.where(
            tri, jnp.broadcast_to(row.astype(jnp.float32), (q, q)), 0.0
        )

    low = lower(g_row)
    # the bf16 parts are taken of the row, before the broadcast
    between = sum(
        _mm(lower(part).astype(jnp.bfloat16), after, _NN)
        for part in _bf16_parts(g_row)
    )
    from_start = jnp.sum(low, axis=1, keepdims=True)
    to_end = jnp.sum(g_row - low, axis=1, keepdims=True)
    return between, from_start, to_end


def _chunk_masks(q: int):
    """(i <= r, i < r as [Q, Q] bools over (r, i); [r > i] and [r < i] as
    bf16 0/1 matrices: the sets (j, r] of a pair's exponent and their
    transpose)."""
    r = lax.broadcasted_iota(jnp.int32, (q, q), 0)
    i = lax.broadcasted_iota(jnp.int32, (q, q), 1)
    one = jnp.where(r > i, 1.0, 0.0).astype(jnp.bfloat16)
    one_t = jnp.where(r < i, 1.0, 0.0).astype(jnp.bfloat16)
    return i <= r, i < r, one, one_t


def _gdn_prep_fwd_kernel(
    q_ref, k_ref, g_ref, qd_ref, ke_ref, p_ref, gam_ref, a_ref, kd_ref,
    *, chunks: int, q: int, scale: float,
):
    """Q exp(G), K exp(G_Q - G), P, exp(G_Q), the strictly lower A
    (`_side_by_side`) and K exp(G) (float32, for the triangular system) of
    each of the program's chunks and each value head of its key head, from
    the RAW q, k [n Q, dk]
    of the key head, read where the convolution left them and put over
    their own 2-norm here (`_unit_root`: float32, ROUNDED to the input's
    dtype; q times `scale`), and the heads' log-decays g [group, n, Q]."""
    f32 = jnp.float32
    dtype = q_ref.dtype
    group = g_ref.shape[0]
    tri, strict, after, _ = _chunk_masks(q)

    def one_chunk(c, i, half, _):
        rows = pl.ds(pl.multiple_of(c * q, q), q)
        qn, _ = _unit_root(q_ref[rows, :], scale)
        kn, _ = _unit_root(k_ref[rows, :], 1.0)
        qf, kf = qn.astype(f32), kn.astype(f32)
        # one product a KEY head meets its value heads' decays
        scores = _mm(jnp.concatenate([qn, kn], axis=0), kn, _NT)
        qk, kk = scores[:q], scores[q:]
        for h in range(group):
            between, from_start, to_end = _head_decays(
                g_ref[h, pl.ds(c, 1), :], tri, after
            )
            m = jnp.exp(between)
            from_start = jnp.exp(from_start)
            p_ref[h, rows, :] = jnp.where(tri, m * qk, 0.0).astype(dtype)
            a_ref[(h, *_beside(i, half, q))] = jnp.where(strict, m * kk, 0.0)
            qd_ref[h, rows, :] = (qf * from_start).astype(dtype)
            kd_ref[h, rows, :] = kf * from_start
            ke_ref[h, rows, :] = (kf * jnp.exp(to_end)).astype(dtype)
            gam_ref[h, c] = jnp.broadcast_to(
                from_start[q - 1:q, :], (1, kf.shape[1])
            )

    _each_chunk(chunks, one_chunk)


def _gdn_prep_bwd_kernel(
    q_ref, k_ref, g_ref, dqd_ref, dke_ref, dp_ref, dgam_ref, da_ref, dkd_ref,
    dq_ref, dk_ref, dg_ref, *, chunks: int, q: int, scale: float,
):
    """The cotangents of the RAW q, k (those of the normalised ones summed
    over the key head's value heads in float32, then through the norm,
    `_unit_cotangent`, and written where q and k were read) and of g from
    those of `_gdn_prep_fwd_kernel`'s six results, the norms, the products
    and every decay recomputed. With dE the cotangent of the pairs'
    exponents G_r - G_j, x_r that of G_r and y_r that of G_Q - G_r, g's is
    the sum down the rows of (dE [i > j]^T + x_r) where i <= r and of y_r
    elsewhere: the forward's sums transposed."""
    f32 = jnp.float32
    dtype = q_ref.dtype
    group = g_ref.shape[0]
    tri, strict, after, after_t = _chunk_masks(q)
    last = lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1

    def one_chunk(c, i, half, _):
        rows = pl.ds(pl.multiple_of(c * q, q), q)
        qn, q_root = _unit_root(q_ref[rows, :], scale)
        kn, k_root = _unit_root(k_ref[rows, :], 1.0)
        qf, kf = qn.astype(f32), kn.astype(f32)
        both = jnp.concatenate([qn, kn], axis=0)
        scores = _mm(both, kn, _NT)
        qk, kk = scores[:q], scores[q:]
        dq = jnp.zeros(qf.shape, f32)
        dk = jnp.zeros(kf.shape, f32)
        dqk = jnp.zeros((q, q), f32)
        dkk = jnp.zeros((q, q), f32)
        for h in range(group):
            between, from_start, to_end = _head_decays(
                g_ref[h, pl.ds(c, 1), :], tri, after
            )
            m = jnp.where(tri, jnp.exp(between), 0.0)
            from_start, to_end = jnp.exp(from_start), jnp.exp(to_end)
            dqd, dkd = dqd_ref[h, rows, :].astype(f32), dkd_ref[h, rows, :]
            dke = dke_ref[h, rows, :].astype(f32)
            dp = dp_ref[h, rows, :].astype(f32)
            da = jnp.where(strict, da_ref[(h, *_beside(i, half, q))], 0.0)
            dq = dq + dqd * from_start
            dk = dk + dkd * from_start + dke * to_end
            dqk = dqk + m * dp
            dkk = dkk + m * da
            # exp(G_Q) is the decay from the start at the last position
            at_start = dqd * qf + dkd * kf + jnp.where(last, dgam_ref[h, c], 0.0)
            x = jnp.sum(at_start, axis=1, keepdims=True) * from_start
            y = jnp.sum(dke * kf, axis=1, keepdims=True) * to_end
            d_between = sum(
                _mm(part, after_t, _NN)
                for part in _bf16_parts((dp * qk + da * kk) * m)
            )
            dg_ref[h, pl.ds(c, 1), :] = jnp.sum(
                jnp.where(tri, d_between + x, y), axis=0, keepdims=True
            )
        dqk, dkk = dqk.astype(dtype), dkk.astype(dtype)
        dq_ref[rows, :] = _unit_cotangent(
            q_ref[rows, :], q_root, dq + _mm(dqk, kn, _NN), scale
        ).astype(dq_ref.dtype)
        dk = dk + _mm(dkk, kn, _NN) + _mm(
            jnp.concatenate([dqk, dkk], axis=0), both, _TN
        )
        dk_ref[rows, :] = _unit_cotangent(
            k_ref[rows, :], k_root, dk, 1.0
        ).astype(dk_ref.dtype)

    _each_chunk(chunks, one_chunk)


class _HeadPrepBlocks:
    """The BlockSpecs over the grid (batch, KEY head, group of n chunks):
    q, k and their cotangents lie as the model has them, [b, s, hk * dk], a
    key head of dk = 128 key channels ONE 128-lane column block of the
    program's rows (`key_head`: what `conv_silu` wrote and what its backward
    reads, no heads-first copy between); what a value head has,
    [b, hv, s, .], the rows of the key head's `group` value heads (head
    hi * group onward: a key head is indexed, never repeated), A and its
    cotangent (`beside`) `side` chunks side by side,
    [b, hv, s / side, side * Q]; g [b, hv, c / n, n, Q] and exp(G_Q)
    [b, hv, c, 1, dk] likewise."""

    def __init__(self, b: int, hk: int, group: int, s: int, dk: int, q: int):
        c = s // q
        n = self.chunks = next(n for n in _PREP_CHUNKS if c % n == 0)
        side = self.side = _side_by_side(n)
        self.grid = (b, hk, c // n)

        def rows(width, count=n * q):
            return pl.BlockSpec(
                (None, group, count, width), lambda bi, hi, gi: (bi, hi, gi, 0)
            )

        self.beside = rows(side * q, n * q // side)

        self.key_head = pl.BlockSpec(
            (None, n * q, dk), lambda bi, hi, gi: (bi, gi, hi)
        )
        self.key, self.scores = rows(dk), rows(q)
        self.decay = pl.BlockSpec(
            (None, group, None, n, q), lambda bi, hi, gi: (bi, hi, gi, 0, 0)
        )
        self.gamma = pl.BlockSpec(
            (None, group, n, 1, dk), lambda bi, hi, gi: (bi, hi, gi, 0, 0)
        )
        # a program's blocks in a bf16 step (q, k and the six results or
        # their cotangents; a [., Q] tile fills 128 lanes, side by side or
        # not), twice for the
        # pipeline's two buffers, and as much again for what the body holds
        block = n * q * (
            2 * 2 * dk + group * (2 * 2 * dk + 4 * dk + 6 * _LANES)
        )
        self.params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=max(4 * block, 16 * 1024 * 1024),
        )


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _head_prep_forward(q, k, g, chunk, dk, interpret):
    """The kernel on the RAW q, k [b, s, hk * dk] in the model's layout (the
    convolution's pieces as they lie) and g [b, hv, s] float32; by chunk and
    VALUE head out ([b, hv, c, Q, .]): qd, ke, p, gamma as
    `head_decay_operands` has them of the normalised q and k, then A
    (`_side_by_side`: [b, hv, c / 2, Q, 2 Q] where the chunks are even in
    number) and K exp(G) [., Q, dk], float32."""
    f32 = jnp.float32
    b, s, width = q.shape
    hk, hv = width // dk, g.shape[1]
    c = s // chunk
    at = _HeadPrepBlocks(b, hk, hv // hk, s, dk, chunk)

    def rows(width, dtype):
        return jax.ShapeDtypeStruct((b, hv, s, width), dtype)

    qd, ke, p, gamma, a, kd = pl.pallas_call(
        functools.partial(
            _gdn_prep_fwd_kernel, chunks=at.chunks, q=chunk, scale=dk ** -0.5
        ),
        grid=at.grid,
        in_specs=[at.key_head, at.key_head, at.decay],
        out_specs=[at.key, at.key, at.scores, at.gamma, at.beside, at.key],
        out_shape=[
            rows(dk, q.dtype), rows(dk, q.dtype), rows(chunk, q.dtype),
            jax.ShapeDtypeStruct((b, hv, c, 1, dk), f32),
            jax.ShapeDtypeStruct((b, hv, s // at.side, at.side * chunk), f32),
            rows(dk, f32),
        ],
        compiler_params=at.params,
        interpret=interpret,
        name="gdn_prep_fwd",
    )(q, k, g.reshape(b, hv, c // at.chunks, at.chunks, chunk))

    def by_chunk(t):
        return t.reshape(b, hv, -1, chunk, t.shape[-1])

    return by_chunk(qd), by_chunk(ke), by_chunk(p), gamma, by_chunk(a), by_chunk(kd)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _head_prep_backward(q, k, g, cotangents, chunk, dk, interpret):
    """The cotangents of `_head_prep_forward`'s three inputs, q's and k's in
    the model's layout as q and k were read."""
    b, s, width = q.shape
    hk, hv = width // dk, g.shape[1]
    at = _HeadPrepBlocks(b, hk, hv // hk, s, dk, chunk)
    dqd, dke, dp, dgam, da, dkd = cotangents
    by_program = (b, hv, s // chunk // at.chunks, at.chunks, chunk)
    dq, dkey, dg = pl.pallas_call(
        functools.partial(
            _gdn_prep_bwd_kernel, chunks=at.chunks, q=chunk, scale=dk ** -0.5
        ),
        grid=at.grid,
        in_specs=[
            at.key_head, at.key_head, at.decay,
            at.key, at.key, at.scores, at.gamma, at.beside, at.key,
        ],
        out_specs=[at.key_head, at.key_head, at.decay],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(by_program, g.dtype),
        ],
        compiler_params=at.params,
        interpret=interpret,
        name="gdn_prep_bwd",
    )(q, k, g.reshape(by_program),
      _rows(dqd), _rows(dke), _rows(dp), dgam, _rows(da), _rows(dkd))
    return dq, dkey, dg.reshape(g.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def head_chunk_scores(q, k, g, chunk: int, dk: int):
    """`chunk_scores` for ONE log-decay a value head: what
    `head_decay_operands` takes from q, k and the log-decays alone, as Pallas
    kernels with a WRITTEN backward (`_head_prep_forward`'s inputs and
    results): the kernels read q and k RAW, heads of `dk` columns in the
    model's layout, and normalise them in VMEM. What the backward keeps is
    those inputs (what the node's checkpoint holds anyway): it recomputes
    the norms, the products and every decay."""
    return _head_prep_forward(q, k, g, chunk, dk, context.interpret_default())


def _head_chunk_scores_fwd(q, k, g, chunk, dk):
    return head_chunk_scores(q, k, g, chunk, dk), (q, k, g)


def _head_chunk_scores_bwd(chunk, dk, kept, cotangents):
    return _head_prep_backward(
        *kept, cotangents, chunk, dk, context.interpret_default()
    )


head_chunk_scores.defvjp(_head_chunk_scores_fwd, _head_chunk_scores_bwd)


def head_kernel_operands(q, k, v, g, beta, chunk: int, dk: int):
    """`head_decay_operands` on the "kda" route, from the node's RAW q, k
    [b, s, hk * dk] and v [b, s, hv * dv] in the MODEL's layout (the
    convolution's pieces as they lie, s whole chunks) beside g and beta
    [b, hv, s]: the norms, the decays and the scores from one kernel, the
    triangular inverse from another, its two products with K exp(G) and V
    from a third (`_kernel_corrected`), which reads v where it lies too."""
    b, hv, s = g.shape
    qd, ke, p, gamma, a, kd = head_chunk_scores(q, k, g, chunk, dk)
    w, uv = _kernel_corrected(a, kd, v, beta.reshape(b, hv, s // chunk, chunk))
    return qd, w, uv, ke, p, gamma


def scan_route(key_dim: int, value_dim: int, chunk: int) -> str:
    """Which form the recurrence takes, the chunks' operands and
    `chunk_scan` alike, from what the trace can observe:

    - "kda": the Pallas kernels, where the backend is a TPU (or the CPU with
      interpret mode opted in, `context.interpret_default`), a head's key
      and value sizes are multiples of 128 lanes (the published 128 / 128)
      and its chunk of whole sublane tiles, and the trace admits a bare
      Pallas call (`context.admits_bare_pallas_call`: a sharded form as
      `kernels/ssm` has is not written yet, ROADMAP Reach (5));
    - "xla": everything else, the scan over the chunks."""
    if key_dim % _LANES or value_dim % _LANES or chunk % 16:
        return "xla"
    admitted = context.admits_bare_pallas_call(context.interpret_default())
    return "kda" if admitted else "xla"


def operand_form(attrs: GatedDeltaAttrs, route: str, seq: int) -> str:
    """Which form the chunks' operands of a node take on `route`
    (`scan_route`'s answer) over `seq` positions, noted as the node's
    `delta_rule_operands` as well (`kernels/context.note`): the attrs' own
    `decay` names the set of kernels, and with one decay a head the sequence
    says whether they read q, k and v where the convolution left them
    (`head_kernels_in_place`: `gdn_prep_fwd` / `gdn_prep_bwd` and
    `kda_corrected_*` on whole chunks) or padded copies of them
    (`head_kernels`), `head_xla` being the same form from
    `head_decay_operands` on heads-first copies; with a decay a key channel
    `channel_kernels` (`kda_prep_fwd` / `kda_prep_bwd`) or `xla`
    (`chunk_operands`); nothing else chooses. The "xla" route also settles
    the products around the triangular inverse (`triangular_products`,
    `_kernel_corrected`)."""
    if attrs.per_head_decay and route == "kda":
        padded = seq % attrs.chunk_size
        form = "head_kernels" if padded else "head_kernels_in_place"
    elif attrs.per_head_decay:
        form = "head_xla"
    else:
        form = "channel_kernels" if route == "kda" else "xla"
    context.note("delta_rule_operands", form)
    if route != "kda":
        # on the "kda" route the number of a head's chunks chooses
        # (`_kernel_corrected`)
        context.note("triangular_products", "xla")
    return form


# ---------------------------------------------------------------------------
# the gated norm, the Pallas form: one pass over the rows each way
# ---------------------------------------------------------------------------
#
# y = o r gain gate, r = rsqrt(mean_head(o^2) + eps) a position and head of dv
# lanes and gate = silu(z) or sigmoid(gate_up + gate_bias), elementwise but
# for the head's sum. o is read where `chunk_scan` left it, HEADS FIRST
# [b, h, s, dv], a head's rows of a block as one [rows, dv] slab, and the
# gate's operand where the projection left it, [b, s, .] (z as the column
# block of the input projection's row it is): no copy of either is made for
# the kernels, and do goes back heads first. A program holds a block of rows
# of `_NORM_HEADS` heads of o, of the operand's columns of those heads (and
# of dy's) in the model's dtype and walks it `_NORM_ROWS` rows a step, a
# head at a time, float32 in vector registers; r and the gate are recomputed
# in the backward, so nothing but the operands is kept and no float32 of the
# rows' width reaches HBM. With n = o r and dn = dy gate gain:
#
#     do = r (dn - n mean_head(dn n))      d(operand) = dy n gain gate'
#     dgain = sum dy n gate                dbias = sum d(operand)
#
# the two sums over a program's rows as ONE float32 tile a program (eight
# sublanes of partial sums: the rows are added eight apart), which XLA adds
# up after.
#
# What was ranked on the chip, kernels alone at [8192, 4096] bf16 under the
# silu (Qwen3-Next's node; my chip run, PR 58, o still in the model's
# layout): with 64 or 16 rows a step the forward runs in 0.314 ms and the
# backward in 0.523, 640 GB/s each way, the rate a plain pass streams at; 32
# rows a step ran the backward in 0.675 (its bundles say 0.44: a stall they
# do not count). Blocks of 1, 2, 4 and 8 MB an operand (128 to 1,024 rows):
# 0.669, 0.675, 0.688, 0.717 backward and 0.317-0.323 forward at 32 rows a
# step, so 2 MB. The head's sum through the MXU (the squares' three bf16
# parts against a [128, 128] block of ones, `_split_product`) ties with the
# XLU's from 256 rows a step up (0.3146 / 0.5227) and is slower below (1.23
# ms backward at 32): the XLU's it is, the pass being the HBM's either way.
# With o read heads first, as now (same run, second call): 0.315 / 0.518 at
# 64 rows a step, 0.317 / 0.515 at 16, 0.313 / 0.517 at 128, 0.317 / 0.675
# at 32 again; Kimi's node ([4096, 4096] under the sigmoid with its bias)
# 0.159 / 0.265. In the cells' traced steps a node's pair reads 0.28 + 0.52
# ms (Qwen3-Next) and 0.11 + 0.27 (Kimi), and the heads-first read took the
# [rows, width] copies of o and do off the step (`PERF.md` section 6, PR 58).
# All of that with every head of a row in one program; `_NORM_HEADS` below.

# rows a step of a program's loop (64: eight float32 registers a value)
_NORM_ROWS = 64
# heads a program (fewer where the node has fewer), the grid's third axis.
# The body is written out a head, so its trace and lowering grow with them:
# all 32 heads a program lowered in 1.0 s here and about 3 s on the chip
# tool's host, `setup_s` +3.0 s warm in both cells; 4 heads in an eighth of
# that. Kernels alone (my chip run, PR 58, forward / backward ms at Qwen3-
# Next's shape, blocks of 2 MB an operand): 32 heads 0.315 / 0.518, 16
# 0.320 / 0.522, 8 0.318 / 0.528, 4 0.314 / 0.529, 2 0.345 / 0.599; at 4
# heads blocks of 1 and 4 MB 0.323 / 0.535 and 0.313 / 0.532.
_NORM_HEADS = 4
# rows a program (the largest that divides the rows and fits `_NORM_BLOCK`)
_NORM_BLOCKS = (2048, 1024, 512, 256, 128, 64)
_NORM_BLOCK = 2 * 1024 * 1024  # bytes of one operand's block


def _gate_and_slope(x, silu: bool):
    """(gate, its slope) at the float32 pre-activation x."""
    s = jax.nn.sigmoid(x)
    if silu:
        return x * s, s * (1.0 + x * (1.0 - s))
    return s, s * (1.0 - s)


def _head_mean(t):
    """[rows, dv] -> [rows, 1]: a head's mean over its lanes."""
    return jnp.mean(t, axis=-1, keepdims=True)


def _norm_rows(i):
    return pl.ds(pl.multiple_of(i * _NORM_ROWS, _NORM_ROWS), _NORM_ROWS)


def _head_lanes(o_ref):
    """[(head, its lanes of a [rows, heads * dv] block)] of o [h, rows, dv]."""
    heads, _, dv = o_ref.shape
    return [(h, slice(h * dv, (h + 1) * dv)) for h in range(heads)]


def _head_norm_gate_fwd_kernel(*refs, eps: float):
    """refs: o [heads, rows, dv], the gate's operand [rows, heads * dv],
    (bias [1, heads * dv] float32 where the gate is the sigmoid), gain
    [1, dv] float32; y [rows, heads * dv]."""
    o_ref, x_ref, *bias_ref, gain_ref, y_ref = refs
    f32 = jnp.float32
    gain = gain_ref[:]

    def step(i, _):
        rows = _norm_rows(i)
        for h, lanes in _head_lanes(o_ref):
            of = o_ref[h, rows, :].astype(f32)
            x = x_ref[rows, lanes].astype(f32)
            if bias_ref:
                x = x + bias_ref[0][:, lanes]
            root = lax.rsqrt(_head_mean(of * of) + eps)
            gate, _ = _gate_and_slope(x, not bias_ref)
            y_ref[rows, lanes] = (of * root * gain * gate).astype(y_ref.dtype)

    lax.fori_loop(0, o_ref.shape[1] // _NORM_ROWS, step, None)


def _head_norm_gate_bwd_kernel(*refs, eps: float, biased: bool):
    """refs: o, the gate's operand, (bias where `biased`), gain as the
    forward's, dy [rows, heads * dv]; do [heads, rows, dv], the operand's
    cotangent [rows, heads * dv], the gain's partial sums [8, dv] float32
    (and the bias's [8, heads * dv])."""
    o_ref, x_ref, *bias_ref, gain_ref, dy_ref = refs[:4 + biased]
    do_ref, dx_ref, dgain_ref, *dbias_ref = refs[4 + biased:]
    f32 = jnp.float32
    gain = gain_ref[:]
    if dbias_ref:
        dbias_ref[0][:] = jnp.zeros(dbias_ref[0].shape, f32)

    def step(i, dgain):
        rows = _norm_rows(i)
        for h, lanes in _head_lanes(o_ref):
            of = o_ref[h, rows, :].astype(f32)
            x = x_ref[rows, lanes].astype(f32)
            if bias_ref:
                x = x + bias_ref[0][:, lanes]
            dy = dy_ref[rows, lanes].astype(f32)
            root = lax.rsqrt(_head_mean(of * of) + eps)
            gate, slope = _gate_and_slope(x, not bias_ref)
            n = of * root
            dyn = dy * n
            dgain = dgain + _eight_apart(dyn * gate)
            dx = dyn * gain * slope
            dx_ref[rows, lanes] = dx.astype(dx_ref.dtype)
            if dbias_ref:
                dbias_ref[0][:, lanes] += _eight_apart(dx)
            dn = dy * gate * gain
            along = _head_mean(dn * n)
            do_ref[h, rows, :] = (root * (dn - n * along)).astype(do_ref.dtype)
        return dgain

    dgain_ref[:] = lax.fori_loop(
        0, o_ref.shape[1] // _NORM_ROWS, step, jnp.zeros((8, o_ref.shape[2]), f32)
    )


class _NormBlocks:
    """The BlockSpecs over the grid (batch row, block of rows, group of
    `_NORM_HEADS` heads) of o [b, heads, s, dv] and of [b, s, .] operands in
    a dtype of `itemsize` bytes (`s` a multiple of `_NORM_ROWS`)."""

    def __init__(self, b: int, heads: int, s: int, dv: int, itemsize: int):
        group = math.gcd(heads, _NORM_HEADS)
        groups, width = heads // group, group * dv
        block = next(
            n for n in _NORM_BLOCKS
            if s % n == 0
            and (n * width * itemsize <= _NORM_BLOCK or n == _NORM_ROWS)
        )
        self.grid = (b, s // block, groups)
        self.heads_first = pl.BlockSpec(
            (None, group, block, dv), lambda bi, ri, hi: (bi, hi, ri, 0)
        )
        self.bias = pl.BlockSpec((1, width), lambda bi, ri, hi: (0, hi))
        self.gain = pl.BlockSpec((1, dv), lambda bi, ri, hi: (0, 0))
        # a program's partial sums: the gain's [b, blocks, groups, 8, dv],
        # the bias's [b, blocks, 8, heads * dv]
        self.gain_sums = pl.BlockSpec(
            (None, None, None, 8, dv), lambda bi, ri, hi: (bi, ri, hi, 0, 0)
        )
        self.bias_sums = pl.BlockSpec(
            (None, None, 8, width), lambda bi, ri, hi: (bi, ri, 0, hi)
        )

        def rows(column_block=0):
            # the group's columns of a [b, s, .] operand's `column_block`-th
            # heads * dv columns
            return pl.BlockSpec(
                (None, block, width),
                lambda bi, ri, hi: (bi, ri, column_block * groups + hi),
            )

        self.rows = rows
        # the backward's five blocks of rows, twice for the pipeline's two
        # buffers, and room for the rows of partial sums and the body
        self.params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=max(
                12 * block * width * itemsize, 16 * 1024 * 1024
            ),
        )


def _norm_operands(o, x, bias, gain, first: int):
    """(the operands as the kernels take them, x's column block, the
    positions they were padded with): o [b, h, s, dv] and x [b, s, .] with
    s padded with zeros to whole steps (a zero row adds nothing to a sum),
    bias and gain as float32 rows. The gate's operand is x's columns from
    `first` on: read in place where they are a whole column block, copied
    out otherwise."""
    f32 = jnp.float32
    b, heads, s, dv = o.shape
    width = heads * dv
    if first % width:
        x, first = x[..., first:first + width], 0
    pad = -s % _NORM_ROWS
    if pad:
        o = jnp.pad(o, ((0, 0), (0, 0), (0, pad), (0, 0)))
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    rows = [] if bias is None else [bias.astype(f32)[None, :]]
    return [o, x, *rows, gain.astype(f32)[None, :]], first // width, pad


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _norm_forward(o, x, bias, gain, eps, first, interpret):
    """`head_norm_gate`'s y as a kernel."""
    b, heads, s, dv = o.shape
    operands, column_block, pad = _norm_operands(o, x, bias, gain, first)
    at = _NormBlocks(b, heads, s + pad, dv, o.dtype.itemsize)
    y = pl.pallas_call(
        functools.partial(_head_norm_gate_fwd_kernel, eps=eps),
        grid=at.grid,
        in_specs=[at.heads_first, at.rows(column_block)]
        + [at.bias] * (bias is not None) + [at.gain],
        out_specs=at.rows(),
        out_shape=jax.ShapeDtypeStruct((b, s + pad, heads * dv), o.dtype),
        compiler_params=at.params,
        interpret=interpret,
        name="head_norm_gate_fwd",
    )(*operands)
    return y[:, :s] if pad else y


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _norm_backward(o, x, bias, gain, dy, eps, first, interpret):
    """The cotangents of (o, x, bias, gain) from y's."""
    f32 = jnp.float32
    b, heads, s, dv = o.shape
    width = heads * dv
    operands, column_block, pad = _norm_operands(o, x, bias, gain, first)
    at = _NormBlocks(b, heads, s + pad, dv, o.dtype.itemsize)
    if pad:
        dy = jnp.pad(dy, ((0, 0), (0, pad), (0, 0)))
    biased = bias is not None
    do, dx, dgain, *dbias = pl.pallas_call(
        functools.partial(_head_norm_gate_bwd_kernel, eps=eps, biased=biased),
        grid=at.grid,
        in_specs=[at.heads_first, at.rows(column_block)]
        + [at.bias] * biased + [at.gain, at.rows()],
        out_specs=[at.heads_first, at.rows(), at.gain_sums]
        + [at.bias_sums] * biased,
        out_shape=[
            jax.ShapeDtypeStruct((b, heads, s + pad, dv), o.dtype),
            jax.ShapeDtypeStruct((b, s + pad, width), x.dtype),
            jax.ShapeDtypeStruct((*at.grid, 8, dv), f32),
        ] + [jax.ShapeDtypeStruct((*at.grid[:2], 8, width), f32)] * biased,
        compiler_params=at.params,
        interpret=interpret,
        name="head_norm_gate_bwd",
    )(*operands, dy)
    if pad:
        do, dx = do[:, :, :s], dx[:, :s]
    after = x.shape[-1] - first - width
    if first or after:  # the columns of x that the gate does not read
        dx = jnp.pad(dx, ((0, 0), (0, 0), (first, after)))
    return (
        do, dx,
        jnp.sum(dbias[0], axis=(0, 1, 2)).astype(bias.dtype) if biased else None,
        jnp.sum(dgain, axis=(0, 1, 2, 3)).astype(gain.dtype),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def head_norm_gate(o, x, bias, gain, eps: float, first: int = 0):
    """y [b, s, h * dv]: the rms norm of each head's dv features of o
    [b, h, s, dv], HEADS FIRST as the recurrence leaves it (dv a multiple of
    128 lanes; gain [dv], shared by the heads), under a gate of the h * dv
    columns of x [b, s, .] from `first` on: their silu where `bias` is None,
    else the sigmoid of them + bias [h * dv]; float32 inside, o's dtype out
    (`_head_norm_silu`, `_head_norm_gate` on o in the model's layout), as
    the kernels `head_norm_gate_fwd` and, its WRITTEN backward,
    `head_norm_gate_bwd` (the section's comment). What the backward keeps is
    the operands; x's other columns get a zero cotangent."""
    return _norm_forward(o, x, bias, gain, eps, first, context.interpret_default())


def _head_norm_gate_vjp_fwd(o, x, bias, gain, eps, first):
    return head_norm_gate(o, x, bias, gain, eps, first), (o, x, bias, gain)


def _head_norm_gate_vjp_bwd(eps, first, kept, dy):
    return _norm_backward(*kept, dy, eps, first, context.interpret_default())


head_norm_gate.defvjp(_head_norm_gate_vjp_fwd, _head_norm_gate_vjp_bwd)


# ---------------------------------------------------------------------------
# the node
# ---------------------------------------------------------------------------


def _unit_root(t, scale: float):
    """(t [.., d] over its own 2-norm, times `scale`, in float32 and back;
    the inverse root [.., 1] float32)."""
    tf = t.astype(jnp.float32)
    root = lax.rsqrt(jnp.sum(tf * tf, axis=-1, keepdims=True) + L2_EPS)
    return (tf * (root * scale)).astype(t.dtype), root


def _unit(t, scale: float):
    """t [.., d] over its own 2-norm, times `scale`, in float32 and back."""
    return _unit_root(t, scale)[0]


def _recurrence(attrs: GatedDeltaAttrs, route: str, qkv, f_up, dt_bias, a_log,
                b_logit):
    """qkv [b, s, 2*h*dk + h*dv] after the convolution, f_up [b, s, h*dk] the
    decay's pre-activation, b_logit [b, s, h] -> o [b, h, s, dv], heads
    first as `chunk_scan` leaves it."""
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
        v = heads_first(qkv[..., 2 * kw:], dv)
        beta = jax.nn.sigmoid(heads_first(b_logit, 1).astype(f32))[..., 0]
    if route == "kda":
        # the scores' kernels read q, k and f_up where they lie and do the
        # rest of the gates in VMEM
        with jax.named_scope("prep"):
            operands = kernel_operands(
                qkv, f_up, dt_bias, a_log, v, beta, chunk
            )
    else:
        with jax.named_scope("gates"):
            q = _unit(heads_first(qkv[..., :kw], dk), dk ** -0.5)
            k = _unit(heads_first(qkv[..., kw:2 * kw], dk), 1.0)
            rate = -jnp.exp(a_log.astype(f32))[:, None, None]
            g = rate * jax.nn.softplus(
                heads_first(f_up, dk).astype(f32)
                + dt_bias.astype(f32).reshape(h, 1, dk)
            )
        with jax.named_scope("prep"):
            operands = chunk_operands(q, k, v, g, beta, chunk)
    with jax.named_scope("scan"):
        o = chunk_scan(route, *operands)
    return o.reshape(b, h, s + pad, dv)[:, :, :s]


def _recurrence_head_decay(attrs: GatedDeltaAttrs, route: str, qkv, a_pre,
                           dt_bias, a_log, b_logit):
    """`_recurrence` for one log-decay a value head: qkv, the convolution's
    result in its three pieces (q and k [b, s, hk*dk], v [b, s, hv*dv]:
    `conv_silu`'s `pieces`, so that their cotangents go back apart), a_pre
    and b_logit [b, s, hv] -> o [b, hv, s, dv]. On the "kda" route the
    kernels of `head_kernel_operands` read the three pieces where they lie
    (`operand_form`: `head_kernels_in_place`; padded to the chunk first
    where the sequence is not whole chunks, `head_kernels`) and hand their
    cotangents back the same way; on the "xla" route q, k and v are turned
    heads first and q and k normalised here, under `gates`."""
    f32 = jnp.float32
    q, k, v = qkv
    b, s, _ = q.shape
    hv, hk, dk, dv, chunk = (
        attrs.num_heads, attrs.key_heads, attrs.key_dim, attrs.value_dim,
        attrs.chunk_size,
    )
    pad = -s % chunk

    def heads_first(t, heads):
        # [b, s, heads * width] -> [b, heads, s + pad, width]; a padded
        # position has k = 0 and v = 0: it writes nothing
        t = jnp.swapaxes(t.reshape(b, s, heads, -1), 1, 2)
        return jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else t

    with jax.named_scope("gates"):
        beta = jax.nn.sigmoid(heads_first(b_logit, hv).astype(f32))[..., 0]
        g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            heads_first(a_pre, hv)[..., 0].astype(f32)
            + dt_bias.astype(f32)[:, None]
        )
        if route != "kda":
            v = heads_first(v, hv)
            q = _unit(heads_first(q, hk), dk ** -0.5)
            k = _unit(heads_first(k, hk), 1.0)
        elif pad:
            q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in qkv)
    with jax.named_scope("prep"):
        if route == "kda":
            operands = head_kernel_operands(q, k, v, g, beta, chunk, dk)
        else:
            operands = head_decay_operands(q, k, v, g, beta, chunk)
    with jax.named_scope("scan"):
        o = chunk_scan(route, *operands)
    return o.reshape(b, hv, s + pad, dv)[:, :, :s]


def _head_norm(o, gain, heads: int, eps: float):
    """rms_norm of each head's dv features of o (gain [dv], shared by the
    heads), float32 [b, s, heads * dv]."""
    f32 = jnp.float32
    b, s, width = o.shape
    of = o.astype(f32).reshape(b, s, heads, width // heads)
    root = lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) + eps)
    return (of * root * gain.astype(f32)).reshape(b, s, width)


def _head_norm_silu(o, z, gain, heads: int, eps: float):
    """`_head_norm` of o times silu(z), float32 inside, o's dtype out."""
    normed = _head_norm(o, gain, heads, eps)
    return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


def _gated_delta_head_decay(attrs: GatedDeltaAttrs, u, weights):
    """`gated_delta_forward` for `decay` "head"."""
    w_in, w_ba, w_conv, dt_bias, a_log, gain, w_out = weights
    cw, kw, hv = attrs.conv_width, attrs.key_width, attrs.num_heads
    proj = u @ w_in
    with jax.named_scope("conv"):
        qkv = conv_silu(proj, w_conv, None, pieces=(kw, kw, cw - 2 * kw))
    with jax.named_scope("gates"):
        ba = u @ w_ba
    route = scan_route(attrs.key_dim, attrs.value_dim, attrs.chunk_size)
    operand_form(attrs, route, u.shape[1])
    o = jax.checkpoint(
        functools.partial(_recurrence_head_decay, attrs, route),
        policy=_KEEP_INVERSE,
    )(qkv, ba[..., hv:], dt_bias, a_log, ba[..., :hv])
    with jax.named_scope("norm"):
        y = _gated_head_norm(attrs, route, o, proj, None, gain, first=cw)
    return y @ w_out


def _head_norm_gate(o, gate_up, gate_bias, gain, heads: int, eps: float):
    """`_head_norm` of o times sigmoid(gate_up + gate_bias), float32 inside,
    o's dtype out."""
    f32 = jnp.float32
    normed = _head_norm(o, gain, heads, eps)
    gate = jax.nn.sigmoid(gate_up.astype(f32) + gate_bias.astype(f32))
    return (normed * gate).astype(o.dtype)


def _gated_head_norm(attrs: GatedDeltaAttrs, route: str, o, x, bias, gain,
                     first: int = 0):
    """The node's last part before W_out, y [b, s, h * dv]: the heads' norm
    of the recurrence's o [b, h, s, dv] under the gate of x's h * dv columns
    from `first` on (their silu where `bias` is None: one decay a head; else
    the sigmoid of them + bias), in the form the node's `route` says
    (`scan_route`, nothing else chooses), noted as the node's `head_norms`
    (`kernels/context.note`): `kernels` (`head_norm_gate`: the norm under its
    gate and the whole of its backward from `head_norm_gate_fwd` /
    `head_norm_gate_bwd`) on "kda", else `xla`, the plain form on o in the
    model's layout, differentiated by JAX under a checkpoint of its own."""
    heads, eps = attrs.num_heads, attrs.norm_eps
    context.note("head_norms", "kernels" if route == "kda" else "xla")
    if route == "kda":
        return head_norm_gate(o, x, bias, gain, eps, first)
    b, _, s, dv = o.shape
    o = jnp.swapaxes(o, 1, 2).reshape(b, s, heads * dv)
    x = x[..., first:first + heads * dv]
    if bias is None:
        plain = functools.partial(_head_norm_silu, heads=heads, eps=eps)
        return jax.checkpoint(plain)(o, x, gain)
    plain = functools.partial(_head_norm_gate, heads=heads, eps=eps)
    return jax.checkpoint(plain)(o, x, bias, gain)


def gated_delta_forward(
    attrs: GatedDeltaAttrs, u: jnp.ndarray, weights: Sequence[jnp.ndarray]
) -> jnp.ndarray:
    """u [b, s, D] -> [b, s, D]; weights in `GatedDeltaAttrs` slot order."""
    if attrs.per_head_decay:
        return _gated_delta_head_decay(attrs, u, weights)
    w_in, w_conv, w_f, dt_bias, a_log, w_g, b_g, gain, w_out = weights
    cw, rank = attrs.conv_width, attrs.gate_rank
    proj = u @ w_in
    with jax.named_scope("conv"):
        qkv = conv_silu(proj, w_conv, None)
    with jax.named_scope("gates"):
        f_up = proj[..., cw:cw + rank] @ w_f
        g_up = proj[..., cw + rank:cw + 2 * rank] @ w_g
    route = scan_route(attrs.key_dim, attrs.value_dim, attrs.chunk_size)
    operand_form(attrs, route, u.shape[1])
    o = jax.checkpoint(
        functools.partial(_recurrence, attrs, route),
        policy=_KEEP_INVERSE,
    )(qkv, f_up, dt_bias, a_log, proj[..., cw + 2 * rank:])
    with jax.named_scope("norm"):
        y = _gated_head_norm(attrs, route, o, g_up, b_g, gain)
    return y @ w_out
