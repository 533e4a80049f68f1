"""OLMoE-1B-7B (Muennighoff et al., arXiv:2409.02060; Hugging Face
`modeling_olmoe.py`) as this benchmark runs it: `build` for the system under
test, `reference_losses` as the plain float32 `jax.numpy` reference, and the
arithmetic the per-layer metrics need. Departures from the published model are
in the `.json` beside this file; the reference makes the same ones. Nothing
below `build` imports the program.

The block, as the reference computes it (s positions of one sequence):

    x = E[ids]                                      # no scale, no positions
    per layer:
      a = rms(x; g_in)       rms(u; g) = u * rsqrt(mean(u^2, last) + eps) * g
      q = a Wq, k = a Wk, v = a Wv                  # no bias
      q = rms(q; g_q), k = rms(k; g_k)              # over all h*d features
      per head of d: RoPE, pairs (i, i + d/2), angle pos * theta^(-2j/d)
      x = x + concat_h(softmax(q_h k_h^T / sqrt(d) + causal) v_h) Wo
      m = rms(x; g_post)
      r = m Wg;  p = softmax(r);  S = the k experts of largest p
      x = x + sum_{e in S} p_e * ((silu(m W1_e) * (m W3_e)) W2_e)
    h = rms(x; g_f);  logits = h W_head
    loss = mean next-token CE + c_bal * sum_layers LB + c_z * sum_layers Z
    LB = E * sum_e f_e P_e,  f_e = (tokens with e in S) / N  (no gradient),
    P_e = mean_n p[n, e],  Z = mean_n (logsumexp_e r[n, e])^2

N is the tokens of the batch. Parameter layouts the reference has to know (the
program's public weight formats): embedding `weight0` [entries, hidden]; rms
norm `weight0` gain; dense `weight0` [in, out]; attention `weight0`
[per_head, heads], the rows of one head being its wq [hidden, d] | wk | wv |
wo [d, hidden], each flattened row-major, then `weight1` g_q and `weight2`
g_k, both [heads * d]; experts `weight0` router [hidden, E], `weight1` W1
[E, hidden, width], `weight2` W3, `weight3` W2 [E, width, hidden].
"""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from reference_lib import split_layers

# |system - reference| allowed on a loss (natural log, mean over positions;
# the auxiliary terms included). The system multiplies in bf16 with float32
# accumulation; its router, softmax, norms' statistics, RoPE and the
# auxiliary scalar are float32. Two readings set the bound (my chip runs,
# PR 26; PERF.md section 6). Over 12 runs of `olmoe_s4096_1chip` on seven seeds
# the system differed from this reference by at most 6.9e-4 before the step
# (median 3.4e-4) and 1.21e-3 after it (median 1.7e-4): more than the dense
# configurations' 2.9e-4, because QK-norm makes the attention scores of order
# one at initialisation, where GPT-2's are near zero and its softmax uniform,
# and because rounding the router's input to bf16 gives 0.13-0.20% of the
# (position, expert) decisions to another near-tied expert, each of which
# swaps one of a token's eight terms; Adam's first step, a sign step of 4e-4
# on every weight, doubles what the forward pass shows. The nearest precision
# below must fail: this reference with every matmul operand rounded to
# float8_e4m3 (`OPERANDS`) is off its float32 self by 3.3e-3 and 1.24e-2 (two
# seeds; bf16 operands: 5.7e-6 and 3.1e-4). 2e-3 is 1.65 times the largest
# bf16 reading and under the smallest float8 one. One Adam step moves the
# loss by 0.27, so a backward pass that does nothing fails (b) a hundredfold.
LOSS_TOLERANCE = 2e-3

INPUT_NAMES = ("input_ids",)
# positions the reference takes at a time where a whole sequence's tensor
# would not fit beside the system's state (attention scores, logits)
BLOCK = 1024
BLOCK_PREFIXES = ("ln_in_", "attn", "ln_post_", "moe")


def build(sizes, batch, seq):
    """(graph builder, logits tensor) through the public builder. The
    builder and not its bare graph, so that `from_computation_graph` adopts
    the experts' auxiliary outputs it recorded."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import TruncatedNormalInitializerAttrs

    hidden = sizes["hidden_size"]
    heads = sizes["num_attention_heads"]
    eps = sizes["rms_norm_eps"]
    std = sizes["initializer_range"]
    init = TruncatedNormalInitializerAttrs(
        stddev=std, min_cutoff=-3 * std, max_cutoff=3 * std
    )
    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    h = b.embedding(ids, sizes["vocab_size"], hidden, kernel_initializer=init,
                    name="embed")
    for i in range(sizes["num_hidden_layers"]):
        x = b.rms_norm(h, eps=eps, name=f"ln_in_{i}")
        attn = b.multihead_attention(
            x, x, x, hidden, heads, kdim=hidden // heads, vdim=hidden // heads,
            bias=sizes["attention_bias"], causal=True,
            rope_theta=float(sizes["rope_theta"]), qk_norm_eps=eps,
            initializer=init, name=f"attn{i}",
        )
        h = b.add(h, attn)
        x = b.rms_norm(h, eps=eps, name=f"ln_post_{i}")
        moe = b.experts(
            x, sizes["num_experts"], sizes["num_experts_per_tok"],
            sizes["intermediate_size"], activation=Activation.SILU,
            capacity_factor=None, use_bias=False, gated=True,
            renormalize=sizes["norm_topk_prob"],
            lambda_bal=sizes["router_aux_loss_coef"],
            lambda_z=sizes["router_z_loss_coef"],
            initializer=init, name=f"moe{i}",
        )[0]
        h = b.add(h, moe)
    h = b.rms_norm(h, eps=eps, name="ln_f")
    logits = b.dense(h, sizes["vocab_size"], use_bias=False,
                     kernel_initializer=init, name="head")
    return b, logits


def make_data(rs, sizes, n, seq):
    """`n` seeded sequences of `seq` + 1 tokens: inputs are the first `seq`,
    labels the next token at each position."""
    tokens = rs.randint(0, sizes["vocab_size"], (n, seq + 1)).astype(np.int32)
    return {"input_ids": tokens[:, :-1].copy()}, tokens[:, 1:].copy()


# -- the plain reference ----------------------------------------------------

# Every matrix product of the reference goes through `mm`, whose operands pass
# `OPERANDS` first: the identity here. The probe behind LOSS_TOLERANCE sets it
# to a rounding to float8_e4m3 and back, which is the reference "computed in
# the nearest precision below" bf16 (PERF.md section 6, PR 26).
OPERANDS = None


def mm(spec, a, b):
    if OPERANDS is not None:
        a, b = OPERANDS(a), OPERANDS(b)
    return jnp.einsum(spec, a, b)


def rms(u, gain, eps):
    return u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x [heads, s, d]: rotate-half pairing (i, i + d/2)."""
    _, s, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(w, x, sizes, rope_on=True, qk_norm_on=True):
    """Causal self-attention on x [s, hidden] from the flat weight."""
    heads = sizes["num_attention_heads"]
    hidden = x.shape[-1]
    d = hidden // heads
    flat = w["attn.weight0"]  # [3*hidden*d + d*hidden, heads]
    n = hidden * d
    wq = flat[0 * n:1 * n].reshape(hidden, d, heads)
    wk = flat[1 * n:2 * n].reshape(hidden, d, heads)
    wv = flat[2 * n:3 * n].reshape(hidden, d, heads)
    wo = flat[3 * n:4 * n].reshape(d, hidden, heads)
    # [s, heads, d]: the whole row q W_q with head h's features at [h, :]
    q = mm("se,edh->shd", x, wq)
    k = mm("se,edh->shd", x, wk)
    v = mm("se,edh->hsd", x, wv)
    s = x.shape[0]
    if qk_norm_on:
        eps = sizes["rms_norm_eps"]
        q = rms(q.reshape(s, -1), w["attn.weight1"], eps).reshape(s, heads, d)
        k = rms(k.reshape(s, -1), w["attn.weight2"], eps).reshape(s, heads, d)
    q, k = jnp.swapaxes(q, 0, 1), jnp.swapaxes(k, 0, 1)
    if rope_on:
        q, k = rope(q, sizes["rope_theta"]), rope(k, sizes["rope_theta"])

    def query_block(start):
        # the same softmax(q k^T / sqrt(d) + causal) v, for BLOCK queries
        # against every key, so that a 4,096-position sequence's scores
        # need not exist at once beside the system's own state
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = mm("hsd,htd->hst", qb, k) / np.sqrt(d)
        scores = jnp.where(
            rows[:, None] >= jnp.arange(s)[None, :], scores, -jnp.inf
        )
        return mm("hst,htd->hsd", jax.nn.softmax(scores, axis=-1), v)

    block = min(s, BLOCK)
    ctx = jax.lax.map(
        jax.checkpoint(query_block), jnp.arange(0, s, block)
    )  # [blocks, heads, block, d]
    ctx = jnp.swapaxes(ctx, 0, 1).reshape(heads, s, d)
    return mm("hsd,deh->se", ctx, wo)


def router(w, m, sizes):
    """(logits r [s, E], probabilities p [s, E], 0/1 mask of the chosen
    experts [s, E])."""
    r = mm("sd,de->se", m, w["moe.weight0"])
    p = jax.nn.softmax(r, axis=-1)
    _, chosen = jax.lax.top_k(p, sizes["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=p.dtype), axis=1)
    return r, p, mask


def experts(w, m, p, mask, sizes):
    """sum_{e in S} p_e * ((silu(m W1_e) * (m W3_e)) W2_e): every expert
    applied to every position, densely, and kept under the 0/1 mask."""
    weight = p * mask
    if sizes["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)

    def one(acc, expert):
        w1, w3, w2, we = expert
        h = jax.nn.silu(mm("sd,dh->sh", m, w1)) * mm("sd,dh->sh", m, w3)
        y = mm("sh,hd->sd", h, w2)
        return acc + we[:, None] * y, None

    out, _ = jax.lax.scan(
        lambda acc, e: jax.checkpoint(one)(acc, e),
        jnp.zeros_like(m),
        (w["moe.weight1"], w["moe.weight2"], w["moe.weight3"], weight.T),
    )
    return out


def final_hidden(p, sizes, ids):
    """One sequence ids [s]: (rms(x; g_f) [s, hidden], per layer the
    router's logits, probabilities and chosen-expert masks, each
    [layers, s, E])."""
    outer, layers = p
    eps = sizes["rms_norm_eps"]

    def block(h, w):
        a = rms(h, w["ln_in_.weight0"], eps)
        h = h + attention(w, a, sizes)
        m = rms(h, w["ln_post_.weight0"], eps)
        r, prob, mask = router(w, m, sizes)
        return h + experts(w, m, prob, mask, sizes), (r, prob, mask)

    h, routed = jax.lax.scan(
        lambda h, w: jax.checkpoint(block)(h, w),
        outer["embed.weight0"][ids], layers,
    )
    return rms(h, outer["ln_f.weight0"], eps), routed


def forward(p, sizes, ids):
    """(logits [s, vocab], the routers' outputs as `final_hidden`)."""
    h, routed = final_hidden(p, sizes, ids)
    return mm("sd,dv->sv", h, p[0]["head.weight0"]), routed


def chosen_counts(p, sizes, ids):
    """[layers, E]: positions of this sequence that chose each expert."""
    _, (_, _, mask) = final_hidden(p, sizes, ids)
    return jnp.sum(mask, axis=1)


def cross_entropy_sum(h, head, labels):
    """Summed next-token cross-entropy of h [s, hidden], BLOCK positions'
    logits at a time."""
    block = min(h.shape[0], BLOCK)

    def one(args):
        hb, yb = args
        logp = jax.nn.log_softmax(mm("sd,dv->sv", hb, head), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, yb[:, None], axis=-1))

    return jnp.sum(jax.lax.map(
        jax.checkpoint(one),
        (h.reshape(-1, block, h.shape[-1]), labels.reshape(-1, block)),
    ))


def sequence_loss(p, sizes, ids, labels, frac):
    """One sequence's share of the batch's loss, times the batch's N: summed
    cross-entropy, and the two auxiliary terms with their means over the
    batch's tokens written as sums (`frac` [layers, E] is the batch's f_e,
    counted beforehand and carrying no gradient, so the sum over sequences
    of these shares over N is the batch's loss exactly)."""
    h, (r, prob, _) = final_hidden(p, sizes, ids)
    ce = cross_entropy_sum(h, p[0]["head.weight0"], labels)
    balance = sizes["num_experts"] * jnp.sum(frac * jnp.sum(prob, axis=1))
    z = jnp.sum(jnp.square(jax.nn.logsumexp(r, axis=-1)))
    return (
        ce + sizes["router_aux_loss_coef"] * balance
        + sizes["router_z_loss_coef"] * z
    )


def adam_first_step(grad, params, adam):
    """Adam's first step (m and v start at zero) with the weight decay as an
    L2 term, as `reference_lib.losses_with_adam_step` takes it."""

    def one(g, w):
        g = g + adam["weight_decay"] * w
        m = (1.0 - adam["beta1"]) * g
        v = (1.0 - adam["beta2"]) * jnp.square(g)
        alpha_t = (
            adam["alpha"] * np.sqrt(1.0 - adam["beta2"]) / (1.0 - adam["beta1"])
        )
        return w - alpha_t * m / (jnp.sqrt(v) + adam["epsilon"])

    return jax.tree_util.tree_map(one, grad, params)


def routing_report(p, sizes, ids):
    """What the routing of one sequence looks like, first layer: the largest
    expert load over the mean, and the share of the (position, expert)
    decisions that change when the router's input and matrix are rounded to
    bf16 first, which is what the system's compute dtype does to them."""
    outer, layers = p
    first = jax.tree_util.tree_map(lambda x: x[0], layers)
    eps = sizes["rms_norm_eps"]
    h = outer["embed.weight0"][ids]
    h = h + attention(first, rms(h, first["ln_in_.weight0"], eps), sizes)
    m = rms(h, first["ln_post_.weight0"], eps)
    _, _, mask = router(first, m, sizes)

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    low = dict(first, **{"moe.weight0": rounded(first["moe.weight0"])})
    _, _, mask_low = router(low, rounded(m), sizes)
    load = jnp.sum(mask, axis=0)
    k = sizes["num_experts_per_tok"]
    return (
        jnp.max(load) / jnp.mean(load),
        jnp.sum(mask * (1 - mask_low)) / (mask.shape[0] * k),
    )


def reference_losses(params, inputs, labels, sizes, adam):
    """(loss before, loss after one Adam step) on one batch, one sequence at
    a time. `LB` is a statistic of the whole batch: f_e is counted over the
    batch in a first pass and then held fixed (it carries no gradient) while
    loss and gradient are summed over the sequences, which is exact; after
    the step it is counted again with the stepped parameters. The batch is
    an argument of every program and never a constant in it."""
    p = split_layers(params, sizes["num_hidden_layers"], BLOCK_PREFIXES)
    rows = (jnp.asarray(inputs["input_ids"]), jnp.asarray(labels))
    positions = labels.size

    @jax.jit
    def fractions(p, ids):
        def one(total, row):
            return total + chosen_counts(p, sizes, row), None

        shape = (sizes["num_hidden_layers"], sizes["num_experts"])
        total, _ = jax.lax.scan(one, jnp.zeros(shape), ids)
        return total / positions

    def mean_loss(p, rows, frac):
        # a sequence is recomputed in the backward pass, so the gradient is
        # ONE accumulator the size of the model beside the system's state
        def one(total, row):
            share = jax.checkpoint(
                lambda p, row: sequence_loss(p, sizes, *row, frac)
            )(p, row)
            return total + share / positions, None

        total, _ = jax.lax.scan(one, jnp.zeros(()), rows)
        return total

    loss_and_grad = jax.jit(jax.value_and_grad(mean_loss))
    mean_loss = jax.jit(mean_loss)

    with jax.default_matmul_precision("highest"):
        frac = fractions(p, rows[0])
        before, grad = loss_and_grad(p, rows, frac)
        imbalance, flipped = jax.jit(
            lambda p, ids: routing_report(p, sizes, ids)
        )(p, rows[0][0])
        stepped = jax.jit(
            lambda g, p: adam_first_step(g, p, adam), donate_argnums=0
        )(grad, p)
        del grad
        after = mean_loss(stepped, rows, fractions(stepped, rows[0]))
    # every decision is kept: k per position, nothing dropped
    chosen_per_token = float(jnp.sum(frac, axis=-1).mean())
    assert abs(chosen_per_token - sizes["num_experts_per_tok"]) < 1e-3, frac
    print("olmoe reference routing: " + json.dumps({
        "max_over_mean_expert_load": float(imbalance),
        "decisions_changed_by_bf16_router_share": float(flipped),
        "chosen_per_token": chosen_per_token,
    }), file=sys.stderr)
    return float(before), float(after)


# -- arithmetic for the per-layer metrics -----------------------------------


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls and attention only, nothing recomputed.
    Active parameters: a token runs its 8 experts, not all 64. Causal
    attention needs half the pairs of positions."""
    hidden = sizes["hidden_size"]
    per_layer = (
        2 * 4 * hidden * hidden
        + 2 * 2 * hidden * (seq + 1) / 2
        + 2 * hidden * sizes["num_experts"]
        + sizes["num_experts_per_tok"] * 3 * 2 * hidden
        * sizes["intermediate_size"]
    )
    head = 2 * hidden * sizes["vocab_size"]
    return 3.0 * (sizes["num_hidden_layers"] * per_layer + head)


def kernel_costs(sizes, batch, seq):
    """Least work of one training step on one chip, by kernel.

    `flash`: as in `cerebras-gpt-1.3b.py` (causal, 16 heads of 128).
    `moe`: the router and the nine grouped matmuls of a step (forward three,
    backward three for the rows and three for the matrices) at N * k rows;
    the expert matrices read once in each pass and their gradient written
    once, the gathered rows read and the experts' rows written once in each
    pass, all in bf16. The hidden [rows, width] tensors are left out: a
    fused expert would never write them."""
    hidden = sizes["hidden_size"]
    layers = sizes["num_hidden_layers"]
    width = sizes["intermediate_size"]
    experts_ = sizes["num_experts"]
    tokens = batch * seq
    rows = tokens * sizes["num_experts_per_tok"]
    pair = 2 * batch * seq * (seq + 1) / 2 * hidden
    tensor = 2 * batch * seq * hidden
    matrices = 3 * experts_ * hidden * width
    return {
        "flash": {
            "flops": layers * (2 + 5) * pair,
            "bytes": layers * (4 + 8) * tensor,
        },
        "moe": {
            "flops": layers * (
                9 * 2 * rows * hidden * width
                + 3 * 2 * tokens * hidden * experts_
            ),
            "bytes": layers * 2 * (3 * matrices + 4 * rows * hidden),
        },
    }
