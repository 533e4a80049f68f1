"""Mellum2-12B-A2.5B-Instruct (`model_type` `mellum`) as this benchmark runs
it: ONE chip's share of four of the 28 layers its config.json states, one
whole period `sliding sliding sliding full`. `build` for the system under
test, `reference_losses` as the plain float32 `jax.numpy` reference, and the
arithmetic the per-layer metrics need. The cut, the deployment it stands for,
every departure and every assumed value are in the `.json` beside this file;
the reference makes the same ones. Nothing below `build` imports the program.

The step, as the reference computes it (s positions of one sequence, token
ids t_1..t_s, labels t_2..t_{s+1}; D = hidden_size; rms(x; g) = x *
rsqrt(mean(x^2) + rms_norm_eps) * g; every matrix without bias):

    x = E[ids]
    layer i:  h = x + Attn_i(rms(x; g_ia));  x = h + MoE_i(rms(h; g_ib))
    L = mean over the s positions of CE(rms(x; g_f) W_head, labels)

Attn_i on u [s, D] (h = 32 query heads over kv = 4 key/value heads of d = 128;
query head j reads key/value head j // 8):
    q = u W_q [s, h, d];  k = u W_k,  v = u W_v [s, kv, d]
    q = rms(q; g_q [d]),  k = rms(k; g_k [d])    # EACH head's d columns alone
    q, k = rope_i(q), rope_i(k)                  # rotate-half pairs (j, j + d/2)
    out = softmax(q k^T / sqrt(d) + mask_i) v W_o
  `sliding_attention` (layer_types): key p is seen from query t when
    t - sliding_window < p <= t; rope: angle pos * theta^(-2j/d), j < d/2.
  `full_attention`: the causal mask alone; rope YaRN (`rope_type` `yarn` as
    the public `transformers` code computes it, arXiv:2309.00071):
      f_j = theta^(-2j/d);  c(r) = d ln(orig / (2 pi r)) / (2 ln theta)
      low = floor(c(beta_fast)), high = ceil(c(beta_slow)), clipped to [0, d-1]
      ramp_j = clip((j - low) / (high - low), 0, 1)
      inv_freq_j = f_j (1 - ramp_j) + (f_j / factor) ramp_j
    and cos and sin both times attention_factor, on q and on k alike.
MoE_i on m [s, D]: p = softmax(m W_r) over all `num_experts_total` experts
    in float32, S the `num_experts_per_tok` largest, w_e = p_e / sum_S p;
    out = sum_{e in S, e HELD} w_e (silu(m W1_e) * (m W3_e)) W2_e. The `held`
    experts first .. first + num_experts - 1 are here, and what the others
    would add is left out, in the program and here alike. No shared expert.
    The program takes the held rows in passes of `held_window_factor` times
    the uniform share (the file's `assumed` says why); that moves no value,
    so nothing here reads it.

Parameter layouts the reference has to know (the program's public weight
formats; `<layer>.weight<j>`): embedding and rms norm `weight0`; dense
`weight0` [in, out]; grouped-query attention `weight0` one flat column
W_q | W_k | W_v | W_o, each row-major with head-major columns, `weight1` g_q
[d], `weight2` g_k [d]; experts `weight0` W_r [D, E], `weight1` W1
[held, D, I], `weight2` W3, `weight3` W2 [held, I, D].
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference_lib import losses_with_adam_step

# |system - reference| allowed on the loss (natural log; the mean over the
# 8,192 positions of one sequence), before the step (a) and after it (b). The
# system multiplies in bf16 with float32 accumulation; its router, the norms'
# statistics, the rotary's frequencies, tables and products, the softmaxes and
# the loss are float32. Two readings set the bound, both taken by the harness's
# own comparison (my chip runs, PR 60; PERF.md section 6): over READINGS_RUNS
# runs of `mellum2_12b_s8192_1chip`, each on its own seed, the system differed
# from this reference by at most BF16_SYSTEM_MAX[0] before the step and
# BF16_SYSTEM_MAX[1] after it. The nearest precision below must fail:
# `benchmark/precision_control.py --operands float8_e4m3fn` runs the cell
# through `run.py` with every matmul operand of this reference rounded to
# float8_e4m3 (`OPERANDS`), and `correct` came out false: the system is off
# that reference by FLOAT8_REFERENCE_MIN[0] before the step and
# FLOAT8_REFERENCE_MIN[1] after it. `run.py` holds (a) and (b) to this ONE
# number; as in the other held-expert files (5e-3 there too) it is the
# Adam-amplified (b) that holds the precision: a sign step of 3e-4 on every
# weight moves this loss by 0.28 (10.56 -> 10.27), so the gradient signs a
# rounding flips show. 5e-3 is 16 times the largest bf16 reading and a 45th
# of the smaller float8 one on (b). Check (a) does NOT hold the precision:
# the float8 controls' (a) readings, 6.95e-3 and 7.99e-4, lie on both sides
# of the limit, so one control passed (a); both failed (b). A backward pass
# that does nothing fails (b) by the whole move, 56 times over; a band left
# out, a default rotary on the full layer or a dropped amplitude fail the
# float32 tolerances of tests/test_mellum2.py at the toy size.
LOSS_TOLERANCE = 5e-3
# sixteen runs on fifteen seeds (thirteen `--trace 0`, three `--trace 1`,
# one traced seed twice), twelve of them above 2**31, in three calls, the
# last from the tree's `git archive`; two float8 controls on seeds of their
# own (the smaller reading of each check is written down)
READINGS_RUNS = 16
BF16_SYSTEM_MAX = (2.54e-4, 3.02e-4)
FLOAT8_REFERENCE_MIN = (7.99e-4, 0.226)

INPUT_NAMES = ("input_ids",)
# queries the reference's attention takes at a time (32 heads' scores against
# 8,192 keys are 1 MB a query in float32, and the system's 7.1 GB of state
# lies beside the reference on the chip), and positions of logits at a time
ATTENTION_BLOCK = 256
BLOCK = 512

# Every matrix product of the reference goes through `mm`, whose operands
# pass this first: the identity here, a rounding to float8_e4m3 and back
# under `precision_control.py`, the control behind LOSS_TOLERANCE.
OPERANDS = None


def layer_kinds(sizes):
    """["sliding_attention" | "full_attention"] of the layers built."""
    kinds = sizes["layer_types"]
    assert len(kinds) == sizes["num_hidden_layers"], (
        kinds, sizes["num_hidden_layers"]
    )
    assert set(kinds) <= {"sliding_attention", "full_attention"}, kinds
    assert set(sizes["mlp_layer_types"]) == {"sparse"}, sizes["mlp_layer_types"]
    return list(kinds)


def held_range(sizes):
    """(first, count) of the routed experts this chip holds."""
    return sizes["held_experts_first"], sizes["num_experts"]


def attention_names(sizes, kind):
    """The attention nodes' layer names of one kind, as `build` names them."""
    return [f"attn{i}" for i, k in enumerate(layer_kinds(sizes)) if k == kind]


def make_data(rs, sizes, n, seq):
    """`n` seeded sequences of `seq` + 1 tokens over the vocabulary slice:
    inputs are the first `seq`, labels the next token at each position."""
    tokens = rs.randint(
        0, sizes["vocab_rows_held"], (n, seq + 1)
    ).astype(np.int32)
    return {"input_ids": tokens[:, :-1].copy()}, tokens[:, 1:].copy()


def build(sizes, batch, seq):
    """(graph builder, logits tensor) through the public builder."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops import YarnScaling
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import TruncatedNormalInitializerAttrs

    assert sizes["hidden_act"] == "silu" and not sizes["attention_bias"]
    assert not sizes["tie_word_embeddings"] and sizes["use_sliding_window"]
    hidden, vocab = sizes["hidden_size"], sizes["vocab_rows_held"]
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    eps = sizes["rms_norm_eps"]
    std = sizes["initializer_range"]
    init = TruncatedNormalInitializerAttrs(
        stddev=std, min_cutoff=-3 * std, max_cutoff=3 * std
    )
    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    h = b.embedding(ids, vocab, hidden, kernel_initializer=init, name="embed")
    for i, kind in enumerate(layer_kinds(sizes)):
        rope = sizes["rope_parameters"][kind]
        scaling = None
        if rope["rope_type"] == "yarn":
            scaling = YarnScaling(
                factor=float(rope["factor"]),
                original_max_position_embeddings=rope[
                    "original_max_position_embeddings"
                ],
                beta_fast=float(rope["beta_fast"]),
                beta_slow=float(rope["beta_slow"]),
                attention_factor=rope["attention_factor"],
            )
        else:
            assert rope["rope_type"] == "default", rope
        x = b.rms_norm(h, eps=eps, name=f"norm{i}a")
        y = b.multihead_attention(
            x, x, x, hidden, heads, kdim=d, vdim=d, causal=True,
            rope_theta=float(rope["rope_theta"]), rope_scaling=scaling,
            qk_norm_eps=eps, qk_norm_per_head=True,
            num_kv_heads=sizes["num_key_value_heads"],
            window=(
                sizes["sliding_window"] if kind == "sliding_attention" else None
            ),
            initializer=init, name=f"attn{i}",
        )
        h = b.add(h, y)
        x = b.rms_norm(h, eps=eps, name=f"norm{i}b")
        y = b.experts(
            x, sizes["num_experts_total"], sizes["num_experts_per_tok"],
            sizes["moe_intermediate_size"], activation=Activation.SILU,
            capacity_factor=None, use_bias=False, gated=True,
            renormalize=sizes["norm_topk_prob"], scoring="softmax",
            shared_hidden_size=0, held_experts=held_range(sizes),
            held_window_factor=sizes["held_window_factor"],
            initializer=init, name=f"moe{i}",
        )[0]
        h = b.add(h, y)
    h = b.rms_norm(h, eps=eps, name="norm_f")
    logits = b.dense(h, vocab, use_bias=False, kernel_initializer=init,
                     name="head")
    return b, logits


# -- the plain reference ----------------------------------------------------


def mm(spec, a, b):
    if OPERANDS is not None:
        a, b = OPERANDS(a), OPERANDS(b)
    return jnp.einsum(spec, a, b)


def rms(u, gain, eps):
    return u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * gain


def swiglu(m, w1, w3, w2):
    return mm(
        "sh,hd->sd",
        jax.nn.silu(mm("sd,dh->sh", m, w1)) * mm("sd,dh->sh", m, w3), w2,
    )


def yarn_range(rope, width):
    """(low, high): the first pair YaRN's ramp touches and the first it
    leaves `factor` times slower."""

    def c(rotations):
        return width * math.log(
            rope["original_max_position_embeddings"] / (rotations * 2 * math.pi)
        ) / (2 * math.log(rope["rope_theta"]))

    return (
        max(math.floor(c(rope["beta_fast"])), 0),
        min(math.ceil(c(rope["beta_slow"])), width - 1),
    )


def rope_frequencies(rope, width):
    """(inv_freq [width / 2], amplitude) of one layer type's rotary, from its
    `rope_parameters` entry: the formula of this file's docstring, written
    out here and not taken from the program."""
    f = rope["rope_theta"] ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    if rope["rope_type"] == "default":
        return f, 1.0
    assert rope["rope_type"] == "yarn", rope
    low, high = yarn_range(rope, width)
    ramp = jnp.clip(
        (jnp.arange(width // 2, dtype=jnp.float32) - low)
        / max(high - low, 0.001), 0.0, 1.0,
    )
    inv_freq = f * (1.0 - ramp) + f / rope["factor"] * ramp
    amplitude = rope.get("attention_factor")
    if amplitude is None:
        amplitude = 0.1 * math.log(rope["factor"]) + 1.0
    return inv_freq, float(amplitude)


def rotary(x, rope):
    """x [heads, s, d]: rotate-half pairing (j, j + d/2), positions 0..s-1."""
    _, s, d = x.shape
    inv_freq, amplitude = rope_frequencies(rope, d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1) * amplitude
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1) * amplitude
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def masked_attention(q, k, v, window):
    """softmax(q k^T / sqrt(d) + mask) v on [h, s, d] operands, dense,
    ATTENTION_BLOCK queries against every key at a time, so that an
    8,192-position sequence's scores need not exist at once. The mask keeps
    key p for query t when p <= t and, with `window`, t - window < p."""
    _, s, d = q.shape
    block = min(s, ATTENTION_BLOCK)
    assert s % block == 0, (s, block)

    def query_block(start):
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = mm("hsd,htd->hst", qb, k) / np.sqrt(d)
        ahead = rows[:, None] - jnp.arange(s)[None, :]
        keep = ahead >= 0
        if window is not None:
            keep = keep & (ahead < window)
        scores = jnp.where(keep, scores, -jnp.inf)
        return mm("hst,htd->hsd", jax.nn.softmax(scores, axis=-1), v)

    ctx = jax.lax.map(jax.checkpoint(query_block), jnp.arange(0, s, block))
    return jnp.swapaxes(ctx, 0, 1).reshape(q.shape[0], s, d)


def attention(w, name, u, sizes, kind):
    """The attention mixer of one layer type on u [s, D]: each key/value
    head repeated for the query heads that read it."""
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hidden, d, eps = u.shape[-1], sizes["head_dim"], sizes["rms_norm_eps"]
    rope = sizes["rope_parameters"][kind]
    flat = w[f"{name}.weight0"].reshape(-1)
    cuts = np.cumsum([0, hidden * heads * d, hidden * kv * d,
                      hidden * kv * d, heads * d * hidden])
    wq = flat[cuts[0]:cuts[1]].reshape(hidden, heads, d)
    wk = flat[cuts[1]:cuts[2]].reshape(hidden, kv, d)
    wv = flat[cuts[2]:cuts[3]].reshape(hidden, kv, d)
    wo = flat[cuts[3]:cuts[4]].reshape(heads, d, hidden)
    q = rotary(rms(mm("se,ehd->hsd", u, wq), w[f"{name}.weight1"], eps), rope)
    k = rotary(rms(mm("se,ehd->hsd", u, wk), w[f"{name}.weight2"], eps), rope)
    v = mm("se,ehd->hsd", u, wv)
    k, v = (jnp.repeat(t, heads // kv, axis=0) for t in (k, v))
    window = sizes["sliding_window"] if kind == "sliding_attention" else None
    return mm("hsd,hde->se", masked_attention(q, k, v, window), wo)


def router(w, name, m, sizes):
    """The combine weights [s, E]: zero where an expert was not chosen."""
    p = jax.nn.softmax(mm("sd,de->se", m, w[f"{name}.weight0"]), axis=-1)
    _, chosen = jax.lax.top_k(p, sizes["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=p.dtype), axis=1)
    weight = p * mask
    if sizes["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight


def experts(w, name, m, sizes, held=None):
    """The held experts applied to every position, densely, and kept under
    the router's weights. `held` (first, count) where it is not the file's:
    the test that adds the shares up (`w`'s expert slots then hold that
    share's experts)."""
    first, count = held or held_range(sizes)
    weight = router(w, name, m, sizes)

    def one(acc, expert):
        w1, w3, w2, we = expert
        return acc + we[:, None] * swiglu(m, w1, w3, w2), None

    out, _ = jax.lax.scan(
        lambda acc, e: jax.checkpoint(one)(acc, e),
        jnp.zeros_like(m),
        (w[f"{name}.weight1"], w[f"{name}.weight2"], w[f"{name}.weight3"],
         weight[:, first:first + count].T),
    )
    return out


def cross_entropy_sum(h, head, labels):
    """Summed next-token cross-entropy of h [s, D], BLOCK positions' logits
    at a time."""
    block = min(h.shape[0], BLOCK)

    def one(args):
        hb, yb = args
        logp = jax.nn.log_softmax(mm("sd,dv->sv", hb, head), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, yb[:, None], axis=-1))

    return jnp.sum(jax.lax.map(
        jax.checkpoint(one),
        (h.reshape(-1, block, h.shape[-1]), labels.reshape(-1, block)),
    ))


def final_hidden(w, sizes, ids):
    """One sequence ids [s] -> rms(x; g_f) [s, D]. A layer is recomputed in
    the backward pass, so that a sequence's activations fit beside the
    system's own state; the arithmetic is unchanged."""
    eps = sizes["rms_norm_eps"]
    h = w["embed.weight0"][ids]
    for i, kind in enumerate(layer_kinds(sizes)):

        def layer(w, h, i=i, kind=kind):
            u = rms(h, w[f"norm{i}a.weight0"], eps)
            h = h + attention(w, f"attn{i}", u, sizes, kind)
            m = rms(h, w[f"norm{i}b.weight0"], eps)
            return h + experts(w, f"moe{i}", m, sizes)

        h = jax.checkpoint(layer)(w, h)
    return rms(h, w["norm_f.weight0"], eps)


def sequence_loss(w, sizes, ids, labels):
    """One sequence's summed next-token cross-entropy."""
    return cross_entropy_sum(
        final_hidden(w, sizes, ids), w["head.weight0"], labels
    )


def reference_losses(params, inputs, labels, sizes, adam):
    """(L before, L after one Adam step) on one batch, one sequence at a
    time (`reference_lib.losses_with_adam_step`: the gradient is ONE
    accumulator the size of the model beside the system's state, and the
    batch an argument of every program, never a constant in it)."""
    return losses_with_adam_step(
        lambda w, row: sequence_loss(w, sizes, *row), dict(params),
        (inputs["input_ids"], labels), labels.size, adam,
    )


# -- arithmetic for the per-layer metrics -----------------------------------


def parameter_counts(sizes):
    """The parameters as built, term by term."""
    hidden, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    expert = 3 * hidden * sizes["moe_intermediate_size"]
    attention_ = 2 * hidden * heads * d + 2 * hidden * kv * d + 2 * d
    layer = {
        "attention": attention_,
        "router": hidden * sizes["num_experts_total"],
        "experts": sizes["num_experts"] * expert,
        "norms": 2 * hidden,
    }
    layers = sizes["num_hidden_layers"]
    table = hidden * sizes["vocab_rows_held"]
    return {
        "layer": layer,
        "layers": layers * sum(layer.values()),
        "embedding": table, "head": table, "final_norm": hidden,
        "total": layers * sum(layer.values()) + 2 * table + hidden,
    }


def live_pairs(sizes, seq, kind):
    """(query, key) pairs one head's mask keeps over one sequence: the causal
    half, or the band's (`window * seq - window * (window - 1) / 2`: query t
    sees min(t + 1, window) keys)."""
    window = seq
    if kind == "sliding_attention":
        window = min(sizes["sliding_window"], seq)
    return window * (window + 1) / 2 + (seq - window) * window


def attention_pairs(sizes, seq, kind):
    """FLOPs of one [seq, seq] product of a node's live pairs, all TRUE 32
    query heads of d = 128, one sequence."""
    return (
        2 * live_pairs(sizes, seq, kind) * sizes["num_attention_heads"]
        * sizes["head_dim"]
    )


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls and attention over the LIVE pairs,
    nothing recomputed, of this chip's share. A token runs the experts it is
    routed to that are HERE: k * held / E of an expert on average."""
    hidden, d = sizes["hidden_size"], sizes["head_dim"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    here = (
        sizes["num_experts_per_tok"] * sizes["num_experts"]
        / sizes["num_experts_total"]
    )
    moe = (
        2 * hidden * sizes["num_experts_total"]
        + 3 * 2 * hidden * sizes["moe_intermediate_size"] * here
    )
    total = 0.0
    for kind in layer_kinds(sizes):
        total += (
            2 * hidden * d * (2 * heads + 2 * kv)
            + 2 * attention_pairs(sizes, seq, kind) / seq + moe
        )
    return 3.0 * (total + 2 * hidden * sizes["vocab_rows_held"])


def kernel_costs(sizes, batch, seq):
    """Least work of one training step on one chip, by kernel; the same
    whatever implements it and whatever tiles a kernel visits.

    `flash_window`: the core of every sliding-window node, forward (2
    products) and backward (5), over the PAIRS inside the band
    (`live_pairs`) at the TRUE 32 query heads and d = 128. `flash`: the same
    for every full node over the causal half of the pairs. Bytes in bf16,
    each: q and o at 32 heads, k and v at the 4 published key/value heads,
    once forward; those with do read and dq, dk, dv written backward."""
    tokens = batch * seq
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    q_tensor, kv_tensor = 2 * tokens * heads * d, 2 * tokens * kv * d
    costs = {}
    for key, kind in (("flash_window", "sliding_attention"),
                      ("flash", "full_attention")):
        nodes = layer_kinds(sizes).count(kind)
        costs[key] = {
            "flops": nodes * batch * 7 * attention_pairs(sizes, seq, kind),
            "bytes": nodes * (6 * q_tensor + 6 * kv_tensor),
        }
    return costs
