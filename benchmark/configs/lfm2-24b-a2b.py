"""LFM2-24B-A2B (`model_type` `lfm2_moe`) as this benchmark runs it: ONE
chip's share of five of the 40 layers its config.json states. `build` for the
system under test, `reference_losses` as the plain float32 `jax.numpy`
reference, and the arithmetic the per-layer metrics need. The cut, the
deployment it stands for and every departure from the published description
are in the `.json` beside this file; the reference makes the same ones.
Nothing below `build` imports the program.

The tower, as the reference computes it (s positions of one sequence; layer i
is a token mixer and then a feed-forward part, each under a pre-norm
residual; D = hidden_size, every matrix without bias):

    x = E[ids]
    per layer:  x = x + mixer_i(rms(x; g_ia));  x = x + ffn_i(rms(x; g_ib))
    logits = rms(x; g_f) W_head;  loss = mean next-token CE over the slice

`conv` (layer_types "conv"; W = conv_width = D, L = conv_L_cache taps), u the
normed row:
    B | C | z = u W_in                       # [D, 3W], three W-wide parts
    g = B * z
    c_t = sum_{j<L} w[j] * g_{t-(L-1)+j}     # depthwise, causal, zeros before 0
    out = (C * c) W_out                      # no bias, no activation
`full_attention` (h query heads over kv key/value heads of d = D / h):
    q = u W_q [s, h, d];  k = u W_k,  v = u W_v [s, kv, d]
    q = rms(q; g_q [d]),  k = rms(k; g_k [d])   # EACH head's d columns by themselves
    q, k = rope(q), rope(k)                  # rotate-half, theta, positions 0..s-1
    query head j reads key/value head j // (h / kv)
    out = causal softmax(q k^T / sqrt(d)) v W_o
Feed-forward: the first `num_dense_layers` layers dense SwiGLU
    (silu(m W1) * (m W3)) W2 of width intermediate_size; the others experts:
    r = m W_g (float32), s = sigmoid(r), S = the k of largest s + b (b the
    selection bias, a buffer at zero), w_e = s_e / (sum_S s + 1e-20) * scale;
    out = sum_{e in S, e HELD} w_e (silu(m W1_e) * (m W3_e)) W2_e. The router
    is num_experts_total wide and picks num_experts_per_tok; the `held`
    experts first .. first + num_experts - 1 are here, and what the others
    would add is left out, in the program and here alike. No shared expert.

Parameter layouts the reference has to know (the program's public weight
formats; `<layer>.weight<j>`): embedding and rms norm `weight0`; dense
`weight0` [in, out]; short-conv `weight0` W_in [D, 3W], `weight1` w [L, W],
`weight2` W_out [W, D]; grouped-query attention `weight0` one flat column
W_q | W_k | W_v | W_o, each row-major with head-major columns, `weight1` g_q
[d], `weight2` g_k [d]; experts `weight0` W_g [D, E], `weight1` b [E],
`weight2` W1 [held, D, I], `weight3` W3, `weight4` W2 [held, I, D].
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _load_tower():
    """`nemotron-twotower-30b-a3b.py`, this file's own copy: its `mm` (every
    matrix product of the reference, through `OPERANDS`), `rms`, the loss a
    block of positions at a time, Adam's first step and the data."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "nemotron-twotower-30b-a3b.py",
    )
    spec = importlib.util.spec_from_file_location("bench_lfm2_tower", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


tower = _load_tower()

# |system - reference| allowed on a loss (natural log, mean over the 16,384
# positions of two sequences). The system multiplies in bf16 with float32
# accumulation; its router, the norms' statistics, the convolution's sums and
# the softmax are float32. Two readings set the bound, both taken by the
# harness's own comparison (my chip runs, PR 47; PERF.md section 6). Over
# READINGS_RUNS runs of `lfm2moe24b_s8192_1chip`, each on its own seed, the
# system differed from this reference by at most BF16_SYSTEM_MAX[0] before the
# step and BF16_SYSTEM_MAX[1] after it. The nearest precision below must fail:
# `benchmark/precision_control.py --operands float8_e4m3fn` runs the cell
# through `run.py` with every matmul operand of this reference rounded to
# float8_e4m3 (`OPERANDS`), and `correct` came out false: the system is off
# that reference by FLOAT8_REFERENCE_MIN[0] before the step and
# FLOAT8_REFERENCE_MIN[1] after it. `run.py` holds (a) and (b) to this ONE
# number; as in the other held-expert files it is the Adam-amplified (b)
# that holds the precision (a sign step of 3e-4 on every weight moves this
# loss by 0.46, so the gradient signs a rounding flips show).
# 2e-3 is 3.4 times the largest bf16 reading and a 210th of the float8 one;
# float8 passes (a) (7.6e-4) and fails by (b) alone. One Adam step moves the
# loss by 0.46, so a backward pass that does nothing fails (b) 230 times over.
LOSS_TOLERANCE = 2e-3
# twelve `--trace 0` runs and two `--trace 1` runs in two calls, fourteen
# seeds; one float8 control on a fifteenth
READINGS_RUNS = 14
BF16_SYSTEM_MAX = (5.94e-4, 5.45e-4)
FLOAT8_REFERENCE_MIN = (7.6e-4, 0.42)

INPUT_NAMES = tower.INPUT_NAMES
make_data = tower.make_data
# queries the reference's attention takes at a time: 32 heads' scores against
# 8,192 keys are 1 MB a query in float32, and the system's 7.8 GB of state
# lies beside the reference on the chip
ATTENTION_BLOCK = 256

# Every matrix product of the reference goes through the tower's `mm`, whose
# operands pass this `OPERANDS` first (`reference_losses` hands it over): the
# identity here, a rounding to float8_e4m3 and back under
# `precision_control.py`, the control behind LOSS_TOLERANCE.
OPERANDS = None


def layer_names(sizes):
    """[(index, mixer kind "conv" | "full_attention", dense feed-forward?)]
    of the layers built, in order."""
    kinds = sizes["layer_types"]
    assert len(kinds) == sizes["num_hidden_layers"], (
        kinds, sizes["num_hidden_layers"]
    )
    assert set(kinds) <= {"conv", "full_attention"}, kinds
    return [
        (i, kind, i < sizes["num_dense_layers"]) for i, kind in enumerate(kinds)
    ]


def held_range(sizes):
    """(first, count) of the routed experts this chip holds."""
    return sizes["held_experts_first"], sizes["num_experts"]


def mixer_name(i, kind):
    return f"{'conv' if kind == 'conv' else 'attn'}{i}"


def build(sizes, batch, seq):
    """(graph builder, logits tensor) through the public builder."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import TruncatedNormalInitializerAttrs

    assert not sizes["conv_bias"] and sizes["use_expert_bias"]
    hidden = sizes["hidden_size"]
    heads = sizes["num_attention_heads"]
    eps = sizes["norm_eps"]
    std = sizes["initializer_range"]
    init = TruncatedNormalInitializerAttrs(
        stddev=std, min_cutoff=-3 * std, max_cutoff=3 * std
    )
    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    h = b.embedding(ids, sizes["vocab_rows_held"], hidden, kernel_initializer=init,
                    name="embed")
    for i, kind, dense in layer_names(sizes):
        x = b.rms_norm(h, eps=eps, name=f"norm{i}a")
        if kind == "conv":
            y = b.short_conv(
                x, sizes["conv_width"], conv_kernel=sizes["conv_L_cache"],
                initializer=init, name=mixer_name(i, kind),
            )
        else:
            y = b.multihead_attention(
                x, x, x, hidden, heads, kdim=hidden // heads,
                vdim=hidden // heads, causal=True,
                rope_theta=float(sizes["rope_parameters"]["rope_theta"]),
                qk_norm_eps=eps, qk_norm_per_head=True,
                num_kv_heads=sizes["num_key_value_heads"],
                initializer=init, name=mixer_name(i, kind),
            )
        h = b.add(h, y)
        x = b.rms_norm(h, eps=eps, name=f"norm{i}b")
        if dense:
            width = sizes["intermediate_size"]
            gate = b.dense(x, width, use_bias=False, kernel_initializer=init,
                           name=f"ffn{i}_w1")
            up = b.dense(x, width, use_bias=False, kernel_initializer=init,
                         name=f"ffn{i}_w3")
            y = b.dense(b.multiply(b.silu(gate), up), hidden, use_bias=False,
                        kernel_initializer=init, name=f"ffn{i}_w2")
        else:
            y = b.experts(
                x, sizes["num_experts_total"], sizes["num_experts_per_tok"],
                sizes["moe_intermediate_size"], activation=Activation.SILU,
                capacity_factor=None, use_bias=False, gated=True,
                renormalize=sizes["norm_topk_prob"], scoring="sigmoid",
                selection_bias=True,
                routed_scale=float(sizes["routed_scaling_factor"]),
                shared_hidden_size=0, held_experts=held_range(sizes),
                initializer=init, name=f"moe{i}",
            )[0]
        h = b.add(h, y)
    h = b.rms_norm(h, eps=eps, name="norm_f")
    logits = b.dense(h, sizes["vocab_rows_held"], use_bias=False,
                     kernel_initializer=init, name="head")
    return b, logits


# -- the plain reference ----------------------------------------------------


def mm(spec, a, b):
    return tower.mm(spec, a, b)


rms = tower.rms


def swiglu(m, w1, w3, w2):
    return mm(
        "sh,hd->sd",
        jax.nn.silu(mm("sd,dh->sh", m, w1)) * mm("sd,dh->sh", m, w3), w2,
    )


def short_conv(w, name, u, sizes):
    """The `conv` mixer on u [s, D]: the taps as shifted adds."""
    taps, width, s = sizes["conv_L_cache"], sizes["conv_width"], u.shape[0]
    row = mm("sd,df->sf", u, w[f"{name}.weight0"])
    b_, c_, z = (row[:, j * width:(j + 1) * width] for j in range(3))
    padded = jnp.concatenate([jnp.zeros((taps - 1, width)), b_ * z])
    w_c = w[f"{name}.weight1"]
    conv = sum(w_c[j] * padded[j:j + s] for j in range(taps))
    return mm("sf,fd->sd", c_ * conv, w[f"{name}.weight2"])


def rope(x, theta):
    """x [heads, s, d]: rotate-half pairing (i, i + d/2), positions 0..s-1."""
    _, s, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def causal_attention(q, k, v):
    """softmax(q k^T / sqrt(d) + causal) v on [h, s, d] operands,
    ATTENTION_BLOCK queries against every key at a time, so that an
    8,192-position sequence's scores need not exist at once."""
    _, s, d = q.shape
    block = min(s, ATTENTION_BLOCK)

    def query_block(start):
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = mm("hsd,htd->hst", qb, k) / np.sqrt(d)
        scores = jnp.where(
            rows[:, None] >= jnp.arange(s)[None, :], scores, -jnp.inf
        )
        return mm("hst,htd->hsd", jax.nn.softmax(scores, axis=-1), v)

    ctx = jax.lax.map(jax.checkpoint(query_block), jnp.arange(0, s, block))
    return jnp.swapaxes(ctx, 0, 1).reshape(q.shape[0], s, d)


def attention(w, name, u, sizes):
    """The `full_attention` mixer on u [s, D]: each key/value head repeated
    for the query heads that read it."""
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hidden = u.shape[-1]
    d, eps = hidden // heads, sizes["norm_eps"]
    theta = float(sizes["rope_parameters"]["rope_theta"])
    flat = w[f"{name}.weight0"].reshape(-1)
    cuts = np.cumsum([0, hidden * heads * d, hidden * kv * d,
                      hidden * kv * d, heads * d * hidden])
    wq = flat[cuts[0]:cuts[1]].reshape(hidden, heads, d)
    wk = flat[cuts[1]:cuts[2]].reshape(hidden, kv, d)
    wv = flat[cuts[2]:cuts[3]].reshape(hidden, kv, d)
    wo = flat[cuts[3]:cuts[4]].reshape(heads, d, hidden)
    q = rope(rms(mm("se,ehd->hsd", u, wq), w[f"{name}.weight1"], eps), theta)
    k = rope(rms(mm("se,ehd->hsd", u, wk), w[f"{name}.weight2"], eps), theta)
    v = mm("se,ehd->hsd", u, wv)
    k, v = (jnp.repeat(t, heads // kv, axis=0) for t in (k, v))
    return mm("hsd,hde->se", causal_attention(q, k, v), wo)


def router(w, name, m, sizes):
    """(0/1 mask of the chosen experts [s, E], their combine weights [s, E])."""
    r = mm("sd,de->se", m, w[f"{name}.weight0"])
    score = jax.nn.sigmoid(r)
    _, chosen = jax.lax.top_k(
        score + w[f"{name}.weight1"], sizes["num_experts_per_tok"]
    )
    mask = jnp.sum(jax.nn.one_hot(chosen, r.shape[-1], dtype=r.dtype), axis=1)
    weight = score * mask
    if sizes["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return mask, weight * sizes["routed_scaling_factor"]


def experts(w, name, m, sizes, held=None):
    """The held experts applied to every position, densely, and kept under
    the router's weights (zero where an expert was not chosen). ([s, D], the
    0/1 mask [s, E]). `held` (first, count) where it is not the file's: the
    test that adds the shares up."""
    first, count = held or held_range(sizes)
    mask, weight = router(w, name, m, sizes)

    def one(acc, expert):
        w1, w3, w2, we = expert
        return acc + we[:, None] * swiglu(m, w1, w3, w2), None

    out, _ = jax.lax.scan(
        lambda acc, e: jax.checkpoint(one)(acc, e),
        jnp.zeros_like(m),
        (w[f"{name}.weight2"], w[f"{name}.weight3"], w[f"{name}.weight4"],
         weight[:, first:first + count].T),
    )
    return out, mask


MIXERS = {"conv": short_conv, "full_attention": attention}


def final_hidden(w, sizes, ids):
    """One sequence ids [s]: (rms(x; g_f) [s, D], the expert layers' chosen
    masks [expert layers, s, E])."""
    eps = sizes["norm_eps"]
    h = w["embed.weight0"][ids]
    masks = []
    for i, kind, dense in layer_names(sizes):

        def layer(w, h, i=i, kind=kind, dense=dense):
            u = rms(h, w[f"norm{i}a.weight0"], eps)
            h = h + MIXERS[kind](w, mixer_name(i, kind), u, sizes)
            m = rms(h, w[f"norm{i}b.weight0"], eps)
            if dense:
                y = swiglu(m, *(w[f"ffn{i}_w{j}.weight0"] for j in (1, 3, 2)))
                return h + y, jnp.zeros(())
            y, mask = experts(w, f"moe{i}", m, sizes)
            return h + y, mask

        h, mask = jax.checkpoint(layer)(w, h)
        if not dense:
            masks.append(mask)
    return rms(h, w["norm_f.weight0"], eps), jnp.stack(masks)


def sequence_loss(w, sizes, ids, labels):
    """One sequence's summed next-token cross-entropy."""
    h, _ = final_hidden(w, sizes, ids)
    return tower.cross_entropy_sum(h, w["head.weight0"], labels)


def routing_report(w, sizes, ids):
    """Of one sequence's routing decisions, per expert layer: the share that
    landed on the held experts, and the fullest held expert over the mean
    held expert."""
    first, held = held_range(sizes)
    _, masks = final_hidden(w, sizes, ids)
    load = jnp.sum(masks[:, :, first:first + held], axis=1)  # [layers, held]
    decisions = masks.shape[1] * sizes["num_experts_per_tok"]
    return (
        jnp.sum(load, axis=-1) / decisions,
        jnp.max(load, axis=-1) / jnp.maximum(jnp.mean(load, axis=-1), 1e-30),
    )


def reference_losses(params, inputs, labels, sizes, adam):
    """(loss before, loss after one Adam step) on one batch, one sequence at
    a time, as the tower's file does it: a sequence is recomputed in the
    backward pass, so the gradient is ONE accumulator the size of the model
    beside the system's state. The batch is an argument of every program."""
    tower.OPERANDS = OPERANDS
    w = dict(params)
    rows = (jnp.asarray(inputs["input_ids"]), jnp.asarray(labels))
    positions = labels.size

    def mean_loss(w, rows):
        def one(total, row):
            share = jax.checkpoint(
                lambda w, row: sequence_loss(w, sizes, *row)
            )(w, row)
            return total + share / positions, None

        total, _ = jax.lax.scan(one, jnp.zeros(()), rows)
        return total

    loss_and_grad = jax.jit(jax.value_and_grad(mean_loss))
    mean_loss = jax.jit(mean_loss)

    with jax.default_matmul_precision("highest"):
        before, grad = loss_and_grad(w, rows)
        share, imbalance = jax.jit(
            lambda w, ids: routing_report(w, sizes, ids)
        )(w, rows[0][0])
        stepped = jax.jit(
            lambda g, w: tower.adam_first_step(g, w, adam), donate_argnums=0
        )(grad, w)
        del grad
        after = mean_loss(stepped, rows)
    print("lfm2 reference routing: " + json.dumps({
        "held_share_of_decisions_by_layer": [float(x) for x in share],
        "max_over_mean_held_expert_load_by_layer": [
            float(x) for x in imbalance
        ],
        "expected_share": sizes["num_experts"] / sizes["num_experts_total"],
    }), file=sys.stderr)
    return float(before), float(after)


# -- arithmetic for the per-layer metrics -----------------------------------


def counts(sizes):
    """(conv layers, attention layers, dense feed-forward layers, expert
    layers)."""
    layers = layer_names(sizes)
    conv = sum(kind == "conv" for _, kind, _ in layers)
    dense = sum(d for _, _, d in layers)
    return conv, len(layers) - conv, dense, len(layers) - dense


def attention_pairs(sizes, seq):
    """FLOPs of one causal [seq, seq] product a head, all TRUE query heads,
    one sequence: the causal half of the pairs, d wide."""
    heads = sizes["num_attention_heads"]
    d = sizes["hidden_size"] // heads
    return 2 * (seq * (seq + 1) / 2) * heads * d


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls and attention, nothing recomputed, of
    this chip's share. A token runs the experts it is routed to that are
    HERE: k * held / E of an expert on average. Causal attention needs half
    the pairs of positions. The convolution's taps and the gates are not
    matrix products and are not counted."""
    hidden = sizes["hidden_size"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = hidden // heads
    conv = 2 * hidden * 3 * sizes["conv_width"] + 2 * sizes["conv_width"] * hidden
    attn = (
        2 * hidden * d * (2 * heads + 2 * kv)
        + 2 * attention_pairs(sizes, seq) / seq
    )
    here = (
        sizes["num_experts_per_tok"] * sizes["num_experts"]
        / sizes["num_experts_total"]
    )
    moe = (
        2 * hidden * sizes["num_experts_total"]
        + 3 * 2 * hidden * sizes["moe_intermediate_size"] * here
    )
    dense = 3 * 2 * hidden * sizes["intermediate_size"]
    n_conv, n_attn, n_dense, n_moe = counts(sizes)
    layers = n_conv * conv + n_attn * attn + n_dense * dense + n_moe * moe
    return 3.0 * (layers + 2 * hidden * sizes["vocab_rows_held"])


def kernel_costs(sizes, batch, seq):
    """Least work of one training step on one chip, by kernel; the same
    whatever implements it.

    `shortconv`: every `conv` mixer node, whole. FLOPs: its two projections
    (2 D 3W and 2 W D a token) over three passes (forward, the inputs'
    gradients, the weights' gradients); the taps and gates are elementwise
    and ride the bytes. Bytes in bf16: the node's input u, W_in, w, W_out
    and its output, or their cotangents, once in each of the three passes:
    what a node that kept the chain in VMEM between its two matmuls would
    move. The projection's row is not among them. At the published sizes
    the FLOPs bind (6.6 TFLOP, 33.5 ms, against 2.0 GB).
    `flash`: the attention layer's causal core, forward (2 products) and
    backward (5), over the causal half of the pairs at the TRUE head count
    (32 query heads) and d = 64. Bytes in bf16: q and o at 32 heads, k and v
    at the 8 published key/value heads, once forward; those with do read and
    dq, dk, dv written backward."""
    tokens = batch * seq
    n_conv, n_attn, _, _ = counts(sizes)
    hidden, width = sizes["hidden_size"], sizes["conv_width"]
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = hidden // heads
    conv_weights = hidden * 3 * width + sizes["conv_L_cache"] * width + width * hidden
    q_tensor, kv_tensor = 2 * tokens * heads * d, 2 * tokens * kv * d
    return {
        "shortconv": {
            "flops": n_conv * tokens * 3 * (2 * hidden * 3 * width + 2 * width * hidden),
            "bytes": n_conv * 3 * 2 * (2 * tokens * hidden + conv_weights),
        },
        "flash": {
            "flops": n_attn * batch * 7 * attention_pairs(sizes, seq),
            "bytes": n_attn * (6 * q_tensor + 6 * kv_tensor),
        },
    }
