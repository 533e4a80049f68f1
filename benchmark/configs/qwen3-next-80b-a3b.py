"""Qwen3-Next-80B-A3B-Instruct (`model_type` `qwen3_next`) as this benchmark
runs it: ONE chip's share of one whole period (G G G A) of the 48 layers its
config.json states. `build` for the system under test, `reference_losses` as
the plain float32 `jax.numpy` reference, and the arithmetic the per-layer
metrics need. The cut, the deployment it stands for and every departure from
the published description are in the `.json` beside this file; the reference
makes the same ones. Nothing below `build` imports the program.

The tower, as the reference computes it (s positions of one sequence; D =
hidden_size, eps = rms_norm_eps, every projection without bias):

    zrms(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w)        # w from ZERO
    x = E[ids]
    per layer:  x = x + mixer_i(zrms(x; w_ia));  x = x + moe_i(zrms(x; w_ib))
    logits = zrms(x; w_f) W_head;  loss = mean next-token CE over the slice

Layer i (0-based) is `A` where (i + 1) % full_attention_interval == 0, else
`G`.

`G`, Gated DeltaNet (hk key heads of dk, hv value heads of dv, 4 taps), u the
normed row:
    q~ | k~ | v~ | z = u W_in            # hk*dk | hk*dk | hv*dv | hv*dv
    b | a = u W_ba                       # hv | hv
    q, k, v = silu(sum_{j<4} w_c[j] * (q~ | k~ | v~)_{t-3+j})   # causal, depthwise, no bias
    q = q * rsqrt(|q|^2 + 1e-6) * dk^-0.5,  k = k * rsqrt(|k|^2 + 1e-6)   # per KEY head
    value head h reads key head h // (hv / hk)
    g_t[h] = -exp(A_log[h]) * softplus(a_t[h] + dt_bias[h])    # ONE log-decay a head
    beta_t[h] = sigmoid(b_t[h])
    S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T   # [dk, dv], S_0 = 0
    o_t = S_t^T q_t                                             # STEP BY STEP here
    out = [ o * rsqrt(mean_dv o^2 + eps) * gain [dv] * silu(z) ] W_out   # gain from ONE
`A`, output-gated grouped-query attention (h query heads over kv key/value
heads, head size d, rotary on the first r = partial_rotary_factor * d columns):
    q | gate = u W_q per head            # W_q [D, h*2*d]: a head's query, then its gate
    k = u W_k,  v = u W_v                # [D, kv*d]
    q = zrms_d(q; w_q [d]),  k = zrms_d(k; w_k [d])            # each head's own d columns
    rotate-half on columns 0..r-1 of every q and k head: pairs (j, j + r/2),
    angle pos * theta^(-2j/r); columns r..d-1 pass
    ctx = causal softmax(q k^T * d^-0.5) v;  query head j reads key/value head j // (h / kv)
    out = (ctx * sigmoid(gate)) W_o
Experts (E routed of width I, top k, one shared of width Is), m the normed row:
    p = softmax(m W_g) over all E;  S = the k largest;  w_e = p_e / sum_S p
    out = sum_{e in S, e HELD} w_e (silu(m W1_e) * (m W3_e)) W2_e
          + sigmoid(m w_sg) * (silu(m Ws1) * (m Ws3)) Ws2

Parameter layouts the reference has to know (the program's public weight
formats; `<layer>.weight<j>`): embedding and norm `weight0`; dense `weight0`
[in, out]; gated-delta `weight0` W_in [D, 2*hk*dk + 2*hv*dv], `weight1` W_ba
[D, 2*hv] (b, then a), `weight2` w_c [4, 2*hk*dk + hv*dv], `weight3` dt_bias
[hv], `weight4` A_log [hv], `weight5` gain [dv], `weight6` W_out [hv*dv, D];
attention `weight0` one flat column: Wq [D, h*2*d] | Wk [D, kv*d] | Wv | Wo
[h*d, D], each row-major, `weight1` w_q [d], `weight2` w_k [d]; experts
`weight0` W_g [D, E], `weight1` W1 [held, D, I], `weight2` W3, `weight3` W2
[held, I, D], `weight4` Ws1 [D, Is], `weight5` Ws3, `weight6` Ws2 [Is, D],
`weight7` w_sg [D, 1].
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
    matrix product of the reference, through `OPERANDS`), the loss a block of
    positions at a time, Adam's first step and the data."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "nemotron-twotower-30b-a3b.py",
    )
    spec = importlib.util.spec_from_file_location("bench_qwen3next_tower", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


tower = _load_tower()

# |system - reference| allowed on a loss (natural log, mean over the 8,192
# positions of one sequence). The system multiplies in bf16 with float32
# accumulation; its router, the norms' statistics, softplus, the running sums
# of the log-decays, every decay, the triangular inverse, the states and the
# softmax are float32. Two readings set the bound, both taken by the harness's
# own comparison (my chip runs, PR 51; PERF.md section 6). Over READINGS_RUNS
# runs of `qwen3next80b_s8192_1chip`, each on its own seed, the system
# differed from this reference by at most BF16_SYSTEM_MAX[0] before the step
# and BF16_SYSTEM_MAX[1] after it. The nearest precision below must fail:
# `benchmark/precision_control.py --operands float8_e4m3fn` runs the cell
# through `run.py` with every matmul operand of this reference rounded to
# float8_e4m3 (`OPERANDS`), and `correct` came out false: the system is off
# that reference by FLOAT8_REFERENCE_MIN[0] before the step and
# FLOAT8_REFERENCE_MIN[1] after it. `run.py` holds (a) and (b) to this ONE
# number; as in the other held-expert files it is the Adam-amplified (b)
# that holds the precision (a sign step of 3e-4 on every weight moves this
# loss by 1.44, so the gradient signs a rounding flips show).
# 5e-3 is 1.96 times the largest bf16 (b) reading and a 250th of the float8 one.
# Check (a) does NOT hold the precision: the float8 control's (a) reading,
# 2.05e-3, is over three times the largest sound one (6.38e-4) and still
# under the limit, so it passes (a). The cell's precision control rests on
# (b) alone (1.248 against a sound maximum of 2.55e-3). One Adam step moves the
# loss by 1.44, so a backward pass that does nothing fails (b) 290 times over.
LOSS_TOLERANCE = 5e-3
# seventeen `--trace 0` runs and four `--trace 1` runs in four calls, the
# last two from the tree's `git archive` (the fourth after the review),
# twenty seeds; one float8 control on a twenty-first
READINGS_RUNS = 20
BF16_SYSTEM_MAX = (6.38e-4, 2.55e-3)
FLOAT8_REFERENCE_MIN = (2.05e-3, 1.248)

INPUT_NAMES = tower.INPUT_NAMES
make_data = tower.make_data
# queries the reference's attention takes at a time: 16 heads' scores against
# 8,192 keys are 0.5 MB a query in float32, and the system's 10 GB of state
# lies beside the reference on the chip
ATTENTION_BLOCK = 256
# positions of the recurrence between two kept states in the reference's
# gradient (a state is [hv, dk, dv] float32, 2 MB at the published sizes)
SCAN_BLOCK = 64
L2_EPS = 1e-6

# Every matrix product of the reference goes through the tower's `mm`, whose
# operands pass this `OPERANDS` first (`reference_losses` hands it over): the
# identity here, a rounding to float8_e4m3 and back under
# `precision_control.py`, the control behind LOSS_TOLERANCE.
OPERANDS = None


def layer_names(sizes):
    """[(index, mixer kind "G" | "A")] of the layers built, in order."""
    every = sizes["full_attention_interval"]
    return [
        (i, "A" if (i + 1) % every == 0 else "G")
        for i in range(sizes["num_hidden_layers"])
    ]


def held_range(sizes):
    """(first, count) of the routed experts this chip holds."""
    return sizes["held_experts_first"], sizes["num_experts"]


def mixer_name(i, kind):
    return f"{'gdn' if kind == 'G' else 'attn'}{i}"


def rotary_dim(sizes):
    return int(sizes["head_dim"] * sizes["partial_rotary_factor"])


def build(sizes, batch, seq):
    """(graph builder, logits tensor) through the public builder."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import TruncatedNormalInitializerAttrs

    assert sizes["hidden_act"] == "silu" and sizes["decoder_sparse_step"] == 1
    assert sizes["mlp_only_layers"] == [] and not sizes["use_sliding_window"]
    hidden = sizes["hidden_size"]
    eps = sizes["rms_norm_eps"]
    std = sizes["initializer_range"]
    init = TruncatedNormalInitializerAttrs(
        stddev=std, min_cutoff=-3 * std, max_cutoff=3 * std
    )
    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    h = b.embedding(ids, sizes["vocab_rows_held"], hidden, kernel_initializer=init,
                    name="embed")
    for i, kind in layer_names(sizes):
        x = b.rms_norm(h, eps=eps, zero_centered=True, name=f"norm{i}a")
        if kind == "G":
            y = b.gated_delta(
                x, sizes["linear_num_value_heads"],
                sizes["linear_key_head_dim"], sizes["linear_value_head_dim"],
                conv_kernel=sizes["linear_conv_kernel_dim"],
                chunk_size=sizes["gdn_chunk_size"], norm_eps=eps,
                num_key_heads=sizes["linear_num_key_heads"], decay="head",
                initializer=init, name=mixer_name(i, kind),
            )
        else:
            y = b.multihead_attention(
                x, x, x, hidden, sizes["num_attention_heads"],
                kdim=sizes["head_dim"], vdim=sizes["head_dim"], causal=True,
                rope_theta=float(sizes["rope_theta"]),
                rotary_dim=rotary_dim(sizes), qk_norm_eps=eps,
                qk_norm_per_head=True, qk_norm_zero_centered=True,
                num_kv_heads=sizes["num_key_value_heads"], output_gate=True,
                initializer=init, name=mixer_name(i, kind),
            )
        h = b.add(h, y)
        x = b.rms_norm(h, eps=eps, zero_centered=True, name=f"norm{i}b")
        y = b.experts(
            x, sizes["num_experts_total"], sizes["num_experts_per_tok"],
            sizes["moe_intermediate_size"], activation=Activation.SILU,
            capacity_factor=None, use_bias=False, gated=True,
            renormalize=sizes["norm_topk_prob"], scoring="softmax",
            shared_hidden_size=sizes["shared_expert_intermediate_size"],
            shared_gate=True, held_experts=held_range(sizes),
            initializer=init, name=f"moe{i}",
        )[0]
        h = b.add(h, y)
    h = b.rms_norm(h, eps=eps, zero_centered=True, name="norm_f")
    logits = b.dense(h, sizes["vocab_rows_held"], use_bias=False,
                     kernel_initializer=init, name="head")
    return b, logits


# -- the plain reference ----------------------------------------------------


def mm(spec, a, b):
    return tower.mm(spec, a, b)


def zrms(u, w, eps):
    """The zero-centred RMS norm over the last dim: the gain is 1 + w."""
    return (
        u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps)
        * (1.0 + w)
    )


def swiglu(m, w1, w3, w2):
    return mm(
        "sh,hd->sd",
        jax.nn.silu(mm("sd,dh->sh", m, w1)) * mm("sd,dh->sh", m, w3), w2,
    )


def delta_recurrence(q, k, v, g, beta):
    """The gated delta rule STEP BY STEP over the positions, one log-decay a
    head: q, k [s, h, dk], v [s, h, dv], g and beta [s, h] -> o [s, h, dv].
    One `lax.scan` step a position, no chunk and no WY form; for the
    gradient the positions go in blocks of SCAN_BLOCK whose inner scan is
    recomputed (`jax.checkpoint`), so that a state is kept per block and not
    per position. The state's contractions are float32 sums on the vector
    unit, not matrix products."""
    s, heads, d = q.shape
    block = next(n for n in range(min(SCAN_BLOCK, s), 0, -1) if s % n == 0)

    def step(state, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        decayed = jnp.exp(g_t)[:, None, None] * state
        seen = jnp.sum(decayed * k_t[:, :, None], axis=1)
        state = decayed + (
            b_t[:, None, None] * k_t[:, :, None] * (v_t - seen)[:, None, :]
        )
        return state, jnp.sum(state * q_t[:, :, None], axis=1)

    def run_block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    blocked = tuple(
        t.reshape(s // block, block, *t.shape[1:]) for t in (q, k, v, g, beta)
    )
    _, o = jax.lax.scan(
        lambda state, inputs: jax.checkpoint(run_block)(state, inputs),
        jnp.zeros((heads, d, v.shape[-1]), q.dtype), blocked,
    )
    return o.reshape(s, heads, v.shape[-1])


def gated_delta_net(w, name, u, sizes):
    """The `G` mixer on u [s, D]."""
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps, s = sizes["linear_conv_kernel_dim"], u.shape[0]
    kw, vw = hk * dk, hv * dv
    proj = mm("sd,df->sf", u, w[f"{name}.weight0"])
    ba = mm("sd,df->sf", u, w[f"{name}.weight1"])
    qkv, z = proj[:, :2 * kw + vw], proj[:, 2 * kw + vw:]
    b_logit, a_pre = ba[:, :hv], ba[:, hv:]
    # the causal depthwise convolution as `taps` shifted adds, no bias
    w_c = w[f"{name}.weight2"]
    padded = jnp.concatenate([jnp.zeros((taps - 1, 2 * kw + vw)), qkv])
    qkv = jax.nn.silu(sum(w_c[j] * padded[j:j + s] for j in range(taps)))
    q = qkv[:, :kw].reshape(s, hk, dk)
    k = qkv[:, kw:2 * kw].reshape(s, hk, dk)
    v = qkv[:, 2 * kw:].reshape(s, hv, dv)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)

    # value head h reads key head h // (hv / hk)
    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    g = -jnp.exp(w[f"{name}.weight4"]) * jax.nn.softplus(
        a_pre + w[f"{name}.weight3"]
    )
    o = delta_recurrence(q, k, v, g, jax.nn.sigmoid(b_logit))
    o = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + sizes["rms_norm_eps"]
    ) * w[f"{name}.weight5"]
    return mm(
        "sf,fd->sd", o.reshape(s, vw) * jax.nn.silu(z), w[f"{name}.weight6"]
    )


def rope(x, theta, width):
    """x [heads, s, d]: rotate-half on the first `width` columns, pairs
    (j, j + width/2), positions 0..s-1; the other columns pass."""
    _, s, _ = x.shape
    half = width // 2
    inv_freq = theta ** (-jnp.arange(0, width, 2, dtype=jnp.float32) / width)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :half], x[..., half:width]
    return jnp.concatenate(
        [lo * cos - hi * sin, hi * cos + lo * sin, x[..., width:]], axis=-1
    )


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
    """The `A` mixer on u [s, D]: each key/value head repeated for the query
    heads that read it, the context gated head by head."""
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["head_dim"], sizes["rms_norm_eps"]
    hidden = u.shape[-1]
    theta, width = float(sizes["rope_theta"]), rotary_dim(sizes)
    flat = w[f"{name}.weight0"].reshape(-1)
    cuts = np.cumsum([0, hidden * heads * 2 * d, hidden * kv * d,
                      hidden * kv * d, heads * d * hidden])
    wq = flat[cuts[0]:cuts[1]].reshape(hidden, heads, 2 * d)
    wk = flat[cuts[1]:cuts[2]].reshape(hidden, kv, d)
    wv = flat[cuts[2]:cuts[3]].reshape(hidden, kv, d)
    wo = flat[cuts[3]:cuts[4]].reshape(heads, d, hidden)
    both = mm("se,ehd->hsd", u, wq)
    q, gate = both[..., :d], both[..., d:]
    q = rope(zrms(q, w[f"{name}.weight1"], eps), theta, width)
    k = rope(
        zrms(mm("se,ehd->hsd", u, wk), w[f"{name}.weight2"], eps), theta, width
    )
    v = mm("se,ehd->hsd", u, wv)
    k, v = (jnp.repeat(t, heads // kv, axis=0) for t in (k, v))
    ctx = causal_attention(q, k, v) * jax.nn.sigmoid(gate)
    return mm("hsd,hde->se", ctx, wo)


def router(w, name, m, sizes):
    """(0/1 mask of the chosen experts [s, E], their combine weights [s, E])."""
    p = jax.nn.softmax(mm("sd,de->se", m, w[f"{name}.weight0"]), axis=-1)
    _, chosen = jax.lax.top_k(p, sizes["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=p.dtype), axis=1)
    weight = p * mask
    if sizes["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return mask, weight


def experts(w, name, m, sizes, held=None, shared=True):
    """The held experts applied to every position, densely, and kept under
    the router's weights (zero where an expert was not chosen), plus the
    gated shared expert. ([s, D], the 0/1 mask [s, E]). `held` (first,
    count) where it is not the file's, and `shared` false for the routed
    part alone: the test that adds the shares up."""
    first, count = held or held_range(sizes)
    mask, weight = router(w, name, m, sizes)

    def one(acc, expert):
        w1, w3, w2, we = expert
        return acc + we[:, None] * swiglu(m, w1, w3, w2), None

    out, _ = jax.lax.scan(
        lambda acc, e: jax.checkpoint(one)(acc, e),
        jnp.zeros_like(m),
        (w[f"{name}.weight1"], w[f"{name}.weight2"], w[f"{name}.weight3"],
         weight[:, first:first + count].T),
    )
    if shared:
        out = out + jax.nn.sigmoid(
            mm("sd,do->so", m, w[f"{name}.weight7"])
        ) * swiglu(
            m, w[f"{name}.weight4"], w[f"{name}.weight5"], w[f"{name}.weight6"]
        )
    return out, mask


MIXERS = {"G": gated_delta_net, "A": attention}


def final_hidden(w, sizes, ids):
    """One sequence ids [s]: (zrms(x; w_f) [s, D], the expert layers' chosen
    masks [layers, s, E])."""
    eps = sizes["rms_norm_eps"]
    h = w["embed.weight0"][ids]
    masks = []
    for i, kind in layer_names(sizes):

        def layer(w, h, i=i, kind=kind):
            u = zrms(h, w[f"norm{i}a.weight0"], eps)
            h = h + MIXERS[kind](w, mixer_name(i, kind), u, sizes)
            y, mask = experts(
                w, f"moe{i}", zrms(h, w[f"norm{i}b.weight0"], eps), sizes
            )
            return h + y, mask

        h, mask = jax.checkpoint(layer)(w, h)
        masks.append(mask)
    return zrms(h, w["norm_f.weight0"], eps), jnp.stack(masks)


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
    print("qwen3-next reference routing: " + json.dumps({
        "held_share_of_decisions_by_layer": [float(x) for x in share],
        "max_over_mean_held_expert_load_by_layer": [
            float(x) for x in imbalance
        ],
        "expected_share": sizes["num_experts"] / sizes["num_experts_total"],
    }), file=sys.stderr)
    return float(before), float(after)


# -- arithmetic for the per-layer metrics -----------------------------------


def counts(sizes):
    """(Gated DeltaNet layers, attention layers, dense feed-forward layers,
    expert layers)."""
    layers = layer_names(sizes)
    gdn = sum(kind == "G" for _, kind in layers)
    return gdn, len(layers) - gdn, 0, len(layers)


def gdn_scan_flops_per_token(sizes):
    """Least forward FLOPs of the chunked delta rule with ONE decay a head,
    for one position of one `G` layer: the two score matrices K K^T and
    Q K^T once a KEY head over the causal half of the chunk ((Q + 1) / 2 of
    its Q positions, dk wide), their masks elementwise and not counted; then
    a VALUE head: the scores' product with the corrected values (dv wide),
    the unit-triangular system solved ONCE, by substitution, for the dv value
    and dk key columns of its right-hand side (the same half), and the
    state's three [dk, dv] products (what the state predicts, what the query
    reads of it, the state's update). A form that broadcasts the decay over
    the key channels and builds the scores level by level computes more, and
    pays for it in the share."""
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    half = (sizes["gdn_chunk_size"] + 1) / 2
    return (
        hk * 2 * (2 * half * dk)
        + hv * (2 * half * dv + 2 * half * (dk + dv) + 3 * 2 * dk * dv)
    )


def gdn_row_bytes(sizes):
    """Bytes of one position's operands of the recurrence: q and k at the KEY
    heads, v and o at the value heads in bf16, the log-decay and beta one a
    value head in float32."""
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    return 2 * (2 * hk * dk + 2 * hv * dv) + 2 * 4 * hv


def attention_pairs(sizes, seq):
    """FLOPs of one causal [seq, seq] product a head, all TRUE query heads,
    one sequence: the causal half of the pairs, d wide."""
    return (
        2 * (seq * (seq + 1) / 2) * sizes["num_attention_heads"]
        * sizes["head_dim"]
    )


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls, attention and the recurrence's least,
    nothing recomputed, of this chip's share. A token runs the experts it is
    routed to that are HERE: k * held / E of an expert on average, and the
    shared one with its gate. Causal attention needs half the pairs."""
    hidden = sizes["hidden_size"]
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    kw = hk * sizes["linear_key_head_dim"]
    vw = hv * sizes["linear_value_head_dim"]
    gdn = (
        2 * hidden * (2 * kw + 2 * vw + 2 * hv + vw)
        + gdn_scan_flops_per_token(sizes)
    )
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    attn = (
        2 * hidden * d * (3 * heads + 2 * kv)
        + 2 * attention_pairs(sizes, seq) / seq
    )
    here = (
        sizes["num_experts_per_tok"] * sizes["num_experts"]
        / sizes["num_experts_total"]
    )
    moe = (
        2 * hidden * (sizes["num_experts_total"] + 1)
        + 3 * 2 * hidden * (
            sizes["moe_intermediate_size"] * here
            + sizes["shared_expert_intermediate_size"]
        )
    )
    n_gdn, n_attn, _, n_moe = counts(sizes)
    layers = n_gdn * gdn + n_attn * attn + n_moe * moe
    return 3.0 * (layers + 2 * hidden * sizes["vocab_rows_held"])


def kernel_costs(sizes, batch, seq):
    """Least work of one training step on one chip, by kernel; the same
    whatever implements it.

    `gdn_scan`: the recurrence of every `G` layer, forward and backward, in
    the SCALAR-decay form. FLOPs: `gdn_scan_flops_per_token` forward and
    twice that for the backward by its own count (each product's transpose
    is two products of its size), recomputation not counted. Bytes:
    `gdn_row_bytes` (q, k at 16 heads, v, o at 32, the log-decay, beta) once
    in each of three passes: the forward reads five and writes o; the
    backward reads them again with do and writes their five gradients. At
    the published sizes the bytes bind.
    `flash`: the attention layer's causal core, forward (2 products) and
    backward (5), over the causal half of the pairs at the TRUE 16 query
    heads and d = 256. Bytes in bf16: q and o at 16 heads, k and v at the 2
    published key/value heads, once forward; those with do read and dq, dk,
    dv written backward."""
    tokens = batch * seq
    n_gdn, n_attn, _, _ = counts(sizes)
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["head_dim"]
    q_tensor, kv_tensor = 2 * tokens * heads * d, 2 * tokens * kv * d
    return {
        "gdn_scan": {
            "flops": n_gdn * tokens * 3 * gdn_scan_flops_per_token(sizes),
            "bytes": n_gdn * tokens * 3 * gdn_row_bytes(sizes),
        },
        "flash": {
            "flops": n_attn * batch * 7 * attention_pairs(sizes, seq),
            "bytes": n_attn * (6 * q_tensor + 6 * kv_tensor),
        },
    }
