"""JoyAI-LLM-Flash (`model_type` `joyai_llm_flash`, whose config keys are the
`deepseek_v3` family's) as this benchmark runs it: ONE chip's share of the
first five of its 40 layers AND its multi-token-prediction module. `build`
for the system under test, `reference_losses` as the plain float32
`jax.numpy` reference, and the arithmetic the per-layer metrics need. The
cut, the deployment it stands for, every departure and every assumed value
are in the `.json` beside this file; the reference makes the same ones.
Nothing below `build` imports the program.

The step, as the reference computes it (s positions of one sequence, token
ids t_1..t_s, labels t_2..t_{s+1}; D = hidden_size; every norm is
x rsqrt(mean x^2 + eps) g; no bias anywhere):

    x = E[ids]
    block i:   h = x + MLA_i(rms(x; g_ia));  x = h + FFN_i(rms(h; g_ib))
    y = x after the last block
    L_main = mean over s positions of CE(rms(y; g_f) W_head, labels)
    the multi-token-prediction module (depth 1; DeepSeek-V3 technical report,
    section 2.2), its ids the labels and its targets the labels moved on by
    one (the last position has none and weighs nothing):
        h' = [rms(E[labels]; g_e) | rms(y; g_h)] W_eh        # [2D, D]
        z  = one whole block of the expert kind on h', its own weights
        L_mtp = mean over s - 1 positions of CE(rms(z; g_s) W_head, targets)
    L = L_main + lambda L_mtp        # E and W_head are the SAME matrices

MLA (h heads; nope = qk_nope_head_dim, rope = qk_rope_head_dim, v =
v_head_dim), u the normed row:
    c_q = rms(u W_qa; g_q)   ([D, q_lora_rank]);   q = c_q W_qb  ([., h*(nope+rope)])
    [c | k_r] = u W_kva      ([D, kv_lora_rank + rope])
    [k_n | v] = rms(c; g_kv) W_kvb   ([., h*(nope+v)], a head's k_n then its v)
    rot: columns (2j, 2j + 1) of a `rope`-wide row at position p turned by the
    angle p * theta^(-2j / rope)  (`rope_interleave`; `rope_scaling` null)
    head j: q^j = [q_n^j | rot(q_r^j)], key [k_n^j | rot(k_r)] (ONE k_r row for
    all heads), causal softmax(q k^T / sqrt(nope + rope)) v, then W_o.
FFN: layer 0 (`first_k_dense_replace` 1) dense SwiGLU of width
    intermediate_size; the others, and the module's, experts: r = m W_r
    (float32), s = sigmoid(r), S = the k largest of s + b (b the selection
    bias, a buffer at zero), w_e = s_e / (sum_S s + 1e-20) * scale; out =
    sum_{e in S, e HELD} w_e SwiGLU_e(m) + SwiGLU_shared(m). The router is
    num_experts_total wide and picks num_experts_per_tok; the held experts
    first .. first + n_routed_experts - 1 are here, and what the others would
    add is left out, in the program and here alike.

Parameter layouts the reference has to know (the program's public weight
formats; `<layer>.weight<j>`): embedding and rms norm `weight0`; dense
`weight0` [in, out]; latent attention `weight0` one flat column W_qa | W_qb |
W_kva | W_kvb | W_o, each row-major, `weight1` g_kv [kv rank], `weight2` g_q
[q rank]; experts `weight0` W_r [D, E], `weight1` b [E], `weight2` W1
[held, D, I], `weight3` W3, `weight4` W2 [held, I, D], `weight5` Ws1 [D, Is],
`weight6` Ws3, `weight7` Ws2 [Is, D]. The module's embedding and head have no
weights of their own: they read `embed.weight0` and `head.weight0`.
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
    matrix product of the reference, through `OPERANDS`), `rms` and Adam's
    first step."""
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "nemotron-twotower-30b-a3b.py",
    )
    spec = importlib.util.spec_from_file_location("bench_joyai_tower", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


tower = _load_tower()

# |system - reference| allowed on the loss L_main + lambda L_mtp (natural
# log; the means over the 8,192 and 8,191 positions of one sequence). The
# system multiplies in bf16 with float32 accumulation; its router, the norms'
# statistics, the rotary's angles and products, the softmax and both losses
# are float32. Two readings set the bound, both taken by the harness's own
# comparison (my chip runs, PR 53; PERF.md section 6). Over READINGS_RUNS
# runs of `joyaiflash48b_s8192_1chip`, each on its own seed, the system
# differed from this reference by at most BF16_SYSTEM_MAX[0] before the step
# and BF16_SYSTEM_MAX[1] after it. The nearest precision below must fail:
# `benchmark/precision_control.py --operands float8_e4m3fn` runs the cell
# through `run.py` with every matmul operand of this reference rounded to
# float8_e4m3 (`OPERANDS`), and `correct` came out false: the system is off
# that reference by FLOAT8_REFERENCE_MIN[0] before the step (inside the
# limit: (a) holds no precision here) and FLOAT8_REFERENCE_MIN[1] after it,
# almost seventy times the limit. `run.py` holds (a) and (b) to this ONE number; as
# in the other held configurations it is the Adam-amplified (b) that holds
# the precision (a sign step of 3e-4 on every weight moves this loss by
# 0.41, so the gradient signs a rounding flips show), and a
# backward pass that does nothing, or that leaves the module's loss out of
# the gradient, fails (b) by that whole move.
LOSS_TOLERANCE = 5e-3
# 41 runs on 41 seeds, twenty-one of them above 2**31: 13 of the tree this
# file went in with, from the committed files alone; 13 of the same program
# earlier in PR 53, 4 of them from the committed files; 13 with a wider first
# window of the held share (taken out in review; no arithmetic differs) and
# 2 with another form of the rotary pass; three float8 controls (the smaller
# reading of each check is written down)
READINGS_RUNS = 41
BF16_SYSTEM_MAX = (4.06e-4, 5.46e-4)
FLOAT8_REFERENCE_MIN = (2.27e-3, 0.339)

INPUT_NAMES = ("input_ids", "mtp_input_ids", "mtp_labels")
# positions the reference takes at a time where a whole sequence's tensor
# would not fit beside the system's state (attention scores, logits)
BLOCK = tower.BLOCK

# Every matrix product of the reference goes through the tower's `mm`, whose
# operands pass this `OPERANDS` first (`reference_losses` hands it over): the
# identity here, a rounding to float8_e4m3 and back under
# `precision_control.py`, the control behind LOSS_TOLERANCE.
OPERANDS = None


def blocks(sizes):
    """[(name suffix, dense feed-forward?)] of the blocks built, in order:
    the published layers 0 .. num_hidden_layers - 1 and then the
    multi-token-prediction module's (`mtp`), which is of the expert kind."""
    out = [
        (str(i), i < sizes["first_k_dense_replace"])
        for i in range(sizes["num_hidden_layers"])
    ]
    assert sizes["num_nextn_predict_layers"] in (0, 1)
    if sizes["num_nextn_predict_layers"]:
        out.append(("mtp", False))
    return out


def held_range(sizes):
    """(first, count) of the routed experts this chip holds."""
    return sizes["held_experts_first"], sizes["n_routed_experts"]


def make_data(rs, sizes, n, seq):
    """`n` seeded sequences of `seq` + 1 tokens over the vocabulary slice:
    inputs are the first `seq`, labels the next token at each position. The
    module's ids are the labels, and its targets the labels moved on by one
    with -1 (no target) at the last position."""
    tokens = rs.randint(
        0, sizes["vocab_rows_held"], (n, seq + 1)
    ).astype(np.int32)
    labels = tokens[:, 1:].copy()
    targets = np.concatenate(
        [labels[:, 1:], np.full((n, 1), -1, np.int32)], axis=1
    )
    return {
        "input_ids": tokens[:, :-1].copy(),
        "mtp_input_ids": labels.copy(),
        "mtp_labels": targets,
    }, labels


def build(sizes, batch, seq):
    """(graph builder, logits tensor) through the public builder; the
    module's loss is a node of the graph and joins the training loss."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import TruncatedNormalInitializerAttrs

    assert sizes["hidden_act"] == "silu" and sizes["scoring_func"] == "sigmoid"
    assert sizes["n_group"] == 1 and sizes["topk_group"] == 1
    assert sizes["rope_scaling"] is None and not sizes["attention_bias"]
    assert not sizes["tie_word_embeddings"]
    hidden, vocab = sizes["hidden_size"], sizes["vocab_rows_held"]
    eps = sizes["rms_norm_eps"]
    std = sizes["initializer_range"]
    init = TruncatedNormalInitializerAttrs(
        stddev=std, min_cutoff=-3 * std, max_cutoff=3 * std
    )
    b = ComputationGraphBuilder()

    def block(h, tag, dense):
        name = "mtp_" if tag == "mtp" else ""
        i = "" if tag == "mtp" else tag
        x = b.rms_norm(h, eps=eps, name=f"{name}norm{i}a")
        y = b.multihead_attention(
            x, x, x, hidden, sizes["num_attention_heads"],
            kdim=sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
            vdim=sizes["v_head_dim"], causal=True,
            rope_theta=float(sizes["rope_theta"]),
            rope_interleaved=sizes["rope_interleave"],
            kv_latent_rank=sizes["kv_lora_rank"],
            shared_key_dim=sizes["qk_rope_head_dim"],
            kv_latent_norm_eps=eps, q_latent_rank=sizes["q_lora_rank"],
            q_latent_norm_eps=eps, initializer=init, name=f"{name}mla{i}",
        )
        # the module's residual adds carry its name, the trunk's none
        h = b.add(h, y, name="mtp_add_a" if name else None)
        x = b.rms_norm(h, eps=eps, name=f"{name}norm{i}b")
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
                routed_scale=sizes["routed_scaling_factor"],
                shared_hidden_size=sizes["n_shared_experts"]
                * sizes["moe_intermediate_size"],
                held_experts=held_range(sizes),
                initializer=init, name=f"{name}moe{i}",
            )[0]
        return b.add(h, y, name="mtp_add_b" if name else None)

    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    h = b.embedding(ids, vocab, hidden, kernel_initializer=init, name="embed")
    embed_weight = b.weight_log[-1]
    trunk = [(tag, dense) for tag, dense in blocks(sizes) if tag != "mtp"]
    for tag, dense in trunk:
        h = block(h, tag, dense)
    logits = b.dense(
        b.rms_norm(h, eps=eps, name="norm_f"), vocab, use_bias=False,
        kernel_initializer=init, name="head",
    )
    head_weight = b.weight_log[-1]
    if sizes["num_nextn_predict_layers"]:
        next_ids = b.create_input(
            [batch, seq], DataType.INT32, name="mtp_input_ids"
        )
        targets = b.create_input([batch, seq], DataType.INT32, name="mtp_labels")
        with b.reuse_weights([embed_weight]):
            e = b.embedding(next_ids, vocab, hidden, name="mtp_embed")
        both = b.concat(
            [b.rms_norm(e, eps=eps, name="mtp_norm_e"),
             b.rms_norm(h, eps=eps, name="mtp_norm_h")], axis=2,
            name="mtp_concat",
        )
        z = b.dense(both, hidden, use_bias=False, kernel_initializer=init,
                    name="mtp_proj")
        z = b.rms_norm(block(z, "mtp", False), eps=eps, name="mtp_norm_f")
        with b.reuse_weights([head_weight]):
            second = b.dense(z, vocab, use_bias=False, name="mtp_head")
        b.label_cross_entropy(
            second, targets, weight=sizes["mtp_loss_weight"], name="mtp_loss"
        )
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


def rotary_pairs(x, theta):
    """x [..., s, width] with its positions 0..s-1 along the axis before the
    last: columns (2j, 2j + 1) turned by the angle p * theta^(-2j / width),
    pair by pair."""
    s, width = x.shape[-2:]
    j = jnp.arange(width // 2, dtype=jnp.float32)
    angle = (
        jnp.arange(s, dtype=jnp.float32)[:, None]
        * theta ** (-2.0 * j / width)[None, :]
    )
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(x.shape)


def causal_attention(q, k, v):
    """softmax(q k^T / sqrt(width) + causal) v on [h, s, .] operands, BLOCK
    queries against every key at a time, so that an 8,192-position
    sequence's scores need not exist at once beside the system's state."""
    _, s, width = q.shape
    block = min(s, BLOCK)
    assert s % block == 0, (s, block)

    def query_block(start):
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = mm("hsd,htd->hst", qb, k) / np.sqrt(width)
        scores = jnp.where(
            rows[:, None] >= jnp.arange(s)[None, :], scores, -jnp.inf
        )
        return mm("hst,htd->hsd", jax.nn.softmax(scores, axis=-1), v)

    ctx = jax.lax.map(jax.checkpoint(query_block), jnp.arange(0, s, block))
    return jnp.swapaxes(ctx, 0, 1).reshape(q.shape[0], s, v.shape[-1])


def mla(w, name, u, sizes):
    """The latent-attention mixer on u [s, D]."""
    heads, rank, qrank = (
        sizes["num_attention_heads"], sizes["kv_lora_rank"], sizes["q_lora_rank"]
    )
    nope, rope, vd = (
        sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    )
    hidden, s, eps = u.shape[-1], u.shape[0], sizes["rms_norm_eps"]
    theta = float(sizes["rope_theta"])
    assert sizes["rope_interleave"] and sizes["rope_scaling"] is None
    flat = w[f"{name}.weight0"].reshape(-1)
    cuts = np.cumsum([
        0, hidden * qrank, qrank * heads * (nope + rope),
        hidden * (rank + rope), rank * heads * (nope + vd),
        heads * vd * hidden,
    ])
    wq_a = flat[cuts[0]:cuts[1]].reshape(hidden, qrank)
    wq_b = flat[cuts[1]:cuts[2]].reshape(qrank, heads, nope + rope)
    wkv_a = flat[cuts[2]:cuts[3]].reshape(hidden, rank + rope)
    wkv_b = flat[cuts[3]:cuts[4]].reshape(rank, heads, nope + vd)
    wo = flat[cuts[4]:cuts[5]].reshape(heads, vd, hidden)
    c_q = rms(mm("se,er->sr", u, wq_a), w[f"{name}.weight2"], eps)
    q = mm("sr,rhd->hsd", c_q, wq_b)
    q = jnp.concatenate(
        [q[..., :nope], rotary_pairs(q[..., nope:], theta)], axis=-1
    )
    low = mm("se,ef->sf", u, wkv_a)
    kv = mm("sr,rhd->hsd", rms(low[:, :rank], w[f"{name}.weight1"], eps), wkv_b)
    k_r = rotary_pairs(low[:, rank:], theta)  # once a position, for all heads
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[None], (heads, s, rope))], axis=-1
    )
    return mm("hsd,hde->se", causal_attention(q, k, kv[..., nope:]), wo)


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
    the router's weights (zero where an expert was not chosen), plus the
    shared expert. ([s, D], the 0/1 mask [s, E]). `held` (first, count)
    where it is not the file's: the test that adds the shares up."""
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
    shared = swiglu(
        m, w[f"{name}.weight5"], w[f"{name}.weight6"], w[f"{name}.weight7"]
    )
    return out + shared, mask


def run_block(w, sizes, h, tag, dense):
    """One block on h [s, D]: (its output, the expert node's chosen mask or a
    zero)."""
    eps = sizes["rms_norm_eps"]
    pre, i = ("mtp_", "") if tag == "mtp" else ("", tag)

    def layer(w, h):
        h = h + mla(w, f"{pre}mla{i}", rms(h, w[f"{pre}norm{i}a.weight0"], eps), sizes)
        m = rms(h, w[f"{pre}norm{i}b.weight0"], eps)
        if dense:
            y = swiglu(m, *(w[f"ffn{i}_w{j}.weight0"] for j in (1, 3, 2)))
            return h + y, jnp.zeros(())
        y, mask = experts(w, f"{pre}moe{i}", m, sizes)
        return h + y, mask

    return jax.checkpoint(layer)(w, h)


def cross_entropy_sum(h, head, labels):
    """Summed cross-entropy of h [s, D] through `head` over the positions
    whose label is not negative, BLOCK positions' logits at a time."""
    block = min(h.shape[0], BLOCK)

    def one(args):
        hb, yb = args
        logp = jax.nn.log_softmax(mm("sd,dv->sv", hb, head), axis=-1)
        picked = jnp.take_along_axis(logp, jnp.maximum(yb, 0)[:, None], axis=-1)
        return -jnp.sum(jnp.where(yb >= 0, picked[:, 0], 0.0))

    return jnp.sum(jax.lax.map(
        jax.checkpoint(one),
        (h.reshape(-1, block, h.shape[-1]), labels.reshape(-1, block)),
    ))


def loss_sums(w, sizes, ids, labels):
    """One sequence: (summed main cross-entropy over its s positions, summed
    module cross-entropy over its s - 1 positions with a target, the expert
    nodes' chosen masks [nodes, s, E]). The module's ids and targets are
    derived HERE from `labels`: ids the labels, targets the labels moved on
    by one, the last position without one."""
    eps = sizes["rms_norm_eps"]
    h = w["embed.weight0"][ids]
    masks = []
    for tag, dense in blocks(sizes):
        if tag == "mtp":
            continue
        h, mask = run_block(w, sizes, h, tag, dense)
        if not dense:
            masks.append(mask)
    main = cross_entropy_sum(
        rms(h, w["norm_f.weight0"], eps), w["head.weight0"], labels
    )
    if not sizes["num_nextn_predict_layers"]:
        return main, jnp.zeros(()), jnp.stack(masks)
    targets = jnp.concatenate([labels[1:], jnp.full((1,), -1, labels.dtype)])
    both = jnp.concatenate([
        rms(w["embed.weight0"][labels], w["mtp_norm_e.weight0"], eps),
        rms(h, w["mtp_norm_h.weight0"], eps),
    ], axis=-1)
    z, mask = run_block(
        w, sizes, mm("sd,de->se", both, w["mtp_proj.weight0"]), "mtp", False
    )
    masks.append(mask)
    second = cross_entropy_sum(
        rms(z, w["mtp_norm_f.weight0"], eps), w["head.weight0"], targets
    )
    return main, second, jnp.stack(masks)


def reference_losses(params, inputs, labels, sizes, adam):
    """(L before, L after one Adam step) on one batch, L = L_main + lambda
    L_mtp, one sequence at a time: a sequence is recomputed in the backward
    pass, so the gradient is ONE accumulator the size of the model beside
    the system's state. The batch is an argument of every program. Of
    `inputs` only `input_ids` is read: the module's ids and targets come
    from `labels` (a wrong shift in the data then shows as a disagreement).
    Both terms and the first sequence's routing come out of the pass that
    takes the gradient, and go to standard error."""
    tower.OPERANDS = OPERANDS
    w = dict(params)
    rows = (jnp.asarray(inputs["input_ids"]), jnp.asarray(labels))
    n, s = labels.shape
    weight = sizes["mtp_loss_weight"] if sizes["num_nextn_predict_layers"] else 0.0
    first, held = held_range(sizes)

    def mean_loss(w, rows):
        """(L, (L_main, L_mtp, each sequence's decisions a held expert and
        node [n, nodes, held]))."""
        def one(total, row):
            main, second, masks = jax.checkpoint(
                lambda w, row: loss_sums(w, sizes, *row)
            )(w, row)
            return (
                total[0] + main / (n * s),
                total[1] + second / (n * max(s - 1, 1)),
            ), jnp.sum(masks[:, :, first:first + held], axis=1)

        (main, second), load = jax.lax.scan(
            one, (jnp.zeros(()), jnp.zeros(())), rows
        )
        return main + weight * second, (main, second, load)

    with jax.default_matmul_precision("highest"):
        (before, (main, second, load)), grad = jax.jit(
            jax.value_and_grad(mean_loss, has_aux=True)
        )(w, rows)
        stepped = jax.jit(
            lambda g, w: tower.adam_first_step(g, w, adam), donate_argnums=0
        )(grad, w)
        del grad
        after = jax.jit(lambda w, rows: mean_loss(w, rows)[0])(stepped, rows)
    load = np.asarray(load[0], dtype=np.float64)  # the first sequence's
    print("joyai-llm-flash reference: " + json.dumps({
        "L_main": float(main), "L_mtp": float(second), "lambda": weight,
        "ln_vocab_rows_held": float(np.log(sizes["vocab_rows_held"])),
        "held_share_of_decisions_by_node": list(
            load.sum(axis=-1) / (s * sizes["num_experts_per_tok"])
        ),
        "max_over_mean_held_expert_load_by_node": list(
            load.max(axis=-1) / np.maximum(load.mean(axis=-1), 1e-30)
        ),
        "expected_share": held / sizes["num_experts_total"],
    }), file=sys.stderr)
    return float(before), float(after)


# -- arithmetic for the per-layer metrics -----------------------------------


def counts(sizes):
    """(latent-attention nodes, dense feed-forward layers, expert nodes,
    uses of the head), the module's counted."""
    built = blocks(sizes)
    dense = sum(d for _, d in built)
    return (
        len(built), dense, len(built) - dense,
        1 + sizes["num_nextn_predict_layers"],
    )


def attention_pairs(sizes, seq):
    """(key-wide, value-wide) FLOPs of one causal [seq, seq] product a head,
    all heads, one sequence: the causal half of the pairs, the TRUE key width
    (192, not the 256 the kernel pads to)."""
    heads = sizes["num_attention_heads"]
    pairs = seq * (seq + 1) / 2
    kd = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    return 2 * pairs * heads * kd, 2 * pairs * heads * sizes["v_head_dim"]


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls and attention, nothing recomputed, of
    this chip's share; the module's block, its [2D, D] projection and the
    head's second use counted. A token runs the experts it is routed to that
    are HERE: k * held / E of an expert on average, and the shared one.
    Causal attention needs half the pairs of positions."""
    hidden = sizes["hidden_size"]
    heads = sizes["num_attention_heads"]
    nope, rope, vd = (
        sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    )
    lat, qlat = sizes["kv_lora_rank"], sizes["q_lora_rank"]
    key_pairs, value_pairs = attention_pairs(sizes, seq)
    mla_ = (
        2 * hidden * qlat + 2 * qlat * heads * (nope + rope)
        + 2 * hidden * (lat + rope) + 2 * lat * heads * (nope + vd)
        + 2 * heads * vd * hidden + (key_pairs + value_pairs) / seq
    )
    here = (
        sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
        / sizes["num_experts_total"]
    )
    width = sizes["moe_intermediate_size"]
    moe = 2 * hidden * sizes["num_experts_total"] + 3 * 2 * hidden * width * (
        here + sizes["n_shared_experts"]
    )
    dense = 3 * 2 * hidden * sizes["intermediate_size"]
    n_mla, n_dense, n_moe, heads_used = counts(sizes)
    layers = n_mla * mla_ + n_dense * dense + n_moe * moe
    module = sizes["num_nextn_predict_layers"] * 2 * (2 * hidden) * hidden
    return 3.0 * (
        layers + module + heads_used * 2 * hidden * sizes["vocab_rows_held"]
    )


def kernel_costs(sizes, batch, seq):
    """Least work of one training step on one chip, by kernel.

    `flash`: the causal core of every latent-attention node (the module's
    counted), forward (2 products) and backward (5), over the causal half of
    the pairs at the TRUE widths: the 192-wide key in the four products that
    contract or produce it, the 128-wide value in the three that do so.
    Bytes in bf16: q, k (192 wide), v, o (128) once forward; those with do
    read and dq, dk, dv written backward."""
    tokens = batch * seq
    n_mla = counts(sizes)[0]
    heads = sizes["num_attention_heads"]
    key_pairs, value_pairs = attention_pairs(sizes, seq)
    kd = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    key_tensor = 2 * tokens * heads * kd
    value_tensor = 2 * tokens * heads * sizes["v_head_dim"]
    return {
        "flash": {
            "flops": n_mla * batch * (4 * key_pairs + 3 * value_pairs),
            "bytes": n_mla * (6 * key_tensor + 6 * value_tensor),
        },
    }
