"""Ouro-2.6B (ByteDance; `model_type` `ouro`; the LoopLM paper, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741) as this
benchmark runs it: the first `num_hidden_layers` of its 48 layers, applied
`total_ut_steps` = 4 times in a row on ONE set of weights, an exit gate after
every pass and the expected loss over the four exits. `build` for the system
under test, `reference_losses` as the plain float32 `jax.numpy` reference,
and the arithmetic the per-layer metrics need. The cut, the deployment it
stands for, every departure and every assumed value are in the `.json` beside
this file; the reference makes the same ones. Nothing below `build` imports
the program.

The step, as the reference computes it (positions i of one sequence, token
ids t_1..t_s, labels t_2..t_{s+1}; D = hidden_size; `rms(x; g) = x rsqrt(mean
x^2 + eps) g`; no bias anywhere but the gate's):

    h_0 = E[ids]
    one layer:  a = x + rms(Attn(rms(x; g1)); g2)
                x' = a + rms(SwiGLU(rms(a; g3)); g4)
        Attn: q, k, v = u W_q, u W_k, u W_v (h heads of d, no QK-norm); the
        rotate-half rotary, pairs (j, j + d/2) of all d columns turned by
        p * theta^(-2j/d); causal softmax(q k^T / sqrt(d)) v, then W_o
        SwiGLU: (silu(u W_1) * (u W_3)) W_2
    pass t = 1..T:  h_t = rms(Stack(h_{t-1}); g_f)    # the SAME layers, g_f
                                                      # and head every pass
        l_t[i] = CE(h_t[i] W_head, label[i])
        z_t[i] = h_t[i] . w_g + b_g                   # the exit gate, ONE for
        lam_t = sigmoid(z_t), 1 - lam_t = sigmoid(-z_t)   # all passes, read
                                                          # at t = 1..T-1
    p_1 = lam_1;  p_t = lam_t prod_{j<t} (1 - lam_j)  (1 < t < T);
    p_T = prod_{j<T} (1 - lam_j)                      # the last exit takes
                                                      # what is left
    L = mean_i [ sum_t p_t[i] l_t[i]  +  beta sum_t p_t[i] log p_t[i] ]

No stop-gradient anywhere: the gate learns from the four losses and the
stream from the gate.

Parameter layouts the reference has to know (the program's public weight
formats, `<layer>.weight<j>`; a layer applied four times has ONE such set):
embedding and rms norm `weight0`; dense `weight0` [in, out], the gate's
`weight1` its bias [1]; attention `weight0` [per_head, heads], the rows of one
head being its wq [hidden, d] | wk | wv | wo [d, hidden], each flattened
row-major.
"""

import contextlib
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

# |system - reference| allowed on the loss L (natural log; the mean over the
# 8,192 positions of one sequence of the expected cross-entropy over the four
# exits less beta times the exit distribution's entropy). The system
# multiplies in bf16 with float32 accumulation; the norms' statistics, the
# rotary, the softmax, the four losses and the exit distribution (from the
# gate's bf16 pre-activation on) are float32. `run.py` holds (a), the loss
# before the step, and (b), the loss after one step, to this ONE number. It
# lies between two readings, both taken by the harness's own comparison on
# the chip (my chip runs, PR 62; PERF.md section 6):
#
# below, what a sound system reads: over READINGS_RUNS runs of
# `ouro26b_s8192_1chip` on 42 seeds, at most
# BF16_SYSTEM_MAX[0] on (a) and BF16_SYSTEM_MAX[1] on (b). (b) is a
# heavy-tailed draw: median 2.4e-4, under 8.5e-4 on all but seven of 42
# seeds, 1.33e-3, 1.40e-3, 1.71e-3, 2.05e-3, 2.64e-3, 3.45e-3, and 5.64e-3 on
# seed 161263244, on which the driver's check refused the cell while this
# limit stood at 3.4e-3. What it is was measured on that seed
# (`.bench_scratch/diagnose4.py`, PERF.md section 6, third session): the
# system's gradient is sound (its slope on the reference's is 0.9991 to
# 1.0006 in every group of matrices and gains, its error 1.5 to 3.4% of the
# norm), the system's own forward
# adds -2.2e-4, and the rest, 5.86e-3, is what the two UPDATES differ by.
# Adam's first step is `alpha * sign(g + 0.1 w)`; where the gradient nearly
# cancels the L2 pull the bf16 rounding decides the sign, and 0.076% of the
# attention weights, 0.042% of the SwiGLUs' and 0.009% of the embedding's
# step the OTHER way than the reference's, 6e-4 apart (the same count on a
# seed that reads 1e-4; the norms' gains and the gate's bias never do). The
# gradient at the INITIAL weights times that difference is nothing (7e-6 for
# the attention group). But this step is far outside the linear regime: on
# those elements of W_v and W_o (each attention branch ends in an RMS norm,
# so its output's scale is free and its curvature large) the gradient
# CHANGES over the reference's step by 6.3e-4 where it was 2.9e-4. The
# gradient at the reference's stepped state times the difference is the
# whole reading (4.15e-3 of the attention group's 4.20e-3, 1.32e-3 of the
# SwiGLUs' 1.36e-3, 3.0e-4 of the embedding's 3.1e-4; the difference's own
# curvature is 1% of it): the step's second-order term, sampled on the
# elements that stepped the other way, with the sign of the system's
# gradient error on each. Where that error is independent of the gradient's
# change the sum is a random walk (1.3e-4 for the attention group); on this
# seed the two correlate at -0.14 (54.0% of the signs line up) and the sum
# is 33 such walks. All signs lined up would read 3.6e-2 (2.3e-2 in the
# attention group, 1.0e-2 in the SwiGLUs), so that is what a sound step can
# read at the very most.
#
# above, the nearest precision below, which must fail:
# `benchmark/precision_control.py --operands float8_e4m3fn` runs the cell
# through `run.py` with every matmul operand of this reference rounded to
# float8_e4m3 (`OPERANDS`), and reads at least FLOAT8_REFERENCE_MIN[1] on (b):
# 0.106, 8.38e-2 and 7.47e-2 in the first three controls and 1.70e-2 in the
# fourth (seed 2166136261, third session, which a limit of 2e-2, the middle
# of 5.64e-3 and the three, let PASS: (b) of the control is a draw by seed
# as the sound reading is, for the same reason). (a) read 1.06e-2, 7.86e-4,
# 1.14e-2 and 6.3e-3: at the initial weights every exit's loss is
# ln 49,152 whatever the operands' precision, so (a) holds little precision
# here.
#
# 1e-2 is the geometric middle of 5.64e-3 and 1.70e-2 (9.8e-3), with a
# factor of 1.8 above the largest sound reading of READINGS_RUNS and 1.7
# under the smallest (b) of four controls: ALL the room there is. The two
# readings the limit has to part are a factor of three apart in this cell,
# each a heavy-tailed draw, so a seed can put a sound step over it (the
# readings' tail about halves with each doubling, 7 of 42 seeds over
# 1.3e-3, 3 over 2.6e-3, 1 over 5.6e-3: one run in fifty or so, if it goes
# on so) or a
# float8 reference under it:
# the cure is not a number but a check that the seed's loss landscape does
# not move (PERF.md section 7, Left by PR 62 (9)).
#
# What (b) cannot see, at this limit or at any: a step that does NOTHING.
# The step moves this loss by 0.0015 to 0.098 by seed (reference before ->
# after; up on most seeds, down on others: 94-99% of the signs are the L2
# pull's, a shrink that knows nothing of the loss), so a state left
# unchanged reads UNCHANGED_STATE_MIN on seed 161263244, UNDER that seed's
# sound reading, 6.8e-3 on seed 1117299323 and 0.04 on the median seed. The
# review of the first session asked for a limit under 6.8e-3 for that
# reason, and 3.4e-3 stood until the driver's seeds showed that the two
# readings overlap. A wrong or missing first update (a shared weight
# stepped from one reader's gradient, say) is held by
# `tests/test_ouro.py` at float32 tolerances on the CPU (a shared weight's
# gradient equals the sum over its four readers; system against reference
# after one step), by (c) if it lasts, and by nothing in this harness on
# the chip: PERF.md section 7 asks a `benchmark` PR for the norm of the
# parameters' change beside the loss.
LOSS_TOLERANCE = 1e-2
# runs, most seeds above 2**31 and each run's its own but for 161263244
# (the same to the last digit in the driver's run and in my three): twelve in the
# first session of PR 62 (judged at 5e-3 and 3e-3), eight in the second
# (3.4e-3), nine in the third (3.4e-3), eight of a staged tree at 2e-2
# and, at this limit, the last seven of the tree this file went in with,
# from the committed files alone; five float8 controls on four seeds (the
# smallest reading of each check is written down)
READINGS_RUNS = 44
BF16_SYSTEM_MAX = (3.76e-4, 5.64e-3)
UNCHANGED_STATE_MIN = 1.5e-3
FLOAT8_REFERENCE_MIN = (7.86e-4, 1.70e-2)

INPUT_NAMES = ("input_ids", "labels")
# positions the reference takes at a time where a whole sequence's tensor
# would not fit beside the system's state (attention scores, logits)
BLOCK = 512

# Every matrix product of the reference goes through `mm`, whose operands pass
# `OPERANDS` first: the identity here, a rounding to float8_e4m3 and back
# under `precision_control.py`, the control behind LOSS_TOLERANCE.
OPERANDS = None

# the names `build` gives, which the per-layer metrics' readers ask for
# a pass's exit, `<name>#<t>`: the final norm, the head, the gate, the loss
# node, and the elementwise nodes of the exit distribution between them
EXIT_NODES = ("norm_f", "head", "gate", "exit") + tuple(
    f"exit_{what}" for what in (
        "z", "z32", "lam", "neg", "stay", "p", "left", "logp", "plogp",
    )
)
ENTROPY = "entropy"  # the term's node; its sums are `entropy_sum#<t>`


def layer_nodes(sizes):
    """The names of the looped layers' nodes (without their pass): what lies
    under `ff.<kind>.<name>#<pass>` for them is the loop's time."""
    names = []
    for i in range(sizes["num_hidden_layers"]):
        names += [
            f"norm{i}a", f"attn{i}", f"norm{i}b", f"norm{i}c",
            f"ffn{i}_w1", f"ffn{i}_w3", f"ffn{i}_w2", f"norm{i}d",
            f"silu{i}", f"gated{i}", f"add{i}a", f"add{i}b",
        ]
    return names


def make_data(rs, sizes, n, seq):
    """`n` seeded sequences of `seq` + 1 tokens: inputs are the first `seq`,
    labels the next token at each position, which the graph's loss nodes read
    as one more input."""
    tokens = rs.randint(0, sizes["vocab_size"], (n, seq + 1)).astype(np.int32)
    labels = tokens[:, 1:].copy()
    return {"input_ids": tokens[:, :-1].copy(), "labels": labels.copy()}, labels


def build(sizes, batch, seq):
    """(graph builder, the last pass's logits) through the public builder:
    the layers and the final norm inside ONE `shared_block` applied
    `total_ut_steps` times, the gate inside another (read after every pass
    but the last), the head with its loss node (the exit probability as its
    position weights) inside a third, and the entropy term from elementwise
    nodes. The layer applications of the first `recomputed_passes` passes
    and, where that is not 0, every exit are `recompute` groups."""
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import TruncatedNormalInitializerAttrs

    assert sizes["hidden_act"] == "silu" and sizes["rope_scaling"] is None
    assert not sizes["tie_word_embeddings"] and not sizes["use_sliding_window"]
    assert sizes["num_key_value_heads"] == sizes["num_attention_heads"]
    hidden, vocab = sizes["hidden_size"], sizes["vocab_size"]
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    width, eps = sizes["intermediate_size"], sizes["rms_norm_eps"]
    passes = sizes["total_ut_steps"]
    std = sizes["initializer_range"]
    init = TruncatedNormalInitializerAttrs(
        stddev=std, min_cutoff=-3 * std, max_cutoff=3 * std
    )
    b = ComputationGraphBuilder()

    def dense(x, out, name):
        return b.dense(x, out, use_bias=False, kernel_initializer=init,
                       name=name)

    def layer(x, i):
        u = b.rms_norm(x, eps=eps, name=f"norm{i}a")
        y = b.multihead_attention(
            u, u, u, hidden, heads, kdim=d, vdim=d, causal=True,
            rope_theta=float(sizes["rope_theta"]), initializer=init,
            name=f"attn{i}",
        )
        a = b.add(x, b.rms_norm(y, eps=eps, name=f"norm{i}b"), name=f"add{i}a")
        u = b.rms_norm(a, eps=eps, name=f"norm{i}c")
        y = dense(
            b.multiply(
                b.silu(dense(u, width, f"ffn{i}_w1"), name=f"silu{i}"),
                dense(u, width, f"ffn{i}_w3"), name=f"gated{i}",
            ),
            hidden, f"ffn{i}_w2",
        )
        return b.add(a, b.rms_norm(y, eps=eps, name=f"norm{i}d"), name=f"add{i}b")

    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    labels = b.create_input([batch, seq], DataType.INT32, name="labels")
    h = b.embedding(ids, vocab, hidden, kernel_initializer=init, name="embed")
    loop, gate, exit_ = b.shared_block(), b.shared_block(), b.shared_block()
    left = None  # prod_{j<t} (1 - lam_j), float32 [batch, seq]
    terms = []  # p_t log p_t
    for t in range(1, passes + 1):
        with loop:
            for i in range(sizes["num_hidden_layers"]):
                # a layer application of the first `recomputed_passes`
                # keeps its input for the backward pass and computes the rest
                # again there; a later pass's, which the backward pass
                # reaches first, is kept whole
                recomputed = t <= sizes["recomputed_passes"]
                with b.recompute() if recomputed else contextlib.nullcontext():
                    h = layer(h, i)
            h = b.rms_norm(h, eps=eps, name="norm_f")

        def named(what, t=t):
            return f"exit_{what}#{t}"

        if t < passes:
            with gate:
                z = b.dense(h, 1, kernel_initializer=init, name="gate")
            # the exit distribution in float32 from the pre-activation on
            z = b.cast(
                b.reshape(z, [batch, seq], name=named("z")), DataType.FLOAT,
                name=named("z32"),
            )
            lam = b.sigmoid(z, name=named("lam"))
            stay = b.sigmoid(
                b.scalar_multiply(z, -1.0, name=named("neg")), name=named("stay")
            )
            p = lam if left is None else b.multiply(lam, left, name=named("p"))
            left = stay if left is None else b.multiply(
                left, stay, name=named("left")
            )
        else:
            p = left  # the last exit takes what is left (one pass: no gate)
        # the head and its loss: the [seq, vocab] logits of a pass are
        # computed again in the backward pass, not kept through three more
        # (`recomputed_passes` 0: nothing is, and the graph has no group)
        again = sizes["recomputed_passes"] > 0
        with exit_, b.recompute() if again else contextlib.nullcontext():
            logits = dense(h, vocab, "head")
            b.label_cross_entropy(
                logits, labels, position_weights=p, name="exit"
            )
        if p is not None:
            terms.append(b.multiply(
                p, b.log(p, name=named("logp")), name=named("plogp")
            ))
    if terms:
        total = terms[0]
        for t, term in enumerate(terms[1:], 2):
            total = b.add(total, term, name=f"{ENTROPY}_sum#{t}")
        b.mean_loss(total, weight=sizes["exit_entropy_weight"], name=ENTROPY)
    return b, logits


# -- the plain reference ----------------------------------------------------


def mm(spec, a, b):
    if OPERANDS is not None:
        a, b = OPERANDS(a), OPERANDS(b)
    return jnp.einsum(spec, a, b)


def rms(u, gain, eps):
    return u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x [heads, s, d]: rotate-half pairing (j, j + d/2)."""
    _, s, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + rotated * sin


def attention(flat, x, sizes):
    """Causal self-attention on x [s, hidden] from the flat weight
    [3*hidden*d + d*hidden, heads], BLOCK queries against every key at a
    time, so that an 8,192-position sequence's scores need not exist at once
    beside the system's state."""
    heads, d = sizes["num_attention_heads"], sizes["head_dim"]
    s, hidden = x.shape
    n = hidden * d
    wq = flat[0 * n:1 * n].reshape(hidden, d, heads)
    wk = flat[1 * n:2 * n].reshape(hidden, d, heads)
    wv = flat[2 * n:3 * n].reshape(hidden, d, heads)
    wo = flat[3 * n:4 * n].reshape(d, hidden, heads)
    theta = float(sizes["rope_theta"])
    q = rope(mm("se,edh->hsd", x, wq), theta)
    k = rope(mm("se,edh->hsd", x, wk), theta)
    v = mm("se,edh->hsd", x, wv)
    block = min(s, BLOCK)
    assert s % block == 0, (s, block)

    def query_block(start):
        rows = start + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = mm("hsd,htd->hst", qb, k) / np.sqrt(d)
        scores = jnp.where(
            rows[:, None] >= jnp.arange(s)[None, :], scores, -jnp.inf
        )
        return mm("hst,htd->hsd", jax.nn.softmax(scores, axis=-1), v)

    ctx = jax.lax.map(jax.checkpoint(query_block), jnp.arange(0, s, block))
    ctx = jnp.swapaxes(ctx, 0, 1).reshape(heads, s, d)
    return mm("hsd,deh->se", ctx, wo)


def swiglu(u, w1, w3, w2):
    return mm(
        "sh,hd->sd",
        jax.nn.silu(mm("sd,dh->sh", u, w1)) * mm("sd,dh->sh", u, w3), w2,
    )


def one_layer(w, i, x, sizes):
    eps = sizes["rms_norm_eps"]
    y = attention(
        w[f"attn{i}.weight0"], rms(x, w[f"norm{i}a.weight0"], eps), sizes
    )
    a = x + rms(y, w[f"norm{i}b.weight0"], eps)
    y = swiglu(
        rms(a, w[f"norm{i}c.weight0"], eps),
        *(w[f"ffn{i}_w{j}.weight0"] for j in (1, 3, 2)),
    )
    return a + rms(y, w[f"norm{i}d.weight0"], eps)


def cross_entropy_rows(h, head, labels):
    """Each position's cross-entropy of h [s, D] through `head`, BLOCK
    positions' logits at a time; 0 where the label is negative."""
    block = min(h.shape[0], BLOCK)

    def one(args):
        hb, yb = args
        logp = jax.nn.log_softmax(mm("sd,dv->sv", hb, head), axis=-1)
        picked = jnp.take_along_axis(logp, jnp.maximum(yb, 0)[:, None], axis=-1)
        return jnp.where(yb >= 0, -picked[:, 0], 0.0)

    return jax.lax.map(
        jax.checkpoint(one),
        (h.reshape(-1, block, h.shape[-1]), labels.reshape(-1, block)),
    ).reshape(-1)


def exit_distribution(z):
    """p [T, s] from the gate's pre-activations z [T - 1, s] (none: one
    exit of probability one)."""
    left = jnp.ones(z.shape[1:], z.dtype)
    p = []
    for zt in z:
        p.append(jax.nn.sigmoid(zt) * left)
        left = left * jax.nn.sigmoid(-zt)
    return jnp.stack(p + [left])


def sequence_terms(w, sizes, ids, labels):
    """One sequence: (each exit's cross-entropy a position [T, s], the exit
    distribution p [T, s]); the passes a Python loop over ONE set of
    weights. A pass keeps its input for the backward pass and a layer
    application inside it its own: both are computed again there, so that a
    sequence's activations fit beside the system's state."""
    eps = sizes["rms_norm_eps"]

    def stack(w, h):
        for i in range(sizes["num_hidden_layers"]):
            h = jax.checkpoint(
                lambda w, h, i=i: one_layer(w, i, h, sizes)
            )(w, h)
        return rms(h, w["norm_f.weight0"], eps)

    h = w["embed.weight0"][ids]
    losses, z = [], []
    for t in range(sizes["total_ut_steps"]):
        h = jax.checkpoint(stack)(w, h)
        losses.append(cross_entropy_rows(h, w["head.weight0"], labels))
        if t + 1 < sizes["total_ut_steps"]:
            z.append(
                mm("sd,do->so", h, w["gate.weight0"])[:, 0] + w["gate.weight1"][0]
            )
    z = jnp.stack(z) if z else jnp.zeros((0, ids.shape[0]))
    return jnp.stack(losses), exit_distribution(z)


def sequence_loss(w, sizes, ids, labels):
    """One sequence's summed objective: the expected cross-entropy over the
    exits plus beta times sum_t p_t log p_t (less beta times the entropy)."""
    losses, p = sequence_terms(w, sizes, ids, labels)
    entropy_term = jnp.sum(p * jnp.log(p)) if p.shape[0] > 1 else 0.0
    return jnp.sum(p * losses) + sizes["exit_entropy_weight"] * entropy_term


def adam_first_step(grad, params, adam):
    """Adam's first step (m and v start at zero, so neither is kept) with the
    weight decay as an L2 term, as `reference_lib.losses_with_adam_step`
    takes it."""

    def one(g, w):
        g = g + adam["weight_decay"] * w
        m = (1.0 - adam["beta1"]) * g
        v = (1.0 - adam["beta2"]) * jnp.square(g)
        alpha_t = (
            adam["alpha"] * np.sqrt(1.0 - adam["beta2"]) / (1.0 - adam["beta1"])
        )
        return w - alpha_t * m / (jnp.sqrt(v) + adam["epsilon"])

    return jax.tree_util.tree_map(one, grad, params)


def reference_losses(params, inputs, labels, sizes, adam):
    """(L before, L after one Adam step) on one batch, one sequence at a
    time: a sequence is recomputed in the backward pass (each pass inside it
    again, and each layer application inside that), so the gradient is ONE
    accumulator the size of the model beside the system's state.
    (`reference_lib.losses_with_adam_step` keeps a sequence's gradient beside
    the accumulator: 8.97 GB at six layers where the system's state leaves
    8.15, my first chip run, PR 62.) Of `inputs` only `input_ids` is read:
    the labels are the harness's own. The batch is an argument of every
    program. The first sequence's terms by exit go to standard error."""
    w = dict(params)
    rows = (jnp.asarray(inputs["input_ids"]), jnp.asarray(labels))

    def mean_loss(w, rows):
        # a Python loop and no `lax.scan`: a scan's backward keeps one
        # sequence's gradient BESIDE the accumulated one (2 GB each at six
        # layers; 8.52 GB where 8.15 were free, my second chip run, PR 62)
        return sum(
            jax.checkpoint(lambda w, row: sequence_loss(w, sizes, *row))(w, row)
            for row in zip(*rows)
        ) / labels.size

    with jax.default_matmul_precision("highest"):
        before, grad = jax.jit(jax.value_and_grad(mean_loss))(w, rows)
        stepped = jax.jit(
            lambda g, w: adam_first_step(g, w, adam), donate_argnums=0
        )(grad, w)
        del grad
        after = jax.jit(mean_loss)(stepped, rows)
        del stepped
        losses, p = jax.jit(
            lambda w, ids, y: sequence_terms(w, sizes, ids, y)
        )(w, rows[0][0], rows[1][0])
    print("ouro-2.6b reference: " + json.dumps({
        "expected_loss_by_exit": [float(x) for x in jnp.mean(p * losses, axis=1)],
        "mean_loss_by_exit": [float(x) for x in jnp.mean(losses, axis=1)],
        "exit_mass": [float(x) for x in jnp.mean(p, axis=1)],
        "entropy_term": float(jnp.mean(jnp.sum(p * jnp.log(p), axis=0))),
        "beta": sizes["exit_entropy_weight"],
        "ln_vocab": float(np.log(sizes["vocab_size"])),
    }), file=sys.stderr)
    return float(before), float(after)


# -- arithmetic for the per-layer metrics -----------------------------------


def parameter_counts(sizes):
    """The parameters as built, term by term: ONE set, whatever the passes."""
    hidden, width = sizes["hidden_size"], sizes["intermediate_size"]
    layer = {
        "attention": 4 * hidden * sizes["num_attention_heads"] * sizes["head_dim"],
        "swiglu": 3 * hidden * width,
        "norms": 4 * hidden,
    }
    layers = sizes["num_hidden_layers"] * sum(layer.values())
    table = hidden * sizes["vocab_size"]
    gate = (hidden + 1) * (sizes["total_ut_steps"] > 1)
    return {
        "layer": layer, "layers": layers, "embedding": table, "head": table,
        "final_norm": hidden, "gate": gate,
        "total": layers + 2 * table + hidden + gate,
    }


def applications(sizes):
    """Layer applications a step: every layer, every pass."""
    return sizes["num_hidden_layers"] * sizes["total_ut_steps"]


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls and attention only, nothing recomputed.
    A layer runs once a pass and so does the head. Causal attention needs
    half the pairs of positions."""
    hidden = sizes["hidden_size"]
    per_application = (
        2 * 4 * hidden * hidden
        + 2 * 2 * hidden * (seq + 1) / 2
        + 3 * 2 * hidden * sizes["intermediate_size"]
    )
    head = 2 * hidden * sizes["vocab_size"]
    gate = 2 * hidden * (sizes["total_ut_steps"] - 1)
    return 3.0 * (
        applications(sizes) * per_application
        + sizes["total_ut_steps"] * head + gate
    )


def kernel_costs(sizes, batch, seq):
    """Least work of one training step on one chip, by kernel.

    `flash`: the causal core of every layer application (16 heads of 128),
    forward (2 products) and backward (5), over the causal half of the
    pairs; q, k, v, o read or written once forward, those with do read and
    dq, dk, dv written backward, in bf16."""
    hidden = sizes["num_attention_heads"] * sizes["head_dim"]
    pair = 2 * batch * seq * (seq + 1) / 2 * hidden
    tensor = 2 * batch * seq * hidden
    return {
        "flash": {
            "flops": applications(sizes) * (2 + 5) * pair,
            "bytes": applications(sizes) * (4 + 8) * tensor,
        },
    }
