"""Cerebras-GPT (Dey et al., arXiv:2304.03208; the GPT-2 architecture) as this
benchmark runs it: `build` for the system under test, `reference_losses` as
the plain float32 `jax.numpy` reference, and the arithmetic the per-layer
metrics need. Departures from the published model are in the `.json` beside
this file; the reference makes the same ones. Parameter layouts are those
described in `bert-large-uncased.py`; causal attention is the program's
`RingAttentionAttrs(causal=True)`, whose weights have the same layout.
"""

import jax
import jax.numpy as jnp
import numpy as np

from reference_lib import (
    attention,
    gelu_tanh,
    layer_norm,
    losses_with_adam_step,
    run_blocks,
    split_layers,
)

# |system - reference| allowed on a loss (natural log, mean over positions).
# The system multiplies in bf16 with float32 accumulation and keeps float32
# parameters and optimizer state. Over 41 chip runs of PR 22 (every cell of
# both configurations, one chip and four) its loss differed from the float32
# reference by 8e-5 (standard deviation; at most 2.9e-4) before the step and
# by 1.1e-4 (at most 2.3e-4) after it, so 5e-4 is five deviations. The next
# lower precision fails it: with the weights rounded to float8_e4m3 before
# the bf16 matmuls the two losses were off by 9.8e-4 and 1.4e-3, and with
# float8 operands throughout the loss was not finite (scratch runs on the
# chip, `bertlarge_s128_1chip`). One Adam step moves the loss by 0.09 to
# 0.11, so a backward pass or an optimizer that does nothing, or steps the
# wrong way, fails (b) by two hundred times the bound. Parameters and state
# kept in bf16 move the loss after one step by only 4e-4, inside the bound:
# `run.py` checks their dtype against `training.state_dtype` instead.
LOSS_TOLERANCE = 5e-4

INPUT_NAMES = ("input_ids", "position_ids")


def build(sizes, batch, seq):
    """(computation graph, logits tensor) through the public builder."""
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.ops.ring_attention import RingAttentionAttrs
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import NormInitializerAttrs

    embd = sizes["n_embd"]
    heads = sizes["n_head"]
    eps = sizes["layer_norm_epsilon"]
    init = NormInitializerAttrs(stddev=sizes["initializer_range"])
    attn_attrs = RingAttentionAttrs(
        embd, heads, kdim=embd // heads, vdim=embd // heads, bias=True,
        causal=True,
    )
    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    pos = b.create_input([batch, seq], DataType.INT32, name="position_ids")
    h = b.add(
        b.embedding(ids, sizes["vocab_size"], embd, kernel_initializer=init,
                    name="wte"),
        b.embedding(pos, sizes["n_positions"], embd, kernel_initializer=init,
                    name="wpe"),
    )
    for i in range(sizes["n_layer"]):
        x = b.layer_norm(h, axes=[-1], eps=eps, name=f"ln1_{i}")
        (attn,) = b.add_layer(attn_attrs, [x, x, x], [init], name=f"attn{i}")
        h = b.add(h, attn)
        x = b.layer_norm(h, axes=[-1], eps=eps, name=f"ln2_{i}")
        ff = b.dense(x, sizes["n_inner"], kernel_initializer=init,
                     name=f"fc_{i}")
        ff = b.dense(b.gelu(ff), embd, kernel_initializer=init,
                     name=f"proj_{i}")
        h = b.add(h, ff)
    h = b.layer_norm(h, axes=[-1], eps=eps, name="ln_f")
    logits = b.dense(h, sizes["vocab_size"], use_bias=False,
                     kernel_initializer=init, name="head")
    return b.graph, logits


def make_data(rs, sizes, n, seq):
    """`n` seeded sequences of `seq` + 1 tokens: inputs are the first `seq`,
    labels the next token at each position."""
    tokens = rs.randint(0, sizes["vocab_size"], (n, seq + 1)).astype(np.int32)
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (n, seq)).copy()
    return (
        {"input_ids": tokens[:, :-1].copy(), "position_ids": pos},
        tokens[:, 1:].copy(),
    )


# -- the plain reference ----------------------------------------------------


BLOCK_PREFIXES = ("ln1_", "attn", "ln2_", "fc_", "proj_")


def _sequence_loss(p, sizes, ids, pos, labels):
    """Summed next-token cross-entropy of one sequence [s], pre-LN GPT-2."""
    outer, layers = p
    eps = sizes["layer_norm_epsilon"]
    heads = sizes["n_head"]
    h = outer["wte.weight0"][ids] + outer["wpe.weight0"][pos]

    def block(h, w):
        x = layer_norm(h, w["ln1_.weight0"], w["ln1_.weight1"], eps)
        h = h + attention(w, "attn", x, heads, causal=True)
        x = layer_norm(h, w["ln2_.weight0"], w["ln2_.weight1"], eps)
        f = gelu_tanh(x @ w["fc_.weight0"] + w["fc_.weight1"])
        return h + f @ w["proj_.weight0"] + w["proj_.weight1"]

    h = run_blocks(block, h, layers)
    h = layer_norm(h, outer["ln_f.weight0"], outer["ln_f.weight1"], eps)
    logp = jax.nn.log_softmax(h @ outer["head.weight0"], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def reference_losses(params, inputs, labels, sizes, adam):
    """(loss before, loss after one Adam step) on one batch; see
    `reference_lib.losses_with_adam_step`."""
    cols = [inputs[k] for k in INPUT_NAMES]
    return losses_with_adam_step(
        lambda p, row: _sequence_loss(p, sizes, *row),
        split_layers(params, sizes["n_layer"], BLOCK_PREFIXES),
        (*cols, labels), labels.size, adam,
    )


# -- arithmetic for the per-layer metrics -----------------------------------


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls and attention only, nothing recomputed.
    Causal attention needs half the pairs of positions: a position attends
    to (seq + 1) / 2 others on average."""
    embd = sizes["n_embd"]
    per_layer = (
        2 * 4 * embd * embd
        + 2 * 2 * embd * (seq + 1) / 2
        + 2 * 2 * embd * sizes["n_inner"]
    )
    head = 2 * embd * sizes["vocab_size"]
    return 3.0 * (sizes["n_layer"] * per_layer + head)


def kernel_costs(sizes, batch, seq):
    """Least work of the attention kernel calls of one training step on one
    chip; as in `bert-large-uncased.py`, with the causal half of the pairs."""
    embd = sizes["n_embd"]
    layers = sizes["n_layer"]
    pair = 2 * batch * seq * (seq + 1) / 2 * embd
    tensor = 2 * batch * seq * embd
    return {
        "flash": {
            "flops": layers * (2 + 5) * pair,
            "bytes": layers * (4 + 8) * tensor,
        }
    }
