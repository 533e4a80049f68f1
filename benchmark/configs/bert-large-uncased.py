"""BERT encoder (Devlin et al., arXiv:1810.04805) as this benchmark runs it.

Three things live here, found by the configuration's name: `build`, which
states the model to the system under test through its public graph builder;
`reference_losses`, the plain float32 `jax.numpy` implementation the system is
compared with (independent of the program: it shares no code with
`flexflow_tpu`, only the parameter layouts described below); and the
arithmetic the per-layer metrics need (`flops_per_token`, `kernel_costs`).

Departures from the published model are listed in the `.json` beside this
file under `reduced` and `departures`; the reference makes the same ones, so
that it computes what the system is asked to compute.

Parameter layouts the reference has to know (they are the program's public
weight formats, `op_attrs/ops/attention.py`):

- dense: `weight0` [in, out], `weight1` [out];
- layer norm: `weight0` gamma, `weight1` beta;
- embedding: `weight0` [entries, hidden];
- attention: `weight0` [per_head, heads], the rows of one head being its
  wq [hidden, d] | wk [hidden, d] | wv [hidden, d] | wo [d, hidden], each
  flattened row-major; `weight1` [3d], the q|k|v input bias, **shared by all
  heads** (a departure: BERT has one bias per head); `weight2` [hidden].
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

INPUT_NAMES = ("input_ids", "position_ids", "token_type_ids")


def build(sizes, batch, seq):
    """(computation graph, logits tensor) through the public builder."""
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import NormInitializerAttrs

    hidden = sizes["hidden_size"]
    heads = sizes["num_attention_heads"]
    eps = sizes["layer_norm_eps"]
    init = NormInitializerAttrs(stddev=sizes["initializer_range"])
    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    pos = b.create_input([batch, seq], DataType.INT32, name="position_ids")
    typ = b.create_input([batch, seq], DataType.INT32, name="token_type_ids")
    h = b.add(
        b.add(
            b.embedding(ids, sizes["vocab_size"], hidden,
                        kernel_initializer=init, name="tok"),
            b.embedding(pos, sizes["max_position_embeddings"], hidden,
                        kernel_initializer=init, name="pos"),
        ),
        b.embedding(typ, sizes["type_vocab_size"], hidden,
                    kernel_initializer=init, name="typ"),
    )
    h = b.layer_norm(h, axes=[-1], eps=eps, name="ln_emb")
    for i in range(sizes["num_hidden_layers"]):
        attn = b.multihead_attention(
            h, h, h, hidden, heads, kdim=hidden // heads,
            vdim=hidden // heads, bias=True, initializer=init,
            name=f"attn{i}",
        )
        h = b.layer_norm(b.add(h, attn), axes=[-1], eps=eps, name=f"ln1_{i}")
        ff = b.dense(h, sizes["intermediate_size"], kernel_initializer=init,
                     name=f"ff1_{i}")
        ff = b.dense(b.gelu(ff), hidden, kernel_initializer=init,
                     name=f"ff2_{i}")
        h = b.layer_norm(b.add(h, ff), axes=[-1], eps=eps, name=f"ln2_{i}")
    logits = b.dense(h, sizes["vocab_size"], kernel_initializer=init,
                     name="head")
    return b.graph, logits


def make_data(rs, sizes, n, seq):
    """`n` seeded sequences: (inputs by name, labels), all int32 [n, seq].
    Two segments per sequence, split at a seeded point, as in pre-training."""
    ids = rs.randint(0, sizes["vocab_size"], (n, seq)).astype(np.int32)
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (n, seq)).copy()
    split = rs.randint(seq // 4, 3 * seq // 4, (n, 1))
    typ = (np.arange(seq)[None, :] >= split).astype(np.int32)
    labels = rs.randint(0, sizes["vocab_size"], (n, seq)).astype(np.int32)
    return {"input_ids": ids, "position_ids": pos, "token_type_ids": typ}, labels


# -- the plain reference ----------------------------------------------------


BLOCK_PREFIXES = ("attn", "ln1_", "ff1_", "ff2_", "ln2_")


def _sequence_loss(p, sizes, ids, pos, typ, labels):
    """Summed cross-entropy of one sequence [s], post-LN BERT."""
    outer, layers = p
    eps = sizes["layer_norm_eps"]
    heads = sizes["num_attention_heads"]
    h = (
        outer["tok.weight0"][ids] + outer["pos.weight0"][pos]
        + outer["typ.weight0"][typ]
    )
    h = layer_norm(h, outer["ln_emb.weight0"], outer["ln_emb.weight1"], eps)

    def block(h, w):
        a = attention(w, "attn", h, heads, causal=False)
        h = layer_norm(h + a, w["ln1_.weight0"], w["ln1_.weight1"], eps)
        f = gelu_tanh(h @ w["ff1_.weight0"] + w["ff1_.weight1"])
        f = f @ w["ff2_.weight0"] + w["ff2_.weight1"]
        return layer_norm(h + f, w["ln2_.weight0"], w["ln2_.weight1"], eps)

    h = run_blocks(block, h, layers)
    logits = h @ outer["head.weight0"] + outer["head.weight1"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def reference_losses(params, inputs, labels, sizes, adam):
    """(loss before, loss after one Adam step) on one batch, in float32 at
    the highest matmul precision, one sequence at a time so that it fits.
    `params` maps `<layer>.weight<i>` to float32 arrays; `adam` holds
    alpha, beta1, beta2, epsilon and weight_decay (an L2 term added to the
    gradient, as the program's optimizer defines it)."""
    cols = [inputs[k] for k in INPUT_NAMES]
    return losses_with_adam_step(
        lambda p, row: _sequence_loss(p, sizes, *row),
        split_layers(params, sizes["num_hidden_layers"], BLOCK_PREFIXES),
        (*cols, labels), labels.size, adam,
    )


# -- arithmetic for the per-layer metrics -----------------------------------


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls and attention only, nothing recomputed.
    The embedding look-ups do no multiplication."""
    hidden = sizes["hidden_size"]
    per_layer = (
        2 * 4 * hidden * hidden  # q, k, v and output projections
        + 2 * 2 * seq * hidden  # scores and context, every pair of positions
        + 2 * 2 * hidden * sizes["intermediate_size"]
    )
    head = 2 * hidden * sizes["vocab_size"]
    return 3.0 * (sizes["num_hidden_layers"] * per_layer + head)


def kernel_costs(sizes, batch, seq):
    """Least work of the attention kernel calls of one training step on one
    chip: FLOPs and HBM bytes for all layers, forward (2 matmuls) and
    backward (5 matmuls: scores again, dV, dP, dQ, dK). Bytes are each
    operand read and each result written once in bf16: forward reads q, k,
    v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    hidden = sizes["hidden_size"]
    layers = sizes["num_hidden_layers"]
    pair = 2 * batch * seq * seq * hidden  # one [s,s]x[d] matmul, all heads
    tensor = 2 * batch * seq * hidden  # one [b, s, hidden] bf16 tensor
    return {
        "flash": {
            "flops": layers * (2 + 5) * pair,
            "bytes": layers * (4 + 8) * tensor,
        }
    }
