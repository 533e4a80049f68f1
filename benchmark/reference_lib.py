"""What the configurations' plain references share: float32 `jax.numpy`
building blocks written from the published equations, and the driver that
takes a mean loss, its gradient and Adam's first step one sequence at a time.
Nothing here imports the program under test."""

import jax
import jax.numpy as jnp
import numpy as np


def layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def gelu_tanh(x):
    return 0.5 * x * (
        1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x**3))
    )


def attention(p, name, x, heads, causal):
    """Multi-head self-attention on x [s, hidden] from the flat weight."""
    hidden = x.shape[-1]
    d = hidden // heads
    w = p[f"{name}.weight0"]  # [3*hidden*d + d*hidden, heads]
    n = hidden * d
    wq = w[0 * n:1 * n].reshape(hidden, d, heads)
    wk = w[1 * n:2 * n].reshape(hidden, d, heads)
    wv = w[2 * n:3 * n].reshape(hidden, d, heads)
    wo = w[3 * n:4 * n].reshape(d, hidden, heads)
    q = jnp.einsum("se,edh->hsd", x, wq)
    k = jnp.einsum("se,edh->hsd", x, wk)
    v = jnp.einsum("se,edh->hsd", x, wv)
    if f"{name}.weight1" in p:
        bias = p[f"{name}.weight1"]
        q, k, v = q + bias[:d], k + bias[d:2 * d], v + bias[2 * d:]
    scores = jnp.einsum("hsd,htd->hst", q, k) / np.sqrt(d)
    if causal:
        s = x.shape[0]
        scores = jnp.where(
            jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], scores, -jnp.inf
        )
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hst,htd,deh->se", probs, v, wo)
    if f"{name}.weight2" in p:
        out = out + p[f"{name}.weight2"]
    return out


def split_layers(params, n_layers, prefixes):
    """(outer, layers): the parameters of the `n_layers` identical blocks
    stacked along a new leading axis, so that the reference runs the blocks
    as one `lax.scan` (one block to compile, not `n_layers`). A block's
    parameter `<prefix><i>.weight<j>` is found in `layers` as
    `<prefix>.weight<j>` with shape [n_layers, ...]; everything else stays
    in `outer` under its own name."""
    outer = dict(params)
    layers = {}
    for prefix in prefixes:
        slot = 0
        while f"{prefix}0.weight{slot}" in outer:
            layers[f"{prefix}.weight{slot}"] = jnp.stack(
                [outer.pop(f"{prefix}{i}.weight{slot}") for i in range(n_layers)]
            )
            slot += 1
    return outer, layers


def run_blocks(block, h, layers):
    """`block(h, one layer's parameters)` over the stacked layers. A block is
    recomputed in the backward pass, so that a sequence's activations fit
    beside the system's own state; the arithmetic is unchanged."""
    h, _ = jax.lax.scan(
        lambda h, layer: (jax.checkpoint(block)(h, layer), None), h, layers
    )
    return h


def losses_with_adam_step(sequence_loss, params, rows, positions, adam):
    """Shared by the configurations' references: mean loss and its gradient
    accumulated one sequence at a time, Adam's first step, mean loss again.
    `sequence_loss(params, row)` is the summed loss of one sequence; `rows`
    are the batch's arrays, one row a sequence. The gradient's buffers are
    donated to the stepped parameters, so that the reference needs one copy
    of the model beside the system's own state. The batch is an argument of
    every program and never a constant in it: a program that holds the data
    is another program for every seed, and compiles in every run."""
    rows = tuple(jnp.asarray(r) for r in rows)

    def mean_share(p, row):
        return sequence_loss(p, row) / positions

    @jax.jit
    def loss_and_grad(p, rows):
        def one(carry, row):
            loss, grad = jax.value_and_grad(mean_share)(p, row)
            total, acc = carry
            return (total + loss, jax.tree_util.tree_map(jnp.add, acc, grad)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        (total, acc), _ = jax.lax.scan(one, (jnp.zeros(()), zero), rows)
        return total, acc

    def adam_first_step(g, p):
        # m and v start at zero, so the first step needs neither kept
        def one(g, w):
            g = g + adam["weight_decay"] * w
            m = (1.0 - adam["beta1"]) * g
            v = (1.0 - adam["beta2"]) * jnp.square(g)
            alpha_t = (
                adam["alpha"] * np.sqrt(1.0 - adam["beta2"])
                / (1.0 - adam["beta1"])
            )
            return w - alpha_t * m / (jnp.sqrt(v) + adam["epsilon"])

        return jax.tree_util.tree_map(one, g, p)

    @jax.jit
    def mean_loss(p, rows):
        def one(total, row):
            return total + mean_share(p, row), None

        total, _ = jax.lax.scan(one, jnp.zeros(()), rows)
        return total

    with jax.default_matmul_precision("highest"):
        before, grad = loss_and_grad(params, rows)
        stepped = jax.jit(adam_first_step, donate_argnums=0)(grad, params)
        del grad
        return float(before), float(mean_loss(stepped, rows))
