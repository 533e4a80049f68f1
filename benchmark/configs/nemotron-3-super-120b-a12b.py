"""NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (`model_type` `nemotron_h`: the
Mamba-2 / attention / sparse-expert hybrid whose routed experts live in a
latent space; equations as in Hugging Face `modeling_nemotron_h.py` and Dao &
Gu, arXiv:2405.21060) as this benchmark runs it: ONE chip's share of eleven
of the 88 layers its config.json states. `build` for the system under test,
`reference_losses` as the plain float32 `jax.numpy` reference, and the
arithmetic the per-layer metrics need. The cut, the deployment it stands for
and every departure from the published description are in the `.json` beside
this file; the reference makes the same ones. Nothing below `build` imports
the program.

The tower is `nemotron-twotower-30b-a3b.py`'s at other counts, and its `M`
and `*` mixers, the recurrence step by step, the router, the loss and Adam's
first step are loaded from that file as they are (`tower`, this file's own
copy of the module): here a chip holds ONE Mamba-2 group of 16 heads of 64
and 4 query heads over the 1 key/value head they read, so
`mamba_num_heads`, `n_groups`, `num_attention_heads` and
`num_key_value_heads` are the share's, and a mixer's output projection gives
this chip's part of the sum over the shares. **`E` is new**, for m = rms(x)
[s, 4096]:

    r = m W_g (float32, [512]);  s = sigmoid(r);  S = the 22 of largest s + b
    w_e = s_e / (sum_S s + 1e-20) * 5.0
    z = m W_down                                   [4096, 1024], no bias
    u = sum_{e in S, e HELD} w_e W2_e relu(W1_e z)^2    W1_e [1024, 2688]
    out = u W_up + Ws2 relu(Ws1 m)^2               W_up [1024, 4096]

The router and the shared expert read m, the experts z; the `held` experts
first .. first + n_routed_experts - 1 are here, applied densely to every
position of z and kept under the router's weights, and what the others would
add is left out, in the program and here alike.

Parameter layouts beyond the tower file's: experts `weight0` W_g [D, E],
`weight1` b [E], `weight2` W_down [D, L], `weight3` W1 [held, L, I],
`weight4` W2 [held, I, L], `weight5` W_up [L, D], `weight6` Ws1 [D, Is],
`weight7` Ws2 [Is, D].
"""

import importlib.util
import os

import jax
import jax.numpy as jnp


def _load_tower():
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "nemotron-twotower-30b-a3b.py",
    )
    spec = importlib.util.spec_from_file_location("bench_super_tower", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


tower = _load_tower()

# |system - reference| allowed on a loss (natural log, mean over the 4,096
# positions of one sequence). The system multiplies in bf16 with float32
# accumulation; its router, the norms' statistics, softplus, the scan's
# running sums, decays and states and the experts' combine are float32. Two
# readings set the bound, as in the tower's file (my chip runs, PR 39;
# PERF.md section 6), both taken by the harness's own comparison. Over
# READINGS_RUNS runs of `super120b_s4096_1chip` (38 seeds; 14 of the runs took
# the held rows in a wider window, since dropped) the system differed from this reference by at most BF16_SYSTEM_MAX[0]
# before the step and BF16_SYSTEM_MAX[1] after it, the tower's own range
# (8.8e-4 / 2.21e-3): one sequence of 4,096 positions a step, and Adam's
# first step, a sign step of 3e-4 on every weight, moves this loss by 2.2,
# so the gradient signs bf16 flips show. The nearest precision below must
# fail: `benchmark/precision_control.py --operands float8_e4m3fn` runs the
# cell through `run.py` with every matmul operand of this reference rounded
# to float8_e4m3 (`OPERANDS`), and `correct` came out false on both seeds:
# the system is off that reference by 7.0e-4 and 1.18e-3 before the step,
# which PASSES, and by 1.885 and 1.943 after it (with bf16 operands 5.0e-4
# and 6.8e-4, `correct`). So check (a) has no upper reading under the limit
# and does not tell float8 from bf16: `run.py` holds (a) and (b) to this ONE
# number, and only the Adam-amplified (b) holds the precision. 5e-3 is 3.1
# times the largest bf16 reading and a 377th of the float8 one. A backward
# pass that does nothing fails (b) four hundredfold.
# `tests/test_nemotron_super.py` runs the same control at toy widths.
LOSS_TOLERANCE = 5e-3
READINGS_RUNS = 49
BF16_SYSTEM_MAX = (6.8e-4, 1.61e-3)
FLOAT8_REFERENCE_MIN = (7.0e-4, 1.885)

INPUT_NAMES = tower.INPUT_NAMES
layer_names = tower.layer_names
held_range = tower.held_range
make_data = tower.make_data

# Every matrix product of the reference goes through the tower's `mm`, whose
# operands pass this `OPERANDS` first (`reference_losses` hands it over): the
# identity here, a rounding to float8_e4m3 and back under
# `precision_control.py`, the control behind LOSS_TOLERANCE.
OPERANDS = None


def build(sizes, batch, seq):
    """(graph builder, logits tensor) through the public builder."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.initializer import TruncatedNormalInitializerAttrs

    assert sizes["mlp_hidden_act"] == "relu2" and sizes["n_group"] == 1
    hidden = sizes["hidden_size"]
    eps = sizes["layer_norm_epsilon"]
    std = sizes["initializer_range"]
    init = TruncatedNormalInitializerAttrs(
        stddev=std, min_cutoff=-3 * std, max_cutoff=3 * std
    )
    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    h = b.embedding(ids, sizes["vocab_rows_held"], hidden, kernel_initializer=init,
                    name="embed")
    for kind, norm, name in layer_names(sizes):
        x = b.rms_norm(h, eps=eps, name=norm)
        if kind == "M":
            y = b.state_space(
                x, sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                sizes["ssm_state_size"], num_groups=sizes["n_groups"],
                conv_kernel=sizes["conv_kernel"],
                chunk_size=sizes["chunk_size"], norm_eps=eps,
                initializer=init, name=name,
            )
        elif kind == "*":
            y = b.multihead_attention(
                x, x, x, hidden, sizes["num_attention_heads"],
                kdim=sizes["head_dim"], vdim=sizes["head_dim"],
                bias=sizes["attention_bias"], causal=True,
                num_kv_heads=sizes["num_key_value_heads"],
                initializer=init, name=name,
            )
        else:
            y = b.experts(
                x, sizes["n_routed_experts_total"],
                sizes["num_experts_per_tok"], sizes["moe_intermediate_size"],
                activation=Activation.RELU2, capacity_factor=None,
                use_bias=False, renormalize=sizes["norm_topk_prob"],
                scoring="sigmoid", selection_bias=True,
                routed_scale=sizes["routed_scaling_factor"],
                shared_hidden_size=sizes["n_shared_experts"]
                * sizes["moe_shared_expert_intermediate_size"],
                held_experts=held_range(sizes),
                latent_size=sizes["moe_latent_size"],
                initializer=init, name=name,
            )[0]
        h = b.add(h, y)
    h = b.rms_norm(h, eps=eps, name="norm_f")
    logits = b.dense(h, sizes["vocab_rows_held"], use_bias=False,
                     kernel_initializer=init, name="head")
    return b, logits


# -- the plain reference ----------------------------------------------------


def experts(w, name, m, sizes):
    """The `E` mixer on m [s, D]: the held experts applied to every position
    of the latent rows, densely, and kept under the router's weights (zero
    where an expert was not chosen), projected back up once, plus the shared
    expert on m. ([s, D], the 0/1 mask of the chosen experts [s, E])."""
    mm, relu2 = tower.mm, tower.relu2
    first, held = held_range(sizes)
    _, mask, weight = tower.router(w, name, m, sizes)
    z = mm("sd,dl->sl", m, w[f"{name}.weight2"])

    def one(acc, expert):
        w1, w2, we = expert
        y = mm("sh,hl->sl", relu2(mm("sl,lh->sh", z, w1)), w2)
        return acc + we[:, None] * y, None

    u, _ = jax.lax.scan(
        lambda acc, e: jax.checkpoint(one)(acc, e),
        jnp.zeros_like(z),
        (w[f"{name}.weight3"], w[f"{name}.weight4"],
         weight[:, first:first + held].T),
    )
    shared = mm(
        "sh,hd->sd", relu2(mm("sd,dh->sh", m, w[f"{name}.weight6"])),
        w[f"{name}.weight7"],
    )
    return mm("sl,ld->sd", u, w[f"{name}.weight5"]) + shared, mask


# the tower's layer loop looks its `E` mixer up in its own module: in this
# file's copy of that module it is the latent one
tower.experts = experts


def reference_losses(params, inputs, labels, sizes, adam):
    """(loss before, loss after one Adam step) on one batch: the tower's own
    procedure (one sequence at a time, the recurrence step by step, float32
    under `highest`) with the `E` mixer above."""
    tower.OPERANDS = OPERANDS
    return tower.reference_losses(params, inputs, labels, sizes, adam)


# -- arithmetic for the per-layer metrics -----------------------------------

scan_flops_per_token = tower.scan_flops_per_token


def held_share(sizes):
    """Routed experts a token runs HERE, on average: k * held / E."""
    return (
        sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
        / sizes["n_routed_experts_total"]
    )


def expert_layer_flops_per_token(sizes):
    """Forward FLOPs of one `E` layer for one position: the router over all
    the experts, both latent projections, the held experts the position is
    routed to (in the latent space) and the whole shared expert."""
    hidden, latent = sizes["hidden_size"], sizes["moe_latent_size"]
    shared = sizes["n_shared_experts"] * sizes["moe_shared_expert_intermediate_size"]
    return (
        2 * hidden * sizes["n_routed_experts_total"]
        + 2 * 2 * hidden * latent
        + 2 * 2 * held_share(sizes) * latent * sizes["moe_intermediate_size"]
        + 2 * 2 * hidden * shared
    )


def flops_per_token(sizes, seq):
    """Model FLOPs of one training step per label position: forward plus
    backward (3 x forward), matmuls, attention and the scan's least, nothing
    recomputed, of this chip's share. Causal attention needs half the pairs
    of positions."""
    hidden = sizes["hidden_size"]
    heads, p = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
    inner = heads * p
    in_proj = 2 * inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"] + heads
    mamba_ = 2 * hidden * (in_proj + inner) + scan_flops_per_token(sizes)
    qo = sizes["num_attention_heads"] * sizes["head_dim"]
    kv = sizes["num_key_value_heads"] * sizes["head_dim"]
    attn = 2 * hidden * (2 * qo + 2 * kv) + 2 * 2 * qo * (seq + 1) / 2
    pattern = sizes["hybrid_override_pattern"]
    layers = (
        pattern.count("M") * mamba_ + pattern.count("*") * attn
        + pattern.count("E") * expert_layer_flops_per_token(sizes)
    )
    return 3.0 * (layers + 2 * hidden * sizes["vocab_rows_held"])


def kernel_costs(sizes, batch, seq):
    """Least work of one training step on one chip, by kernel.

    `ssm_scan`: as in the tower's file, at this share's 16 heads in 1 group.
    `latent_moe`: every `E` layer, forward and backward (3 x the forward's
    FLOPs: each product's transpose is two products of its size): the
    router, both latent projections, the held groups' matmuls at their
    rows (tokens * k * held / E: 176 a group at 4,096 tokens) and the
    shared expert. Bytes, in bf16, once in each of the three passes: every
    matrix of the layer (router, latent, held experts, shared expert) read
    or its gradient written, the token rows [tokens, D] read and written,
    the latent rows [tokens, L] written and read, and the dispatched rows
    [rows, L] read and the experts' rows written. The hidden [rows, I] and
    [tokens, Is] tensors are left out: a fused expert would never write
    them."""
    pattern = sizes["hybrid_override_pattern"]
    tokens = batch * seq
    hidden, latent = sizes["hidden_size"], sizes["moe_latent_size"]
    width = sizes["moe_intermediate_size"]
    shared = sizes["n_shared_experts"] * sizes["moe_shared_expert_intermediate_size"]
    rows = tokens * held_share(sizes)
    matrices = (
        hidden * sizes["n_routed_experts_total"] + 2 * hidden * latent
        + sizes["n_routed_experts"] * 2 * latent * width + 2 * hidden * shared
    )
    moved = 2 * tokens * hidden + 2 * tokens * latent + 2 * rows * latent
    costs = tower.kernel_costs(sizes, batch, seq)
    costs["latent_moe"] = {
        "flops": pattern.count("E") * tokens * 3
        * expert_layer_flops_per_token(sizes),
        "bytes": pattern.count("E") * 3 * 2 * (matrices + moved),
    }
    return costs
