"""The latent-expert hybrid (`benchmark/configs/nemotron-3-super-120b-a12b.py`):
experts in a latent space beside a whole shared expert, one Mamba-2 group of
16 heads through the scan kernels, and one chip's share of a layer's heads,
each against the plain float32 reference that lives with the configuration,
at toy size on the CPU with seeded weights, and the whole tiny tower through
`FFModel.compile -> fit`. Every tolerance states its reason."""

import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_nemotron_h as tower_tests
from test_nemotron_h import (
    BATCH, BENCH, F32, F32_LOSS, assert_trees_close, bench, rand,
)
from test_olmoe import weight_keys

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import forward as kernel_forward
from flexflow_tpu.kernels.moe import experts_forward, held_window_rows
from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.ops import (
    ExpertsAttrs,
    RingAttentionAttrs,
    StateSpaceAttrs,
)

CONFIG = os.path.join(BENCH, "configs", "nemotron-3-super-120b-a12b")
ref = bench.load_module(CONFIG + ".py")

# 4 state-space heads of 8 in ONE group (state 16, chunks of 8), 4 query
# heads over 1 key/value head of 8, 4 held of 16 relu2 experts of width 24
# (top-5) in a latent space of 12 beside a shared expert of 40 on the
# 32-wide row; five layers with every kind in them
TOY = dict(
    bench.load_json(CONFIG + ".json"),
    hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
    n_groups=1, chunk_size=8, num_attention_heads=4, num_key_value_heads=1,
    head_dim=8, moe_intermediate_size=24, moe_latent_size=12,
    moe_shared_expert_intermediate_size=40, n_routed_experts=4,
    n_routed_experts_total=16, held_experts_first=4, num_experts_per_tok=5,
    vocab_rows_held=96, num_hidden_layers=5, hybrid_override_pattern="MEM*E",
    # ten times the published deviation, as in the tower's tests
    initializer_range=0.2,
)
ADAM = TOY["training"]


# -- the experts in their latent space ------------------------------------------


def experts_attrs(held, sizes=TOY):
    return ExpertsAttrs(
        sizes["n_routed_experts_total"], sizes["num_experts_per_tok"],
        sizes["moe_intermediate_size"], activation=Activation.RELU2,
        capacity_factor=None, use_bias=False, renormalize=True,
        scoring="sigmoid", selection_bias=True,
        routed_scale=sizes["routed_scaling_factor"],
        shared_hidden_size=sizes["moe_shared_expert_intermediate_size"],
        held_experts=held, latent_size=sizes["moe_latent_size"],
    )


def experts_case(seed=4, tokens=48, sizes=TOY):
    """(tokens [n, D], the UNCUT layer's weights by reference name)."""
    rs = np.random.RandomState(seed)
    d, e = sizes["hidden_size"], sizes["n_routed_experts_total"]
    latent, width = sizes["moe_latent_size"], sizes["moe_intermediate_size"]
    shared = sizes["moe_shared_expert_intermediate_size"]
    named = {
        "e.weight0": rand(rs, d, e),
        "e.weight1": rand(rs, e, scale=0.2),  # a bias that moves the choice
        "e.weight2": rand(rs, d, latent, scale=0.3),
        "e.weight3": rand(rs, e, latent, width, scale=0.3),
        "e.weight4": rand(rs, e, width, latent, scale=0.3),
        "e.weight5": rand(rs, latent, d, scale=0.3),
        "e.weight6": rand(rs, d, shared, scale=0.3),
        "e.weight7": rand(rs, shared, d, scale=0.3),
    }
    return rand(rs, tokens, d), named


def share_of(named, first, count):
    """The weights one share holds, in the op's slot order."""
    ws = [named[f"e.weight{i}"] for i in range(8)]
    ws[3], ws[4] = ws[3][first:first + count], ws[4][first:first + count]
    return ws


def reference_experts(m, named, first, count, sizes=TOY):
    """The reference on the share's own weights (as the benchmark gives it
    the program's)."""
    sizes = dict(sizes, held_experts_first=first, n_routed_experts=count)
    held = {
        f"e.weight{i}": w for i, w in enumerate(share_of(named, first, count))
    }
    with jax.default_matmul_precision("highest"):
        return ref.experts(held, "e", m, sizes)


def test_latent_slots_shapes_and_roles():
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    attrs = experts_attrs((4, 4))
    shapes = attrs.weight_shapes(TensorShape((2, 6, 32), DataType.FLOAT))
    assert attrs.weight_roles() == [
        "router", "router", "latent", "expert", "expert", "latent",
        "shared", "shared",
    ]
    assert [s.dims for s in shapes] == [
        (32, 16), (16,), (32, 12), (4, 12, 24), (4, 24, 12), (12, 32),
        (32, 40), (40, 32),
    ]
    # without a latent size the op is the one it was
    import dataclasses

    plain = dataclasses.replace(attrs, latent_size=None)
    assert plain.weight_roles() == [
        "router", "router", "expert", "expert", "shared", "shared",
    ]


@pytest.mark.parametrize("held", [(0, 16), (4, 4), (12, 4)], ids=str)
def test_latent_experts_forward_and_gradients(held):
    """The whole layer (every expert held) and two shares of four, against
    the reference's dense masked experts on the latent rows: output, the
    input's gradient and every trainable slot's; the selection bias gets
    none. 1e-4 on the gradients as in the tower's test of the plain form."""
    m, named = experts_case()
    attrs = experts_attrs(held)
    cot = rand(np.random.RandomState(5), *m.shape)

    def system(m, named):
        return experts_forward(attrs, m, share_of(named, *held))[0]

    def reference(m, named):
        return reference_experts(m, named, *held)[0]

    np.testing.assert_allclose(system(m, named), reference(m, named), **F32)
    got = jax.grad(lambda *a: jnp.sum(system(*a) * cot), (0, 1))(m, named)
    want = jax.grad(lambda *a: jnp.sum(reference(*a) * cot), (0, 1))(m, named)
    assert float(jnp.max(jnp.abs(got[1]["e.weight1"]))) == 0.0
    for name in ("e.weight2", "e.weight5"):  # both projections are reached
        assert float(jnp.max(jnp.abs(want[1][name]))) > 1e-3
    assert_trees_close(got, want, rtol=1e-4, atol=1e-4)


def test_an_expert_shard_gives_its_held_shares_part():
    """One expert-parallel shard inside a `shard_map` (`expert_shard`: the
    whole op's attrs, a slice of the expert tensors) computes what the op
    that holds that range computes."""
    m, named = experts_case(seed=7)
    shard = experts_forward(
        experts_attrs(None), m, share_of(named, 8, 4), expert_shard=(8, 4)
    )[0]
    held = experts_forward(experts_attrs((8, 4)), m, share_of(named, 8, 4))[0]
    np.testing.assert_allclose(shard, held, **F32)


def test_a_router_that_sends_everything_here_drops_nothing():
    """8 of 64 experts held and a selection bias that puts every one of
    them among a token's 22 choices: the share gets 8 rows a token where a
    uniform router sends 2.75, more than the window's quarter over that
    (`held_window_rows`, the one rule of every held share), so the loop over
    the later windows runs twice, and the result is still the reference's."""
    sizes = dict(TOY, n_routed_experts_total=64, num_experts_per_tok=22)
    m, named = experts_case(seed=8, tokens=256, sizes=sizes)
    named["e.weight1"] = jnp.where(jnp.arange(64) < 8, 4.0, 0.0)
    attrs = experts_attrs((0, 8), sizes)
    decisions, here = m.shape[0] * 22, m.shape[0] * 8
    assert held_window_rows(decisions, 8, 64) == 896
    assert held_window_rows(90112, 8, 512) == 1792  # the cell's (ISSUE 39)
    from flexflow_tpu.observability import routing

    with routing.collecting() as recorded:
        got = experts_forward(attrs, m, share_of(named, 0, 8))[0]
    (row,) = recorded
    assert int(jnp.sum(row[:8])) == here and int(row[-2]) == 3  # windows
    want = reference_experts(m, named, 0, 8, sizes)[0]
    np.testing.assert_allclose(got, want, **F32)


# -- the shares add up -----------------------------------------------------------


def _state_space_layer(rs, d, heads, p, groups, n, taps=4):
    """The UNCUT `M` layer's weights by reference name."""
    inner, conv = heads * p, heads * p + 2 * groups * n
    return {
        "m.weight0": rand(rs, d, 2 * inner + 2 * groups * n + heads, scale=0.3),
        "m.weight1": rand(rs, taps, conv, scale=0.3),
        "m.weight2": rand(rs, conv, scale=0.3),
        "m.weight3": jnp.asarray(
            np.log(np.expm1(rs.uniform(1e-3, 0.3, heads))), jnp.float32
        ),
        "m.weight4": jnp.asarray(np.log(rs.uniform(1.0, 16.0, heads)), jnp.float32),
        "m.weight5": rand(rs, heads),
        "m.weight6": rand(rs, inner),
        "m.weight7": rand(rs, inner, d, scale=0.3),
    }


def _group_share(w, g, heads, p, groups, n):
    """Group g's share of an `M` layer: its heads' columns of z, x and dt,
    its own B and C, the convolution's and the norm's entries for those, and
    its heads' rows of the output projection."""
    per, inner = heads // groups, heads * p
    x_cols = np.arange(g * per * p, (g + 1) * per * p)
    h_cols = np.arange(g * per, (g + 1) * per)
    bc = np.arange(g * n, (g + 1) * n)
    conv = np.concatenate([x_cols, inner + bc, inner + groups * n + bc])
    in_cols = np.concatenate(
        [x_cols, inner + conv, 2 * inner + 2 * groups * n + h_cols]
    )
    return [
        w["m.weight0"][:, in_cols], w["m.weight1"][:, conv],
        w["m.weight2"][conv], w["m.weight3"][h_cols], w["m.weight4"][h_cols],
        w["m.weight5"][h_cols], w["m.weight6"][x_cols],
        w["m.weight7"][x_cols],
    ]


def _head_share(flat, j, hidden, heads, kv, d):
    """Key/value head j's share of a `*` layer's flat weight: that head's Wk
    and Wv and its query heads' Wq and Wo."""
    cuts = np.cumsum([0, hidden * heads * d, hidden * kv * d,
                      hidden * kv * d, heads * d * hidden])
    flat = flat.reshape(-1)
    per = heads // kv
    wq = flat[cuts[0]:cuts[1]].reshape(hidden, heads, d)[:, j * per:(j + 1) * per]
    wk = flat[cuts[1]:cuts[2]].reshape(hidden, kv, d)[:, j:j + 1]
    wv = flat[cuts[2]:cuts[3]].reshape(hidden, kv, d)[:, j:j + 1]
    wo = flat[cuts[3]:cuts[4]].reshape(heads, d, hidden)[j * per:(j + 1) * per]
    return jnp.concatenate([t.reshape(-1) for t in (wq, wk, wv, wo)])[:, None]


def _shares_of_the_expert_layer():
    m, named = experts_case(seed=6)
    with jax.default_matmul_precision("highest"):
        shared = ref.tower.mm(
            "sh,hd->sd",
            ref.tower.relu2(ref.tower.mm("sd,dh->sh", m, named["e.weight6"])),
            named["e.weight7"],
        )
    # `W_up` is applied in every share; the shared expert is counted once
    parts = [
        experts_forward(experts_attrs((first, 4)), m, share_of(named, first, 4))[0]
        - shared
        for first in (0, 4, 8, 12)
    ]
    return parts, shared, reference_experts(m, named, 0, 16)[0]


def _shares_of_the_state_space_layer():
    d, heads, p, groups, n, seq = 32, 8, 8, 4, 16, 24
    sizes = dict(TOY, mamba_num_heads=heads, mamba_head_dim=p, n_groups=groups,
                 ssm_state_size=n)
    rs = np.random.RandomState(9)
    w, u = _state_space_layer(rs, d, heads, p, groups, n), rand(rs, 1, seq, d)
    share = StateSpaceAttrs(
        heads // groups, p, n, 1, sizes["conv_kernel"], sizes["chunk_size"],
        sizes["layer_norm_epsilon"],
    )
    parts = [
        kernel_forward(share, [u], _group_share(w, g, heads, p, groups, n))[0][0]
        for g in range(groups)
    ]
    with jax.default_matmul_precision("highest"):
        whole = ref.tower.mamba(w, "m", u[0], sizes)
    return parts, 0.0, whole


def _shares_of_the_attention_layer():
    hidden, heads, kv, d, seq = 32, 8, 2, 8, 24
    sizes = dict(TOY, num_attention_heads=heads, num_key_value_heads=kv, head_dim=d)
    rs = np.random.RandomState(10)
    flat = rand(rs, 2 * hidden * heads * d + 2 * hidden * kv * d, 1, scale=0.3)
    x = rand(rs, 1, seq, hidden)
    share = RingAttentionAttrs(
        hidden, heads // kv, kdim=d, vdim=d, causal=True, num_kv_heads=1
    )
    parts = [
        kernel_forward(
            share, [x, x, x], [_head_share(flat, j, hidden, heads, kv, d)]
        )[0][0]
        for j in range(kv)
    ]
    with jax.default_matmul_precision("highest"):
        whole = ref.tower.attention({"a.weight0": flat}, "a", x[0], sizes)
    return parts, 0.0, whole


@pytest.mark.parametrize(
    "shares",
    [_shares_of_the_expert_layer, _shares_of_the_state_space_layer,
     _shares_of_the_attention_layer],
    ids=["E_by_held_experts", "M_by_group", "attention_by_kv_head"],
)
def test_shares_add_up_to_the_uncut_layer(shares):
    """The guide's share test for each kind of layer the configuration
    divides: the parts that all shares give (an `E` layer's four shares of
    four experts, with `W_up` applied in every share and the shared expert
    counted once; an `M` layer's four groups; a `*` layer's two key/value
    heads with their query heads), each computed by the PROGRAM from slices
    of the whole layer's weights, add up to what the uncut reference gives.
    Every part is a strict part."""
    parts, once, whole = shares()
    for part in parts:
        assert float(jnp.max(jnp.abs(part))) > 1e-3
        assert float(jnp.max(jnp.abs(part + once - whole))) > 1e-3
    np.testing.assert_allclose(sum(parts) + once, whole, **F32)


# -- a group of 16 heads through the scan kernels -----------------------------------


def g16_operands(seq, dtype, seed=3):
    """(x, dt, a_log, B, C, D) of `selective_scan`: one row, 16 heads of 64
    in one group, state 128 (the cell's widths)."""
    heads, p, n = 16, 64, 128
    rs = np.random.RandomState(seed)
    return (
        rand(rs, 1, seq, heads, p).astype(dtype),
        jnp.asarray(rs.uniform(1e-3, 0.3, (1, seq, heads)), jnp.float32),
        jnp.asarray(np.log(rs.uniform(1.0, 16.0, heads)), jnp.float32),
        rand(rs, 1, seq, 1, n, scale=0.3).astype(dtype),
        rand(rs, 1, seq, 1, n, scale=0.3).astype(dtype),
        rand(rs, heads),
    )


def test_scan_route_gives_a_group_of_16_heads_the_kernels(monkeypatch, entered):
    from flexflow_tpu.kernels.ssm import scan_route

    assert scan_route(1, 16, 64, 1, 128, 128) == "xla"  # the CPU, no opt-in
    entered(context.described_tpu())
    assert scan_route(1, 16, 64, 1, 128, 128) == "ssd"
    assert scan_route(1, 128, 64, 8, 128, 128) == "ssd"  # the uncut mixer
    assert scan_route(1, 32, 64, 1, 128, 128) == "ssd"  # two column blocks (PR 68)


def test_a_group_of_16_heads_through_the_scan_kernels(monkeypatch):
    """Two chunks of 128 positions, so that the [1024, 128] state goes from
    one to the next: y and the six gradients of the kernels (interpreted)
    against the XLA form's, float32, within the bound the tower's tests give
    the two forms at kernel widths."""
    operands = g16_operands(256, jnp.float32)
    cot = rand(np.random.RandomState(4), *operands[0].shape)
    want = tower_tests.scan_value_and_gradients(operands, cot)
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    got = tower_tests.scan_value_and_gradients(operands, cot)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert np.max(np.abs(g - w)) <= 1e-4 * max(1.0, np.max(np.abs(w)))
    assert not np.array_equal(np.asarray(got[0]), np.asarray(want[0]))


# -- the whole tiny tower through FFModel --------------------------------------


def data(seq, seed=0):
    return ref.make_data(np.random.RandomState(seed), TOY, BATCH, seq)


def compiled_model(seq, compute_dtype=None, **config):
    builder, logits = ref.build(TOY, BATCH, seq)
    model = FFModel.from_computation_graph(
        builder, logits,
        FFConfig(batch_size=BATCH, seed=7, print_freq=0, **config),
    )
    model.compile(
        AdamOptimizer(
            alpha=ADAM["alpha"], beta1=ADAM["beta1"], beta2=ADAM["beta2"],
            epsilon=ADAM["epsilon"], weight_decay=ADAM["weight_decay"],
        ),
        ADAM["loss"], compute_dtype=compute_dtype,
    )
    return model


def test_fit_step_matches_reference_adam_step():
    """Loss before and after one `fit` step against the reference's own
    gradient and Adam step (1e-5: float32 rounding through two forward
    passes and the update); both latent projections moved, the selection
    bias did not."""
    seq = 32
    model = compiled_model(seq, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    moved = ("moe1.weight2", "moe4.weight5")
    assert named["moe1.weight2"].shape == (32, 12)
    assert named["moe1.weight5"].shape == (12, 32)
    before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    # on the host: the step donates the parameters it is given
    initial = {name: np.asarray(named[name]) for name in moved}
    system_loss = tower_tests.system_loss
    assert abs(system_loss(model, inputs, labels) - before) <= F32_LOSS
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(system_loss(model, inputs, labels) - after) <= F32_LOSS
    assert before - after > 100 * F32_LOSS  # the step did something
    stepped = bench.named_parameters(model.instance, model.params)
    for name in moved:
        assert np.max(np.abs(np.asarray(stepped[name]) - initial[name])) > 1e-4
    assert float(jnp.max(jnp.abs(stepped["moe1.weight1"]))) == 0.0


def test_data_parallel_template_shards_the_latent_op_and_trains():
    """The batch template on two devices through the searched backend: the
    experts op with its two latent slots is sharded with the rest (no node
    left serial), the loss is the one-device loss, and a step reduces it."""
    seq = 32
    inputs, labels = data(seq)
    one = compiled_model(seq, max_devices=1)
    two = compiled_model(
        seq, max_devices=2, search_budget=2,
        force_strategy_seed="dp2xtp1xsp1",
    )
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance

    assert isinstance(two.instance, DistributedTrainingInstance)
    assert two.search_provenance["serial_compute_nodes"] == []
    keys1, keys2 = weight_keys(one.instance), weight_keys(two.instance)
    assert set(keys1) == set(keys2)
    one.params = {
        keys1[name]: jnp.asarray(np.asarray(two.params[keys2[name]]))
        for name in keys1
    }
    system_loss = tower_tests.system_loss
    first = system_loss(two, inputs, labels)
    assert abs(first - system_loss(one, inputs, labels)) <= F32_LOSS
    two.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert system_loss(two, inputs, labels) < first - 0.01


def test_data_parallel_rule_matches_the_latent_form_only():
    from flexflow_tpu.substitutions.operator_pattern import (
        op_attrs_satisfy_pattern,
    )
    from flexflow_tpu.substitutions.rules import data_parallel_experts_rule

    latent = data_parallel_experts_rule(2, False, shared=True, latent=True)
    plain = data_parallel_experts_rule(2, False, shared=True)
    assert latent.name == "data_parallel_experts_nb_sh_lat_2"
    assert plain.name == "data_parallel_experts_nb_sh_2"

    def node_pattern(rule):
        graph = rule.pattern.graph
        (node,) = graph.topological_ordering()
        return graph.node_label(node), len(graph.inputs_of(node))

    (with_latent, slots), (without, plain_slots) = map(
        node_pattern, (latent, plain)
    )
    assert (slots, plain_slots) == (1 + 8, 1 + 6)
    assert op_attrs_satisfy_pattern(experts_attrs((4, 4)), with_latent)
    assert not op_attrs_satisfy_pattern(
        tower_tests.experts_attrs((4, 4)), with_latent
    )
    assert op_attrs_satisfy_pattern(tower_tests.experts_attrs((4, 4)), without)


# -- the toy steps' programs, pinned ----------------------------------------------

# sha256 of the toy steps' StableHLO, lowered for the TPU platform: the text
# holds no Mosaic kernel at these widths, so no source location either. PR 39
# pinned the two older ones to show that `latent_size=None` is the op it was;
# PR 40 (the router's pick and the histograms by comparison, the picked
# weights behind a barrier where they are renormalised) changed all three
# programs and wrote its own; PR 41 (the state-space node's convolution with
# SiLU and its gated norm, each with a written backward) changed the two
# that hold such a node and left `olmoe` as it was; PR 59 (`conv_silu` takes
# the projection's row and the convolution's first column, and at these toy
# widths slices its columns out itself before its `custom_vjp`) swapped two
# neighbouring slices of that row in the same two, x B C's and dt's, and with
# them the two pads that carry their cotangents back: the same operations on
# the same operands, nothing more kept. A PR that means to change them does
# too.
PINNED_TOY_STEPS = {
    "olmoe": "ba9e83a700b28d5db0dcecc91e1189c8156340b2c7c11118e46c7dd09e8c7c49",
    "twotower": "4e05c8a14fe554ef83d2c703670ec5a3ed8e7b0b2a5152bb3af8a446cc45a1f9",
    "super": "39af48dc57d2c45d8f83bad7b27661898bb8b84a34f6570185ef7f445520ae3f",
}


def _toy_model(which):
    if which == "olmoe":
        import test_olmoe

        return test_olmoe.compiled_model(jnp.bfloat16, max_devices=1)
    if which == "twotower":
        return tower_tests.compiled_model(32, jnp.bfloat16, max_devices=1)
    return compiled_model(32, jnp.bfloat16, max_devices=1)


@pytest.mark.parametrize("which", sorted(PINNED_TOY_STEPS))
def test_toy_steps_lower_to_the_pinned_text(which):
    text = tower_tests.traced_step(_toy_model(which)).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED_TOY_STEPS[which]


def test_step_moves_no_routing_value_an_element_at_a_time(top_k_jvp_refused):
    """The tower's test (`test_nemotron_h.py`) on the latent-expert step:
    of the `[N, 16]` scores nothing is fetched or sent back by index, the
    share's five bins are counted by comparison, `top_k` has no JVP; the
    element moves left are a window of the decisions."""
    decisions = (BATCH * 32 * TOY["num_experts_per_tok"],)
    traced = tower_tests.traced_step(_toy_model("super"))
    assert tower_tests.element_moves(traced) == {
        ("gather", decisions), ("scatter-add", decisions),
    }


# -- the benchmark's CPU rehearsal of the cell ---------------------------------


def test_rehearsal_cell_runs_correct_on_the_cpu_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(BENCH, "rehearsal-super.json"), "--workload",
         "rehearsal_super_s128_1chip", "--seed", "2147483659", "--seconds",
         "1", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], (result["checks"], result["losses"])
    assert result["device"]["platform"] == "cpu"
    # no device trace on the CPU mesh: the five trace readers return
    # nothing; the routing counter is the program's own and is there
    for name in ("latent_moe_ms", "latent_routed_ms", "latent_moe_roofline",
                 "ssm_g16_ms", "ssm_g16_scan_roofline"):
        assert name not in result["metrics"]
    share = result["metrics"]["latent_held_rows_pct"]["value"]
    assert 6.0 < share < 25.0  # 4 of 32 experts held: 12.5% if uniform


@pytest.mark.parametrize(
    "operands, correct", [("float8_e4m3fn", False), ("bfloat16", True)]
)
def test_the_harness_refuses_a_float8_reference(tmp_path, operands, correct):
    """`LOSS_TOLERANCE`'s control through the harness's own `correct`
    (`benchmark/precision_control.py`): against the reference with float8
    operands the cell is NOT correct, by check (b) alone (Adam's first step
    amplifies the gradient signs float8 flips; (a) does not tell float8
    from bf16), and with bf16 operands it is."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "precision_control.py"),
         "--operands", operands, "--manifest",
         os.path.join(BENCH, "rehearsal-super.json"), "--workload",
         "rehearsal_super_s128_1chip", "--seed", "3000000019", "--seconds",
         "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    result = json.loads(done.stdout.strip().splitlines()[-1])
    checks = result["checks"]
    assert result["correct"] is correct, (checks, result["losses"])
    assert checks["b_loss_after_step_matches_reference"] is correct
    assert checks["a_loss_matches_reference"]
