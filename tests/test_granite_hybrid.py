"""granite-4.0-h-micro (`benchmark/configs/granite-4.0-h-micro.py`): a
Mamba-2 mixer or a NoPE grouped-query layer beside a SwiGLU in every layer,
under four muP multipliers and a tied head, through the public builder and
`FFModel.compile -> fit` against the plain float32 reference that lives with
the configuration, at toy size on the CPU with seeded weights; the parameter
sum at the published sizes; the scan of a group wider than a program holds in
COLUMN BLOCKS (`kernels/ssm._column_blocks`) against `_scan_core`; and a
stated `softmax_scale` on every core `mha_core_route` names. Every tolerance
states its reason."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_nemotron_h import BENCH, F32_LOSS, bench, rand

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import flash_attention as fa
from flexflow_tpu.kernels import forward as kernel_forward
from flexflow_tpu.kernels import ssm
from flexflow_tpu.kernels.ops import mha_core_route
from flexflow_tpu.op_attrs.ops import (
    MultiHeadAttentionAttrs,
    RingAttentionAttrs,
)

CONFIG = os.path.join(BENCH, "configs", "granite-4.0-h-micro")
ref = bench.load_module(CONFIG + ".py")
PUBLISHED = bench.load_json(CONFIG + ".json")

# 4 scan heads of 8 in ONE group (state 16, chunks of 8), 4 query heads over
# 2 key/value heads of 8 at a scale that is NOT 8 ** -0.5, a SwiGLU of 48 on
# the 32-wide row, the stack M M A M, all four multipliers as published
TOY = dict(
    PUBLISHED,
    hidden_size=32, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
    mamba_chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=0.2, shared_intermediate_size=48,
    intermediate_size=48, vocab_rows_held=96, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    # ten times the published deviation: at toy width 0.02 leaves every
    # activation so small that a wrong term would hide inside a tolerance
    initializer_range=0.2,
)
BATCH, SEQ = 2, 32
ADAM = TOY["training"]
# float32 against float32 on the CPU: the two sides order their sums
# differently (chunked matrix products against a position-by-position
# recurrence, fused rows against per-head einsums), nothing else. The
# gradients of a mean over 64 positions are small numbers: relative to each
# tensor's largest entry, measured under 2e-5.
GRAD_REL = 2e-4


def compiled_model(sizes=TOY, compute_dtype=None):
    builder, logits = ref.build(sizes, BATCH, SEQ)
    model = FFModel.from_computation_graph(
        builder, logits,
        FFConfig(batch_size=BATCH, seed=7, print_freq=0, max_devices=1),
    )
    model.compile(
        AdamOptimizer(
            alpha=ADAM["alpha"], beta1=ADAM["beta1"], beta2=ADAM["beta2"],
            epsilon=ADAM["epsilon"], weight_decay=ADAM["weight_decay"],
        ),
        ADAM["loss"], compute_dtype=compute_dtype,
    )
    return model


def system_loss(model, inputs, labels):
    read = bench.make_loss_reader(model.instance)
    batch, label = bench.place_batch(model.instance, inputs, labels)
    return read(model.params, batch, label)


def reference_mean_loss(w, inputs, labels, sizes=TOY):
    rows = zip(jnp.asarray(inputs["input_ids"]), jnp.asarray(labels))
    return sum(ref.loss_sum(w, sizes, *row) for row in rows) / labels.size


@pytest.fixture(scope="module")
def step():
    """The toy stack's loss, gradients and stepped parameters, the system's
    and the reference's, computed once."""
    model = compiled_model()
    inputs, labels = ref.make_data(np.random.RandomState(0), TOY, BATCH, SEQ)
    # copies: `fit` donates the parameters' buffers to the step
    named = {
        k: jnp.array(v, copy=True)
        for k, v in bench.named_parameters(model.instance, model.params).items()
    }
    batch, label = bench.place_batch(model.instance, inputs, labels)
    grads = jax.grad(
        lambda p: model.instance.loss_fn(p, batch, label)[0]
    )(model.params)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(reference_mean_loss)(
            named, inputs, labels
        )
        before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    assert abs(float(ref_loss) - before) < 1e-6
    grads = bench.named_parameters(model.instance, grads)
    found = {"before": system_loss(model, inputs, labels)}
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    found["after"] = system_loss(model, inputs, labels)
    stepped = bench.named_parameters(model.instance, model.params)
    alpha_t = ADAM["alpha"] * np.sqrt(1 - ADAM["beta2"]) / (1 - ADAM["beta1"])

    def adam(w, g):
        g = g + ADAM["weight_decay"] * w
        m, v = (1 - ADAM["beta1"]) * g, (1 - ADAM["beta2"]) * g * g
        return w - alpha_t * m / (jnp.sqrt(v) + ADAM["epsilon"])

    return dict(
        found, ref_before=before, ref_after=after,
        grads=grads,
        ref_grads=ref_grads, named=named, stepped=stepped,
        ref_stepped={k: adam(named[k], ref_grads[k]) for k in named},
    )


def toy_weight_names():
    names = ["embed.weight0", "norm_f.weight0"]
    for kind, norm_a, mixer, norm_b, ffn in ref.layer_names(TOY):
        names += [f"{norm_a}.weight0", f"{norm_b}.weight0"]
        names += [f"{ffn}_w{j}.weight0" for j in (1, 3, 2)]
        slots = 8 if kind == "mamba" else 1
        names += [f"{mixer}.weight{j}" for j in range(slots)]
    return names


def test_loss_before_and_after_adams_first_step_match_the_reference(step):
    """1e-5 is float32 rounding through two forward passes and the update;
    the step moves the loss twenty times that (2.0e-4: under the multipliers
    a sign step of 3e-4 moves little, at the published sizes 0.029 too; the
    stepped weights themselves are held one by one below)."""
    assert abs(step["before"] - step["ref_before"]) <= F32_LOSS
    assert abs(step["after"] - step["ref_after"]) <= F32_LOSS
    assert step["ref_before"] - step["ref_after"] > 10 * F32_LOSS
    assert sorted(step["named"]) == sorted(toy_weight_names())


@pytest.mark.parametrize("name", toy_weight_names())
def test_every_gradient_matches_the_reference(step, name):
    """The tied matrix's gradient is the sum of its two readers'
    (embedding rows times 12, the head's columns divided by 8); a mixer's
    carries the 0.22, the attention node's the stated scale."""
    got, want = np.asarray(step["grads"][name]), np.asarray(step["ref_grads"][name])
    assert got.shape == want.shape and np.max(np.abs(want)) > 0
    assert np.max(np.abs(got - want)) <= GRAD_REL * np.max(np.abs(want))


@pytest.mark.parametrize("name", toy_weight_names())
def test_adams_first_step_matches_the_reference(step, name):
    """A first step is a sign step of alpha on every weight whose gradient
    is not tiny (m / sqrt(v) is the gradient's sign there): where the two
    gradients agree to 2e-4 the stepped weights agree to a few percent of ONE
    step, except where a gradient is so near zero that epsilon and the L2
    term decide: 5% of a step on average."""
    got, want = np.asarray(step["stepped"][name]), np.asarray(step["ref_stepped"][name])
    one_step = ADAM["alpha"]
    moved = np.abs(np.asarray(step["named"][name]) - want)
    assert np.max(moved) > 0.5 * one_step
    assert np.mean(np.abs(got - want)) <= 0.05 * one_step


def test_multipliers_and_the_scale_are_in_the_graph_not_in_the_weights():
    """Each multiplier moves the loss: a build without it differs."""
    inputs, labels = ref.make_data(np.random.RandomState(0), TOY, BATCH, SEQ)
    base = system_loss(compiled_model(), inputs, labels)
    for key, other in (
        ("embedding_multiplier", 1), ("residual_multiplier", 1.0),
        ("logits_scaling", 1), ("attention_multiplier", 2.0),
    ):
        changed = system_loss(
            compiled_model(dict(TOY, **{key: other})), inputs, labels
        )
        assert abs(changed - base) > 10 * F32_LOSS, key


# -- the parameter sum at the published sizes ------------------------------------

PARAMETERS = {
    "mamba_mixer/in_proj": 17_432_576,
    "mamba_mixer/conv": 21_760,
    "mamba_mixer/dt_bias_A_log_D": 192,
    "mamba_mixer/norm_gain": 4_096,
    "mamba_mixer/out_proj": 8_388_608,
    "mamba_mixer/total": 25_847_232,
    "swiglu": 50_331_648,
    "attention": 10_485_760,
    "norms_a_layer": 4_096,
    "mamba_layer": 76_182_976,
    "attention_layer": 60_821_504,
    "layers": 746_468_288,
    "final_norm": 2_048,
    "tied_matrix": 25_690_112,
    "total": 772_160_448,
}


@pytest.mark.parametrize("term", list(PARAMETERS))
def test_parameter_sum_term_for_term_at_the_published_sizes(term):
    counts = ref.parameter_counts(PUBLISHED)
    for key in term.split("/"):
        counts = counts[key]
    assert counts == PARAMETERS[term]


def test_the_graph_at_the_published_sizes_holds_that_many_parameters():
    """Shapes only: building a graph allocates nothing."""
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    builder, _ = ref.build(PUBLISHED, 1, 4096)
    graph = builder.graph
    by_layer = {}
    for n in graph.topological_ordering():
        if isinstance(graph.op_attrs(n), WeightAttrs):
            (out,) = graph.outputs_of(n)
            name = graph.layer_attrs(n).name
            layer = name.split(".")[0]
            by_layer[layer] = by_layer.get(layer, 0) + int(
                np.prod(graph.tensor_shape(out).dims)
            )
    assert sum(by_layer.values()) == PARAMETERS["total"]
    assert by_layer["mamba0"] == PARAMETERS["mamba_mixer/total"]
    assert by_layer["attn5"] == PARAMETERS["attention"]
    assert by_layer["embed"] == PARAMETERS["tied_matrix"]
    assert "head" not in by_layer  # the tied head holds no matrix of its own
    assert sorted(k for k in by_layer if k.startswith("attn")) == ["attn5"]
    assert PUBLISHED["reduced"].keys() == {
        "num_hidden_layers", "layer_types", "vocab_rows_held"
    }


# -- the scan of a wide group, in column blocks ----------------------------------

SCAN_OUTPUTS = ("y", "dx", "ddt", "dA_log", "dB", "dC", "dD")


def wide_scan(seq=512, heads=32, p=64, groups=1, n=128, seed=3):
    rs = np.random.RandomState(seed)
    return (
        rand(rs, 1, seq, heads, p, scale=0.5),
        jnp.asarray(rs.uniform(1e-3, 0.3, (1, seq, heads)), jnp.float32),
        jnp.asarray(np.log(rs.uniform(1.0, 16.0, heads)), jnp.float32),
        rand(rs, 1, seq, groups, n, scale=0.3),
        rand(rs, 1, seq, groups, n, scale=0.3),
        rand(rs, heads),
    ), rand(rs, 1, seq, heads, p)


def scan_and_gradients(scan, operands, cot):
    def loss(*operands):
        y = scan(*operands)
        return jnp.sum(y * cot), y

    (_, y), grads = jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True
    )(*operands)
    return dict(zip(SCAN_OUTPUTS, [y, *grads]))


@pytest.fixture(scope="module")
def column_block_scan():
    """One group of 64 heads of 64 (the published 4,096 columns) at chunks
    of 256: four column blocks of 1,024, through the kernels in interpret
    mode, against `_scan_core` with the skip added."""
    chunk = 256
    operands, cot = wide_scan(heads=64)
    assert ssm._column_blocks(64 * 64) == 4

    def plain(x, dt, a_log, b_mat, c_mat, d_skip):
        y = ssm._scan_core(x, dt, a_log, b_mat, c_mat, chunk)
        return y + d_skip[:, None] * x

    want = scan_and_gradients(plain, operands, cot)
    os.environ["FLEXFLOW_TPU_FLASH_INTERPRET"] = "1"
    try:
        assert ssm.scan_route(1, 64, 64, 1, 128, chunk) == "ssd"
        got = scan_and_gradients(
            lambda *o: ssm.selective_scan(*o, chunk), operands, cot
        )
    finally:
        del os.environ["FLEXFLOW_TPU_FLASH_INTERPRET"]
    return got, want


@pytest.mark.parametrize("output", SCAN_OUTPUTS)
def test_column_block_scan_matches_the_xla_form(column_block_scan, output):
    """float32 on both sides: the order of the sums differs (dB and dC are
    summed over four blocks' partials, then over nothing; `_scan_core` sums
    over all 64 heads at once). Relative to each tensor's largest entry,
    measured at most 2e-5 (dA_log, a sum over every position)."""
    got, want = (np.asarray(t[output]) for t in column_block_scan)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= 1e-4 * np.max(np.abs(want))


def test_a_group_of_exactly_the_widest_program_is_one_program(monkeypatch):
    """1,024 columns, at any chunk, is the group whole: the program the two
    `nemotron_h` cells lower. One column more than a tile wider is cut."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert ssm._MAX_GROUP_COLUMNS == 1024
    assert ssm._column_blocks(1024) == 1
    for chunk in (128, 256):
        assert ssm.scan_column_blocks(1, 16, 64, 1, 128, chunk) == 1
    x = jax.ShapeDtypeStruct((1, 512, 1024), jnp.float32)
    bc = jax.ShapeDtypeStruct((1, 512, 128), jnp.float32)
    dt = jax.ShapeDtypeStruct((1, 512, 16), jnp.float32)
    whole = ssm._Blocks(x, bc, dt, 1, 256, reverse=False)
    assert (whole.programs, whole.blocks, whole.rp) == (1, 1, 1024)
    assert whole.bc is whole.bc_part  # dB and dC are written where B, C lie
    # the published group: 4,096 columns, 4 blocks of 1,024
    assert ssm._column_blocks(4096) == 4
    assert ssm.scan_column_blocks(1, 64, 64, 1, 128, 256) == 4
    assert ssm._column_blocks(1536) == 2  # 12 heads of 128: 768 + 768
    assert ssm._column_blocks(1408) == 11  # 11 tiles: no wider block divides
    monkeypatch.delenv("FLEXFLOW_TPU_FLASH_INTERPRET")
    assert ssm.scan_column_blocks(1, 64, 64, 1, 128, 256) == 0  # "xla"


def test_the_program_counter_keeps_the_blocks_of_every_scan_node(monkeypatch):
    """`observability/trace.scan_column_blocks()` after a lowering: the toy
    widths fall to `_scan_core` and say 0."""
    from flexflow_tpu.observability import trace

    model = compiled_model()
    inputs, labels = ref.make_data(np.random.RandomState(0), TOY, BATCH, SEQ)
    system_loss(model, inputs, labels)  # lowers the graph
    counted = trace.scan_column_blocks()
    for name in ("mamba0", "mamba1", "mamba3"):
        assert counted[f"ff.ssm.{name}"] == 0
    routes = trace.attention_routes()
    assert routes["ff.ring_attention.attn2"].endswith(" scale=0.2")


# -- a stated scale for the scores ------------------------------------------------

SCALE = 0.05  # neither 64 ** -0.5 nor 128 ** -0.5 nor 96 ** -0.5


def plain_attention(attrs, x, weight, causal, scale):
    """softmax(q k^T * scale) v through the node's own projections, dense,
    float32: the reference every route is held to."""
    from flexflow_tpu.kernels.ops import mha_project_qkv_bshf

    h, kv = attrs.num_heads, attrs.kv_heads
    kd, vd = attrs.q_proj_size, attrs.v_proj_size
    qp, kp, vp, wo = mha_project_qkv_bshf(attrs, x, x, x, weight, None)
    b, s, _ = x.shape
    q = qp.reshape(b, s, h, kd)
    k = jnp.repeat(kp.reshape(b, s, kv, kd), h // kv, axis=2)
    v = jnp.repeat(vp.reshape(b, s, kv, vd), h // kv, axis=2)
    scores = jnp.einsum("bshk,bthk->bhst", q, k) * scale
    if causal:
        keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        scores = jnp.where(keep, scores, -jnp.inf)
    ctx = jnp.einsum("bhst,bthv->bshv", jax.nn.softmax(scores, axis=-1), v)
    return ctx.reshape(b, s, h * vd) @ wo


# (attrs' fields, causal, batch, positions, the route the node must take)
ROUTE_CASES = {
    "head_pairs_on_one_fused_row": (
        dict(embed_dim=256, num_heads=4), False, 2, 128, "fused_row_qkv"),
    "lane_heads_one_tile": (
        dict(embed_dim=256, num_heads=2), False, 2, 256, "fused_row"),
    "lane_heads_causal_tile_schedule": (
        dict(embed_dim=256, num_heads=2), True, 1, 1024, "fused_row"),
    "grouped_heads_of_64_padded_to_lanes": (
        dict(embed_dim=256, num_heads=4, num_kv_heads=2, kdim=64, vdim=64),
        True, 1, 1024, "fused_row"),
    "other_heads_on_rows": (
        dict(embed_dim=192, num_heads=2), False, 2, 256, "rows"),
    "dense_below_the_least_length": (
        dict(embed_dim=256, num_heads=4), True, 2, 64, "dense"),
}


def node_value_and_gradients(attrs, x, weight):
    """The node's value and both gradients as a one-device `flash_mesh` in
    interpret mode lowers them: the way a CPU trace takes the attention
    kernels (a bare call asks for a TPU)."""
    from jax.sharding import Mesh

    def loss(x, weight):
        (y,) = kernel_forward(attrs, [x, x, x], [weight])
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    def run(x, weight):
        (_, y), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True
        )(x, weight)
        return [y, *grads]

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with mesh, context.flash_mesh(mesh, "data", None, True):
        return jax.jit(run)(x, weight)


def route_of(attrs, shape):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with context.flash_mesh(mesh, "data", None, True):
        return mha_core_route(attrs, shape, shape, shape, True)


@pytest.fixture
def kernels_on_the_cpu(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", "128")


@pytest.mark.parametrize("case", list(ROUTE_CASES))
def test_softmax_scale_on_every_route(kernels_on_the_cpu, case):
    """The node at a stated scale against the dense float32 reference at
    that scale, value and both gradients: 2e-5 of the largest entry is
    float32 rounding between a blockwise and a whole softmax (a wrong scale
    is off by tens of percent). And None is today's node: the same bits as
    the node's own kdim ** -0.5 stated (a power of two on these heads, so
    the dense form's division by sqrt(kdim) and a product agree too)."""
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    fields, causal, b, s, route = ROUTE_CASES[case]
    cls = RingAttentionAttrs if causal else MultiHeadAttentionAttrs
    extra = dict(causal=True) if causal else {}
    attrs = cls(**fields, softmax_scale=SCALE, **extra)
    shape = (b, s, attrs.embed_dim)
    assert route_of(attrs, shape) == route
    rs = np.random.RandomState(5)
    x = rand(rs, *shape)
    in_shape = TensorShape(shape, DataType.FLOAT)
    w_shape = attrs.weights_shape(in_shape, in_shape, in_shape)
    weight = rand(rs, *w_shape.dims, scale=attrs.embed_dim ** -0.5)
    got = node_value_and_gradients(attrs, x, weight)

    def reference(x, weight):
        y = plain_attention(attrs, x, weight, causal, SCALE)
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), y

    with jax.default_matmul_precision("highest"):
        (_, y), grads = jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True
        )(x, weight)
    for g, w in zip(got, [y, *grads]):
        g, w = np.asarray(g), np.asarray(w)
        assert np.max(np.abs(g - w)) <= 2e-5 * np.max(np.abs(w)) + 1e-6
    if attrs.q_proj_size in (64, 128):
        default = dataclasses.replace(attrs, softmax_scale=None)
        stated = dataclasses.replace(
            attrs, softmax_scale=attrs.q_proj_size ** -0.5
        )
        assert default.scale == stated.scale
        for d, t in zip(
            node_value_and_gradients(default, x, weight),
            node_value_and_gradients(stated, x, weight),
        ):
            assert np.array_equal(np.asarray(d), np.asarray(t))
        assert not np.array_equal(np.asarray(d), np.asarray(got[-1]))


def dense_reference(q, k, v, causal, scale):
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
    if causal:
        s = q.shape[2]
        scores = jnp.where(
            jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], scores, -jnp.inf
        )
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(scores, axis=-1), v)


# the wrappers a route reaches only at other lengths or blocks, called as
# the entries call them: (entry, heads, head size, positions, blocks, causal)
ENTRY_CASES = {
    "bshf_lane_two_tiles_one_pass_backward": ("bshf", 2, 128, 256, 128, False),
    "bshf_lane_four_tiles_dq_and_dkv": ("bshf", 2, 128, 512, 128, False),
    "bshf_pairs_distinct_operands": ("bshf", 4, 64, 128, None, True),
    "rows_tiles_dq_and_dkv": ("rows", 2, 96, 256, 128, True),
    "rows_one_tile_fused_backward": ("rows", 2, 96, 128, None, False),
}


@pytest.mark.parametrize("case", list(ENTRY_CASES))
def test_softmax_scale_in_every_wrapper(case):
    """Each kernel wrapper that computed d ** -0.5 takes the stated scale,
    forward and backward (interpret mode), against the dense reference."""
    entry, h, d, s, block, causal = ENTRY_CASES[case]
    rs = np.random.RandomState(6)
    q, k, v, cot = (rand(rs, 2, h, s, d) for _ in range(4))
    blocks = {} if block is None else dict(block_q=block, block_k=block)

    def rows(t):  # [b, h, s, d] -> [b, s, h * d]
        return jnp.swapaxes(t, 1, 2).reshape(2, s, h * d)

    def kernel(q, k, v):
        if entry == "rows":
            return fa.flash_attention(
                q, k, v, causal=causal, interpret=True, scale=SCALE, **blocks
            )
        o = fa.flash_attention_bshf(
            rows(q), rows(k), rows(v), h, causal=causal, interpret=True,
            scale=SCALE, **blocks
        )
        return jnp.swapaxes(o.reshape(2, s, h, d), 1, 2)

    got = jax.value_and_grad(
        lambda *a: jnp.sum(kernel(*a) * cot), argnums=(0, 1, 2)
    )(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(
            lambda *a: jnp.sum(dense_reference(*a, causal, SCALE) * cot),
            argnums=(0, 1, 2),
        )(q, k, v)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert np.max(np.abs(g - w)) <= 2e-5 * np.max(np.abs(w)) + 1e-6


@pytest.mark.parametrize("kind", ["latent", "differential", "not_positive"])
def test_softmax_scale_is_refused_where_it_is_not_carried(kind):
    fields = {
        "latent": dict(
            embed_dim=64, num_heads=2, kdim=24, vdim=16, kv_latent_rank=8,
            shared_key_dim=8,
        ),
        "differential": dict(
            embed_dim=64, num_heads=4, kdim=16, vdim=16, num_kv_heads=2,
            differential=True,
        ),
        "not_positive": dict(embed_dim=64, num_heads=2),
    }[kind]
    MultiHeadAttentionAttrs(**fields)  # without a scale the node is there
    scale = 0.0 if kind == "not_positive" else SCALE
    with pytest.raises(AssertionError, match="softmax_scale"):
        MultiHeadAttentionAttrs(**fields, softmax_scale=scale)


def test_a_sequence_shard_refuses_a_stated_scale():
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.parallel_tensor_shape import (
        lift_to_parallel_with_degrees,
    )
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    attrs = RingAttentionAttrs(64, 2, softmax_scale=SCALE, causal=True)
    shape = TensorShape((2, 64, 64), DataType.FLOAT)
    whole = lift_to_parallel_with_degrees(shape, 1, 1, (1, 1, 1))
    attrs.parallel_output_shape(whole, whole, whole)
    shard = lift_to_parallel_with_degrees(shape, 1, 1, (1, 2, 1))
    with pytest.raises(AssertionError, match="softmax_scale"):
        attrs.parallel_output_shape(shard, shard, shard)


# -- the benchmark's CPU rehearsal of the cell ---------------------------------


def test_rehearsal_cell_runs_correct_on_the_cpu_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(BENCH, "rehearsal-granite.json"), "--workload",
         "rehearsal_granite_s256_1chip", "--seed", "2147483659", "--seconds",
         "1", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], (result["checks"], result["losses"])
    assert result["device"]["platform"] == "cpu"
    # no device trace on the CPU mesh: the three trace readers return
    # nothing; the counter is the program's own and is there: the toy's two
    # scan nodes run a 2,048-column group as 2 blocks (interpret mode)
    for name in ("granite_ssm_ms", "granite_ssm_scan_roofline",
                 "granite_swiglu_ms"):
        assert name not in result["metrics"]
    assert result["metrics"]["granite_scan_column_blocks"]["value"] == 2
