"""Mellum2-12B-A2.5B (`benchmark/configs/mellum2-12b-a2.5b.py`): plain
grouped-query attention under a sliding window on three layers of four, a
YaRN rotary on the fourth alone, a held share of renormalised softmax
experts, through the public builder and `FFModel.compile -> fit`, each part
against the plain float32 reference that lives with the configuration, at toy
size on the CPU with seeded weights. Every tolerance states its reason."""

import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_nemotron_h import (
    BENCH, F32, F32_LOSS, assert_trees_close, bench, rand,
)

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import flash_attention as flash
from flexflow_tpu.kernels import forward as kernel_forward
from flexflow_tpu.kernels import ops
from flexflow_tpu.kernels.moe import experts_forward
from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.core import get_parallel_output_shapes
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    ExpertsAttrs,
    MultiHeadAttentionAttrs,
    RingAttentionAttrs,
    WeightAttrs,
    YarnScaling,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape

CONFIG = os.path.join(BENCH, "configs", "mellum2-12b-a2.5b")
ref = bench.load_module(CONFIG + ".py")
PUBLISHED = bench.load_json(CONFIG + ".json")

# 4 query heads over 2 key/value heads of 8 (a ramp from pair 1 to pair 3 of
# the 4: the published rotary numbers at this width), a window of 10 keys, 4
# held of 16 SwiGLU experts of width 24 (top 3), one whole period S S S F
TOY = dict(
    PUBLISHED,
    hidden_size=32, head_dim=8, num_attention_heads=4, num_key_value_heads=2,
    moe_intermediate_size=24, num_experts=4, num_experts_total=16,
    held_experts_first=4, num_experts_per_tok=3, vocab_rows_held=96,
    sliding_window=10,
    # ten times the published deviation, as in the other towers' tests: at
    # toy width 0.02 leaves every activation so small that a wrong term
    # would hide inside a tolerance
    initializer_range=0.2,
)
BATCH = 4
ADAM = TOY["training"]
YARN = PUBLISHED["rope_parameters"]["full_attention"]
PUBLISHED_YARN = YarnScaling(
    YARN["factor"], YARN["original_max_position_embeddings"],
    YARN["beta_fast"], YARN["beta_slow"], YARN["attention_factor"],
)

# gradients through the projections, the norms and a softmax in float32 on
# the CPU: sums of a few dozen products in another order. Measured 5e-6 here.
F32_GRADS = dict(rtol=1e-4, atol=1e-4)


# -- the parameters, term by term ------------------------------------------------


def test_parameter_count_term_by_term():
    counts = ref.parameter_counts(PUBLISHED)
    assert counts["layer"] == {
        "attention": 21_233_920, "router": 147_456, "experts": 99_090_432,
        "norms": 4_608,
    }
    assert 2 * 9_437_184 + 2 * 1_179_648 + 2 * 128 == 21_233_920
    assert 16 * 6_193_152 == 99_090_432
    assert sum(counts["layer"].values()) == 120_476_416
    assert counts["layers"] == 481_905_664
    assert counts["embedding"] == counts["head"] == 56_623_104
    assert counts["final_norm"] == 2_304
    assert counts["total"] == 595_154_176
    assert "595,154,176" in PUBLISHED["parameters"]["as_built"]
    # and the graph the builder makes at the published sizes holds as many
    builder, _ = ref.build(PUBLISHED, 1, 8192)
    graph = builder.graph
    built = sum(
        math.prod(graph.op_attrs(n).shape.dims)
        for n in graph.topological_ordering()
        if isinstance(graph.op_attrs(n), WeightAttrs)
    )
    assert built == 595_154_176


def test_the_file_states_the_cut_and_keeps_every_published_width():
    assert PUBLISHED["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert (PUBLISHED["num_experts"], PUBLISHED["num_experts_total"]) == (16, 64)
    assert (PUBLISHED["vocab_rows_held"], PUBLISHED["vocab_size"]) == (24576, 98304)
    assert set(PUBLISHED["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
        "vocab_rows_held",
    }
    for key, value in dict(
        hidden_size=2304, head_dim=128, num_attention_heads=32,
        num_key_value_heads=4, moe_intermediate_size=896,
        num_experts_per_tok=8, sliding_window=1024,
    ).items():
        assert PUBLISHED[key] == value
    assert "4 chips share each layer" in PUBLISHED["deployment"]


# -- the rotary's frequencies ----------------------------------------------------


def test_yarn_frequencies_follow_the_formula():
    """Low 18, high 35 of the 64 pairs at the published numbers; the pairs
    below `low` turn as they did, those from `high` on 16 times slower, a
    line between; bit for bit the default's product where the ramp is 0."""
    assert PUBLISHED_YARN.correction_range(500000.0, 128) == (18, 35)
    assert ref.yarn_range(YARN, 128) == (18, 35)
    plain, none = ops.rope_frequencies(128, 500000.0)
    assert none is None
    np.testing.assert_array_equal(
        plain, 500000.0 ** (-jnp.arange(64, dtype=jnp.float32) * 2.0 / 128)
    )
    scaled, amplitude = ops.rope_frequencies(128, 500000.0, PUBLISHED_YARN)
    assert amplitude == YARN["attention_factor"]
    assert abs(amplitude - (0.1 * math.log(16) + 1)) < 1e-12
    np.testing.assert_array_equal(scaled[:19], plain[:19])  # 17, and 18 itself
    np.testing.assert_allclose(scaled[35:], plain[35:] / 16, rtol=1e-6)
    j = np.arange(64)
    ramp = np.clip((j - 18) / (35 - 18), 0, 1)
    f = 500000.0 ** (-2.0 * j / 128)
    np.testing.assert_allclose(
        scaled, f * (1 - ramp) + f / 16 * ramp, rtol=2e-6
    )
    # the reference's own writing of the formula agrees
    want, ref_amplitude = ref.rope_frequencies(YARN, 128)
    np.testing.assert_allclose(scaled, want, rtol=1e-6)
    assert ref_amplitude == amplitude
    assert PUBLISHED_YARN.describe(500000.0, 128) == (
        "yarn factor=16 low=18 high=35 amp=1.2773"
    )


def test_yarn_at_factor_one_is_the_default_rotary():
    one = YarnScaling(1.0, 8192)
    assert one.amplitude == 1.0
    plain, _ = ops.rope_frequencies(128, 500000.0)
    scaled, _ = ops.rope_frequencies(128, 500000.0, one)
    np.testing.assert_allclose(scaled, plain, rtol=1e-6)
    x = rand(np.random.RandomState(0), 2, 40, 4 * 16)
    np.testing.assert_allclose(
        ops.rope_bshf(x, 4, 1e4, scaling=one), ops.rope_bshf(x, 4, 1e4),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.parametrize("rotary_dim", [None, 8])
def test_yarn_amplitude_is_on_the_query_and_on_the_key(rotary_dim):
    """`rope_bshf` under a scaling is the reference's rotary: the scaled
    angles, and cosine and sine both times the amplitude (so a node's scores
    carry its square). Also on a rotary narrower than the head."""
    scaling = YarnScaling(16.0, 512, 4.0, 1.0)
    rope = dict(rope_type="yarn", rope_theta=1e4, factor=16.0, beta_fast=4.0,
                beta_slow=1.0, original_max_position_embeddings=512)
    heads, d, s = 3, 16, 200
    x = rand(np.random.RandomState(1), 2, s, heads * d)
    got = ops.rope_bshf(x, heads, 1e4, rotary_dim, scaling)
    rows = jnp.transpose(x.reshape(2, s, heads, d), (0, 2, 1, 3))
    width = rotary_dim or d
    turned = jax.vmap(lambda r: ref.rotary(r[..., :width], rope))(rows)
    want = jnp.concatenate([turned, rows[..., width:]], axis=-1)
    np.testing.assert_allclose(
        got, jnp.transpose(want, (0, 2, 1, 3)).reshape(x.shape),
        rtol=1e-5, atol=1e-5,
    )
    low, high = scaling.correction_range(1e4, width)
    assert 0 < low < high < width // 2  # a real ramp at this toy width
    assert scaling.amplitude == pytest.approx(0.1 * math.log(16) + 1)


def test_a_scaling_needs_a_rotary_and_a_plain_node():
    with pytest.raises(AssertionError, match="needs one"):
        MultiHeadAttentionAttrs(32, 4, rope_scaling=PUBLISHED_YARN)
    with pytest.raises(AssertionError, match="latent attention takes none"):
        MultiHeadAttentionAttrs(
            32, 4, 12, 8, rope_theta=1e4, kv_latent_rank=4, shared_key_dim=4,
            rope_scaling=PUBLISHED_YARN,
        )


# -- the band on a plain grouped node --------------------------------------------


def dense_attention(q, k, v, heads, window, scale):
    """XLA's attention under the dense band mask; k and v hold `heads` heads
    or fewer (a head a group of query heads)."""
    b, s, _ = q.shape
    split = lambda t: t.reshape(b, s, -1, 128)  # noqa: E731
    q4, k4, v4 = split(q), split(k), split(v)
    group = heads // k4.shape[2]
    k4, v4 = (jnp.repeat(t, group, axis=2) for t in (k4, v4))
    scores = jnp.einsum("bshd,bthd->bhst", q4, k4) * scale
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    keep = (ahead >= 0) & (ahead < window)
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs, v4).reshape(b, s, -1)


@pytest.mark.parametrize("form", ["folded", "in_place"])
@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("window", [50, 128, 200, 512])
def test_band_on_grouped_heads_matches_the_dense_mask(
    window, group, form, monkeypatch
):
    """A window shorter than, equal to and longer than a tile of 128 and one
    of the whole length, groups of 1 and 8, forward and every gradient in
    interpret mode against XLA's attention under the dense band mask, for
    both forms of rows the plan can pick, each reading the keys and values
    in place for the group (`CausalPlan.group`): folded (rows that fit the
    default scope, the whole-row delta: this cell's) and long rows (the
    delta by tiles, the grouped names). Float32 sums in another order
    (measured 5e-6)."""
    rs = np.random.RandomState(7)
    kv, s = 1, 512
    heads = kv * group
    q, cot = (rand(rs, 1, s, heads * 128) for _ in range(2))
    k, v = (rand(rs, 1, s, kv * 128) for _ in range(2))
    if form == "in_place":  # every row is a long row
        monkeypatch.setattr(flash, "_SCOPED_ROWS_BUDGET", 0)
    plan = flash.causal_plan(1, s, heads, kv, 128, 128, 4, 128, 128, window)
    assert plan.group == group
    assert plan.fwd_name.endswith("_window") == (window < s)
    assert plan.fwd_name.startswith(
        "flash_fwd_causal_grouped" if form == "in_place"
        else "flash_fwd_causal_bshf"
    )
    assert (plan.delta_block is None) == (form == "folded")

    def kernel(q, k, v):
        return jnp.sum(cot * flash.flash_attention_bshf(
            q, k, v, heads, causal=True, block_q=128, block_k=128,
            interpret=True, window=window, num_kv_heads=kv,
        ))

    def dense(q, k, v):
        return jnp.sum(cot * dense_attention(q, k, v, heads, window, 128 ** -0.5))

    got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    assert_trees_close(got, want, rtol=2e-5, atol=5e-5)


def attention_attrs(kind, sizes=TOY, causal=True, **overrides):
    rope = sizes["rope_parameters"][kind]
    scaling = None
    if rope["rope_type"] == "yarn":
        scaling = YarnScaling(
            rope["factor"], rope["original_max_position_embeddings"],
            rope["beta_fast"], rope["beta_slow"], rope["attention_factor"],
        )
    fields = dict(
        embed_dim=sizes["hidden_size"], num_heads=sizes["num_attention_heads"],
        kdim=sizes["head_dim"], vdim=sizes["head_dim"],
        rope_theta=float(rope["rope_theta"]), rope_scaling=scaling,
        qk_norm_eps=sizes["rms_norm_eps"], qk_norm_per_head=True,
        num_kv_heads=sizes["num_key_value_heads"],
        window=sizes["sliding_window"] if kind == "sliding_attention" else None,
    )
    fields.update(overrides)
    if causal:
        return RingAttentionAttrs(**fields, causal=True)
    return MultiHeadAttentionAttrs(**fields)


def attention_case(seq=24, seed=3, sizes=TOY, batch=2):
    rs = np.random.RandomState(seed)
    hidden, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    kv, d = sizes["num_key_value_heads"], sizes["head_dim"]
    flat = 2 * hidden * heads * d + 2 * hidden * kv * d
    ws = [
        rand(rs, flat, 1, scale=0.3),
        1.0 + rand(rs, d, scale=0.3), 1.0 + rand(rs, d, scale=0.3),
    ]
    return rand(rs, batch, seq, hidden), ws


def reference_attention(kind, u, ws, sizes=TOY):
    w = {f"a.weight{i}": t for i, t in enumerate(ws)}
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda row: ref.attention(w, "a", row, sizes, kind))(u)


def program_attention(kind, u, ws, sizes=TOY):
    with jax.default_matmul_precision("highest"):
        return kernel_forward(attention_attrs(kind, sizes), [u, u, u], ws)[0]


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_node_matches_the_reference_on_the_dense_route(kind):
    """The node of each layer type (the band as a mask on XLA's attention,
    the YaRN rotary on the full one) against the reference's, forward and
    every gradient; and each differs from the other kind's, so neither the
    band nor the scaling is a no-op at this size."""
    u, ws = attention_case()
    got = program_attention(kind, u, ws)
    np.testing.assert_allclose(got, reference_attention(kind, u, ws), **F32)
    cot = rand(np.random.RandomState(4), *u.shape)

    def grads(fn):
        return jax.grad(lambda u, ws: jnp.sum(fn(kind, u, ws) * cot), (0, 1))(u, ws)

    assert_trees_close(
        grads(program_attention), grads(reference_attention), **F32_GRADS
    )
    other = dict(TOY, sliding_window=None) if kind == "sliding_attention" else dict(
        TOY, rope_parameters=dict(
            TOY["rope_parameters"],
            full_attention=TOY["rope_parameters"]["sliding_attention"],
        )
    )
    assert float(jnp.max(jnp.abs(got - program_attention(kind, u, ws, other)))) > 1e-3


def test_a_window_as_long_as_the_sequence_is_no_window():
    u, ws = attention_case()
    wide = dict(TOY, sliding_window=24)
    np.testing.assert_allclose(
        program_attention("sliding_attention", u, ws, wide),
        program_attention("sliding_attention", u, ws, dict(TOY, sliding_window=None)),
        rtol=1e-6, atol=1e-6,
    )


def test_windowed_node_on_the_banded_kernels_matches_the_reference(monkeypatch, entered):
    """The node as the cell runs it, in interpret mode: 8 query heads over 1
    key/value head of 128 (a group of 8) on two causal tiles under a 300-key
    window, the per-head norm and the rotary on the one key head as it lies
    (the kernels read it in place for the group), against
    the reference's masked softmax; forward and every gradient. The kernels
    take exp2 of scaled scores and fold row sums by lanes: 2e-4. The route
    says the window, the counter the tiles the band visits, and the rotary
    its kind."""
    from flexflow_tpu.observability import trace

    sizes = dict(
        TOY, hidden_size=64, head_dim=128, num_attention_heads=8,
        num_key_value_heads=1, sliding_window=300,
    )
    u, ws = attention_case(seq=1024, sizes=sizes, batch=1)
    u, cot = u * 0.5, rand(np.random.RandomState(4), 1, 1024, 64)
    kind = "sliding_attention"
    want = jax.value_and_grad(
        lambda u, ws: jnp.sum(reference_attention(kind, u, ws, sizes) * cot), (0, 1)
    )(u, ws)
    # the per-head norm and the rotary before the core are the cell's too:
    # ONE Pallas pass each way (`kernels/norm_rotary`), interpreted
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    entered(context.described_tpu())
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_Q", "512")
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_K", "512")
    monkeypatch.setattr(
        flash, "flash_attention_bshf",
        functools.partial(flash.flash_attention_bshf, interpret=True),
    )
    attrs = attention_attrs(kind, sizes)
    shape = (1, 1024, 64)
    assert ops.mha_core_route(attrs, shape, shape, shape, True) == "fused_row"

    with context.lowering_node("ff.ring_attention.attn0"):
        got = jax.value_and_grad(
            lambda u, ws: jnp.sum(program_attention(kind, u, ws, sizes) * cot),
            (0, 1),
        )(u, ws)
    assert_trees_close(got, want, rtol=2e-4, atol=2e-4)
    assert trace.attention_routes()["ff.ring_attention.attn0"] == (
        "fused_row window=300 group=8"
    )
    assert trace.window_tiles()["ff.ring_attention.attn0"] == (3, 3)
    assert trace.rotaries()["ff.ring_attention.attn0"] == "default theta=500000"


def test_window_node_hands_the_kernels_its_keys_and_values_as_they_lie(
    monkeypatch, entered,
):
    """The window node at the published head shape and window (8 query heads
    over 1 key/value head of 128, a group of 8 as the cell's 32 over 4; 1,024
    keys over 2,048 positions), forward and backward, in interpret mode: the
    causal kernels' k and v operands are the node's own `[b, s, kv * d]`
    rows, nothing in the program writes them out a query head, the route
    says the group, and the loss and every gradient are the repeated form's
    (the plan's group forced to 1: `mha_between` writes the rows out eight
    times and its transpose sums dk and dv; here the float32 sum over the
    group follows the kernel) to float32 sums in another order."""
    from test_step_scopes import pallas_eqns

    from flexflow_tpu.observability import trace

    sizes = dict(
        TOY, hidden_size=64, head_dim=128, num_attention_heads=8,
        num_key_value_heads=1, sliding_window=PUBLISHED["sliding_window"],
    )
    b, s, group, d = 1, 2048, 8, 128
    u, ws = attention_case(seq=s, sizes=sizes, batch=b)
    u, cot = u * 0.5, rand(np.random.RandomState(4), b, s, 64)
    kind = "sliding_attention"
    # (the norm and the rotary before the core: `kernels/norm_rotary`,
    # interpreted like the core)
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    entered(context.described_tpu())
    monkeypatch.setattr(
        flash, "flash_attention_bshf",
        functools.partial(flash.flash_attention_bshf, interpret=True),
    )

    def step(u, ws):
        return jax.value_and_grad(
            lambda u, ws: jnp.sum(program_attention(kind, u, ws, sizes) * cot),
            (0, 1),
        )(u, ws)

    with context.lowering_node("ff.ring_attention.attn0"):
        jaxpr = jax.make_jaxpr(step)(u, ws).jaxpr
        got = step(u, ws)
    assert trace.attention_routes()["ff.ring_attention.attn0"] == (
        "fused_row window=1024 group=8"
    )
    kernels = {
        eqn.params["name"]: [v.aval.shape for v in eqn.invars[1:3]]
        for eqn in pallas_eqns(jaxpr)
        if "delta" not in eqn.params["name"]
        and not eqn.params["name"].startswith("norm_rotary")
    }
    assert kernels == {
        name: [(b, s, d)] * 2 for name in (
            "flash_fwd_causal_bshf_window", "flash_bwd_causal_bshf_window"
        )
    }

    # the repeat's broadcast to [b, s, kv, group, d] is nowhere (the
    # backward's sum over the group reshapes dk and dv to that shape)
    repeat = f"f32[{b},{s},1,{group},{d}] = broadcast_in_dim"
    assert repeat not in str(jaxpr)
    plan_of = ops._causal_plan_of
    monkeypatch.setattr(
        ops, "_causal_plan_of",
        lambda *a, **k: dataclasses.replace(plan_of(*a, **k), group=1),
    )
    # (a new function: JAX keeps a function's trace by its identity)
    repeated = jax.make_jaxpr(lambda u, ws: step(u, ws))(u, ws).jaxpr
    assert repeat in str(repeated)
    assert_trees_close(got, step(u, ws), rtol=2e-5, atol=2e-5)


def test_a_windowed_node_never_takes_a_route_without_a_band(monkeypatch, entered):
    """At the published shape the windowed node takes the causal tile
    kernels; where an unwindowed node of the same shapes would take the
    head-pair, fused-qkv or per-head kernels (none has a band) the windowed
    one takes the mask on XLA's attention; a forced route raises."""
    entered(context.described_tpu())
    shape = (1, 8192, 2304)
    attrs = attention_attrs("sliding_attention", PUBLISHED)
    assert ops.mha_core_route(attrs, shape, shape, shape, True) == "fused_row"
    plan = ops._causal_plan_of(attrs, 8192)
    assert (plan.fwd_name, plan.group, plan.window) == (
        "flash_fwd_causal_bshf_window", 8, 1024
    )
    assert flash.causal_tile_schedule(8192, plan.block_q, plan.block_k, 1024)[0] == 45
    assert flash.causal_tile_schedule(8192, plan.block_q, plan.block_k)[0] == 136
    for fields, unwindowed in (
        (dict(embed_dim=640, num_heads=8), "rows"),  # heads of 80
        (dict(embed_dim=1024, num_heads=16), "fused_row_qkv"),  # heads of 64
    ):
        short = (2, 512, fields["embed_dim"])
        open_ = RingAttentionAttrs(**fields, causal=True)
        assert ops.mha_core_route(open_, short, short, short, True) == unwindowed
        banded = dataclasses.replace(open_, window=100)
        assert ops.mha_core_route(banded, short, short, short, True) == "dense"
        # and a window that hides nothing leaves the route alone
        whole = dataclasses.replace(open_, window=512)
        assert ops.mha_core_route(whole, short, short, short, True) == unwindowed
    u, ws = attention_case()
    attrs = attention_attrs("sliding_attention")
    monkeypatch.setattr(ops, "mha_core_route", lambda *a, **k: "rows")
    with pytest.raises(ValueError, match="has no band"):
        kernel_forward(attrs, [u, u, u], ws)
    monkeypatch.undo()
    with pytest.raises(ValueError, match="causal=False"):
        kernel_forward(
            attention_attrs("sliding_attention", causal=False), [u, u, u], ws
        )


def test_a_sequence_shard_of_a_windowed_node_is_refused_with_its_reason():
    x = TensorShape((4, 24, 32), DataType.FLOAT)
    sequence = lift_to_parallel_with_degrees(x, 1, 1, (1, 2, 1))
    batch = lift_to_parallel_with_degrees(x, 1, 1, (2, 1, 1))
    # no rotary, no norm and equal heads: nothing else refuses this shard
    banded = RingAttentionAttrs(32, 4, window=10, causal=True)
    with pytest.raises(AssertionError, match="halo"):
        get_parallel_output_shapes(banded, [sequence] * 3)
    (out,) = get_parallel_output_shapes(banded, [batch] * 3)
    assert out.shard_dim_at(0).degree == 2
    (out,) = get_parallel_output_shapes(
        dataclasses.replace(banded, window=None), [sequence] * 3
    )
    assert out.shard_dim_at(1).degree == 2


def test_window_and_scaling_round_trip_through_the_graph_file_format():
    """The new fields are part of a saved graph: three windows and the one
    nested `YarnScaling` come back as they were written."""
    from flexflow_tpu.pcg.file_format import (
        computation_graph_from_json,
        computation_graph_to_json,
    )

    builder, _ = ref.build(TOY, 2, 24)
    back = computation_graph_from_json(computation_graph_to_json(builder.graph))
    nodes = [
        back.op_attrs(n) for n in back.topological_ordering()
        if isinstance(back.op_attrs(n), RingAttentionAttrs)
    ]
    assert [a.window for a in nodes] == [10, 10, 10, None]
    assert [a.rope_scaling for a in nodes] == [None] * 3 + [PUBLISHED_YARN]
    assert nodes == [
        attention_attrs(kind) for kind in TOY["layer_types"]
    ]


# -- the held share of the experts -------------------------------------------------


def experts_attrs(held, sizes=TOY, factor=None):
    return ExpertsAttrs(
        sizes["num_experts_total"], sizes["num_experts_per_tok"],
        sizes["moe_intermediate_size"], activation=Activation.SILU,
        capacity_factor=None, use_bias=False, gated=True, renormalize=True,
        scoring="softmax", shared_hidden_size=0, held_experts=held,
        held_window_factor=factor,
    )


def test_held_window_factor_sizes_the_pass():
    """The cell's shard takes 65,536 decisions a node in passes of two and
    a quarter times the uniform share of 16 of 64 (the configuration's
    `held_window_factor`: 56.25% of the decisions, between the 50 and 62.5%
    that 4 and 5 held choices of 8 give a sequence whose tokens choose alike);
    without one the rule is a quarter over, as every other held cell has it.
    A factor belongs to a held share and is at least 1."""
    from flexflow_tpu.kernels.moe import held_window_rows

    decisions = 8192 * PUBLISHED["num_experts_per_tok"]
    assert PUBLISHED["held_window_factor"] == 2.25
    assert held_window_rows(decisions, 16, 64) == 20480
    assert held_window_rows(decisions, 16, 64, 2.25) == 36864
    assert 4 / 8 < 36864 / decisions < 5 / 8
    assert held_window_rows(decisions, 16, 64, 2.0) == 32768
    assert held_window_rows(decisions, 16, 64, 1.25) == 20480
    assert held_window_rows(decisions, 16, 64, 8.0) == decisions
    assert held_window_rows(288, 4, 16, 1.0) == 128  # one tile at least
    with pytest.raises(AssertionError, match="held_window_factor"):
        experts_attrs(None, factor=2.0)
    with pytest.raises(AssertionError, match="held_window_factor"):
        experts_attrs((0, 4), factor=0.5)


@pytest.mark.parametrize("factor", [1.0, 2.0, 4.0])
def test_held_window_factor_changes_no_value(factor):
    """The factor moves where a share's rows are cut into passes and nothing
    else: of 1,536 decisions 1,534 land on the 4 held of 16 experts (the
    gate's held columns raised), so the share takes four passes of 384 rows
    at factor 1, three of 512 at the default, two of 768 at 2 and one at 4;
    output and gradients equal the default's to float32 rounding (the
    passes' sums in another order)."""
    rs = np.random.RandomState(11)
    d, e, width = TOY["hidden_size"], 16, TOY["moe_intermediate_size"]
    gate = rand(rs, d, e)
    gate = gate.at[:, 4:8].add(jnp.abs(rand(rs, d, 1)))
    weights = [gate] + [
        rand(rs, 4, *shape, scale=0.1)
        for shape in ((d, width), (d, width), (width, d))
    ]
    x = jnp.abs(rand(rs, 1, 512, d))

    def run(f):
        def loss(x, weights):
            out = experts_forward(experts_attrs((4, 4), factor=f), x, weights)
            return jnp.mean(out[0] ** 2), out[0]

        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            x, weights
        )
        return out, grads

    with jax.default_matmul_precision("highest"):
        plain, given = run(None), run(factor)
    assert float(jnp.max(jnp.abs(plain[0]))) > 1e-3
    assert_trees_close(given, plain, **F32)


def test_shares_add_up_to_the_uncut_layer():
    """The model's own split in miniature, at 16 experts in 4 shares of 4 (64
    in 4 of 16 in the deployment): every share's part of a layer, attention
    and norms counted ONCE, adds up to the uncut reference layer over all 16
    experts (a windowed attention mixer under its norm, then the experts
    under theirs, each with its residual)."""
    rs = np.random.RandomState(5)
    d, e, width = TOY["hidden_size"], 16, TOY["moe_intermediate_size"]
    named = {
        "e.weight0": rand(rs, d, e),
        "e.weight1": rand(rs, e, d, width, scale=0.3),
        "e.weight2": rand(rs, e, d, width, scale=0.3),
        "e.weight3": rand(rs, e, width, d, scale=0.3),
        "na.weight0": 1.0 + rand(rs, d, scale=0.2),
        "nb.weight0": 1.0 + rand(rs, d, scale=0.2),
    }
    h, attn = attention_case(seed=6, batch=1)
    named.update({f"a.weight{i}": t for i, t in enumerate(attn)})
    h, eps = h[0], TOY["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        # once, on every chip alike: the mixer under its norm, the second norm
        h = h + ref.attention(
            named, "a", ref.rms(h, named["na.weight0"], eps), TOY,
            "sliding_attention",
        )
        m = ref.rms(h, named["nb.weight0"], eps)
        parts = [
            experts_forward(
                experts_attrs((first, 4)), m[None],
                [named["e.weight0"]]
                + [named[f"e.weight{i}"][first:first + 4] for i in (1, 2, 3)],
            )[0][0]
            for first in (0, 4, 8, 12)
        ]
        whole = h + ref.experts(named, "e", m, TOY, held=(0, 16))
    for part in parts:  # every share is a strict part of the layer
        assert float(jnp.max(jnp.abs(part))) > 1e-3
        assert float(jnp.max(jnp.abs(h + part - whole))) > 1e-3
    np.testing.assert_allclose(h + sum(parts), whole, **F32)


# -- the whole tiny model through FFModel -------------------------------------------


def data(seq, seed=0):
    return ref.make_data(np.random.RandomState(seed), TOY, BATCH, seq)


def compiled_model(seq, compute_dtype=None, sizes=TOY, **config):
    builder, logits = ref.build(sizes, BATCH, seq)
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


def system_loss(model, inputs, labels):
    read = bench.make_loss_reader(model.instance)
    batch, label = bench.place_batch(model.instance, inputs, labels)
    return read(model.params, batch, label)


def test_fit_step_matches_reference_adam_step():
    """The four-layer model's loss before and after one `fit` step against
    the reference's own gradient and Adam step: 1e-5 is float32 rounding
    through two forward passes and the update. The program names each
    attention node's route with its window and each node's rotary, and the
    routing counters report the held rows of the four expert nodes."""
    from flexflow_tpu.observability import routing, trace

    seq = 24
    model = compiled_model(seq, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    assert abs(system_loss(model, inputs, labels) - before) <= F32_LOSS
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(system_loss(model, inputs, labels) - after) <= F32_LOSS
    assert before - after > 100 * F32_LOSS  # the step did something
    counted = routing.published()
    assert counted["nodes"] == ["moe0", "moe1", "moe2", "moe3"]
    assert list(counted["decisions"]) == [BATCH * seq * 3] * 4  # one step
    assert 0.0 < counted["held_rows_pct"] < 100.0
    routes, rotaries = trace.attention_routes(), trace.rotaries()
    for i in (0, 1, 2):
        assert routes[f"ff.ring_attention.attn{i}"] == "dense window=10"
        assert rotaries[f"ff.ring_attention.attn{i}"] == "default theta=500000"
    assert routes["ff.ring_attention.attn3"] == "dense"
    assert rotaries["ff.ring_attention.attn3"] == (
        "yarn factor=16 low=1 high=3 amp=1.2773"
    )


@pytest.mark.parametrize("control", ["no_band", "default_rotary_everywhere"])
def test_reference_without_the_mechanism_is_outside_the_tolerance(control):
    """The float32 comparison above holds the mechanisms: a reference with
    the band left out, or with the default rotary on the full layer too, is
    off the system's loss by far more than float32 rounding."""
    seq = 24
    model = compiled_model(seq, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    rope = TOY["rope_parameters"]
    wrong = dict(TOY, sliding_window=seq) if control == "no_band" else dict(
        TOY, rope_parameters=dict(rope, full_attention=rope["sliding_attention"])
    )
    before, _ = ref.reference_losses(named, inputs, labels, wrong, ADAM)
    assert abs(system_loss(model, inputs, labels) - before) > 100 * F32_LOSS


def test_bf16_compute_is_inside_its_tolerance_and_outside_float32s():
    """The same graph at bf16 compute: inside 2e-2 (a mean over 96 positions
    averages little rounding away) and outside the float32 bound, so the
    float32 tests above would catch a bf16 path."""
    seq = 24
    model = compiled_model(seq, compute_dtype=jnp.bfloat16, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    before, _ = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    off = abs(system_loss(model, inputs, labels) - before)
    assert F32_LOSS < off < 2e-2, off


def test_flops_and_kernel_costs_by_hand():
    """The cell's arithmetic at the published sizes: the pairs inside the
    band, the cores at 7 products, the step's model FLOPs by part."""
    seq = 8192
    band = 1024 * seq - 1024 * 1023 // 2
    assert ref.live_pairs(PUBLISHED, seq, "sliding_attention") == band
    assert ref.live_pairs(PUBLISHED, seq, "full_attention") == seq * (seq + 1) // 2
    costs = ref.kernel_costs(PUBLISHED, 1, seq)
    assert costs["flash_window"]["flops"] == 3 * 7 * 2 * band * 32 * 128
    assert costs["flash"]["flops"] == 7 * 2 * (seq * (seq + 1) // 2) * 32 * 128
    q, kv = 2 * seq * 32 * 128, 2 * seq * 4 * 128
    assert costs["flash"]["bytes"] == 6 * q + 6 * kv
    assert costs["flash_window"]["bytes"] == 3 * (6 * q + 6 * kv)
    # 0.45 and 1.92 TFLOP a node, as the cell's `why` says
    assert round(costs["flash_window"]["flops"] / 3e12, 2) == 0.45
    assert round(costs["flash"]["flops"] / 1e12, 2) == 1.92
    per_token = ref.flops_per_token(PUBLISHED, seq)
    projections = 4 * 3 * 2 * 2304 * 128 * (2 * 32 + 2 * 4)
    cores = 3 * 2 * (3 * 2 * band + 2 * seq * (seq + 1) // 2) * 32 * 128 / seq
    experts = 4 * 3 * (2 * 2304 * 64 + 3 * 2 * 2304 * 896 * 2)
    head = 3 * 2 * 2304 * 24576
    assert per_token == pytest.approx(projections + cores + experts + head)
    assert ref.attention_names(PUBLISHED, "sliding_attention") == [
        "attn0", "attn1", "attn2"
    ]
    assert ref.attention_names(PUBLISHED, "full_attention") == ["attn3"]
