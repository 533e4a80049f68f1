"""The short-convolution / grouped-query / held-share expert tower
(`benchmark/configs/lfm2-24b-a2b.py`) through the public builder and
`FFModel.compile -> fit`, each part against the plain float32 reference that
lives with the configuration, at toy size on the CPU with seeded weights.
Every tolerance states its reason."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_nemotron_h import (
    BENCH, F32, F32_LOSS, assert_trees_close, bench, rand,
)

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import forward as kernel_forward
from flexflow_tpu.kernels.moe import experts_forward
from flexflow_tpu.kernels.short_conv import gated_short_conv
from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.core import (
    get_parallel_output_shapes,
    get_parallel_weight_shapes,
    get_weight_shapes,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    ExpertsAttrs,
    MultiHeadAttentionAttrs,
    RingAttentionAttrs,
    ShortConvAttrs,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape

CONFIG = os.path.join(BENCH, "configs", "lfm2-24b-a2b")
ref = bench.load_module(CONFIG + ".py")

# conv mixers 32 wide with the published 3 taps; 4 query heads over 2
# key/value heads of 8; 4 held of 16 SwiGLU experts of width 24 (top 3); the
# dense layer and one whole period A C C C
TOY = dict(
    bench.load_json(CONFIG + ".json"),
    hidden_size=32, conv_width=32, intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, moe_intermediate_size=24,
    num_experts=4, num_experts_total=16, held_experts_first=4,
    num_experts_per_tok=3, vocab_rows_held=96,
    # ten times the published deviation, as in the other towers' tests: at
    # toy width 0.02 leaves every activation so small that a wrong term
    # would hide inside a tolerance
    initializer_range=0.2,
)
BATCH = 4
ADAM = TOY["training"]

# gradients through two projections and the gates in float32 on the CPU:
# sums of a few dozen products in another order. Measured 4e-6 here.
F32_GRADS = dict(rtol=1e-4, atol=1e-4)


# -- the short-convolution op ----------------------------------------------------


def conv_case(seq=24, seed=1, sizes=TOY, batch=2):
    """(u [b, s, D], the op's weights in slot order), the taps drawn as
    wide as the gates so that a wrong shift would show."""
    rs = np.random.RandomState(seed)
    d, width = sizes["hidden_size"], sizes["conv_width"]
    ws = [
        rand(rs, d, 3 * width, scale=0.3),
        rand(rs, sizes["conv_L_cache"], width, scale=0.5),
        rand(rs, width, d, scale=0.3),
    ]
    return rand(rs, batch, seq, d), ws


def reference_conv(u, ws, sizes=TOY):
    w = {f"c.weight{i}": t for i, t in enumerate(ws)}
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda row: ref.short_conv(w, "c", row, sizes))(u)


def program_conv(u, ws, sizes=TOY):
    attrs = ShortConvAttrs(sizes["conv_width"], sizes["conv_L_cache"])
    with jax.default_matmul_precision("highest"):
        return kernel_forward(attrs, [u], ws)[0]


def test_short_conv_slots_shapes_and_the_refused_sequence_shard():
    attrs = ShortConvAttrs(width=32, conv_kernel=3)
    x = TensorShape((4, 24, 16), DataType.FLOAT)
    assert [w.dims for w in get_weight_shapes(attrs, [x])] == [
        (16, 96), (3, 32), (32, 16),
    ]
    batch = lift_to_parallel_with_degrees(x, 1, 1, (2, 1, 1))
    (out,) = get_parallel_output_shapes(attrs, [batch])
    assert out.shard_dim_at(0).degree == 2 and out.sum_degree == 1
    # the weights are whole on every batch shard
    for w in get_parallel_weight_shapes(attrs, [batch]):
        assert w.discard_copy_degree == 2
        assert all(w.shard_dim_at(i).degree == 1 for i in range(w.num_dims))
    # no halo is expressed: a sequence shard is refused, and says why
    sequence = lift_to_parallel_with_degrees(x, 1, 1, (1, 2, 1))
    with pytest.raises(AssertionError, match="halo"):
        get_parallel_output_shapes(attrs, [sequence])
    channel = lift_to_parallel_with_degrees(x, 1, 2, (1, 1, 1))
    with pytest.raises(AssertionError, match="channel-sharded"):
        get_parallel_output_shapes(attrs, [channel])


@pytest.mark.parametrize("seq", [24, 2])
def test_short_conv_matches_the_plain_chain(seq):
    """Shorter than the taps too: the zeros before position 0."""
    u, ws = conv_case(seq)
    np.testing.assert_allclose(program_conv(u, ws), reference_conv(u, ws), **F32)


def test_short_conv_written_backward_matches_the_chains_own_gradient():
    """The written backward (kept: the input and the weights; the row, the
    gates and the taps recomputed) against `jax.grad` of the plain chain."""
    u, ws = conv_case()
    cot = rand(np.random.RandomState(2), *u.shape)

    def grads(fn):
        return jax.grad(lambda u, ws: jnp.sum(fn(u, ws) * cot), (0, 1))(u, ws)

    assert_trees_close(grads(program_conv), grads(reference_conv), **F32_GRADS)


def test_short_conv_in_bf16_is_inside_a_stated_bound():
    """bf16 operands, float32 sums: each of the chain's five tensors is
    rounded once (2^-9 relative), through two matmuls of a few dozen terms.
    Output and gradients within 6% of their largest element (measured 2%),
    and outside float32's bound, so a float32 path would show."""
    u, ws = conv_case()
    cot = rand(np.random.RandomState(2), *u.shape)
    bf16 = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t.astype(jnp.bfloat16), tree
    )

    def run(fn, u, ws, cot):
        out, vjp = jax.vjp(fn, u, ws)
        return out, vjp(cot)

    got = run(lambda u, ws: gated_short_conv(u, *ws), *bf16((u, ws, cot)))
    want = run(reference_conv, u, ws, cot)
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == jnp.bfloat16
        off = float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)) / jnp.max(jnp.abs(w)))
        assert off < 0.06, off
        worst = max(worst, off)
    assert worst > 1e-4, worst


# -- per-head QK-norm and rotary on grouped-query heads --------------------------


def attention_attrs(sizes=TOY):
    heads = sizes["num_attention_heads"]
    d = sizes["hidden_size"] // heads
    return RingAttentionAttrs(
        embed_dim=sizes["hidden_size"], num_heads=heads, kdim=d, vdim=d,
        rope_theta=float(sizes["rope_parameters"]["rope_theta"]),
        qk_norm_eps=sizes["norm_eps"], qk_norm_per_head=True,
        num_kv_heads=sizes["num_key_value_heads"], causal=True,
    )


def attention_case(seq=24, seed=3, sizes=TOY):
    rs = np.random.RandomState(seed)
    hidden, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    kv, d = sizes["num_key_value_heads"], sizes["hidden_size"] // heads
    flat = 2 * hidden * heads * d + 2 * hidden * kv * d
    ws = [
        rand(rs, flat, 1, scale=0.3),
        1.0 + rand(rs, d, scale=0.3), 1.0 + rand(rs, d, scale=0.3),
    ]
    return rand(rs, 2, seq, hidden), ws


def reference_attention(u, ws, sizes=TOY):
    w = {f"a.weight{i}": t for i, t in enumerate(ws)}
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda row: ref.attention(w, "a", row, sizes))(u)


def program_attention(u, ws, sizes=TOY):
    with jax.default_matmul_precision("highest"):
        return kernel_forward(attention_attrs(sizes), [u, u, u], ws)[0]


def test_per_head_qk_norm_slots():
    attrs = attention_attrs()
    x = TensorShape((2, 24, 32), DataType.FLOAT)
    shapes = [w.dims for w in get_weight_shapes(attrs, [x, x, x])]
    # W_q | W_k | W_v | W_o in one column, then one head's gain for q and k
    assert shapes == [(2 * 32 * 32 + 2 * 32 * 16, 1), (8,), (8,)]
    # the whole-row form keeps its [h * d] gains
    whole = MultiHeadAttentionAttrs(embed_dim=32, num_heads=4, qk_norm_eps=1e-5)
    assert [w.dims for w in get_weight_shapes(whole, [x, x, x])][1:] == [
        (32,), (32,),
    ]


def test_whole_row_qk_norm_on_grouped_heads_is_refused_and_says_why():
    with pytest.raises(AssertionError, match="no row for a key/value head"):
        MultiHeadAttentionAttrs(
            embed_dim=32, num_heads=4, num_kv_heads=2, qk_norm_eps=1e-5
        )
    with pytest.raises(AssertionError, match="needs one"):
        MultiHeadAttentionAttrs(embed_dim=32, num_heads=4, qk_norm_per_head=True)


def test_grouped_heads_with_per_head_norm_and_rotary_match_the_reference():
    u, ws = attention_case()
    np.testing.assert_allclose(
        program_attention(u, ws), reference_attention(u, ws), **F32
    )
    cot = rand(np.random.RandomState(4), *u.shape)

    def grads(fn):
        return jax.grad(lambda u, ws: jnp.sum(fn(u, ws) * cot), (0, 1))(u, ws)

    assert_trees_close(
        grads(program_attention), grads(reference_attention), **F32_GRADS
    )


def test_padded_heads_route_is_read_from_shapes_and_backend(monkeypatch, entered):
    from flexflow_tpu.kernels import flash_attention as flash
    from flexflow_tpu.kernels.ops import mha_core_route, mha_pads_heads

    attrs = attention_attrs(bench.load_json(CONFIG + ".json"))
    shape = (2, 8192, 2048)
    assert mha_core_route(attrs, shape, shape, shape, True) == "dense"  # the CPU
    entered(context.described_tpu())
    # heads of 64 over 16 causal tiles: the d % 128 tile schedule, padded
    assert mha_pads_heads(attrs, 8192)
    assert mha_core_route(attrs, shape, shape, shape, True) == "fused_row"
    # one tile is the head-pair kernels', unpadded, as it always was
    short = (2, 512, 2048)
    assert not mha_pads_heads(attrs, 512)
    assert mha_core_route(attrs, short, short, short, True) == "fused_row"
    # ... up to 1,024 positions, which would be TWO causal tiles of 512 on
    # padded heads: no plan is asked there, so nobody reads its group and the
    # pair kernels get their keys and values repeated a query head
    from flexflow_tpu.kernels.ops import _causal_plan_of

    assert _causal_plan_of(attrs, 1024) is None
    assert _causal_plan_of(attrs, 8192).group == 4
    # and without the mask nothing is padded: the per-head kernels' route
    open_ = MultiHeadAttentionAttrs(embed_dim=2048, num_heads=32)
    assert not mha_pads_heads(open_, 8192)
    assert mha_core_route(open_, shape, shape, shape, True) == "rows"


def test_padded_heads_on_the_causal_tile_kernels_match_the_reference(monkeypatch, entered):
    """The node as the cell runs it, in interpret mode: 4 query heads over 2
    key/value heads of 64 on two causal tiles, each head padded to 128
    lanes for `flash_attention_bshf`'s causal tile schedule, against the
    reference's masked softmax; forward and every gradient. The kernels take
    exp2 of scaled scores and fold row sums by lanes: 2e-4."""
    import functools

    from flexflow_tpu.kernels import flash_attention as flash

    sizes = dict(TOY, hidden_size=256)
    u, ws = attention_case(seq=1024, sizes=sizes)
    u, cot = u[:1] * 0.5, rand(np.random.RandomState(4), 1, 1024, 256)
    want = jax.value_and_grad(
        lambda u, ws: jnp.sum(reference_attention(u, ws, sizes) * cot), (0, 1)
    )(u, ws)
    # the per-head norm and the rotary before the core are the cell's too:
    # two heads of 64 a lane tile through `kernels/norm_rotary`, interpreted
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    entered(context.described_tpu())
    # the head-pair kernels' one tile is 1,024 positions by default: at 512
    # this sequence is more than one, as the cell's 8,192 are at 1,024
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_Q", "512")
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_K", "512")
    monkeypatch.setattr(
        flash, "flash_attention_bshf",
        functools.partial(flash.flash_attention_bshf, interpret=True),
    )
    got = jax.value_and_grad(
        lambda u, ws: jnp.sum(program_attention(u, ws, sizes) * cot), (0, 1)
    )(u, ws)
    assert_trees_close(got, want, rtol=2e-4, atol=2e-4)


# -- the held share of the experts -------------------------------------------------


def experts_attrs(held, sizes=TOY):
    return ExpertsAttrs(
        sizes["num_experts_total"], sizes["num_experts_per_tok"],
        sizes["moe_intermediate_size"], activation=Activation.SILU,
        capacity_factor=None, use_bias=False, gated=True, renormalize=True,
        scoring="sigmoid", selection_bias=True, routed_scale=1.0,
        shared_hidden_size=0, held_experts=held,
    )


def share_of(named, first, count):
    ws = [named[f"e.weight{i}"] for i in range(5)]
    for i in (2, 3, 4):
        ws[i] = ws[i][first:first + count]
    return ws


def test_shares_add_up_to_the_uncut_layer():
    """The model's own split in miniature, at 16 experts in 4 shares of 4 (64
    in 8 of 8 in the deployment): every share's part of an expert layer,
    mixer and norms counted ONCE, adds up to the uncut reference layer over
    all 16 experts (a conv mixer under its norm, then the experts under
    theirs, each with its residual)."""
    rs = np.random.RandomState(5)
    d, e, width = TOY["hidden_size"], 16, TOY["moe_intermediate_size"]
    named = {
        "e.weight0": rand(rs, d, e),
        "e.weight1": rand(rs, e, scale=0.2),  # a bias that moves the choice
        "e.weight2": rand(rs, e, d, width, scale=0.3),
        "e.weight3": rand(rs, e, d, width, scale=0.3),
        "e.weight4": rand(rs, e, width, d, scale=0.3),
        "na.weight0": 1.0 + rand(rs, d, scale=0.2),
        "nb.weight0": 1.0 + rand(rs, d, scale=0.2),
    }
    h, conv = conv_case(seed=6, batch=1)
    named.update({f"c.weight{i}": t for i, t in enumerate(conv)})
    h, eps = h[0], TOY["norm_eps"]
    with jax.default_matmul_precision("highest"):
        # once, on every chip alike: the mixer under its norm, the second norm
        h = h + ref.short_conv(named, "c", ref.rms(h, named["na.weight0"], eps), TOY)
        m = ref.rms(h, named["nb.weight0"], eps)
        parts = [
            experts_forward(
                experts_attrs((first, 4)), m[None], share_of(named, first, 4)
            )[0][0]
            for first in (0, 4, 8, 12)
        ]
        whole = h + ref.experts(named, "e", m, TOY, held=(0, 16))[0]
    for part in parts:  # every share is a strict part of the layer
        assert float(jnp.max(jnp.abs(part))) > 1e-3
        assert float(jnp.max(jnp.abs(h + part - whole))) > 1e-3
    np.testing.assert_allclose(h + sum(parts), whole, **F32)


# -- the whole tiny tower through FFModel --------------------------------------


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


def test_layers_are_the_published_period():
    assert ref.layer_names(TOY) == [
        (0, "conv", True), (1, "full_attention", False), (2, "conv", False),
        (3, "conv", False), (4, "conv", False),
    ]
    assert ref.counts(TOY) == (4, 1, 1, 4)


def test_fit_step_matches_reference_adam_step():
    """The five-layer tower's loss before and after one `fit` step against
    the reference's own gradient and Adam step: 1e-5 is float32 rounding
    through two forward passes and the update. The selection bias is still
    zero afterwards, the routing counters report the held rows of this
    model's four expert nodes, and the program names the attention node's
    route."""
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
    stepped = bench.named_parameters(model.instance, model.params)
    for i in (1, 2, 3, 4):
        assert float(jnp.max(jnp.abs(stepped[f"moe{i}.weight1"]))) == 0.0
    counted = routing.published()
    assert counted["nodes"] == ["moe1", "moe2", "moe3", "moe4"]
    assert list(counted["decisions"]) == [BATCH * seq * 3] * 4  # one step
    assert 0.0 < counted["held_rows_pct"] < 100.0
    assert trace.attention_routes()["ff.ring_attention.attn1"] == "dense"


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
    assert 10 * F32_LOSS < off < 2e-2, off


def test_data_parallel_plan_shards_the_new_op_and_trains():
    """The batch template on four devices through the searched backend: the
    short-convolution op and the grouped-query attention are sharded over
    the batch (no node left serial), the loss is the one-device loss, and a
    step reduces it."""
    seq = 24
    inputs, labels = data(seq)
    one = compiled_model(seq, max_devices=1)
    four = compiled_model(
        seq, max_devices=4, search_budget=2,
        force_strategy_seed="dp4xtp1xsp1",
    )
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance
    from test_olmoe import weight_keys

    assert isinstance(four.instance, DistributedTrainingInstance)
    assert four.search_provenance["serial_compute_nodes"] == []
    keys1, keys4 = weight_keys(one.instance), weight_keys(four.instance)
    assert set(keys1) == set(keys4)
    one.params = {
        keys1[name]: jnp.asarray(np.asarray(four.params[keys4[name]]))
        for name in keys1
    }
    first = system_loss(four, inputs, labels)
    assert abs(first - system_loss(one, inputs, labels)) <= F32_LOSS
    four.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert system_loss(four, inputs, labels) < first - 0.01


def test_arithmetic_of_the_published_cut_by_hand():
    sizes = bench.load_json(CONFIG + ".json")
    # the graph at the published widths (shapes only, nothing is allocated):
    # its weights add up to the configuration's `parameters.as_built`
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    builder, _ = ref.build(sizes, 2, 8192)
    graph = builder.graph
    built = sum(
        int(np.prod(graph.tensor_attrs(graph.outputs_of(n)[0]).shape.dims))
        for n in graph.topological_ordering()
        if isinstance(graph.op_attrs(n), WeightAttrs)
    )
    assert built == 486_062_464
    assert sizes["parameters"]["as_built"].startswith("486,062,464 ")
    costs = ref.kernel_costs(sizes, 2, 8192)
    tokens = 2 * 8192
    # two projections (2048 -> 6144, 2048 -> 2048), three passes, four nodes
    assert costs["shortconv"]["flops"] == 4 * tokens * 3 * 2 * 2048 * (6144 + 2048)
    # u and the output, W_in, the taps and W_out in bf16, once a pass
    assert costs["shortconv"]["bytes"] == 4 * 3 * 2 * (
        2 * tokens * 2048 + 2048 * 6144 + 3 * 2048 + 2048 * 2048
    )
    pairs = 8192 * 8193 / 2
    # 2 products forward and 5 backward over the causal half, 32 heads of 64
    assert costs["flash"]["flops"] == 2 * 7 * 2 * pairs * 32 * 64
    # q, o (32 heads) and k, v (8 heads) once forward; with do and the three
    # gradients once backward
    assert costs["flash"]["bytes"] == 6 * 2 * tokens * 64 * (32 + 8)
    # per token forward: 4 conv mixers, 1 attention, the dense feed-forward,
    # 4 expert layers (router + 4 * 8 / 64 of an expert), the head
    forward = (
        4 * 2 * 2048 * (6144 + 2048)
        + 2 * 2048 * 64 * (2 * 32 + 2 * 8) + 2 * 2 * pairs * 32 * 64 / 8192
        + 3 * 2 * 2048 * 11776
        + 4 * (2 * 2048 * 64 + 3 * 2 * 2048 * 1536 * 0.5)
        + 2 * 2048 * 8192
    )
    assert ref.flops_per_token(sizes, 8192) == 3.0 * forward


# -- the benchmark's CPU rehearsal of the cell ---------------------------------


def test_rehearsal_cell_runs_correct_on_the_cpu_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(BENCH, "rehearsal-lfm2.json"), "--workload",
         "rehearsal_lfm2_s128_1chip", "--seed", "2147483659", "--seconds",
         "1", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], (result["checks"], result["losses"])
    assert result["device"]["platform"] == "cpu"
    # no device trace on the CPU mesh: the four trace readers return nothing
    for name in ("shortconv_ms", "shortconv_roofline", "gqa64_flash_roofline",
                 "lfm2_moe_held_ms"):
        assert name not in result["metrics"]
    assert "lfm2 reference routing" in done.stderr
    # the program's route counter reaches the reader's standard error
    assert '"ff.ring_attention.attn1": "dense"' in done.stderr
