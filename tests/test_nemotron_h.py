"""The hybrid state-space / grouped-query attention / held-share expert tower
(`benchmark/configs/nemotron-twotower-30b-a3b.py`) through the public builder
and `FFModel.compile -> fit`, each part against the plain float32 reference
that lives with the configuration, at toy size on the CPU with seeded
weights. The reference's recurrence runs position by position; the program's
in chunks. Every tolerance states its reason."""

import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as bench  # noqa: E402  (benchmark/run.py: the harness's loaders)

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel  # noqa: E402
from flexflow_tpu.kernels import context  # noqa: E402
from flexflow_tpu.kernels import forward as kernel_forward  # noqa: E402
from flexflow_tpu.kernels.moe import experts_forward, route  # noqa: E402
from flexflow_tpu.op_attrs.activation import Activation  # noqa: E402
from flexflow_tpu.op_attrs.ops import (  # noqa: E402
    ExpertsAttrs,
    RingAttentionAttrs,
    StateSpaceAttrs,
)

CONFIG = os.path.join(BENCH, "configs", "nemotron-twotower-30b-a3b")
ref = bench.load_module(CONFIG + ".py")

# the issue's toy sizes: 4 state-space heads of 8, state 16, 2 groups, chunks
# of 8; 8 query and 2 key/value heads of 8; 4 held of 16 relu2 experts of
# width 24 (top-3) beside a shared one of 40; the whole published pattern cut
# to five layers with every kind in it
TOY = dict(
    bench.load_json(CONFIG + ".json"),
    hidden_size=32, mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
    n_groups=2, chunk_size=8, num_attention_heads=8, num_key_value_heads=2,
    head_dim=8, moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    n_routed_experts=4, n_routed_experts_total=16, held_experts_first=4,
    num_experts_per_tok=3, vocab_rows_held=96, num_hidden_layers=5,
    hybrid_override_pattern="MEM*E",
    # ten times the published deviation: at toy width 0.02 leaves every
    # activation so small that a wrong term would hide inside a tolerance
    initializer_range=0.2,
)
BATCH = 2
ADAM = TOY["training"]

# float32 against float32 on the CPU: the two sides order their sums
# differently (chunked matrix products against a position-by-position
# recurrence, a sorted grouped matmul against a dense masked one, fused rows
# against per-head einsums), nothing else. Measured 1e-7 to 3e-6 here.
F32 = dict(rtol=2e-5, atol=2e-5)
F32_LOSS = 1e-5
# the state-space op at the widths the Pallas kernels take (inner 256, state
# 128): its projections and norm sum eight times the products, and BOTH forms
# of the scan read 9.8e-5 against the recurrence there, on the same element
F32_WIDE = dict(rtol=2e-5, atol=2e-4)
# and their gradients 5.1e-4 (the XLA form) and 6.0e-4 (the kernels) over rtol
F32_WIDE_GRADS = dict(rtol=1e-4, atol=1e-3)


def rand(rs, *shape, scale=1.0):
    return jnp.asarray(rs.randn(*shape).astype(np.float32) * scale)


def assert_trees_close(got, want, **tol):
    flat_got, tree = jax.tree_util.tree_flatten(got)
    flat_want, tree_want = jax.tree_util.tree_flatten(want)
    assert tree == tree_want
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **tol)


# -- the state-space op ------------------------------------------------------


# the least widths `kernels/ssm.scan_route` gives the Pallas kernels: chunks
# and a state of 128, 2 heads of 64 a group (128 columns a program)
KERNEL_TOY = dict(
    TOY, mamba_num_heads=4, mamba_head_dim=64, ssm_state_size=128, n_groups=2,
    chunk_size=128,
)
SIZES = {"toy": TOY, "kernels": KERNEL_TOY}


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The opt-in under which the CPU backend runs the Pallas kernels in
    interpret mode (`context.interpret_default`)."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")


def state_space_case(seq, seed=0, sizes=TOY):
    attrs = StateSpaceAttrs(
        sizes["mamba_num_heads"], sizes["mamba_head_dim"],
        sizes["ssm_state_size"], sizes["n_groups"], sizes["conv_kernel"],
        sizes["chunk_size"], sizes["layer_norm_epsilon"],
    )
    rs = np.random.RandomState(seed)
    d, heads = sizes["hidden_size"], attrs.num_heads
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    shapes = attrs.weight_shapes(TensorShape((BATCH, seq, d), DataType.FLOAT))
    weights = [rand(rs, *s.dims, scale=0.3) for s in shapes]
    # rates and step sizes in their published ranges, so that decays differ
    # by orders of magnitude between heads
    weights[3] = jnp.asarray(np.log(np.expm1(rs.uniform(1e-3, 0.3, heads))), jnp.float32)
    weights[4] = jnp.asarray(np.log(rs.uniform(1.0, 16.0, heads)), jnp.float32)
    return attrs, rand(rs, BATCH, seq, d), weights


def reference_state_space(u, weights, sizes=TOY):
    named = {f"m.weight{i}": w for i, w in enumerate(weights)}
    with jax.default_matmul_precision("highest"):
        return jax.vmap(lambda row: ref.mamba(named, "m", row, sizes))(u)


def scan_route_of(attrs, batch=BATCH):
    from flexflow_tpu.kernels.ssm import scan_route

    return scan_route(
        batch, attrs.num_heads, attrs.head_dim, attrs.num_groups,
        attrs.state_size, attrs.chunk_size,
    )


# (sizes, positions, the form `scan_route` names): the toy widths take the
# XLA form; the kernel widths the Pallas kernels, interpreted here
SCAN_CASES = [
    ("toy", 32, "xla"), ("toy", 40, "xla"), ("toy", 13, "xla"),
    ("kernels", 256, "ssd"), ("kernels", 200, "ssd"),
]


@pytest.mark.parametrize("sizes,seq,route", SCAN_CASES)
def test_state_space_forward_matches_the_step_by_step_recurrence(
    sizes, seq, route, interpreted_kernels
):
    """32 and 40 positions are whole chunks of 8, 256 two of 128; 13 and
    200 are not, and are padded inside the op."""
    attrs, u, weights = state_space_case(seq, sizes=SIZES[sizes])
    assert scan_route_of(attrs) == route
    (got,) = kernel_forward(attrs, [u], weights)
    want = reference_state_space(u, weights, SIZES[sizes])
    np.testing.assert_allclose(got, want, **(F32 if sizes == "toy" else F32_WIDE))


@pytest.mark.parametrize(
    "sizes,seq,route", [case for case in SCAN_CASES if case[1] != 13]
)
def test_state_space_gradients_match_the_step_by_step_recurrence(
    sizes, seq, route, interpreted_kernels
):
    """The input's gradient and all eight weights', under a random
    cotangent. 1e-4: the gradients of `A_log` and `dt_bias` sum thousands of
    products of decays, in another order on each side."""
    attrs, u, weights = state_space_case(seq, seed=1, sizes=SIZES[sizes])
    assert scan_route_of(attrs) == route
    cot = rand(np.random.RandomState(2), *u.shape)

    def system(u, weights):
        return jnp.sum(kernel_forward(attrs, [u], weights)[0] * cot)

    def reference(u, weights):
        return jnp.sum(reference_state_space(u, weights, SIZES[sizes]) * cot)

    got = jax.grad(system, argnums=(0, 1))(u, weights)
    want = jax.grad(reference, argnums=(0, 1))(u, weights)
    for g in jax.tree_util.tree_leaves(want):
        assert float(jnp.max(jnp.abs(g))) > 1e-3  # every slot is reached
    tol = dict(rtol=1e-4, atol=1e-4) if sizes == "toy" else F32_WIDE_GRADS
    assert_trees_close(got, want, **tol)


def scan_operands(seq, dtype, seed=3, heads=4, p=64):
    """(x, dt, a_log, B, C, D) of `selective_scan` at the kernel widths."""
    g, n = 2, 128
    rs = np.random.RandomState(seed)
    return (
        rand(rs, BATCH, seq, heads, p).astype(dtype),
        jnp.asarray(rs.uniform(1e-3, 0.3, (BATCH, seq, heads)), jnp.float32),
        jnp.asarray(np.log(rs.uniform(1.0, 16.0, heads)), jnp.float32),
        rand(rs, BATCH, seq, g, n, scale=0.3).astype(dtype),
        rand(rs, BATCH, seq, g, n, scale=0.3).astype(dtype),
        rand(rs, heads),
    )


def scan_value_and_gradients(operands, cot, chunk=128):
    from flexflow_tpu.kernels.ssm import selective_scan

    def loss(*operands):
        y = selective_scan(*operands, chunk)
        return jnp.sum(y.astype(jnp.float32) * cot), y

    (_, y), grads = jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True
    )(*operands)
    return [y, *grads]


@pytest.mark.parametrize(
    "seq,heads,p", [(256, 4, 64), (200, 4, 64), (128, 2, 128)]
)
def test_bf16_scan_kernels_agree_with_the_xla_form(seq, heads, p, monkeypatch):
    """bf16 operands: y and the six gradients of the kernels against the XLA
    form's, each within 2e-2 of the XLA form's largest entry (the bound this
    file gives bf16 compute; measured at most 7e-3: both forms cast the same
    operands and differ in the order of their float32 sums, which moves a
    bf16 result by an ulp). Heads of 64 share a 128-lane tile, heads of 128
    have one each."""
    operands = scan_operands(seq, jnp.bfloat16, heads=heads, p=p)
    cot = rand(np.random.RandomState(4), *operands[0].shape)
    want = scan_value_and_gradients(operands, cot)
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    got = scan_value_and_gradients(operands, cot)
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.max(np.abs(g - w)) <= 2e-2 * np.max(np.abs(w))
    assert not np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_scan_kernels_map_over_the_batch_shards_of_a_declared_mesh(
    interpreted_kernels,
):
    """Under a `flash_mesh` with whole heads (the data-parallel backend's
    step) the kernels run per batch shard: values and gradients are the
    one-device kernels'."""
    from jax.sharding import Mesh

    from flexflow_tpu.kernels.context import flash_mesh

    operands = scan_operands(128, jnp.float32)
    cot = rand(np.random.RandomState(4), *operands[0].shape)
    want = scan_value_and_gradients(operands, cot)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    with mesh, flash_mesh(mesh, "data", None, True):
        got = jax.jit(scan_value_and_gradients)(operands, cot)
    assert_trees_close(got, want, rtol=1e-5, atol=1e-5)


def test_scan_route_is_read_from_shapes_backend_and_trace(monkeypatch, entered):
    """The published widths take the kernels on a TPU, one row or many; toy
    widths, a chunk of 8, a CPU without the interpret opt-in and a trace
    under `no_flash()` take `_scan_core`; a group wider than a program holds
    takes the kernels as column blocks (PR 68; `test_granite_hybrid.py`),
    except under a declared mesh; a declared mesh maps the kernels over its
    batch shards if the heads are whole and the batch divides."""
    from jax.sharding import Mesh

    from flexflow_tpu.kernels.ssm import scan_route

    cell = dict(batch=1, heads=64, head_dim=64, groups=8, state=128, chunk=128)
    assert scan_route(**cell) == "xla"  # the CPU, no opt-in
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert scan_route(**cell) == "ssd"
    monkeypatch.delenv("FLEXFLOW_TPU_FLASH_INTERPRET")
    entered(context.described_tpu())
    assert scan_route(**cell) == "ssd"
    assert scan_route(**dict(cell, batch=8)) == "ssd"
    for other in (
        dict(heads=4, head_dim=8, groups=2, state=16, chunk=8),  # the toy
        dict(chunk=8), dict(chunk=192), dict(state=64), dict(head_dim=32),
        dict(heads=8),  # one head a group: 64 columns
    ):
        assert scan_route(**dict(cell, **other)) == "xla", other
    # 32 heads of 64 a group: 2,048 columns, two column blocks of 1,024
    assert scan_route(**dict(cell, groups=2)) == "ssd"
    with context.no_flash():
        assert scan_route(**cell) == "xla"
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    with context.flash_mesh(mesh, "data", None, False), context.no_flash():
        assert scan_route(**dict(cell, batch=4)) == "ssd_sharded"
        assert scan_route(**dict(cell, batch=3)) == "xla"
        assert scan_route(**dict(cell, batch=4, groups=2)) == "xla"
    with context.flash_mesh(mesh, None, "data", False):
        assert scan_route(**dict(cell, batch=4)) == "xla"


def traced_step(model):
    """The model's train step traced against example arguments, as
    `tools/lowered_step_text.py` traces a cell's (`jax.stages.Traced`)."""
    from flexflow_tpu.analysis import lowering

    example = lowering.step_example_args_cg(model.instance, model.loss_attrs)
    return model.instance.compiled_step().trace(
        model.params, model.opt_state, *example
    )


def kernel_tower():
    """The five-layer tower at the kernel widths (four heads a group, so
    that a group's `[256, 128]` state is no `[128, 128]` tensor)."""
    sizes = dict(KERNEL_TOY, mamba_num_heads=8)
    return compiled_model(256, jnp.bfloat16, sizes=sizes, max_devices=1)


@functools.cache
def lowered_tower_step(kernels: bool) -> str:
    """The StableHLO of `kernel_tower`'s step, lowered for the TPU
    platform. `kernels`: the caller has told the kernel gates that a TPU is
    there."""
    return traced_step(kernel_tower()).lower(
        lowering_platforms=("tpu",)
    ).as_text()


def element_moves(traced):
    """{(primitive, operand's shape)} of every gather and scatter of a traced
    step under an expert node's scope that moves ONE element an index (a gather whose
    slice, a scatter whose update window, is a single element): what XLA's
    TPU backend runs an element at a time. Whole rows (`x2[token]`,
    `out.at[token].add`) are not among them."""
    from jax.extend import core as jex

    def walk(jaxpr, outer):
        for eqn in jaxpr.eqns:
            stack = f"{outer}/{eqn.source_info.name_stack}"
            yield eqn, stack
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [value]:
                    if isinstance(sub, jex.ClosedJaxpr):
                        sub = sub.jaxpr
                    if isinstance(sub, jex.Jaxpr):
                        yield from walk(sub, stack)

    moves = set()
    for eqn, stack in walk(traced.jaxpr.jaxpr, ""):
        name = eqn.primitive.name
        if "ff.experts" not in stack or not name.startswith(("gather", "scatter")):
            continue
        one = (
            set(eqn.params["slice_sizes"]) == {1} if name == "gather"
            else not eqn.params["dimension_numbers"].update_window_dims
        )
        if one:
            moves.add((name, eqn.invars[0].aval.shape))
    return moves


def test_step_moves_no_routing_value_an_element_at_a_time(top_k_jvp_refused):
    """Under the expert nodes' scopes the step picks the chosen scores and
    counts the share's keys by comparison: no `[N, E]` gather, none
    transposed to a scatter, no histogram by scatter-add, and no JVP of
    `top_k` (the step is traced anew here, with the rule refusing). What is
    left an element at a time is a WINDOW of the 1,536 decisions: the
    window's decisions and weights read from the sorted order, and the
    weights' gradient going back."""
    decisions = (BATCH * 256 * KERNEL_TOY["num_experts_per_tok"],)
    assert element_moves(traced_step(kernel_tower())) == {
        ("gather", decisions), ("scatter-add", decisions),
    }


def test_lowered_step_holds_the_scan_kernels_and_no_mask_tensor(monkeypatch, entered):
    """Each of the tower's two state-space layers calls the forward kernel,
    the state pass and the backward kernel once (the jitted callers
    `_ssd_forward` and `_ssd_backward`; their bodies, `ssd_fwd_chunk`,
    `ssd_states_chunk` and `ssd_bwd_chunk`, are in the text once for both
    layers), and no float32 `[.., 128, 128]` tensor (C.B, a decay mask,
    their product) is left in the program, which the XLA form has."""
    import re


    mask = re.compile(r"tensor<(?:\d+x)+128x128xf32>")
    assert mask.findall(lowered_tower_step(False))  # the XLA form
    entered(context.described_tpu())
    text = lowered_tower_step(True)
    assert sorted(
        name for name in re.findall(r'kernel_name = "(\w+)"', text)
        if name.startswith("ssd_")
    ) == ["ssd_bwd_chunk", "ssd_fwd_chunk", "ssd_states_chunk"]
    calls = re.findall(r"call @(_ssd_\w+?)(?:_\d+)?\(", text)
    assert sorted(calls) == ["_ssd_backward"] * 2 + ["_ssd_forward"] * 4
    assert mask.findall(text) == []


def test_lowered_step_starts_no_loop_of_the_held_share_from_zeros():
    """The held share's loops take the windows after the first, so what
    they accumulate into is what the first window gave: each of the tower's
    two expert layers has a loop that carries the experts' `[4, 32, 24]` /
    `[4, 24, 32]` stacks twice (the matrices and their gradients), and no
    operand of any `while` is a constant or a broadcast of that shape, as
    the zero-filled accumulators were. Both call sites of a window function
    (straight-line and loop body) call the one function of the index."""
    import re

    text = lowered_tower_step(False)
    stack = re.compile(r"tensor<4x(?:32x24|24x32)xbf16>")
    carried, filled = [], []
    for function in text.split("func.func")[1:]:  # a value's name is local
        made_by = dict(re.findall(r"(%\w+)(?::\d+)? = ([\w.]+)", function))
        for line in re.findall(r"stablehlo\.while\((.*)", function):
            operands, types = line.split(") : ")
            values = re.findall(r"= (%\w+)", operands)
            stacks = [
                made_by.get(value) for value, kind in zip(values, types.split(", "))
                if stack.fullmatch(kind)
            ]
            carried.append(len(stacks))
            filled += [
                made for made in stacks
                if made in ("stablehlo.broadcast_in_dim", "stablehlo.constant")
            ]
    assert sorted(carried)[-2:] == [4, 4], carried
    assert filled == []
    calls = re.findall(r"call @(_held_window_\w+?)(?:_\d+)?\(", text)
    assert sorted(calls) == (
        ["_held_window_add"] * 4 + ["_held_window_grads"] * 4
    )


def test_scan_keeps_no_state_per_position():
    """What the backward pass keeps from the scan is its inputs: the
    largest residual of the op's VJP is far under one [heads, P, N] state a
    position."""
    attrs, u, weights = state_space_case(64)
    _, vjp = jax.vjp(lambda u, w: kernel_forward(attrs, [u], w)[0], u, weights)
    per_position_states = (
        BATCH * 64 * attrs.num_heads * attrs.head_dim * attrs.state_size
    )
    largest = max(x.size for x in jax.tree_util.tree_leaves(vjp))
    assert largest < per_position_states / 4, (largest, per_position_states)


# -- grouped-query attention ---------------------------------------------------


def rotary_gqa_reference(x, w, heads, kv, d, theta):
    """Grouped-query causal attention with rotate-half RoPE on each query
    and key head, head by head with a full masked softmax."""
    hidden, s = x.shape[-1], x.shape[-2]
    cuts = np.cumsum([0, hidden * heads * d, hidden * kv * d,
                      hidden * kv * d, heads * d * hidden])
    flat = w.reshape(-1)
    wq = flat[cuts[0]:cuts[1]].reshape(hidden, heads, d)
    wk = flat[cuts[1]:cuts[2]].reshape(hidden, kv, d)
    wv = flat[cuts[2]:cuts[3]].reshape(hidden, kv, d)
    wo = flat[cuts[3]:cuts[4]].reshape(heads, d, hidden)
    angle = jnp.arange(s)[:, None] * theta ** (-jnp.arange(d // 2) * 2.0 / d)
    cos, sin = jnp.cos(angle), jnp.sin(angle)

    def rope(t):  # [b, s, h, d]
        lo, hi = t[..., : d // 2], t[..., d // 2:]
        c, n = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([lo * c - hi * n, hi * c + lo * n], axis=-1)

    q = rope(jnp.einsum("bse,ehd->bshd", x, wq))
    k = rope(jnp.einsum("bse,ehd->bshd", x, wk))
    v = jnp.einsum("bse,ehd->bshd", x, wv)
    out = 0.0
    for h in range(heads):
        g = h // (heads // kv)
        scores = jnp.einsum("bsd,btd->bst", q[:, :, h], k[:, :, g]) / np.sqrt(d)
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        ctx = jnp.einsum("bst,btd->bsd", jax.nn.softmax(scores, -1), v[:, :, g])
        out = out + ctx @ wo[h]
    return out


@pytest.mark.parametrize("theta", [None, 10000.0], ids=["no_rotary", "rotary"])
def test_grouped_query_attention_forward_and_gradients(theta):
    """8 query heads read 2 key/value heads; the weight keeps the small
    key/value projections ([hidden, 2 * 8] each). With `rope_theta` the
    rotation is of the 2 key heads, before they are repeated."""
    heads, kv, d, hidden, seq = 8, 2, 8, TOY["hidden_size"], 24
    attrs = RingAttentionAttrs(
        hidden, heads, kdim=d, vdim=d, causal=True, num_kv_heads=kv,
        rope_theta=theta,
    )
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    x_shape = TensorShape((BATCH, seq, hidden), DataType.FLOAT)
    (w_shape,) = [attrs.weights_shape(x_shape, x_shape, x_shape)]
    assert w_shape.dims == (2 * hidden * heads * d + 2 * hidden * kv * d, 1)
    rs = np.random.RandomState(3)
    x, w = rand(rs, BATCH, seq, hidden), rand(rs, *w_shape.dims, scale=0.3)
    cot = rand(rs, BATCH, seq, hidden)

    def system(x, w):
        return kernel_forward(attrs, [x, x, x], [w])[0]

    def reference(x, w):
        with jax.default_matmul_precision("highest"):
            if theta is not None:
                return rotary_gqa_reference(x, w, heads, kv, d, theta)
            return jax.vmap(
                lambda row: ref.attention({"a.weight0": w}, "a", row, TOY)
            )(x)

    np.testing.assert_allclose(system(x, w), reference(x, w), **F32)
    got = jax.grad(lambda x, w: jnp.sum(system(x, w) * cot), (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(reference(x, w) * cot), (0, 1))(x, w)
    assert_trees_close(got, want, **F32)


# -- the router and the experts ---------------------------------------------


def experts_attrs(held):
    return ExpertsAttrs(
        TOY["n_routed_experts_total"], TOY["num_experts_per_tok"],
        TOY["moe_intermediate_size"], activation=Activation.RELU2,
        capacity_factor=None, use_bias=False, renormalize=True,
        scoring="sigmoid", selection_bias=True,
        routed_scale=TOY["routed_scaling_factor"],
        shared_hidden_size=TOY["moe_shared_expert_intermediate_size"],
        held_experts=held,
    )


def experts_case(seed=4, tokens=48):
    """(tokens [n, D], the UNCUT layer's weights by reference name)."""
    rs = np.random.RandomState(seed)
    d, e = TOY["hidden_size"], TOY["n_routed_experts_total"]
    width, shared = TOY["moe_intermediate_size"], TOY["moe_shared_expert_intermediate_size"]
    named = {
        "e.weight0": rand(rs, d, e),
        # a bias that does move the choice
        "e.weight1": rand(rs, e, scale=0.2),
        "e.weight2": rand(rs, e, d, width, scale=0.3),
        "e.weight3": rand(rs, e, width, d, scale=0.3),
        "e.weight4": rand(rs, d, shared, scale=0.3),
        "e.weight5": rand(rs, shared, d, scale=0.3),
    }
    return rand(rs, tokens, d), named


def share_of(named, first, count):
    """The weights one share holds, in the op's slot order."""
    return [
        named["e.weight0"], named["e.weight1"],
        named["e.weight2"][first:first + count],
        named["e.weight3"][first:first + count],
        named["e.weight4"], named["e.weight5"],
    ]


def reference_experts(m, named, first, count):
    """The reference on the share's own weights (as the benchmark gives it
    the program's)."""
    sizes = dict(TOY, held_experts_first=first, n_routed_experts=count)
    held = {
        f"e.weight{i}": w for i, w in enumerate(share_of(named, first, count))
    }
    with jax.default_matmul_precision("highest"):
        return ref.experts(held, "e", m, sizes)


def test_sigmoid_router_bias_renormalise_scale():
    m, named = experts_case()
    attrs = experts_attrs((0, 16))
    _, scores, chosen, weights = route(
        attrs, m, named["e.weight0"], named["e.weight1"]
    )
    with jax.default_matmul_precision("highest"):
        score, mask, weight = ref.router(named, "e", m, TOY)
    np.testing.assert_allclose(scores, score, **F32)
    got_mask = jnp.sum(jax.nn.one_hot(chosen, 16), axis=1)
    np.testing.assert_array_equal(np.asarray(got_mask), np.asarray(mask))
    # the bias changed some choices and entered no weight
    plain = jax.lax.top_k(score, attrs.num_select)[1]
    assert not np.array_equal(np.sort(plain), np.sort(np.asarray(chosen)))
    np.testing.assert_allclose(
        jnp.sum(weights, axis=-1), TOY["routed_scaling_factor"], rtol=1e-5
    )
    dense = jnp.zeros_like(score).at[
        jnp.arange(m.shape[0])[:, None], chosen
    ].set(weights)
    np.testing.assert_allclose(dense, weight, **F32)


@pytest.mark.parametrize("held", [(0, 16), (4, 4), (12, 4)], ids=str)
def test_relu2_experts_forward_and_gradients(held):
    """The whole layer (every expert held) and two shares of four, against
    the reference's dense masked experts: output, the input's gradient and
    every trainable slot's; the selection bias gets none."""
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
    assert_trees_close(got, want, rtol=1e-4, atol=1e-4)


def test_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that the four shares of a
    16-expert layer give, the shared expert counted once, add up to what the
    uncut reference gives for the whole layer."""
    m, named = experts_case(seed=6)
    with jax.default_matmul_precision("highest"):
        shared = ref.mm(
            "sh,hd->sd",
            ref.relu2(ref.mm("sd,dh->sh", m, named["e.weight4"])),
            named["e.weight5"],
        )
    parts = [
        experts_forward(
            experts_attrs((first, 4)), m, share_of(named, first, 4)
        )[0] - shared
        for first in (0, 4, 8, 12)
    ]
    # every share routes over all 16 and keeps its own: each is a strict part
    whole = reference_experts(m, named, 0, 16)[0]
    for part in parts:
        assert float(jnp.max(jnp.abs(part))) > 1e-3
        assert float(jnp.max(jnp.abs(part - (whole - shared)))) > 1e-3
    np.testing.assert_allclose(sum(parts) + shared, whole, **F32)


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


def test_fit_step_matches_reference_adam_step(capfd):
    """Loss before and after one `fit` step against the reference's own
    gradient and Adam step: 1e-5 is float32 rounding through two forward
    passes and the update. The selection bias is still zero afterwards, and
    the step's routing counters reached `observability.routing`."""
    from flexflow_tpu.observability import routing

    seq = 32
    model = compiled_model(seq, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    assert abs(system_loss(model, inputs, labels) - before) <= F32_LOSS
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(system_loss(model, inputs, labels) - after) <= F32_LOSS
    assert before - after > 100 * F32_LOSS  # the step did something
    stepped = bench.named_parameters(model.instance, model.params)
    for name in ("moe1.weight1", "moe4.weight1"):
        assert float(jnp.max(jnp.abs(stepped[name]))) == 0.0
    counted = routing.published()
    assert counted["nodes"] == ["moe1", "moe4"]
    assert list(counted["decisions"]) == [BATCH * seq * 3] * 2  # one step
    # the reference's own count of the first layer's decisions on held experts
    report = json.loads(
        capfd.readouterr().err.split("nemotron reference routing: ")[1]
        .splitlines()[0]
    )
    first_sequence = report["held_share_of_decisions_by_layer"][0]
    assert 0.0 < first_sequence < 1.0
    assert 0.0 < counted["held_rows_pct"] < 100.0
    assert counted["rows"].shape == (2, 4)


def test_bf16_compute_is_inside_its_tolerance_and_outside_float32s():
    """The same graph at bf16 compute: inside 2e-2 (a mean over 64
    positions averages little rounding away) and outside the float32
    bound, so the float32 tests above would catch a bf16 path."""
    seq = 32
    model = compiled_model(seq, compute_dtype=jnp.bfloat16, max_devices=1)
    inputs, labels = data(seq)
    named = bench.named_parameters(model.instance, model.params)
    before, _ = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    off = abs(system_loss(model, inputs, labels) - before)
    assert 10 * F32_LOSS < off < 2e-2, off


def test_data_parallel_plan_shards_the_new_ops_and_trains():
    """The batch template on two devices through the searched backend: the
    state-space op, grouped-query attention and the held-share experts are
    all sharded (no node left serial), the loss is the one-device loss, and
    a step reduces it."""
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
    from test_olmoe import weight_keys

    keys1, keys2 = weight_keys(one.instance), weight_keys(two.instance)
    assert set(keys1) == set(keys2)
    one.params = {
        keys1[name]: jnp.asarray(np.asarray(two.params[keys2[name]]))
        for name in keys1
    }
    first = system_loss(two, inputs, labels)
    assert abs(first - system_loss(one, inputs, labels)) <= F32_LOSS
    two.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert system_loss(two, inputs, labels) < first - 0.01


# -- the benchmark's CPU rehearsal of the cell ---------------------------------


def test_rehearsal_cell_runs_correct_on_the_cpu_mesh(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         os.path.join(BENCH, "rehearsal-nemotron.json"), "--workload",
         "rehearsal_nemotron_s128_1chip", "--seed", "2147483659", "--seconds",
         "1", "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"], (result["checks"], result["losses"])
    assert result["device"]["platform"] == "cpu"
    # no device trace on the CPU mesh: the three trace readers return
    # nothing; the routing counter is the program's own and is there
    for name in ("ssm_ms", "ssm_scan_roofline", "moe_held_ms"):
        assert name not in result["metrics"]
    share = result["metrics"]["moe_held_rows_pct"]["value"]
    assert 10.0 < share < 45.0  # 4 of 16 experts held: 25% if uniform
    assert "nemotron reference routing" in done.stderr


# selection biases over the 16 experts that send the share (4, 4) of 96
# tokens' 288 decisions none, a uniform quarter, two of every three, all
ROUTERS = {
    "no_decision": (jnp.zeros(16).at[4:8].set(-5.0), 0),
    "one_window": (None, 1),
    "two_windows": (jnp.zeros(16).at[4:6].set(5.0), 2),
    "three_windows": (jnp.zeros(16).at[4:8].set(5.0), 3),
}


@pytest.mark.parametrize("router", list(ROUTERS))
def test_a_router_that_sends_everything_here_drops_nothing(router):
    """The held rows are taken in windows sized for a uniform router (a
    quarter more than 4 / 16 of 288 decisions: one 128-row window). The
    first window is straight-line code and runs whatever the router sent,
    also where it sent nothing; with a selection bias that sends the held
    experts more the op takes a second and a third in its loop. Output,
    the gradients of x, the router and `w1` / `w2`, and the windows the op
    says it ran, against the reference's dense masked experts."""
    from flexflow_tpu.kernels.moe import held_window_rows
    from flexflow_tpu.observability import routing

    bias, windows = ROUTERS[router]
    m, named = experts_case(seed=8, tokens=96)
    held = (4, 4)
    if bias is not None:
        named["e.weight1"] = bias
    assert held_window_rows(96 * 3, 4, 16) == 128
    rows = float(jnp.sum(reference_experts(m, named, *held)[1][:, 4:8]))
    assert -(-rows // 128) == windows, rows
    cot = rand(np.random.RandomState(9), *m.shape)

    def system(m, named):
        return experts_forward(experts_attrs(held), m, share_of(named, *held))[0]

    def reference(m, named):
        return reference_experts(m, named, *held)[0]

    with routing.collecting() as recorded:
        got = system(m, named)
    np.testing.assert_allclose(got, reference(m, named), **F32)
    (row,) = recorded  # the share's counts, the decisions, windows, a step
    assert [int(v) for v in row[-3:]] == [96 * 3, max(windows, 1), 1]
    assert float(jnp.sum(row[:4])) == rows
    got = jax.grad(lambda *a: jnp.sum(system(*a) * cot), (0, 1))(m, named)
    want = jax.grad(lambda *a: jnp.sum(reference(*a) * cot), (0, 1))(m, named)
    for name in ("e.weight0", "e.weight2", "e.weight3"):
        moved = float(jnp.max(jnp.abs(want[1][name])))
        assert (moved > 1e-3) == (windows > 0), name
    assert_trees_close(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize(
    "bias,windows", [(None, [1, 1]), (5.0, [2, 1])],
    ids=["uniform", "overflowing"],
)
def test_windows_counter_reads_what_the_loop_ran(bias, windows):
    """The program's windows counter through `fit` on the CPU mesh, two
    steps of the toy tower (192 decisions a step, 128-row windows): 1 a
    step for each held node under the initial router, 2 for the first node
    once its selection bias sends every decision to the held experts."""
    from flexflow_tpu.observability import routing
    from test_olmoe import weight_keys

    seq = 32
    model = compiled_model(seq, max_devices=1)
    if bias is not None:
        key = weight_keys(model.instance)["moe1.weight1"]
        model.params = dict(
            model.params, **{key: jnp.zeros(16).at[4:8].set(bias)}
        )
    inputs, labels = ref.make_data(np.random.RandomState(0), TOY, 2 * BATCH, seq)
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    counted = routing.published()
    assert list(counted["decisions"]) == [BATCH * seq * 3 * 2] * 2
    assert list(counted["windows_per_step"]) == windows
