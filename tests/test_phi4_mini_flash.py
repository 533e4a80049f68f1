"""The decoder-hybrid-decoder step (`benchmark/configs/phi-4-mini-flash-reasoning.py`):
the Mamba-1 selective scan and its kernels, differential attention under a
window, the full layer that hands its keys and values on, the cross layer and
the gated memory unit that read another layer's tensors, and the tied head,
through the public builder and `FFModel.compile -> fit`, each part against the
plain float32 reference that lives with the configuration, at toy size on the
CPU with seeded weights. Every tolerance states its reason."""

import dataclasses
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
from flexflow_tpu.kernels import selective_scan as s6
from flexflow_tpu.kernels.ops import mha_core_route
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.core import (
    OperatorType,
    get_default_weight_initializers,
    get_incoming_tensor_roles,
    get_output_shapes,
    get_parallel_output_shapes,
    get_parallel_weight_shapes,
    get_weight_shapes,
    num_outputs,
    op_type_of,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    LinearAttrs,
    MultiHeadAttentionAttrs,
    RingAttentionAttrs,
    SelectiveScanAttrs,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape

CONFIG = os.path.join(BENCH, "configs", "phi-4-mini-flash-reasoning")
ref = bench.load_module(CONFIG + ".py")

# 4 query and 2 key/value heads of 8 (two differential heads over one group
# with a 16-wide value), 64 channels with an 8-wide state and a step rank of
# 4, a window of 10 keys, feed-forwards of 48, 96 vocabulary rows; the same
# six published layers
TOY = dict(
    bench.load_json(CONFIG + ".json"),
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=48, sliding_window=10, vocab_rows_held=96,
    mamba_d_state=8, mamba_dt_rank=4,
    # ten times the published deviation, as in the other configurations'
    # tests: at toy width 0.02 leaves every activation so small that a wrong
    # term would hide inside a tolerance
    initializer_range=0.2,
)
BATCH = 2
SEQ = 24
ADAM = TOY["training"]

# gradients through softmaxes, norms, a recurrence of 24 steps and several
# projections in float32 on the CPU: sums of a few hundred products in
# another order than the reference's, on gradients of up to a hundred
F32_GRADS = dict(rtol=1e-3, atol=1e-3)


def weights_for(attrs, inputs, rs, scale=0.3):
    """The op's weights in slot order, every one drawn."""
    return [
        rand(rs, *shape.dims, scale=scale)
        for shape in get_weight_shapes(attrs, inputs)
    ]


# -- the selective-scan node ---------------------------------------------------


def scan_attrs(memory=False, sizes=TOY):
    width, n, rank, taps = ref.scan_sizes(sizes)
    return SelectiveScanAttrs(width, n, rank, taps, memory_output=memory)


def scan_case(seq=SEQ, seed=1, sizes=TOY):
    rs = np.random.RandomState(seed)
    attrs = scan_attrs(True, sizes)
    x = TensorShape((BATCH, seq, sizes["hidden_size"]), DataType.FLOAT)
    ws = weights_for(attrs, [x], rs)
    ws[6] = jnp.log(1.0 + jnp.abs(ws[6]) * 8.0)  # A_log: decays of 1..4
    ws[5] = ws[5] - 2.0  # the step's bias: steps around 0.1
    return attrs, rand(rs, BATCH, seq, sizes["hidden_size"]), ws


def reference_scan(u, ws, sizes=TOY):
    named = {f"s.weight{i}": w for i, w in enumerate(ws)}
    with jax.default_matmul_precision("highest"):
        outs = [ref.scan_mixer(named, "s", row, sizes) for row in u]
    return [jnp.stack([o[k] for o in outs]) for k in (0, 1)]


def test_scan_node_forward_and_memory_match_the_reference():
    attrs, u, ws = scan_case()
    with jax.default_matmul_precision("highest"):
        got = kernel_forward(attrs, [u], ws)
    assert len(got) == 2 and got[1].shape == (BATCH, SEQ, attrs.channels)
    assert_trees_close(got, reference_scan(u, ws), **F32)
    # without the second output the first is the same
    alone = kernel_forward(dataclasses.replace(attrs, memory_output=False), [u], ws)
    assert len(alone) == 1


def test_scan_node_gradients_match_the_reference():
    attrs, u, ws = scan_case()
    cot = [rand(np.random.RandomState(2), *s) for s in
           ((BATCH, SEQ, 32), (BATCH, SEQ, attrs.channels))]

    def loss(f):
        def of(u, ws):
            out, memory = f(u, ws)
            return jnp.sum(out * cot[0]) + jnp.sum(memory * cot[1])
        return of

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda u, ws: kernel_forward(attrs, [u], ws)), (0, 1))(u, ws)
        want = jax.grad(loss(reference_scan), (0, 1))(u, ws)
    assert_trees_close(got, want, **F32_GRADS)


def scan_operands(seq, width, n, dtype, seed=3):
    rs = np.random.RandomState(seed)
    return tuple(t.astype(dtype) for t in (
        rand(rs, BATCH, seq, width), rand(rs, BATCH, seq, width) - 2.0,
        rand(rs, width, scale=0.5),
        jnp.log(jnp.broadcast_to(jnp.arange(1.0, n + 1), (width, n)))
        + rand(rs, width, n, scale=0.1),
        rand(rs, BATCH, seq, n), rand(rs, BATCH, seq, n), rand(rs, width),
    ))


def scan_value_and_gradients(operands, cot, route, chunk):
    def loss(*operands):
        y = s6.selective_scan(
            *operands, chunk=chunk, route=route, interpret=True
        )
        return jnp.sum(y.astype(jnp.float32) * cot), y

    (_, y), grads = jax.value_and_grad(
        loss, argnums=tuple(range(7)), has_aux=True
    )(*operands)
    return y, grads


@pytest.mark.parametrize("seq", [40, 64])
def test_scan_kernels_agree_with_the_lax_scan_route(seq):
    """The Pallas kernels in interpret mode against the `lax.scan` route,
    forward and the written backward, in float32 at a length that is no
    multiple of the block (40 over chunks of 16: the last is padded) and at
    one that is: the same products summed in another order, measured 2e-6 on
    outputs of 40 and 1e-5 on gradients of 40."""
    operands = scan_operands(seq, 256, 16, jnp.float32)
    cot = rand(np.random.RandomState(4), BATCH, seq, 256)
    want = scan_value_and_gradients(operands, cot, "scan", 16)
    got = scan_value_and_gradients(operands, cot, "pallas", 16)
    assert_trees_close(got, want, rtol=2e-5, atol=2e-4)


def test_bf16_scan_kernels_agree_with_the_lax_scan_route():
    """bf16 operands, float32 state in both forms: what differs is the order
    of float32 sums, below a bf16 result's last bit almost everywhere (a
    gradient of 30 rounds to 0.125)."""
    operands = scan_operands(50, 128, 16, jnp.bfloat16)
    cot = rand(np.random.RandomState(4), BATCH, 50, 128)
    want = scan_value_and_gradients(operands, cot, "scan", 16)
    got = scan_value_and_gradients(operands, cot, "pallas", 16)
    as_f32 = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda t: t.astype(jnp.float32), tree
    )
    assert_trees_close(as_f32(got), as_f32(want), rtol=2e-2, atol=0.3)


def test_a_bfloat16_scan_state_is_outside_the_tolerance():
    """The control of the float32 tolerance above: the same recurrence with
    its state rounded to bfloat16 after every step is off by far more."""
    operands = scan_operands(64, 256, 16, jnp.float32)
    x, r, bias, a_log, b_mat, c_mat, d_skip = operands
    dt = jax.nn.softplus(r + bias)
    a = -jnp.exp(a_log)

    def step(state, row):
        x_t, dt_t, b_t, c_t = row
        state = (
            jnp.exp(dt_t[..., None] * a) * state
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        ).astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    rows = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b_mat, c_mat))
    _, y = jax.lax.scan(step, jnp.zeros((BATCH, 256, 16)), rows)
    rounded = jnp.moveaxis(y, 0, 1) + d_skip * x
    exact = s6.selective_scan(*operands, chunk=16, route="scan")
    assert float(jnp.max(jnp.abs(rounded - exact))) > 50 * 2e-4


def test_scan_route_is_read_from_shapes_backend_and_trace(monkeypatch):
    assert s6.scan_route(256, 16) == "scan"  # the CPU, no opt-in
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    assert s6.scan_route(256, 16) == "pallas"
    assert s6.scan_route(64, 16) == "scan"  # channels fill no lane tile
    assert s6.scan_route(256, 4) == "scan"  # a state of no whole sublane tile
    with context.no_flash():
        assert s6.scan_route(256, 16) == "scan"


def test_scan_attrs_shapes_slots_and_parallel_rule():
    attrs = scan_attrs(True)
    x = TensorShape((4, SEQ, 32), DataType.FLOAT)
    assert op_type_of(attrs) == OperatorType.SELECTIVE_SCAN
    assert num_outputs(attrs) == 2
    assert [s.dims for s in get_output_shapes(attrs, [x])] == [
        (4, SEQ, 32), (4, SEQ, 64)
    ]
    assert [s.dims for s in get_weight_shapes(attrs, [x])] == [
        (32, 128), (4, 64), (64,), (64, 4 + 16), (4, 64), (64,), (64, 8),
        (64,), (64, 32),
    ]
    assert SelectiveScanAttrs(5120).rank_for(2560) == 160  # ceil(D / 16)
    assert len(get_incoming_tensor_roles(attrs)) == 10
    inits = get_default_weight_initializers(attrs, 9)
    assert type(inits[6]).__name__ == "LogOfColumnIndexInitializerAttrs"
    from flexflow_tpu.pcg.initializer import initialize

    a_log = initialize(inits[6], jax.random.PRNGKey(0), (3, 4), jnp.float32)
    np.testing.assert_allclose(np.exp(a_log), [[1, 2, 3, 4]] * 3, rtol=1e-6)
    batch = lift_to_parallel_with_degrees(x, 1, 1, (2, 1, 1))
    outs = get_parallel_output_shapes(attrs, [batch])
    assert [o.shard_degrees() for o in outs] == [(2, 1, 1), (2, 1, 1)]
    assert all(
        w.discard_copy_degree == 2
        for w in get_parallel_weight_shapes(attrs, [batch])
    )
    with pytest.raises(AssertionError, match="hand-over that is not expressed"):
        get_parallel_output_shapes(
            attrs, [lift_to_parallel_with_degrees(x, 1, 1, (1, 2, 1))]
        )
    with pytest.raises(AssertionError, match="not channel-parallel yet"):
        get_parallel_output_shapes(
            attrs, [lift_to_parallel_with_degrees(x, 1, 2, (1, 1, 1))]
        )


# -- the band in the causal tile schedule -------------------------------------


def dense_attention(q, k, v, heads, window, scale):
    b, s, _ = q.shape
    split = lambda t: t.reshape(b, s, heads, -1)  # noqa: E731
    scores = jnp.einsum("bshd,bthd->bhst", split(q), split(k)) * scale
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    keep = ahead >= 0
    if window is not None:
        keep = keep & (ahead < window)
    probs = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs, split(v)).reshape(b, s, -1)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("window", [1, 50, 128, 200, 511, 512])
def test_banded_kernels_match_the_dense_mask(window, blocks):
    """A window shorter than, equal to and longer than a tile (and one key,
    and the whole length), forward and backward in interpret mode against
    XLA's attention under the dense band mask: float32 sums in another order
    (measured 4e-6)."""
    rs = np.random.RandomState(5)
    heads, s = 2, 512
    q, k, v, cot = (rand(rs, 1, s, heads * 128) for _ in range(4))

    def kernel(q, k, v):
        return jnp.sum(cot * flash.flash_attention_bshf(
            q, k, v, heads, causal=True, block_q=blocks[0], block_k=blocks[1],
            interpret=True, window=window,
        ))

    def dense(q, k, v):
        return jnp.sum(cot * dense_attention(q, k, v, heads, window, 128 ** -0.5))

    got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
    assert_trees_close(got, want, rtol=2e-5, atol=5e-5)


@pytest.mark.parametrize("s,bq,bk,window,want", [
    (2048, 512, 512, None, (10, 4, 16)),  # the parent's schedule
    (4096, 512, 512, None, (36, 8, 64)),
    (4096, 512, 512, 512, (15, 15, 64)),  # the cell's window layer
    (4096, 512, 512, 513, (15, 15, 64)),
    (4096, 512, 512, 1024, (21, 14, 64)),
    (4096, 512, 512, 100, (15, 15, 64)),
    (4096, 512, 512, 1, (8, 8, 64)),
    (4096, 512, 512, 4096, (36, 8, 64)),
    (512, 128, 256, 200, (6, 6, 8)),
])
def test_causal_tile_schedule_counts_the_band(s, bq, bk, window, want):
    assert flash.causal_tile_schedule(s, bq, bk, window) == want
    # the counts are the mask's own: a tile is visited exactly when some
    # pair in it is kept, and masked exactly when some pair in it is not
    ahead = np.arange(s)[:, None] - np.arange(s)[None, :]
    keep = (ahead >= 0) & (ahead < (window or s))
    tiles = keep.reshape(s // bq, bq, s // bk, bk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    assert (some.sum(), (some & ~every).sum()) == want[:2]


def test_no_window_is_the_parents_plan_and_ranges():
    plan = flash.causal_plan(1, 4096, 40, 40, 128, 128, 2)
    assert plan.window is None and plan.fwd_name == "flash_fwd_causal_bshf"
    assert flash.causal_plan(1, 4096, 40, 40, 128, 128, 2, window=4096) == plan
    banded = flash.causal_plan(1, 4096, 40, 40, 128, 128, 2, window=512)
    assert dataclasses.replace(
        banded, window=None, fwd_name=plan.fwd_name, bwd_name=plan.bwd_name
    ) == plan
    assert (banded.fwd_name, banded.bwd_name, banded.delta_name) == (
        "flash_fwd_causal_bshf_window", "flash_bwd_causal_bshf_window",
        "flash_delta_bshf",
    )
    assert flash._causal_k_range(3, 512, 512) == (3, 4)
    assert flash._causal_q_range(3, 512, 512) == (3, 4)
    assert flash._causal_k_range(3, 512, 512, 512) == (2, 3, 3, 4)
    assert flash._causal_q_range(3, 512, 512, 512, 4096) == (3, 4, 4, 5)


def test_a_window_on_a_body_without_a_band_is_an_error():
    q = jnp.zeros((1, 512, 256))
    with pytest.raises(ValueError, match="has no band"):
        flash.flash_attention_bshf(q, q, q, 2, causal=False, window=100)
    with pytest.raises(ValueError, match="has no band"):  # one tile
        flash.flash_attention_bshf(
            q, q, q, 2, causal=True, window=100, block_q=512, block_k=512
        )
    # a plain node takes a window since PR 60; what it does not take
    with pytest.raises(AssertionError, match="differential nodes'"):
        MultiHeadAttentionAttrs(32, 4, kv_outputs=True)
    with pytest.raises(AssertionError, match="latent attention is not lowered"):
        MultiHeadAttentionAttrs(
            32, 4, 12, 8, kv_latent_rank=4, shared_key_dim=4, window=8
        )


# -- differential attention ----------------------------------------------------


def diff_attrs(kind, index, sizes=TOY):
    heads, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    head = sizes["hidden_size"] // heads
    return RingAttentionAttrs(
        sizes["hidden_size"], heads, head, head, bias=True, num_kv_heads=kv,
        differential=True, lambda_init=ref.lambda_init(index),
        diff_norm_eps=sizes["diff_norm_eps"],
        window=sizes["sliding_window"] if kind == "window" else None,
        kv_outputs=kind == "full", external_kv=kind == "cross", causal=True,
    )


def diff_case(kind, index, seed=7, sizes=TOY):
    rs = np.random.RandomState(seed)
    attrs = diff_attrs(kind, index, sizes)
    hidden = sizes["hidden_size"]
    kv_width = sizes["num_key_value_heads"] * (hidden // sizes["num_attention_heads"])
    x = TensorShape((BATCH, SEQ, hidden), DataType.FLOAT)
    kv = TensorShape((BATCH, SEQ, kv_width), DataType.FLOAT)
    inputs = [x, kv, kv] if kind == "cross" else [x, x, x]
    ws = weights_for(attrs, inputs, rs)
    ws[7] = 1.0 + ws[7]  # the sub-norm's gain around one
    u = rand(rs, BATCH, SEQ, hidden)
    handed = (rand(rs, BATCH, SEQ, kv_width), rand(rs, BATCH, SEQ, kv_width))
    return attrs, u, handed, ws


def program_diff(attrs, u, handed, ws):
    inputs = [u, *handed] if attrs.external_kv else [u, u, u]
    with jax.default_matmul_precision("highest"):
        return kernel_forward(attrs, inputs, ws)


def reference_diff(kind, index, u, handed, ws, sizes=TOY):
    named = {f"a.weight{i}": w for i, w in enumerate(ws)}
    with jax.default_matmul_precision("highest"):
        outs = [
            ref.differential_attention(
                named, "a", u[b], sizes, index, kind,
                (handed[0][b], handed[1][b]),
            ) for b in range(u.shape[0])
        ]
    return [jnp.stack([o[k] for o in outs]) for k in range(3)]


KINDS = [("window", 1), ("full", 17), ("cross", 19)]


@pytest.mark.parametrize("kind,index", KINDS)
def test_differential_node_matches_the_reference(kind, index):
    attrs, u, handed, ws = diff_case(kind, index)
    got = program_diff(attrs, u, handed, ws)
    want = reference_diff(kind, index, u, handed, ws)
    assert len(got) == (3 if kind == "full" else 1)
    assert_trees_close(got, want[:len(got)], **F32)


@pytest.mark.parametrize("kind,index", KINDS)
def test_differential_node_gradients_match_the_reference(kind, index):
    attrs, u, handed, ws = diff_case(kind, index)
    rs = np.random.RandomState(8)
    cots = [rand(rs, BATCH, SEQ, 32), rand(rs, BATCH, SEQ, 16), rand(rs, BATCH, SEQ, 16)]

    def loss(f):
        def of(u, handed, ws):
            outs = f(u, handed, ws)
            return sum(jnp.sum(o * c) for o, c in zip(outs, cots))
        return of

    n = 3 if kind == "full" else 1
    got = jax.grad(
        loss(lambda u, handed, ws: program_diff(attrs, u, handed, ws)), (0, 1, 2)
    )(u, handed, ws)
    want = jax.grad(
        loss(lambda u, handed, ws: reference_diff(kind, index, u, handed, ws)[:n]),
        (0, 1, 2),
    )(u, handed, ws)
    if kind != "cross":  # the handed tensors are not read
        got, want = (got[0], got[2]), (want[0], want[2])
    assert_trees_close(got, want, **F32_GRADS)


def test_a_dropped_second_map_is_outside_the_tolerance():
    """The control of the tolerances above: the same node with lambda held
    at zero (plain attention through the sub-norm) is off by far more."""
    attrs, u, handed, ws = diff_case("window", 1)
    got = program_diff(attrs, u, handed, ws)[0]
    dropped = reference_diff(
        "window", 1, u, handed, ws, dict(TOY, drop_second_map=True)
    )[0]
    assert float(jnp.max(jnp.abs(got - dropped))) > 100 * F32["atol"]


def test_the_kernel_core_matches_the_dense_core_with_the_band(monkeypatch, entered):
    """At kernel widths (4 query heads of 64 over a 128-wide value, 1,024
    positions, a 300-key window) the node on the causal tile kernels in
    interpret mode against the same node on XLA's dense attention with the
    band as a mask; the route says the node's kind and window, and the
    tiles' counter what the band skips."""
    sizes = dict(TOY, hidden_size=256, sliding_window=300)
    rs = np.random.RandomState(9)
    attrs = diff_attrs("window", 1, sizes)
    x = TensorShape((1, 1024, 256), DataType.FLOAT)
    ws = weights_for(attrs, [x, x, x], rs, scale=0.1)
    ws[7] = 1.0 + ws[7]
    u = rand(rs, 1, 1024, 256)
    assert mha_core_route(attrs, u.shape, u.shape, u.shape, False) == "dense"
    dense = kernel_forward(attrs, [u, u, u], ws)[0]
    import functools

    entered(context.described_tpu())
    monkeypatch.setattr(
        flash, "flash_attention_bshf",
        functools.partial(flash.flash_attention_bshf, interpret=True),
    )
    assert mha_core_route(attrs, u.shape, u.shape, u.shape, False) == "fused_row"

    with context.lowering_node("ff.ring_attention.attn1"):
        kernels = kernel_forward(attrs, [u, u, u], ws)[0]
    # float32 on both sides; the online softmax sums in another order
    np.testing.assert_allclose(kernels, dense, rtol=2e-5, atol=2e-5)
    assert trace.attention_routes()["ff.ring_attention.attn1"] == (
        "fused_row differential window=300"
    )
    # 1,024 positions in tiles of 512: both tiles of the second q block are
    # under the band's edge or on the diagonal
    assert trace.window_tiles()["ff.ring_attention.attn1"] == (3, 3)


def test_differential_attrs_slots_outputs_and_refusals():
    full = diff_attrs("full", 17)
    cross = diff_attrs("cross", 19)
    x = TensorShape((BATCH, SEQ, 32), DataType.FLOAT)
    kv = TensorShape((BATCH, SEQ, 16), DataType.FLOAT)
    assert num_outputs(full) == 3 and num_outputs(cross) == 1
    assert [s.dims for s in get_output_shapes(full, [x, x, x])] == [
        (BATCH, SEQ, 32), (BATCH, SEQ, 16), (BATCH, SEQ, 16)
    ]
    # W_q | W_k | W_v | W_o flat, the full input bias, b_o, four lambda
    # vectors a head wide, the sub-norm's gain two heads wide
    assert [s.dims for s in get_weight_shapes(full, [x, x, x])] == [
        (32 * 32 + 2 * 32 * 16 + 32 * 32, 1), (32 + 16 + 16,), (32,),
        (8,), (8,), (8,), (8,), (16,),
    ]
    assert [s.dims for s in get_weight_shapes(cross, [x, kv, kv])] == [
        (32 * 32 + 32 * 32, 1), (32,), (32,), (8,), (8,), (8,), (8,), (16,),
    ]
    assert len(get_incoming_tensor_roles(full)) == 3 + 8
    with pytest.raises(AssertionError, match="external keys and values"):
        get_weight_shapes(cross, [x, x, x])
    batch = lift_to_parallel_with_degrees(x, 1, 1, (2, 1, 1))
    outs = get_parallel_output_shapes(full, [batch] * 3)
    assert [o.shard_degrees() for o in outs] == [(2, 1, 1)] * 3
    assert len(get_parallel_weight_shapes(full, [batch] * 3)) == 8
    with pytest.raises(AssertionError, match="grouped-query weight layout"):
        MultiHeadAttentionAttrs(32, 4, differential=True)
    with pytest.raises(AssertionError, match="hands its own keys"):
        diff = diff_attrs("full", 17)
        dataclasses.replace(diff, external_kv=True)
    plain = MultiHeadAttentionAttrs(32, 4)  # the op as it always was
    assert get_output_shapes(plain, [x, x, x])[0].dims == (BATCH, SEQ, 32)
    assert len(get_weight_shapes(plain, [x, x, x])) == 1


# -- the tied head ---------------------------------------------------------------


def test_tied_head_reads_the_embeddings_matrix_and_its_gradients_sum():
    """One weight node, two readers: the gradient of the tied matrix through
    `value_and_grad` of the graph is the sum of the embedding's (a
    scatter-add of the rows looked up) and the head's (h^T dlogits), each
    taken alone on its own copy."""
    from flexflow_tpu.local_execution.training_backing import (
        forward_interpreter,
        init_params,
        param_key,
    )
    from flexflow_tpu.op_attrs.ops import WeightAttrs
    from flexflow_tpu.pcg import ComputationGraphBuilder

    b = ComputationGraphBuilder()
    ids = b.create_input([BATCH, 6], DataType.INT32, name="ids")
    h = b.embedding(ids, 20, 8, name="embed")
    table = b.weight_log[-1]
    logits = b.tied_dense(b.dense(h, 8, use_bias=False, name="mid"), table, name="head")
    graph = b.graph
    weights = [
        n for n in graph.topological_ordering()
        if isinstance(graph.op_attrs(n), WeightAttrs)
    ]
    assert len(weights) == 2  # the table once, and the middle matrix
    head = [
        n for n in graph.topological_ordering()
        if isinstance(graph.op_attrs(n), LinearAttrs)
        and graph.op_attrs(n).weight_transposed
    ]
    assert len(head) == 1
    assert graph.tensor_shape(logits).dims == (BATCH, 6, 20)
    params = init_params(graph, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 20, (BATCH, 6)))
    cot = rand(np.random.RandomState(1), BATCH, 6, 20)
    key = {graph.layer_attrs(n).name: param_key(n) for n in weights}

    def through_graph(params):
        env = forward_interpreter(graph, params, {"ids": tokens})
        return jnp.sum(env[logits] * cot)

    def by_hand(embedding, head_table, mid):
        return jnp.sum(((embedding[tokens] @ mid) @ head_table.T) * cot)

    got = jax.grad(through_graph)(params)
    e = params[key["embed.weight0"]]
    d_embed, d_head, d_mid = jax.grad(by_hand, (0, 1, 2))(
        e, e, params[key["mid.weight0"]]
    )
    np.testing.assert_allclose(
        got[key["embed.weight0"]], d_embed + d_head, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(got[key["mid.weight0"]], d_mid, rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(d_embed))) > 0 and float(jnp.max(jnp.abs(d_head))) > 0
    with pytest.raises(AssertionError, match="takes no bias"):
        LinearAttrs(8, use_bias=True, weight_transposed=True)


# -- the whole tiny step through FFModel ------------------------------------------


def data(seq=SEQ, seed=0):
    return ref.make_data(np.random.RandomState(seed), TOY, BATCH, seq)


def compiled_model(seq=SEQ, compute_dtype=None, sizes=TOY, **config):
    builder, logits = ref.build(sizes, BATCH, seq)
    model = FFModel.from_computation_graph(
        builder, logits,
        FFConfig(batch_size=BATCH, seed=3, print_freq=0, **config),
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
    loss, _ = model.instance.loss_fn(
        model.params, {k: jnp.asarray(v) for k, v in inputs.items()},
        jnp.asarray(labels),
    )
    return float(loss)


def test_layer_kinds_follow_the_published_index():
    assert ref.layer_kinds(TOY) == [
        (0, "scan"), (1, "window"), (16, "scan"), (17, "full"), (18, "gmu"),
        (19, "cross"),
    ]
    whole = dict(TOY, num_hidden_layers=32, held_layers=list(range(32)))
    kinds = [k for _, k in ref.layer_kinds(whole)]
    assert [kinds.count(k) for k in ("scan", "window", "full", "gmu", "cross")] == [
        9, 8, 1, 7, 7
    ]
    assert abs(ref.lambda_init(17) - (0.8 - 0.6 * np.exp(-5.1))) < 1e-12
    with pytest.raises(AssertionError, match="needs the full layer"):
        ref.layer_kinds(dict(TOY, num_hidden_layers=1, held_layers=[19]))


def test_fit_step_matches_reference_adam_step():
    """The whole six-layer step before and after one `fit` step against the
    reference's own gradient and Adam step: 1e-5 is float32 rounding through
    two forward passes and the update. The tied matrix is one weight."""
    model = compiled_model(max_devices=1)
    inputs, labels = data()
    named = bench.named_parameters(model.instance, model.params)
    assert "head.weight0" not in named and named["embed.weight0"].shape == (96, 32)
    assert "attn19.weight0" in named and named["attn19.weight0"].shape == (2048, 1)
    before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    assert abs(system_loss(model, inputs, labels) - before) <= F32_LOSS
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(system_loss(model, inputs, labels) - after) <= F32_LOSS
    assert before - after > 100 * F32_LOSS  # the step did something
    assert abs(before - np.log(96)) < 1.0  # over the slice of the vocabulary
    routes = trace.attention_routes()
    assert routes["ff.ring_attention.attn1"] == "dense differential window=10"
    assert routes["ff.ring_attention.attn17"] == "dense differential"


def test_whole_model_gradients_match_the_reference():
    """Every parameter's gradient of the whole toy model's loss, the tied
    matrix's (both uses), layer 17's key and value weights (read by layers
    17 and 19) and layer 16's scan weights (its memory read by layer 18)
    among them."""
    model = compiled_model(max_devices=1)
    inputs, labels = data()
    named = bench.named_parameters(model.instance, model.params)
    from test_olmoe import weight_keys

    keys = weight_keys(model.instance)
    batch = {k: jnp.asarray(v) for k, v in inputs.items()}
    got = jax.grad(
        lambda p: model.instance.loss_fn(p, batch, jnp.asarray(labels))[0]
    )(model.params)
    rows = (jnp.asarray(inputs["input_ids"]), jnp.asarray(labels))

    def reference(w):
        return sum(
            ref.loss_sum(w, TOY, rows[0][i], rows[1][i]) for i in range(BATCH)
        ) / (BATCH * SEQ)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(reference)(dict(named))
    assert set(want) == set(keys)
    for name, grad in want.items():
        np.testing.assert_allclose(
            got[keys[name]], grad, err_msg=name, **F32_GRADS
        )
        assert float(jnp.max(jnp.abs(grad))) > 0, name


def handed_tensors(model):
    """(graph, the memory tensor, the handed keys, the handed values)."""
    graph = model.instance.cg
    by_name = {
        graph.layer_attrs(n).name: n for n in graph.topological_ordering()
    }
    memory = graph.outputs_of(by_name["s16"])[1]
    keys, values = graph.outputs_of(by_name["attn17"])[1:]
    return graph, by_name, memory, keys, values


def test_handed_tensors_have_their_readers_and_their_cotangents_sum():
    """Layer 16's memory is read by the memory unit of layer 18 and layer
    17's keys and values by layer 19 (beside layer 17 itself, inside the
    node); with a second cross-decoder pair (layers 20, 21) each has two
    readers, and its cotangent is the sum of what each reader sends back:
    the loss is linear in a perturbation e of the handed tensor, so the
    gradient at e = 0 through ALL readers equals the sum of the gradients
    through each reader alone."""
    from flexflow_tpu.local_execution.training_backing import forward_interpreter

    sizes = dict(TOY, num_hidden_layers=8, held_layers=[0, 1, 16, 17, 18, 19, 20, 21])
    model = compiled_model(sizes=sizes, max_devices=1)
    graph, by_name, memory, keys, values = handed_tensors(model)
    readers = {
        t: sorted(graph.layer_attrs(use.node).name for use in graph.uses_of(t))
        for t in (memory, keys, values)
    }
    assert readers[memory] == ["gmu18_gate", "gmu20_gate"]
    assert readers[keys] == readers[values] == ["attn19", "attn21"]
    inputs, labels = ref.make_data(np.random.RandomState(0), sizes, BATCH, SEQ)
    batch = {k: jnp.asarray(v) for k, v in inputs.items()}

    def loss_with(perturbed):
        """The model's loss with `e` added to a handed tensor on its way to
        the readers named (`perturbed`: {tensor: (e, reader names)})."""
        from flexflow_tpu.kernels import forward as op_forward
        from flexflow_tpu.local_execution.training_backing import (
            param_key, split_slot_values,
        )
        from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs

        env = {}
        for n in graph.topological_ordering():
            attrs, outs = graph.op_attrs(n), graph.outputs_of(n)
            name = graph.layer_attrs(n).name
            if isinstance(attrs, InputAttrs):
                env[outs[0]] = batch[name]
            elif isinstance(attrs, WeightAttrs):
                env[outs[0]] = model.params[param_key(n)]
            else:
                slots = [
                    env[v] + perturbed[v][0]
                    if v in perturbed and name in perturbed[v][1] else env[v]
                    for v in graph.inputs_of(n)
                ]
                data_vals, weight_vals = split_slot_values(attrs, slots)
                for o, r in zip(outs, op_forward(attrs, data_vals, weight_vals)):
                    env[o] = r
        from flexflow_tpu.kernels.loss import loss_forward

        return loss_forward(
            model.loss_attrs, env[model.instance.logit_tensor], jnp.asarray(labels)
        )

    for tensor in (memory, keys, values):
        zero = jnp.zeros(graph.tensor_shape(tensor).dims)
        through = lambda names: jax.grad(  # noqa: E731
            lambda e: loss_with({tensor: (e, names)})
        )(zero)
        both = through(readers[tensor])
        alone = [through([name]) for name in readers[tensor]]
        np.testing.assert_allclose(both, alone[0] + alone[1], rtol=1e-5, atol=1e-7)
        assert all(float(jnp.max(jnp.abs(g))) > 0 for g in alone)


def test_bf16_compute_is_inside_its_tolerance_and_outside_float32s():
    """bf16 operands against the float32 reference at the toy size: inside
    the rehearsal's stated tolerance (0.02), and far outside float32's, so
    the comparison can tell the two precisions apart."""
    model = compiled_model(compute_dtype=jnp.bfloat16, max_devices=1)
    inputs, labels = data()
    named = bench.named_parameters(model.instance, model.params)
    before, _ = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    off = abs(system_loss(model, inputs, labels) - before)
    assert 10 * F32_LOSS < off <= 0.02


def test_parameter_count_is_the_configurations():
    """The built model's parameter count at the PUBLISHED widths equals the
    file's `as_built`, term for term (shapes only: nothing is allocated)."""
    sizes = bench.load_json(CONFIG + ".json")
    builder, _ = ref.build(sizes, 1, 512)
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    graph = builder.graph
    count = {}
    for n in graph.topological_ordering():
        if isinstance(graph.op_attrs(n), WeightAttrs):
            name = graph.layer_attrs(n).name.split(".")[0]
            count[name] = count.get(name, 0) + int(
                np.prod(graph.tensor_shape(graph.outputs_of(n)[0]).dims)
            )
    assert count["s0"] == count["s16"] == 41_241_600
    assert count["attn1"] == count["attn17"] == 19_668_864
    assert count["attn19"] == 13_112_704
    assert count["gmu18_w1"] + count["gmu18_w2"] == 26_214_400
    assert sum(v for k, v in count.items() if k.startswith("ffn")) == 471_859_200
    assert sum(v for k, v in count.items() if k.startswith("norm")) == 66_560
    assert count["embed"] == 64_020_480 and "head" not in count
    assert sum(count.values()) == 697_094_272
    assert "697,094,272" in sizes["parameters"]["as_built"]


def test_two_devices_train_the_step_on_the_data_parallel_backend():
    """The step on the data-parallel backend (GSPMD over the batch; what
    `compile` picks without a search budget): the loss is the one-device
    loss and a step reduces it. (Tensors read by many later nodes make the
    PCG no series-parallel graph; the search prices a levelled tree for it
    since PR 62, `tests/test_ouro.py`.)"""
    from flexflow_tpu.parallel.data_parallel import DataParallelTrainingInstance

    from test_olmoe import weight_keys

    inputs, labels = data()
    one = compiled_model(max_devices=1)
    two = compiled_model(max_devices=2)
    assert isinstance(two.instance, DataParallelTrainingInstance)
    named = bench.named_parameters(two.instance, two.params)
    keys = weight_keys(one.instance)
    one.params = {keys[name]: jnp.asarray(np.asarray(w)) for name, w in named.items()}
    first = system_loss(two, inputs, labels)
    assert abs(first - system_loss(one, inputs, labels)) <= F32_LOSS
    two.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert system_loss(two, inputs, labels) < first - 0.01


def test_data_parallel_rules_cover_the_scan_node():
    from flexflow_tpu.substitutions.rules import data_parallel_state_space_rule

    names = {
        data_parallel_state_space_rule(
            2, OperatorType.SELECTIVE_SCAN, memory_output=memory
        ).name for memory in (False, True)
    }
    assert names == {
        "data_parallel_selective_scan_2", "data_parallel_selective_scan_memory_2"
    }
