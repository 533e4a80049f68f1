"""`kernels/moe.held_rows_sum`: a held window's rows summed over each token's
own rows by row copies (PR 50), against the scatter-add it replaces, on the
CPU in Pallas interpret mode; its two sites in `_held_rows_forward` (the
forward's output, the gradient of the node's input) against the XLA form;
the rule that picks the form, and the trace's counter of it.

The four held cells' (decisions a token, held, experts, row width):
`lfm2moe24b_s8192_1chip`, `kimilinear48b_s4096_1chip`,
`twotower30b_s4096_1chip`, `super120b_s4096_1chip`; the kernel's cases run at
the cell's own width with 64 tokens, the node's at toy widths. The kernel
takes bf16 rows (two columns a 32-bit word on their way, `held_rows_lanes`);
float32 compute keeps the scatter-add."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import moe
from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.ops import ExpertsAttrs

CELLS = {
    "lfm2": (4, 8, 64, 2048),
    "kimi": (8, 8, 256, 2304),
    "twotower": (6, 8, 128, 2688),
    "super": (22, 8, 512, 1024),
}
# tokens a step and tokens a program of the kernel in the cells themselves
CELL_TOKENS = {"lfm2": (16384, 256), "kimi": (4096, 64), "twotower": (4096, 64),
               "super": (4096, 32)}
TOKENS = 64


def _window(case, k, rng):
    """(decision [window], valid [window]) of one window of 128 rows over
    `TOKENS` tokens of k decisions each."""
    window = 128
    if case == "collisions":
        # token 0 holds none, 1 one, 2 two, 3 all k, the rest one each at most
        held = [k * 1 + 0, k * 2 + 0, k * 2 + k - 1] + [k * 3 + j for j in range(k)]
        rest = rng.permutation(np.arange(4, TOKENS))[: 40]
        held += [int(t) * k + int(rng.integers(k)) for t in rest]
        held = rng.permutation(held)
    elif case == "every_slot":
        # a tile whose every decision is held: tokens 8..15, all k
        held = rng.permutation(
            [t * k + j for t in range(8, 8 + window // k) for j in range(k)]
        )[:window]
    elif case == "masked_tail":
        held = rng.permutation(TOKENS * k)[:70]
    elif case == "empty":
        held = np.zeros((0,), np.int64)
    else:
        raise AssertionError(case)
    held = np.asarray(held, np.int32)
    decision = np.zeros((window,), np.int32)
    decision[: len(held)] = held
    # past the share's last row the window reads `order` clipped: any decision
    decision[len(held):] = rng.integers(0, TOKENS * k, window - len(held))
    return jnp.asarray(decision), jnp.arange(window) < len(held)


def _scatter_add(src, decision, valid, k, dtype=jnp.float32):
    rows = jnp.where(valid[:, None], src.astype(jnp.float32), 0)
    out = jnp.zeros((TOKENS, src.shape[1]), jnp.float32).at[decision // k].add(rows)
    return out.astype(dtype)


def _sum(src, decision, valid, k, dtype, weight=None):
    return moe.held_rows_sum(
        moe.held_rows_lanes(src, True), *moe._token_order(decision, valid, TOKENS * k),
        weight, TOKENS, k, src.shape[1], dtype, True,
    )


@pytest.mark.parametrize("case", ["collisions", "every_slot", "masked_tail", "empty"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_rows_summed_by_token_equal_the_scatter_add(cell, case):
    """Whole numbers, so that every order of a token's sum is exact: the
    kernel's result is the scatter-add's to the bit, for tokens that hold 0,
    1, 2 and all k rows of the window, a tile whose every slot is held, a
    window's masked tail and a share no decision reached."""
    k, _, _, width = CELLS[cell]
    rng = np.random.default_rng(sum(map(ord, cell + case)))
    decision, valid = _window(case, k, rng)
    src = jnp.asarray(rng.integers(-8, 9, (128, width)), jnp.bfloat16)
    out = _sum(src, decision, valid, k, jnp.float32)
    assert out.shape == (TOKENS, width) and out.dtype == jnp.float32
    np.testing.assert_array_equal(out, _scatter_add(src, decision, valid, k))


@pytest.mark.parametrize("cell", list(CELLS))
def test_rows_are_weighted_in_float32_before_they_are_summed(cell):
    """The forward's site: `weight[row] * row` in float32, a product bf16
    cannot hold (257 x 3), summed; the masked rows' weights are zero."""
    k, _, _, width = CELLS[cell]
    rng = np.random.default_rng(11)
    decision, valid = _window("collisions", k, rng)
    src = jnp.asarray(rng.integers(250, 258, (128, width)), jnp.bfloat16)
    weight = jnp.where(valid, jnp.asarray(rng.integers(1, 4, (128,)), jnp.float32), 0.0)
    out = _sum(src, decision, valid, k, jnp.float32, weight)
    want = jnp.zeros((TOKENS, width), jnp.float32).at[decision // k].add(
        weight[:, None] * src.astype(jnp.float32)
    )
    np.testing.assert_array_equal(out, want)
    assert float(jnp.max(out)) > 256 * 3


@pytest.mark.parametrize("cell", list(CELLS))
def test_bf16_cotangent_is_summed_in_float32_and_rounded_once(cell):
    """bf16 rows whose float32 sum needs more than bf16's eight bits: the
    kernel's bf16 result is the float32 sum rounded once, not a sum of bf16
    partial sums."""
    k, _, _, width = CELLS[cell]
    rng = np.random.default_rng(5)
    decision, valid = _window("collisions", k, rng)
    # 256 + 1 + 1 ...: a bf16 accumulator loses every 1 added to 256
    src = jnp.where(
        (decision == 3 * k)[:, None], 256.0, 1.0
    ).astype(jnp.bfloat16) * jnp.ones((1, width), jnp.bfloat16)
    out = _sum(src, decision, valid, k, jnp.bfloat16)
    want = _scatter_add(src, decision, valid, k, jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        out.astype(jnp.float32), want.astype(jnp.float32)
    )
    # token 3 holds all k: 256 and k - 1 ones, rounded once
    assert float(out[3, 0]) == float(jnp.asarray(255.0 + k, jnp.bfloat16)) > 256.0


def test_a_tokens_rows_are_added_in_the_order_of_its_decisions():
    """Values whose float32 sum depends on the order: the kernel adds a
    token's rows with j ascending, whatever their order in the window."""
    k, width = 4, 128
    values = np.asarray([2.0**30, 1.0, -(2.0**30), 1.0], np.float32)  # bf16 holds them
    decision = jnp.asarray([5 * k + 2, 5 * k + 0, 5 * k + 3, 5 * k + 1] + [0] * 124, jnp.int32)
    valid = jnp.arange(128) < 4
    src = jnp.zeros((128, width), jnp.bfloat16).at[:4, 0].set(
        jnp.asarray(values[[2, 0, 3, 1]], jnp.bfloat16)
    )
    out = _sum(src, decision, valid, k, jnp.float32)
    # ((2^30 + 1) - 2^30) + 1: the first 1 is lost, the last is not
    assert float(out[5, 0]) == 1.0


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_token_tile_fits_the_cells(cell):
    """`_held_sum_tile` at the cells' shapes: k slots a token of a tile
    within `_HELD_SUM_SLOT_BYTES`, a row's word sublanes whole (8, 128)
    tiles of pairs of its 128-lane groups."""
    k, _, _, width = CELLS[cell]
    tokens, tile = CELL_TOKENS[cell]
    pairs, sublanes = moe._word_groups(width)
    assert pairs == -(-(width // 128) // 2) and sublanes % 8 == 0 <= sublanes - pairs < 8
    assert moe._held_sum_tile(tokens, k, width) == tile
    assert 4 * 128 * sublanes * k * tile <= moe._HELD_SUM_SLOT_BYTES
    lanes = moe.held_rows_lanes(jnp.zeros((128, width), jnp.bfloat16), True)
    assert lanes.shape == (128 * sublanes, 128) and lanes.dtype == jnp.uint32


@pytest.mark.parametrize(
    "pallas, tokens, width, dtype, form",
    [
        (True, 4096, 2048, jnp.bfloat16, "pallas"),
        (False, 4096, 2048, jnp.bfloat16, "xla"),  # the CPU mesh, a global-view trace
        (True, 4096, 2048, jnp.float32, "xla"),  # float32 compute
        (True, 4096, 1000, jnp.bfloat16, "xla"),  # no whole 128-lane tiles
        (True, 4100, 2048, jnp.bfloat16, "xla"),  # no tile of tokens divides them
    ],
)
def test_the_form_is_read_from_the_gate_and_the_shape(pallas, tokens, width, dtype, form):
    assert moe._held_sum_form(pallas, tokens, 4, width, dtype) == form


# -- the two sites in `_held_rows_forward` ------------------------------------


def _held_share(cell, variant, seed=0, overflow=False):
    """(attrs, share, x2, flat_e, topv, ws) of a held share at the cell's
    (k, held, experts) and toy widths; `overflow`: every decision of the
    first 40 tokens lands on the share, more than one window."""
    k, held, experts, _ = CELLS[cell]
    hidden, width, out = 128, 64, 128
    gated, biased, capacity = {
        "plain": (False, False, None), "gated": (True, False, None),
        "biased": (False, True, None), "capacity": (True, False, 0.5),
    }[variant]
    attrs = ExpertsAttrs(
        experts, k, width, activation=Activation.SILU, capacity_factor=capacity,
        use_bias=biased, gated=gated,
    )  # the share is handed to `_held_rows_forward` as an expert shard's is
    rng = np.random.default_rng(seed)
    scores = rng.random((TOKENS, experts))
    if overflow:
        scores[:40, :held] += 1.0  # the held experts first, where k allows
    topi = np.argsort(-scores, axis=1)[:, :k].astype(np.int32)
    topv = jnp.asarray(np.take_along_axis(scores, topi, 1), jnp.float32)
    x2 = jnp.asarray(rng.standard_normal((TOKENS, hidden)), jnp.bfloat16)

    def matrix(*shape):
        return jnp.asarray(rng.standard_normal(shape) / 8, jnp.bfloat16)

    ws = {"w1": matrix(held, hidden, width), "w2": matrix(held, width, out)}
    if gated:
        ws["w3"] = matrix(held, hidden, width)
    if biased:
        ws["b1"], ws["b2"] = matrix(held, width), matrix(held, out)
    return attrs, (0, held), x2, jnp.asarray(topi.reshape(-1)), topv, ws


def _routed(attrs, share, x2, flat_e, topv, ws):
    def loss(x2, topv, ws):
        out, here, windows = moe._held_rows_forward(
            attrs, share, x2, flat_e, topv, ws, False
        )
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape))), (
            out, here, windows
        )

    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(x2, topv, ws)


@pytest.fixture
def kernel_form(monkeypatch):
    """The kernel in interpret mode wherever the shape has a tile, the
    grouped matmuls on `ragged_dot`: the form alone differs."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    moe_form = moe._held_sum_form  # the rule itself, the gate as the test says

    def switch(on):
        monkeypatch.setattr(
            moe, "_held_sum_form",
            lambda pallas, n, k, width, dtype: moe_form(on, n, k, width, dtype),
        )

    return switch


@pytest.mark.parametrize("variant", ["plain", "gated", "biased", "capacity"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_held_rows_forward_with_the_kernel_equals_the_xla_form(
    kernel_form, cell, variant
):
    """Value, windows and the gradients in x2, the router's weights and
    every matrix: the kernel at both sites against the scatter-adds, the
    share overflowing its first window (`windows` 2 or more), gated, biased
    and with a capacity factor. To bf16's rounding, not to the bit: XLA's
    CPU backend keeps the experts' outputs in float32 between the grouped
    matmul and the product with the weight where the kernel is handed them
    as the bf16 they are declared (the chip's `gmm` writes bf16 either
    way), and x2's gradient is bf16 in both forms, where the scatter-add
    rounds after every row it adds and the kernel once. The kernel's own
    arithmetic is held to the bit by the cases above."""
    case = _held_share(cell, variant, overflow=True)
    kernel_form(False)
    (_, (want, here, windows)), want_grads = _routed(*case)
    kernel_form(True)
    (_, (got, got_here, got_windows)), got_grads = _routed(*case)
    assert int(windows) == int(got_windows) >= 2
    np.testing.assert_array_equal(here, got_here)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=5e-2)
    assert jax.tree_util.tree_structure(want_grads) == jax.tree_util.tree_structure(got_grads)
    for g_want, g_got in zip(*map(jax.tree_util.tree_leaves, (want_grads, got_grads))):
        assert g_want.dtype == g_got.dtype and g_want.shape == g_got.shape
        scale = float(jnp.max(jnp.abs(g_want.astype(jnp.float32))))
        np.testing.assert_allclose(
            g_got.astype(jnp.float32), g_want.astype(jnp.float32),
            rtol=5e-2, atol=2e-2 * scale,
        )
    assert got_grads[0].dtype == jnp.bfloat16


def test_float32_compute_keeps_the_scatter_add(kernel_form):
    """float32 rows are no two to a word: the rule says `xla` and the
    result is the scatter-add's to the bit."""
    attrs, share, x2, flat_e, topv, ws = _held_share("lfm2", "gated", overflow=True)
    case = (attrs, share, x2.astype(jnp.float32), flat_e, topv,
            {name: w.astype(jnp.float32) for name, w in ws.items()})
    kernel_form(False)
    (_, (want, _, _)), want_grads = _routed(*case)
    kernel_form(True)
    (_, (got, _, _)), got_grads = _routed(*case)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got_grads[0], want_grads[0])


@pytest.mark.parametrize("cell", list(CELLS))
def test_second_window_of_a_share_that_overflows_its_first(kernel_form, cell):
    """Window t = 1 by itself (`_held_window_add` on a zero accumulator):
    the rows past the first window reach their tokens, the first window's
    do not."""
    attrs, share, x2, flat_e, topv, ws = _held_share(cell, "gated", overflow=True)
    k, held = attrs.num_select, share[1]
    key = jnp.where(flat_e < held, flat_e, held)
    order = jnp.argsort(key, stable=True)
    counts = moe._count_keys(key, held + 1)[:held]
    window = moe.held_window_rows(TOKENS * k, held, attrs.num_experts)
    assert int(jnp.sum(counts)) > window
    ws = {name: w.astype(x2.dtype) for name, w in ws.items()}
    zero = jnp.zeros((TOKENS, ws["w2"].shape[-1]), jnp.float32)
    args = (zero, np.int32(1), order, counts, x2, topv.reshape(-1), ws, attrs, False)
    want = moe._held_window_add(*args, ("xla", "xla"))
    kernel_form(True)
    got = moe._held_window_add(*args, ("pallas", "pallas"))
    second = np.zeros((TOKENS,), bool)
    second[np.asarray(order[window:min(2 * window, int(jnp.sum(counts)))]) // k] = True
    assert second.any() and not second.all()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=5e-2)
    assert float(jnp.max(jnp.abs(got[~second]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(got[second]), axis=1))) > 0.0


# -- the counter ------------------------------------------------------------------


def _cell_node(cell):
    """One expert node at the cell's own shapes, as zeros: nothing runs."""
    k, held, experts, width = CELLS[cell]
    tokens, _ = CELL_TOKENS[cell]
    attrs = ExpertsAttrs(
        experts, k, 256, activation=Activation.SILU, capacity_factor=None,
        use_bias=False, gated=True, held_experts=(0, held),
    )
    x = jnp.zeros((tokens, width), jnp.bfloat16)
    weights = [
        jnp.zeros((width, experts), jnp.bfloat16),
        jnp.zeros((held, width, 256), jnp.bfloat16),
        jnp.zeros((held, width, 256), jnp.bfloat16),
        jnp.zeros((held, 256, width), jnp.bfloat16),
    ]

    def loss(x, weights):
        return jnp.sum(moe.experts_forward(attrs, x, weights)[0].astype(jnp.float32))

    return jax.grad(loss, argnums=(0, 1)), x, weights


@pytest.mark.parametrize("cell", list(CELLS))
def test_counter_says_pallas_for_both_sums_at_the_cells_shapes(monkeypatch, cell):
    """`trace.held_row_sums()` after an expert node is traced with the gate
    forced (nothing runs): `pallas` for the forward's sum and the
    backward's, the window's rows, the row's width and dtype and the token
    tile (of the first window, which every step runs); the `held_rows_sum`
    kernel in the traced program. On the CPU
    mesh, as it is, both say `xla` and the program has none."""
    from flexflow_tpu.kernels import flash_attention as flash
    from flexflow_tpu.observability import trace

    k, held, experts, width = CELLS[cell]
    tokens, tile = CELL_TOKENS[cell]
    window = moe.held_window_rows(tokens * k, held, experts)
    grad, x, weights = _cell_node(cell)
    monkeypatch.setattr(trace, "_HELD_ROW_SUMS", {})
    monkeypatch.setattr(trace._lowering, "scope", "ff.experts.on_xla", raising=False)
    text = str(jax.make_jaxpr(grad)(x, weights))
    assert "held_rows_sum" not in text
    assert len(re.findall(r"(f32|bf16)\[\d+,\d+\] = scatter-add", text)) == 2
    assert trace.held_row_sums() == {
        "ff.experts.on_xla": {
            site: {"form": "xla", "window_rows": window, "width": width,
                   "dtype": "bfloat16", "sum_dtype": dtype, "token_tile": None}
            for site, dtype in (("forward", "float32"), ("backward", "bfloat16"))
        }
    }

    monkeypatch.setattr(flash, "_backend_ok", lambda allow_interpret=False: True)
    monkeypatch.setattr(trace._lowering, "scope", "ff.experts.e1")
    grad, x, weights = _cell_node(cell)  # a new function: traced anew
    text = str(jax.make_jaxpr(grad)(x, weights))
    assert trace.held_row_sums()["ff.experts.e1"] == {
        site: {"form": "pallas", "window_rows": window, "width": width,
               "dtype": "bfloat16", "sum_dtype": dtype, "token_tile": tile}
        for site, dtype in (("forward", "float32"), ("backward", "bfloat16"))
    }
    assert "name=held_rows_sum" in text and "name=held_rows_lanes" in text
    # megablox counts its tiles with scatter-adds of integers; of rows one is
    # left, in the backward loop's body: a LATER window's gradient of x2
    assert not re.search(r"f32\[\d+,\d+\] = scatter-add", text)
    assert len(re.findall(r"bf16\[\d+,\d+\] = scatter-add", text)) == 1


def test_counter_says_xla_for_a_width_of_no_whole_lane_tiles(monkeypatch):
    from flexflow_tpu.kernels import flash_attention as flash
    from flexflow_tpu.observability import trace

    attrs = ExpertsAttrs(
        16, 2, 256, activation=Activation.SILU, capacity_factor=None,
        use_bias=False, gated=False, held_experts=(0, 4),
    )
    x = jnp.zeros((1024, 192), jnp.bfloat16)
    weights = [
        jnp.zeros((192, 16), jnp.bfloat16),
        jnp.zeros((4, 192, 256), jnp.bfloat16),
        jnp.zeros((4, 256, 192), jnp.bfloat16),
    ]
    monkeypatch.setattr(trace, "_HELD_ROW_SUMS", {})
    monkeypatch.setattr(flash, "_backend_ok", lambda allow_interpret=False: True)
    monkeypatch.setattr(trace._lowering, "scope", "ff.experts.odd", raising=False)
    text = str(jax.make_jaxpr(
        lambda x, weights: moe.experts_forward(attrs, x, weights)[0]
    )(x, weights))
    noted = trace.held_row_sums()["ff.experts.odd"]
    assert [noted[site]["form"] for site in ("forward", "backward")] == ["xla", "xla"]
    assert "held_rows_sum" not in text
    assert re.search(r"f32\[1024,192\] = scatter-add", text)
