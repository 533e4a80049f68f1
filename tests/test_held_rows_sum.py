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

from flexflow_tpu.kernels import context
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


# -- the row stages stop at the share's last row (PR 61) ------------------------

LIVE_K, LIVE_HELD, LIVE_EXPERTS = 4, 8, 32
# a pass of 384 rows, twice the 192 a uniform router sends (`_window_stages`
# bounds the stages of a pass half again its share or more), in three tiles
# of 128: a share under a
# tile, one that ends inside the second, one that fills the pass to the row,
# one that takes a second pass (66 rows of it), and a share no decision reached
LIVE_SHARES = {"under_a_tile": 40, "inside_a_tile": 200, "fills_the_pass": 384,
               "second_pass": 450, "empty": 0}
# a held share has no biases (`ExpertsAttrs`): an expert shard's pass is a
# quarter over its share, and half again it only where that is under a tile: 64
# tokens, a pass of ONE tile of 128 rows for a uniform 64
BIASED_SHARES = {"under_a_tile": 40, "inside_a_tile": 100, "fills_the_pass": 128,
                 "second_pass": 150, "empty": 0}


def _live_sizes(variant):
    """(tokens, the pass's rows, the shares) of a variant's cases."""
    return (64, 128, BIASED_SHARES) if variant == "biased" else (192, 384, LIVE_SHARES)


def _live_share(share, variant, seed=0):
    """(attrs, share, x2, flat_e, topv, ws) with the case's count of decisions
    on the share's experts, at widths the grouped matmul's kernels take."""
    gated, biased, activation = {
        "gated": (True, False, Activation.SILU),  # the five gated held cells'
        "relu2": (False, False, Activation.RELU2),  # TwoTower's and Super's
        "biased": (False, True, Activation.SILU),
        "product": (True, False, None),
    }[variant]
    tokens, _, shares = _live_sizes(variant)
    rows = shares[share]
    attrs = ExpertsAttrs(
        LIVE_EXPERTS, LIVE_K, 128, activation=activation,
        capacity_factor=None, use_bias=biased, gated=gated,
        **({} if biased else dict(held_experts=(0, LIVE_HELD), held_window_factor=2.0)),
    )
    rng = np.random.default_rng(seed)
    decisions = tokens * LIVE_K
    flat_e = rng.integers(LIVE_HELD, LIVE_EXPERTS, decisions)
    flat_e[rng.permutation(decisions)[:rows]] = rng.integers(0, LIVE_HELD, rows)
    topv = jnp.asarray(rng.random((tokens, LIVE_K)), jnp.float32)
    x2 = jnp.asarray(rng.standard_normal((tokens, 128)), jnp.bfloat16)

    def matrix(*shape):
        return jnp.asarray(rng.standard_normal(shape) / 8, jnp.bfloat16)

    ws = {"w1": matrix(LIVE_HELD, 128, 128), "w2": matrix(LIVE_HELD, 128, 128)}
    if gated:
        ws["w3"] = matrix(LIVE_HELD, 128, 128)
    if biased:
        ws["b1"], ws["b2"] = matrix(LIVE_HELD, 128), matrix(LIVE_HELD, 128)
    return attrs, (0, LIVE_HELD), x2, jnp.asarray(flat_e, jnp.int32), topv, ws


_ON_KERNELS = {}  # (rows, variant) -> the compiled step, every share's


def _routed_on_kernels(rows, variant, attrs, share, x2, flat_e, topv, ws):
    """`_routed` with the gate open (every kernel interpreted) and the row
    stages `rows` ("live": the rule; "window": the parent's form, the fills,
    the mask, XLA's passes over the whole window), compiled once a variant
    with XLA's default flags. The routing is an argument, so one executable
    runs every share."""
    def loss(x2, topv, ws, flat_e):
        out, here, windows = moe._held_rows_forward(
            attrs, share, x2, flat_e, topv, ws, True
        )
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape))), (
            out, here, windows
        )

    if (rows, variant) not in _ON_KERNELS:
        rule = moe._window_stages
        if rows == "window":
            moe._window_stages = lambda *args: dict.fromkeys(rule(*args), "window")
        try:
            for traced_once in (moe._held_window_add, moe._held_window_grads):
                traced_once.clear_cache()  # the rule is read while tracing
            step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
            _ON_KERNELS[rows, variant] = step.lower(x2, topv, ws, flat_e).compile()
        finally:
            moe._window_stages = rule
            for traced_once in (moe._held_window_add, moe._held_window_grads):
                traced_once.clear_cache()
    return _ON_KERNELS[rows, variant](x2, topv, ws, flat_e)


@pytest.fixture
def on_kernels(kernel_form):
    """Both sums on the kernel, in interpret mode."""
    kernel_form(True)


# a stage kernel computes a tile in float32 and rounds where it stores; the
# window's form is XLA's bf16 chain. One rounded operation a value (a squared
# relu, the gate's product alone) is the same number either way; a SiLU is
# several, which XLA's CPU backend rounds one by one
ONE_ROUNDING = ("relu2", "product")


@pytest.mark.parametrize("variant", ["gated", "relu2", "biased", "product"])
@pytest.mark.parametrize("share", list(LIVE_SHARES))
def test_row_stages_that_stop_at_the_live_rows_equal_the_window_form(
    on_kernels, share, variant
):
    """The output, the windows and the gradients of x2, the router's weights
    and every matrix and bias: the stages that stop at the share's last row
    (no fill, no mask, the kernels of `_live_rows_call`) against the form
    over the whole window (the fills, the mask, XLA's passes), on the same
    grouped matmuls and sums, in interpret mode, where a row no kernel wrote
    reads NaN. To the BIT where a value is rounded once either way
    (`ONE_ROUNDING`), to bf16's rounding under a SiLU (the bounds of
    `test_held_rows_forward_with_the_kernel_equals_the_xla_form`; the
    stage's own arithmetic is held to the bit by
    `test_stage_kernels_round_once_where_they_store`)."""
    case = _live_share(share, variant)
    tokens, window, shares = _live_sizes(variant)
    assert window == moe.held_window_rows(
        tokens * LIVE_K, LIVE_HELD, LIVE_EXPERTS, case[0].held_window_factor
    )
    (_, (want, here, windows)), want_grads = _routed_on_kernels("window", variant, *case)
    (_, (got, got_here, got_windows)), got_grads = _routed_on_kernels("live", variant, *case)
    assert int(windows) == int(got_windows) == max(1, -(-shares[share] // window))
    assert int(jnp.sum(here)) == shares[share]
    np.testing.assert_array_equal(here, got_here)
    exact = variant in ONE_ROUNDING
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=5e-2)
    assert jax.tree_util.tree_structure(want_grads) == jax.tree_util.tree_structure(got_grads)
    for g_want, g_got in zip(*map(jax.tree_util.tree_leaves, (want_grads, got_grads))):
        assert g_want.dtype == g_got.dtype and g_want.shape == g_got.shape
        g_want, g_got = g_want.astype(jnp.float32), g_got.astype(jnp.float32)
        if exact:
            np.testing.assert_array_equal(g_got, g_want)
        else:
            scale = float(jnp.max(jnp.abs(g_want)))
            np.testing.assert_allclose(g_got, g_want, rtol=5e-2, atol=2e-2 * scale)
    if shares[share]:
        assert float(jnp.max(jnp.abs(got))) > 0.0


@pytest.mark.parametrize("variant", ["gated", "relu2", "biased", "product"])
def test_stage_kernels_round_once_where_they_store(on_kernels, variant):
    """`experts_hidden_fwd` and `experts_hidden_bwd` on the rows up to the
    live count, to the bit: `_expert_hidden` and its `jax.vjp` in float32 on
    the bf16 operands, each result rounded to bf16 once (what XLA's fusion
    of the chain computes on the chip). 200 of 384 rows live: the tile the
    last row shares is written whole, the tile past it not at all."""
    attrs = _live_share("inside_a_tile", variant)[0]
    rng = np.random.default_rng(7)

    def rows():
        return jnp.asarray(rng.standard_normal((384, 128)), jnp.bfloat16)

    h1, g = rows(), rows()
    h3 = rows() if attrs.gated else None
    c1 = rows() if attrs.use_bias else None
    given = [a for a in (h1, h3, c1) if a is not None]

    def wide(*given):
        blocks = iter(a.astype(jnp.float32) for a in given)
        return moe._expert_hidden(
            attrs, next(blocks), next(blocks) if attrs.gated else None,
            next(blocks) if attrs.use_bias else None,
        )

    want, transpose = jax.vjp(wide, *given)
    got, got_transpose = jax.vjp(
        lambda h1, h3, c1: moe._live_hidden(attrs, jnp.int32(200), h1, h3, c1),
        h1, h3, c1,
    )
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        got[:200].astype(jnp.float32), want[:200].astype(jnp.bfloat16).astype(jnp.float32)
    )
    assert bool(jnp.all(jnp.isnan(got[256:].astype(jnp.float32))))
    got_grads = [a for a in got_transpose(g) if a is not None]
    for g_want, g_got in zip(transpose(g.astype(jnp.float32)), got_grads):
        assert g_got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            g_got[:200].astype(jnp.float32), g_want[:200].astype(jnp.float32)
        )
    # a gradient is written over its operand: past the live tiles it is the operand
    np.testing.assert_array_equal(got_grads[0][256:], h1[256:])


def test_no_row_past_the_live_count_reaches_an_output_or_a_gradient(
    monkeypatch, on_kernels
):
    """Every intermediate's rows past the share's last are NaN: the gathered
    rows and their cotangent's, each grouped matmul's, each stage kernel's
    (interpret mode leaves NaN where a kernel wrote nothing; the wrappers
    here poison what XLA gathered and the partly live tile as well). The
    window's own `y` shows them; the node's output and gradients hold none."""
    attrs, share, x2, flat_e, topv, ws = _live_share("inside_a_tile", "gated")

    def poisoned(fn, live_of):
        def run(*args, **kwargs):
            live = live_of(*args, **kwargs)
            dead = (jnp.arange(384) >= live)[:, None]
            return jax.tree_util.tree_map(
                lambda a: jnp.where(dead, jnp.nan, a) if a.shape[0] == 384 else a,
                fn(*args, **kwargs),
            )
        return run

    monkeypatch.setattr(moe, "_live_rows_call", poisoned(
        moe._live_rows_call, lambda body, name, live, *rest, **over: live
    ))
    monkeypatch.setattr(moe, "_grouped_matmul", poisoned(
        moe._grouped_matmul, lambda rows, w, sizes, pallas: jnp.sum(sizes[:w.shape[0]])
    ))
    monkeypatch.setattr(moe, "_window_rows", poisoned(
        moe._window_rows, lambda x2, token, valid, live, *rest: live
    ))

    key = jnp.where(flat_e < share[1], flat_e, share[1])
    order = jnp.argsort(key, stable=True)
    counts = moe._count_keys(key, share[1] + 1)[:share[1]]
    w = moe._held_window(
        np.int32(0), order, counts, x2, topv.reshape(-1),
        {name: m.astype(x2.dtype) for name, m in ws.items()}, attrs, True,
        ("pallas", "pallas"),
    )
    assert int(w["live"]) == 200
    assert bool(jnp.all(jnp.isnan(w["y"][200:].astype(jnp.float32))))
    assert not bool(jnp.any(jnp.isnan(w["y"][:200].astype(jnp.float32))))
    (value, (out, _, _)), grads = _routed_on_kernels(
        "live", "poisoned", attrs, share, x2, flat_e, topv, ws
    )
    del _ON_KERNELS["live", "poisoned"]
    for leaf in jax.tree_util.tree_leaves((value, out, grads)):
        assert not bool(jnp.any(jnp.isnan(leaf.astype(jnp.float32))))


# -- the counter ------------------------------------------------------------------


STAGES = ("rows_in", "zero_fill", "elementwise", "lanes")


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
def test_counter_says_pallas_for_both_sums_at_the_cells_shapes(
    monkeypatch, cell, entered
):
    """`trace.kernel_choices("held_row_sums")` after an expert node is traced
    with the gate forced (nothing runs): `pallas` for the forward's sum and the
    backward's, the window's rows, the row's width and dtype and the token
    tile (of the first window, which every step runs); the `held_rows_sum`
    kernel in the traced program. On the CPU
    mesh, as it is, both say `xla` and the program has none."""
    from flexflow_tpu.observability import trace

    k, held, experts, width = CELLS[cell]
    tokens, tile = CELL_TOKENS[cell]
    window = moe.held_window_rows(tokens * k, held, experts)
    grad, x, weights = _cell_node(cell)
    monkeypatch.setattr(context, "_CHOICES", {})
    entered(context.lowering_node("ff.experts.on_xla"))
    text = str(jax.make_jaxpr(grad)(x, weights))
    assert "held_rows_sum" not in text
    assert len(re.findall(r"(f32|bf16)\[\d+,\d+\] = scatter-add", text)) == 2
    noted = trace.kernel_choices("held_row_sums")
    assert list(noted) == ["ff.experts.on_xla"]
    assert noted["ff.experts.on_xla"].pop("stages") == dict.fromkeys(STAGES, "window")
    assert noted == {
        "ff.experts.on_xla": {
            site: {"form": "xla", "window_rows": window, "width": width,
                   "dtype": "bfloat16", "sum_dtype": dtype, "token_tile": None}
            for site, dtype in (("forward", "float32"), ("backward", "bfloat16"))
        }
    }

    entered(context.described_tpu())
    entered(context.lowering_node("ff.experts.e1"))
    grad, x, weights = _cell_node(cell)  # a new function: traced anew
    text = str(jax.make_jaxpr(grad)(x, weights))
    noted = trace.kernel_choices("held_row_sums")["ff.experts.e1"]
    # a quarter over the uniform share: the parent's forms over the window
    assert noted.pop("stages") == dict.fromkeys(STAGES, "window")
    assert noted == {
        site: {"form": "pallas", "window_rows": window, "width": width,
               "dtype": "bfloat16", "sum_dtype": dtype, "token_tile": tile}
        for site, dtype in (("forward", "float32"), ("backward", "bfloat16"))
    }
    assert "name=experts_" not in text  # the stages' kernels
    assert "name=held_rows_sum" in text and "name=held_rows_lanes" in text
    # megablox counts its tiles with scatter-adds of integers; of rows one is
    # left, in the backward loop's body: a LATER window's gradient of x2
    assert not re.search(r"f32\[\d+,\d+\] = scatter-add", text)
    assert len(re.findall(r"bf16\[\d+,\d+\] = scatter-add", text)) == 1


def test_counter_says_xla_for_a_width_of_no_whole_lane_tiles(monkeypatch, entered):
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
    monkeypatch.setattr(context, "_CHOICES", {})
    entered(context.described_tpu())
    entered(context.lowering_node("ff.experts.odd"))
    text = str(jax.make_jaxpr(
        lambda x, weights: moe.experts_forward(attrs, x, weights)[0]
    )(x, weights))
    noted = trace.kernel_choices("held_row_sums")["ff.experts.odd"]
    assert [noted[site]["form"] for site in ("forward", "backward")] == ["xla", "xla"]
    assert noted["stages"] == dict.fromkeys(STAGES, "window")
    assert "held_rows_sum" not in text
    assert re.search(r"f32\[1024,192\] = scatter-add", text)


@pytest.mark.parametrize(
    "case, pallas, forms, hidden, factor, stages",
    [
        # Mellum2's passes: 2.25 times the uniform share
        ("a_generous_pass", True, ("pallas", "pallas"), 1024, 2.25,
         dict.fromkeys(STAGES, "live")),
        # where the stages were measured against XLA's forms on the chip
        ("half_again_the_share", True, ("pallas", "pallas"), 1024, 1.5,
         dict.fromkeys(STAGES, "live")),
        # every held graph's rule but Mellum2's: a quarter over the share
        ("a_quarter_over", True, ("pallas", "pallas"), 1024, None,
         dict.fromkeys(STAGES, "window")),
        ("under_half_again", True, ("pallas", "pallas"), 1024, 1.4,
         dict.fromkeys(STAGES, "window")),
        # a later window's gradient of x2 is the scatter-add: its mask stays
        ("a_later_window", True, ("pallas", "xla"), 1024, 2.25,
         dict(dict.fromkeys(STAGES, "live"), rows_in="window")),
        ("no_gate", False, ("pallas", "pallas"), 1024, 2.25,
         dict.fromkeys(STAGES, "window")),
        ("float32_or_odd_width", True, ("xla", "xla"), 1024, 2.25,
         dict.fromkeys(STAGES, "window")),
        # a matrix the grouped matmul's kernels do not take (`_gmm_tile`)
        ("no_tile", True, ("pallas", "pallas"), 1000, 2.25,
         dict.fromkeys(STAGES, "window")),
    ],
)
def test_the_row_stages_are_read_from_the_gate_the_forms_the_pass_and_the_matrices(
    case, pallas, forms, hidden, factor, stages
):
    attrs = ExpertsAttrs(
        64, 8, hidden, activation=Activation.SILU, capacity_factor=None,
        use_bias=False, gated=True, held_experts=(0, 16), held_window_factor=factor,
    )
    ws = {
        "w1": jnp.zeros((16, 2304, hidden), jnp.bfloat16),
        "w3": jnp.zeros((16, 2304, hidden), jnp.bfloat16),
        "w2": jnp.zeros((16, hidden, 2304), jnp.bfloat16),
    }
    assert moe._window_stages(pallas, forms, attrs, 16, 65536, ws) == stages


def test_counter_says_live_for_the_stages_of_a_generous_pass(monkeypatch, entered):
    """Mellum2's expert node (16 of 64 held, 8 a token, passes of 2.25 times
    the uniform share: 36,864 rows for 16,384) traced with the gate forced:
    `trace.kernel_choices("held_row_sums")` says `live` for every row stage
    and the traced program holds their kernels, and no select of megablox's
    over a window's rows; on the CPU mesh it says `window`."""
    from flexflow_tpu.observability import trace

    attrs = ExpertsAttrs(
        64, 8, 896, activation=Activation.SILU, capacity_factor=None,
        use_bias=False, gated=True, renormalize=True, held_experts=(0, 16),
        held_window_factor=2.25,
    )
    x = jnp.zeros((8192, 2304), jnp.bfloat16)
    weights = [jnp.zeros((2304, 64), jnp.bfloat16)] + [
        jnp.zeros(shape, jnp.bfloat16)
        for shape in ((16, 2304, 896), (16, 2304, 896), (16, 896, 2304))
    ]

    def grad():
        def loss(x, weights):
            return jnp.sum(moe.experts_forward(attrs, x, weights)[0].astype(jnp.float32))

        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, weights))

    monkeypatch.setattr(context, "_CHOICES", {})
    entered(context.lowering_node("ff.experts.on_xla"))
    assert "name=experts_" not in grad()
    assert trace.kernel_choices("held_row_sums")["ff.experts.on_xla"]["stages"] == dict.fromkeys(
        STAGES, "window"
    )
    entered(context.described_tpu())
    entered(context.lowering_node("ff.experts.moe0"))
    for traced_once in (moe._held_window_add, moe._held_window_grads):
        traced_once.clear_cache()  # the gate is read while tracing
    text = grad()
    noted = trace.kernel_choices("held_row_sums")["ff.experts.moe0"]
    assert noted["stages"] == dict.fromkeys(STAGES, "live")
    assert noted["forward"]["window_rows"] == 36864
    for kernel in ("experts_hidden_fwd", "experts_hidden_bwd", "experts_cotangent"):
        assert f"name={kernel}" in text
    assert not re.search(r"bf16\[36864,\d+\] = select_n", text.split("while[")[0])
