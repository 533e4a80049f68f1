"""The norm and the rotary of a plain attention node's fused row as two
Pallas kernels behind one `custom_vjp` (`kernels/norm_rotary.norm_rotary`,
chosen by `kernels/ops.between_form`), in interpret mode on the CPU: the
forward against the plain form (`rms_norm`, then `rope_bshf`) in the model's
dtype and every cotangent against `jax.vjp` of the plain form, at the node
forms of the five cells that have a norm or a rotary between projection and
core; the rule's answers case by case; and what the lowering costs, with no
clock: a body whose equations do not follow the head count, one trace for q
and k of one shape and for every application of a looped block, a pinned
count of `pallas_call`s a node."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import flash_attention as flash
from flexflow_tpu.kernels import norm_rotary as nr
from flexflow_tpu.kernels import ops
from flexflow_tpu.op_attrs.ops import RingAttentionAttrs, YarnScaling

YARN = YarnScaling(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
PER_HEAD = dict(qk_norm_eps=1e-6, qk_norm_per_head=True)
# the cells' node forms at cut rows and head counts: (heads, head size, attrs)
FORMS = {
    # Mellum2's window and full nodes: a group of 8 over one key/value head,
    # the per-head norm, the default rotary and the YaRN one
    "mellum2_default": (8, 128, dict(
        PER_HEAD, rope_theta=500000.0, num_kv_heads=1)),
    "mellum2_yarn": (8, 128, dict(
        PER_HEAD, rope_theta=500000.0, num_kv_heads=1, rope_scaling=YARN)),
    # Ouro's: a rotary and no norm
    "ouro_rotary_alone": (4, 128, dict(rope_theta=1e6)),
    # OLMoE's: the norm over the whole row
    "olmoe_row_norm": (4, 128, dict(rope_theta=1e4, qk_norm_eps=1e-5)),
    # LFM2's: two heads of 64 a lane tile
    "lfm2_heads_of_64": (8, 64, dict(
        PER_HEAD, rope_theta=1e6, num_kv_heads=2)),
    # Qwen3-Next's heads and gain (1 + w) with the rotary over the whole
    # head: the cell's own node, whose rotary turns 64 of 256 columns,
    # keeps the plain form (`test_rule` below)
    "heads_of_256_zero_centred": (2, 256, dict(
        PER_HEAD, rope_theta=1e7, num_kv_heads=1, qk_norm_zero_centered=True)),
    # rows that are no power of two of lanes, taken a head at a time: 12
    # query heads (1,536 lanes) over a key row of 768
    "twelve_heads_over_a_key_row_of_768": (12, 128, dict(
        PER_HEAD, rope_theta=1e4, num_kv_heads=6)),
}
ROWS = 384


def attrs_of(form, **more):
    heads, d, fields = FORMS[form]
    return RingAttentionAttrs(256, heads, d, d, causal=True, **fields, **more)


def operands(attrs, dtype, rows=ROWS, batch=2, heads=None):
    rs = np.random.RandomState(7)
    d, centre = attrs.q_proj_size, 0.0 if attrs.qk_norm_zero_centered else 1.0

    def rand(*shape, scale=1.0, shift=0.0, dtype=dtype):
        return jnp.asarray(shift + scale * rs.standard_normal(shape), dtype)

    q = rand(batch, rows, (heads or attrs.num_heads) * d)
    k = rand(batch, rows, (heads or attrs.kv_heads) * d)
    gains = None
    if attrs.qk_norm:
        widths = (d, d) if attrs.qk_norm_per_head else (q.shape[-1], k.shape[-1])
        gains = tuple(
            rand(w, scale=0.3, shift=centre, dtype=jnp.float32) for w in widths
        )
    return q, k, gains, (rand(*q.shape), rand(*k.shape))


def plain(attrs, q, k, gains):
    return ops.mha_between(attrs, q, k, k, gains, repeat=False)[:2]


def kernels(attrs, q, k, gains):
    return ops.mha_between(
        attrs, q, k, k, gains, repeat=False, form=ops._pass_form(attrs)
    )[:2]


def outputs_and_cotangents(form_fn, attrs, q, k, gains, cots):
    out, vjp = jax.vjp(functools.partial(form_fn, attrs), q, k, gains)
    return out, vjp(cots)


def fresh_traces():
    """Forget the jitted wrappers' traces (they are kept by shapes and static
    parameters alone, not by the module constants a test patches)."""
    nr._forward.clear_cache()
    nr._backward.clear_cache()


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")


def ulps_apart(got, want):
    """(elements that differ, the furthest apart in representable values of
    the dtype: the floats' bit patterns, ordered, subtracted)."""
    ints = {2: np.int16, 4: np.int32}[np.asarray(want).dtype.itemsize]

    def ordered(t):
        bits = np.asarray(t).view(ints).astype(np.int64)
        return np.where(bits < 0, -(bits & np.iinfo(ints).max), bits)

    steps = np.abs(ordered(got) - ordered(want))
    return int((steps > 0).sum()), int(steps.max())


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", list(FORMS))
def test_forward_is_the_plain_forms_in_the_models_dtype(interpreted, form, dtype):
    """y of q and of k: float32 inside, the norm's result rounded before the
    rotary reads it, the result rounded once, as `rms_norm` and `rope_bshf`
    do. What XLA's CPU backend leaves open is whether a product and a sum
    are one fused operation: in bf16 at most one element in ten thousand
    lies a step of the dtype away, in float32 the last bits differ."""
    attrs = attrs_of(form)
    assert ops.between_form(attrs, "fused_row", ROWS) == (
        "pallas", ops._pass_form(attrs)
    )
    q, k, gains, _ = operands(attrs, jnp.dtype(dtype))
    for got, want in zip(kernels(attrs, q, k, gains), plain(attrs, q, k, gains)):
        assert got.shape == want.shape and got.dtype == want.dtype
        if dtype == "bfloat16":
            # (where the rotary's two products cancel, a last bit of float32
            # is several steps of the small result: those are held to 1e-6)
            assert ulps_apart(got, want)[0] <= 1e-4 * want.size
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                rtol=2.0 ** -7, atol=1e-6,
            )
        else:
            np.testing.assert_allclose(got, want, rtol=4e-6, atol=4e-6)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("form", list(FORMS))
def test_cotangents_are_jax_vjps_of_the_plain_form(interpreted, form, dtype):
    """The cotangents of q, k and both gains from the WRITTEN backward
    against `jax.vjp` of the plain form: float32 sums in another order, and
    in bf16 the roundings the plain form's transpose makes (the cotangent of
    the norm's result, dx)."""
    attrs = attrs_of(form)
    case = operands(attrs, jnp.dtype(dtype))
    _, got = outputs_and_cotangents(kernels, attrs, *case)
    _, want = outputs_and_cotangents(plain, attrs, *case)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    leaves = zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))
    for g, w in leaves:
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol * np.abs(w).max())
    if dtype == "bfloat16":
        # dq and dk round where the plain form's do: all but a few elements
        # in ten thousand are the same bf16 value
        for g, w in zip(got[:2], want[:2]):
            assert ulps_apart(g, w)[0] <= 1e-3 * w.size


@pytest.mark.parametrize("form", ["mellum2_default", "olmoe_row_norm",
                                  "ouro_rotary_alone"])
def test_rows_that_are_no_multiple_of_the_block(interpreted, form):
    """200 rows: padded with zeros to 256 (a zero row adds nothing to the
    gain's sum) and cut off again, forward and backward."""
    attrs = attrs_of(form)
    assert ops.between_form(attrs, "fused_row", 200)[0] == "pallas"
    assert nr.pass_plan(200, 512, ops._pass_form(attrs)).pad == 56
    case = operands(attrs, jnp.float32, rows=200)
    got = outputs_and_cotangents(kernels, attrs, *case)
    want = outputs_and_cotangents(plain, attrs, *case)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape
        np.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(w)))
        )


@pytest.mark.parametrize("form", ["mellum2_default", "olmoe_row_norm",
                                  "lfm2_heads_of_64"])
def test_a_block_walked_in_several_steps(monkeypatch, interpreted, form):
    """16 or 32 rows a step on blocks of 512 rows: the loop's later steps
    read their own rows of x, dy and the tables, and the gain's partial sums
    add up over the steps and the programs."""
    monkeypatch.setattr(nr, "_STEP", 32 * 128)
    attrs = attrs_of(form)
    plan = nr.pass_plan(512, 512, ops._pass_form(attrs))
    assert plan.block == 512 and plan.step in (16, 32)
    case = operands(attrs, jnp.float32, rows=512, batch=1)
    fresh_traces()
    got = outputs_and_cotangents(kernels, attrs, *case)
    fresh_traces()
    want = outputs_and_cotangents(plain, attrs, *case)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=2e-5, atol=2e-5 * float(jnp.max(jnp.abs(w)))
        )


def test_lane_tables_are_a_position_wide_and_hold_the_plain_rotary():
    """`rope_lane_tables`: float32 [s, max(d, 128)], a head's lanes cos | cos
    and -sin | sin, heads of 64 twice, YaRN's amplitude on both."""
    cos, sin = ops.rope_lane_tables(24, 128, 1e4)
    assert cos.shape == sin.shape == (24, 128) and cos.dtype == jnp.float32
    inv_freq, _ = ops.rope_frequencies(128, 1e4)
    angle = jnp.arange(24, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    np.testing.assert_array_equal(cos[:, :64], jnp.cos(angle))
    np.testing.assert_array_equal(cos[:, 64:], jnp.cos(angle))
    np.testing.assert_array_equal(sin[:, :64], -jnp.sin(angle))
    np.testing.assert_array_equal(sin[:, 64:], jnp.sin(angle))
    cos, sin = ops.rope_lane_tables(24, 64, 1e4, YARN)
    assert cos.shape == (24, 128)
    np.testing.assert_array_equal(cos[:, :64], cos[:, 64:])
    np.testing.assert_array_equal(sin[:, :64], sin[:, 64:])
    assert float(jnp.max(cos)) == pytest.approx(YARN.amplitude)
    assert ops.rope_lane_tables(24, 256, 1e4)[0].shape == (24, 256)


def other(**fields):
    heads = fields.pop("heads", 4)
    d = fields.pop("d", 128)
    return RingAttentionAttrs(
        256, heads, d, d, causal=True, rope_theta=1e4, **fields
    )


RULE = {
    # name: (attrs, the core's route, rows, under a mesh, the rule's answer)
    "mellum2": (attrs_of("mellum2_yarn"), "fused_row", 8192, False, "pallas"),
    "ouro": (attrs_of("ouro_rotary_alone"), "fused_row", 8192, False, "pallas"),
    "olmoe": (attrs_of("olmoe_row_norm"), "fused_row", 4096, False, "pallas"),
    "lfm2": (attrs_of("lfm2_heads_of_64"), "fused_row", 8192, False, "pallas"),
    "a_norm_alone": (
        RingAttentionAttrs(256, 4, 128, 128, **PER_HEAD), "fused_row", ROWS,
        False, "pallas"),
    "one_block_of_64_rows": (
        attrs_of("mellum2_default"), "fused_row", 64, False, "pallas"),
    # Qwen3-Next's node: 64 of a head's 256 columns turned, q cut out of
    # the `[q | gate]` row
    "qwen3next": (
        other(d=256, rotary_dim=64, output_gate=True, num_kv_heads=1,
              qk_norm_zero_centered=True, **PER_HEAD),
        "fused_row", 8192, False, "xla (rotary_dim)"),
    "q_cut_out_of_a_gated_row": (
        attrs_of("mellum2_default", output_gate=True), "fused_row", ROWS,
        False, "xla (output_gate)"),
    "the_rows_core": (attrs_of("mellum2_default"), "rows", ROWS, False, "xla (route)"),
    "the_dense_core": (attrs_of("olmoe_row_norm"), "dense", ROWS, False, "xla (route)"),
    "under_a_declared_mesh": (
        attrs_of("mellum2_default"), "fused_row", ROWS, True, "xla (route)"),
    "heads_of_96": (other(d=96), "fused_row", ROWS, False, "xla (head 96)"),
    "heads_of_384": (other(d=384), "fused_row", ROWS, False, "xla (head 384)"),
    # a whole-row norm over a row that is no power of two of lanes: 12 heads
    # of 128 (85 rows a step would not divide a block of 512), rows of 768
    # (a whole-row norm is never grouped: the key's row is the query's)
    "a_row_norm_over_12_heads": (
        other(heads=12, qk_norm_eps=1e-5), "fused_row", ROWS, False,
        "xla (row 1536)"),
    "a_row_norm_over_24_heads": (
        other(heads=24, qk_norm_eps=1e-5), "fused_row", ROWS, False,
        "xla (row 3072)"),
    "a_row_norm_over_a_row_of_768_lanes": (
        other(heads=6, qk_norm_eps=1e-5), "fused_row", ROWS, False,
        "xla (row 768)"),
    "a_row_norm_over_12_heads_of_64": (
        other(heads=12, d=64, qk_norm_eps=1e-5), "fused_row",
        ROWS, False, "xla (row 768)"),
    # the same rows under a per-head norm are slabs of ONE head: taken
    "a_head_norm_over_12_heads_and_a_key_row_of_768": (
        other(heads=12, num_kv_heads=6, **PER_HEAD), "fused_row", ROWS, False,
        "pallas"),
    "heads_of_32": (other(d=32), "fused_row", ROWS, False, "xla (head 32)"),
    "an_odd_count_of_heads_of_64": (
        other(d=64, heads=3), "fused_row", ROWS, False, "xla (row 192)"),
    "a_row_norm_wider_than_a_slab": (
        other(heads=64, qk_norm_eps=1e-5), "fused_row", ROWS, False,
        "xla (row 8192)"),
    "neither_norm_nor_rotary": (
        RingAttentionAttrs(256, 4, 128, 128, num_kv_heads=2), "fused_row",
        ROWS, False, None),
}


@pytest.mark.parametrize("case", list(RULE))
def test_rule(case):
    """`between_form` from what the trace can observe: the core's route, a
    declared mesh, the attrs' shapes. No configuration's name, no switch."""
    attrs, route, rows, meshed, want = RULE[case]
    # the pass's form goes with "pallas" and with nothing else
    want = (want, ops._pass_form(attrs) if want == "pallas" else None)
    if meshed:
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        with context.flash_mesh(mesh, ("data",), None):
            assert ops.between_form(attrs, route, rows) == want
    else:
        assert ops.between_form(attrs, route, rows) == want


# every width a row of whole lane tiles can have up to 4,096 lanes and then
# some, at row counts that pad, fill one block and fill many
@pytest.mark.parametrize("span", ["head", "row", None])
@pytest.mark.parametrize("d", [64, 128, 256, 384, 512])
def test_an_admitted_plan_walks_every_row_of_its_block(d, span):
    """`pass_plan` over heads of every size and rows of every count of lane
    tiles: a plan it admits has a step that divides its block (the forward's
    walk writes every row) and is whole sublanes of eight (the backward's
    sums add whole registers), whatever the width; a width that is no power
    of two is a slab only as ONE head's 128 or 256 lanes with the heads on
    the grid. (Before the review of PR 66 a whole-row norm over 12 heads of
    128 got 85 rows a step in blocks of 512: rows 510 and 511 of every block
    unwritten, the backward's reshape refused at trace time.)"""
    form = nr.PassForm(d, span, True)
    admitted = 0
    for f in range(128, 8192 + 1, 128):
        for s in (64, 200, 512, 4096, 8192):
            plan = nr.pass_plan(s, f, form)
            if plan is None:
                continue
            admitted += 1
            assert d in nr.HEAD_SIZES and f % max(d, 128) == 0
            assert plan.width == (f if span == "row" else max(d, 128))
            assert plan.width & (plan.width - 1) == 0
            assert plan.block % plan.step == 0 and plan.step % 16 == 0
            assert (s + plan.pad) % plan.block == 0 and plan.pad < 64
    assert bool(admitted) == (d in nr.HEAD_SIZES)
    if span == "row" and d == 128:
        widths = [f for f in range(128, 8193, 128)
                  if nr.pass_plan(512, f, form) is not None]
        assert widths == [128, 256, 512, 1024, 2048, 4096]


# -- what the lowering costs, without a clock ----------------------------------


def kernel_jaxprs(attrs, heads, rows=256):
    """{kernel name: its body's jaxpr} of the pass at `heads` query and key
    heads, forward and backward."""
    from test_step_scopes import pallas_eqns

    q, k, gains, cots = operands(attrs, jnp.bfloat16, rows, 1, heads=heads)
    jaxpr = jax.make_jaxpr(
        lambda q, k, g: outputs_and_cotangents(kernels, attrs, q, k, g, cots)
    )(q, k, gains).jaxpr
    return {
        eqn.params["name"]: eqn.params["jaxpr"] for eqn in pallas_eqns(jaxpr)
    }


def equations(jaxpr):
    """Equations of a jaxpr, those of its loops' bodies included."""
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                total += equations(inner)
    return total


@pytest.mark.parametrize("form", ["mellum2_default", "olmoe_row_norm",
                                  "ouro_rotary_alone", "lfm2_heads_of_64"])
def test_body_does_not_grow_with_the_heads(interpreted, form):
    """The kernel bodies at 4, 16 and 32 heads hold the same number of
    equations, for each kind of norm: the heads are on the grid, or one
    reduction and one roll-and-select span the row's heads; nothing is
    written out a head or a lane tile."""
    attrs = attrs_of(form)
    counts = []
    for heads in (4, 16, 32):
        fresh_traces()
        bodies = kernel_jaxprs(attrs, heads)
        assert sorted(bodies) == ["norm_rotary_bwd", "norm_rotary_fwd"]
        counts.append({name: equations(body) for name, body in bodies.items()})
    assert counts[0] == counts[1] == counts[2]
    assert counts[0]["norm_rotary_fwd"] <= 60
    assert counts[0]["norm_rotary_bwd"] <= 90


def node_step(attrs, rows=1024, seed=3):
    """(step, operands): a plain node through `_mha_forward`, the value and
    every gradient."""
    from flexflow_tpu.op_attrs.core import get_weight_shapes
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape

    rs = np.random.RandomState(seed)
    shape = TensorShape((1, rows, attrs.embed_dim), DataType.FLOAT)
    x = jnp.asarray(0.5 * rs.standard_normal(shape.dims), jnp.float32)
    flat, *gains = [
        jnp.asarray(0.3 * rs.standard_normal(w.dims), jnp.float32)
        for w in get_weight_shapes(attrs, [shape] * 3)
    ]
    gains = [1.0 + g for g in gains] or None

    def step(x, flat, gains):
        def loss(x, flat, gains):
            return jnp.sum(jnp.square(ops._mha_forward(
                attrs, x, x, x, flat, causal=True, qk_gains=gains
            )))

        return jax.value_and_grad(loss, (0, 1, 2) if gains else (0, 1))(
            x, flat, gains
        )

    return step, (x, flat, gains)


@pytest.fixture
def fused_row_on_the_cpu(monkeypatch, interpreted, entered):
    """The "fused_row" route on the CPU: the gates told a TPU is there, the
    causal core interpreted over tiles of 512."""
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_Q", "512")
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_BLOCK_K", "512")
    entered(context.described_tpu())
    monkeypatch.setattr(
        flash, "flash_attention_bshf",
        functools.partial(flash.flash_attention_bshf, interpret=True),
    )
    fresh_traces()


@pytest.fixture
def body_traces(monkeypatch):
    """{kernel: times its body was traced}: what a trace of the jitted
    wrapper costs is a trace of the body (JAX keeps the wrapper's trace by
    its arguments' shapes and static parameters, for every call site)."""
    counted = {"fwd": 0, "bwd": 0}

    def counting(key, body):
        @functools.wraps(body)
        def traced(*refs, **static):
            counted[key] += 1
            return body(*refs, **static)

        return traced

    monkeypatch.setattr(nr, "_forward_kernel", counting("fwd", nr._forward_kernel))
    monkeypatch.setattr(nr, "_backward_kernel", counting("bwd", nr._backward_kernel))
    return counted


def lowered_under(scope, step, case):
    with context.lowering_node(scope):
        return jax.make_jaxpr(step)(*case).jaxpr


def test_q_and_k_of_one_shape_are_one_trace(fused_row_on_the_cpu, body_traces):
    """OLMoE's node form (q and k both `[1, 1024, 4 * 128]`, gains of one
    shape): the node's value and gradients trace each jitted wrapper, and
    with it each kernel's body, ONCE, and the node emits the pass's two
    kernels twice (q and k) beside the core's three: the count is pinned."""
    from test_step_scopes import pallas_eqns

    from flexflow_tpu.observability import trace

    attrs = attrs_of("olmoe_row_norm")
    step, case = node_step(attrs)
    jaxpr = lowered_under("ff.ring_attention.attn0", step, case)
    assert body_traces == {"fwd": 1, "bwd": 1}
    names = sorted(eqn.params["name"] for eqn in pallas_eqns(jaxpr))
    assert names == [
        "flash_bwd_causal_bshf", "flash_delta_bshf", "flash_fwd_causal_bshf",
        "norm_rotary_bwd", "norm_rotary_bwd", "norm_rotary_fwd",
        "norm_rotary_fwd",
    ]
    assert trace.kernel_choices("between_passes")["ff.ring_attention.attn0"] == "pallas"
    assert "norm and rotary of the plain attention nodes" in trace.setup_report()


def test_a_grouped_node_is_two_traces_and_matches_the_plain_form(
    monkeypatch, fused_row_on_the_cpu, body_traces
):
    """Mellum2's window node (8 query heads over 1 key/value head of 128
    under a 300-key window): q and k differ in shape, so two entries each
    way; no float32 table or rolled copy a row of q wide is in the program;
    and the loss and every gradient are the plain form's to float32 sums in
    another order."""
    attrs = attrs_of("mellum2_default", window=300)
    rows, width = 1024, attrs.num_heads * attrs.q_proj_size
    step, case = node_step(attrs, rows)
    jaxpr = lowered_under("ff.ring_attention.attn0", step, case)
    assert body_traces == {"fwd": 2, "bwd": 2}

    def row_wide_tables(jaxpr):
        return [
            v.aval.shape for eqn in jaxpr.eqns for v in eqn.outvars
            if v.aval.shape[-2:] == (rows, width) and eqn.primitive.name in (
                "cos", "sin", "tile", "concatenate", "broadcast_in_dim")
        ]

    assert row_wide_tables(jaxpr) == []
    got = step(*case)
    monkeypatch.setattr(
        ops, "between_form", lambda *a, **k: ("xla (route)", None)
    )
    step, case = node_step(attrs, rows)  # a new function: a new trace
    assert row_wide_tables(jax.make_jaxpr(step)(*case).jaxpr)
    want = step(*case)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-4 * float(jnp.max(jnp.abs(w)))
        )


def test_a_block_applied_four_times_traces_each_kernel_once(
    fused_row_on_the_cpu, body_traces
):
    """An Ouro-like block (a rotary, no norm, q and k of one shape) applied
    four times on one set of weights, two of the passes recomputed in the
    backward: the backward's body is traced ONCE and the forward's once a
    tracing context (JAX keeps a trace by its context too, and a
    `jax.checkpoint` is one of its own: twice, not twelve times); the
    program holds the forward kernel 2 x (4 + 2) and the backward's 2 x 4
    times."""
    from test_step_scopes import pallas_eqns

    attrs = attrs_of("ouro_rotary_alone")
    step, (x, flat, _) = node_step(attrs)

    def looped(x, flat):
        def once(h):
            return ops._mha_forward(attrs, h, h, h, flat, causal=True)

        h = x
        for recompute in (True, True, False, False):
            h = h + (jax.checkpoint(once) if recompute else once)(h)
        return jnp.sum(jnp.square(h))

    jaxpr = jax.make_jaxpr(jax.value_and_grad(looped, (0, 1)))(x, flat).jaxpr
    assert body_traces == {"fwd": 2, "bwd": 1}
    names = [eqn.params["name"] for eqn in pallas_eqns(jaxpr)]
    assert names.count("norm_rotary_fwd") == 2 * (4 + 2)
    assert names.count("norm_rotary_bwd") == 2 * 4


def test_a_node_with_nothing_between_emits_what_it_emitted_before(
    monkeypatch, fused_row_on_the_cpu
):
    """A grouped-query node without norm and rotary (TwoTower's, Super's and
    Cerebras-GPT's form): `between_form` is None, nothing is noted, the
    node holds the core's three kernels and no other, and its jaxpr is to
    the letter the one it has with the rule answering "xla": the plain path,
    which is the code from before there was a pass."""
    from test_step_scopes import pallas_eqns

    from flexflow_tpu.observability import trace

    attrs = RingAttentionAttrs(256, 4, 128, 128, causal=True, num_kv_heads=2)
    assert ops.between_form(attrs, "fused_row", 1024) == (None, None)
    monkeypatch.setattr(context, "_CHOICES", {})
    step, case = node_step(attrs)
    jaxpr = lowered_under("ff.ring_attention.plain", step, case)
    assert trace.kernel_choices("between_passes") == {}
    assert sorted(eqn.params["name"] for eqn in pallas_eqns(jaxpr)) == [
        "flash_bwd_causal_bshf", "flash_delta_bshf", "flash_fwd_causal_bshf",
    ]
    monkeypatch.setattr(
        ops, "between_form", lambda *a, **k: ("xla (route)", None)
    )
    step, case = node_step(attrs)  # a new function: a new trace
    assert str(jaxpr) == str(lowered_under("ff.ring_attention.plain", step, case))
