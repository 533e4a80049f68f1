"""Mixture-of-experts tests: GroupBy/Aggregate parity ops, the fused Experts
op, expert parallelism on the 8-device CPU mesh, and the FFModel.moe API.

Reference behavior: examples/cpp/mixture_of_experts/moe.cc (ff.moe composition
gating dense -> softmax -> TopK -> GroupBy -> expert towers -> Aggregate);
SURVEY.md §2.12 expert-parallelism row.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import context
from flexflow_tpu.kernels import moe as moe_kernels
from flexflow_tpu.kernels.moe import (
    aggregate_forward,
    dispatch_mask,
    experts_forward,
    group_by_forward,
    route,
)
from flexflow_tpu.op_attrs.core import (
    get_incoming_tensor_roles,
    get_output_shapes,
    get_parallel_output_shapes,
    get_parallel_weight_shapes,
    get_weight_shapes,
    num_outputs,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    AggregateAttrs,
    ExpertsAttrs,
    GroupByAttrs,
    expert_capacity,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape


def test_dispatch_mask_routes_in_order_and_drops_overflow():
    assign = jnp.asarray([0, 1, 0, 0, 1], jnp.int32)
    d = dispatch_mask(assign, n_experts=2, capacity=2)
    assert d.shape == (5, 2, 2)
    # expert 0 receives decisions 0 (pos 0) and 2 (pos 1); decision 3 dropped
    assert d[0, 0, 0] == 1 and d[2, 0, 1] == 1 and d[3].sum() == 0
    # expert 1 receives decisions 1 and 4
    assert d[1, 1, 0] == 1 and d[4, 1, 1] == 1
    # each decision goes to at most one (expert, slot)
    assert float(d.sum()) == 4.0


def test_group_by_aggregate_roundtrip():
    """GroupBy then Aggregate with identity experts and unit gates returns
    the input (for tokens within capacity)."""
    rs = np.random.RandomState(0)
    B, D, E, k = 8, 4, 4, 2
    data = jnp.asarray(rs.randn(B, D), jnp.float32)
    assign = jnp.asarray(rs.randint(0, E, (B, k)), jnp.int32)
    gb = GroupByAttrs(E, alpha=float(E))  # capacity large enough: no drops
    groups = group_by_forward(gb, data, assign)
    shapes = get_output_shapes(
        gb,
        [
            TensorShape((B, D), DataType.FLOAT),
            TensorShape((B, k), DataType.INT32),
        ],
    )
    assert [g.shape for g in groups] == [s.dims for s in shapes]
    agg = AggregateAttrs(E)
    ones = jnp.ones((B, k), jnp.float32)
    out = aggregate_forward(agg, ones, assign, groups)
    # every token was dispatched k times with weight 1 -> k * data
    np.testing.assert_allclose(out, k * np.asarray(data), rtol=1e-5)


def _dense_moe_reference(attrs, x, weights):
    """Per-token loop reference for the fused experts op (no capacity
    drops assumed)."""
    gate_w, w1, b1, w2, b2 = weights
    x2 = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = x2 @ np.asarray(gate_w, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros((x2.shape[0], w2.shape[-1]))
    for n in range(x2.shape[0]):
        top = np.argsort(-probs[n])[: attrs.num_select]
        sel = probs[n, top] / probs[n, top].sum()
        for e, g in zip(top, sel):
            h = x2[n] @ np.asarray(w1[e], np.float64) + np.asarray(b1[e])
            h = np.maximum(h, 0.0)
            out[n] += g * (h @ np.asarray(w2[e], np.float64) + np.asarray(b2[e]))
    return out.reshape(*x.shape[:-1], -1)


def make_experts(B=6, D=8, E=4, k=2, H=16, alpha=4.0, lambda_bal=0.0, seed=0):
    attrs = ExpertsAttrs(
        num_experts=E,
        num_select=k,
        hidden_size=H,
        capacity_factor=alpha,
        lambda_bal=lambda_bal,
    )
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(B, D), jnp.float32)
    weights = [
        jnp.asarray(rs.randn(D, E) * 0.5, jnp.float32),
        jnp.asarray(rs.randn(E, D, H) * 0.1, jnp.float32),
        jnp.asarray(rs.randn(E, H) * 0.1, jnp.float32),
        jnp.asarray(rs.randn(E, H, D) * 0.1, jnp.float32),
        jnp.asarray(rs.randn(E, D) * 0.1, jnp.float32),
    ]
    return attrs, x, weights


def test_experts_matches_per_token_reference():
    attrs, x, weights = make_experts()
    (out,) = experts_forward(attrs, x, weights)
    ref = _dense_moe_reference(attrs, x, weights)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


# -- the router's pick and the histograms, by comparison ------------------------


def route_by_gather(attrs, x2, gate_w, select_bias=None):
    """`route` as it stood before `_pick_columns`: the sigmoid scores
    fetched by `take_along_axis`, the softmax probabilities by `top_k`'s own
    values (which JAX differentiates by gather). The same arithmetic around
    them, so every output and gradient is equal to the bit."""
    logits = x2.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    if attrs.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores
        if select_bias is not None:
            choice = scores + jax.lax.stop_gradient(
                select_bias.astype(jnp.float32)
            )
        _, topi = jax.lax.top_k(choice, attrs.num_select)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
        if attrs.renormalize:
            topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
        return logits, scores, topi, topv * attrs.routed_scale
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, attrs.num_select)
    if attrs.renormalize:
        topv = topv / topv.sum(axis=-1, keepdims=True)
    if attrs.routed_scale != 1.0:
        topv = topv * attrs.routed_scale
    return logits, probs, topi, topv


def traces_to(primitive, fn, *args):
    """Whether `fn`'s jaxpr binds `primitive`, nested jits included."""
    return f" {primitive}[" in str(jax.make_jaxpr(fn)(*args))


@pytest.mark.parametrize(
    "scoring, experts, select, renormalize",
    [
        ("sigmoid", 512, 22, True),  # nemotron-3-super
        ("sigmoid", 128, 6, True),  # nemotron-twotower
        ("softmax", 64, 8, True),
        ("softmax", 64, 8, False),  # olmoe
        ("sigmoid", 4097, 6, True),  # wider than any router here
        ("softmax", 4097, 6, False),
    ],
)
def test_router_picks_by_comparison_what_the_gather_fetched(
    scoring, experts, select, renormalize
):
    """The chosen experts, their weights and the gradients of x, of the
    router's matrix and (by the helper alone) of the scores, equal to the
    bit, with tied scores in every row: columns 1, 2 and 7 of the router
    repeat column 0, bias included. At any width, and with no gather."""
    rs = np.random.RandomState(experts + select)
    tokens, d = 24, 16
    x = jnp.asarray(rs.randn(tokens, d), jnp.float32)
    gate = rs.randn(d, experts).astype(np.float32)
    bias = (0.2 * rs.randn(experts)).astype(np.float32)
    # a column that is ranked wherever its logit is positive, or by its bias
    gate[:, 0] *= 4
    bias[0] = 1.0
    for tied in (1, 2, 7):
        gate[:, tied], bias[tied] = gate[:, 0], bias[0]
    gate = jnp.asarray(gate)
    bias = jnp.asarray(bias) if scoring == "sigmoid" else None
    attrs = ExpertsAttrs(
        experts, select, 8, scoring=scoring, renormalize=renormalize,
        selection_bias=bias is not None,
        routed_scale=2.5 if scoring == "sigmoid" else 1.0,
    )
    mix = jnp.asarray(rs.randn(tokens, select), jnp.float32)

    def weighed(router):
        def loss(x, gate):
            _, scores, topi, topv = router(attrs, x, gate, bias)
            return jnp.sum(topv * mix), (scores, topi, topv)

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(x, gate)

    (_, (scores, topi, topv)), grads = weighed(route)
    (_, (_, want_i, want_v)), want_grads = weighed(route_by_gather)
    np.testing.assert_array_equal(np.asarray(topi), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(topv), np.asarray(want_v))
    for got, want in zip(grads, want_grads):
        assert float(jnp.max(jnp.abs(want))) > 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a tie was ranked in some row, or the case does not test one
    assert np.isin(np.asarray(topi), (0, 1, 2, 7)).sum(axis=-1).max() >= 2

    def pick(values):
        return moe_kernels._pick_columns(values, topi)

    def fetch(values):
        return jnp.take_along_axis(values, topi, axis=-1)

    got, pulled = jax.vjp(pick, scores)
    want, want_pulled = jax.vjp(fetch, scores)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(pulled(mix)[0]), np.asarray(want_pulled(mix)[0])
    )
    assert not traces_to("gather", pick, scores)


def test_ranking_the_experts_builds_no_jvp_of_top_k(top_k_jvp_refused):
    """Only the indices of `top_k` are used, of a `stop_gradient`: the rule
    that differentiates its values is never called, whichever the scoring."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(8, 16), jnp.float32)
    gate = jnp.asarray(rs.randn(16, 12), jnp.float32)
    for scoring in ("sigmoid", "softmax"):
        attrs = ExpertsAttrs(12, 3, 8, scoring=scoring, renormalize=True)
        grad = jax.grad(lambda x: jnp.sum(route(attrs, x, gate)[3] ** 2))(x)
        assert float(jnp.max(jnp.abs(grad))) > 0


@pytest.mark.parametrize(
    "case, bins",
    [
        ("spread", 9), ("empty_share", 9), ("one_bin", 9), ("overflow", 9),
        ("spread", 64), ("spread", 512), ("spread", 4097),
    ],
)
def test_keys_counted_by_comparison_equal_the_scatter_add(case, bins):
    """`held + 1` bins of a share (the last takes every decision that
    landed elsewhere), or a router's E: a share nothing reached, one expert
    taking everything, and a key past the bins, which neither form counts."""
    rs = np.random.RandomState(bins)
    keys = {
        "spread": rs.randint(0, bins, size=1000),
        "empty_share": np.full(1000, bins - 1),
        "one_bin": np.full(1000, 3),
        "overflow": np.where(rs.rand(1000) < 0.1, bins, rs.randint(0, bins, 1000)),
    }[case].astype(np.int32)
    keys = jnp.asarray(keys)

    def count(keys):
        return moe_kernels._count_keys(keys, bins)

    got = count(keys)
    want = jnp.zeros((bins,), jnp.int32).at[keys].add(1)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got.sum()) == int(jnp.sum(keys < bins))
    assert not traces_to("scatter-add", count, keys)


def test_experts_shapes_roles_and_aux():
    attrs = ExpertsAttrs(4, 2, 16, lambda_bal=0.01)
    x = TensorShape((6, 8), DataType.FLOAT)
    outs = get_output_shapes(attrs, [x])
    assert [o.dims for o in outs] == [(6, 8), (1,)]
    assert num_outputs(attrs) == 2
    ws = get_weight_shapes(attrs, [x])
    assert [w.dims for w in ws] == [
        (8, 4), (4, 8, 16), (4, 16), (4, 16, 8), (4, 8),
    ]
    roles = get_incoming_tensor_roles(attrs)
    assert len(roles) == 6 and roles[0].value == "input"

    attrs2, x2, weights = make_experts(lambda_bal=0.01)
    out, aux = experts_forward(attrs2, x2, weights)
    assert aux.shape == (1,) and float(aux[0]) > 0
    # balanced-ish routing: aux is lambda * E * sum(f*P) >= lambda (cauchy-
    # schwarz lower bound at perfect balance)
    assert float(aux[0]) >= 0.01 * 0.99


def test_experts_gradients_flow():
    attrs, x, weights = make_experts()

    def loss(x, weights):
        (out,) = experts_forward(attrs, x, weights)
        return jnp.sum(out**2)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, weights)
    assert float(jnp.abs(gx).sum()) > 0
    assert float(jnp.abs(gw[0]).sum()) > 0  # gate weight gets gradient
    assert float(jnp.abs(gw[1]).sum()) > 0  # expert weights get gradient


@pytest.mark.parametrize("alpha", [4.0, 0.5], ids=["no_drops", "drops"])
@pytest.mark.parametrize("ep", [2, 4])
def test_expert_shards_parts_add_up_to_the_whole(ep, alpha):
    """What `experts_forward` gives for each expert-parallel shard's slice
    of the expert tensors (`expert_shard`, the form `shard_map` calls) adds
    up to the unsharded call's output and gradients: biases, a capacity
    that drops decisions and the auxiliary scalar (the same on every shard)
    included."""
    attrs, x, weights = make_experts(B=24, alpha=alpha, lambda_bal=0.01)
    here = attrs.num_experts // ep
    cot = jnp.asarray(np.random.RandomState(1).randn(*x.shape), jnp.float32)

    def whole(x, weights):
        out, aux = experts_forward(attrs, x, weights)
        return jnp.sum(out * cot) + aux[0]

    def parts(x, weights):
        gate, rest = weights[0], weights[1:]
        total = 0.0
        for i in range(ep):
            mine = [w[i * here:(i + 1) * here] for w in rest]
            out, aux = experts_forward(
                attrs, x, [gate, *mine], expert_shard=(i * here, here)
            )
            total = total + jnp.sum(out * cot)
        return total + aux[0]

    tol = dict(rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(parts(x, weights), whole(x, weights), **tol)
    got = jax.grad(parts, argnums=(0, 1))(x, weights)
    want = jax.grad(whole, argnums=(0, 1))(x, weights)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, **tol)


def test_experts_parallel_shapes_expert_parallelism():
    """Replicated input (discard_copy=ep) -> expert weights sharded on the
    expert dim, output carries sum_degree=ep (the Unity reduction pattern)."""
    ep, dp = 2, 2
    x = lift_to_parallel_with_degrees(
        TensorShape((8, 16), DataType.FLOAT), 1, ep, (dp, 1)
    )
    attrs = ExpertsAttrs(4, 2, 32)
    (out,) = get_parallel_output_shapes(attrs, [x])
    assert out.sum_degree == ep
    assert out.shard_degrees() == (dp, 1)
    ws = get_parallel_weight_shapes(attrs, [x])
    # gate replicated, expert tensors sharded degree ep on dim 0
    assert ws[0].shard_degrees() == (1, 1)
    assert ws[0].discard_copy_degree == ep * dp
    for w in ws[1:]:
        assert w.shard_degrees()[0] == ep
        assert w.discard_copy_degree == dp


def test_expert_parallel_training_on_mesh():
    """PCG with replicate(ep) -> experts -> reduce lowers and trains on the
    8-device CPU mesh (dp=2 x ep=2 uses 4 of 8 devices' axes)."""
    from flexflow_tpu.kernels.metrics import METRIC_ACCURACY
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.parallel import DistributedTrainingInstance, MachineMesh
    from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs
    from flexflow_tpu.pcg.parallel_computation_graph_builder import (
        ParallelComputationGraphBuilder,
    )

    dp, ep = 2, 2
    B, D, E, k, H, V = 8, 16, 4, 2, 32, 8
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(
        lift_to_parallel_with_degrees(
            TensorShape((B, D), DataType.FLOAT), 1, 1, (dp, 1)
        ),
        name="x",
    )
    h = b.parallel_replicate(x, ep)
    (h,) = b.experts(h, E, k, H, capacity_factor=4.0)
    h = b.parallel_reduce(h, ep)
    logits = b.dense(h, V, name="head")

    mm = MachineMesh.for_devices(8)
    inst = DistributedTrainingInstance(
        b.graph,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        SGDOptimizerAttrs(lr=0.05),
        mm,
        metrics=frozenset({METRIC_ACCURACY}),
    )
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    x_val = jnp.asarray(rs.randn(B, D), jnp.float32)
    y_val = jnp.asarray(rs.randint(0, V, (B,)), jnp.int32)
    losses = []
    for _ in range(5):
        params, opt_state, loss, _ = inst.train_step(
            params, opt_state, {"x": x_val}, y_val
        )
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_ffmodel_moe_trains():
    """FFModel.moe (reference ff.moe signature) trains end-to-end with the
    load-balance aux loss wired into the training loss."""
    from flexflow_tpu.core import FFConfig, FFModel

    cfg = FFConfig(batch_size=8, epochs=1, seed=0)
    ff = FFModel(cfg)
    x = ff.create_tensor([8, 16], name="x")
    t = ff.moe(x, num_exp=4, num_select=2, hidden_size=32, alpha=4.0,
               lambda_bal=0.01)
    t = ff.dense(t, 8)
    ff.compile(loss_type="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    assert ff._aux_loss_tensors, "aux loss tensor must be registered"
    rs = np.random.RandomState(0)
    xs = rs.randn(64, 16).astype(np.float32)
    ys = rs.randint(0, 8, (64,)).astype(np.int32)
    m = ff.fit(xs, ys, epochs=2, verbose=False)
    assert m.accuracy is not None


def test_capacity_formula():
    assert expert_capacity(64, 4, 2, 1.0) == 32
    assert expert_capacity(64, 4, 2, 2.0) == 64
    assert expert_capacity(1, 64, 1, 1.0) == 1


def test_searched_moe_finds_expert_parallelism():
    """VERDICT round-1 gap #3: the Unity search must be reachable for
    aux-loss (lambda_bal>0) MoE graphs and able to discover expert
    parallelism; the aux loss must survive into the searched training step."""
    import jax

    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.core.ffmodel import _find_aux_outputs

    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device")
    batch = 64
    cfg = FFConfig(batch_size=batch, epochs=1, seed=0, search_budget=4)
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, 128], name="x")
    t = ff.moe(x, num_exp=8, num_select=2, hidden_size=256, alpha=4.0,
               lambda_bal=0.01)
    t = ff.dense(t, 8, use_bias=False)
    ff.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
               metrics=["accuracy"])
    from flexflow_tpu.op_attrs import OperatorType, op_type_of
    from flexflow_tpu.op_attrs.ops.moe import ExpertsAttrs
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance

    assert isinstance(ff.instance, DistributedTrainingInstance), (
        "aux-loss graph must take the searched path, not fall back to DP"
    )
    assert ff.instance.aux_loss_tensors, (
        "searched instance lost the load-balance aux loss"
    )
    assert _find_aux_outputs(ff.instance.pcg)
    # round-2 verdict weak #5: this test must FAIL if the search returns a
    # serial plan — the winning plan must actually shard the experts (each
    # Experts op's weight inputs carry an expert-dim Repartition)
    pcg = ff.instance.pcg
    expert_nodes = [
        n for n in pcg.nodes
        if isinstance(pcg.op_attrs(n), ExpertsAttrs)
    ]
    assert expert_nodes
    ep_degrees = []
    for n in expert_nodes:
        for v in pcg.inputs_of(n):
            at = pcg.op_attrs(v.node)
            if op_type_of(at) == OperatorType.REPARTITION and (
                at.repartition_dim == 0
            ):
                ep_degrees.append(at.repartition_degree)
    assert ep_degrees and max(ep_degrees) > 1, (
        f"searched MoE plan is not expert-parallel: {ff.search_provenance}"
    )
    prov = ff.search_provenance or {}
    assert prov["estimated_ms"] < prov["serial_ms"]
    rs = np.random.RandomState(0)
    xs = rs.randn(batch, 128).astype(np.float32)
    ys = rs.randint(0, 8, (batch,)).astype(np.int32)
    m = ff.fit(xs, ys, epochs=1, verbose=False)
    assert m.train_all == batch


def test_expert_parallel_aux_rule_applies():
    """The with_aux Experts rule rewrites a lambda_bal>0 graph, keeping the
    (unconsumed) aux output available structurally."""
    from flexflow_tpu.core.ffmodel import _find_aux_outputs
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )
    from flexflow_tpu.substitutions import (
        apply_substitution,
        find_pattern_matches,
        is_valid_match_for_substitution,
    )
    from flexflow_tpu.substitutions.rules import expert_parallel_experts_rule

    b = ComputationGraphBuilder()
    x = b.create_input([8, 16], name="x")
    outs = b.experts(x, 4, 2, 32, lambda_bal=0.01)
    pcg = pcg_from_computation_graph(b.graph)
    assert len(_find_aux_outputs(pcg)) == 1
    rule = expert_parallel_experts_rule(2, use_bias=True, with_aux=True)
    matches = find_pattern_matches(rule.pattern, pcg)
    assert matches
    m = matches[0]
    assert is_valid_match_for_substitution(pcg, rule, m)
    new_pcg = apply_substitution(pcg, rule, m)
    aux = _find_aux_outputs(new_pcg)
    assert len(aux) == 1
    # per-shard partial aux: copy degree ep on the rewritten experts op
    assert new_pcg.tensor_shape(aux[0]).dims.discard_copy_degree == 2


# -- the grouped matmul's tiles (PR 48) -------------------------------------
# the five sparse cells: rows a call (OLMoE every decision, the others the
# held share's window), matrices a call, the expert matrix [K, N] of `w1`
_CELL_SHAPES = {
    "olmoe_s4096_1chip": (131072, 64, 2048, 1024),
    "lfm2moe24b_s8192_1chip": (10240, 8, 2048, 1536),
    "kimilinear48b_s4096_1chip": (1280, 8, 2304, 1024),
    "twotower30b_s4096_1chip": (1920, 8, 2688, 1856),
    "super120b_s4096_1chip": (1792, 8, 1024, 2688),
}


def test_the_windows_are_the_cells():
    """`_CELL_SHAPES`' rows are what `held_window_rows` gives each held cell
    (decisions a step, 8 held of the router's width)."""
    for cell, decisions, experts in [
        ("lfm2moe24b_s8192_1chip", 65536, 64),
        ("kimilinear48b_s4096_1chip", 32768, 256),
        ("twotower30b_s4096_1chip", 24576, 128),
        ("super120b_s4096_1chip", 90112, 512),
    ]:
        assert moe_kernels.held_window_rows(decisions, 8, experts) == (
            _CELL_SHAPES[cell][0]
        )


@pytest.mark.parametrize("matrix", ["w1", "w2"])
@pytest.mark.parametrize("call", moe_kernels._CALLS)
@pytest.mark.parametrize("cell", list(_CELL_SHAPES))
def test_grouped_matmul_tile_fits_the_calls_own_shape(cell, call, matrix):
    """The tile rule as a pure function, at each cell's shape and window, for
    each of the three calls of `w1` [K, N] and of `w2` [N, K]: every side of
    the tile a multiple of 128, the row tile a divisor of the rows, the
    contraction and the columns padded by nothing (1,856, no multiple of 128,
    by 1,920 / 1,856), the bytes inside the bound, and OLMoE's the tile
    measured at its shape (PR 26)."""
    m, groups, k, n = _CELL_SHAPES[cell]
    if matrix == "w2":
        k, n = n, k
    tiles = moe_kernels._gmm_tiles(m, k, n, groups)
    tile = tiles[moe_kernels._CALLS.index(call)]
    if call == "input_gradient":
        k, n = n, k  # it contracts over the forward's columns
    assert tile == moe_kernels._gmm_tile(m, k, n, groups, call)
    tm, tk, tn = tile
    assert tm % 128 == tk % 128 == tn % 128 == 0 and m % tm == 0
    assert tk <= k and tn <= n
    for size, t in ((k, tk), (n, tn)):
        padded = moe_kernels._whole_tiles(size, t) / size
        assert padded == 1.0 or (size == 1856 and padded <= 1.04), (size, t)
    assert moe_kernels._tile_bytes(call, tm, tk, tn) <= moe_kernels._TILE_BYTES
    if cell == "olmoe_s4096_1chip":
        assert tile == (512, 1024, 1024)
    if m // groups < 512:
        assert tm == 128  # every group costs whole row tiles


@pytest.mark.parametrize(
    "m, k, n, groups",
    [(1000, 2048, 1024, 8), (1280, 64, 1024, 8), (1280, 1, 1024, 8),
     (1280, 1024, 96, 8), (1280, 2080, 1024, 8)],
    ids=["rows_no_tiles", "contraction_64", "bias_column", "columns_96",
         "contraction_2080"],
)
def test_grouped_matmul_tile_refuses_what_the_kernels_refuse(m, k, n, groups):
    assert moe_kernels._gmm_tiles(m, k, n, groups) is None


@pytest.mark.parametrize(
    "k, n, tiles",
    [
        (256, 384, ((128, 256, 384), (128, 128, 256), (128, 128, 128))),
        (384, 256, ((128, 128, 256), (128, 256, 128), (128, 384, 128))),
        (256, 384, ((128, 256, 256), (128, 256, 256), (128, 256, 256))),
    ],
    ids=["256x384", "384x256", "256x384_partial_blocks"],
)
@pytest.mark.parametrize("rest", [0, 1], ids=["all_groups", "rest_group"])
def test_grouped_matmul_vjp_with_a_tile_a_call(k, n, tiles, rest):
    """`_gmm`, the `custom_vjp` over megablox's backend kernels, in interpret
    mode against `lax.ragged_dot` under `jax.grad`: the value and the
    cotangents of the rows and the matrices, with tiles that differ between
    the forward, the input gradient and the weight gradient, over uneven
    groups of which one is empty and none starts on a tile's boundary; with
    a rest group (`group_offset` 0 into one size more than matrices) whose
    rows come back zero and take no gradient. The third case's tiles end the
    384-wide side in a partial block, which megablox masks or drops."""
    m, groups = 384, 3
    sizes = jnp.asarray([100, 0, 284 - 90 * rest] + [90] * rest, jnp.int32)
    key = jax.random.PRNGKey(k + rest)
    rows = jax.random.normal(key, (m, k), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (groups, k, n)) / k**0.5
    cot = jax.random.normal(jax.random.fold_in(key, 2), (m, n), jnp.float32)
    offset = jnp.zeros((), jnp.int32) if rest else None

    def kernels(rows, w):
        out = moe_kernels._gmm(rows, w, sizes, offset, tiles, True)
        return jnp.sum(out * cot), out

    def reference(rows, w):
        # `lax.ragged_dot`, a matrix of zeros for the rest group
        out = moe_kernels._grouped_matmul(rows, w, sizes, False)
        return jnp.sum(out * cot), out

    (_, got), got_grads = jax.value_and_grad(kernels, (0, 1), has_aux=True)(rows, w)
    (_, want), want_grads = jax.value_and_grad(reference, (0, 1), has_aux=True)(rows, w)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for g, r in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)
    if rest:
        assert float(jnp.max(jnp.abs(got[-90:]))) == 0.0
        assert float(jnp.max(jnp.abs(got_grads[0][-90:]))) == 0.0


@pytest.mark.parametrize("held", [(4, 4), None], ids=["held_share", "all_rows"])
def test_grouped_matmul_tiles_counter_names_what_a_lowered_node_took(
    monkeypatch, held, entered
):
    """`trace.kernel_choices("grouped_matmul_tiles")` after an expert node is
    traced for the kernels (the backend gate forced; nothing runs): under the node's scope,
    for each of `w1`, `w3`, `w2` and each of the three calls, the call's own
    shape (the input gradient contracts over the forward's columns), the
    tile `_gmm_tiles` gives it and 1.0 padded over true. A node on
    `ragged_dot` notes nothing."""
    from flexflow_tpu.observability import trace
    from flexflow_tpu.op_attrs.activation import Activation

    tokens, hidden, width, experts, select = 1024, 256, 384, 16, 2
    attrs = ExpertsAttrs(
        experts, select, width, activation=Activation.SILU,
        capacity_factor=None, use_bias=False, gated=True, held_experts=held,
    )
    here = held[1] if held else experts
    rows = moe_kernels.held_window_rows(tokens * select, here, experts)
    x = jnp.zeros((tokens, hidden), jnp.bfloat16)
    weights = [
        jnp.zeros((hidden, experts), jnp.bfloat16),
        jnp.zeros((here, hidden, width), jnp.bfloat16),
        jnp.zeros((here, hidden, width), jnp.bfloat16),
        jnp.zeros((here, width, hidden), jnp.bfloat16),
    ]

    def loss(x, weights):
        return jnp.sum(experts_forward(attrs, x, weights)[0].astype(jnp.float32))

    monkeypatch.setattr(context, "_CHOICES", {})
    entered(context.lowering_node("ff.experts.on_xla"))
    jax.make_jaxpr(loss)(x, weights)
    assert trace.kernel_choices("grouped_matmul_tiles") == {}

    entered(context.described_tpu())
    entered(context.lowering_node("ff.experts.e1"))
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, weights))
    noted = trace.kernel_choices("grouped_matmul_tiles")
    assert list(noted) == ["ff.experts.e1"]
    entries = noted["ff.experts.e1"]
    assert sorted(entries) == sorted(
        f"{w}/{call}" for w in ("w1", "w2", "w3") for call in moe_kernels._CALLS
    )
    for name, (k, n) in {"w1": (hidden, width), "w3": (hidden, width),
                         "w2": (width, hidden)}.items():
        tiles = moe_kernels._gmm_tiles(rows, k, n, here)
        shapes = [(rows, k, n), (rows, n, k), (rows, k, n)]
        for call, shape, tile in zip(moe_kernels._CALLS, shapes, tiles):
            assert entries[f"{name}/{call}"] == {
                "shape": shape, "tile": tile, "padded_over_true": 1.0,
            }
    assert "pallas_call[" in text
