"""A stack of layers applied again on ONE set of weights (`SharedBlock`), the
loss node with a weight a position, the step whose loss is its loss nodes
alone, and the looped step of `benchmark/configs/ouro-2.6b.py` through the
public builder and `FFModel.compile -> fit`, each part against the plain
float32 reference that lives with the configuration, at toy size on the CPU
with seeded weights. Every tolerance states its reason."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_nemotron_h import BENCH, F32, F32_LOSS, assert_trees_close, bench, rand

from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.kernels.loss import label_cross_entropy, mean_loss
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.core import (
    OperatorType,
    get_output_shapes,
    get_parallel_output_shapes,
    num_data_inputs,
    op_type_of,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    LabelCrossEntropyAttrs,
    MeanLossAttrs,
    WeightAttrs,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    lift_to_parallel_with_degrees,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.pcg import ComputationGraphBuilder

CONFIG = os.path.join(BENCH, "configs", "ouro-2.6b")
ref = bench.load_module(CONFIG + ".py")

# 4 heads of 16, a SwiGLU of 160, 2 layers applied 4 times, 300 tokens
TOY = dict(
    bench.load_json(CONFIG + ".json"),
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=160, num_hidden_layers=2,
    layer_types=["full_attention"] * 2, vocab_size=300, rope_theta=100.0,
    # ten times the published deviation, as in the other configurations'
    # tests: at toy width 0.02 leaves the gate's pre-activation so small
    # that the four exits weigh a quarter each and a wrong exit distribution
    # would hide inside a tolerance
    initializer_range=0.2,
)
# the same with every activation kept: what may be lifted to a PCG (a graph
# with a `recompute` group is refused there)
KEPT = dict(TOY, recomputed_passes=0)
BATCH, SEQ = 2, 24
ADAM = TOY["training"]

# gradients through four passes of two layers in float32 on the CPU: sums of
# a few thousand products in another order than the reference's, on
# gradients of up to ten
F32_GRADS = dict(rtol=2e-3, atol=2e-4)
# the loss after one step: a loss of 6.6 has float32 steps of 4.8e-7, and the
# step's gradient went through eight layer applications computed a second
# time in the backward pass, in another order than the kept forward's, before
# Adam's division by its root (read 1.1e-5)
F32_STEPPED = 3e-5


def data(seq=SEQ, seed=0, sizes=TOY):
    return ref.make_data(np.random.RandomState(seed), sizes, BATCH, seq)


def compiled_model(sizes=TOY, seq=SEQ, **config):
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
        ADAM["loss"],
    )
    return model


def system_loss(model, inputs, labels):
    read = bench.make_loss_reader(model.instance)
    batch, label = bench.place_batch(model.instance, inputs, labels)
    return read(model.params, batch, label)


def step_grads(model, inputs, labels):
    batch, label = bench.place_batch(model.instance, inputs, labels)
    grads = jax.grad(
        lambda p: model.instance.loss_fn(p, batch, label)[0]
    )(model.params)
    return bench.named_parameters(model.instance, grads)


@pytest.fixture(scope="module")
def toy():
    """(model, its named parameters before any step, inputs, labels)."""
    model = compiled_model(max_devices=1)
    inputs, labels = data()
    return model, bench.named_parameters(model.instance, model.params), inputs, labels


# -- the builder's block ------------------------------------------------------------


def node_names(graph):
    return [
        graph.layer_attrs(n).name for n in graph.topological_ordering()
        if graph.layer_attrs(n).name
    ]


def weight_readers(graph):
    """{weight tensor: the nodes that read it} of the weights with more than
    one reader."""
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    readers = {}
    for n in graph.topological_ordering():
        for v in graph.inputs_of(n):
            if isinstance(graph.op_attrs(v.node), WeightAttrs):
                readers.setdefault(v, []).append(n)
    return {v: ns for v, ns in readers.items() if len(ns) > 1}


def test_a_block_applied_again_reads_the_first_applications_weights():
    """Four applications are four times the layers' nodes, named
    `<layer>#<pass>`, on ONE weight node a layer slot, named after the layer;
    the graph's parameters are `parameter_counts()`."""
    builder, _ = ref.build(TOY, BATCH, SEQ)
    graph = builder.graph
    names = node_names(graph)
    for t in (1, 2, 3, 4):
        for base in ref.layer_nodes(TOY) + ["norm_f", "head", "exit"]:
            assert names.count(f"{base}#{t}") == 1, (base, t)
    assert [n for n in names if n.startswith("gate#")] == [
        "gate#1", "gate#2", "gate#3"
    ]
    weights = [
        n for n in graph.topological_ordering()
        if isinstance(graph.op_attrs(n), WeightAttrs)
    ]
    assert sorted(graph.layer_attrs(n).name for n in weights) == sorted(
        [f"{base}.weight0" for base in (
            "embed", "norm_f", "head", "gate",
            *(f"{kind}{i}{part}" for i in (0, 1) for kind, part in (
                ("norm", "a"), ("norm", "b"), ("norm", "c"), ("norm", "d"),
                ("attn", ""), ("ffn", "_w1"), ("ffn", "_w3"), ("ffn", "_w2"),
            )),
        )] + ["gate.weight1"]
    )
    counted = sum(
        int(np.prod(graph.tensor_shape(graph.outputs_of(n)[0]).dims))
        for n in weights
    )
    assert counted == ref.parameter_counts(TOY)["total"]
    readers = {
        graph.layer_attrs(v.node).name: len(ns)
        for v, ns in weight_readers(graph).items()
    }
    assert readers["embed.weight0"] if "embed.weight0" in readers else True
    assert readers["head.weight0"] == 4 and readers["attn1.weight0"] == 4
    assert readers["gate.weight0"] == 3 and "embed.weight0" not in readers


def block_of_two(b, x, widths=(16, 16), names=("d0", "d1")):
    h = b.dense(b.rms_norm(x, name="n0"), widths[0], use_bias=False, name=names[0])
    return b.dense(h, widths[1], use_bias=False, name=names[1])


@pytest.mark.parametrize("case", ["shape", "name", "fewer", "more"])
def test_a_block_refuses_an_application_that_differs(case):
    b = ComputationGraphBuilder()
    x = b.create_input([2, 8, 16], name="x")
    block = b.shared_block()
    with block:
        h = block_of_two(b, x)
    with pytest.raises(ValueError, match="shared block"):
        with block:
            if case == "shape":
                block_of_two(b, h, widths=(16, 8))
            elif case == "name":
                block_of_two(b, h, names=("d0", "other"))
            elif case == "fewer":
                b.rms_norm(h, name="n0")
            else:
                b.dense(block_of_two(b, h), 16, use_bias=False, name="d2")
    # the builder is whole again: a well-formed application still builds
    assert b._block is None and b._reuse_queue is None
    with block:
        block_of_two(b, h)


def test_a_block_refuses_an_input_of_another_shape():
    b = ComputationGraphBuilder()
    block = b.shared_block()
    with block:
        block_of_two(b, b.create_input([2, 8, 16], name="x"))
    with pytest.raises((ValueError, AssertionError), match="shape"):
        with block:
            block_of_two(b, b.create_input([2, 8, 32], name="y"))


def test_reuse_weights_inside_a_block_binds_its_own_list_first():
    """A tied matrix read inside a block that is applied again: the inner
    list is bound, then the block's own weights go on."""
    b = ComputationGraphBuilder()
    ids = b.create_input([2, 8], DataType.INT32, name="ids")
    h = b.embedding(ids, 32, 16, name="embed")
    table = b.weight_log[-1]
    block = b.shared_block()
    for _ in range(2):
        with block:
            h = b.rms_norm(h, name="n")
            logits = b.tied_dense(h, table, name="head")
            h = b.dense(h, 16, use_bias=False, name="d")
    assert b.graph.tensor_shape(logits).dims == (2, 8, 32)
    readers = {
        b.graph.layer_attrs(v.node).name: len(ns)
        for v, ns in weight_readers(b.graph).items()
    }
    assert readers == {"embed.weight0": 3, "n.weight0": 2, "d.weight0": 2}


# -- the loss nodes -----------------------------------------------------------------


def plain_weighted_loss(logit, label, weight):
    logp = jax.nn.log_softmax(logit.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(label, 0)[..., None], -1)[..., 0]
    valid = label >= 0
    return jnp.sum(jnp.where(valid, -picked * weight, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1
    )


@pytest.mark.parametrize("negatives", [0, 5, "all"])
def test_weighted_loss_node_and_its_gradients(negatives):
    """The fused form against `jax.grad` of the plain one, to the logits AND
    to the weights, with positions that have no label: float32 on both
    sides, sums over 40 classes in another order."""
    rs = np.random.RandomState(3)
    logit = rand(rs, 2, 12, 40, scale=2.0)
    weight = jnp.asarray(rs.uniform(0.05, 0.95, (2, 12)).astype(np.float32))
    label = rs.randint(0, 40, (2, 12)).astype(np.int32)
    if negatives == "all":
        label[:] = -1
    elif negatives:
        label.reshape(-1)[rs.choice(24, negatives, replace=False)] = -1
    label = jnp.asarray(label)
    attrs = LabelCrossEntropyAttrs(0.7, position_weights=True)

    def system(logit, weight):
        return label_cross_entropy(attrs, logit, label, weight)[0]

    def plain(logit, weight):
        return 0.7 * plain_weighted_loss(logit, label, weight)

    got = jax.value_and_grad(system, argnums=(0, 1))(logit, weight)
    want = jax.value_and_grad(plain, argnums=(0, 1))(logit, weight)
    assert_trees_close(got, want, **F32)
    if negatives == "all":
        assert float(got[0]) == 0.0
    # a position without a label moves neither gradient
    off = np.asarray(label) < 0
    assert not np.asarray(got[1][1])[off].any()
    assert not np.asarray(got[1][0])[off].any()


def test_uniform_weights_are_the_unweighted_node():
    rs = np.random.RandomState(4)
    logit, label = rand(rs, 2, 9, 17), jnp.asarray(rs.randint(-1, 17, (2, 9)))
    weighted = label_cross_entropy(
        LabelCrossEntropyAttrs(1.0, True), logit, label, jnp.ones((2, 9))
    )
    plain = label_cross_entropy(LabelCrossEntropyAttrs(1.0), logit, label)
    np.testing.assert_allclose(weighted, plain, rtol=1e-6)


def test_loss_nodes_shapes_roles_and_parallel_shapes():
    logit = TensorShape((4, 8, 32), DataType.FLOAT)
    label = TensorShape((4, 8), DataType.INT32)
    weight = TensorShape((4, 8), DataType.FLOAT)
    plain, weighted = LabelCrossEntropyAttrs(), LabelCrossEntropyAttrs(1.0, True)
    assert plain == LabelCrossEntropyAttrs(1.0, False)
    assert num_data_inputs(plain) == 2 and num_data_inputs(weighted) == 3
    assert get_output_shapes(weighted, [logit, label, weight]) == [
        TensorShape((1,), DataType.FLOAT)
    ]
    with pytest.raises(AssertionError):
        get_output_shapes(weighted, [logit, label])
    with pytest.raises(AssertionError):
        get_output_shapes(plain, [logit, label, weight])
    with pytest.raises(AssertionError):
        get_output_shapes(weighted, [logit, label, label])
    term = MeanLossAttrs(0.1)
    assert op_type_of(term) == OperatorType.MEAN_LOSS
    assert num_data_inputs(term) == 1
    assert get_output_shapes(term, [weight]) == [TensorShape((1,), DataType.FLOAT)]
    # positions sharded two ways: each shard's scalar is a partial sum
    par = [
        lift_to_parallel_with_degrees(s, 1, 1, (2,) + (1,) * (len(s.dims) - 1))
        for s in (logit, label, weight)
    ]
    (out,) = get_parallel_output_shapes(weighted, par)
    assert out.sum_degree == 2 and out.shard_degrees() == (1,)
    (out,) = get_parallel_output_shapes(term, par[2:])
    assert out.sum_degree == 2
    np.testing.assert_allclose(
        mean_loss(term, jnp.arange(6.0).reshape(2, 3)), [0.25], rtol=1e-6
    )


# -- the exit distribution --------------------------------------------------------


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_exit_probabilities_sum_to_one_and_the_last_takes_the_rest(passes):
    rs = np.random.RandomState(5)
    z = rand(rs, passes - 1, 50, scale=3.0)
    p = np.asarray(ref.exit_distribution(z))
    assert p.shape == (passes, 50) and (p > 0).all()
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    np.testing.assert_allclose(p[-1], 1.0 - p[:-1].sum(axis=0), atol=1e-6)
    if passes > 1:
        np.testing.assert_allclose(p[0], jax.nn.sigmoid(z[0]), rtol=1e-6)


# -- the whole tiny step through FFModel ----------------------------------------


def test_one_weight_set_sixteen_bytes_a_parameter(toy):
    model, named, _, _ = toy
    counted = sum(int(np.prod(v.shape)) for v in named.values())
    assert counted == ref.parameter_counts(TOY)["total"]
    state = jax.tree_util.tree_leaves((model.params, model.opt_state))
    floats = [x for x in state if jnp.issubdtype(x.dtype, jnp.floating)]
    assert sum(x.size * x.dtype.itemsize for x in floats) in (
        12 * counted, 12 * counted + 4, 16 * counted,
    )


def test_fit_step_matches_reference_adam_step(toy):
    """The whole looped step before and after one `fit` step against the
    reference's own gradient (summed over the four readers of every weight)
    and Adam step: 1e-5 is float32 rounding through a forward pass of four
    passes, `F32_STEPPED` through the update as well. The five terms are counted apart, and
    the exit masses are a distribution."""
    model, named, inputs, labels = toy
    before, after = ref.reference_losses(named, inputs, labels, TOY, ADAM)
    assert abs(system_loss(model, inputs, labels) - before) <= F32_LOSS
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(system_loss(model, inputs, labels) - after) <= F32_STEPPED
    assert before - after > 100 * F32_STEPPED  # the step did something
    terms = trace.loss_terms()
    assert list(terms) == [
        "ff.label_loss.exit#1", "ff.label_loss.exit#2", "ff.label_loss.exit#3",
        "ff.label_loss.exit#4", "ff.mean_loss.entropy",
    ]
    assert terms["ff.mean_loss.entropy"]["weight"] == TOY["exit_entropy_weight"]
    total = sum(t["weight"] * t["mean"] for t in terms.values())
    assert abs(total - before) <= F32_LOSS
    masses = [terms[f"ff.label_loss.exit#{t}"]["mass"] for t in (1, 2, 3, 4)]
    assert abs(sum(masses) - 1.0) <= 1e-5 and min(masses) > 0.01
    assert "mass" not in terms["ff.mean_loss.entropy"]
    # sum_t p_t log p_t lies between -log 4 and 0
    assert -np.log(4) <= terms["ff.mean_loss.entropy"]["mean"] < 0


@pytest.mark.parametrize("recomputed", [1, 3, 4])
def test_recompute_groups_change_no_value(recomputed):
    """The same graph with nothing recomputed and with the layer
    applications of `recomputed` passes and every exit under a checkpoint
    (`run_group`: loss terms carried out of the checkpoint, a shared weight
    read by a group AND outside it): the loss, every gradient and
    `loss_terms()` of one `fit` step are equal. The forward pass is the same
    program (equal to the bit); the backward pass multiplies recomputed
    values that XLA may have fused in another way than the kept ones
    (`F32_GRADS`)."""
    inputs, labels = data()
    kept = compiled_model(KEPT, max_devices=1)
    again = compiled_model(dict(TOY, recomputed_passes=recomputed), max_devices=1)
    assert not kept.instance.cg.recompute_groups
    groups = again.instance.cg.recompute_groups
    # a group a layer application of the recomputed passes, one an exit
    assert len(groups) == (
        recomputed * TOY["num_hidden_layers"] + TOY["total_ut_steps"]
    )
    assert [
        jnp.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(kept.params),
            jax.tree_util.tree_leaves(again.params),
        )
    ].count(False) == 0
    assert system_loss(kept, inputs, labels) == system_loss(again, inputs, labels)
    assert_trees_close(
        step_grads(again, inputs, labels), step_grads(kept, inputs, labels),
        **F32_GRADS,
    )
    terms = []
    for model in (kept, again):
        model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
        terms.append(trace.loss_terms())
    assert list(terms[0]) == list(terms[1]) and len(terms[0]) == 5
    for name, term in terms[0].items():
        assert term == pytest.approx(terms[1][name], rel=1e-6), name
    assert abs(
        system_loss(kept, inputs, labels) - system_loss(again, inputs, labels)
    ) <= F32_STEPPED


def test_a_graph_with_recompute_groups_is_not_lifted_to_a_pcg():
    """A PCG holds no groups: the lift, and with it the search, the searched
    plan's executor and the memory model, refuse the graph and say so,
    where they would keep (and price) what the groups drop."""
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    builder, logits = ref.build(TOY, 4, SEQ)
    with pytest.raises(ValueError, match="recompute group.*norm0a#1"):
        pcg_from_computation_graph(builder.graph)
    model = FFModel.from_computation_graph(
        builder, logits,
        FFConfig(batch_size=4, seed=7, print_freq=0, max_devices=4,
                 search_budget=2),
    )
    with pytest.raises(ValueError, match="only_data_parallel"):
        model.compile(AdamOptimizer(alpha=ADAM["alpha"]), ADAM["loss"])


def unshared_loss(copies, sizes, ids, labels):
    """The reference's objective with pass t reading its OWN copy of every
    weight, from the reference's parts."""
    eps = sizes["rms_norm_eps"]
    h = copies[0]["embed.weight0"][ids]
    losses, z = [], []
    for t, w in enumerate(copies):
        for i in range(sizes["num_hidden_layers"]):
            h = ref.one_layer(w, i, h, sizes)
        h = ref.rms(h, w["norm_f.weight0"], eps)
        losses.append(ref.cross_entropy_rows(h, w["head.weight0"], labels))
        if t + 1 < len(copies):
            z.append(h @ w["gate.weight0"][:, 0] + w["gate.weight1"][0])
    p = ref.exit_distribution(jnp.stack(z))
    return (
        jnp.sum(p * jnp.stack(losses))
        + sizes["exit_entropy_weight"] * jnp.sum(p * jnp.log(p))
    )


def test_a_shared_weights_gradient_is_the_sum_over_its_four_readers():
    model = compiled_model(max_devices=1)
    inputs, labels = data()
    named = bench.named_parameters(model.instance, model.params)
    got = step_grads(model, inputs, labels)

    def mean_loss_of(copies):
        return sum(
            unshared_loss(copies, TOY, ids, y)
            for ids, y in zip(inputs["input_ids"], labels)
        ) / labels.size

    with jax.default_matmul_precision("highest"):
        by_copy = jax.grad(mean_loss_of)([dict(named) for _ in range(4)])
    want = {
        name: sum(g[name] for g in by_copy) for name in named
    }
    assert_trees_close(got, want, **F32_GRADS)
    # and no one reader's share is the whole (the sum is a sum)
    for g in by_copy:
        assert not np.allclose(g["head.weight0"], want["head.weight0"], rtol=0.05)


def test_one_pass_is_the_plain_stack():
    """`total_ut_steps` 1: one exit of probability one, no gate and no
    entropy term; the loss is the plain next-token mean of the same layers
    built without a block, on the same weights."""
    sizes = dict(TOY, total_ut_steps=1)
    looped = compiled_model(sizes, max_devices=1)
    inputs, labels = data()
    named = bench.named_parameters(looped.instance, looped.params)
    assert "gate.weight0" not in named
    assert not weight_readers(looped.instance.cg)

    def plain(w, ids, y):
        h = w["embed.weight0"][ids]
        for i in range(sizes["num_hidden_layers"]):
            h = ref.one_layer(w, i, h, sizes)
        h = ref.rms(h, w["norm_f.weight0"], sizes["rms_norm_eps"])
        return jnp.sum(ref.cross_entropy_rows(h, w["head.weight0"], y))

    with jax.default_matmul_precision("highest"):
        want = sum(
            plain(named, ids, y) for ids, y in zip(inputs["input_ids"], labels)
        ) / labels.size
        before, _ = ref.reference_losses(named, inputs, labels, sizes, ADAM)
    got = system_loss(looped, inputs, labels)
    assert abs(got - float(want)) <= F32_LOSS and abs(got - before) <= F32_LOSS


def test_loss_nodes_alone_need_a_loss_node():
    b = ComputationGraphBuilder()
    x = b.create_input([2, 8], name="x")
    out = b.dense(x, 4, name="d")
    model = FFModel.from_computation_graph(
        b, out, FFConfig(batch_size=2, print_freq=0, max_devices=1)
    )
    with pytest.raises(AssertionError, match="loss node"):
        model.compile(AdamOptimizer(), "loss_nodes")


def test_two_data_parallel_devices_train_to_the_one_device_loss():
    """The data-parallel backend on the same graph and weights: a shared
    weight is one replicated buffer with one gradient there too."""
    from flexflow_tpu.parallel.data_parallel import DataParallelTrainingInstance

    inputs, labels = data()
    one = compiled_model(max_devices=1)
    two = compiled_model(max_devices=2, only_data_parallel=True)
    assert isinstance(two.instance, DataParallelTrainingInstance)
    two.params = jax.tree_util.tree_map(
        lambda mine, theirs: jax.device_put(np.asarray(theirs), mine.sharding),
        two.params, one.params,
    )
    assert abs(
        system_loss(one, inputs, labels) - system_loss(two, inputs, labels)
    ) <= F32_LOSS
    for model in (one, two):
        model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert abs(
        system_loss(one, inputs, labels) - system_loss(two, inputs, labels)
    ) <= F32_STEPPED


def test_memory_analysis_counts_a_shared_weight_once():
    """Weight, gradient and two moments once a weight node, whatever its
    readers: 16 bytes a parameter of `parameter_counts()`."""
    from flexflow_tpu.analysis.memory_analysis import analyze_memory
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    builder, _ = ref.build(KEPT, BATCH, SEQ)
    analysis = analyze_memory(pcg_from_computation_graph(builder.graph))
    (device,) = analysis.per_device.values()
    total = ref.parameter_counts(TOY)["total"]
    resident = device.peak_breakdown
    assert resident["params"] == resident["grads"] == 4 * total
    assert resident["opt_state"] == 8 * total


# -- shared weights under the search ------------------------------------------------


def toy_graph(which):
    """(compiled_model(**config), data()) of one of the three toy graphs
    whose weights have several readers: this file's looped step, JoyAI's
    step (embedding and head read twice, two loss terms) and Phi's (a tied
    head, tensors handed on to later layers)."""
    if which in ("ouro", "ouro_deep"):
        # batch 4, so that a plan may divide it four ways. At two layers the
        # serial plan wins; at four layers of 32 positions the data-parallel
        # seed does, which leaves the returned logits in shards (no Combine after the last
        # head: its one reader, the loss node, takes the shards)
        sizes = KEPT if which == "ouro" else dict(
            KEPT, num_hidden_layers=4, layer_types=["full_attention"] * 4
        )
        seq = SEQ if which == "ouro" else 32

        def model(**config):
            builder, logits = ref.build(sizes, 4, seq)
            m = FFModel.from_computation_graph(
                builder, logits,
                FFConfig(batch_size=4, seed=7, print_freq=0, **config),
            )
            m.compile(AdamOptimizer(alpha=ADAM["alpha"]), ADAM["loss"])
            return m

        return model, lambda: ref.make_data(np.random.RandomState(0), sizes, 4, seq)
    if which == "joyai":
        import test_joyai_llm_flash as joyai

        return (lambda **c: joyai.compiled_model(24, **c)), (lambda: joyai.data(24))
    import test_phi4_mini_flash as phi

    return phi.compiled_model, phi.data


@pytest.mark.parametrize("which", ["ouro", "ouro_deep", "joyai", "phi"])
def test_the_search_plans_and_trains_a_graph_with_shared_weights(which):
    """On four devices, with a search budget, each graph gets a plan that
    the verifiers pass (`compile` runs them and raises on an error) and
    trains one step to the one-device loss on the same weights: before PR 62
    the machine mapping refused all three ("seed ... is unmappable": their
    data flow is no series-parallel graph, which `_levelled_decomposition`
    now gives a tree). Float32 on the CPU, the same arithmetic in both
    programs: 1e-5, and `F32_STEPPED` after the update."""
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance

    from test_olmoe import weight_keys

    model, data_of = toy_graph(which)
    inputs, labels = data_of()
    four = model(max_devices=4, search_budget=2)
    assert isinstance(four.instance, DistributedTrainingInstance)
    assert four.search_provenance["seed_runtimes"]
    if which == "ouro_deep":
        # a plan that divides the work: all but a few nodes at degree 4
        assert len(four.search_provenance["serial_compute_nodes"]) < 10
    one = model(max_devices=1)
    k1, k4 = weight_keys(one.instance), weight_keys(four.instance)
    assert sorted(k1) == sorted(k4)  # one weight a name in the plan too
    one.params = {
        k1[name]: jnp.asarray(np.asarray(four.params[k4[name]])) for name in k1
    }
    assert abs(
        system_loss(one, inputs, labels) - system_loss(four, inputs, labels)
    ) <= F32_LOSS
    for m in (one, four):
        m.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    after = system_loss(four, inputs, labels)
    assert abs(system_loss(one, inputs, labels) - after) <= F32_STEPPED


def test_a_graph_that_is_no_series_parallel_one_gets_a_levelled_tree():
    """The looped step's exits are an N-shape among compute nodes (an exit
    probability is read by its own loss node AND by the entropy term): no
    series-parallel graph with the sources collapsed or not. The tree is a
    series of stages, every node on a path of its own, and every real edge
    runs from an earlier stage to a later one."""
    from flexflow_tpu.analysis.pcg_verify import verify_pcg
    from flexflow_tpu.compiler.machine_mapping.problem_tree import (
        machine_mapping_problem_tree,
    )
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    builder, _ = ref.build(dict(KEPT, total_ut_steps=2, num_hidden_layers=1), 4, SEQ)
    pcg = pcg_from_computation_graph(builder.graph)
    _, path_of, levelled = machine_mapping_problem_tree(pcg)
    assert levelled and len(set(path_of.values())) == len(pcg.nodes)
    diags = verify_pcg(pcg)
    assert [d.rule_id for d in diags if d.rule_id == "PCG007"] == ["PCG007"]
    # one pass is a plain stack with one loss node: a series-parallel graph
    plain, _ = ref.build(dict(KEPT, total_ut_steps=1), 4, SEQ)
    assert not machine_mapping_problem_tree(pcg_from_computation_graph(plain.graph))[2]


def test_the_step_is_traced_once_by_the_harness_and_fit():
    """`benchmark/run.py` lowers the step for its bytes before `fit` runs
    it: the example label of a `loss_nodes` step is the integer one `fit`
    hands over, so both take ONE trace (a float label the logits' size
    traced, lowered and compiled the step a second time: `step_traces` 2 in
    the first chip runs of PR 62)."""
    from flexflow_tpu.analysis.lowering import lower_step_trace

    model = compiled_model(max_devices=1)
    before = (trace.span_totals().get(trace.STEP_TRACE) or {"count": 0})["count"]
    lower_step_trace(
        model.instance, model.loss_attrs, params=model.params,
        opt_state=model.opt_state,
    )
    inputs, labels = data()
    model.fit(inputs, labels, epochs=1, shuffle=False, verbose=False)
    assert trace.span_totals()[trace.STEP_TRACE]["count"] == before + 1
