"""PCG -> mesh lowering tests on the virtual 8-device CPU mesh.

The TPU-native analogue of the reference's (absent) fake-cluster tests
(SURVEY.md §4): tp/dp lowering, axis-assignment consistency, and numerical
equivalence of the distributed executor against an unconstrained run.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorDims,
    ParallelTensorShape,
    ShardParallelDim,
)
from flexflow_tpu.op_attrs.ops.loss_functions import (
    SparseCategoricalCrossEntropyLossAttrs,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.parallel import (
    DistributedTrainingInstance,
    MachineMesh,
    partition_spec_for_shape,
    pcg_shardings,
)
from flexflow_tpu.parallel.mesh import AxisPool, prime_factorization
from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs
from flexflow_tpu.pcg.parallel_computation_graph_builder import (
    ParallelComputationGraphBuilder,
)


def pts(sizes, degrees=None, sum_degree=1, copy=1):
    degrees = degrees or [1] * len(sizes)
    return ParallelTensorShape(
        ParallelTensorDims(
            tuple(ShardParallelDim(s, d) for s, d in zip(sizes, degrees)),
            sum_degree,
            copy,
        ),
        DataType.FLOAT,
    )


def test_prime_factorization():
    assert prime_factorization(1) == []
    assert prime_factorization(8) == [2, 2, 2]
    assert prime_factorization(12) == [3, 2, 2]


def test_machine_mesh_axes():
    mm = MachineMesh.for_devices(8, num_nodes=2)
    assert mm.node_axes == (("n0", 2),)
    assert mm.device_axes == (("d0", 2), ("d1", 2))
    assert mm.num_devices == 8
    assert mm.mesh.shape == {"n0": 2, "d0": 2, "d1": 2}


def test_axis_pool_allocation():
    mm = MachineMesh.for_devices(8, num_nodes=2)
    pool = AxisPool(mm)
    assert pool.allocate(4) == ("d0", "d1")
    assert pool.allocate(2) == ("n0",)  # ICI exhausted, falls to DCN
    pool2 = AxisPool(mm)
    assert pool2.allocate(2, prefer_inter=True) == ("n0",)
    assert pool2.allocate(4) == ("d0", "d1")


def test_partition_spec_megatron_consistency():
    """Activation tp axes must equal weight tp axes (no resharding in the
    Megatron chain)."""
    mm = MachineMesh.for_devices(8)  # d0,d1,d2 all size 2
    dp, tp = 2, 2
    act = partition_spec_for_shape(pts([8, 16, 32], [dp, 1, tp]), mm)
    norm = [e[0] if isinstance(e, tuple) and len(e) == 1 else e for e in act]
    assert norm == ["d0", None, "d1"]
    w = partition_spec_for_shape(
        pts([32, 64], [1, tp], copy=dp), mm, is_weight=True
    )
    # weight reserves dp's axes (d0) first -> tp lands on d1, matching act
    assert list(w) == [None, "d1"]


def test_sum_degree_unconstrained():
    mm = MachineMesh.for_devices(8)
    assert partition_spec_for_shape(pts([8, 16], [2, 1], sum_degree=2), mm) is None


def test_inexpressible_degree_unconstrained():
    mm = MachineMesh.for_devices(8)
    assert partition_spec_for_shape(pts([30, 16], [3, 1]), mm) is None


def build_tp_dp_mlp(batch, hidden, out, dp, tp):
    """Megatron-style 2-layer MLP as a Unity PCG: replicate -> col-parallel
    dense -> relu -> row-parallel dense -> reduce."""
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([batch, hidden], [dp, 1]), name="x")
    xr = b.parallel_replicate(x, tp)
    h = b.dense(xr, 4 * hidden, name="fc1")
    h = b.relu(h)
    y = b.dense(h, out, name="fc2")
    logits = b.parallel_reduce(y, tp)
    return b, logits


def test_tp_dp_pcg_shapes():
    b, logits = build_tp_dp_mlp(8, 32, 10, dp=2, tp=2)
    sh = b.graph.tensor_shape(logits)
    assert sh.sizes() == (8, 10)
    assert sh.shard_degrees() == (2, 1)
    assert sh.sum_degree == 1


def test_distributed_training_step_runs_sharded():
    b, logits = build_tp_dp_mlp(8, 32, 10, dp=2, tp=2)
    mm = MachineMesh.for_devices(8)
    inst = DistributedTrainingInstance(
        b.graph,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        SGDOptimizerAttrs(lr=0.1),
        mm,
    )
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    x = jax.device_put(
        jnp.asarray(rs.randn(8, 32), jnp.float32), inst.input_sharding("x")
    )
    y = jnp.asarray(rs.randint(0, 10, (8,)), jnp.int32)
    ls = inst.label_sharding()
    if ls is not None:
        y = jax.device_put(y, ls)
    params, opt_state, loss, _ = inst.train_step(params, opt_state, {"x": x}, y)
    jax.block_until_ready(loss)
    assert jnp.isfinite(loss)
    # fc1 weight stays sharded on its tp axis after the step
    fc1_key = next(
        k
        for n in b.graph.topological_ordering()
        for k in [f"n{n.idx}"]
        if (la := b.graph.layer_attrs(n)).name == "fc1.weight0"
    )
    spec = params[fc1_key].sharding.spec
    assert "d1" in jax.tree_util.tree_leaves(list(spec))


def test_distributed_matches_unconstrained():
    """Same PCG, same seed: 8-device sharded run == single-device run."""
    b, logits = build_tp_dp_mlp(8, 32, 10, dp=2, tp=2)
    loss_attrs = SparseCategoricalCrossEntropyLossAttrs()
    opt = SGDOptimizerAttrs(lr=0.1)
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(8, 32), jnp.float32)
    yv = jnp.asarray(rs.randint(0, 10, (8,)), jnp.int32)

    losses = []
    for ndev in (8, 1):
        mm = MachineMesh.for_devices(ndev)
        inst = DistributedTrainingInstance(b.graph, logits, loss_attrs, opt, mm)
        params, opt_state = inst.initialize(seed=0)
        cur = []
        for _ in range(3):
            params, opt_state, loss, _ = inst.train_step(
                params, opt_state, {"x": xv}, yv
            )
            cur.append(float(loss))
        losses.append(cur)
    np.testing.assert_allclose(losses[0], losses[1], rtol=2e-5)


def test_searched_mapping_feeds_lowering():
    """End-to-end: unity search output (machine_mapping) plugs into
    pcg_shardings without error."""
    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
        AnalyticTPUCostEstimator,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingContext,
    )
    from flexflow_tpu.compiler import MachineMappingCache
    from flexflow_tpu.compiler.unity_algorithm import evaluate_pcg
    from flexflow_tpu.pcg.machine_view import MachineSpecification

    b, logits = build_tp_dp_mlp(8, 32, 10, dp=2, tp=2)
    spec = MachineSpecification(1, 1, 8, 25.0, 400.0)
    ctx = MachineMappingContext(
        AnalyticTPUCostEstimator(spec), make_default_allowed_machine_views()
    )
    result = evaluate_pcg(b.graph, ctx, spec, MachineMappingCache())
    if result is None:
        pytest.skip("PCG not SP-decomposable with this builder output")
    mm = MachineMesh.from_spec(spec)
    sh = pcg_shardings(b.graph, mm, result.machine_mapping)
    all_tensors = {
        o for n in b.graph.topological_ordering() for o in b.graph.outputs_of(n)
    }
    assert set(sh) == all_tensors


def test_pinned_reduction_collective(monkeypatch):
    """A sum_degree>1 producer + Reduction lowers through the PINNED
    shard_map+psum path (executor._try_pinned_reduction), the forward HLO
    carries exactly as many all-reduces as the plan priced Reduction nodes,
    and the numerics match the single-device run (round-3 verdict weak #3:
    sum_degree>1 tensors previously lowered unconstrained, leaving the
    executed collectives to GSPMD's discretion)."""
    import flexflow_tpu.parallel.executor as ex
    from flexflow_tpu.op_attrs.ops import ReductionAttrs

    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([8, 32], [1, 4]), name="x")
    y = b.dense(x, 16, use_bias=False, name="fc")  # row-parallel: partials
    logits = b.parallel_reduce(y, 4)
    assert b.graph.tensor_shape(y).sum_degree == 4

    calls = []
    orig = ex._try_pinned_reduction

    def spy(*a, **kw):
        out = orig(*a, **kw)
        if out is not None:
            calls.append(1)
        return out

    monkeypatch.setattr(ex, "_try_pinned_reduction", spy)

    loss_attrs = SparseCategoricalCrossEntropyLossAttrs()
    opt = SGDOptimizerAttrs(lr=0.1)
    inst = DistributedTrainingInstance(
        b.graph, logits, loss_attrs, opt, MachineMesh.for_devices(4)
    )
    params, _ = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(8, 32), jnp.float32)
    out = inst.forward(params, {"x": xv})
    assert calls, "pinned-reduction path did not engage"

    # numerics: identical to the single-device (serial-semantics) run
    ref = DistributedTrainingInstance(
        b.graph, logits, loss_attrs, opt, MachineMesh.for_devices(1)
    )
    rp, _ = ref.initialize(seed=0)
    # different summation order (4 local partials + psum vs one full
    # contraction) moves the last f32 digit
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.forward(rp, {"x": xv})),
        rtol=1e-4, atol=1e-5,
    )

    # collective count: forward all-reduces == Reduction nodes in the plan
    n_reductions = sum(
        isinstance(b.graph.op_attrs(n), ReductionAttrs) for n in b.graph.nodes
    )
    with inst.machine_mesh.mesh:
        txt = inst._jit_fwd.lower(params, {"x": xv}).compile().as_text()
    n_allreduce = txt.count(" all-reduce(")
    n_allreduce += txt.count(" all-reduce-start(")
    assert n_allreduce == n_reductions, (
        f"priced {n_reductions} reduction all-reduce(s), compiled "
        f"{n_allreduce}"
    )


def test_pinned_reduction_keeps_fusion_barrier():
    """Round-4 review regression: the pinned-reduction fast path must not
    drop the LM-head optimization barrier (barrier_nodes) — a tp-sharded
    bias-free head is exactly a node that takes the pinned path."""
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([8, 32], [1, 4]), name="x")
    logits = b.parallel_reduce(b.dense(x, 16, use_bias=False, name="head"), 4)
    inst = DistributedTrainingInstance(
        b.graph, logits, SparseCategoricalCrossEntropyLossAttrs(),
        SGDOptimizerAttrs(lr=0.1), MachineMesh.for_devices(4),
    )
    assert inst._barrier_nodes  # the head IS the barrier node
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    x_v = jnp.asarray(rs.randn(8, 32), jnp.float32)
    y_v = jnp.asarray(rs.randint(0, 16, (8,)), jnp.int32)
    with inst.machine_mesh.mesh:
        txt = jax.jit(inst._step, donate_argnums=(0, 1)).lower(
            params, opt_state, {"x": x_v}, y_v, jax.random.PRNGKey(0)
        ).as_text()
    assert "optimization_barrier" in txt, (
        "fusion barrier lost on the pinned path"
    )


def test_weight_repartition_chain_rests_fully_sharded():
    """Round-4 review regression: when a weight feeds a chain of
    Repartitions, EVERY link adopts the final sharding (an intermediate
    partial spec would force a per-step all-gather of the resident
    parameter)."""
    from flexflow_tpu.op_attrs.ops import RepartitionAttrs, WeightAttrs
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape
    from flexflow_tpu.op_attrs.datatype import DataType as DT
    from flexflow_tpu.pcg.parallel_computation_graph import (
        ParallelComputationGraph,
        ParallelLayerAttrs,
        ParallelTensorAttrs,
    )
    from flexflow_tpu.op_attrs.core import get_parallel_output_shapes
    from flexflow_tpu.op_attrs.parallel_tensor_shape import lift_to_parallel

    pcg = ParallelComputationGraph()
    wts = TensorShape((32, 16), DT.FLOAT)
    _, (v,) = pcg.add_node(
        ParallelLayerAttrs(WeightAttrs(wts), "w"),
        [],
        [ParallelTensorAttrs(lift_to_parallel(wts), True, None)],
    )
    chain_vals = [v]
    for attrs in (RepartitionAttrs(0, 2), RepartitionAttrs(1, 2)):
        (shape,) = get_parallel_output_shapes(attrs, [pcg.tensor_shape(v)])
        _, (v,) = pcg.add_node(
            ParallelLayerAttrs(attrs, None), [v],
            [ParallelTensorAttrs(shape, True, None)],
        )
        chain_vals.append(v)
    mm = MachineMesh.for_devices(4)
    sh = pcg_shardings(pcg, mm)
    # the weight AND every chain link adopt the final (fully sharded) spec
    final = sh[chain_vals[-1]]
    assert final is not None
    for cv in chain_vals:
        assert sh[cv] is final, (cv, sh[cv])


def test_biased_attention_data_parallel_step_on_four_devices(monkeypatch):
    """The batch-parallel template carries [b/4, s, e] into BERT's biased
    attention, so the executor's flash path shards it over the batch: the
    compiled step gathers no activation and reduces each gradient once (and
    the loss); one step equals a single device's."""
    import re

    from flexflow_tpu.analysis.lowering import lower_step_trace
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.local_execution.training_backing import (
        ModelTrainingInstance,
    )
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    from test_seed_templates import bert_like_graph

    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", "128")

    def compiled(**config):
        graph, logits = bert_like_graph(batch=8, seq=128)
        model = FFModel.from_computation_graph(
            graph, logits,
            FFConfig(batch_size=8, seed=0, print_freq=0, **config),
        )
        model.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
        graph = getattr(model.instance, "pcg", None) or model.instance.cg
        keys = {
            graph.layer_attrs(n).name: f"n{n.idx}"
            for n in graph.topological_ordering()
            if isinstance(graph.op_attrs(n), WeightAttrs)
        }
        return model, keys

    four, keys4 = compiled(
        max_devices=4, search_budget=2, force_strategy_seed="dp4xtp1xsp1"
    )
    assert isinstance(four.instance, DistributedTrainingInstance)
    assert four.search_provenance["serial_compute_nodes"] == []

    text = lower_step_trace(
        four.instance, four.loss_attrs,
        params=four.params, opt_state=four.opt_state,
    ).compile().as_text()
    assert "flash_fwd" in text  # the sharded kernel path, not dense XLA
    collectives = [
        line for line in text.split("\n")
        if re.search(
            r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
            r"collective-permute)(-start)?\(", line,
        )
    ]
    # since PR 30 a replicated weight is stored, and updated, a quarter a
    # chip: the only gathers are those of the weights' compute copies
    gathers = [c for c in collectives if " all-gather(" in c]
    collectives = [c for c in collectives if c not in gathers]
    assert len(gathers) >= len(four.instance.update_shardings) > 0
    weight_shapes = {tuple(v.shape) for v in four.params.values()}
    for c in gathers:
        (dims,) = re.findall(r"= f32\[([0-9,]*)\]", c)
        assert tuple(int(d) for d in dims.split(",")) in weight_shapes, c
    assert collectives and all(" all-reduce(" in c for c in collectives)
    assert not any("/psum" in c for c in collectives)
    reduced = sum(
        int(np.prod([int(d) for d in dims.split(",") if d]))
        for c in collectives
        for dims in re.findall(r"\w+\[([0-9,]*)\]", c.split(" all-reduce(")[0])
    )
    n_params = sum(int(np.prod(v.shape)) for v in four.params.values())
    assert reduced == n_params + 1  # every gradient once, and the loss

    one, keys1 = compiled(max_devices=1)
    assert isinstance(one.instance, ModelTrainingInstance)
    assert one.search_provenance is None
    assert set(keys1) == set(keys4)
    one.params = {
        keys1[name]: jnp.asarray(np.asarray(four.params[keys4[name]]))
        for name in keys1
    }
    rs = np.random.RandomState(0)
    x = rs.randn(8, 128, 32).astype(np.float32)
    y = rs.randint(0, 8, (8, 128)).astype(np.int32)
    four.fit(x, y, epochs=1, verbose=False)
    one.fit(x, y, epochs=1, verbose=False)
    for name in keys1:
        np.testing.assert_allclose(
            np.asarray(four.params[keys4[name]]),
            np.asarray(one.params[keys1[name]]),
            atol=1e-5, rtol=0, err_msg=name,
        )


@pytest.mark.parametrize(
    "seed,seq,route,kernel",
    [
        ("dp2xtp1xsp1", 128, "fused_row_sharded", "flash_fwd_pair_qkv"),
        ("dp1xtp2xsp1", 128, "rows_sharded", "flash_fwd_rows_folded"),
        ("dp2xtp1xsp1", 64, "dense", None),
        ("dp1xtp1xsp2-ring", 128, "seq_parallel", None),
    ],
)
def test_attention_routes_after_compile(monkeypatch, seed, seq, route, kernel):
    """The instance names, per attention node, the route the executor will
    lower it by, readable after compile() with nothing traced; the compiled
    step holds that route's kernel and no other."""
    from flexflow_tpu.analysis.lowering import lower_step_trace
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    from test_seed_templates import bert_like_graph

    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_MIN_SEQ", "128")
    # two heads of 64: one head pair, so a batch shard can take the fused row
    graph, logits = bert_like_graph(
        batch=4, seq=seq, hidden=128, heads=2, blocks=2, bias=False
    )
    model = FFModel.from_computation_graph(
        graph, logits,
        FFConfig(
            batch_size=4, seed=0, print_freq=0, max_devices=2,
            search_budget=2, force_strategy_seed=seed,
        ),
    )
    model.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy")
    assert isinstance(model.instance, DistributedTrainingInstance)
    # the sequence-parallel template rewrites the op; scopes carry the kind
    kind = "ring_attention" if route == "seq_parallel" else "mha"
    assert model.instance.attention_routes == {
        f"ff.{kind}.attn{i}": route for i in range(2)
    }
    text = lower_step_trace(
        model.instance, model.loss_attrs,
        params=model.params, opt_state=model.opt_state,
    ).compile().as_text()
    found = set(re.findall(r"flash_fwd_\w+", text))
    assert found == ({kernel} if kernel else set()), found
