"""The weight update sharded over the axes a weight is replicated on (ISSUE
30), on the virtual CPU mesh: a weight's float32 master and its optimizer
slots are stored at the weight's sharding plus every free mesh axis
(`update_partition_spec`), the update runs there, and the copy the step
computes with is gathered where the PCG places the weight.

The reference of every equivalence here is the SAME instance with the rule
switched off (`update_partition_spec` patched to place nothing): the
replicated state and update the executor ran before.
"""

import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flexflow_tpu.op_attrs.ops.loss_functions import (
    SparseCategoricalCrossEntropyLossAttrs,
)
from flexflow_tpu.parallel import DistributedTrainingInstance, MachineMesh
from flexflow_tpu.parallel import executor
from flexflow_tpu.parallel.sharding import update_partition_spec
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs, SGDOptimizerAttrs
from flexflow_tpu.pcg.parallel_computation_graph_builder import (
    ParallelComputationGraphBuilder,
)

from test_parallel_lowering import build_tp_dp_mlp, pts

MESH_2x2 = {"d0": 2, "d1": 2}


# -- the rule ------------------------------------------------------------------


@pytest.mark.parametrize(
    "shape, spec, mesh, want",
    [
        # replicated matrix: both axes on the first dimension
        ((1024, 4096), (), MESH_2x2, ((("d0", "d1"), None), ("d0", "d1"))),
        # BERT's [30522, 1024] embedding: 30522 % 4 != 0, take the second
        ((30522, 1024), (None, None), MESH_2x2,
         ((None, ("d0", "d1")), ("d0", "d1"))),
        # a vector of odd length stays whole
        ((9,), (None,), MESH_2x2, ((None,), ())),
        # 30522 is even but 4 does not divide it: all free axes or none
        ((30522,), (), MESH_2x2, ((None,), ())),
        # column-parallel weight, replicated over d0: d0 joins dimension 0
        ((32, 128), (None, "d1"), MESH_2x2, (("d0", "d1"), ("d0",))),
        # row-parallel weight: dimension 0 is held by d1, d0 is appended
        ((128, 9), ("d1", None), MESH_2x2, ((("d1", "d0"), None), ("d0",))),
        # ... unless the local extent does not divide: fall to dimension 1
        ((2, 8), ("d1", None), MESH_2x2, (("d1", "d0"), ("d0",))),
        # sharded over every axis: no free axis
        ((8, 8), ("d0", "d1"), MESH_2x2, (("d0", "d1"), ())),
        # one chip: nothing to place, a scalar neither
        ((8, 8), (), {"d0": 1}, ((None, None), ())),
        ((), (), MESH_2x2, ((), ())),
    ],
)
def test_update_partition_spec(shape, spec, mesh, want):
    assert update_partition_spec(shape, spec, mesh) == want


# -- instances -----------------------------------------------------------------


def dp_mlp(batch, dp):
    """A data-parallel MLP whose every weight is replicated: a [6, 16]
    matrix (6 % 4 != 0: dimension 1), a [16, 9] one and a [9] bias that no
    axis divides."""
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([batch, 6], [dp, 1]), name="x")
    h = b.dense(x, 16, name="fc1")
    h = b.relu(h)
    logits = b.dense(h, 9, name="fc2")
    return b, logits, 6, 9


def tp_dp_mlp(batch, dp):
    """Megatron MLP: fc1 column-parallel, fc2 row-parallel over tp = 2,
    both replicated over dp."""
    b, logits = build_tp_dp_mlp(batch, 32, 10, dp=dp, tp=2)
    return b, logits, 32, 10


OPTIMIZERS = {
    "adam": AdamOptimizerAttrs(alpha=1e-2, weight_decay=0.01),
    "sgd_momentum": SGDOptimizerAttrs(lr=0.1, momentum=0.9, weight_decay=0.01),
}
PLANS = {
    "dp2": (dp_mlp, 2, 2),
    "dp4": (dp_mlp, 4, 4),
    "tp2_dp2": (tp_dp_mlp, 2, 4),
    "tp2_dp1": (tp_dp_mlp, 1, 2),
}


def whole_update(monkeypatch):
    """The rule switched off: every slot like its weight, as before."""
    monkeypatch.setattr(
        executor, "update_partition_spec",
        lambda shape, spec, mesh_shape: (tuple(spec), ()),
    )


def build(plan, opt):
    make, dp, ndev = PLANS[plan]
    b, logits, width, classes = make(8, dp)
    inst = DistributedTrainingInstance(
        b.graph, logits, SparseCategoricalCrossEntropyLossAttrs(),
        OPTIMIZERS[opt], MachineMesh.for_devices(ndev),
    )
    return inst, b.graph, width, classes


def train(inst, width, classes, steps=3):
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    x = jax.device_put(
        jnp.asarray(rs.randn(8, width), jnp.float32), inst.input_sharding("x")
    )
    y = jax.device_put(
        jnp.asarray(rs.randint(0, classes, (8,)), jnp.int32),
        inst.label_sharding(),
    )
    for _ in range(steps):
        params, opt_state, loss, _ = inst.train_step(
            params, opt_state, {"x": x}, y
        )
    return params, opt_state, (x, y)


@pytest.mark.parametrize("opt", sorted(OPTIMIZERS))
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_sharded_update_equals_the_replicated_update(plan, opt, monkeypatch):
    """Three steps: parameters and slots equal the replicated update's bit
    for bit. On the CPU mesh both programs reduce each gradient with the
    same all-reduce (XLA's CPU pipeline has no reduce-scatter rewrite, so
    the order of the sum is one), and the update is elementwise."""
    inst, graph, width, classes = build(plan, opt)
    params, opt_state, _ = train(inst, width, classes)
    assert inst.update_shardings, "no leaf took a free axis"

    # placements: the master and every slot at the update sharding, which
    # refines what the PCG says of the weight; the step counter replicated
    slots = [v for v in opt_state.values() if isinstance(v, dict)]
    assert slots
    mesh_shape = dict(inst.machine_mesh.mesh.shape)
    for k, w in params.items():
        (out,) = [
            graph.outputs_of(n)[0] for n in graph.topological_ordering()
            if executor.param_key(n) == k
        ]
        placed = inst.shardings.get(out)
        spec, axes = update_partition_spec(w.shape, placed.spec, mesh_shape)
        at = inst.update_shardings.get(k, placed)
        assert (k in inst.update_shardings) == bool(axes)
        assert at.is_equivalent_to(
            jax.sharding.NamedSharding(inst.machine_mesh.mesh, P(*spec)), w.ndim
        )
        assert w.sharding == at, (k, w.sharding, at)
        for slot in slots:
            assert slot[k].sharding == at, (k, slot[k].sharding, at)
    assert opt_state["step"].sharding.spec == P()

    whole_update(monkeypatch)
    ref, _, _, _ = build(plan, opt)
    ref_params, ref_state, _ = train(ref, width, classes)
    assert not ref.update_shardings
    for k in params:
        assert np.array_equal(np.asarray(params[k]), np.asarray(ref_params[k])), k
    for a, r in zip(jax.tree_util.tree_leaves(opt_state),
                    jax.tree_util.tree_leaves(ref_state)):
        assert np.array_equal(np.asarray(a), np.asarray(r))


def test_leaves_left_whole_and_sharded_by_plan():
    """The leaf no axis divides keeps its weight's sharding; a weight the
    tensor-parallel plan already shards takes only the axis left."""
    inst, graph, _, _ = build("dp4", "adam")
    inst.initialize(seed=0)
    names = {
        executor.param_key(n): graph.layer_attrs(n).name
        for n in graph.topological_ordering()
    }
    at = {names[k]: s.spec for k, s in inst.update_shardings.items()}
    assert at == {
        "fc1.weight0": P(None, ("d0", "d1")),  # 6 rows: dimension 1
        "fc1.weight1": P(("d0", "d1")),
        "fc2.weight0": P(("d0", "d1"), None),
    }  # fc2.weight1 [9] is whole
    inst, graph, _, _ = build("tp2_dp2", "adam")
    inst.initialize(seed=0)
    names = {
        executor.param_key(n): graph.layer_attrs(n).name
        for n in graph.topological_ordering()
    }
    at = {names[k]: s.spec for k, s in inst.update_shardings.items()}
    assert at["fc1.weight0"] == P("d0", "d1")  # [32, 128] column-parallel
    assert at["fc2.weight0"] == P(("d1", "d0"), None)  # [128, 10] row-parallel


def compiled_step_of(inst, width, classes):
    params, opt_state = inst.initialize(seed=0)
    x = jax.device_put(jnp.zeros((8, width)), inst.input_sharding("x"))
    y = jax.device_put(jnp.zeros((8,), jnp.int32), inst.label_sharding())
    with inst.machine_mesh.mesh:
        return inst.compiled_step().lower(
            params, opt_state, {"x": x}, y, jax.random.PRNGKey(0)
        ).compile(), params, (x, y)


@pytest.mark.parametrize("plan", ["dp4", "tp2_dp2"])
def test_argument_bytes_and_gathers_are_what_the_rule_predicts(plan):
    """Per device the step's arguments are each weight and its two slots at
    1/ways of the piece the PCG places, and everything the step gathers is
    a sharded leaf's compute copy: no activation, no gradient."""
    inst, graph, width, classes = build(plan, "adam")
    compiled, params, batch = compiled_step_of(inst, width, classes)
    mesh_shape = dict(inst.machine_mesh.mesh.shape)
    mesh = inst.machine_mesh.mesh

    def local_bytes(a):
        return int(np.prod(a.sharding.shard_shape(a.shape))) * 4

    want = 4  # the step counter (jit drops the unused rng key)
    want += sum(local_bytes(a) for a in batch)
    stored = placed_total = gathered = 0
    gatherable = set()
    for k, w in params.items():
        (out,) = [
            graph.outputs_of(n)[0] for n in graph.topological_ordering()
            if executor.param_key(n) == k
        ]
        placed = inst.shardings.get(out)
        piece = int(np.prod(placed.shard_shape(w.shape))) * 4
        _, axes = update_partition_spec(w.shape, placed.spec, mesh_shape)
        ways = int(np.prod([mesh_shape[a] for a in axes]))
        want += 3 * piece // ways
        stored += piece // ways
        placed_total += piece
        if axes:
            gathered += piece
            gatherable.add(tuple(placed.shard_shape(w.shape)))
    assert compiled.memory_analysis().argument_size_in_bytes == want
    record = inst.update_record
    assert record["bytes_per_device"] == stored
    assert record["bytes_per_device_as_placed"] == placed_total
    assert record["gather_bytes_per_device"] == gathered
    assert record["leaves_sharded"] == len(inst.update_shardings)
    gathers = [
        line for line in compiled.as_text().split("\n")
        if re.search(r" all-gather(-start)?\(", line)
    ]
    assert len(gathers) >= len(inst.update_shardings)
    for line in gathers:
        (dims,) = re.findall(r"= f32\[([0-9,]*)\]", line)
        assert tuple(int(d) for d in dims.split(",")) in gatherable, line


def test_second_step_does_not_recompile():
    """The state comes back under the shardings it arrived with (PR 21's
    trap), the sharded slots included."""
    inst, _, width, classes = build("tp2_dp2", "adam")
    train(inst, width, classes, steps=3)
    assert inst.compiled_step()._cache_size() == 1


def test_one_device_lowers_no_update_sharding():
    """No free axis on one chip: nothing is constrained, nothing gathered."""
    b, logits, width, classes = dp_mlp(8, 1)
    inst = DistributedTrainingInstance(
        b.graph, logits, SparseCategoricalCrossEntropyLossAttrs(),
        OPTIMIZERS["adam"], MachineMesh.for_devices(1),
    )
    compiled, _, _ = compiled_step_of(inst, width, classes)
    assert inst.update_shardings == {}
    text = compiled.as_text()
    assert "all-gather" not in text


# -- through FFModel: checkpoints and recompile ----------------------------------


def _model(max_devices):
    from flexflow_tpu.core import FFConfig, FFModel

    m = FFModel(FFConfig(
        batch_size=16, seed=0, print_freq=0, search_budget=2,
        max_devices=max_devices,
    ))
    x = m.create_tensor([16, 32], name="x")
    h = m.relu(m.dense(x, 64, name="fc1"))
    logits = m.dense(h, 10, name="head")
    m.compile(
        AdamOptimizerAttrs(alpha=1e-2), "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    return m


def _xy(n=64):
    rs = np.random.RandomState(0)
    return rs.randn(n, 32).astype(np.float32), rs.randint(0, 10, n)


def _named_moments(m):
    graph = getattr(m.instance, "pcg", None) or m.instance.cg
    names = {
        executor.param_key(n): graph.layer_attrs(n).name
        for n in graph.topological_ordering()
    }
    return {
        (slot, names[k]): np.asarray(v)
        for slot in ("m", "v") for k, v in m.opt_state[slot].items()
    }


@pytest.mark.parametrize("restore_on", [1, 4])
def test_checkpoint_with_sharded_moments_restores_and_recompiles(restore_on):
    """Weights and moments saved from a 2 x 2 mesh at their update
    shardings restore on one device and on a 2 x 2 layout value for value,
    at the restoring plan's own placement, and `recompile()` keeps training
    (TRN001-004 pass: the verifier raises on a fatal rule)."""
    X, Y = _xy()
    four = _model(4)
    assert isinstance(four.instance, DistributedTrainingInstance)
    assert four.instance.update_shardings
    record = four.search_provenance["update_sharding"]
    assert record == four.instance.update_record
    assert record["leaves_sharded"] == len(four.instance.update_shardings)
    assert record["bytes_per_device"] < record["bytes_per_device_as_placed"]
    four.fit(X, Y, epochs=1, batch_size=16, verbose=False)
    assert any(
        len(v.sharding.device_set) == 4 and not v.sharding.is_fully_replicated
        for v in four.opt_state["m"].values()
    )
    saved = _named_moments(four)
    ckpt = tempfile.mkdtemp()
    four.save_checkpoint(ckpt)

    other = _model(restore_on)
    other.load_checkpoint(ckpt)
    restored = _named_moments(other)
    assert saved.keys() == restored.keys()
    for key in saved:
        assert np.array_equal(saved[key], restored[key]), key
    if restore_on == 4:
        for k, s in other.instance.update_shardings.items():
            assert other.opt_state["m"][k].sharding == s
            assert other.opt_state["v"][k].sharding == s
    if restore_on == 4:
        before = float(
            other.instance.loss_fn(other.params, {"x": X[:16]}, Y[:16])[0]
        )

    other.recompile()
    after_swap = _named_moments(other)
    for key in saved:
        assert np.array_equal(saved[key], after_swap[key]), key
    other.fit(X, Y, epochs=1, batch_size=16, verbose=False)
    assert all(np.isfinite(v).all() for v in _named_moments(other).values())
    if restore_on == 4:
        # the recompiled instance compiled its step once and kept training
        assert other.instance.compiled_step()._cache_size() == 1
        after = float(
            other.instance.loss_fn(other.params, {"x": X[:16]}, Y[:16])[0]
        )
        assert after < before
