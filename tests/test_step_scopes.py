"""Device-trace scopes (observability/trace.py `node_scope` / `step_scope` /
`parse_scope`): every operation a step program lowers carries the name of
its PCG node and its part of the step, the parser is the format's inverse,
the program is the same with and without the scopes, and every flash
`pallas_call` lowers under a stable kernel name.

All on the virtual CPU mesh: names and counts, never a time."""

import contextlib
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.analysis.lowering import lower_step_trace
from flexflow_tpu.kernels import flash_attention as fa
from flexflow_tpu.kernels import ring_flash
from flexflow_tpu.local_execution.training_backing import ModelTrainingInstance
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.core import OperatorType, PARALLEL_OP_TYPES
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs
from flexflow_tpu.op_attrs.ops.loss_functions import (
    SparseCategoricalCrossEntropyLossAttrs,
)
from flexflow_tpu.parallel import DistributedTrainingInstance, MachineMesh
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
from flexflow_tpu.pcg.parallel_computation_graph_builder import (
    ParallelComputationGraphBuilder,
)
from flexflow_tpu.utils.shard_map_compat import shard_map_compat

from test_parallel_lowering import pts

OP_NAME = re.compile(r'op_name="([^"]*)"')
COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)


# -- (i) format and parser ---------------------------------------------------


@pytest.mark.parametrize("op_type", list(OperatorType), ids=lambda t: t.value)
def test_parse_scope_round_trips_every_kind(op_type):
    kind = trace.scope_kind(op_type)
    assert re.fullmatch(r"[a-z0-9_]+", kind), kind  # no dot inside a kind
    assert kind.startswith("parallel_") == (op_type in PARALLEL_OP_TYPES)
    scope = f"ff.{kind}.blk3.attn"
    assert trace.parse_scope(f"jit(step)/jvp({scope})/dot_general") == (
        "fwd", kind, "blk3.attn",
    )
    assert trace.parse_scope(f"jit(step)/transpose(jvp({scope}))/mul") == (
        "bwd", kind, "blk3.attn",
    )
    assert trace.parse_scope(
        f"jit(step)/jvp(checkpoint)/rematted_computation/{scope}/exp"
    ) == ("bwd", kind, "blk3.attn")


def _one_node_graph(attrs, name):
    return SimpleNamespace(
        layer_attrs=lambda n: SimpleNamespace(attrs=attrs, name=name)
    )


@pytest.mark.parametrize(
    "layer_name, want",
    [
        ("enc/block 0 (attn)", "enc_block_0__attn_"),
        ("a.b-c_d", "a.b-c_d"),
        # a `SharedBlock`'s layer carries its application (PR 62)
        ("attn3#2", "attn3#2"),
        ("jvp(x)/transpose(y)", "jvp_x__transpose_y_"),
        (None, "n7"),
        ("", "n7"),
    ],
)
def test_scope_name_survives_the_name_stack(layer_name, want):
    graph = _one_node_graph(WeightAttrs(None), layer_name)
    scope = trace.scope_name(graph, SimpleNamespace(idx=7))
    assert scope == "ff.weight." + want
    # through JAX's own name stack and its transforms, not a hand-made string

    def f(x):
        with trace.node_scope(graph, SimpleNamespace(idx=7)):
            return jnp.sin(x) * 2.0

    text = jax.jit(jax.grad(f)).lower(1.0).compile().as_text()
    parsed = {trace.parse_scope(n) for n in OP_NAME.findall(text)}
    parsed.discard(("unattributed", "", ""))
    assert parsed and parsed <= {
        ("fwd", "weight", want), ("bwd", "weight", want)
    }


@pytest.mark.parametrize(
    "op_name, want",
    [
        ("jit(_step)/ff.optimizer/sub", ("opt", "optimizer", "")),
        ("jit(_step)/jvp(ff.cast)/convert_element_type", ("other", "cast", "")),
        ("jit(_step)/transpose(jvp(ff.cast))/convert_element_type",
         ("other", "cast", "")),
        ("jit(_step)/ff.metrics/argmax", ("other", "metrics", "")),
        ("jit(_step)/ff.health/reduce_sum", ("other", "health", "")),
        ("jit(_step)/jvp(ff.loss)/reduce_sum", ("fwd", "loss", "")),
        ("jit(_step)/transpose(jvp(ff.loss))/mul", ("bwd", "loss", "")),
        # an operator of kind `cast` is a node, not the step's own cast
        ("jit(_step)/jvp(ff.cast.to_half)/convert_element_type",
         ("fwd", "cast", "to_half")),
        # a transpose OPERATOR is not JAX's transpose transform
        ("jit(_step)/jvp(ff.transpose.t0)/transpose", ("fwd", "transpose", "t0")),
        ("jit(_step)/jvp(ff.mha.a0)/shard_map/flash_fwd_pair/pallas_call",
         ("fwd", "mha", "a0")),
        ("jit(_step)/while/body/transpose(jvp(ff.dense.l0))/dot_general",
         ("bwd", "dense", "l0")),
        # a state-space node's scan keeps its own scope in the name
        ("jit(_step)/jvp(ff.ssm.m0)/scan/dot_general", ("fwd", "ssm", "m0/scan")),
        ("jit(_step)/transpose(jvp(ff.ssm.m0))/scan/checkpoint/mul",
         ("bwd", "ssm", "m0/scan")),
        ("jit(_step)/jvp(ff.ssm.m0)/dot_general", ("fwd", "ssm", "m0")),
        # and so do its convolution with SiLU and its gated norm, whose
        # written backwards close the part's scope with the node's
        ("jit(_step)/jvp(ff.ssm.m0)/conv/convert_element_type",
         ("fwd", "ssm", "m0/conv")),
        ("jit(_step)/transpose(jvp(ff.ssm.m0/conv))/reduce_sum",
         ("bwd", "ssm", "m0/conv")),
        ("jit(_step)/jvp(ff.ssm.m0)/norm/reduce_sum", ("fwd", "ssm", "m0/norm")),
        ("jit(_step)/transpose(jvp(ff.ssm.m0/norm))/convert_element_type",
         ("bwd", "ssm", "m0/norm")),
        ("jit(_step)/jvp(ff.ssm.m0)/normal/mul", ("fwd", "ssm", "m0")),
        ("jit(_step)/jvp(ff.dense.d)/scan/mul", ("fwd", "dense", "d")),
        # an experts node's parts (`trace.NODE_PARTS`) keep theirs too
        ("jit(_step)/jvp(ff.experts.moe1)/router/dot_general",
         ("fwd", "experts", "moe1/router")),
        ("jit(_step)/transpose(jvp(ff.experts.moe1))/latent/dot_general",
         ("bwd", "experts", "moe1/latent")),
        ("jit(_step)/jvp(ff.experts.moe1)/routed/grouped_matmul/gmm/pallas_call",
         ("fwd", "experts", "moe1/routed")),
        ("jit(_step)/transpose(jvp(ff.experts.moe1))/shared/shared_expert/mul",
         ("bwd", "experts", "moe1/shared")),
        # the gated delta-rule node's parts; its recurrence is recomputed
        # under one `jax.checkpoint`, which writes the rematerialised
        # forward's own scope between the node's and the part's
        ("jit(_step)/jvp(ff.kda.kda2)/prep/dot_general",
         ("fwd", "kda", "kda2/prep")),
        ("jit(_step)/jvp(ff.kda.kda2)/scan/kda_fwd_chunk/pallas_call",
         ("fwd", "kda", "kda2/scan")),
        ("jit(_step)/transpose(jvp(ff.kda.kda2))/jvp(ff.kda.kda2)/checkpoint"
         "/rematted_computation/prep/exp", ("bwd", "kda", "kda2/prep")),
        ("jit(_step)/transpose(jvp(ff.kda.kda2))/jvp(ff.kda.kda2)/checkpoint"
         "/scan/kda_bwd_chunk/pallas_call", ("bwd", "kda", "kda2/scan")),
        ("jit(_step)/transpose(jvp(ff.kda.kda2))/gates/dot_general",
         ("bwd", "kda", "kda2/gates")),
        ("jit(_step)/transpose(jvp(ff.kda.kda2))/jvp(ff.kda.kda2)/checkpoint"
         "/mul", ("bwd", "kda", "kda2")),
        # latent attention's low-rank projections and its core
        ("jit(_step)/jvp(ff.ring_attention.mla4)/latent/dot_general",
         ("fwd", "ring_attention", "mla4/latent")),
        ("jit(_step)/transpose(jvp(ff.ring_attention.mla4))/core"
         "/flash_bwd_causal_bshf/pallas_call",
         ("bwd", "ring_attention", "mla4/core")),
        ("jit(_step)/jvp(ff.ring_attention.a0)/dot_general",
         ("fwd", "ring_attention", "a0")),
        # the short-convolution node: the chain between its two projections
        # (the input gate, the taps, the output gate), whose written backward
        # opens the part's scope again under the node's transposed one
        ("jit(_step)/jvp(ff.shortconv.conv2)/conv/mul",
         ("fwd", "shortconv", "conv2/conv")),
        ("jit(_step)/transpose(jvp(ff.shortconv.conv2))/conv/reduce_sum",
         ("bwd", "shortconv", "conv2/conv")),
        ("jit(_step)/transpose(jvp(ff.shortconv.conv2))/dot_general",
         ("bwd", "shortconv", "conv2")),
        ("jit(_step)/jvp(ff.ring_attention.attn1)/core/flash_fwd_causal_bshf"
         "/pallas_call", ("fwd", "ring_attention", "attn1/core")),
        # the selective-scan node (kind `s6`) and its five parts; the scan's
        # written backward opens the part's scope under the transposed node's
        ("jit(_step)/jvp(ff.s6.s16)/in_proj/dot_general",
         ("fwd", "s6", "s16/in_proj")),
        ("jit(_step)/jvp(ff.s6.s16)/scan/s6_scan_fwd/pallas_call",
         ("fwd", "s6", "s16/scan")),
        ("jit(_step)/transpose(jvp(ff.s6.s16))/scan/s6_scan_bwd/pallas_call",
         ("bwd", "s6", "s16/scan")),
        ("jit(_step)/transpose(jvp(ff.s6.s0))/gate/mul",
         ("bwd", "s6", "s0/gate")),
        ("jit(_step)/jvp(ff.s6.s0)/out_proj/dot_general",
         ("fwd", "s6", "s0/out_proj")),
        # a differential node's parts
        ("jit(_step)/jvp(ff.ring_attention.attn1)/qkv/dot_general",
         ("fwd", "ring_attention", "attn1/qkv")),
        ("jit(_step)/jvp(ff.ring_attention.attn1)/core"
         "/flash_fwd_causal_bshf_window/pallas_call",
         ("fwd", "ring_attention", "attn1/core")),
        ("jit(_step)/transpose(jvp(ff.ring_attention.attn19))/combine/mul",
         ("bwd", "ring_attention", "attn19/combine")),
        ("jit(_step)/transpose(jvp(ff.ring_attention.attn19))/out_proj"
         "/dot_general", ("bwd", "ring_attention", "attn19/out_proj")),
        # a plain windowed node (PR 60) keeps the names a plain node had: its
        # banded kernels, the rotary and the repeat lie under the node's scope
        # and no part's (the folded form opens no `core`)
        ("jit(_step)/jvp(ff.ring_attention.attn0)/flash_fwd_causal_bshf_window"
         "/pallas_call", ("fwd", "ring_attention", "attn0")),
        ("jit(_step)/transpose(jvp(ff.ring_attention.attn2))"
         "/flash_bwd_causal_bshf_window/pallas_call",
         ("bwd", "ring_attention", "attn2")),
        ("jit(_step)/transpose(jvp(ff.ring_attention.attn3))/flash_delta_bshf"
         "/pallas_call", ("bwd", "ring_attention", "attn3")),
        ("jit(_step)/jvp(ff.ring_attention.attn3)/cos",
         ("fwd", "ring_attention", "attn3")),
        # a looped model's layer names its pass, `<layer>#<pass>` (PR 62); a
        # `recompute` group's second forward is backward time; the sum of a
        # shared weight's gradients is JAX's own `add_any`, under the scope
        # of the reader whose backward reaches it
        ("jit(_step)/jvp(ff.ring_attention.attn3#2)/flash_fwd_causal_bshf"
         "/pallas_call", ("fwd", "ring_attention", "attn3#2")),
        ("jit(_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation"
         "/ff.dense.ffn0_w2#1/dot_general", ("bwd", "dense", "ffn0_w2#1")),
        ("jit(_step)/transpose(jvp(ff.label_loss.exit#4))/mul",
         ("bwd", "label_loss", "exit#4")),
        ("jit(_step)/jvp(ff.mean_loss.entropy)/reduce_sum",
         ("fwd", "mean_loss", "entropy")),
        ("jit(_step)/transpose(jvp(ff.dense.ffn0_w2#1))/add_any",
         ("bwd", "dense", "ffn0_w2#1")),
        # a scope that only begins like a part, and a part of another kind
        ("jit(_step)/jvp(ff.experts.moe1)/shared_expert/mul",
         ("fwd", "experts", "moe1")),
        ("jit(_step)/jvp(ff.experts.moe1)/scan/mul", ("fwd", "experts", "moe1")),
        ("params['n3']", ("unattributed", "", "")),
        ("jit(_step)/jvp(diff.dense.x)/mul", ("unattributed", "", "")),
        ("", ("unattributed", "", "")),
    ],
)
def test_parse_scope_phases(op_name, want):
    assert trace.parse_scope(op_name) == want
    assert want[0] in trace.PHASES


# -- the step programs ---------------------------------------------------------


def toy_transformer(batch=4, seq=16, hidden=32, heads=2, vocab=64, blocks=2):
    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    h = b.embedding(ids, vocab, hidden, name="tok")
    for i in range(blocks):
        attn = b.multihead_attention(
            h, h, h, hidden, heads, kdim=hidden // heads,
            vdim=hidden // heads, bias=True, name=f"attn/{i} (self)",
        )
        h = b.layer_norm(b.add(h, attn), axes=[-1], name=f"ln1_{i}")
        ff = b.dense(b.gelu(b.dense(h, 2 * hidden, name=f"ff1_{i}")), hidden,
                     name=f"ff2_{i}")
        h = b.layer_norm(b.add(h, ff), axes=[-1], name=f"ln2_{i}")
    return b.graph, b.dense(h, vocab, name="head")


def single_instance():
    graph, logits = toy_transformer()
    return ModelTrainingInstance(
        graph, logits, SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-3, weight_decay=0.01),
        metrics=frozenset({"accuracy"}), compute_dtype=jnp.bfloat16,
    )


def searched_instance():
    """Megatron MLP over dp 2 x tp 2: a Replicate, a row-parallel dense whose
    partial sums meet in the pinned reduction's psum, and a Reduction."""
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([8, 32], [2, 1]), name="x")
    h = b.relu(b.dense(b.parallel_replicate(x, 2), 128, name="fc1"))
    logits = b.parallel_reduce(b.dense(h, 10, use_bias=False, name="fc2"), 2)
    return DistributedTrainingInstance(
        b.graph, logits, SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-3), MachineMesh.for_devices(4),
        compute_dtype=jnp.bfloat16,
    )


def compiled_step_text(instance):
    return lower_step_trace(instance, instance.loss_attrs).compile().as_text()


def lowered_op_names(text):
    """(instruction line, op_name) of everything JAX lowered: XLA's own
    instructions carry an argument's or an instruction's name there, and no
    name stack."""
    out = []
    for line in text.split("\n"):
        m = OP_NAME.search(line)
        if m and "/" in m.group(1) and " parameter(" not in line:
            out.append((line, m.group(1)))
    return out


@pytest.fixture(scope="module")
def single_text():
    return compiled_step_text(single_instance())


@pytest.fixture(scope="module")
def searched_text():
    return compiled_step_text(searched_instance())


def test_single_step_every_lowered_op_is_scoped(single_text):
    names = lowered_op_names(single_text)
    assert len(names) > 100
    bare = [n for _, n in names if trace.parse_scope(n)[0] == "unattributed"]
    assert bare == []


def test_single_step_has_every_phase(single_text):
    phases = {trace.parse_scope(n)[0] for _, n in lowered_op_names(single_text)}
    assert {"fwd", "bwd", "opt", "other"} <= phases
    kinds = {trace.parse_scope(n)[1:] for _, n in lowered_op_names(single_text)}
    assert {("optimizer", ""), ("loss", ""), ("cast", ""), ("metrics", "")} <= kinds


def test_single_step_every_layer_in_forward_and_backward(single_text):
    graph, _ = toy_transformer()
    seen = {trace.parse_scope(n) for _, n in lowered_op_names(single_text)}
    layers = 0
    for n in graph.topological_ordering():
        la = graph.layer_attrs(n)
        if isinstance(la.attrs, (InputAttrs, WeightAttrs)):
            continue  # nothing is lowered for them on one device
        _, kind, name = trace.parse_scope(trace.scope_name(graph, n))
        assert ("fwd", kind, name) in seen, (kind, name)
        if la.name is None:
            continue  # a residual add's backward is the identity
        assert ("bwd", kind, name) in seen, (kind, name)
        layers += 1
    assert layers == 2 * 5 + 2
    assert ("fwd", "mha", "attn_0__self_") in seen


def test_searched_step_scopes_parallel_ops_and_collectives(searched_text):
    names = lowered_op_names(searched_text)
    parsed = {trace.parse_scope(n) for _, n in names}
    assert any(kind.startswith("parallel_") for _, kind, _ in parsed), parsed
    # a parameter's reshard is booked to the parameter or, where the step
    # computes in another dtype, to the cast whose result is resharded:
    # since PR 30 that is the all-gather of the weight's compute copy
    assert any(kind in ("weight", "cast") for _, kind, _ in parsed), parsed
    assert [n for _, n in names if trace.parse_scope(n)[0] == "unattributed"] == []
    collectives = [
        line for line in searched_text.split("\n") if COLLECTIVE.search(line)
    ]
    assert collectives
    for line in collectives:
        m = OP_NAME.search(line)
        assert m and trace.parse_scope(m.group(1))[0] != "unattributed", line
    gathers = [line for line in collectives if " all-gather(" in line]
    assert gathers and all(
        trace.parse_scope(OP_NAME.search(line).group(1))[1]
        in ("weight", "cast")
        for line in gathers
    )
    # the pinned reduction's psum is the row-parallel dense's, both ways
    owners = {
        trace.parse_scope(OP_NAME.search(line).group(1))
        for line in collectives if "/psum" in line
    }
    assert owners == {("fwd", "dense", "fc2"), ("bwd", "dense", "fc2")}


def strip_metadata(text):
    """HLO text without what names where an instruction came from: each
    instruction's `metadata={...}`, the module's source-location tables and
    the spelling of instruction names."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(
        r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n(.+\n)*", "",
        text, flags=re.M,
    )
    # an instruction's own name is cut from its name stack
    # (`%jvp_jit_take_along_axis__.14`): number them by first appearance, so
    # that the dataflow is compared and the spelling is not
    order = {}
    return re.sub(
        r"%[\w.\-]+",
        lambda m: f"%v{order.setdefault(m.group(0), len(order))}", text,
    )


@pytest.fixture
def no_compile_cache():
    """jax's persistent compile cache leaves metadata out of its key: with
    it on (any `FFModel` built earlier in the process turns it on) a program
    that differs from a cached one only in its scopes loads the other's
    executable, names and all."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("make", [single_instance, searched_instance])
def test_scopes_change_nothing_but_metadata(
    make, monkeypatch, request, no_compile_cache
):
    scoped = request.getfixturevalue(
        "single_text" if make is single_instance else "searched_text"
    )
    null = lambda *a, **k: contextlib.nullcontext()
    monkeypatch.setattr(trace, "node_scope", null)
    monkeypatch.setattr(trace, "step_scope", null)
    bare = compiled_step_text(make())
    assert not any("ff." in n for _, n in lowered_op_names(bare))
    assert strip_metadata(bare) == strip_metadata(scoped)


# -- (v) kernel names ----------------------------------------------------------


def pallas_eqns(jaxpr):
    """Every pallas_call equation of a jaxpr, those of nested jaxprs
    included, in order."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from pallas_eqns(inner)


def pallas_names(jaxpr):
    """`name` of every pallas_call in a jaxpr, with the tail of the name
    stack it was bound under."""
    return [
        (eqn.params["name"], str(eqn.source_info.name_stack))
        for eqn in pallas_eqns(jaxpr)
    ]


def _rows(s, d=64):
    return (jax.ShapeDtypeStruct((1, 4, s, d), jnp.float32),) * 3


def _bshf(s, h, d):
    return (jax.ShapeDtypeStruct((2, s, h * d), jnp.float32),) * 3


def _sum(fn):
    return lambda *args: jnp.sum(fn(*args))


def _ring(q, k, v):
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("sp",))
    spec = jax.sharding.PartitionSpec(None, None, "sp", None)
    body = lambda qb, kb, vb: ring_flash.ring_flash_attention_block(
        qb, kb, vb, ("sp",), 2, True, interpret=True
    )
    return shard_map_compat(
        body, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec
    )(q, k, v)


KERNEL_CASES = {
    "rows_two_pass": (
        lambda q, k, v: fa.flash_attention(
            q, k, v, block_q=128, block_k=128, interpret=True),
        _rows(256), {},
        {"flash_fwd_rows_folded", "flash_bwd_dq_rows", "flash_bwd_dkv_rows"},
    ),
    "rows_one_row_per_program": (
        lambda q, k, v: fa.flash_attention(
            q, k, v, block_q=128, block_k=128, interpret=True),
        _rows(256), {"FLEXFLOW_TPU_FLASH_BATCH_BLOCK": "1"},
        {"flash_fwd_rows", "flash_bwd_dq_rows", "flash_bwd_dkv_rows"},
    ),
    "rows_fused_backward": (
        lambda q, k, v: fa.flash_attention(q, k, v, interpret=True),
        _rows(128), {},
        {"flash_fwd_rows_folded", "flash_delta_rows", "flash_bwd_fused_rows"},
    ),
    "head_pair": (
        lambda q, k, v: fa.flash_attention_bshf(q, k, v, 2, interpret=True),
        _bshf(128, 2, 64), {},
        {"flash_fwd_pair", "flash_bwd_fused_pair"},
    ),
    "head_pair_fused_qkv": (
        lambda qkv: fa.flash_attention_bshf_qkv(qkv, 2, interpret=True),
        (jax.ShapeDtypeStruct((2, 128, 3 * 128), jnp.float32),), {},
        {"flash_fwd_pair_qkv", "flash_bwd_fused_pair_qkv"},
    ),
    "bshf_fused_backward": (
        lambda q, k, v: fa.flash_attention_bshf(q, k, v, 2, interpret=True),
        _bshf(128, 2, 128), {},
        {"flash_fwd_bshf", "flash_delta_bshf", "flash_bwd_fused_bshf"},
    ),
    "bshf_one_pass_backward": (
        lambda q, k, v: fa.flash_attention_bshf(
            q, k, v, 2, block_q=128, block_k=128, interpret=True),
        _bshf(256, 2, 128), {},
        {"flash_fwd_bshf", "flash_delta_bshf", "flash_bwd_onepass_bshf"},
    ),
    # non-causal with more than two q blocks: the constant-memory pair
    "bshf_two_pass_backward": (
        lambda q, k, v: fa.flash_attention_bshf(
            q, k, v, 2, block_q=128, block_k=128, interpret=True),
        _bshf(512, 2, 128), {},
        {"flash_fwd_bshf", "flash_delta_bshf", "flash_bwd_dq_bshf",
         "flash_bwd_dkv_bshf"},
    ),
    # causal: the tile schedule's one-visit backward
    "bshf_causal_backward": (
        lambda q, k, v: fa.flash_attention_bshf(
            q, k, v, 2, causal=True, block_q=128, block_k=128,
            interpret=True),
        _bshf(256, 2, 128), {},
        {"flash_fwd_causal_bshf", "flash_delta_bshf", "flash_bwd_causal_bshf"},
    ),
    "ring": (
        _ring, _rows(256, 16), {},
        {"ring_flash_fwd_step", "ring_flash_bwd_dq_step",
         "ring_flash_bwd_dkv_step"},
    ),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_flash_kernels_lower_under_their_names(case, monkeypatch):
    fn, args, env, want = KERNEL_CASES[case]
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    grad = jax.grad(_sum(fn), argnums=tuple(range(len(args))))
    found = pallas_names(jax.make_jaxpr(grad)(*args).jaxpr)
    assert {name for name, _ in found} == want
    # `name=` is also the innermost scope the call is bound under, which is
    # where the HLO instruction (the trace's `pallas/<name>`) gets its name
    for name, stack in found:
        assert name in stack, (name, stack)


def test_every_pallas_call_is_named():
    import inspect

    # a literal, a wrapper's `name` argument, or one of `CausalPlan`'s three
    for module, sites in ((fa, 17), (ring_flash, 3)):
        source = inspect.getsource(module)
        calls = source.count("pl.pallas_call(")
        assert calls == sites
        named = r"\bname=(\"[a-z_]+\"|name|plan\.[a-z]+_name),"
        assert len(re.findall(named, source)) == calls
