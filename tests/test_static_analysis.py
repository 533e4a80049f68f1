"""Static verification layer tests (flexflow_tpu/analysis, ISSUE 4).

Covers: negative-path PCGs pinning each verifier rule id, the rule-audit
regression (an interface-breaking substitution that
is_valid_match_for_substitution accepts must be rejected), clean lints over
the package, the tier-1 gate (ffcheck --all-templates / --audit-rules /
--lint in-process), and the ffcheck CLI exit-code contract over >= 8
distinct seeded violations.
"""

import json
import os
import subprocess
import sys

import pytest

from flexflow_tpu.analysis import (
    PCG_RULE_CATALOG,
    LINT_CATALOG,
    assert_verifier_clean,
    audit_substitution,
    errors_of,
    lint_package,
    lint_source,
    verify_pcg,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import (
    CombineAttrs,
    ElementUnaryAttrs,
    ElementUnaryOpType,
    InputAttrs,
    LinearAttrs,
    RepartitionAttrs,
    ReplicateAttrs,
    WeightAttrs,
)
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorDims,
    ParallelTensorShape,
    ShardParallelDim,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.machine_view import (
    MachineSpaceCoordinate,
    MachineSpecification,
    MachineView,
    MachineViewDimension,
    ProjectionType,
)
from flexflow_tpu.pcg.parallel_computation_graph import (
    ParallelComputationGraph,
    ParallelLayerAttrs,
    ParallelTensorAttrs,
    pcg_from_computation_graph,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FFCHECK = os.path.join(REPO, "tools", "ffcheck.py")

SPEC4 = MachineSpecification(1, 1, 4, 25.0, 400.0)


def pts(dims, degrees=None, sum_degree=1, dtype=DataType.FLOAT):
    degrees = degrees or [1] * len(dims)
    return ParallelTensorShape(
        ParallelTensorDims(
            tuple(ShardParallelDim(s, d) for s, d in zip(dims, degrees)),
            sum_degree,
            1,
        ),
        dtype,
    )


def add(pcg, attrs, ins, shapes, name=None):
    _, outs = pcg.add_node(
        ParallelLayerAttrs(attrs, name),
        ins,
        [ParallelTensorAttrs(s) for s in shapes],
    )
    return outs[0] if len(outs) == 1 else outs


def rule_ids(diags):
    return {d.rule_id for d in errors_of(diags)}


# ---------------------------------------------------------------------------
# violating PCG builders (shared by the in-process negative tests and the
# ffcheck CLI exit-code tests)
# ---------------------------------------------------------------------------


def bad_pcg002_indivisible_repartition():
    """Repartition(0, 3) over a size-16 dim: inference rejects the op. The
    relu consumes the repartition so this document carries EXACTLY one
    violation."""
    g = ParallelComputationGraph()
    x = add(g, InputAttrs(TensorShape((16, 16))), [], [pts([16, 16])], "x")
    r = add(g, RepartitionAttrs(0, 3), [x], [pts([16, 16])])
    add(g, ElementUnaryAttrs(ElementUnaryOpType.RELU), [r], [pts([16, 16])])
    return g


def bad_pcg003_unconserved_combine():
    """Combine(0, 2) whose recorded output keeps the sharded shape."""
    g = ParallelComputationGraph()
    x = add(g, InputAttrs(TensorShape((16, 16))), [], [pts([16, 16])], "x")
    r = add(g, RepartitionAttrs(0, 2), [x], [pts([16, 16], [2, 1])])
    add(g, CombineAttrs(0, 2), [r], [pts([16, 16], [2, 1])])  # wrong label
    return g


def bad_pcg004_dtype_drift():
    """Relu recorded as bfloat16 on a float32 input."""
    g = ParallelComputationGraph()
    x = add(g, InputAttrs(TensorShape((8, 8))), [], [pts([8, 8])], "x")
    add(
        g,
        ElementUnaryAttrs(ElementUnaryOpType.RELU),
        [x],
        [pts([8, 8], dtype=DataType.BFLOAT16)],
    )
    return g


def bad_pcg005_escaped_sum():
    """Reduction-parallel Linear with the Reduction missing: partial sums
    reach the sink."""
    g = ParallelComputationGraph()
    x = add(g, InputAttrs(TensorShape((16, 16))), [], [pts([16, 16])], "x")
    w = add(g, WeightAttrs(TensorShape((16, 8))), [], [pts([16, 8])], "w")
    rx = add(g, RepartitionAttrs(-1, 2), [x], [pts([16, 16], [1, 2])])
    rw = add(g, RepartitionAttrs(0, 2), [w], [pts([16, 8], [2, 1])])
    add(
        g,
        LinearAttrs(out_channels=8, use_bias=False),
        [rx, rw],
        [pts([16, 8], sum_degree=2)],
    )
    return g


def bad_pcg006_dangling_repartition():
    g = ParallelComputationGraph()
    x = add(g, InputAttrs(TensorShape((16, 16))), [], [pts([16, 16])], "x")
    add(g, ElementUnaryAttrs(ElementUnaryOpType.RELU), [x], [pts([16, 16])])
    add(g, RepartitionAttrs(0, 2), [x], [pts([16, 16], [2, 1])])  # unused
    return g


def bad_pcg007_non_sp():
    """Interior N-shape: a feeds {c, d}, b feeds only d."""
    b = ComputationGraphBuilder()
    x = b.create_input([8, 8], name="x")
    a = b.relu(x, name="a")
    bb = b.gelu(x, name="b")
    c = b.relu(a, name="c")
    d = b.add(a, bb, name="d")
    b.add(c, d, name="e")
    return pcg_from_computation_graph(b.graph)


def _branch_pcg():
    """x -> two degree-2 branches -> add (a clean parallel split)."""
    g = ParallelComputationGraph()
    x = add(g, InputAttrs(TensorShape((16, 16))), [], [pts([16, 16])], "x")
    vals = {}
    for tag, op in (("a", ElementUnaryOpType.RELU), ("b", ElementUnaryOpType.GELU)):
        r = add(g, RepartitionAttrs(0, 2), [x], [pts([16, 16], [2, 1])], f"r{tag}")
        u = add(g, ElementUnaryAttrs(op), [r], [pts([16, 16], [2, 1])], tag)
        c = add(g, CombineAttrs(0, 2), [u], [pts([16, 16])], f"c{tag}")
        vals[tag] = c
    from flexflow_tpu.op_attrs.ops import ElementBinaryAttrs, ElementBinaryOpType

    add(
        g,
        ElementBinaryAttrs(ElementBinaryOpType.ADD),
        [vals["a"], vals["b"]],
        [pts([16, 16])],
        "add",
    )
    return g


def _view(start_dev, *dims):
    return MachineView(
        MachineSpaceCoordinate(0, start_dev),
        tuple(MachineViewDimension(s, ProjectionType.INTRA_NODE) for s in dims),
    )


def _branch_mapping(g, a_start=0, b_start=2, a_stride=1):
    """Full mapping for _branch_pcg: each branch (repartition, unary,
    combine) on its own device block, the shared input/add on device 0."""
    mapping = {}
    for n in g.nodes:
        name = g.layer_attrs(n).name or ""
        shape = g.tensor_shape(g.outputs_of(n)[0])
        degree2 = any(d.degree == 2 for d in shape.dims.shard_dims)
        start = {"a": a_start, "b": b_start}.get(name[-1:], 0)
        stride = a_stride if name.endswith("a") else 1
        mapping[n] = _view(start, stride) if degree2 else _view(start, 1)
    return mapping


# ---------------------------------------------------------------------------
# negative-path verifier tests: one pinned rule id each
# ---------------------------------------------------------------------------


class TestVerifierNegativePaths:
    def test_pcg001_shard_divisibility(self):
        # the dataclass asserts forbid direct construction; a deserialized
        # or hand-mutated graph can still carry a bad dim
        bad_dim = ShardParallelDim.__new__(ShardParallelDim)
        object.__setattr__(bad_dim, "size", 7)
        object.__setattr__(bad_dim, "degree", 2)
        shape = ParallelTensorShape(
            ParallelTensorDims((bad_dim,), 1, 1), DataType.FLOAT
        )
        g = ParallelComputationGraph()
        add(g, InputAttrs(TensorShape((14,))), [], [shape], "x")
        assert "PCG001" in rule_ids(verify_pcg(g, check_sp=False))

    def test_pcg002_inference_failed(self):
        ids = rule_ids(verify_pcg(bad_pcg002_indivisible_repartition()))
        assert ids == {"PCG002"}, ids

    def test_pcg003_degree_conservation(self):
        assert "PCG003" in rule_ids(verify_pcg(bad_pcg003_unconserved_combine()))

    def test_pcg004_dtype_mismatch(self):
        ids = rule_ids(verify_pcg(bad_pcg004_dtype_drift()))
        assert "PCG004" in ids
        assert "PCG003" not in ids  # dims match; only the dtype drifted

    def test_pcg005_escaped_sum_degree(self):
        ids = rule_ids(verify_pcg(bad_pcg005_escaped_sum()))
        assert ids == {"PCG005"}, ids  # the graph is otherwise consistent

    def test_pcg006_dead_output(self):
        assert "PCG006" in rule_ids(verify_pcg(bad_pcg006_dangling_repartition()))

    def test_pcg007_not_series_parallel(self):
        """A warning since PR 62: such a graph is priced on a levelled tree
        (`problem_tree._levelled_decomposition`) and no longer refused."""
        diags = verify_pcg(bad_pcg007_non_sp())
        assert "PCG007" in {d.rule_id for d in diags}
        assert not errors_of(diags)

    def test_mv001_view_arity(self):
        g = _branch_pcg()
        mapping = _branch_mapping(g)
        # give the 1-task add node a 2-dim view
        (bad,) = [n for n in g.nodes if g.layer_attrs(n).name == "add"]
        mapping[bad] = _view(0, 1, 1)
        assert "MV001" in rule_ids(verify_pcg(g, SPEC4, mapping))

    def test_mv002_view_out_of_grid(self):
        g = _branch_pcg()
        # stride 4 puts task 1 at device 4 on a 4-device machine
        mapping = _branch_mapping(g, a_stride=4)
        assert "MV002" in rule_ids(verify_pcg(g, SPEC4, mapping))

    def test_mv003_oversubscription(self):
        g = _branch_pcg()
        # branch a on {0,1}, branch b on {1,2}: partial overlap
        mapping = _branch_mapping(g, a_start=0, b_start=1)
        assert "MV003" in rule_ids(verify_pcg(g, SPEC4, mapping))

    def test_mv004_slice_straddle(self):
        """ISSUE 17: on a multi-slice machine a view projecting a
        TENSOR-sharded task axis INTER (across the DCN boundary) is an
        error pinned to MV004; the same plan kept INTRA is clean."""
        g = ParallelComputationGraph()
        x = add(g, InputAttrs(TensorShape((16, 16))), [], [pts([16, 16])], "x")
        r = add(g, RepartitionAttrs(1, 2), [x], [pts([16, 16], [1, 2])], "r")
        u = add(
            g,
            ElementUnaryAttrs(ElementUnaryOpType.RELU),
            [r],
            [pts([16, 16], [1, 2])],
            "u",
        )
        add(g, CombineAttrs(1, 2), [u], [pts([16, 16])], "c")
        spec = MachineSpecification(2, 1, 2, 2.0, 25.0)  # 2 slices x 2 devs
        inter = MachineView(
            MachineSpaceCoordinate(0, 0),
            (MachineViewDimension(1, ProjectionType.INTER_NODE),),
        )
        mapping = {}
        for n in g.nodes:
            shape = g.tensor_shape(g.outputs_of(n)[0])
            sharded = any(d.degree == 2 for d in shape.dims.shard_dims)
            mapping[n] = inter if sharded else _view(0, 1)
        ids = rule_ids(verify_pcg(g, spec, mapping))
        assert "MV004" in ids, ids
        intra = {
            n: _view(0, 1) if v is inter else v for n, v in mapping.items()
        }
        assert_verifier_clean(g, spec, intra)

    def test_disjoint_and_colocated_branches_clean(self):
        g = _branch_pcg()
        assert_verifier_clean(g, SPEC4, _branch_mapping(g))  # disjoint
        mapping = _branch_mapping(g, a_start=0, b_start=0)  # identical
        assert_verifier_clean(g, SPEC4, mapping)

    def test_catalog_covers_every_emitted_rule(self):
        for g in (
            bad_pcg002_indivisible_repartition(),
            bad_pcg003_unconserved_combine(),
            bad_pcg004_dtype_drift(),
            bad_pcg005_escaped_sum(),
            bad_pcg006_dangling_repartition(),
            bad_pcg007_non_sp(),
        ):
            for d in verify_pcg(g):
                assert d.rule_id in PCG_RULE_CATALOG, d


# ---------------------------------------------------------------------------
# rule-audit regression: unsound rule accepted by is_valid, rejected here
# ---------------------------------------------------------------------------


def _interface_breaking_rule():
    """Linear -> Repartition(Linear(Repartition(a), Replicate(w))) with NO
    closing Combine: the output stays sharded."""
    from flexflow_tpu.op_attrs.core import OperatorType
    from flexflow_tpu.substitutions.operator_pattern import (
        OperatorAttributePattern,
    )
    from flexflow_tpu.substitutions.output_graph import (
        AttrConstant,
        CopyAttrsFromMatched,
        OutputGraphExpr,
    )
    from flexflow_tpu.substitutions.pcg_pattern import PCGPattern
    from flexflow_tpu.substitutions.substitution import Substitution
    from flexflow_tpu.substitutions.tensor_pattern import TensorAttributePattern

    p = PCGPattern()
    a = p.add_input(TensorAttributePattern.dim_divisible_by(0, 2))
    w = p.add_input()
    node, (y,) = p.add_operator(
        OperatorAttributePattern.for_op_type(
            OperatorType.LINEAR, use_bias=False
        ),
        [a, w],
    )
    og = OutputGraphExpr()
    oa, ow = og.add_input(), og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(0, 2)), [oa])
    _, (wr,) = og.add_operator(AttrConstant(ReplicateAttrs(2)), [ow])
    _, (oy,) = og.add_operator(CopyAttrsFromMatched(node), [ap, wr])
    return Substitution(
        "broken_no_combine", p, og, ((a, oa), (w, ow)), ((y, oy),)
    )


class TestRuleAudit:
    def test_interface_breaking_rule_rejected(self):
        from flexflow_tpu.substitutions.pcg_pattern import find_pattern_matches
        from flexflow_tpu.substitutions.substitution import (
            is_valid_match_for_substitution,
        )

        bad = _interface_breaking_rule()
        # validity alone ACCEPTS it (shape inference succeeds on the RHS)
        b = ComputationGraphBuilder()
        x = b.create_input([8, 16], name="x")
        b.dense(x, 16, use_bias=False, name="fc")
        host = pcg_from_computation_graph(b.graph)
        matches = find_pattern_matches(bad.pattern, host)
        assert matches and all(
            is_valid_match_for_substitution(host, bad, m) for m in matches
        )
        # the auditor rejects it with the interface-equivalence rule
        res = audit_substitution(bad)
        assert res.status == "unsound"
        assert {d.rule_id for d in res.diagnostics} == {"RULE002"}

    def test_all_registered_rules_sound(self):
        from flexflow_tpu.analysis import audit_rules, registered_rules_for_grid

        rules = registered_rules_for_grid(8)
        results, diags = audit_rules(rules)
        assert not errors_of(diags), [d.message for d in errors_of(diags)]
        # every rule in the live vocabulary is actually exercised, not
        # silently skipped
        assert all(r.status == "ok" for r in results), [
            (r.name, r.status) for r in results if r.status != "ok"
        ]

    def test_sound_rule_passes(self):
        from flexflow_tpu.substitutions.rules import data_parallel_linear_rule

        res = audit_substitution(data_parallel_linear_rule(4))
        assert res.status == "ok" and not res.diagnostics

    def test_legacy_converted_rule_audits_ok(self):
        """The TASO-format loader's converted substitutions (parallel-op
        dst vocabulary) are inside the auditor's vocabulary too."""
        import test_legacy_rules as tlr
        from flexflow_tpu.substitutions.legacy_rules import (
            load_rule_collection,
            to_substitution,
        )

        sub = to_substitution(load_rule_collection(tlr.EXAMPLE).rules[0])
        res = audit_substitution(sub)
        assert res.status == "ok", res.diagnostics

    def test_reference_corpus_audits_without_unsoundness(self):
        """Every convertible rule of the reference's legacy corpus passes
        the soundness audit (skipped when the corpus isn't mounted)."""
        from flexflow_tpu.analysis import audit_rules
        from flexflow_tpu.substitutions.legacy_rules import (
            load_legacy_substitutions,
        )

        path = "/root/reference/substitutions/graph_subst_3_v2.json"
        if not os.path.exists(path):
            pytest.skip("reference legacy corpus not mounted")
        subs, _ = load_legacy_substitutions(path)
        _, diags = audit_rules(subs)
        assert not errors_of(diags), [d.message for d in errors_of(diags)]


# ---------------------------------------------------------------------------
# source lints
# ---------------------------------------------------------------------------


class TestSourceLints:
    def test_lint001_host_sync_in_step(self):
        src = (
            "import numpy as np\n"
            "def _step(params, batch):\n"
            "    loss = params['w'] @ batch\n"
            "    return np.asarray(loss)\n"
        )
        diags = lint_source(src)
        assert {d.rule_id for d in diags} == {"LINT001"}

    def test_lint001_item_in_jitted_fn(self):
        src = (
            "import jax\n"
            "def fwd(x):\n"
            "    return x.item()\n"
            "f = jax.jit(fwd)\n"
        )
        assert {d.rule_id for d in lint_source(src)} == {"LINT001"}

    def test_lint001_device_get_in_kernel(self):
        src = (
            "import jax\n"
            "def attention_kernel(q_ref, o_ref):\n"
            "    o_ref[...] = jax.device_get(q_ref)\n"
        )
        assert {d.rule_id for d in lint_source(src)} == {"LINT001"}

    def test_lint001_host_sync_outside_jit_allowed(self):
        src = (
            "import numpy as np\n"
            "def read_back(x):\n"
            "    return np.asarray(x)\n"
        )
        assert lint_source(src) == []

    def test_lint002_persistent_id_cache(self):
        src = (
            "class C:\n"
            "    def put(self, x):\n"
            "        self._cache[id(x)] = 1\n"
        )
        assert {d.rule_id for d in lint_source(src)} == {"LINT002"}

    def test_lint002_module_level_id_cache(self):
        src = "CACHE = {}\ndef f(x):\n    return CACHE.get(id(x))\n"
        assert {d.rule_id for d in lint_source(src)} == {"LINT002"}

    def test_lint002_local_id_dict_allowed(self):
        src = (
            "def f(xs):\n"
            "    seen = {}\n"
            "    for x in xs:\n"
            "        seen[id(x)] = x\n"
            "    return seen\n"
        )
        assert lint_source(src) == []

    def test_lint003_set_iteration(self):
        src = (
            "def f(xs):\n"
            "    out = []\n"
            "    for x in set(xs):\n"
            "        out.append(x)\n"
            "    return out + [y for y in {1, 2}]\n"
        )
        diags = lint_source(src)
        assert [d.rule_id for d in diags] == ["LINT003", "LINT003"]

    def test_lint003_sorted_set_allowed(self):
        src = (
            "def f(xs):\n"
            "    return [x for x in sorted(set(xs))]\n"
        )
        assert lint_source(src) == []

    def test_lint005_host_transfer_in_fit_loop_driver(self):
        """np.asarray / jax.device_get lexically inside a `_fit_*` driver:
        a blocking host transfer on the step-dispatch critical path."""
        src = (
            "import numpy as np\n"
            "def _fit_epochs(self, it):\n"
            "    for batch in it:\n"
            "        loss = self.step(batch)\n"
            "        last = np.asarray(loss)\n"
        )
        assert {d.rule_id for d in lint_source(src)} == {"LINT005"}

    def test_lint005_device_get_in_fit_loop_driver(self):
        src = (
            "import jax\n"
            "def _fit_epochs(self, it):\n"
            "    for batch in it:\n"
            "        loss = jax.device_get(self.step(batch))\n"
        )
        assert {d.rule_id for d in lint_source(src)} == {"LINT005"}

    def test_lint005_has_one_driver_to_judge(self):
        """`FFModel` has one training-loop driver under the `_fit_` prefix
        LINT005 keys on (and `_fit_loop`, its set-up), and its source is
        clean of blocking host transfers."""
        import inspect
        import textwrap

        from flexflow_tpu.core import FFModel

        drivers = sorted(n for n in vars(FFModel) if n.startswith("_fit_"))
        assert drivers == ["_fit_epochs", "_fit_loop"]
        for name in drivers:
            src = textwrap.dedent(inspect.getsource(getattr(FFModel, name)))
            assert [
                d for d in lint_source(src) if d.rule_id == "LINT005"
            ] == [], name

    def test_lint005_nested_background_thread_body_exempt(self):
        """Nested defs (writer thread bodies) are the sanctioned home
        for host transfers — the driver itself stays clean."""
        src = (
            "import numpy as np, jax\n"
            "def _fit_epochs(self, it):\n"
            "    def _writer():\n"
            "        return np.asarray(jax.device_get(it))\n"
            "    for batch in it:\n"
            "        pass\n"
        )
        assert lint_source(src) == []

    def test_lint005_non_driver_functions_exempt(self):
        """Host transfers in named helpers outside the drivers (the
        _read_losses_host pattern) and in thread bodies are allowed."""
        src = (
            "import numpy as np\n"
            "def _read_losses_host(losses):\n"
            "    return np.asarray(losses)\n"
            "def _producer(self):\n"
            "    return np.asarray(self.q.get())\n"
        )
        assert lint_source(src) == []

    def test_lint006_bare_except_in_runtime_module(self):
        """A bare `except:` anywhere under flexflow_tpu/runtime/ is
        flagged — the supervision layer only works if errors reach it."""
        src = (
            "def commit(src, dst):\n"
            "    try:\n"
            "        replace(src, dst)\n"
            "    except:\n"
            "        retry()\n"
        )
        diags = lint_source(src, path="flexflow_tpu/runtime/checkpoint.py")
        assert {d.rule_id for d in diags} == {"LINT006"}

    def test_lint006_pass_only_broad_handler_in_runtime(self):
        src = (
            "def save(tree):\n"
            "    try:\n"
            "        write(tree)\n"
            "    except Exception:\n"
            "        pass\n"
        )
        diags = lint_source(src, path="flexflow_tpu/runtime/supervisor.py")
        assert {d.rule_id for d in diags} == {"LINT006"}

    def test_lint006_swallow_in_fit_driver_any_module(self):
        """The fit-loop drivers are in scope regardless of module path."""
        src = (
            "def _fit_epochs(self, it):\n"
            "    for batch in it:\n"
            "        try:\n"
            "            step(batch)\n"
            "        except BaseException:\n"
            "            continue\n"
        )
        diags = lint_source(src, path="flexflow_tpu/core/ffmodel.py")
        assert {d.rule_id for d in diags} == {"LINT006"}

    def test_lint006_routed_broad_handler_allowed(self):
        """Catching Exception and ROUTING it (channel post, structured
        re-raise, record-and-fall-back) is exactly what the supervision
        layer wants — only the discard is banned."""
        src = (
            "def _run(self):\n"
            "    try:\n"
            "        work()\n"
            "    except BaseException as e:\n"
            "        self.channel.post('writer', e)\n"
            "def load(path):\n"
            "    try:\n"
            "        return read(path)\n"
            "    except Exception as e:\n"
            "        raise CorruptError(str(e))\n"
        )
        assert lint_source(
            src, path="flexflow_tpu/runtime/checkpoint.py"
        ) == []

    def test_lint006_narrow_handler_with_pass_allowed(self):
        """`except queue.Full: pass` is a narrow, intentional drop — only
        the BROAD swallow hides faults."""
        src = (
            "import queue\n"
            "def drain(q):\n"
            "    try:\n"
            "        q.get_nowait()\n"
            "    except queue.Empty:\n"
            "        pass\n"
        )
        assert lint_source(
            src, path="flexflow_tpu/runtime/chaos.py"
        ) == []

    def test_lint006_out_of_scope_modules_exempt(self):
        """The same swallow outside runtime/ and outside a fit driver is
        not LINT006's business (other reviews own it)."""
        src = (
            "def helper():\n"
            "    try:\n"
            "        probe()\n"
            "    except Exception:\n"
            "        pass\n"
        )
        assert lint_source(src, path="flexflow_tpu/compiler/foo.py") == []

    def test_lint007_unlocked_mutation_in_thread_target(self):
        """A runtime/ thread target assigning shared instance state
        outside the class's lock is a cross-thread data race."""
        src = (
            "import threading\n"
            "class Producer:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self.channel = None\n"
            "        self._t = threading.Thread(target=self._pump)\n"
            "    def _pump(self):\n"
            "        self.count = 1\n"
        )
        diags = lint_source(src, path="flexflow_tpu/runtime/pump.py")
        assert {d.rule_id for d in diags} == {"LINT007"}
        assert "self.count" in diags[0].message

    def test_lint007_locked_mutation_allowed(self):
        src = (
            "import threading\n"
            "class Producer:\n"
            "    def __init__(self):\n"
            "        self._cv = threading.Condition()\n"
            "        self.channel = None\n"
            "        self._t = threading.Thread(target=self._pump)\n"
            "    def _pump(self):\n"
            "        with self._cv:\n"
            "            self.count = 1\n"
        )
        assert lint_source(src, path="flexflow_tpu/runtime/pump.py") == []

    def test_lint007_thread_without_fault_route(self):
        """A Thread whose owning class carries no FaultChannel route (no
        *channel* reference, .post call, or supervision primitive): its
        death never reaches the supervision layer (the PR-8 invariant)."""
        src = (
            "import threading\n"
            "class Pump:\n"
            "    def __init__(self):\n"
            "        self._t = threading.Thread(target=self._pump)\n"
            "    def _pump(self):\n"
            "        while True:\n"
            "            work()\n"
        )
        diags = lint_source(src, path="flexflow_tpu/runtime/pump.py")
        assert {d.rule_id for d in diags} == {"LINT007"}
        assert "no fault route" in diags[0].message

    def test_lint007_thread_subclass_run_checked(self):
        src = (
            "import threading\n"
            "class Worker(threading.Thread):\n"
            "    def run(self):\n"
            "        self.done = True\n"
        )
        diags = lint_source(src, path="flexflow_tpu/runtime/w.py")
        ids = [d.rule_id for d in diags]
        assert ids.count("LINT007") == 2  # unlocked mutation AND no route

    def test_lint007_channel_route_satisfies(self):
        src = (
            "import threading\n"
            "class Writer:\n"
            "    def __init__(self, fault_channel):\n"
            "        self.fault_channel = fault_channel\n"
            "        self._t = threading.Thread(target=self._run)\n"
            "    def _run(self):\n"
            "        try:\n"
            "            work()\n"
            "        except BaseException as e:\n"
            "            self.fault_channel.post('writer', e)\n"
        )
        assert lint_source(src, path="flexflow_tpu/runtime/w.py") == []

    def test_lint007_bare_target_not_shadowed_by_class_method(self):
        """A module-level thread target is checked even when a class
        method elsewhere shares its name (and a class's own thread site
        is not re-attributed to the module function)."""
        src = (
            "import threading\n"
            "def pump():\n"
            "    while True:\n"
            "        work()\n"
            "T = threading.Thread(target=pump)\n"
            "class Other:\n"
            "    def pump(self):\n"
            "        return self.channel\n"
        )
        diags = lint_source(src, path="flexflow_tpu/runtime/pump.py")
        assert [d.rule_id for d in diags] == ["LINT007"]
        assert "'pump'" in diags[0].message

    def test_lint007_one_route_finding_per_class(self):
        """The missing route is a class-level defect: one diagnostic,
        however many threads the class starts."""
        src = (
            "import threading\n"
            "class Pump:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Thread(target=self._pump)\n"
            "        self._b = threading.Thread(target=self._drain)\n"
            "    def _pump(self):\n"
            "        work()\n"
            "    def _drain(self):\n"
            "        work()\n"
        )
        diags = lint_source(src, path="flexflow_tpu/runtime/pump.py")
        assert [d.rule_id for d in diags] == ["LINT007"]
        assert "_pump" in diags[0].message and "_drain" in diags[0].message

    def test_lint007_out_of_scope_modules_exempt(self):
        """The dataloader's producer thread (core/) has its own LINT005
        context; LINT007 polices the supervision package only."""
        src = (
            "import threading\n"
            "class Pump:\n"
            "    def __init__(self):\n"
            "        self._t = threading.Thread(target=self._pump)\n"
            "    def _pump(self):\n"
            "        self.count = 1\n"
        )
        assert lint_source(src, path="flexflow_tpu/core/dataloader.py") == []

    def test_lint008_undonated_step_jit(self):
        """A jax.jit of a step callable without donate_argnums doubles
        peak HBM on the training/serving critical path."""
        src = (
            "import jax\n"
            "class Inst:\n"
            "    def compiled_step(self):\n"
            "        self._jit = jax.jit(self._step)\n"
            "        return self._jit\n"
        )
        diags = lint_source(src)
        assert {d.rule_id for d in diags} == {"LINT008"}
        assert "_step" in diags[0].message

    def test_lint008_decode_step_and_wrapper_names(self):
        """The step token matches wrapper names too (the data-parallel
        backend's step_with_mesh_ctx pattern) and serving decode steps."""
        src = (
            "import jax\n"
            "f = jax.jit(decode_step)\n"
            "g = jax.jit(step_with_mesh_ctx)\n"
        )
        assert [d.rule_id for d in lint_source(src)] == [
            "LINT008", "LINT008",
        ]

    def test_lint008_donated_and_readonly_exempt(self):
        """Donating via either kwarg is clean; read-only step-adjacent
        callables (fwd/eval/loss/stats) carry no donation obligation, and
        lambdas have no step identity to judge."""
        src = (
            "import jax\n"
            "a = jax.jit(_step, donate_argnums=(0, 1))\n"
            "b = jax.jit(multi_step, donate_argnames=('params',))\n"
            "c = jax.jit(fwd_step)\n"
            "d = jax.jit(step_statistics)\n"
            "e = jax.jit(lambda x: x)\n"
            "f = jax.jit(forward)\n"
        )
        assert lint_source(src) == []

    def test_lint009_literal_prngkey_in_jitted_step(self):
        src = (
            "import jax\n"
            "def _step(params, opt_state, batch, label, rng):\n"
            "    k = jax.random.PRNGKey(0)\n"
            "    return params\n"
        )
        diags = lint_source(src)
        assert [d.rule_id for d in diags] == ["LINT009"]
        assert diags[0].line == 3
        assert "keystream" in diags[0].message

    def test_lint009_literal_key_in_scan_body(self):
        """A lax.scan body runs inside the step trace even when defined
        at module scope — jax.random.key counts like PRNGKey."""
        src = (
            "import jax\n"
            "from jax import lax\n"
            "def body(carry, x):\n"
            "    k = jax.random.key(7)\n"
            "    return carry, x\n"
            "def outer(xs):\n"
            "    return lax.scan(body, 0, xs)\n"
        )
        diags = lint_source(src)
        assert [d.rule_id for d in diags] == ["LINT009"]

    def test_lint009_shard_map_body_flagged(self):
        """shard_map kernel bodies run inside the step trace — the
        carried-keystream contract applies there too."""
        src = (
            "import jax\n"
            "from flexflow_tpu.utils.shard_map_compat import "
            "shard_map_compat\n"
            "def ring_body(q, k, v):\n"
            "    noise_key = jax.random.PRNGKey(0)\n"
            "    return q\n"
            "def outer(mesh, q, k, v):\n"
            "    return shard_map_compat(ring_body, mesh, None, None)(q, k, v)\n"
        )
        assert [d.rule_id for d in lint_source(src)] == ["LINT009"]

    def test_lint009_keyword_seed_flagged(self):
        src = (
            "import jax\n"
            "def _step(params, opt_state, batch, label, rng):\n"
            "    return jax.random.PRNGKey(seed=0)\n"
        )
        assert [d.rule_id for d in lint_source(src)] == ["LINT009"]

    def test_lint009_nested_scan_body_flagged_once(self):
        src = (
            "import jax\n"
            "from jax import lax\n"
            "def _step(params, opt_state, batch, label, rng):\n"
            "    def body(c, x):\n"
            "        return c, jax.random.PRNGKey(1)\n"
            "    return lax.scan(body, 0, batch)\n"
        )
        assert [d.rule_id for d in lint_source(src)] == ["LINT009"]

    def test_lint009_carried_key_derivation_allowed(self):
        """split/fold_in of the CARRIED key is the sanctioned pattern;
        literal keys outside traced bodies (init, host seeding) and
        non-constant seeds are out of scope."""
        src = (
            "import jax\n"
            "def _step(params, opt_state, batch, label, rng):\n"
            "    a, b = jax.random.split(rng)\n"
            "    c = jax.random.fold_in(rng, 3)\n"
            "    return params\n"
            "def initialize(seed):\n"
            "    return jax.random.PRNGKey(seed)\n"
            "def host_setup():\n"
            "    return jax.random.PRNGKey(0)\n"
        )
        assert lint_source(src) == []

    def test_lint010_committed_reshard_positional(self):
        src = (
            "import jax\n"
            "def restore(value, template):\n"
            "    return jax.device_put(value, template.sharding)\n"
        )
        diags = lint_source(src)
        assert [d.rule_id for d in diags] == ["LINT010"]
        assert diags[0].line == 3
        assert "recompile" in diags[0].message

    def test_lint010_device_kwarg_flagged(self):
        src = (
            "import jax\n"
            "def restore(value, template):\n"
            "    return jax.device_put(value, device=template.sharding)\n"
        )
        assert [d.rule_id for d in lint_source(src)] == ["LINT010"]

    def test_lint010_recompile_home_exempt(self):
        """runtime/recompile.py IS the sanctioned committed-aware
        placement path — the one home the ban carves out."""
        src = (
            "import jax\n"
            "def _place_like(value, template):\n"
            "    return jax.device_put(value, template.sharding)\n"
        )
        assert (
            lint_source(src, "flexflow_tpu/runtime/recompile.py") == []
        )

    def test_lint010_bare_and_explicit_targets_allowed(self):
        """Default placement and explicit device/mesh targets carry no
        template sharding — out of scope."""
        src = (
            "import jax\n"
            "def f(value, dev, sh):\n"
            "    a = jax.device_put(value)\n"
            "    b = jax.device_put(value, dev)\n"
            "    return jax.device_put(value, sh)\n"
        )
        assert lint_source(src) == []

    def test_package_is_lint_clean(self):
        """Satellite: no live violations in flexflow_tpu/ — pins regressions
        (a new host sync in a _step body, a persistent id() cache, a
        blocking transfer in a fit-loop driver, a swallowed exception
        in runtime/, an undonated step jit, or a literal mid-step
        PRNGKey fails tier-1)."""
        diags = lint_package()
        assert diags == [], [
            f"{d.path}:{d.line} {d.rule_id} {d.message}" for d in diags
        ]

    def test_lint_catalog_covers_rules(self):
        for rid in (
            "LINT001", "LINT002", "LINT003", "LINT004", "LINT005",
            "LINT006", "LINT007", "LINT008", "LINT009", "LINT010",
        ):
            assert rid in LINT_CATALOG


# ---------------------------------------------------------------------------
# FF_TPU_VERIFY wiring
# ---------------------------------------------------------------------------


def _escaped_sum_rule():
    """Reduction-parallel Linear WITHOUT the closing Reduction: the rewrite
    re-infers consistently (apply_substitution always does), but the
    rewritten output carries sum_degree=2 into the sink — the PCG005 class
    of unsoundness only a verifier catches."""
    from flexflow_tpu.op_attrs.core import OperatorType
    from flexflow_tpu.substitutions.operator_pattern import (
        OperatorAttributePattern,
    )
    from flexflow_tpu.substitutions.output_graph import (
        AttrConstant,
        CopyAttrsFromMatched,
        OutputGraphExpr,
    )
    from flexflow_tpu.substitutions.pcg_pattern import PCGPattern
    from flexflow_tpu.substitutions.substitution import Substitution
    from flexflow_tpu.substitutions.tensor_pattern import TensorAttributePattern

    p = PCGPattern()
    a = p.add_input(TensorAttributePattern.dim_divisible_by(-1, 2))
    w = p.add_input()
    node, (y,) = p.add_operator(
        OperatorAttributePattern.for_op_type(
            OperatorType.LINEAR, use_bias=False
        ),
        [a, w],
    )
    og = OutputGraphExpr()
    oa, ow = og.add_input(), og.add_input()
    _, (ap,) = og.add_operator(AttrConstant(RepartitionAttrs(-1, 2)), [oa])
    _, (wp,) = og.add_operator(AttrConstant(RepartitionAttrs(0, 2)), [ow])
    _, (oy,) = og.add_operator(CopyAttrsFromMatched(node), [ap, wp])
    return Substitution(
        "broken_no_reduction", p, og, ((a, oa), (w, ow)), ((y, oy),)
    )


class TestVerifyWiring:
    def test_apply_substitution_rejects_under_env(self, monkeypatch):
        """With FF_TPU_VERIFY=1, a substitution whose rewrite lets partial
        sums escape raises instead of returning the bad graph."""
        from flexflow_tpu.substitutions.pcg_pattern import find_pattern_matches
        from flexflow_tpu.substitutions.substitution import apply_substitution

        bad = _escaped_sum_rule()
        b = ComputationGraphBuilder()
        x = b.create_input([8, 16], name="x")
        b.dense(x, 16, use_bias=False, name="fc")  # linear IS the sink
        host = pcg_from_computation_graph(b.graph)
        (match,) = find_pattern_matches(bad.pattern, host)

        monkeypatch.delenv("FF_TPU_VERIFY", raising=False)
        raw = apply_substitution(host, bad, match)  # silently wrong today
        assert "PCG005" in rule_ids(verify_pcg(raw, check_sp=False))

        monkeypatch.setenv("FF_TPU_VERIFY", "1")
        with pytest.raises(ValueError, match="FF_TPU_VERIFY"):
            apply_substitution(host, bad, match)

    def test_sound_substitution_passes_under_env(self, monkeypatch):
        from flexflow_tpu.substitutions.pcg_pattern import find_pattern_matches
        from flexflow_tpu.substitutions.rules import data_parallel_linear_rule
        from flexflow_tpu.substitutions.substitution import apply_substitution

        monkeypatch.setenv("FF_TPU_VERIFY", "1")
        sub = data_parallel_linear_rule(2)
        b = ComputationGraphBuilder()
        x = b.create_input([8, 16], name="x")
        b.dense(x, 16, use_bias=False, name="fc")
        host = pcg_from_computation_graph(b.graph)
        matches = find_pattern_matches(sub.pattern, host)
        assert matches
        new = apply_substitution(host, sub, matches[0])
        assert_verifier_clean(new)

    def test_imported_illformed_strategy_rejected(self, tmp_path):
        """compile() with --import-strategy pointing at an ill-formed plan
        aborts with the verifier's diagnostics instead of crashing inside
        the GSPMD lowering (or silently training a wrong graph)."""
        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
        from flexflow_tpu.runtime.strategy import save_strategy

        path = str(tmp_path / "bad_plan.json")
        save_strategy(path, bad_pcg003_unconserved_combine(), {})
        cfg = FFConfig(batch_size=16, search_budget=2,
                       import_strategy_file=path)
        m = FFModel(cfg)
        x = m.create_tensor([16, 16], name="x")
        m.dense(x, 4, use_bias=False, name="out")
        with pytest.raises(ValueError, match="ill-formed"):
            m.compile(
                SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy"
            )
        verify = (m.search_provenance or {}).get("verify") or {}
        assert verify.get("errors", 0) >= 1

    def test_searched_compile_records_verify_provenance(self, monkeypatch):
        """FF_TPU_VERIFY=1 end-to-end: the winner's verifier summary lands
        in search_provenance['verify'] and is clean."""
        import numpy as np

        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

        monkeypatch.setenv("FF_TPU_VERIFY", "1")
        batch = 16
        cfg = FFConfig(batch_size=batch, epochs=1, seed=0, search_budget=2)
        m = FFModel(cfg)
        x = m.create_tensor([batch, 64], name="x")
        h = m.dense(x, 64, use_bias=False, name="fc1")
        h = m.relu(h)
        m.dense(h, 8, use_bias=False, name="fc2")
        m.compile(
            SGDOptimizer(lr=0.01),
            "sparse_categorical_crossentropy",
            metrics=["accuracy"],
        )
        prov = m.search_provenance or {}
        verify = prov.get("verify")
        assert verify is not None, prov.keys()
        assert verify["clean"] is True
        assert verify["errors"] == 0


# ---------------------------------------------------------------------------
# tier-1 gate: the three ffcheck passes in-process
# ---------------------------------------------------------------------------


class TestFfcheckGate:
    @staticmethod
    def _main(argv):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import ffcheck

            return ffcheck.main(argv)
        finally:
            sys.path.pop(0)

    def test_all_templates_clean(self):
        assert self._main(["--all-templates"]) == 0

    def test_audit_rules_clean(self):
        assert self._main(["--audit-rules", "--devices-per-node", "8"]) == 0

    def test_package_lint_clean(self):
        assert self._main(["--lint"]) == 0


# ---------------------------------------------------------------------------
# ffcheck CLI: structured non-zero exits on >= 8 distinct seeded violations
# ---------------------------------------------------------------------------


def _write_graph(tmp_path, name, pcg):
    from flexflow_tpu.pcg.file_format import pcg_to_json

    p = tmp_path / name
    p.write_text(pcg_to_json(pcg))
    return str(p)


def _write_strategy(tmp_path, name, pcg, mapping):
    from flexflow_tpu.runtime.strategy import strategy_to_doc

    p = tmp_path / name
    p.write_text(json.dumps(strategy_to_doc(pcg, mapping)))
    return str(p)


@pytest.mark.filterwarnings("ignore")
def test_ffcheck_cli_seeded_violations(tmp_path):
    """One subprocess run over nine seeded documents: exit 1 and one
    structured JSON diagnostic per seeded rule id; PCG007 (a graph that is
    no series-parallel one: priced on a levelled tree since PR 62) is
    reported as a warning, and alone does not fail the gate."""
    g = _branch_pcg()
    arity = _branch_mapping(g)
    (addn,) = [n for n in g.nodes if g.layer_attrs(n).name == "add"]
    arity[addn] = _view(0, 1, 1)

    files = {
        "PCG002": _write_graph(
            tmp_path, "pcg002.json", bad_pcg002_indivisible_repartition()
        ),
        "PCG003": _write_graph(
            tmp_path, "pcg003.json", bad_pcg003_unconserved_combine()
        ),
        "PCG004": _write_graph(tmp_path, "pcg004.json", bad_pcg004_dtype_drift()),
        "PCG005": _write_graph(tmp_path, "pcg005.json", bad_pcg005_escaped_sum()),
        "PCG006": _write_graph(
            tmp_path, "pcg006.json", bad_pcg006_dangling_repartition()
        ),
        "MV001": _write_strategy(tmp_path, "mv001.json", g, arity),
        "MV002": _write_strategy(
            tmp_path, "mv002.json", g, _branch_mapping(g, a_stride=4)
        ),
        "MV003": _write_strategy(
            tmp_path, "mv003.json", g, _branch_mapping(g, a_start=0, b_start=1)
        ),
    }
    assert len(files) >= 8
    warned = {
        "PCG007": _write_graph(tmp_path, "pcg007.json", bad_pcg007_non_sp()),
    }
    proc = subprocess.run(
        [
            sys.executable,
            FFCHECK,
            "--json",
            "--nodes", "1",
            "--devices-per-node", "4",
            *files.values(),
            *warned.values(),
        ],
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    diags = [json.loads(line) for line in proc.stdout.splitlines() if line]
    by_path = {}
    for d in diags:
        assert {"rule_id", "severity", "message"} <= set(d)
        if d.get("path"):
            by_path.setdefault(os.path.basename(d["path"]), {})[
                d["rule_id"]
            ] = d["severity"]
    for rule, path in {**files, **warned}.items():
        got = by_path.get(os.path.basename(path), {})
        assert rule in got, f"{rule} missing for {path}: {got}"
    for rule, path in warned.items():
        assert by_path[os.path.basename(path)] == {rule: "warning"}
        assert TestFfcheckGate._main(
            ["--json", "--nodes", "1", "--devices-per-node", "4", path]
        ) == 0
    # and EACH violation alone exits non-zero (in-process for speed; the
    # subprocess above already pinned the real CLI exit code)
    for rule, path in files.items():
        rc = TestFfcheckGate._main(
            ["--json", "--nodes", "1", "--devices-per-node", "4", path]
        )
        assert rc == 1, f"{rule}: ffcheck exited {rc} for {path}"


@pytest.mark.filterwarnings("ignore")
def test_ffcheck_cli_slices_flag(tmp_path):
    """ISSUE 17: `ffcheck --slices N` arms MV004 — a strategy whose
    tensor-sharded axis straddles the slice boundary exits 1 naming
    MV004; the intra placement of the same plan is clean under the same
    flag."""
    g = ParallelComputationGraph()
    x = add(g, InputAttrs(TensorShape((16, 16))), [], [pts([16, 16])], "x")
    r = add(g, RepartitionAttrs(1, 2), [x], [pts([16, 16], [1, 2])], "r")
    u = add(
        g,
        ElementUnaryAttrs(ElementUnaryOpType.RELU),
        [r],
        [pts([16, 16], [1, 2])],
        "u",
    )
    add(g, CombineAttrs(1, 2), [u], [pts([16, 16])], "c")
    inter = MachineView(
        MachineSpaceCoordinate(0, 0),
        (MachineViewDimension(1, ProjectionType.INTER_NODE),),
    )
    straddle, intra = {}, {}
    for n in g.nodes:
        shape = g.tensor_shape(g.outputs_of(n)[0])
        sharded = any(d.degree == 2 for d in shape.dims.shard_dims)
        straddle[n] = inter if sharded else _view(0, 1)
        intra[n] = _view(0, 1)
    bad = _write_strategy(tmp_path, "mv004.json", g, straddle)
    good = _write_strategy(tmp_path, "mv004_intra.json", g, intra)
    proc = subprocess.run(
        [
            sys.executable, FFCHECK, "--json",
            "--slices", "2", "--devices-per-node", "2", bad,
        ],
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rules = {
        json.loads(line)["rule_id"]
        for line in proc.stdout.splitlines() if line
    }
    assert "MV004" in rules, rules
    rc = TestFfcheckGate._main(
        ["--slices", "2", "--devices-per-node", "2", good]
    )
    assert rc == 0


def test_ffcheck_cli_clean_inputs_exit_zero(tmp_path):
    """Seed templates and a searched winner strategy exit 0."""
    from flexflow_tpu.compiler import (
        AnalyticTPUCostEstimator,
        MachineMappingContext,
        OptimizerConfig,
        graph_optimize,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler.unity_algorithm import data_parallel_seed
    from flexflow_tpu.substitutions import generate_parallelization_rules

    b = ComputationGraphBuilder()
    x = b.create_input([16, 64], name="x")
    h = b.dense(x, 64, use_bias=False, name="fc1")
    h = b.relu(h)
    b.dense(h, 64, use_bias=False, name="fc2")
    pcg = pcg_from_computation_graph(b.graph)

    ctx = MachineMappingContext(
        AnalyticTPUCostEstimator(SPEC4), make_default_allowed_machine_views()
    )
    result = graph_optimize(
        pcg,
        ctx,
        SPEC4,
        generate_parallelization_rules([2, 4]),
        OptimizerConfig(alpha=1.3, budget=2),
    )
    clean = [
        _write_graph(tmp_path, "seed.json", data_parallel_seed(pcg, 4)),
        _write_strategy(
            tmp_path, "winner.json", result.pcg, result.machine_mapping
        ),
    ]
    proc = subprocess.run(
        [
            sys.executable,
            FFCHECK,
            "--nodes", "1",
            "--devices-per-node", "4",
            *clean,
        ],
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# shared check-dispatch / summary-emission contract (ISSUE 19 satellite):
# every per-file flag routes through ffcheck's ONE dispatch table and ONE
# summary-emission path, and each summary's field tuple is frozen here so
# the refactor (and any future one) stays behavior-identical
# ---------------------------------------------------------------------------

MEMORY_SUMMARY_FIELDS = (
    "devices",
    "hbm_bytes",
    "memory",
    "optimizer_state_slots",
    "serving",
)

MEMORY_DEVICE_FIELDS = (
    "device",
    "over_capacity",
    "peak_at",
    "peak_breakdown",
    "peak_bytes",
    "resident_bytes",
)

TRANSITION_SUMMARY_FIELDS = (
    "bulk_peak_bytes",
    "carry_remap",
    "contract_new",
    "contract_old",
    "created",
    "dcn_bytes",
    "drifted",
    "exec_verified",
    "hbm_bytes",
    "ici_bytes",
    "leaves",
    "migration_verdict",
    "moved_bytes",
    "moved_leaves",
    "optimizer_state_slots",
    "orphaned",
    "per_leaf",
    "program_changed",
    "rules_tripped",
    "streamed_peak_bytes",
    "transition",
    "verdict",
)

TRANSITION_LEAF_FIELDS = (
    "bytes_global",
    "dst_degrees",
    "dst_piece_bytes",
    "est_ms",
    "link_class",
    "moved",
    "moved_bytes",
    "movement_key",
    "path",
    "src_degrees",
    "src_piece_bytes",
)


class TestSharedSummaryContract:
    @staticmethod
    def _ffcheck():
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import ffcheck

            return ffcheck
        finally:
            sys.path.pop(0)

    def test_dispatch_table_and_renderer_keys(self):
        """The per-file flags run from ONE table; the summary emitters are
        keyed by the same schema names in the same order the CLI prints."""
        import argparse

        ffcheck = self._ffcheck()
        assert tuple(k for k, _ in ffcheck.PER_FILE_CHECKS) == (
            "memory",
            "comm",
            "exec",
        )
        renderers = ffcheck._summary_renderers(argparse.Namespace())
        assert tuple(renderers) == ("memory", "comm", "exec", "transition")
        for key, (summary_fn, table_fn, header) in renderers.items():
            assert callable(summary_fn) and callable(table_fn)
            assert isinstance(header, str) and header

    def test_memory_summary_schema_frozen(self):
        from flexflow_tpu.analysis.memory_analysis import (
            analyze_memory,
            memory_summary_json,
        )

        g = _branch_pcg()
        a = analyze_memory(g, machine_spec=SPEC4, mapping=_branch_mapping(g))
        s = memory_summary_json(a)
        assert s["memory"] == 1  # schema version
        assert tuple(sorted(s)) == MEMORY_SUMMARY_FIELDS
        assert s["devices"]
        assert tuple(sorted(s["devices"][0])) == MEMORY_DEVICE_FIELDS

    def test_transition_summary_schema_frozen(self):
        from flexflow_tpu.analysis.transition_analysis import (
            transition_summary_json,
            verify_transition,
        )

        g = _branch_pcg()
        m = _branch_mapping(g)
        a, diags = verify_transition(g, m, g, m, machine_spec=SPEC4)
        assert errors_of(diags) == []
        s = transition_summary_json(a)
        assert s["transition"] == 1  # schema version
        assert s["verdict"] == "swappable"
        assert tuple(sorted(s)) == TRANSITION_SUMMARY_FIELDS
        for leaf in s["per_leaf"]:
            assert tuple(sorted(leaf)) == TRANSITION_LEAF_FIELDS
