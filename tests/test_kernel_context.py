"""`kernels/context.py` is the one owner of what a kernel may ask of the trace
it is lowered in and of what it chose (PR 70): the arrows between the layers,
read from the sources' imports; the one rule behind the five route rules that
emit a bare Pallas call; the table of choices and its pinned views.
"""

import ast
import glob
import os

import jax
import pytest

from flexflow_tpu.kernels import context, kda, moe, selective_scan, ssm
from flexflow_tpu.observability import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELS = os.path.join(ROOT, "flexflow_tpu", "kernels")
# the two files of `kernels/` that hand a value of the step to
# `observability/` (a named debt, ROADMAP Design), each by the one module
OBSERVED_FROM_KERNELS = {"loss.py": {"trace"}, "moe.py": {"routing"}}


def parsed(path):
    with open(path) as f:
        return ast.parse(f.read(), path)


def imports_of(tree):
    """(module, name) of every import in `tree`, at any depth."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {(alias.name, None) for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            found |= {(node.module or "", alias.name) for alias in node.names}
    return found


def sources():
    here = os.path.abspath(__file__)
    for top in ("flexflow_tpu", "tests", "tools"):
        for path in glob.glob(os.path.join(ROOT, top, "**", "*.py"), recursive=True):
            if os.path.abspath(path) != here:
                yield path


@pytest.mark.parametrize(
    "name", sorted(os.path.basename(p) for p in glob.glob(KERNELS + "/*.py"))
)
def test_kernels_do_not_import_the_layer_that_observes_them(name):
    observed = {
        (module, alias)
        for module, alias in imports_of(parsed(os.path.join(KERNELS, name)))
        if module.startswith("flexflow_tpu.observability")
    }
    allowed = {
        ("flexflow_tpu.observability", alias)
        for alias in OBSERVED_FROM_KERNELS.get(name, ())
    }
    assert observed <= allowed, observed - allowed
    if name == "context.py":
        # the lowest layer: nothing of the program above it
        assert not {
            m for m, _ in imports_of(parsed(os.path.join(KERNELS, name)))
            if m.startswith("flexflow_tpu")
        }


def test_one_file_holds_the_thread_local_and_the_backend_test():
    """No file but `kernels/context.py` names its thread-local or defines
    the backend test, under the old private name or the new;
    `observability/routing.py`'s sink and `TraceRecorder`'s stack are
    thread-locals of their own."""
    own_thread_local = {
        os.path.join(ROOT, "flexflow_tpu", "kernels", "context.py"),
        os.path.join(ROOT, "flexflow_tpu", "observability", "routing.py"),
    }
    complaints = []
    # spelled apart: a grep for either name finds no test either
    backend_test, thread_local = "_backend" + "_ok", "_" + "tls"
    for path in sources():
        with open(path) as f:
            text = f.read()
        if backend_test in text:
            complaints.append(f"{path}: {backend_test}")
        if path in own_thread_local:
            continue
        for node in ast.walk(ast.parse(text, path)):
            if isinstance(node, ast.Name) and node.id == thread_local:
                complaints.append(f"{path}:{node.lineno}: {thread_local}")
            if (
                isinstance(node, ast.Attribute) and node.attr == thread_local
                and not (isinstance(node.value, ast.Name) and node.value.id == "self")
            ):
                complaints.append(f"{path}:{node.lineno}: .{thread_local}")
            if isinstance(node, ast.FunctionDef) and node.name == "on_tpu":
                complaints.append(f"{path}:{node.lineno}: def on_tpu")
    assert not complaints, complaints


def test_nothing_of_the_program_enters_the_described_tpu():
    users = [
        path for path in glob.glob(
            os.path.join(ROOT, "flexflow_tpu", "**", "*.py"), recursive=True
        )
        if "described_tpu" in open(path).read()
        and os.path.basename(path) != "context.py"
    ]
    assert users == []


# -- the facts ------------------------------------------------------------------


def test_the_facts_nest_and_close():
    assert context.declared_mesh() is None
    assert not context.bare_calls_refused()
    assert context.lowering_scope() is None
    with context.flash_mesh("m", "data", None, True):
        assert context.declared_mesh() == ("m", "data", None, True)
        with context.flash_mesh("n", None, "heads"), context.no_flash():
            assert context.declared_mesh() == ("n", None, "heads", False)
            assert context.bare_calls_refused()
        assert context.declared_mesh() == ("m", "data", None, True)
        assert not context.bare_calls_refused()
    with context.lowering_node("ff.a.b"), context.lowering_node("ff.c.d"):
        assert context.lowering_scope() == "ff.c.d"
        with context.lowering_node(None):
            assert context.lowering_scope() is None
    assert context.declared_mesh() is None
    assert context.lowering_scope() is None


def test_the_described_tpu_is_a_tpu_until_it_closes(monkeypatch):
    monkeypatch.delenv("FLEXFLOW_TPU_FLASH_INTERPRET", raising=False)
    assert not context.on_tpu() and context.on_tpu(allow_interpret=True)
    with context.described_tpu():
        assert context.on_tpu()
        with context.described_tpu():
            assert context.on_tpu()
        assert context.on_tpu()
        # interpret mode stays what the environment says: a described chip
        # runs nothing
        assert not context.interpret_default()
    assert not context.on_tpu()


def test_a_backend_that_fails_to_initialise_raises_in_every_gate(monkeypatch):
    def fails():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "default_backend", fails)
    with pytest.raises(RuntimeError):
        context.on_tpu()
    with context.no_flash(), pytest.raises(RuntimeError):
        context.admits_bare_pallas_call(True)


ADMITS = {
    # name: (described, interpret asked, mesh declared, refused) -> admitted
    "the_cpu": (False, False, False, False, False),
    "the_cpu_interpreting": (False, True, False, False, True),
    "a_tpu": (True, False, False, False, True),
    "a_tpu_under_a_mesh": (True, False, True, False, False),
    "a_tpu_refused": (True, False, False, True, False),
    "interpreting_under_a_mesh": (False, True, True, False, False),
    "interpreting_refused": (False, True, False, True, False),
}


@pytest.mark.parametrize("case", list(ADMITS))
def test_a_bare_pallas_call_is_admitted(case, entered):
    described, interpret, mesh, refused, want = ADMITS[case]
    if described:
        entered(context.described_tpu())
    if mesh:
        entered(context.flash_mesh(None, ("data",), None))
    if refused:
        entered(context.no_flash())
    assert context.admits_bare_pallas_call(interpret) is want


# each route rule at shapes its own tests pass: the rule alone decides
ROUTE_RULES = {
    "ssm.conv_route": (lambda: ssm.conv_route(0, 256, 1024, 4), "kernels", "xla"),
    "ssm.scan_route": (
        lambda: ssm.scan_route(2, 8, 64, 1, 128, 128), "ssd", "xla"
    ),
    "kda.scan_route": (lambda: kda.scan_route(128, 128, 64), "kda", "xla"),
    "selective_scan.scan_route": (
        lambda: selective_scan.scan_route(256, 16), "pallas", "scan"
    ),
    "moe._pallas_allowed": (lambda: moe._pallas_allowed(False), True, False),
}


@pytest.mark.parametrize("rule", list(ROUTE_RULES))
def test_one_predicate_decides_for_every_route_rule(rule, monkeypatch):
    """The five rules whose kernels exist only as a bare call ask
    `context.admits_bare_pallas_call` and nothing else about the trace: with
    the predicate replaced, each answers as it says. The mixers pass it
    interpret mode, the experts none."""
    ask, admitted, refused = ROUTE_RULES[rule]
    asked = []

    def says(answer):
        def admits(allow_interpret=False):
            asked.append(allow_interpret)
            return answer

        return admits

    monkeypatch.setenv("FLEXFLOW_TPU_FLASH_INTERPRET", "1")
    monkeypatch.setattr(context, "admits_bare_pallas_call", says(True))
    with context.no_flash():  # the replaced predicate is the only reader
        assert ask() == admitted
    monkeypatch.setattr(context, "admits_bare_pallas_call", says(False))
    with context.described_tpu():
        assert ask() == refused
    assert asked == 2 * [rule != "moe._pallas_allowed"]


# -- the table of choices -------------------------------------------------------

PINNED = {
    # kind: (a value of the type its readers take, the pinned view)
    "attention_routes": ("fused_row window=1024 group=8", trace.attention_routes),
    "window_tiles": ((36, 136), trace.window_tiles),
    "rotaries": ("default theta=500000", trace.rotaries),
    "latent_attention_forms": (
        {"query_rank": None, "rotated_columns": 64, "pairing": "interleaved",
         "core": "flash_fwd_causal_wide_key"},
        trace.latent_attention_forms,
    ),
    "scan_column_blocks": (4, trace.scan_column_blocks),
}


@pytest.mark.parametrize("kind", list(PINNED))
def test_a_choice_is_kept_by_node_and_read_by_its_pinned_view(kind, monkeypatch):
    value, view = PINNED[kind]
    monkeypatch.setattr(context, "_CHOICES", {})
    context.note(kind, value)  # no node is open: dropped
    assert context.choices(kind) == {} == view() and context.choices() == {}
    scope = f"ff.some.{kind}"
    with context.lowering_node(scope):
        context.note(kind, value)
    for read in (context.choices(kind), trace.kernel_choices(kind), view()):
        assert read == {scope: value}
        assert type(read[scope]) is type(value)
    assert trace.kernel_choices() == {scope: {kind: value}}
    # fresh copies: a reader cannot reach the table
    got = view()
    if isinstance(value, dict):
        got[scope]["core"] = "dense"
    got["ff.other"] = value
    assert view() == {scope: value}
    # lowered again, the node's last choice stands
    with context.lowering_node(scope):
        context.note(kind, value)
    assert len(view()) == 1


def test_the_set_up_report_prints_every_kind_noted(monkeypatch):
    monkeypatch.setattr(context, "_CHOICES", {})
    assert "kernel_choices" not in trace.setup_report()
    for name, form in (("attn0", "pallas"), ("attn1", "xla (route)"), ("attn2", "pallas")):
        with context.lowering_node(f"ff.ring_attention.{name}"):
            context.note("between_passes", form)
            context.note("window_tiles", (3, 6))
    with context.lowering_node("ff.s6.mixer0"):
        context.note("selective_scan_routes", "scan")
    lines = trace.setup_report().splitlines()[-3:]
    assert lines == [
        "norm and rotary of the plain attention nodes (between_passes()): "
        "2 pallas, 1 xla (route)",
        'kernel_choices("selective_scan_routes"): 1 scan',
        'kernel_choices("window_tiles"): 3 (3, 6)',
    ]


def test_the_selective_scan_node_notes_its_route(monkeypatch):
    """The one route rule that recorded nothing (ROADMAP D12) says which form
    the node took."""
    import jax.numpy as jnp

    monkeypatch.setattr(context, "_CHOICES", {})

    def scan(x, r, b_mat):
        return selective_scan.selective_scan(
            x, r, jnp.zeros((256,)), jnp.zeros((256, 16)), b_mat, b_mat,
            jnp.zeros((256,)),
        )

    operands = (
        jax.ShapeDtypeStruct((1, 128, 256), jnp.float32),
        jax.ShapeDtypeStruct((1, 128, 256), jnp.float32),
        jax.ShapeDtypeStruct((1, 128, 16), jnp.float32),
    )
    with context.lowering_node("ff.s6.mixer0"):
        jax.eval_shape(scan, *operands)
    assert trace.kernel_choices("selective_scan_routes") == {"ff.s6.mixer0": "scan"}
