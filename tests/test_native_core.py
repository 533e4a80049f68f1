"""Cross-checks the native C++ core (native/src/ffcore.cc) against the pure
Python fallbacks, on random DAGs and on real substitution pattern matching.

Mirrors the reference's approach of unit-testing its native graph library
(lib/utils/test/src/) and pattern matcher (lib/substitutions/test/src/).
"""

import random

import pytest

from flexflow_tpu import native_lib
from flexflow_tpu.utils.graph import algorithms as alg
from flexflow_tpu.utils.graph.digraph import DiGraph, Node

pytestmark = pytest.mark.skipif(
    not native_lib.native_available(), reason="native toolchain unavailable"
)


def random_dag(rng, n, p):
    g = DiGraph()
    nodes = g.add_nodes(n)
    for i in range(n):
        for j in range(i + 1, n):
            if rng.random() < p:
                g.add_edge(nodes[i], nodes[j])
    return g, nodes


def _py_only(monkeypatch):
    monkeypatch.setattr(native_lib, "native_available", lambda: False)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("n,p", [(20, 0.1), (40, 0.25), (64, 0.05)])
def test_algorithms_agree(monkeypatch, seed, n, p):
    rng = random.Random(seed)
    g, _ = random_dag(rng, n, p)

    native = {
        "topo": alg.get_topological_ordering(g),
        "tr": alg.get_transitive_reduction(g),
        "tc": alg.get_transitive_closure(g),
        "dom": alg.get_dominators(g),
        "pdom": alg.get_post_dominators(g),
        "wcc": alg.get_weakly_connected_components(g),
    }
    _py_only(monkeypatch)
    assert alg.get_topological_ordering(g) == native["topo"]
    assert list(alg.get_transitive_reduction(g).edges()) == list(native["tr"].edges())
    assert list(alg.get_transitive_closure(g).edges()) == list(native["tc"].edges())
    assert alg.get_dominators(g) == native["dom"]
    assert alg.get_post_dominators(g) == native["pdom"]
    assert alg.get_weakly_connected_components(g) == native["wcc"]


def test_topo_cycle_raises():
    g = DiGraph()
    a, b = g.add_nodes(2)
    g.add_edge(a, b)
    g.add_edge(b, a)
    # pad above the native dispatch threshold
    g.add_nodes(alg._NATIVE_MIN_NODES)
    with pytest.raises(ValueError):
        alg.get_topological_ordering(g)


def _mlp_pcg():
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    b = ComputationGraphBuilder()
    x = b.create_input([8, 16], name="x")
    h = b.dense(x, 32, use_bias=False, name="fc1")
    h = b.relu(h)
    h = b.dense(h, 32, use_bias=False, name="fc2")
    h = b.relu(h)
    h = b.dense(h, 8, use_bias=False, name="fc3")
    h = b.softmax(h)
    return pcg_from_computation_graph(b.graph)


def test_pattern_matches_agree(monkeypatch):
    from flexflow_tpu.substitutions import pcg_pattern as pp
    from flexflow_tpu.substitutions.rules import generate_parallelization_rules

    pcg = _mlp_pcg()
    rules = generate_parallelization_rules([2, 4])
    native_results = [pp.find_pattern_matches(r.pattern, pcg) for r in rules]
    assert any(len(m) > 0 for m in native_results)
    _py_only(monkeypatch)
    py_results = [pp.find_pattern_matches(r.pattern, pcg) for r in rules]
    assert native_results == py_results


class TestNativeTTSPDecompose:
    """ffc_ttsp_decompose vs the pure-Python reduction (series_parallel.py)."""

    @staticmethod
    def _python_ttsp(monkeypatch, g):
        import flexflow_tpu.utils.graph.series_parallel as spmod

        with monkeypatch.context() as mp:
            _py_only(mp)
            return spmod._ttsp_decomposition(g)

    def test_random_dags_agree(self, monkeypatch):
        from flexflow_tpu.utils.graph.series_parallel import (
            _ttsp_decomposition,
        )

        rng = random.Random(7)
        checked_sp = 0
        for _ in range(200):
            g, _ = random_dag(rng, rng.randint(2, 14), 0.3)
            a = _ttsp_decomposition(g)
            b = self._python_ttsp(monkeypatch, g)
            assert a == b
            if a is not None:
                checked_sp += 1
        assert checked_sp > 10  # the sample must include real SP graphs

    def test_chain_and_diamond(self, monkeypatch):
        from flexflow_tpu.utils.graph import DiGraph
        from flexflow_tpu.utils.graph.series_parallel import (
            SeriesSplit,
            _ttsp_decomposition,
        )

        g = DiGraph()
        a, b, c, d = (g.add_node() for _ in range(4))
        g.add_edge(a, b)
        g.add_edge(a, c)
        g.add_edge(b, d)
        g.add_edge(c, d)
        sp = _ttsp_decomposition(g)
        assert isinstance(sp, SeriesSplit)
        assert sp == self._python_ttsp(monkeypatch, g)


def test_library_is_named_by_source_hash_and_build_failure_is_reported(
    monkeypatch, tmp_path, capsys
):
    """Staleness is a file name, not an mtime (a copied tree resets
    mtimes), and a failed build says why instead of a bare None."""
    import os

    good = os.path.basename(native_lib._so_path())
    bad_src = tmp_path / "ffcore.cc"
    bad_src.write_text("this is not C++\n")
    monkeypatch.setattr(native_lib, "_SRC", str(bad_src))
    monkeypatch.setattr(native_lib, "_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(native_lib, "_lib", None)
    monkeypatch.setattr(native_lib, "_lib_error", None)
    assert os.path.basename(native_lib._so_path()) != good
    assert native_lib.get_lib() is None
    assert "g++ exited" in native_lib.load_error()
    assert "native core unavailable" in capsys.readouterr().err
