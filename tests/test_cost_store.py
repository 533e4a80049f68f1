"""Persistent measurement-calibrated cost database tests (ISSUE 9).

Covers the full three-tier fallthrough (analytic -> cached-measured ->
measure) across sessions plus the movement-store satellites:

- `MovementCostStore.save()` lost-update regression: two interleaved
  store instances sharing a path must not drop each other's entries.
- movement-key schema v2 (device kind) with v1 read-side migration:
  legacy entries are preserved on disk but never preferred.
- `CostStore` op-leaf roundtrip, NaN/negative screens, merge-on-save,
  device-kind isolation, correction-factor fitting.
- estimator integration: the analytic estimator prefers a stored
  measurement and applies fitted per-op-class corrections on a miss; an
  EMPTY attached store changes nothing (identical winner store-on vs
  store-off); the measured estimator writes back what it measures.
- cross-process warm start (the test_compile_cache discipline): a fresh
  process prices previously-measured op leaves with ZERO profile_fn
  calls and reproduces the cold search's winning cost bitwise.
- native/Python DP parity with a populated store.
- `tools/cost_db.py` stats/verify/prune CLI smoke (tier-1, like ffcheck).
- slow-marked: warm-store repeat search >= 1.3x faster than cold on the
  measurement-bound leaf-cost phase of the 12-layer proxy.
"""

import json
import math
import os
import subprocess
import sys
import tempfile

import pytest

from flexflow_tpu.compiler.cost_store import (
    CostStore,
    device_kind_signature,
    op_leaf_key,
)
from flexflow_tpu.compiler.movement_store import (
    LEGACY_V1_PREFIX,
    MovementCostStore,
    movement_edge_key,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import CombineAttrs, LinearAttrs
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorDims,
    ParallelTensorShape,
    ShardParallelDim,
)
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.pcg.machine_view import (
    MachineSpaceCoordinate,
    MachineSpecification,
    MachineView,
    MachineViewDimension,
    ProjectionType,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COST_DB_CLI = os.path.join(REPO, "tools", "cost_db.py")


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


def intra_view(stride=1):
    return MachineView(
        MachineSpaceCoordinate(0, 0),
        (MachineViewDimension(stride, ProjectionType.INTRA_NODE),),
    )


LIN = LinearAttrs(out_channels=8, use_bias=False)
INS = (TensorShape((4, 16)),)
WS = (TensorShape((16, 8)),)


# ---------------------------------------------------------------------------
# satellite: MovementCostStore lost-update fix + schema v2 migration
# ---------------------------------------------------------------------------


class TestMovementStoreLostUpdate:
    def test_interleaved_instances_keep_both_entries(self, tmp_path):
        """The old save() rewrote the whole table from memory: instance B
        (loaded before A saved) silently dropped A's entry on ITS save.
        Now each save merges with the freshly re-read disk table."""
        path = str(tmp_path / "store.json")
        a = MovementCostStore(path)
        b = MovementCostStore(path)  # loads the (empty) table before A saves
        a.put("edge_a", 1.0)
        a.save()
        b.put("edge_b", 2.0)
        b.save()  # pre-fix: clobbered edge_a
        c = MovementCostStore(path)
        assert c.get("edge_a") == 1.0
        assert c.get("edge_b") == 2.0

    def test_last_writer_wins_per_key(self, tmp_path):
        path = str(tmp_path / "store.json")
        a = MovementCostStore(path)
        b = MovementCostStore(path)
        a.put("shared", 1.0)
        a.save()
        b.put("shared", 3.0)
        b.save()
        assert MovementCostStore(path).get("shared") == 3.0

    def test_unwritten_keys_follow_disk(self, tmp_path):
        """A key this instance only LOADED (never wrote) must not shadow a
        newer on-disk value at save time."""
        path = str(tmp_path / "store.json")
        a = MovementCostStore(path)
        a.put("k", 1.0)
        a.save()
        b = MovementCostStore(path)  # sees k=1.0
        c = MovementCostStore(path)
        c.put("k", 9.0)
        c.save()
        b.put("other", 5.0)
        b.save()  # b never wrote k: disk's 9.0 must survive
        final = MovementCostStore(path)
        assert final.get("k") == 9.0
        assert final.get("other") == 5.0


class TestMovementStoreSchemaV2:
    def test_edge_key_carries_device_kind(self):
        attrs = CombineAttrs(0, 4)
        shape = pts([16, 32], [4, 1])
        key = movement_edge_key(attrs, [shape], intra_view())
        # v3 layout: ...|<device kind>|<link class> (link class defaults ici)
        assert key.endswith("|" + device_kind_signature() + "|ici")
        other = movement_edge_key(
            attrs, [shape], intra_view(), device_kind="tpu:TPU v4"
        )
        assert other != key and other.endswith("|tpu:TPU v4|ici")

    def test_v1_file_migrates_read_side(self, tmp_path):
        """A schema-1 store (no device kind in keys) is preserved under the
        legacy prefix but NEVER matched — its measurements' origin device
        is unknowable, which is exactly the CPU-store-on-TPU contamination
        the v2 key prevents."""
        path = str(tmp_path / "store.json")
        attrs = CombineAttrs(0, 4)
        shape = pts([16, 32], [4, 1])
        view = intra_view()
        v1_key = f"{type(attrs).__name__}|8192|{shape!r}|{view!r}"
        with open(path, "w") as f:
            json.dump({"schema": 1, "entries": {v1_key: 0.125}}, f)
        s = MovementCostStore(path)
        assert len(s) == 1  # preserved...
        assert s.get_edge(attrs, [shape], view) is None  # ...never matched
        assert s.get(LEGACY_V1_PREFIX + v1_key) == 0.125
        # a save keeps the legacy entry on disk at the current schema
        s.put_edge(attrs, [shape], view, 0.5)
        s.save()
        data = json.load(open(path))
        assert data["schema"] == 3
        assert data["entries"][LEGACY_V1_PREFIX + v1_key] == 0.125
        assert MovementCostStore(path).get_edge(attrs, [shape], view) == 0.5

    def test_estimator_ignores_foreign_device_kind(self, tmp_path):
        """A store whose matching edge was captured on a DIFFERENT device
        kind must fall through to the analytic estimate."""
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            AnalyticTPUCostEstimator,
        )
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            OpCostEstimateKey,
        )

        spec = MachineSpecification(1, 1, 8, 25.0, 400.0)
        attrs = CombineAttrs(0, 4)
        shape = pts([16, 32], [4, 1])
        view = intra_view()
        key = OpCostEstimateKey(attrs, (shape,), (pts([16, 32]),), view)
        store = MovementCostStore(str(tmp_path / "s.json"))
        store.put(
            movement_edge_key(attrs, [shape], view, device_kind="tpu:TPU v4"),
            0.0625,
        )
        base = AnalyticTPUCostEstimator(spec)
        est = AnalyticTPUCostEstimator(spec, movement_store=store)
        assert est.estimate_op_cost(key) == base.estimate_op_cost(key)
        # same-device capture IS preferred
        store.put_edge(attrs, [shape], view, 0.0625)
        assert est.estimate_op_cost(key) == 0.0625


# ---------------------------------------------------------------------------
# CostStore basics
# ---------------------------------------------------------------------------


class TestCostStoreBasics:
    def test_op_roundtrip_and_screens(self, tmp_path):
        s = CostStore(str(tmp_path))
        assert s.path.endswith("cost_db.json")  # dir -> file resolution
        assert s.get_op(LIN, INS, WS) is None
        s.put_op(LIN, INS, WS, 1.5, 1024)
        s.put_op(LIN, INS, None, float("nan"))  # screened
        s.put_op(LIN, INS, None, -1.0)  # screened
        assert s.get_op(LIN, INS, WS) == (1.5, 1024)
        assert s.get_op(LIN, INS, None) is None
        s.save()
        s2 = CostStore(str(tmp_path))
        assert s2.get_op(LIN, INS, WS) == (1.5, 1024)
        assert s2.op_hits == 1 and s2.op_misses == 0

    def test_unrunnable_verdict_cached(self, tmp_path):
        s = CostStore(str(tmp_path))
        s.put_op(LIN, INS, WS, float("inf"))
        hit = s.get_op(LIN, INS, WS)
        assert hit is not None and math.isinf(hit[0])
        s.save()
        hit2 = CostStore(str(tmp_path)).get_op(LIN, INS, WS)
        assert hit2 is not None and math.isinf(hit2[0])
        # the JSON itself stays finite (portable)
        data = json.load(open(s.path))
        (entry,) = data["entries"].values()
        assert entry["unrunnable"] is True and entry["ms"] == 0.0

    def test_key_carries_dtype_and_device_kind(self):
        k_f32 = op_leaf_key(LIN, INS, WS)
        k_bf16 = op_leaf_key(
            LIN, (TensorShape((4, 16), DataType.BFLOAT16),), WS
        )
        assert k_f32 != k_bf16
        assert device_kind_signature() in k_f32
        assert op_leaf_key(LIN, INS, WS, device_kind="tpu:TPU v4") != k_f32

    def test_device_kind_isolation(self, tmp_path):
        tpu = CostStore(str(tmp_path), device_kind="tpu:TPU v4")
        tpu.put_op(LIN, INS, WS, 0.01)
        tpu.save()
        cpu = CostStore(str(tmp_path), device_kind="cpu:cpu")
        assert cpu.get_op(LIN, INS, WS) is None  # no cross-contamination
        assert len(cpu) == 1  # but the entry is preserved

    def test_merge_on_save(self, tmp_path):
        a = CostStore(str(tmp_path))
        b = CostStore(str(tmp_path))
        a.put_op(LIN, INS, WS, 1.0)
        a.save()
        b.put_op(LIN, INS, None, 2.0)
        b.save()
        c = CostStore(str(tmp_path))
        assert c.get_op(LIN, INS, WS) == (1.0, 0)
        assert c.get_op(LIN, INS, None) == (2.0, 0)

    def test_movement_and_op_entries_coexist(self, tmp_path):
        s = CostStore(str(tmp_path))
        attrs = CombineAttrs(0, 4)
        shape = pts([16, 32], [4, 1])
        s.put_op(LIN, INS, WS, 1.0)
        s.put_edge(attrs, [shape], intra_view(), 0.25)
        s.save()
        s2 = CostStore(str(tmp_path))
        assert s2.get_edge(attrs, [shape], intra_view()) == 0.25
        assert s2.get_op(LIN, INS, WS) == (1.0, 0)
        stats = s2.stats()
        assert stats["by_kind"] == {"op": 1, "movement": 1}
        assert stats["by_op_class"] == {"LinearAttrs": 1}


class TestCorrections:
    def test_fit_gates_clamps_and_geomeans(self, tmp_path):
        s = CostStore(str(tmp_path))
        ins2 = (TensorShape((8, 16)),)
        s.put_op(LIN, INS, WS, 2.0)
        s.note_analytic(LIN, INS, WS, 1.0)  # ratio 2
        assert s.fit_corrections(min_pairs=2) == {}  # gated below min_pairs
        s._corrections = None
        s.put_op(LIN, ins2, WS, 8.0)
        s.note_analytic(LIN, ins2, WS, 1.0)  # ratio 8
        fit = s.fit_corrections(min_pairs=2)
        assert fit["LinearAttrs"]["pairs"] == 2
        assert fit["LinearAttrs"]["factor"] == pytest.approx(4.0)  # geomean
        assert s.correction_for("LinearAttrs") == pytest.approx(4.0)
        assert s.correction_for("ElementUnaryAttrs") == 1.0
        # clamp: a polluted pair set cannot explode every analytic price
        s2 = CostStore(str(tmp_path / "c2"))
        for i, shape in enumerate((INS, ins2)):
            s2.put_op(LIN, shape, WS, 1e6)
            s2.note_analytic(LIN, shape, WS, 1e-3)
        assert s2.correction_for("LinearAttrs") == 20.0

    def test_note_analytic_requires_measurement(self, tmp_path):
        s = CostStore(str(tmp_path))
        s.note_analytic(LIN, INS, WS, 1.0)  # no measured entry: dropped
        assert len(s) == 0 and not s.dirty


# ---------------------------------------------------------------------------
# estimator integration: the three-tier fallthrough
# ---------------------------------------------------------------------------


SPEC4 = MachineSpecification(1, 1, 4, 25.0, 400.0)


def mlp_pcg(batch=16, hidden=32, out=8):
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    b = ComputationGraphBuilder()
    x = b.create_input([batch, hidden], name="x")
    h = b.dense(x, hidden, use_bias=False, name="fc1")
    h = b.relu(h)
    b.dense(h, out, use_bias=False, name="fc2")
    return pcg_from_computation_graph(b.graph)


def analytic_ctx(store=None, spec=SPEC4):
    from flexflow_tpu.compiler import (
        AnalyticTPUCostEstimator,
        MachineMappingContext,
        make_default_allowed_machine_views,
    )

    return MachineMappingContext(
        AnalyticTPUCostEstimator(spec, cost_store=store),
        make_default_allowed_machine_views(),
    )


class TestAnalyticFallthrough:
    def _linear_leaf_key(self):
        """An OpCostEstimateKey for a batch-sharded Linear leaf (data slot
        + weight slot, as problem_tree._leaf_key builds them)."""
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            OpCostEstimateKey,
        )

        lin = LinearAttrs(out_channels=8, use_bias=False)
        data = pts([16, 16], [4, 1])
        weight = pts([16, 8])
        out = pts([16, 8], [4, 1])
        return OpCostEstimateKey(
            lin, (data, weight), (out,), intra_view(), (False, True)
        )

    def test_empty_store_is_identity(self, tmp_path):
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            AnalyticTPUCostEstimator,
        )

        key = self._linear_leaf_key()
        bare = AnalyticTPUCostEstimator(SPEC4)
        with_store = AnalyticTPUCostEstimator(
            SPEC4, cost_store=CostStore(str(tmp_path))
        )
        assert with_store.estimate_op_cost(key) == bare.estimate_op_cost(key)

    def test_stored_measurement_preferred_and_pair_noted(self, tmp_path):
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            AnalyticTPUCostEstimator,
        )

        key = self._linear_leaf_key()
        store = CostStore(str(tmp_path))
        bare = AnalyticTPUCostEstimator(SPEC4)
        analytic_ms = bare.estimate_op_cost(key)
        # store the piece measurement under the leaf's own key split
        pieces = (TensorShape((4, 16)),)
        weights = (TensorShape((16, 8)),)
        store.put_op(key.op_attrs, pieces, weights, 0.777)
        est = AnalyticTPUCostEstimator(SPEC4, cost_store=store)
        assert est.estimate_op_cost(key) == 0.777
        # the hit recorded the raw roofline as the pair's analytic half
        data = store.peek_op(key.op_attrs, pieces, weights)
        assert data == 0.777
        entry = [
            e for e in store._table.values() if e.get("kind") == "op"
        ][0]
        assert entry["analytic_ms"] == pytest.approx(analytic_ms)

    def test_correction_applied_on_miss(self, tmp_path):
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            AnalyticTPUCostEstimator,
        )

        key = self._linear_leaf_key()
        store = CostStore(str(tmp_path))
        # two fitted pairs say Linear measures 3x its roofline...
        for shape in ((TensorShape((2, 4)),), (TensorShape((3, 4)),)):
            store.put_op(key.op_attrs, shape, None, 3.0)
            store.note_analytic(key.op_attrs, shape, None, 1.0)
        bare = AnalyticTPUCostEstimator(SPEC4)
        est = AnalyticTPUCostEstimator(SPEC4, cost_store=store)
        # ...so a MISSED Linear leaf prices at 3x the bare roofline
        assert est.estimate_op_cost(key) == pytest.approx(
            3.0 * bare.estimate_op_cost(key)
        )

    def test_search_winner_identical_store_on_vs_off(self, tmp_path):
        """Acceptance pin: attaching an EMPTY store must not change the
        search outcome — same winner cost, both DPs."""
        from flexflow_tpu.compiler import OptimizerConfig, graph_optimize
        from flexflow_tpu.substitutions import (
            generate_parallelization_rules,
        )

        rules = generate_parallelization_rules([2, 4])
        cfg = OptimizerConfig(alpha=1.2, budget=3)
        off = graph_optimize(mlp_pcg(), analytic_ctx(None), SPEC4, rules, cfg)
        store = CostStore(str(tmp_path))
        on = graph_optimize(mlp_pcg(), analytic_ctx(store), SPEC4, rules, cfg)
        assert on.runtime == off.runtime
        assert on.serial_runtime == off.serial_runtime
        assert on.seed_runtimes == off.seed_runtimes


class TestMeasuredWriteBackAndParity:
    def _measured_ctx(self, store):
        from flexflow_tpu.compiler import (
            MachineMappingContext,
            TPUCostEstimator,
            make_default_allowed_machine_views,
        )
        from flexflow_tpu.kernels.profiling import ProfilingSettings
        from flexflow_tpu.local_execution.cost_estimator import (
            LocalCostEstimator,
        )

        est = TPUCostEstimator(
            SPEC4,
            local_cost_estimator=LocalCostEstimator(
                ProfilingSettings(warmup_iters=1, measure_iters=2)
            ),
            cost_store=store,
        )
        return MachineMappingContext(
            est, make_default_allowed_machine_views()
        )

    def test_measured_search_populates_store_then_prices_without_profiling(
        self, tmp_path, monkeypatch
    ):
        """In-process version of the warm-start contract: a measured
        search writes every runnable leaf into the store; a SECOND
        estimator (fresh in-memory cache) sharing the store re-prices the
        same search with zero profile_fn calls and the identical cost."""
        import flexflow_tpu.local_execution.cost_estimator as lce
        from flexflow_tpu.compiler import OptimizerConfig, graph_optimize
        from flexflow_tpu.substitutions import (
            generate_parallelization_rules,
        )

        store = CostStore(str(tmp_path))
        rules = generate_parallelization_rules([2, 4])
        cfg = OptimizerConfig(alpha=1.2, budget=1)
        cold = graph_optimize(
            mlp_pcg(), self._measured_ctx(store), SPEC4, rules, cfg
        )
        assert len(store) > 0
        store.save()

        calls = []
        orig = lce.profile_fn
        monkeypatch.setattr(
            lce, "profile_fn",
            lambda *a, **k: calls.append(1) or orig(*a, **k),
        )
        warm_store = CostStore(str(tmp_path))
        warm = graph_optimize(
            mlp_pcg(), self._measured_ctx(warm_store), SPEC4, rules, cfg
        )
        assert calls == [], (
            f"warm search re-measured {len(calls)} op leaves"
        )
        assert warm.runtime == cold.runtime

    def test_native_python_dp_parity_with_populated_store(
        self, tmp_path, monkeypatch
    ):
        """Acceptance pin: with a populated store the native DP and the
        pure-Python fallback still return the identical winning cost (the
        store feeds both through the same Python-side leaf tables)."""
        from flexflow_tpu.compiler import OptimizerConfig, graph_optimize
        from flexflow_tpu.substitutions import (
            generate_parallelization_rules,
        )

        store = CostStore(str(tmp_path))
        rules = generate_parallelization_rules([2, 4])
        cfg = OptimizerConfig(alpha=1.2, budget=1)
        graph_optimize(  # populate
            mlp_pcg(), self._measured_ctx(store), SPEC4, rules, cfg
        )
        store.save()

        native = graph_optimize(
            mlp_pcg(),
            self._measured_ctx(CostStore(str(tmp_path))),
            SPEC4, rules, cfg,
        )
        assert native.telemetry["native_dp"] is True
        monkeypatch.setenv("FF_TPU_NO_NATIVE", "1")
        python = graph_optimize(
            mlp_pcg(),
            self._measured_ctx(CostStore(str(tmp_path))),
            SPEC4, rules, cfg,
        )
        assert python.telemetry["native_dp"] is False
        assert native.runtime == python.runtime
        assert native.seed_runtimes == python.seed_runtimes


# ---------------------------------------------------------------------------
# cross-process warm start (the test_compile_cache discipline)
# ---------------------------------------------------------------------------


_SEARCH_CHILD = """
import json, sys, time
sys.path.insert(0, {repo!r})
import jax
jax.config.update('jax_platforms', 'cpu')

# count every real measurement the pricing performs
import flexflow_tpu.local_execution.cost_estimator as lce
_calls = [0]
_orig = lce.profile_fn
def _counting(fn, settings, *a, **k):
    _calls[0] += 1
    return _orig(fn, settings, *a, **k)
lce.profile_fn = _counting

from flexflow_tpu.compiler import (
    MachineMappingContext, OptimizerConfig, TPUCostEstimator,
    graph_optimize, make_default_allowed_machine_views)
from flexflow_tpu.compiler.cost_store import CostStore
from flexflow_tpu.kernels.profiling import ProfilingSettings
from flexflow_tpu.local_execution.cost_estimator import LocalCostEstimator
from flexflow_tpu.pcg.machine_view import MachineSpecification
from flexflow_tpu.substitutions.rules import generate_parallelization_rules

{build_pcg}

spec = MachineSpecification(1, 1, {ndev}, 1.0, 2.0)
store = CostStore({store_dir!r})
est = TPUCostEstimator(
    spec,
    local_cost_estimator=LocalCostEstimator(
        ProfilingSettings(warmup_iters=1, measure_iters=2)),
    ici_latency_ms=0.1, dcn_latency_ms=0.2,
    cost_store=store,
)
ctx = MachineMappingContext(est, make_default_allowed_machine_views())
rules = generate_parallelization_rules({degrees})
t0 = time.perf_counter()
r = graph_optimize(pcg, ctx, spec, rules,
                   OptimizerConfig(alpha=1.2, budget={budget}))
seconds = time.perf_counter() - t0
store.save()
print('RESULT ' + json.dumps({{
    'seconds': seconds,
    'leaf_cost_ms': (r.telemetry or {{}}).get('phase_ms', {{}}).get('leaf_cost'),
    'runtime': r.runtime,
    'profile_calls': _calls[0],
    'store_entries': len(store),
}}))
"""

_MLP_PCG = """
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.parallel_computation_graph import (
    pcg_from_computation_graph)
b = ComputationGraphBuilder()
x = b.create_input([16, 32], name="x")
h = b.dense(x, 32, use_bias=False, name="fc1")
h = b.relu(h)
b.dense(h, 8, use_bias=False, name="fc2")
pcg = pcg_from_computation_graph(b.graph)
"""

_PROXY_PCG = """
from flexflow_tpu.models.flagship import build_flagship_pcg
# the 12-layer proxy at CPU-measurable dims: same topology as the
# flagship, every layer's leaf family measured for real
pcg = build_flagship_pcg(batch=8, seq=32, embed=64, heads=2, layers=12,
                         vocab=256)
"""


def _run_search_child(store_dir, build_pcg, ndev, degrees, budget, timeout):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    code = _SEARCH_CHILD.format(
        repo=REPO, build_pcg=build_pcg, store_dir=store_dir,
        ndev=ndev, degrees=degrees, budget=budget,
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(
        f"search child produced no RESULT:\n{out.stdout}\n{out.stderr[-2000:]}"
    )


class TestWarmStartCrossProcess:
    def test_second_process_prices_with_zero_profile_calls(self):
        """Satellite acceptance: a FRESH process pricing leaves a past
        session measured performs ZERO profile_fn calls and reproduces
        the cold run's winning cost bitwise (the stored floats ARE the
        cold run's measurements)."""
        store_dir = tempfile.mkdtemp(prefix="ffcostdb_")
        cold = _run_search_child(
            store_dir, _MLP_PCG, ndev=4, degrees=[2, 4], budget=1,
            timeout=600,
        )
        assert cold["profile_calls"] > 0, cold
        assert cold["store_entries"] > 0, cold
        assert os.path.exists(os.path.join(store_dir, "cost_db.json"))
        warm = _run_search_child(
            store_dir, _MLP_PCG, ndev=4, degrees=[2, 4], budget=1,
            timeout=600,
        )
        assert warm["profile_calls"] == 0, (
            f"second process re-measured {warm['profile_calls']} leaves"
        )
        assert warm["runtime"] == cold["runtime"]


@pytest.mark.slow
class TestWarmStoreSpeedup:
    def test_warm_repeat_search_beats_cold_on_measurement_phase(self):
        """Round-9 acceptance bar: on the 12-layer proxy the warm-store
        repeat search is >= 1.3x faster on the measurement-bound portion
        (the DP's leaf_cost phase — where profile_fn lives) with the
        identical winning plan cost, and performs zero measurements."""
        store_dir = tempfile.mkdtemp(prefix="ffcostdb_slow_")
        cold = _run_search_child(
            store_dir, _PROXY_PCG, ndev=8, degrees=[2, 4, 8], budget=2,
            timeout=1800,
        )
        warm = _run_search_child(
            store_dir, _PROXY_PCG, ndev=8, degrees=[2, 4, 8], budget=2,
            timeout=1800,
        )
        assert cold["profile_calls"] > 0
        assert warm["profile_calls"] == 0, warm
        assert warm["runtime"] == cold["runtime"], (
            "the persistent store changed the winning plan's cost"
        )
        speedup = cold["leaf_cost_ms"] / max(warm["leaf_cost_ms"], 1e-9)
        assert speedup >= 1.3, (
            f"warm leaf-cost speedup {speedup:.2f}x < 1.3x "
            f"(cold {cold['leaf_cost_ms']:.0f} ms, "
            f"warm {warm['leaf_cost_ms']:.0f} ms)"
        )


# ---------------------------------------------------------------------------
# FFModel provenance + audit feed
# ---------------------------------------------------------------------------


class TestFFModelIntegration:
    def test_compile_records_cost_db_provenance_and_audit_feeds_store(
        self, tmp_path
    ):
        from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

        d = str(tmp_path / "db")
        cfg = FFConfig(
            batch_size=8, seed=0, search_budget=1, plan_audit=True,
            cost_store=d,
        )
        m = FFModel(cfg)
        x = m.create_tensor([8, 16], name="x")
        h = m.dense(x, 16, use_bias=False, name="fc1")
        h = m.relu(h)
        logits = m.dense(h, 4, use_bias=False, name="head")
        m.compile(
            SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
            logit_tensor=logits,
        )
        prov = m.search_provenance["cost_db"]
        assert prov["entries"] > 0
        assert prov["op_misses"] > 0  # cold store: the search missed
        assert set(prov) >= {
            "path", "device_kind", "op_hits", "op_misses",
            "movement_hits", "movement_misses", "fitted_classes",
            "corrections",
        }
        # the audit fed per-op measured ms into the SAME store
        data = json.load(open(os.path.join(d, "cost_db.json")))
        op_keys = [k for k in data["entries"] if k.startswith("op|")]
        assert op_keys, "plan audit fed no op measurements into the store"
        # ...with (analytic, measured) pairs completed in one audit
        pairs = [
            e for e in data["entries"].values()
            if isinstance(e, dict) and e.get("analytic_ms")
        ]
        assert pairs, "audit recorded no correction pairs"
        # a fresh analytic estimator now prices those leaves from the store
        store = CostStore(d)
        assert store.fit_corrections(min_pairs=1)


# ---------------------------------------------------------------------------
# tools/cost_db.py CLI smoke (tier-1, like ffcheck)
# ---------------------------------------------------------------------------


def run_cli(*args):
    env = dict(os.environ)
    return subprocess.run(
        [sys.executable, COST_DB_CLI, *args],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )


class TestCostDbCLI:
    def _make_store(self, tmp_path) -> str:
        s = CostStore(str(tmp_path), device_kind="cpu:cpu")
        s.put_op(LIN, INS, WS, 1.5, 64)
        s.note_analytic(LIN, INS, WS, 0.5)
        s.put_edge(
            CombineAttrs(0, 4), [pts([16, 32], [4, 1])], intra_view(), 0.25
        )
        s.save()
        t = CostStore(str(tmp_path), device_kind="tpu:TPU v4")
        t.put_op(LIN, INS, None, 0.01)
        t.save()
        return s.path

    def test_stats(self, tmp_path):
        path = self._make_store(tmp_path)
        r = run_cli("stats", path, "--json")
        assert r.returncode == 0, r.stderr[-1500:]
        doc = json.loads(r.stdout)
        assert doc["entries"] == 3
        assert doc["by_kind"] == {"movement": 1, "op": 2}
        assert doc["by_device_kind"] == {"cpu:cpu": 2, "tpu:TPU v4": 1}
        assert doc["by_op_class"] == {"LinearAttrs": 2}
        assert doc["analytic_pairs"] == 1

    def test_stats_accepts_directory(self, tmp_path):
        self._make_store(tmp_path)
        r = run_cli("stats", str(tmp_path), "--json")
        assert r.returncode == 0, r.stderr[-1500:]
        assert json.loads(r.stdout)["entries"] == 3

    def test_verify_ok_and_exit1_on_bad_values(self, tmp_path):
        path = self._make_store(tmp_path)
        assert run_cli("verify", path).returncode == 0
        data = json.load(open(path))
        k = next(iter(data["entries"]))
        data["entries"][k] = dict(data["entries"][k], ms=float("nan")) if (
            isinstance(data["entries"][k], dict)
        ) else float("nan")
        # json.dump writes the non-standard NaN literal Python reads back
        with open(path, "w") as f:
            json.dump(data, f)
        r = run_cli("verify", path)
        assert r.returncode == 1
        assert "finite" in r.stderr

    def test_verify_flags_inconsistent_movement_bytes(self, tmp_path):
        """ISSUE 11 satellite: a movement entry whose recorded bytes
        disagree with the movement_edge_key shape/dtype-derived bytes is
        a corrupted or hand-edited key — its measurement would be served
        for the WRONG tensor size — and verify exits 1 naming both."""
        path = self._make_store(tmp_path)
        data = json.load(open(path))
        bad_key = None
        for k in data["entries"]:
            if k.startswith("move|"):
                parts = k.split("|")
                parts[2] = "9999"  # recorded bytes no longer match shape
                bad_key = "|".join(parts)
                data["entries"][bad_key] = data["entries"].pop(k)
                break
        assert bad_key is not None
        with open(path, "w") as f:
            json.dump(data, f)
        r = run_cli("verify", path)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "disagree" in r.stderr and "9999" in r.stderr

    def test_verify_skips_unparsable_and_legacy_movement_keys(self, tmp_path):
        """Keys without a parsable shape signature (legacy migrants,
        empty-input edges) are the schema screen's business, not the
        bytes screen's — they must not false-positive."""
        path = str(tmp_path / "mv.json")
        with open(path, "w") as f:
            json.dump(
                {
                    "schema": 2,
                    "entries": {
                        "legacy1|Combine|64|x|v": 0.5,
                        "ReplicateAttrs|0||MachineView()|cpu:cpu": 0.1,
                    },
                },
                f,
            )
        assert run_cli("verify", path).returncode == 0

    def test_verify_rejects_unknown_schema(self, tmp_path):
        path = str(tmp_path / "s.json")
        with open(path, "w") as f:
            json.dump({"schema": 99, "entries": {"k": 1.0}}, f)
        r = run_cli("verify", path)
        assert r.returncode == 1
        assert "schema" in r.stderr

    def test_prune_device_kind(self, tmp_path):
        path = self._make_store(tmp_path)
        r = run_cli("prune", path, "--device-kind", "tpu:TPU v4")
        assert r.returncode == 0, r.stderr[-1500:]
        data = json.load(open(path))
        assert len(data["entries"]) == 2
        assert all(
            (e.get("device_kind") if isinstance(e, dict) else None)
            != "tpu:TPU v4"
            for e in data["entries"].values()
        )

    def test_prune_legacy_schema_migrants(self, tmp_path):
        # a migrated v1 movement table: legacy entries prune away
        path = str(tmp_path / "mv.json")
        with open(path, "w") as f:
            json.dump({"schema": 1, "entries": {"Combine|64|x|v": 0.5}}, f)
        s = MovementCostStore(path)
        s.put("Combine|64|x|v|cpu:cpu", 0.25)
        s.save()
        r = run_cli("prune", path, "--older-than-schema", "2")
        assert r.returncode == 0, r.stderr[-1500:]
        data = json.load(open(path))
        assert list(data["entries"]) == ["Combine|64|x|v|cpu:cpu"]

    def test_prune_requires_a_criterion(self, tmp_path):
        path = self._make_store(tmp_path)
        assert run_cli("prune", path).returncode == 2

    def _make_v3_movement_store(self, tmp_path) -> str:
        path = str(tmp_path / "mv3.json")
        s = MovementCostStore(path)
        s.put("CombineAttrs|64|x|v|cpu:cpu|ici", 0.25)
        s.put("CombineAttrs|64|x|v|cpu:cpu|dcn", 2.5)
        s.save()
        return path

    def test_stats_link_class_census(self, tmp_path):
        """ISSUE 17 satellite: stats reports the per-link-class census of
        live v3 movement entries."""
        path = self._make_v3_movement_store(tmp_path)
        r = run_cli("stats", path, "--json")
        assert r.returncode == 0, r.stderr[-1500:]
        assert json.loads(r.stdout)["by_link_class"] == {"dcn": 1, "ici": 1}

    def test_verify_flags_unknown_link_class_on_v3(self, tmp_path):
        """A live v3 movement key without a known trailing link class
        would be served for BOTH interconnects — verify exits 1."""
        path = self._make_v3_movement_store(tmp_path)
        assert run_cli("verify", path).returncode == 0
        data = json.load(open(path))
        data["entries"]["CombineAttrs|64|x|v|cpu:cpu"] = 0.5
        with open(path, "w") as f:
            json.dump(data, f)
        r = run_cli("verify", path)
        assert r.returncode == 1
        assert "link class" in r.stderr

    def test_prune_link_class(self, tmp_path):
        path = self._make_v3_movement_store(tmp_path)
        r = run_cli("prune", path, "--link-class", "dcn")
        assert r.returncode == 0, r.stderr[-1500:]
        data = json.load(open(path))
        assert list(data["entries"]) == ["CombineAttrs|64|x|v|cpu:cpu|ici"]
        # an unknown class is a usage error, not a silent no-op
        assert run_cli("prune", path, "--link-class", "nvl").returncode == 2

    def _make_family_store(self, tmp_path) -> str:
        """One fwd+bwd training entry and one forward-only serving entry
        (cost_store.forward_fingerprint's `-fwd` family) for the same op
        on the same device kind — two keys, two families."""
        from flexflow_tpu.compiler.cost_store import forward_fingerprint

        s = CostStore(str(tmp_path), device_kind="cpu:cpu")
        s.put_op(LIN, INS, WS, 1.5, 64)
        s.save()
        f = CostStore(
            str(tmp_path),
            device_kind="cpu:cpu",
            fingerprint=forward_fingerprint(),
        )
        f.put_op(LIN, INS, WS, 0.3, 64)
        f.save()
        return s.path

    def test_stats_forward_family_census(self, tmp_path):
        """ISSUE 19 satellite: `-fwd`-fingerprinted serving entries are
        censused apart from the training op population — the two
        families price different quantities."""
        path = self._make_family_store(tmp_path)
        r = run_cli("stats", path, "--json")
        assert r.returncode == 0, r.stderr[-1500:]
        doc = json.loads(r.stdout)
        assert doc["entries"] == 2
        assert doc["by_op_family"] == {"fwd": 1, "train": 1}
        assert doc["by_op_class"] == {"LinearAttrs": 1}
        assert doc["by_op_class_fwd"] == {"LinearAttrs": 1}

    def test_prune_family(self, tmp_path):
        path = self._make_family_store(tmp_path)
        r = run_cli("prune", path, "--family", "fwd")
        assert r.returncode == 0, r.stderr[-1500:]
        data = json.load(open(path))
        assert len(data["entries"]) == 1
        assert all("-fwd|" not in k for k in data["entries"])
        # pruning the other family empties the op census
        r = run_cli("prune", path, "--family", "train")
        assert r.returncode == 0, r.stderr[-1500:]
        assert json.load(open(path))["entries"] == {}
        # an unknown family is a usage error (argparse choices)
        assert run_cli("prune", path, "--family", "serve").returncode == 2
