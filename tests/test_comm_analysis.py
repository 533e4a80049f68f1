"""Static communication verification (ISSUE 11): HLO collective
extraction, movement-edge prediction export, the census cross-check
(COMM001-COMM004), the ffcheck --comm CLI contract, and the compile-time
winner verification."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FFCHECK = os.path.join(REPO, "tools", "ffcheck.py")

from flexflow_tpu.analysis.comm_analysis import (  # noqa: E402
    COMM_RULE_IDS,
    comm_diagnostics,
    comm_summary_json,
    cross_check_comm,
    extract_collectives,
    format_comm_table,
    trailing_reshard_nodes,
    verify_comm,
)
from flexflow_tpu.analysis.diagnostics import Severity  # noqa: E402
from flexflow_tpu.compiler.machine_mapping.movement_export import (  # noqa: E402
    export_movement_predictions,
)
from flexflow_tpu.op_attrs.datatype import DataType  # noqa: E402
from flexflow_tpu.op_attrs.parallel_tensor_shape import (  # noqa: E402
    ParallelTensorDims,
    ParallelTensorShape,
    ShardParallelDim,
)
from flexflow_tpu.pcg.machine_view import MachineSpecification  # noqa: E402
from flexflow_tpu.pcg.parallel_computation_graph_builder import (  # noqa: E402
    ParallelComputationGraphBuilder,
)

SPEC8 = MachineSpecification(1, 1, 8, 1.0, 2.0)


def pts(dims, sum_degree=1, copy=1):
    return ParallelTensorShape(
        ParallelTensorDims(
            tuple(ShardParallelDim(s, d) for s, d in dims), sum_degree, copy
        ),
        DataType.FLOAT,
    )


def rule_ids(diags):
    return {d.rule_id for d in diags}


def test_catalog_covers_comm_rules():
    from flexflow_tpu.analysis.pcg_verify import PCG_RULE_CATALOG

    assert COMM_RULE_IDS == ("COMM001", "COMM002", "COMM003", "COMM004")
    for rid in COMM_RULE_IDS:
        assert rid in PCG_RULE_CATALOG


def errors_only(diags):
    return [d for d in diags if d.severity == Severity.ERROR]


# ---------------------------------------------------------------------------
# HLO collective extraction
# ---------------------------------------------------------------------------


HLO_SAMPLE = """\
HloModule jit__step

%fused_computation (p0: f32[8,16,64]) -> f32[8,16,64] {
  ROOT %r = f32[8,16,64]{2,1,0} parameter(0)
}

ENTRY %main {
  %ag = f32[16,16,64]{2,1,0} all-gather(f32[8,16,64]{2,1,0} %p0), channel_id=1, replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}, use_global_device_ids=true, metadata={op_name="jit(_step)/jit(main)/add" source_file="/repo/kernels/ops.py" source_line=42}
  %ar = f32[64,256]{1,0} all-reduce(f32[64,256]{1,0} %dot.1), channel_id=2, replica_groups={{0,4},{1,5},{2,6},{3,7}}, use_global_device_ids=true, to_apply=%add.1
  %rs = bf16[8,64]{1,0} reduce-scatter(bf16[64,64]{1,0} %x), channel_id=3, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, to_apply=%add.2
  %cp = f32[64,16,1]{1,0,2} collective-permute(f32[64,16,1]{1,0,2} %s), channel_id=4, source_target_pairs={{0,0},{1,2},{2,4},{3,6},{4,1},{5,3},{6,5},{7,7}}
  %cpid = f32[64,16,1]{1,0,2} collective-permute(f32[64,16,1]{1,0,2} %s2), channel_id=5, source_target_pairs={{0,0},{1,1}}
  %a2a = f32[4,4]{1,0} all-to-all(f32[4,4]{1,0} %y), channel_id=6, replica_groups={{0,1,2,3}}, dimensions={0}
  %solo = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} %z), channel_id=7, replica_groups={{0}}, to_apply=%add.3
  %cc = f32[4,4]{1,0} custom-call(f32[4,4]{1,0} %w), custom_call_target="Sharding"
  %cb = f32[4,4]{1,0} custom-call(f32[4,4]{1,0} %w2), custom_call_target="xla_python_cpu_callback", metadata={op_name="jit(_step)/callback"}
  %of = token[] outfeed(f32[2,2]{1,0} %v, token[] %tok)
}
"""


class TestExtractCollectives:
    def test_kinds_bytes_groups(self):
        cs = extract_collectives(HLO_SAMPLE)
        by_name = {c.name: c for c in cs}
        ag = by_name["ag"]
        assert ag.kind == "all-gather"
        assert ag.bytes == 16 * 16 * 64 * 4
        assert ag.group_size == 2  # iota [4,2]: 4 groups of 2
        assert ag.op_name.endswith("add")
        assert ag.source == "ops.py:42"
        ar = by_name["ar"]
        assert ar.kind == "all-reduce"
        assert ar.bytes == 64 * 256 * 4
        assert ar.group_size == 2  # explicit {{0,4},...}
        rs = by_name["rs"]
        assert rs.kind == "reduce-scatter"
        assert rs.bytes == 8 * 64 * 2  # bf16
        assert rs.group_size == 8
        cp = by_name["cp"]
        assert cp.kind == "collective-permute"
        assert cp.bytes == 64 * 16 * 4
        assert by_name["a2a"].kind == "all-to-all"

    def test_skips_noop_forms(self):
        names = {c.name for c in extract_collectives(HLO_SAMPLE)}
        assert "cpid" not in names  # identity permute moves nothing
        assert "solo" not in names  # single-participant group
        assert "cc" not in names  # partitioning custom-call

    def test_async_start_counts_destination_only(self):
        """An async `-start` result tuple carries the operand alias (and
        context scalars) beside the destination; only the largest
        element — the destination — is the materialized unit, and the
        `-done` half is never double-counted."""
        hlo = (
            "ENTRY %main {\n"
            "  %ags = (f32[8,64]{1,0}, f32[64,64]{1,0}) all-gather-start("
            "f32[8,64]{1,0} %p), channel_id=1, "
            "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n"
            "  %agd = f32[64,64]{1,0} all-gather-done("
            "(f32[8,64]{1,0}, f32[64,64]{1,0}) %ags)\n"
            "}\n"
        )
        (c,) = extract_collectives(hlo)
        assert c.kind == "all-gather"
        assert c.bytes == 64 * 64 * 4  # destination, not operand+dest

    def test_empty_replica_groups_means_all_devices(self):
        """HLO's replica-mode `replica_groups={}` form means ONE group of
        every device — a real full-mesh collective, never skipped."""
        hlo = (
            "ENTRY %main {\n"
            "  %ar = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %p), "
            "channel_id=1, replica_groups={}, to_apply=%add\n"
            "}\n"
        )
        (c,) = extract_collectives(hlo)
        assert c.kind == "all-reduce"
        assert c.group_size == 0  # 0 = all devices
        assert c.bytes == 64 * 64 * 4

    def test_host_transfers(self):
        hosts = [
            c
            for c in extract_collectives(HLO_SAMPLE)
            if c.kind == "host-transfer"
        ]
        targets = {c.target for c in hosts}
        assert "xla_python_cpu_callback" in targets
        assert "outfeed" in targets

    def test_pure_callback_program_detected(self):
        """A real jitted program containing a host callback lowers to a
        custom-call the extractor classifies as a host transfer."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        def f(v):
            r = jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
                v,
            )
            return r * 2

        txt = jax.jit(f).lower(jnp.ones((4, 4))).compile().as_text()
        hosts = [
            c for c in extract_collectives(txt) if c.kind == "host-transfer"
        ]
        assert hosts, "callback custom-call not detected"


# ---------------------------------------------------------------------------
# movement-edge prediction export
# ---------------------------------------------------------------------------


def _chain_pcg():
    """x -> Repartition(8) -> dense -> Replicate-on-nothing... a small
    PCG exercising input-chain, weight-resident, and trailing flags."""
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([(128, 1), (64, 1)]), name="x")
    xs = b.parallel_partition(x, dim=0, degree=8, name="dp")
    h = b.dense(xs, 32, use_bias=False, name="ff")
    b.parallel_combine(h, dim=0, degree=8, name="gather")
    return b.graph


class TestMovementExport:
    def test_export_fields(self):
        pcg = _chain_pcg()
        preds = export_movement_predictions(pcg, None, machine_spec=SPEC8)
        by_name = {p.name: p for p in preds}
        dp = by_name["dp"]
        assert dp.kind == "RepartitionAttrs"
        assert dp.degree == 8
        assert dp.bytes_global == 128 * 64 * 4
        assert dp.input_chain  # moves the host-fed input
        assert not dp.weight_resident
        assert dp.predicted_ms is not None and dp.predicted_ms > 0
        assert dp.templates  # gather-class bwd grad gather
        g = by_name["gather"]
        assert g.kind == "CombineAttrs"
        assert not g.input_chain
        assert g.predicted_bytes == g.bytes_global

    def test_trailing_reshard_nodes(self):
        pcg = _chain_pcg()
        bypassed = trailing_reshard_nodes(pcg)
        preds = export_movement_predictions(pcg, None, machine_spec=SPEC8)
        gather = next(p for p in preds if p.name == "gather")
        assert gather.node_idx in bypassed
        dp = next(p for p in preds if p.name == "dp")
        assert dp.node_idx not in bypassed


# ---------------------------------------------------------------------------
# negative paths: one per COMM rule id
# ---------------------------------------------------------------------------


@pytest.mark.filterwarnings("ignore")
class TestCommRules:
    def test_comm001_overeager_replication(self):
        """The seeded over-eager-replication fixture (COMM_r12.json): a
        hand-built dp plan whose weight replication is implicit (no
        Replicate movement edge), so XLA's per-step weight-gradient
        all-reduce is unpredicted — COMM001 names the collective and its
        bytes."""
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(pts([(128, 1), (64, 1)]), name="x")
        xs = b.parallel_partition(x, dim=0, degree=8, name="dp_shard")
        h = b.dense(xs, 256, use_bias=False, name="ff")
        b.parallel_combine(h, dim=0, degree=8, name="unshard")
        analysis, diags = verify_comm(b.graph, None, machine_spec=SPEC8)
        comm001 = [d for d in diags if d.rule_id == "COMM001"]
        assert comm001, [str(d) for d in diags]
        assert comm001[0].severity == Severity.ERROR
        # the structured diagnostic names the collective and the bytes
        assert "all-reduce" in comm001[0].message
        assert "64.00 KiB" in comm001[0].message
        assert analysis.unmatched

    def test_comm002_dced_movement_edge(self):
        """A mid-network Replicate of an already-replicated activation:
        priced as broadcast + grad all-reduce, lowers to nothing — the
        search overpaid (COMM002 names the edge chain)."""
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(pts([(128, 1), (64, 1)]), name="x")
        h = b.dense(x, 256, use_bias=False, name="ff")
        r = b.parallel_replicate(h, 2, name="over_replicate")
        b.relu(r, name="act")
        analysis, diags = verify_comm(b.graph, None, machine_spec=SPEC8)
        comm002 = [d for d in diags if d.rule_id == "COMM002"]
        assert comm002, [str(d) for d in diags]
        assert "over_replicate" in comm002[0].message
        # truly nothing lowered but the gather of the weight's compute copy
        # (its master is stored cut over the 8 devices that replicate it),
        # which the census holds to the update rule and not to this edge
        assert analysis.weight_gathers == analysis.collectives
        assert [c.bytes for c in analysis.collectives] == [64 * 256 * 4]
        assert analysis.weight_gather_bytes == 64 * 256 * 4

    def test_comm003_bytes_band(self):
        """A synthetic census whose only realization is far smaller than
        the prediction trips the band warning (and only a warning) on a
        non-exempt mid-network edge."""
        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(pts([(128, 1), (64, 1)]), name="x")
        h = b.dense(x, 256, use_bias=False, name="ff")
        r = b.parallel_replicate(h, 2, name="over_replicate")
        b.relu(r, name="act")
        preds = export_movement_predictions(b.graph, None, machine_spec=SPEC8)
        hlo = (
            "ENTRY %main {\n"
            "  %ar = f32[16,64]{1,0} all-reduce(f32[16,64]{1,0} %p), "
            "channel_id=1, replica_groups={{0,1},{2,3},{4,5},{6,7}}, "
            "to_apply=%add\n}\n"
        )
        analysis = cross_check_comm(
            preds,
            extract_collectives(hlo),
            bypassed_nodes=trailing_reshard_nodes(b.graph),
            band=2.0,
        )
        diags = comm_diagnostics(analysis)
        comm003 = [d for d in diags if d.rule_id == "COMM003"]
        assert comm003, [str(d) for d in diags]
        assert all(d.severity == Severity.WARNING for d in comm003)
        assert "over_replicate" in comm003[0].message

    def test_comm004_host_transfer(self):
        """A host callback inside the step program is an error naming
        the custom-call target."""
        pcg = _chain_pcg()
        preds = export_movement_predictions(pcg, None, machine_spec=SPEC8)
        hlo = (
            "ENTRY %main {\n"
            '  %cb = f32[128,64]{1,0} custom-call(f32[128,64]{1,0} %w), '
            'custom_call_target="xla_python_cpu_callback"\n}\n'
        )
        analysis = cross_check_comm(preds, extract_collectives(hlo))
        diags = comm_diagnostics(analysis)
        comm004 = [d for d in diags if d.rule_id == "COMM004"]
        assert comm004 and comm004[0].severity == Severity.ERROR
        assert "xla_python_cpu_callback" in comm004[0].message

    def test_clean_dp_seed_template(self):
        """The canonical dp8 seed template (declared weight Replicates,
        input Repartition, trailing Combine) cross-checks clean: every
        gradient all-reduce is accounted for, nothing is unpredicted,
        no priced edge is DCE'd."""
        from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds
        from flexflow_tpu.pcg import ComputationGraphBuilder
        from flexflow_tpu.pcg.parallel_computation_graph import (
            pcg_from_computation_graph,
        )

        b = ComputationGraphBuilder()
        x = b.create_input([64, 32], name="x")
        h = b.dense(x, 64, name="fc1")
        h = b.relu(h)
        b.dense(h, 8, name="fc2")
        pcg = pcg_from_computation_graph(b.graph)
        seed = dict(enumerate_seeds(pcg, 8))["dp8xtp1xsp1"]
        analysis, diags = verify_comm(seed, None, machine_spec=SPEC8)
        assert not errors_only(diags), [str(d) for d in diags]
        # the dp plan's weight grad syncs really are in the program and
        # really were matched to the declared weight Replicate edges
        assert any(
            e.matched_bytes > 0 and e.prediction.weight_resident
            for e in analysis.edges
        )


# ---------------------------------------------------------------------------
# pipelined census (ISSUE 13 satellite): forced 2-stage fixture
# ---------------------------------------------------------------------------


@pytest.mark.filterwarnings("ignore")
class TestPipelinedCensus:
    """The microbatch collective-permute chain pattern: a 1F1B step
    lowers EVERY inter-stage boundary through one ppermute per microbatch
    tick, so M fwd + M bwd collective-permutes must all claim against the
    boundary's single priced prediction (pooled as ONE chain group, like
    composed reshards) — otherwise COMM001 flags the repeats as
    unpredicted traffic and COMM002 flags the edge as under-realized.

    One device per stage (2-device spec): the bare fixture declares no
    in-stage Replicate edges, so any in-stage replication would add
    weight-grad all-reduces the predictions don't model — the searched
    winners the bench verifies carry those edges explicitly."""

    SPEC2 = MachineSpecification(1, 1, 2, 1.0, 2.0)

    # microbatch hop = (B/M, d) activations = 16 KiB, comfortably above
    # the census bytes floor so the control test below is meaningful
    def _pipelined_pcg(self, S=2, M=4, L=4, d=256, B=64):
        from flexflow_tpu.op_attrs.activation import Activation
        from flexflow_tpu.pcg.pipeline import insert_pipeline_stages

        b = ParallelComputationGraphBuilder()
        x = b.create_input_tensor(pts([(B, 1), (d, 1)]), name="x")
        h = x
        for i in range(L):
            h = b.dense(h, d, activation=Activation.RELU, name=f"l{i}")
        return insert_pipeline_stages(b.graph, S, M)

    def test_forced_two_stage_fixture_is_clean(self):
        M = 4
        pcg = self._pipelined_pcg(M=M)
        analysis, diags = verify_comm(pcg, None, machine_spec=self.SPEC2)
        assert not errors_only(diags), [str(d) for d in diags]
        stage = [
            e
            for e in analysis.edges
            if e.prediction.kind
            in ("StagePartitionAttrs", "StageMergeAttrs")
        ]
        assert stage, "stage movement edges must be exported"
        # one COMM002 unit: every stage-boundary edge shares a chain group
        assert len({e.group for e in stage}) == 1
        # exactly one PRICED inter-stage edge (entry partition and the
        # merge are local slicing, priced zero)
        interior = [e for e in stage if e.prediction.predicted_bytes > 0]
        assert len(interior) == 1
        # the M-repeat permute chain claimed against that single
        # prediction: at least one fwd + one bwd hop per microbatch
        assert interior[0].matched_count >= 2 * M
        assert interior[0].matched_bytes > 0

    def test_unpredicted_permutes_without_stage_edges_flagged(self):
        """Control for the matcher: the same unrolled 1F1B program
        cross-checked against predictions that OMIT the stage edges must
        fail the census — proving the clean verdict above comes from the
        chain matching, not from permutes being invisible."""
        pcg = self._pipelined_pcg()
        predictions = [
            p
            for p in export_movement_predictions(
                pcg, None, machine_spec=self.SPEC2
            )
            if p.kind not in ("StagePartitionAttrs", "StageMergeAttrs")
        ]
        from flexflow_tpu.analysis.lowering import lower_plan

        hlo = lower_plan(pcg, None, machine_spec=self.SPEC2).hlo_text()
        analysis = cross_check_comm(
            predictions,
            extract_collectives(hlo),
            bypassed_nodes=trailing_reshard_nodes(pcg),
        )
        diags = comm_diagnostics(analysis)
        assert any(d.rule_id == "COMM001" for d in errors_only(diags)), [
            str(d) for d in diags
        ]


# ---------------------------------------------------------------------------
# ffcheck --comm CLI (schema + exit-code contract)
# ---------------------------------------------------------------------------


# the frozen --comm --json summary schema (v1): field tuple pinned like
# the JSONL v1 and --memory contracts — extending it requires a new key,
# never a silent rename
COMM_SUMMARY_FIELDS = (
    "band",
    "bytes_floor",
    "bytes_geomean",
    "census",
    "comm",
    "edges",
    "host_transfers",
    "matched_bytes_total",
    "num_collectives",
    "num_edges",
    "predicted_bytes_total",
    "slack",
    "unmatched",
    "unmatched_bytes",
    "unmatched_collectives",
    "weight_gathers",
)

COMM_EDGE_FIELDS = (
    "bytes",
    "bytes_ratio",
    "degree",
    "exempt",
    "fused_kind",
    "input_chain",
    "kind",
    "link_class",
    "matched_bytes",
    "matched_collectives",
    "name",
    "node",
    "predicted_bytes",
    "predicted_ms",
    "realized_bytes",
    "weight_resident",
)


def test_comm_summary_schema_frozen():
    pcg = _chain_pcg()
    analysis, _ = verify_comm(pcg, None, machine_spec=SPEC8)
    s = comm_summary_json(analysis)
    assert s["comm"] == 1  # schema version
    assert tuple(sorted(s.keys())) == COMM_SUMMARY_FIELDS
    assert s["edges"]
    assert tuple(sorted(s["edges"][0].keys())) == COMM_EDGE_FIELDS
    # the table renderer covers the same analysis without crashing
    assert "collective census" in format_comm_table(analysis)


def _write_graph(tmp_path, name, pcg):
    from flexflow_tpu.pcg.file_format import pcg_to_json

    p = tmp_path / name
    p.write_text(pcg_to_json(pcg))
    return str(p)


@pytest.mark.filterwarnings("ignore")
def test_ffcheck_comm_cli(tmp_path):
    """--comm: exit 1 + structured COMM diagnostics + one JSON summary
    object per file on the over-eager fixture; exit 0 on a clean dp
    seed template."""
    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([(128, 1), (64, 1)]), name="x")
    xs = b.parallel_partition(x, dim=0, degree=8, name="dp_shard")
    h = b.dense(xs, 256, use_bias=False, name="ff")
    b.parallel_combine(h, dim=0, degree=8, name="unshard")
    bad = _write_graph(tmp_path, "overeager.json", b.graph)

    proc = subprocess.run(
        [sys.executable, FFCHECK, "--comm", "--json", bad],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
    diag_ids = {d["rule_id"] for d in lines if "rule_id" in d}
    assert "COMM001" in diag_ids
    summaries = [d for d in lines if "comm" in d]
    assert len(summaries) == 1
    s = summaries[0]
    assert s["comm"] == 1
    assert s["path"] == bad
    assert s["unmatched_collectives"] >= 1
    assert tuple(sorted(k for k in s if k != "path")) == COMM_SUMMARY_FIELDS

    from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    cb = ComputationGraphBuilder()
    x = cb.create_input([64, 32], name="x")
    cb.dense(x, 16, name="fc")
    seed = dict(
        enumerate_seeds(pcg_from_computation_graph(cb.graph), 8)
    )["dp8xtp1xsp1"]
    good = _write_graph(tmp_path, "dp8.json", seed)
    proc0 = subprocess.run(
        [sys.executable, FFCHECK, "--comm", "--json", good],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc0.returncode == 0, proc0.stdout + proc0.stderr
    lines0 = [json.loads(l) for l in proc0.stdout.splitlines() if l]
    assert not any("rule_id" in d for d in lines0)
    (s0,) = [d for d in lines0 if "comm" in d]
    assert s0["unmatched_collectives"] == 0


# ---------------------------------------------------------------------------
# compile-time winner verification (search_provenance["comm"])
# ---------------------------------------------------------------------------


@pytest.mark.filterwarnings("ignore")
def test_compile_records_comm_provenance_with_census():
    """A searched compile under --plan-audit records the movement-edge
    predictions AND the lowered-census cross-check in
    search_provenance["comm"] — clean on a forced dp seed — plus the
    census beside the plan audit's movement measurements (one shared
    step compile with the memory cross-check)."""
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    cfg = FFConfig(
        batch_size=64, search_budget=1, plan_audit=True,
        force_strategy_seed="dp8xtp1xsp1",
    )
    m = FFModel(cfg)
    x = m.create_tensor([64, 32], name="x")
    h = m.dense(x, 64, use_bias=False, name="fc1")
    h = m.relu(h)
    m.dense(h, 8, use_bias=False, name="fc2")
    m.compile(SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy")
    prov = m.search_provenance or {}
    comm = prov.get("comm")
    assert comm is not None, prov.keys()
    assert comm["num_edges"] > 0
    assert comm["edges"][0]["kind"].endswith("Attrs")
    # the census cross-check ran off the shared compiled step
    assert comm["comm"] == 1
    assert comm["verify"]["clean"] is True, comm["verify"]
    assert comm["unmatched_collectives"] == 0
    assert comm["host_transfers"] == 0
    # recorded beside the plan audit's movement measurements
    audit_comm = prov["plan_audit"]["comm"]
    assert audit_comm["census"]
    assert audit_comm["unmatched_collectives"] == 0
    # each audited movement edge carries the byte-side prediction too
    edges = prov["plan_audit"]["movement_edges"]
    assert edges and all(
        "predicted_collective_bytes" in e for e in edges
    )
    # the memory cross-check shared the same compile (no second lower)
    assert "xla" in prov["memory"], prov["memory"].get("xla_error")
