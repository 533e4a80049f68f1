"""Execution-contract verification tests (ISSUE 14): the determinism
census + donation/aliasing audit (analysis/exec_contract.py), the
`ffcheck --exec` CLI contract (frozen --json schema + exit codes), the
always-on compile provenance, the resume/recompile DET002 fingerprint
checks on DP + searched-PCG backends, and the serving decode program's
donation-coverage assertion."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FFCHECK = os.path.join(REPO, "tools", "ffcheck.py")

import jax
import jax.numpy as jnp

from flexflow_tpu.analysis.exec_contract import (
    CONTRACT_FILENAME,
    EXEC_RULE_IDS,
    analyze_step_program,
    canonicalize_hlo,
    canonicalize_stablehlo,
    compare_contract_records,
    exec_diagnostics,
    exec_summary_json,
    extract_determinism_findings,
    fingerprint_text,
    read_contract_record,
    verify_exec,
    write_contract_record,
)
from flexflow_tpu.analysis.pcg_verify import PCG_RULE_CATALOG
from flexflow_tpu.pcg import ComputationGraphBuilder
from flexflow_tpu.pcg.parallel_computation_graph import (
    pcg_from_computation_graph,
)

# the frozen `ffcheck --exec --json` summary schema (v1): field tuple
# pinned like the --memory/--comm summaries
EXEC_SUMMARY_FIELDS = (
    "aliased_bytes",
    "aliased_leaves",
    "determinism_by_kind",
    "determinism_findings",
    "donated_bytes",
    "donated_leaves",
    "donation_coverage",
    "dropped_donations",
    "exec",
    "hlo_fingerprint",
    "num_partitions",
    "program_fingerprint",
    "program_key",
    "state_bytes_floor",
    "undonated_state_leaves",
)


def _mlp_seed(label="dp4xtp1xsp2-ring"):
    from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds

    b = ComputationGraphBuilder()
    x = b.create_input([16, 32], name="x")
    h = b.dense(x, 64, use_bias=False, name="fc1")
    h = b.relu(h)
    b.dense(h, 32, use_bias=False, name="fc2")
    pcg = pcg_from_computation_graph(b.graph)
    return dict(enumerate_seeds(pcg, 8))[label]


# ---------------------------------------------------------------------------
# canonicalization + fingerprints
# ---------------------------------------------------------------------------


class TestCanonicalization:
    def test_hlo_metadata_stripped(self):
        """Identical programs from different checkouts (different source
        paths in metadata) must fingerprint identically."""
        a = (
            'HloModule jit__step\n  %x = f32[4]{0} parameter(0), '
            'metadata={op_name="a" source_file="/home/u1/repo/x.py" '
            "source_line=12}\n"
        )
        b = a.replace("/home/u1/repo", "/mnt/other/checkout")
        assert a != b
        assert fingerprint_text(canonicalize_hlo(a)) == fingerprint_text(
            canonicalize_hlo(b)
        )

    def test_stablehlo_loc_stripped(self):
        a = (
            'module @jit__step {\n  %0 = stablehlo.add %a, %b : '
            'tensor<4xf32> loc("/r1/f.py":3:1)\n}\n#loc = loc("/r1/f.py")\n'
        )
        b = a.replace("/r1/", "/somewhere/else/")
        assert fingerprint_text(
            canonicalize_stablehlo(a)
        ) == fingerprint_text(canonicalize_stablehlo(b))

    def test_different_programs_differ(self):
        assert fingerprint_text(canonicalize_hlo("a")) != fingerprint_text(
            canonicalize_hlo("b")
        )


# ---------------------------------------------------------------------------
# DET001 determinism census (seeded HLO text — negative path per form)
# ---------------------------------------------------------------------------


class TestDeterminismCensus:
    def test_rng_default_flagged(self):
        hlo = (
            "  %rng.1 = u32[4]{0} rng-bit-generator(u64[2]{0} %s), "
            "algorithm=rng_default\n"
        )
        (f,) = extract_determinism_findings(hlo)
        assert f.kind == "rng-algorithm"
        assert "rng_default" in f.detail

    def test_rng_philox_flagged_threefry_clean(self):
        def rng_hlo(algo):
            return (
                "  %rng.1 = u32[4]{0} rng-bit-generator(u64[2]{0} %s), "
                f"algorithm={algo}\n"
            )

        assert extract_determinism_findings(rng_hlo("rng_philox"))
        assert extract_determinism_findings(rng_hlo("rng_three_fry")) == []

    def test_tuple_typed_rng_flagged(self):
        """Real lowerings type rng-bit-generator as the (new_state,
        bits) TUPLE — the census must match that form, not only the
        single-typed fixture spelling."""
        hlo = (
            "  %rng.2 = (u64[2]{0}, u32[512]{0}) rng-bit-generator("
            "u64[2]{0} %state), algorithm=rng_default\n"
        )
        (f,) = extract_determinism_findings(hlo)
        assert f.kind == "rng-algorithm"

    def test_legacy_rng_flagged(self):
        hlo = "  %rng.7 = f32[8]{0} rng(f32[] %lo, f32[] %hi), distribution=rng_uniform\n"
        (f,) = extract_determinism_findings(hlo)
        assert f.kind == "rng-algorithm"

    def test_nonunique_float_scatter_flagged(self):
        hlo = (
            "  %scatter.3 = f32[64,16]{1,0} scatter(f32[64,16]{1,0} %a, "
            "s32[8,1]{1,0} %i, f32[8,16]{1,0} %u), update_window_dims={1}, "
            "indices_are_sorted=false, unique_indices=false, "
            "to_apply=%add\n"
        )
        (f,) = extract_determinism_findings(hlo)
        assert f.kind == "nonunique-scatter"

    def test_unique_or_integer_scatter_clean(self):
        unique = (
            "  %scatter.3 = f32[64,16]{1,0} scatter(f32[64,16]{1,0} %a, "
            "s32[8,1]{1,0} %i, f32[8,16]{1,0} %u), unique_indices=true, "
            "to_apply=%add\n"
        )
        integer = (
            "  %scatter.4 = s32[64]{0} scatter(s32[64]{0} %a, "
            "s32[8,1]{1,0} %i, s32[8]{0} %u), unique_indices=false, "
            "to_apply=%add\n"
        )
        sns = (
            "  %select-and-scatter.1 = f32[8,8]{1,0} select-and-scatter("
            "f32[8,8]{1,0} %x, f32[4,4]{1,0} %src, f32[] %init), "
            "to_apply=%add\n"
        )
        assert extract_determinism_findings(unique) == []
        assert extract_determinism_findings(integer) == []
        assert extract_determinism_findings(sns) == []

    def test_channelless_float_reduction_flagged(self):
        hlo = (
            "  %all-reduce.9 = f32[128]{0} all-reduce(f32[128]{0} %g), "
            "replica_groups={}, to_apply=%add\n"
        )
        (f,) = extract_determinism_findings(hlo)
        assert f.kind == "unordered-reduction"
        rs = (
            "  %reduce-scatter.2 = f32[16]{0} reduce-scatter(f32[128]{0} "
            "%g), replica_groups={}, dimensions={0}, to_apply=%add\n"
        )
        (f2,) = extract_determinism_findings(rs)
        assert f2.kind == "unordered-reduction"

    def test_channeled_or_integer_reduction_clean(self):
        with_channel = (
            "  %all-reduce.9 = f32[128]{0} all-reduce(f32[128]{0} %g), "
            "channel_id=3, replica_groups={{0,1,2,3}}, "
            "use_global_device_ids=true, to_apply=%add\n"
        )
        integer = (
            "  %all-reduce.2 = s32[4]{0} all-reduce(s32[4]{0} %g), "
            "replica_groups={}, to_apply=%add\n"
        )
        assert extract_determinism_findings(with_channel) == []
        assert extract_determinism_findings(integer) == []


# ---------------------------------------------------------------------------
# DON001 / DON002 (real compiled programs — negative path per rule id)
# ---------------------------------------------------------------------------


class TestDonationAudit:
    def test_don001_dropped_donation(self):
        """A donated buffer XLA cannot alias (smaller output) trips
        DON001 naming the leaf and its bytes."""

        def truncate(x):
            return x[:2]

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            lo = jax.jit(truncate, donate_argnums=(0,)).lower(
                jnp.zeros((512,))
            )
            compiled = lo.compile()
        analysis = analyze_step_program(
            lo, compiled, arg_names=("x",), expected_inplace=(0,)
        )
        diags = exec_diagnostics(analysis)
        assert [d.rule_id for d in diags] == ["DON001"]
        assert "x" in diags[0].message
        assert analysis.donation_coverage == 0.0

    def test_don001_pruned_donation(self):
        """A donated argument the program never consumes is pruned by
        jax — the donation buys nothing and trips DON001 with the
        pruned note."""

        def ignore(x, y):
            return y * 2.0

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            lo = jax.jit(ignore, donate_argnums=(0,)).lower(
                jnp.zeros((512,)), jnp.zeros((4,))
            )
            compiled = lo.compile()
        analysis = analyze_step_program(
            lo, compiled, arg_names=("x", "y"), expected_inplace=(0,)
        )
        (rec,) = analysis.dropped_donations
        assert not rec.kept
        assert [d.rule_id for d in exec_diagnostics(analysis)] == ["DON001"]

    def test_don002_undonated_state(self):
        """A parameter-update program compiled WITHOUT donation trips
        DON002 for every above-floor state leaf."""

        def update(params, grads):
            return jax.tree_util.tree_map(
                lambda p, g: p - 0.1 * g, params, grads
            )

        p = {"w": jnp.zeros((64, 64)), "tiny": jnp.zeros(())}
        lo = jax.jit(update).lower(p, p)
        compiled = lo.compile()
        analysis = analyze_step_program(
            lo, compiled, arg_names=("params", "grads"),
            expected_inplace=(0,),
        )
        diags = exec_diagnostics(analysis)
        assert [d.rule_id for d in diags] == ["DON002"]
        # the under-floor scalar must NOT be flagged
        assert [r.leaf for r in analysis.undonated_state] == ["params['w']"]

    def test_clean_donated_update(self):
        def update(params, grads):
            return jax.tree_util.tree_map(
                lambda p, g: p - 0.1 * g, params, grads
            )

        p = {"w": jnp.zeros((64, 64))}
        lo = jax.jit(update, donate_argnums=(0,)).lower(p, p)
        compiled = lo.compile()
        analysis = analyze_step_program(
            lo, compiled, arg_names=("params", "grads"),
            expected_inplace=(0,),
        )
        assert exec_diagnostics(analysis) == []
        assert analysis.donation_coverage == 1.0


# ---------------------------------------------------------------------------
# DET002 contract records
# ---------------------------------------------------------------------------


class TestContractRecords:
    REC = {
        "schema": 1,
        "program_key": "k0",
        "hlo_fingerprint": "a" * 64,
        "program_fingerprint": "p" * 64,
        "jax_version": jax.__version__,
    }

    def test_match(self):
        check, diag = compare_contract_records(self.REC, dict(self.REC))
        assert check["match"] is True and diag is None
        assert check["fingerprint_field"] == "hlo_fingerprint"

    def test_drift_trips_det002(self):
        cur = dict(self.REC, hlo_fingerprint="b" * 64)
        check, diag = compare_contract_records(self.REC, cur)
        assert check["match"] is False
        assert diag is not None and diag.rule_id == "DET002"
        assert diag.rule_id in PCG_RULE_CATALOG

    def test_program_change_is_not_drift(self):
        """A different program_key (batch growth, degraded grid) is a
        legitimately different program — recorded, no DET002."""
        cur = dict(self.REC, program_key="k1", hlo_fingerprint="b" * 64)
        check, diag = compare_contract_records(self.REC, cur)
        assert diag is None
        assert check["program_changed"] is True

    def test_falls_back_to_program_fingerprint(self):
        """Trace-only records (DP backends) carry no optimized-HLO
        fingerprint: the comparison uses the strongest field BOTH sides
        have."""
        stored = dict(self.REC, hlo_fingerprint=None)
        check, diag = compare_contract_records(stored, dict(self.REC))
        assert check["fingerprint_field"] == "program_fingerprint"
        assert check["match"] is True and diag is None

    def test_missing_record(self):
        check, diag = compare_contract_records(None, self.REC)
        assert check["match"] is None and diag is None

    def test_file_roundtrip(self, tmp_path):
        d = str(tmp_path)
        write_contract_record(d, self.REC)
        assert read_contract_record(d) == self.REC
        with open(os.path.join(d, CONTRACT_FILENAME), "w") as f:
            f.write("{not json")
        assert read_contract_record(d) is None


# ---------------------------------------------------------------------------
# whole-plan contract (shared lowering) + frozen summary schema
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mlp_contract():
    return verify_exec(_mlp_seed())


class TestPlanContract:
    def test_searched_seed_is_clean(self, mlp_contract):
        analysis, diags = mlp_contract
        assert diags == []
        assert analysis.donation_coverage == 1.0
        assert analysis.determinism == []
        assert analysis.num_partitions == 8
        assert analysis.hlo_fingerprint and analysis.program_fingerprint

    def test_summary_schema_frozen(self, mlp_contract):
        analysis, _ = mlp_contract
        s = exec_summary_json(analysis)
        assert s["exec"] == 1
        assert tuple(sorted(s.keys())) == EXEC_SUMMARY_FIELDS
        assert s["donation_coverage"] == 1.0
        assert s["donated_leaves"] == s["aliased_leaves"] == 3

    def test_catalog_covers_exec_rules(self):
        for rid in EXEC_RULE_IDS:
            assert rid in PCG_RULE_CATALOG
        # ISSUE 19 grew the catalog to 32 verifier rules (TRN001-TRN004);
        # PR 42 took MEM004 out with the dispatch window it judged
        assert len(PCG_RULE_CATALOG) == 31


def test_pipelined_plan_contract():
    """A stage-partitioned pp2m2 plan lowers through the 1F1B executor
    and still honors the donation contract (stacked per-stage params
    aliased through the shard_map/scan program)."""
    from flexflow_tpu.pcg.pipeline import insert_pipeline_stages

    b = ComputationGraphBuilder()
    x = b.create_input([8, 16], name="x")
    h = x
    for i in range(4):
        h = b.dense(h, 16, name=f"fc{i}")
    pcg = pcg_from_computation_graph(b.graph)
    pp = insert_pipeline_stages(pcg, num_stages=2, num_microbatches=2)
    analysis, diags = verify_exec(pp)
    assert diags == []
    assert analysis.donation_coverage == 1.0


# ---------------------------------------------------------------------------
# ffcheck --exec CLI (frozen schema + exit codes)
# ---------------------------------------------------------------------------


def test_ffcheck_exec_cli(tmp_path):
    """--exec: exit 0 + one JSON summary object (frozen schema) on a
    clean dp8 seed; FFC000 + exit 1 on an unparsable file."""
    from flexflow_tpu.pcg.file_format import pcg_to_json

    good = tmp_path / "dp8.json"
    good.write_text(pcg_to_json(_mlp_seed("dp8xtp1xsp1")))
    proc = subprocess.run(
        [sys.executable, FFCHECK, "--exec", "--json", str(good)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
    assert not any("rule_id" in d for d in lines)
    (s,) = [d for d in lines if "exec" in d]
    assert s["exec"] == 1
    assert s["path"] == str(good)
    assert tuple(sorted(k for k in s if k != "path")) == EXEC_SUMMARY_FIELDS
    assert s["donation_coverage"] == 1.0
    assert s["determinism_findings"] == []

    bad = tmp_path / "broken.json"
    bad.write_text("{not json")
    proc1 = subprocess.run(
        [sys.executable, FFCHECK, "--exec", "--json", str(bad)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc1.returncode == 1
    ids = {
        json.loads(l)["rule_id"]
        for l in proc1.stdout.splitlines()
        if l and "rule_id" in l
    }
    assert ids == {"FFC000"}


def test_ffcheck_comm_exec_unlowerable_reports_one_error(
    tmp_path, monkeypatch
):
    """--comm --exec on a plan whose shared lowering fails: ONE FFC000
    for the one root cause, not one per requesting flag."""
    import argparse

    from flexflow_tpu.pcg.file_format import pcg_to_json

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import ffcheck as ffcheck_mod

    import flexflow_tpu.analysis.lowering as lowering_mod

    def boom(*a, **k):
        raise RuntimeError("seeded lowering failure")

    monkeypatch.setattr(lowering_mod, "lower_plan", boom)
    f = tmp_path / "dp8.json"
    f.write_text(pcg_to_json(_mlp_seed("dp8xtp1xsp1")))
    args = argparse.Namespace(
        comm=True, memory=False, serving=False, nodes=1,
        devices_per_node=8, bytes_floor=4096, json=True,
        **{"exec": True},
    )
    diags = ffcheck_mod.check_file(str(f), args)
    ffc = [d for d in diags if d.rule_id == "FFC000"]
    assert len(ffc) == 1, diags
    assert "seeded lowering failure" in ffc[0].message


# ---------------------------------------------------------------------------
# compile-time provenance (always-on) + resume/recompile e2e
# ---------------------------------------------------------------------------


def _small_model(cfg):
    from flexflow_tpu.core import AdamOptimizer, FFModel

    m = FFModel(cfg)
    x = m.create_tensor([16, 32], name="x")
    h = m.dense(x, 64, name="fc1")
    h = m.relu(h)
    m.dense(h, 32, name="fc2")
    m.compile(AdamOptimizer(alpha=1e-3), "sparse_categorical_crossentropy")
    return m


def _xy(n=64):
    rng = np.random.RandomState(0)
    return (
        rng.randn(n, 32).astype(np.float32),
        rng.randint(0, 32, (n,)).astype(np.int32),
    )


@pytest.mark.filterwarnings("ignore")
class TestCompileProvenance:
    def test_searched_compile_records_exec_contract(self):
        """FFModel.compile ALWAYS runs the pass on the searched winner —
        no --plan-audit needed."""
        from flexflow_tpu.core import FFConfig

        m = _small_model(FFConfig(batch_size=16, search_budget=2))
        rec = m.search_provenance["exec"]
        assert rec["verify"]["clean"] is True
        assert rec["donation_coverage"] == 1.0
        assert rec["hlo_fingerprint"] and rec["program_fingerprint"]
        assert rec["determinism_findings"] == []

    def test_env_off_switch_records_skip(self, monkeypatch):
        from flexflow_tpu.core import FFConfig

        monkeypatch.setenv("FF_TPU_NO_EXEC_CONTRACT", "1")
        m = _small_model(FFConfig(batch_size=16, search_budget=2))
        assert m.search_provenance["exec"] == {
            "skipped": "FF_TPU_NO_EXEC_CONTRACT=1"
        }

    def test_unchanged_recompile_matches_bitwise(self):
        from flexflow_tpu.core import FFConfig

        m = _small_model(FFConfig(batch_size=16, search_budget=2))
        m.recompile()
        check = m.search_provenance["exec"]["recompile_check"]
        assert check["match"] is True
        assert check["fingerprint_field"] == "hlo_fingerprint"

    def test_fit_compiles_the_searched_step_once(self):
        """The step hands its state back under the shardings it arrived
        with: left to XLA, an equal layout spelled differently
        (`PartitionSpec()` for `PartitionSpec(None,)`) re-keys jit's cache
        and the second step of every fit recompiles the whole program."""
        from flexflow_tpu.core import FFConfig

        m = _small_model(FFConfig(batch_size=16, search_budget=2))
        X, Y = _xy()
        m.fit(X, Y, epochs=1, batch_size=16, verbose=False)  # 4 steps
        assert m.instance.compiled_step()._cache_size() == 1

    def test_fingerprint_independent_of_call_site(self):
        """XLA prints the Python call stack (file names with the checkout
        path, function names) in the HLO module header: the same searched
        program compiled from two call sites must keep one fingerprint."""
        from flexflow_tpu.core import FFConfig

        def another_call_site():
            return _small_model(FFConfig(batch_size=16, search_budget=2))

        a = _small_model(FFConfig(batch_size=16, search_budget=2))
        b = another_call_site()
        rec_a, rec_b = (m.search_provenance["exec"] for m in (a, b))
        assert rec_a["hlo_fingerprint"] == rec_b["hlo_fingerprint"]
        assert rec_a["program_fingerprint"] == rec_b["program_fingerprint"]


@pytest.mark.filterwarnings("ignore")
class TestResumeContract:
    """DET002's resume half on both backends: the contract is persisted
    beside the checkpoints and re-verified under fit(resume=True)."""

    def _roundtrip(self, cfg_factory, tmp_path):
        from flexflow_tpu.core import FFConfig

        d = str(tmp_path)
        X, Y = _xy()
        m = _small_model(cfg_factory())
        m.fit(X, Y, epochs=1, batch_size=16, checkpoint_dir=d,
              checkpoint_every_n_steps=2)
        assert os.path.exists(os.path.join(d, CONTRACT_FILENAME))
        m2 = _small_model(cfg_factory())
        m2.fit(X, Y, epochs=2, batch_size=16, checkpoint_dir=d, resume=True)
        assert m2.exec_resume_check["match"] is True
        # tampered contract: the mismatch is detected and recorded
        rec = read_contract_record(d)
        rec["program_fingerprint"] = "0" * 64
        rec["hlo_fingerprint"] = None
        write_contract_record(d, rec)
        m3 = _small_model(cfg_factory())
        m3.fit(X, Y, epochs=3, batch_size=16, checkpoint_dir=d, resume=True)
        assert m3.exec_resume_check["match"] is False
        assert m3.exec_resume_check["diagnostic"]["rule_id"] == "DET002"
        return m2

    def test_dp_backend(self, tmp_path):
        from flexflow_tpu.core import FFConfig

        m2 = self._roundtrip(
            lambda: FFConfig(batch_size=16, search_budget=0), tmp_path
        )
        # DP records no search provenance; the check lives on the model
        assert m2.search_provenance is None
        assert m2.exec_resume_check["fingerprint_field"] == (
            "program_fingerprint"
        )

    def test_program_change_re_anchors_contract(self, tmp_path):
        """A legitimately different program on resume (changed
        program_key) must RE-anchor the stored contract, or DET002 stays
        permanently disarmed for that checkpoint dir."""
        from flexflow_tpu.core import FFConfig

        d = str(tmp_path)
        m = _small_model(FFConfig(batch_size=16, search_budget=0))
        current = m._exec_contract_record()
        stale = dict(current, program_key="someoldkey")
        write_contract_record(d, stale)
        m._exec_contract_sync(d, resume=True)
        assert m.exec_resume_check["program_changed"] is True
        assert m.exec_resume_check["re_anchored"] is True
        assert read_contract_record(d)["program_key"] == (
            current["program_key"]
        )

    def test_searched_backend(self, tmp_path):
        from flexflow_tpu.core import FFConfig

        m2 = self._roundtrip(
            lambda: FFConfig(batch_size=16, search_budget=2), tmp_path
        )
        # searched backends compare the optimized-HLO fingerprint and
        # mirror the check into the provenance record
        assert m2.exec_resume_check["fingerprint_field"] == (
            "hlo_fingerprint"
        )
        assert (
            m2.search_provenance["exec"]["resume_check"]
            == m2.exec_resume_check
        )


# ---------------------------------------------------------------------------
# serving programs (prefill + decode)
# ---------------------------------------------------------------------------


@pytest.mark.filterwarnings("ignore")
def test_serving_decode_donation_coverage():
    """The serving decode program donates the KV cache; every cache leaf
    must be aliased (MEM005's admission verdict prices the cache as
    updated in place) — 100% coverage on BOTH phases."""
    from flexflow_tpu.analysis.memory_accounting import ServingMemorySpec
    from flexflow_tpu.serving.kv_cache import attention_layers
    from flexflow_tpu.serving.model import ServingLMConfig, build_serving_lm
    from flexflow_tpu.serving.program import ServingProgram

    cg, _ = build_serving_lm(ServingLMConfig(), 4, 6)
    prog = ServingProgram(
        cg,
        ServingMemorySpec(max_concurrent_seqs=4, max_seq_len=24),
        params_seed=3,
    )
    out = prog.exec_contract(window_steps=3)
    n_cache_leaves = 2 * len(prog.layers)  # K and V per attention layer
    for phase in ("prefill", "decode"):
        analysis, diags = out[phase]
        assert diags == [], phase
        assert analysis.donation_coverage == 1.0, phase
        assert len(analysis.donated) == n_cache_leaves
        assert all(r.arg == "cache" for r in analysis.donated)
        assert analysis.determinism == []
