"""Static plan-transition verification (ISSUE 19).

The verifier over a plan PAIR (analysis/transition_analysis.py):
per-rule negative paths for TRN001-TRN004, the hand-computed dp8 -> tp4
Linear migration co-residency peak, the recompile() provenance +
TransitionError gating, the advisory-gets-verdict path through the
drift monitor, the by-construction agreement between ffcheck
--transition / the advisory verdict / recompile(preserve_resume=True),
and the transition_audit tier-1 smoke subset.
"""

import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from flexflow_tpu.analysis.transition_analysis import (  # noqa: E402
    TRANSITION_RULE_IDS,
    TransitionError,
    transition_verdict_record,
    verify_transition,
)
from flexflow_tpu.pcg import ComputationGraphBuilder  # noqa: E402
from flexflow_tpu.pcg.parallel_computation_graph import (  # noqa: E402
    pcg_from_computation_graph,
)


def _mlp(batch=16, width=64, drop_fc2=False):
    b = ComputationGraphBuilder()
    x = b.create_input([batch, 32], name="x")
    h = b.dense(x, width, use_bias=False, name="fc1")
    h = b.relu(h)
    if not drop_fc2:
        h = b.dense(h, 32, use_bias=False, name="fc2")
    return pcg_from_computation_graph(b.graph)


def _linear():
    b = ComputationGraphBuilder()
    x = b.create_input([16, 32], name="x")
    b.dense(x, 64, use_bias=False, name="fc1")
    return pcg_from_computation_graph(b.graph)


def _flat_spec():
    from flexflow_tpu.pcg.machine_view import MachineSpecification

    return MachineSpecification(
        num_nodes=1,
        num_cpus_per_node=1,
        num_devices_per_node=8,
        inter_node_bandwidth=25.0,
        intra_node_bandwidth=400.0,
    )


def _mapped_seed(pcg, label, spec):
    from flexflow_tpu.compiler import (
        AnalyticTPUCostEstimator,
        MachineMappingCache,
        MachineMappingContext,
        evaluate_pcg,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds

    ctx = MachineMappingContext(
        AnalyticTPUCostEstimator(spec), make_default_allowed_machine_views()
    )
    seed = dict(enumerate_seeds(pcg, spec.num_devices))[label]
    r = evaluate_pcg(seed, ctx, spec, MachineMappingCache())
    assert r is not None, f"seed {label} did not map"
    return r.pcg, r.machine_mapping


# -- per-rule negative paths -------------------------------------------------


class TestRuleNegatives:
    def test_rule_ids_frozen(self):
        assert TRANSITION_RULE_IDS == (
            "TRN001", "TRN002", "TRN003", "TRN004",
        )

    def test_trn001_orphaned_leaf(self):
        a, diags = verify_transition(_mlp(), None, _mlp(drop_fc2=True), None)
        assert a.verdict == "swap_blocked"
        assert a.rules_tripped == ["TRN001"]
        assert a.orphaned == ["fc2/w0"]
        assert any(
            d.rule_id == "TRN001" and "fc2/w0" in d.message for d in diags
        )

    def test_trn001_created_leaf(self):
        a, _ = verify_transition(_mlp(drop_fc2=True), None, _mlp(), None)
        assert a.rules_tripped == ["TRN001"]
        assert a.created == ["fc2/w0"]

    def test_trn001_drifted_leaf(self):
        a, _ = verify_transition(_mlp(width=64), None, _mlp(width=48), None)
        assert a.rules_tripped == ["TRN001"]
        # fc1 changed its own shape; fc2's input dim follows it
        assert a.drifted == ["fc1/w0", "fc2/w0"]

    def test_trn002_migration_over_memory(self):
        a, diags = verify_transition(
            _mlp(), None, _mlp(), None, hbm_bytes=1024.0
        )
        assert a.migration_verdict == "over"
        assert a.rules_tripped == ["TRN002"]
        assert any(
            d.rule_id == "TRN002" and "infeasible" in d.message
            for d in diags
        )

    def test_trn003_batch_schedule_change(self):
        a, diags = verify_transition(
            _mlp(batch=16), None, _mlp(batch=32), None
        )
        assert a.rules_tripped == ["TRN003"]
        assert a.verdict == "swap_blocked"
        assert (
            a.contract_old["batch_schedule"]
            != a.contract_new["batch_schedule"]
        )

    def test_trn003_compatible_change_is_carry_remap(self):
        # an unchanged batch schedule is annotated, not flagged: the key
        # and the dataloader's cursor carry over as they are
        a, _ = verify_transition(_mlp(), None, _mlp(), None)
        assert a.rules_tripped == []
        assert {"rng", "dataloader"} <= set(a.carry_remap)
        assert set(a.contract_new) == {
            "batch_schedule", "pipeline_stages", "pipeline_microbatches",
            "pipeline_region_ok",
        }

    def test_trn004_undonated_new_step(self):
        import jax
        import jax.numpy as jnp

        def _step(params, opt_state, batch, label, rng):
            return params, opt_state, jnp.float32(0.0), jnp.float32(0.0)

        p = {"w": jnp.zeros((64, 64))}
        lo = jax.jit(_step).lower(
            p, p, jnp.zeros((2, 4)), jnp.zeros((2,), jnp.int32),
            jax.random.PRNGKey(0),
        )
        box = types.SimpleNamespace(lowered=lo, compiled=lo.compile())
        a, diags = verify_transition(
            _mlp(), None, _mlp(), None, lowered_new=box
        )
        assert a.exec_verified
        assert a.rules_tripped == ["TRN004"]
        assert any(d.rule_id == "TRN004" for d in diags)


# -- the hand-computed dp8 -> tp4 Linear migration peak ----------------------


class TestMigrationPeak:
    def test_dp8_to_tp4_linear_co_residency(self):
        """One Linear [32x64] f32 leaf, 2 optimizer slots: at rest the
        executor stores the weight's master and both slots cut over every
        axis the plan replicates the weight on (`update_shard_ways`):

        dp8 src: weight replicated, piece = 32*64*4 = 8192 B, cut 8 ways:
                 3 * 1024                                 = 3072 B/device
        tp4 dst: out-dim sharded 4-way, piece = 32*16*4 = 2048 B, the 2
                 replicas left cut it again: 3 * 1024     = 3072 B/device
        bulk peak     = 3072 + 3072                       = 6144 B
        streamed peak = 3072 + (3072 + 3072)              = 9216 B
        (single leaf: the streamed bound's rest-of-state term and the
        in-flight leaf are the same leaf, so streamed > bulk; what MOVES is
        still value + both moments whole: 3*8192)
        """
        spec = _flat_spec()
        old_pcg, old_map = _mapped_seed(_linear(), "dp8xtp1xsp1", spec)
        new_pcg, new_map = _mapped_seed(_linear(), "dp2xtp4xsp1", spec)
        a, _ = verify_transition(
            old_pcg, old_map, new_pcg, new_map,
            machine_spec=spec, hbm_bytes=16 * 2**30,
        )
        (leaf,) = a.leaves
        assert leaf.path == "fc1/w0"
        assert leaf.bytes_global == 32 * 64 * 4
        assert leaf.src_piece_bytes == 8192
        assert leaf.dst_piece_bytes == 2048
        assert leaf.moved and leaf.moved_bytes == 3 * 8192
        assert leaf.link_class == "ici"
        assert a.bulk_peak_bytes == 6144
        assert a.streamed_peak_bytes == 9216
        assert a.migration_verdict == "bulk"
        assert a.verdict == "swappable"

    def test_tight_hbm_flips_to_over(self):
        # 6000 B sits below the 6144 B bulk peak AND below the 9216 B
        # streamed bound: the migration is infeasible, not just streamed
        spec = _flat_spec()
        old_pcg, old_map = _mapped_seed(_linear(), "dp8xtp1xsp1", spec)
        new_pcg, new_map = _mapped_seed(_linear(), "dp2xtp4xsp1", spec)
        a, _ = verify_transition(
            old_pcg, old_map, new_pcg, new_map,
            machine_spec=spec, hbm_bytes=6000.0,
        )
        assert a.migration_verdict == "over"
        assert a.rules_tripped == ["TRN002"]


# -- recompile(): provenance + TransitionError gating ------------------------


def _small_model(batch=8):
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    cfg = FFConfig(batch_size=batch, epochs=1, seed=0, print_freq=0)
    m = FFModel(cfg)
    x = m.create_tensor([batch, 16], name="x")
    t = m.dense(x, 32, use_bias=False, name="fc1")
    t = m.relu(t)
    m.dense(t, 4, use_bias=False, name="out")
    m.compile(SGDOptimizer(lr=0.1), "sparse_categorical_crossentropy",
              metrics=["accuracy"])
    return m


class TestRecompileProvenance:
    def test_identity_recompile_records_swappable(self):
        m = _small_model()
        m.recompile()
        rec = m.search_provenance["transition"]
        assert rec["verdict"] == "swappable"
        assert rec["rules_tripped"] == []
        assert rec["leaves"] == 2

    def test_batch_growth_records_trn003_without_raising(self):
        # the canonical recompile (test_recompile's batch-growth fit)
        # legitimately breaks bitwise resume: recorded, not refused
        m = _small_model(batch=8)
        m.config.batch_size = 16
        m.recompile()
        rec = m.search_provenance["transition"]
        assert rec["verdict"] == "swap_blocked"
        assert rec["rules_tripped"] == ["TRN003"]

    def test_preserve_resume_raises_named_rule(self):
        m = _small_model(batch=8)
        m.config.batch_size = 16
        with pytest.raises(TransitionError) as ei:
            m.recompile(preserve_resume=True)
        assert ei.value.rules == ["TRN003"]
        assert "TRN003" in str(ei.value)


# -- the drift monitor stamps a verdict on every advisory --------------------


def _write_steps(mdir, mss):
    os.makedirs(mdir, exist_ok=True)
    lines = []
    for j, ms in enumerate(mss):
        lines.append(json.dumps(
            {"schema": 1, "step": j, "wallclock_ms": ms}
        ))
    with open(os.path.join(mdir, "events.jsonl"), "a") as f:
        f.write("".join(line + "\n" for line in lines))


SLOW_STREAM = [90.0] * 2 + [12.0] * 4 + [40.0] * 8


def _monitor(mdir, **kw):
    from flexflow_tpu.observability.drift import DriftMonitor

    kw.setdefault("window_steps", 2)
    kw.setdefault("run_length", 2)
    kw.setdefault("warmup_windows", 1)
    kw.setdefault("baseline_windows", 2)
    kw.setdefault("cooldown_windows", 3)
    return DriftMonitor(mdir, 10.0, **kw)


class TestAdvisoryVerdict:
    def test_blocked_candidate_is_never_actionable(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, SLOW_STREAM)
        blocked = {
            "verdict": "swap_blocked", "rules": ["TRN003"],
            "moved_bytes": 0, "ici_bytes": 0, "dcn_bytes": 0,
            "migration_verdict": None,
        }
        mon = _monitor(
            d, seed_runtimes={"cand": 8.0},
            transition_verifier=lambda label: blocked,
        )
        (a,) = mon.poll_once()
        assert a.candidate == "cand"
        assert a.transition == blocked
        assert a.actionable is False

    def test_swappable_candidate_is_actionable(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, SLOW_STREAM)
        seen = []

        def verifier(label):
            seen.append(label)
            return {"verdict": "swappable", "rules": []}

        mon = _monitor(
            d, seed_runtimes={"cand": 8.0}, transition_verifier=verifier,
        )
        (a,) = mon.poll_once()
        assert seen == ["cand"]
        assert a.transition["verdict"] == "swappable"
        assert a.actionable is True

    def test_verifier_failure_degrades_and_counts(self, tmp_path):
        d = str(tmp_path)
        _write_steps(d, SLOW_STREAM)

        def verifier(label):
            raise RuntimeError("verifier exploded")

        mon = _monitor(
            d, seed_runtimes={"cand": 8.0}, transition_verifier=verifier,
        )
        (a,) = mon.poll_once()
        assert a.transition is None  # unverified, not a dead run
        assert mon.transition_errors == 1


# -- by-construction agreement: ffcheck / advisory / recompile ---------------


class TestAgreement:
    def test_rejected_transition_is_blocked_everywhere(self, tmp_path):
        """ONE perturbation (batch growth), three consumers: the pair
        ffcheck --transition rejects (exit 1) is never an actionable
        advisory, and recompile(preserve_resume=True) refuses it with a
        TransitionError naming the same rule."""
        import ffcheck

        from flexflow_tpu.runtime.strategy import save_strategy

        spec = _flat_spec()
        old_pcg, old_map = _mapped_seed(_mlp(batch=16), "dp8xtp1xsp1", spec)
        new_pcg, new_map = _mapped_seed(_mlp(batch=32), "dp8xtp1xsp1", spec)

        # 1. the CLI rejects the pair
        old_p = os.path.join(str(tmp_path), "old.json")
        new_p = os.path.join(str(tmp_path), "new.json")
        save_strategy(old_p, old_pcg, old_map)
        save_strategy(new_p, new_pcg, new_map)
        assert ffcheck.main(["--transition", old_p, new_p, "--json"]) == 1

        # 2. the SAME pair as an advisory candidate is swap_blocked and
        # never actionable
        a, _ = verify_transition(
            old_pcg, old_map, new_pcg, new_map, machine_spec=spec
        )
        rec = transition_verdict_record(a)
        assert rec["verdict"] == "swap_blocked"
        assert "TRN003" in rec["rules"]
        d = str(tmp_path / "metrics")
        _write_steps(d, SLOW_STREAM)
        mon = _monitor(
            d, seed_runtimes={"grown": 8.0},
            transition_verifier=lambda label: rec,
        )
        (adv,) = mon.poll_once()
        assert adv.actionable is False
        assert adv.transition["rules"] == rec["rules"]

        # 3. recompile() performing the same perturbation refuses it
        # under preserve_resume, naming the same rule
        m = _small_model(batch=8)
        m.config.batch_size = 16
        with pytest.raises(TransitionError) as ei:
            m.recompile(preserve_resume=True)
        assert ei.value.rules == ["TRN003"]


# -- the committed-audit smoke subset ----------------------------------------


class TestTransitionAuditSmoke:
    def test_tier1_smoke_passes(self, capsys):
        # fixtures trip their exact rule ids and one zoo pair
        # round-trips ffcheck --transition both ways (rc 0 / rc 1)
        import transition_audit

        assert transition_audit.main(["--tier1-smoke"]) == 0
        out = capsys.readouterr().out
        assert "TRN001=tripped" in out
        assert "LINT010=tripped" in out

    def test_committed_artifact_is_clean(self):
        path = os.path.join(REPO, "TRN_r19.json")
        with open(path) as f:
            doc = json.load(f)
        assert doc["schema"] == 1 and doc["round"] == 19
        assert doc["failures"] == []
        counts = doc["pairs"]["counts"]
        assert counts["total"] == 48
        assert counts["degraded_swappable"] == 48
        assert counts["batch_growth_blocked"] == 48
        assert all(v["tripped"] for v in doc["fixtures"].values())
        assert doc["drift_advisory"]["verdict"] == "swappable"
