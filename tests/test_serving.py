"""Serving subsystem tests (ISSUE 12): KV-cache accounting + MEM005,
DP pruning of over-capacity serving plans (python/native parity +
search/verify agreement), decode-output parity (fused vs per-step,
searched vs single-device), continuous-batching determinism, watchdog
replica shedding via FF_TPU_FAULT_SPEC, ffcheck --memory --serving CLI
contract, and the slow-marked continuous-vs-static throughput gate."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FFCHECK = os.path.join(REPO, "tools", "ffcheck.py")

from flexflow_tpu.analysis.diagnostics import has_errors
from flexflow_tpu.analysis.memory_accounting import (
    ServingMemorySpec,
    kv_cache_piece_bytes,
    leaf_step_memory_bytes,
)
from flexflow_tpu.analysis.memory_analysis import (
    serving_verdict,
    verify_memory,
)
from flexflow_tpu.pcg.machine_view import MachineSpecification
from flexflow_tpu.pcg.parallel_computation_graph import (
    pcg_from_computation_graph,
)
from flexflow_tpu.serving import (
    ServeRequest,
    ServingEngine,
    ServingLMConfig,
    ServingProgram,
    ServingWorkload,
    build_serving_lm,
    optimize_serving_plan,
)
from flexflow_tpu.serving.kv_cache import (
    attention_layers,
    per_device_cache_bytes,
)

SPEC = MachineSpecification(1, 1, 8, 1.0, 2.0)
CFG = ServingLMConfig()  # vocab 64, embed 32, heads 4, layers 2, ffn 64


def _builder(b, s):
    return build_serving_lm(CFG, b, s)


def _prompts(rng, n, length):
    return rng.integers(0, CFG.vocab_size, (n, length)).astype(np.int32)


# ---------------------------------------------------------------------------
# KV-cache accounting (hand-computed units)
# ---------------------------------------------------------------------------


class TestCacheAccounting:
    def test_kv_cache_piece_bytes_hand_computed(self):
        """Unsharded: 2 (K+V) x seqs x positions x heads x head_dim x 4B,
        via attrs.k_proj_size + v_proj_size."""
        pcg = pcg_from_computation_graph(_builder(8, 1)[0])
        layers = attention_layers(pcg)
        assert len(layers) == CFG.num_layers
        spec = ServingMemorySpec(max_concurrent_seqs=8, max_seq_len=16)
        n = layers[0].node
        ins = pcg.inputs_of(n)
        got = kv_cache_piece_bytes(
            layers[0].attrs,
            pcg.tensor_shape(ins[0]),
            pcg.tensor_shape(ins[3]),
            spec,
        )
        head_dim = CFG.embed_dim // CFG.num_heads
        want = 8 * 16 * CFG.num_heads * (head_dim + head_dim) * 4
        assert got == want

    def test_cache_shards_with_batch_degree(self):
        """A dp-sharded plan divides cache sequences per device."""
        from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds

        pcg = pcg_from_computation_graph(_builder(8, 1)[0])
        spec = ServingMemorySpec(max_concurrent_seqs=8, max_seq_len=16)
        serial = per_device_cache_bytes(pcg, attention_layers(pcg), spec)
        seeds = dict(enumerate_seeds(pcg, 8))
        dp8 = seeds["dp8xtp1xsp1"]
        sharded = per_device_cache_bytes(dp8, attention_layers(dp8), spec)
        assert sharded * 8 == serial

    def test_serving_leaf_accounting_forward_only(self):
        """Serving residency of an attention leaf = activations + weights
        + outputs (x1 each, no grads/optimizer) + cache share."""
        from flexflow_tpu.compiler.machine_mapping.problem_tree import (
            _leaf_key,
        )
        from flexflow_tpu.op_attrs.parallel_tensor_shape import (
            get_piece_shape,
        )

        pcg = pcg_from_computation_graph(_builder(8, 1)[0])
        layer = attention_layers(pcg)[0]
        spec = ServingMemorySpec(max_concurrent_seqs=8, max_seq_len=16)
        leaf = _leaf_key(pcg, layer.node)
        got = leaf_step_memory_bytes(leaf, 2, serving=spec)
        ins = [get_piece_shape(s).size_bytes for s in leaf.input_shapes]
        outs = sum(get_piece_shape(s).size_bytes for s in leaf.output_shapes)
        cache = kv_cache_piece_bytes(
            layer.attrs, leaf.input_shapes[0], leaf.input_shapes[3], spec
        )
        # slots: q, k, v (data) + packed weight
        want = sum(ins) + outs + cache
        assert got == want
        # the training accounting for the same leaf charges grads +
        # optimizer slots and no cache — strictly different regime
        assert leaf_step_memory_bytes(leaf, 2) != got


# ---------------------------------------------------------------------------
# MEM005 + the static max-sequences verdict
# ---------------------------------------------------------------------------


class TestServingVerdict:
    def test_mem005_negative_and_positive(self):
        pcg = pcg_from_computation_graph(_builder(8, 1)[0])
        spec = ServingMemorySpec(max_concurrent_seqs=8, max_seq_len=512)
        analysis, diags = verify_memory(
            pcg, SPEC, None, hbm_bytes=64 * 2**20, serving=spec
        )
        assert not has_errors(diags)
        verdict = serving_verdict(analysis, 64 * 2**20)
        assert verdict.max_sequences >= 8

        # per-seq slope hand-check: unsharded per-device cache at 8 seqs,
        # divided by 8
        full = per_device_cache_bytes(pcg, attention_layers(pcg), spec)
        d = verdict.limiting_device
        assert verdict.per_seq_bytes[d] == full // 8

        # capacity that fits the model but not 8 sequences' cache: MEM005
        base = analysis.per_device[d].peak_bytes - full
        tight = base + full // 2  # room for ~4 sequences' cache
        _, diags2 = verify_memory(
            pcg, SPEC, None, hbm_bytes=tight, serving=spec
        )
        ids = {x.rule_id for x in diags2}
        assert "MEM005" in ids
        verdict2 = serving_verdict(
            verify_memory(pcg, SPEC, None, hbm_bytes=tight, serving=spec)[0],
            tight,
        )
        assert verdict2.max_sequences < 8
        assert verdict2.max_sequences >= 3  # ~half the cache fits

    def test_serving_analysis_forward_only(self):
        """No backward ticks, no grad/optimizer categories, cache
        resident."""
        from flexflow_tpu.analysis.memory_analysis import analyze_memory

        pcg = pcg_from_computation_graph(_builder(4, 1)[0])
        spec = ServingMemorySpec(max_concurrent_seqs=4, max_seq_len=16)
        a = analyze_memory(pcg, SPEC, None, serving=spec)
        assert a.num_ticks == len(list(pcg.topological_ordering()))
        for d in a.per_device.values():
            assert d.peak_breakdown.get("grads", 0) == 0
            assert d.peak_breakdown.get("opt_state", 0) == 0
            assert d.peak_breakdown.get("activation_grads", 0) == 0
        held = max(
            d.peak_breakdown.get("kv_cache", 0) for d in a.per_device.values()
        )
        assert held == per_device_cache_bytes(
            pcg, attention_layers(pcg), spec
        )
        # training analysis of the same pcg has backward ticks and grads
        t = analyze_memory(pcg, SPEC, None)
        assert t.num_ticks == 2 * a.num_ticks


# ---------------------------------------------------------------------------
# DP pruning + search/verify agreement
# ---------------------------------------------------------------------------


class TestServingSearch:
    def _tight_budget_gb(self, pcg, cache_spec):
        """A budget the serial plan's cache busts but a dp-sharded one
        fits: serial peak minus half the serial cache."""
        analysis, _ = verify_memory(pcg, SPEC, None, serving=cache_spec)
        peak = max(d.peak_bytes for d in analysis.per_device.values())
        cache = per_device_cache_bytes(pcg, attention_layers(pcg), cache_spec)
        return (peak - cache // 2) / 2**30

    def test_dp_prunes_serving_over_capacity_python_native_parity(self):
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingCache,
        )
        from flexflow_tpu.compiler.unity_algorithm import evaluate_pcg
        from flexflow_tpu.serving import serving_search_context

        wl = ServingWorkload(prompt_len=6, gen_len=8, max_concurrent=8)
        cache_spec = wl.cache_spec(max_seq_len=512)
        pcg = pcg_from_computation_graph(_builder(8, 1)[0])
        tight = self._tight_budget_gb(pcg, cache_spec)

        ctx_free, _ = serving_search_context(SPEC, cache_spec)
        assert (
            evaluate_pcg(pcg, ctx_free, SPEC, MachineMappingCache())
            is not None
        )
        ctx, _ = serving_search_context(SPEC, cache_spec, hbm_gb=tight)
        native = evaluate_pcg(pcg, ctx, SPEC, MachineMappingCache())
        assert native is None  # serial plan's cache busts the budget
        os.environ["FF_TPU_NO_NATIVE"] = "1"
        try:
            python = evaluate_pcg(pcg, ctx, SPEC, MachineMappingCache())
        finally:
            del os.environ["FF_TPU_NO_NATIVE"]
        assert python is None  # exact parity on the serving pruner

    def test_budgeted_search_never_selects_rejected_plan(self):
        """The acceptance contract: a budgeted serving search's winner
        always passes `ffcheck --memory --serving` at the same capacity,
        and the objective breakdown + dedup observability land in
        provenance."""
        wl = ServingWorkload(prompt_len=6, gen_len=8, max_concurrent=8)
        pcg = pcg_from_computation_graph(_builder(8, 1)[0])
        cache_spec = wl.cache_spec(max_seq_len=512)
        tight = self._tight_budget_gb(pcg, cache_spec)
        plan = optimize_serving_plan(
            _builder, SPEC, wl, hbm_gb=tight, budget=4, max_seq_len=512
        )
        for phase in (plan.decode, plan.prefill):
            _, diags = verify_memory(
                phase.pcg,
                SPEC,
                phase.machine_mapping,
                hbm_bytes=tight * 2**30,
                serving=cache_spec,
            )
            assert not has_errors(diags)
        # the winner sharded the cache below the serial residency
        assert per_device_cache_bytes(
            plan.decode.pcg, attention_layers(plan.decode.pcg), cache_spec
        ) < per_device_cache_bytes(pcg, attention_layers(pcg), cache_spec)
        # ms/token objective: decode + amortized prefill
        assert plan.ms_per_token == pytest.approx(
            plan.decode_ms + plan.prefill_ms / wl.gen_len
        )
        prov = plan.provenance
        assert prov["objective"] == "ms_per_token"
        assert prov["forward_only"] is True
        for phase in ("decode", "prefill"):
            assert isinstance(prov[phase]["symmetry_dedup"], bool)
            assert prov[phase]["evaluations"] >= 1

    def test_serving_rules_exclude_sequence_parallel_attention(self):
        from flexflow_tpu.serving import serving_rules
        from flexflow_tpu.substitutions.rules import (
            generate_parallelization_rules,
        )

        rules = serving_rules(SPEC)
        assert rules, "serving search has an empty rule set"
        assert all(
            "sequence_parallel_attention" not in r.name for r in rules
        )
        full = generate_parallelization_rules([2, 4, 8])
        assert any("sequence_parallel_attention" in r.name for r in full)


# ---------------------------------------------------------------------------
# Decode parity
# ---------------------------------------------------------------------------


class TestDecodeParity:
    B, P = 4, 6
    SPEC_MEM = ServingMemorySpec(max_concurrent_seqs=4, max_seq_len=24)

    def _single_device(self):
        cg, _ = _builder(self.B, 1)
        return ServingProgram(cg, self.SPEC_MEM, params_seed=3)

    def test_fused_vs_per_step_bitwise(self):
        """One 8-step fused decode window == 8 single-step windows:
        identical tokens AND bit-identical cache."""
        rng = np.random.default_rng(0)
        prompts = _prompts(rng, self.B, self.P)
        lengths = np.full(self.B, self.P, np.int32)
        fresh = np.ones(self.B, bool)
        active = np.ones(self.B, bool)

        prog = self._single_device()
        cache, tok, _ = prog.prefill(prog.init_cache(), prompts, lengths, fresh)
        cache, tok_f, len_f, toks_fused = prog.decode_window(
            cache, np.asarray(tok), lengths, active, 8
        )

        prog2 = self._single_device()
        c2, t2, _ = prog2.prefill(
            prog2.init_cache(), prompts, lengths, fresh
        )
        t2 = np.asarray(t2)
        l2 = lengths
        steps = []
        for _ in range(8):
            c2, t2, l2, s = prog2.decode_window(c2, t2, l2, active, 1)
            steps.append(np.asarray(s)[:, 0])
        toks_step = np.stack(steps, axis=1)
        assert np.array_equal(np.asarray(toks_fused), toks_step)
        assert np.array_equal(np.asarray(len_f), np.asarray(l2))
        for name, kv in cache.items():
            for part in ("k", "v"):
                assert np.array_equal(
                    np.asarray(kv[part]), np.asarray(c2[name][part])
                ), f"cache {name}/{part} diverged"

    def test_prefill_matches_teacher_forced_decode(self):
        """Prefilling p tokens == prefilling 1 then decode-feeding the
        rest (teacher-forced): the next-token logits agree."""
        rng = np.random.default_rng(1)
        prompts = _prompts(rng, self.B, self.P)
        lengths = np.full(self.B, self.P, np.int32)
        fresh = np.ones(self.B, bool)
        prog = self._single_device()
        _, tok_full, last_full = prog.prefill(
            prog.init_cache(), prompts, lengths, fresh
        )

        prog2 = self._single_device()
        one = np.ones(self.B, np.int32)
        cache, tok, _ = prog2.prefill(
            prog2.init_cache(), prompts[:, :1], one, fresh
        )
        lens = np.array(one)
        active = np.ones(self.B, bool)
        for j in range(1, self.P):
            # force the true prompt token instead of the sampled one
            cache, tok, lens, _ = prog2.decode_window(
                cache, prompts[:, j], lens, active, 1
            )
        # after consuming the full prompt the sampled next token matches
        assert np.array_equal(np.asarray(tok_full), np.asarray(tok))

    def test_searched_vs_single_device(self):
        """A searched 8-device plan generates the same tokens as the
        unsearched single-device lowering with identical params."""
        from flexflow_tpu.parallel.mesh import MachineMesh

        wl = ServingWorkload(prompt_len=self.P, gen_len=8, max_concurrent=4)
        plan = optimize_serving_plan(_builder, SPEC, wl, budget=2)
        mm = MachineMesh.from_spec(SPEC)
        prog = ServingProgram(
            plan.decode.pcg,
            plan.cache_spec,
            mapping=plan.decode.machine_mapping,
            machine_mesh=mm,
            params_seed=3,
        )
        ref_cg, _ = _builder(self.B, 1)
        ref = ServingProgram(ref_cg, plan.cache_spec, params_seed=3)

        rng = np.random.default_rng(2)
        prompts = _prompts(rng, self.B, self.P)
        lengths = np.full(self.B, self.P, np.int32)
        fresh = np.ones(self.B, bool)
        active = np.ones(self.B, bool)
        out = []
        for p in (prog, ref):
            cache, tok, _ = p.prefill(p.init_cache(), prompts, lengths, fresh)
            _, _, _, toks = p.decode_window(
                cache, np.asarray(tok), lengths, active, 6
            )
            out.append(np.asarray(toks))
        assert np.array_equal(out[0], out[1])


# ---------------------------------------------------------------------------
# Engine: continuous batching, determinism, metrics, SLO
# ---------------------------------------------------------------------------


def _mk_requests(rng, n, prompt_len=5, slo=None):
    return [
        ServeRequest(
            rid=f"r{i}",
            prompt=rng.integers(0, CFG.vocab_size, prompt_len).astype(
                np.int32
            ),
            max_new_tokens=int(rng.integers(2, 12)),
            slo_ms_per_token=slo,
        )
        for i in range(n)
    ]


class TestEngine:
    MEM = ServingMemorySpec(max_concurrent_seqs=4, max_seq_len=24)

    def _program(self):
        cg, _ = _builder(4, 1)
        return ServingProgram(cg, self.MEM, params_seed=0)

    def _trace(self, mode):
        """(admission schedule, completion schedule, outputs) of a seeded
        run."""
        eng = ServingEngine(self._program(), mode=mode, window_steps=3)
        schedule = []
        orig = eng._prefill

        def spy(replica, admitted):
            schedule.append((eng.windows, tuple(
                replica.slots[i].request.rid for i in admitted
            )))
            return orig(replica, admitted)

        eng._prefill = spy
        rng = np.random.default_rng(7)
        for r in _mk_requests(rng, 12):
            eng.submit(r)
        recs = eng.run()
        comp = [(r.rid, tuple(r.tokens)) for r in recs]
        return schedule, comp

    def test_continuous_admit_evict_determinism(self):
        """The same seeded arrival trace replays to the identical
        admission schedule, completion order, and generated tokens."""
        s1, c1 = self._trace("continuous")
        s2, c2 = self._trace("continuous")
        assert s1 == s2
        assert c1 == c2
        # continuous batching actually refilled slots mid-run: some
        # admission happened after the first window
        assert any(w > 1 for w, _ in s1)

    def test_static_mode_admits_only_when_drained(self):
        s, comp = self._trace("static")
        assert len(comp) == 12
        # every static admission happens with ZERO active slots, so each
        # admitted group runs to completion before the next: admission
        # windows are strictly spaced by at least the longest generation
        admit_windows = [w for w, _ in s]
        assert len(admit_windows) == len(set(admit_windows))
        assert len(s) == 3  # 12 requests / 4 slots

    def test_metrics_jsonl_and_slo_counter(self, tmp_path):
        from flexflow_tpu.observability.metrics import read_run_events
        from flexflow_tpu.serving.engine import REQUEST_EVENT_FIELDS

        eng = ServingEngine(
            self._program(),
            mode="continuous",
            window_steps=3,
            metrics_dir=str(tmp_path),
        )
        rng = np.random.default_rng(3)
        for r in _mk_requests(rng, 6, slo=1e-6):  # impossible SLO
            eng.submit(r)
        recs = eng.run()
        assert len(recs) == 6
        assert eng.slo_violations == 6
        events = read_run_events(str(tmp_path), "serve_request")
        assert len(events) == 6
        for e in events:
            assert set(REQUEST_EVENT_FIELDS) <= set(e)
            assert e["slo_violated"] is True
            assert e["tokens"] >= 1
        s = eng.summary()
        assert s["slo_violations"] == 6
        assert s["completed"] == 6
        assert s["p50_ms_per_token"] <= s["p99_ms_per_token"]

    def test_admission_respects_static_verdict(self):
        """max_concurrent (the MEM005 verdict) caps admitted sequences
        below the program's slot count."""
        eng = ServingEngine(
            self._program(), mode="continuous", window_steps=3,
            max_concurrent=2,
        )
        rng = np.random.default_rng(5)
        for r in _mk_requests(rng, 6):
            eng.submit(r)
        eng.run()
        assert eng.max_observed_concurrent <= 2
        assert len(eng.completed) == 6

    def test_oversized_request_rejected(self):
        eng = ServingEngine(self._program())
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.submit(
                ServeRequest(
                    rid="big",
                    prompt=np.zeros(20, np.int32),
                    max_new_tokens=20,
                )
            )


# ---------------------------------------------------------------------------
# Supervision: watchdog sheds a hung replica (FF_TPU_FAULT_SPEC e2e)
# ---------------------------------------------------------------------------


def _single_hang_seed(lo, hi, horizon, rate):
    from flexflow_tpu.runtime.fault import FaultSchedule

    for seed in range(100000):
        fired = FaultSchedule(
            seed=seed, sites=frozenset({"hang"}), rate=rate
        ).fire_steps("hang", 1, horizon)
        if len(fired) == 1 and lo <= fired[0] <= hi:
            return seed
    raise AssertionError("no single-firing hang seed found")


class TestReplicaShedding:
    def test_watchdog_sheds_hung_replica(self, monkeypatch, tmp_path):
        """FF_TPU_FAULT_SPEC site "hang" inside an armed decode window:
        the watchdog fires, the replica sheds, its in-flight requests
        resubmit to the healthy replica, and every request completes."""
        from flexflow_tpu.observability.metrics import read_run_events

        # the run lasts ~10 windows; a 40-window horizon with exactly one
        # firing guarantees the SECOND replica never draws a hang
        seed = _single_hang_seed(3, 6, 40, 0.05)
        monkeypatch.setenv(
            "FF_TPU_FAULT_SPEC", f"seed={seed};sites=hang;rate=0.05"
        )
        mem = ServingMemorySpec(max_concurrent_seqs=2, max_seq_len=24)
        cg, _ = _builder(2, 1)
        progs = [
            ServingProgram(cg, mem, params_seed=0),
            ServingProgram(cg, mem, params_seed=0),
        ]
        eng = ServingEngine(
            progs,
            mode="continuous",
            window_steps=2,
            watchdog_factor=2.0,
            # far above a healthy 2 ms window, so that only the injected
            # hang (which blocks until the deadline) can trip it; at 1 ms
            # a window that merely ran late shed the healthy replica too
            watchdog_min_budget_ms=250.0,
            metrics_dir=str(tmp_path),
        )
        rng = np.random.default_rng(0)
        for i in range(8):
            eng.submit(
                ServeRequest(
                    rid=f"r{i}",
                    prompt=rng.integers(0, 64, 4).astype(np.int32),
                    max_new_tokens=6,
                )
            )
        try:
            recs = eng.run()
        finally:
            eng.close()
        assert eng.replica_sheds == 1
        assert sorted(r.rid for r in recs) == [f"r{i}" for i in range(8)]
        assert any(r.resubmitted for r in recs)
        shed_events = read_run_events(str(tmp_path), "replica_shed")
        assert len(shed_events) == 1
        assert "WindowHangError" in shed_events[0]["reason"]
        hang_events = read_run_events(str(tmp_path), "serve_hang")
        assert len(hang_events) == 1
        assert hang_events[0]["budget_ms"] > 0
        # the shed replica serves nothing afterwards
        shed_idx = shed_events[0]["replica"]
        late = [r for r in recs if r.resubmitted]
        assert all(r.replica != shed_idx for r in late)


# ---------------------------------------------------------------------------
# ffcheck --memory --serving CLI (exit codes + --json schema)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_strategy_file(tmp_path_factory):
    from flexflow_tpu.runtime.strategy import save_strategy

    wl = ServingWorkload(prompt_len=6, gen_len=8, max_concurrent=4)
    plan = optimize_serving_plan(_builder, SPEC, wl, budget=2)
    path = tmp_path_factory.mktemp("serve") / "serve_plan.json"
    save_strategy(
        str(path), plan.decode.pcg, plan.decode.machine_mapping,
        plan.decode.runtime,
    )
    return str(path)


@pytest.mark.filterwarnings("ignore")
class TestFfcheckServingCLI:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, FFCHECK, *args],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )

    def test_serving_requires_memory(self, serving_strategy_file):
        proc = self._run("--serving", serving_strategy_file)
        assert proc.returncode == 2
        assert "--memory --serving" in proc.stderr

    def test_clean_exit_and_json_schema(self, serving_strategy_file):
        proc = self._run(
            "--memory", "--serving", "--json", "--max-seqs", "4",
            "--max-seq-len", "16", "--hbm-gb", "16",
            "--devices-per-node", "8", serving_strategy_file,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
        assert not any("rule_id" in d for d in lines)
        (summary,) = [d for d in lines if "memory" in d]
        sv = summary["serving"]
        assert sv["max_concurrent_seqs"] == 4
        assert sv["max_seq_len"] == 16
        v = sv["verdict"]
        assert v["requested_sequences"] == 4
        assert v["max_sequences"] >= 4
        assert v["limiting_device"] is not None
        assert set(v) == {
            "requested_sequences", "max_sequences", "limiting_device",
            "per_seq_bytes", "per_device_max",
        }

    def test_over_capacity_exit_1_with_mem005(self, serving_strategy_file):
        proc = self._run(
            "--memory", "--serving", "--json", "--max-seqs", "64",
            "--max-seq-len", "4096", "--hbm-gb", "0.001",
            "--devices-per-node", "8", serving_strategy_file,
        )
        assert proc.returncode == 1
        lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
        ids = {d["rule_id"] for d in lines if "rule_id" in d}
        assert "MEM005" in ids
        (summary,) = [d for d in lines if "memory" in d]
        assert summary["serving"]["verdict"]["max_sequences"] < 64

    def test_training_mode_summary_has_null_serving(
        self, serving_strategy_file
    ):
        """Without --serving the summary's serving block is null (schema
        stays one shape)."""
        proc = self._run(
            "--memory", "--json", "--hbm-gb", "16",
            "--devices-per-node", "8", serving_strategy_file,
        )
        assert proc.returncode == 0
        lines = [json.loads(l) for l in proc.stdout.splitlines() if l]
        (summary,) = [d for d in lines if "memory" in d]
        assert summary["serving"] is None


# ---------------------------------------------------------------------------
# Throughput gate (slow): continuous >= 1.2x static on sustained rps
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_continuous_beats_static_batching():
    """The regression gate for continuous batching: on the 8-dev
    virtual mesh, continuous batching sustains >= 1.2x the requests/s of
    static batching on a skewed-generation-length backlog."""
    from flexflow_tpu.parallel.mesh import MachineMesh

    wl = ServingWorkload(prompt_len=6, gen_len=24, max_concurrent=4)
    plan = optimize_serving_plan(_builder, SPEC, wl, budget=2)
    mm = MachineMesh.from_spec(SPEC)

    import time

    prog = ServingProgram(
        plan.decode.pcg, plan.cache_spec,
        mapping=plan.decode.machine_mapping, machine_mesh=mm,
        params_seed=0,
    )
    # warm the prefill/decode traces on a scratch cache so the timed
    # region measures serving throughput, not XLA compilation
    b = plan.cache_spec.max_concurrent_seqs
    scratch = prog.init_cache()
    scratch, tok, _ = prog.prefill(
        scratch, np.zeros((b, 6), np.int32),
        np.full(b, 6, np.int32), np.ones(b, bool),
    )
    prog.decode_window(
        scratch, np.asarray(tok), np.full(b, 6, np.int32),
        np.ones(b, bool), 4,
    )

    def one(mode):
        eng = ServingEngine(prog, mode=mode, window_steps=4)
        rng = np.random.default_rng(11)
        for i in range(24):
            gen = 2 if i % 4 else 24  # skewed: a straggler per four
            eng.submit(
                ServeRequest(
                    rid=f"r{i}",
                    prompt=rng.integers(0, 64, 6).astype(np.int32),
                    max_new_tokens=gen,
                )
            )
        t0 = time.perf_counter()
        recs = eng.run()
        elapsed = time.perf_counter() - t0
        assert len(recs) == 24
        return elapsed

    # best-of-4 per mode with the arms INTERLEAVED (the chaos-overhead
    # protocol): the 2-core CI host's dispatch overhead drifts with
    # background load, and interleaving makes the drift hit both arms
    # equally — the policy difference under test is structural (the
    # straggler holds static slots hostage for ~2.3x more decode
    # windows), not a timing accident
    best = {"static": float("inf"), "continuous": float("inf")}
    for _ in range(4):
        for mode in ("static", "continuous"):
            best[mode] = min(best[mode], one(mode))
    static_rps = 24 / best["static"]
    continuous_rps = 24 / best["continuous"]
    assert continuous_rps >= 1.2 * static_rps, (
        f"continuous {continuous_rps:.2f} rps vs static {static_rps:.2f}"
    )


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
