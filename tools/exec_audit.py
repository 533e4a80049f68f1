#!/usr/bin/env python
"""Execution-contract audit artifact generator (ISSUE 14 acceptance):
run the static execution-contract verification
(`analysis/exec_contract.py`, the engine behind `ffcheck --exec`) over
the whole plan surface on the virtual 8-device CPU mesh and commit the
results as DET_r*.json:

1. every dp x tp x sp seed template over the ffcheck model zoo (the
   48-template frontier the search starts from) — all must verify clean
   with 100% donation-alias coverage,
2. the flagship transformer proxy's SEARCHED winner (the same subject
   MEM_r*/COMM_r* audit — one shape family by construction),
3. a pp8m2 pipelined plan (8 stages x 2 microbatches) lowered through the 1F1B executor,
4. the serving prefill + decode programs (`ServingProgram
   .exec_contract()`), with the KV cache as the expected-in-place state,
5. seeded fixtures that DEMONSTRABLY trip each rule id: DET001 (three
   nondeterministic HLO forms, fed to the census as seeded module
   text — XLA-CPU's scatter expander rewrites real scatters into
   loops, so the text fixtures pin the census itself), DET002
   (fingerprint drift between two contract records), DON001 (a real
   compiled program whose donation XLA drops), DON002 (a real update
   program compiled without donation),
6. the cross-process fingerprint stability claim: two FRESH processes
   lower + compile the same plan and must produce identical
   canonicalized HLO fingerprints (what makes DET002 a checkable
   invariant across preemption resume).

Usage:
    python tools/exec_audit.py            # writes DET_r15.json
    python tools/exec_audit.py --round 16 --out DET_r16.json
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

# repo path + the same virtual 8-device CPU mesh the tier-1 suite runs
# on (tests/conftest.py), set BEFORE jax imports — the shared bootstrap
# all audit CLIs use (tools/audit_env.py)
from audit_env import REPO, bootstrap_virtual_mesh

bootstrap_virtual_mesh(8)

ARTIFACT_SCHEMA = 1

# ONE flagship-proxy builder shared with the memory/comm audits (running
# as a script puts tools/ at sys.path[0]) — the MEM_r*, COMM_r*, and
# DET_r* artifacts measure the same shape family by construction
from memory_audit import build_flagship_proxy as build_flagship


def _subject_record(analysis, diags) -> dict:
    from flexflow_tpu.analysis.diagnostics import summarize

    cov = analysis.donation_coverage
    return {
        "hlo_fingerprint": analysis.hlo_fingerprint,
        "program_fingerprint": analysis.program_fingerprint,
        "donated_leaves": len(analysis.donated),
        "donated_bytes": int(analysis.donated_bytes),
        "donation_coverage": None if cov is None else round(cov, 4),
        "determinism_findings": len(analysis.determinism),
        "verify": summarize(diags),
        "clean": not any(d.severity.value == "error" for d in diags),
    }


def audit_templates() -> dict:
    """Every seed template over the ffcheck model zoo, each lowered +
    compiled + contract-verified."""
    from ffcheck import template_zoo

    from flexflow_tpu.analysis.exec_contract import verify_exec
    from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds

    checked = clean = 0
    coverages = []
    dirty = []
    for model, pcg in template_zoo():
        for label, seed in enumerate_seeds(pcg, 8):
            name = f"{model}/{label}"
            try:
                analysis, diags = verify_exec(seed)
            except Exception as e:
                dirty.append(
                    {"template": name,
                     "error": f"{type(e).__name__}: {e}"[:200]}
                )
                checked += 1
                continue
            checked += 1
            cov = analysis.donation_coverage
            coverages.append(cov if cov is not None else 0.0)
            errs = [d for d in diags if d.severity.value == "error"]
            if errs or cov != 1.0:
                dirty.append(
                    {"template": name, "coverage": cov,
                     "rules": sorted({d.rule_id for d in errs})}
                )
            else:
                clean += 1
            print(f"  {name}: coverage={cov} errors={len(errs)}")
    return {
        "checked": checked,
        "clean": clean,
        "donation_coverage_min": min(coverages) if coverages else None,
        "dirty": dirty,
    }


def audit_flagship(search_budget: int) -> dict:
    """The searched flagship winner, via the always-on compile pass."""
    from flexflow_tpu.core import AdamOptimizer, FFConfig

    cfg = FFConfig(batch_size=256, search_budget=search_budget)
    m = build_flagship(cfg, 256)
    m.compile(AdamOptimizer(alpha=1e-3), "sparse_categorical_crossentropy")
    rec = (m.search_provenance or {}).get("exec") or {}
    verify = rec.get("verify") or {}
    return {
        "hlo_fingerprint": rec.get("hlo_fingerprint"),
        "program_fingerprint": rec.get("program_fingerprint"),
        "donated_leaves": rec.get("donated_leaves"),
        "donated_bytes": rec.get("donated_bytes"),
        "donation_coverage": rec.get("donation_coverage"),
        "determinism_findings": len(rec.get("determinism_findings") or ()),
        "verify": verify,
        "clean": bool(verify.get("clean")),
        "parallel_degrees": (m.search_provenance or {}).get(
            "parallel_degrees"
        ),
    }


def build_pp8m2_pcg():
    """A deep dense trunk stage-partitioned
    pp8m2 (8 stages x 2 microbatches on the 8-device mesh)."""
    from flexflow_tpu.pcg import ComputationGraphBuilder
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )
    from flexflow_tpu.pcg.pipeline import insert_pipeline_stages

    b = ComputationGraphBuilder()
    x = b.create_input([16, 64], name="x")
    h = x
    for i in range(8):
        h = b.dense(h, 64, name=f"fc{i}")
    pcg = pcg_from_computation_graph(b.graph)
    return insert_pipeline_stages(pcg, num_stages=8, num_microbatches=2)


def audit_pipelined() -> dict:
    from flexflow_tpu.analysis.exec_contract import verify_exec

    analysis, diags = verify_exec(build_pp8m2_pcg())
    rec = _subject_record(analysis, diags)
    rec["plan"] = "pp8m2"
    return rec


def audit_serving() -> dict:
    """Prefill + decode donated programs of the serving LM, with the KV
    cache as the expected-in-place state."""
    from flexflow_tpu.analysis.memory_accounting import ServingMemorySpec
    from flexflow_tpu.serving.model import ServingLMConfig, build_serving_lm
    from flexflow_tpu.serving.program import ServingProgram

    cg, _ = build_serving_lm(ServingLMConfig(), 8, 12)
    prog = ServingProgram(
        cg,
        ServingMemorySpec(max_concurrent_seqs=8, max_seq_len=48),
        params_seed=0,
    )
    out = {}
    for phase, (analysis, diags) in prog.exec_contract().items():
        out[phase] = _subject_record(analysis, diags)
    return out


# -- seeded rule-id fixtures -------------------------------------------------

# three nondeterministic HLO forms, in the optimized-module syntax the
# census parses (XLA-CPU's scatter expander rewrites real float scatters
# into while loops before the final module, so the census is pinned on
# seeded text — the same way the tier-1 unit tests pin it)
_DET001_HLO = {
    "rng-algorithm": (
        "  %rng.1 = u32[4]{0} rng-bit-generator(u64[2]{0} %state), "
        "algorithm=rng_default\n"
    ),
    "nonunique-scatter": (
        "  %scatter.3 = f32[64,16]{1,0} scatter(f32[64,16]{1,0} %acc, "
        "s32[8,1]{1,0} %idx, f32[8,16]{1,0} %upd), "
        "update_window_dims={1}, inserted_window_dims={0}, "
        "scatter_dims_to_operand_dims={0}, index_vector_dim=1, "
        "indices_are_sorted=false, unique_indices=false, "
        "to_apply=%add.clone\n"
    ),
    "unordered-reduction": (
        "  %all-reduce.9 = f32[128]{0} all-reduce(f32[128]{0} %grad), "
        "replica_groups={}, to_apply=%add.clone\n"
    ),
}


def fixtures() -> dict:
    import warnings

    import jax
    import jax.numpy as jnp

    from flexflow_tpu.analysis.exec_contract import (
        analyze_step_program,
        compare_contract_records,
        exec_diagnostics,
        extract_determinism_findings,
    )

    out = {}
    det = {}
    for kind, hlo in _DET001_HLO.items():
        findings = extract_determinism_findings(hlo)
        det[kind] = {
            "tripped": bool(findings)
            and all(f.kind == kind for f in findings),
            "detail": findings[0].detail if findings else None,
        }
    out["DET001"] = det

    _, diag = compare_contract_records(
        {"program_key": "k0", "hlo_fingerprint": "a" * 64},
        {"program_key": "k0", "hlo_fingerprint": "b" * 64},
    )
    out["DET002"] = {
        "tripped": diag is not None and diag.rule_id == "DET002",
        "detail": diag.message[:160] if diag else None,
    }

    # DON001: a REAL compiled program whose donation XLA drops (the
    # donated buffer cannot alias the smaller output)
    def _truncate(x):
        return x[:2]

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lo = jax.jit(_truncate, donate_argnums=(0,)).lower(
            jnp.zeros((512,))
        )
        compiled = lo.compile()
    analysis = analyze_step_program(
        lo, compiled, arg_names=("x",), expected_inplace=(0,)
    )
    diags = exec_diagnostics(analysis)
    out["DON001"] = {
        "tripped": any(d.rule_id == "DON001" for d in diags),
        "detail": next(
            (d.message[:160] for d in diags if d.rule_id == "DON001"), None
        ),
    }

    # DON002: a REAL parameter-update program compiled without donation
    def _update(params, grads):
        return jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * g, params, grads
        )

    p = {"w": jnp.zeros((64, 64))}
    lo = jax.jit(_update).lower(p, p)
    compiled = lo.compile()
    analysis = analyze_step_program(
        lo, compiled, arg_names=("params", "grads"), expected_inplace=(0,)
    )
    diags = exec_diagnostics(analysis)
    out["DON002"] = {
        "tripped": any(d.rule_id == "DON002" for d in diags),
        "detail": next(
            (d.message[:160] for d in diags if d.rule_id == "DON002"), None
        ),
    }
    return out


# -- cross-process fingerprint stability ------------------------------------


def _fingerprint_child() -> int:
    """Child mode: lower + compile the canonical subject in THIS fresh
    process (the module-level bootstrap already forced the mesh) and
    print its contract fingerprints as one JSON line."""
    from flexflow_tpu.analysis.exec_contract import verify_exec
    from flexflow_tpu.compiler.unity_algorithm import enumerate_seeds
    from ffcheck import template_zoo

    model, pcg = template_zoo()[0]  # mlp
    seed = dict(enumerate_seeds(pcg, 8))["dp4xtp1xsp2-ring"]
    analysis, _ = verify_exec(seed)
    print(json.dumps({
        "hlo_fingerprint": analysis.hlo_fingerprint,
        "program_fingerprint": analysis.program_fingerprint,
    }))
    return 0


def audit_cross_process() -> dict:
    runs = []
    for _ in range(2):
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--fingerprint-child"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if proc.returncode != 0:
            return {"stable": False, "error": proc.stderr[-300:]}
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    return {
        "processes": len(runs),
        "stable": all(r == runs[0] for r in runs),
        "hlo_fingerprint": runs[0]["hlo_fingerprint"],
        "program_fingerprint": runs[0]["program_fingerprint"],
    }


def main(argv=None) -> int:
    if "--fingerprint-child" in (argv or sys.argv[1:]):
        return _fingerprint_child()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--round", type=int, default=15)
    ap.add_argument("--out", type=str, default="")
    ap.add_argument("--search-budget", type=int, default=4)
    args = ap.parse_args(argv)
    out_path = args.out or os.path.join(REPO, f"DET_r{args.round:02d}.json")

    print("auditing seed templates x model zoo ...")
    templates = audit_templates()
    print("auditing flagship searched winner ...")
    flagship = audit_flagship(args.search_budget)
    print("auditing pp8m2 pipelined plan ...")
    pipelined = audit_pipelined()
    print("auditing serving prefill/decode ...")
    serving = audit_serving()
    print("running seeded rule fixtures ...")
    fix = fixtures()
    print("checking cross-process fingerprint stability ...")
    xproc = audit_cross_process()

    artifact = {
        "schema": ARTIFACT_SCHEMA,
        "round": args.round,
        "machine": {"devices": 8, "backend": "cpu_virtual_mesh"},
        "templates": templates,
        "flagship_searched": flagship,
        "pipelined_pp8m2": pipelined,
        "serving": serving,
        "fixtures": fix,
        "cross_process": xproc,
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)

    failures = []
    if templates["clean"] != templates["checked"]:
        failures.append(
            f"templates: {templates['checked'] - templates['clean']} of "
            f"{templates['checked']} not clean: {templates['dirty']}"
        )
    if templates["donation_coverage_min"] != 1.0:
        failures.append(
            "templates: donation coverage below 100% "
            f"({templates['donation_coverage_min']})"
        )
    for name, rec in (
        ("flagship", flagship),
        ("pp8m2", pipelined),
        ("serving/prefill", serving["prefill"]),
        ("serving/decode", serving["decode"]),
    ):
        if not rec.get("clean"):
            failures.append(f"{name}: not clean: {rec.get('verify')}")
        if rec.get("donation_coverage") != 1.0:
            failures.append(
                f"{name}: donation coverage {rec.get('donation_coverage')}"
            )
    for rule, rec in (
        [("DET001/" + k, v) for k, v in fix["DET001"].items()]
        + [("DET002", fix["DET002"]), ("DON001", fix["DON001"]),
           ("DON002", fix["DON002"])]
    ):
        if not rec["tripped"]:
            failures.append(f"fixture {rule} did not trip")
    if not xproc.get("stable"):
        failures.append(f"cross-process fingerprint unstable: {xproc}")

    print(
        f"wrote {out_path}: {templates['clean']}/{templates['checked']} "
        "templates clean, flagship coverage "
        f"{flagship['donation_coverage']}, pp8m2 coverage "
        f"{pipelined['donation_coverage']}, serving decode coverage "
        f"{serving['decode']['donation_coverage']}, cross-process stable="
        f"{xproc.get('stable')}"
    )
    for msg in failures:
        print(f"WARNING: {msg}", file=sys.stderr)
    return 2 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
