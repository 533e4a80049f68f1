#!/usr/bin/env python
"""Communication-audit artifact generator (ISSUE 11 acceptance): run the
static communication verification (`analysis/comm_analysis.py`, the
engine behind `ffcheck --comm`) over three subjects on the virtual
8-device CPU mesh and commit the results as COMM_r*.json:

1. the flagship transformer proxy's SEARCHED winner (batch 256 makes the
   search pick a data-parallel plan with real movement edges) — must
   show zero COMM001/COMM002 and a predicted/lowered bytes geomean
   inside the 1.5x acceptance band,
2. the dp2xtp4xsp1 forced-tp seed of the same model — the
   attribute-parallel plan whose weight reshard chains, Combines and
   Reductions exercise every template class; same bars,
3. a seeded over-eager-replication fixture (a hand-built "data parallel"
   plan whose weight replication is implicit and therefore unpriced) —
   must DEMONSTRABLY trip COMM001 with a structured diagnostic naming
   the collective and its bytes.

Usage:
    python tools/comm_audit.py            # writes COMM_r12.json
    python tools/comm_audit.py --round 13 --out COMM_r13.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# repo path + the same virtual 8-device CPU mesh the tier-1 suite runs
# on (tests/conftest.py), set BEFORE jax imports — the shared bootstrap
# all audit CLIs use (tools/audit_env.py)
from audit_env import REPO, bootstrap_virtual_mesh

bootstrap_virtual_mesh(8)

ARTIFACT_SCHEMA = 1
BAND = 1.5  # the acceptance band on the bytes geomean


# ONE flagship-proxy builder shared with the memory audit (running as a
# script puts tools/ at sys.path[0]) — the MEM_r* and COMM_r* artifacts
# measure the same shape family by construction, not by copy-paste
from memory_audit import build_flagship_proxy as build_flagship


def comm_record(prov) -> dict:
    comm = (prov or {}).get("comm") or {}
    verify = comm.get("verify") or {}
    by_rule = {}
    for d in verify.get("diagnostics", []):
        rid = d.get("rule_id", "?")
        by_rule[rid] = by_rule.get(rid, 0) + 1
    return {
        "num_edges": comm.get("num_edges"),
        "num_collectives": comm.get("num_collectives"),
        "census": comm.get("census"),
        "predicted_bytes_total": comm.get("predicted_bytes_total"),
        "matched_bytes_total": comm.get("matched_bytes_total"),
        "unmatched_collectives": comm.get("unmatched_collectives"),
        "host_transfers": comm.get("host_transfers"),
        "bytes_geomean": comm.get("bytes_geomean"),
        "clean": verify.get("clean"),
        "errors": verify.get("errors"),
        "warnings": verify.get("warnings"),
        "diagnostics_by_rule": by_rule,
        "parallel_degrees": (prov or {}).get("parallel_degrees"),
    }


def run_subject(batch, **cfg_kwargs) -> dict:
    from flexflow_tpu.core import AdamOptimizer, FFConfig

    cfg = FFConfig(batch_size=batch, plan_audit=True, hbm_gb=16.0,
                   **cfg_kwargs)
    m = build_flagship(cfg, batch)
    m.compile(AdamOptimizer(alpha=1e-3), "sparse_categorical_crossentropy")
    return comm_record(m.search_provenance)


def overeager_fixture() -> dict:
    """The seeded COMM001 fixture: a hand-built dp plan whose weight
    replication is implicit (no Replicate movement edge), so XLA's
    per-step weight-gradient all-reduce is communication the search
    never priced. (The PCG verifier also flags the structural side as
    PCG003 — structure and lowering catch the same lie independently.)"""
    from flexflow_tpu.analysis.comm_analysis import verify_comm
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.parallel_tensor_shape import (
        ParallelTensorDims,
        ParallelTensorShape,
        ShardParallelDim,
    )
    from flexflow_tpu.pcg.machine_view import MachineSpecification
    from flexflow_tpu.pcg.parallel_computation_graph_builder import (
        ParallelComputationGraphBuilder,
    )

    def pts(dims):
        return ParallelTensorShape(
            ParallelTensorDims(
                tuple(ShardParallelDim(s, d) for s, d in dims), 1, 1
            ),
            DataType.FLOAT,
        )

    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(pts([(128, 1), (64, 1)]), name="x")
    xs = b.parallel_partition(x, dim=0, degree=8, name="dp_shard")
    b.parallel_combine(
        b.dense(xs, 256, use_bias=False, name="ff"), dim=0, degree=8,
        name="unshard",
    )
    spec = MachineSpecification(1, 1, 8, 1.0, 2.0)
    analysis, diags = verify_comm(b.graph, None, machine_spec=spec)
    comm001 = [d for d in diags if d.rule_id == "COMM001"]
    return {
        "tripped_rules": sorted({d.rule_id for d in diags}),
        "comm001_count": len(comm001),
        "comm001_message": comm001[0].message if comm001 else None,
        "unmatched_bytes": int(
            sum(
                c.bytes
                for c in analysis.unmatched
                if c.bytes >= analysis.bytes_floor
            )
        ),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--round", type=int, default=12)
    ap.add_argument("--out", type=str, default="")
    ap.add_argument("--search-budget", type=int, default=4)
    args = ap.parse_args(argv)
    out_path = args.out or os.path.join(REPO, f"COMM_r{args.round:02d}.json")

    flagship = run_subject(256, search_budget=args.search_budget)
    seed = run_subject(
        16, search_budget=1, force_strategy_seed="dp2xtp4xsp1"
    )
    fixture = overeager_fixture()

    artifact = {
        "schema": ARTIFACT_SCHEMA,
        "round": args.round,
        "machine": {"devices": 8, "backend": "cpu_virtual_mesh"},
        "band": BAND,
        "flagship_searched": flagship,
        "forced_tp_seed": seed,
        "overeager_fixture": fixture,
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)

    failures = []
    for name, rec in (("flagship", flagship), ("forced_tp_seed", seed)):
        by_rule = rec["diagnostics_by_rule"]
        if by_rule.get("COMM001") or by_rule.get("COMM002"):
            failures.append(f"{name}: COMM001/COMM002 errors: {by_rule}")
        g = rec["bytes_geomean"]
        if g is None or not (1 / BAND <= g <= BAND):
            failures.append(
                f"{name}: bytes geomean {g} outside the {BAND}x band"
            )
    if not fixture["comm001_count"]:
        failures.append("over-eager fixture did not trip COMM001")
    print(
        f"wrote {out_path}: flagship geomean "
        f"{flagship['bytes_geomean']} ({flagship['num_collectives']} "
        f"collectives / {flagship['num_edges']} edges), seed geomean "
        f"{seed['bytes_geomean']} ({seed['num_collectives']} / "
        f"{seed['num_edges']}), fixture COMM001 x"
        f"{fixture['comm001_count']}"
    )
    for msg in failures:
        print(f"WARNING: {msg}", file=sys.stderr)
    return 2 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
