#!/usr/bin/env python
"""Memory-audit artifact generator (ISSUE 10 acceptance): run a searched
compile of the flagship transformer proxy on the virtual 8-device CPU
mesh with `--plan-audit` + `--hbm-gb`, and commit the static memory
analysis's predicted per-device peaks beside XLA's own compiled
`memory_analysis()` bytes, with their predicted/measured geomean ratio.

Usage:
    python tools/memory_audit.py            # writes MEM_r11.json
    python tools/memory_audit.py --round 12 --out MEM_r12.json
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


def build_flagship_proxy(cfg, batch=16):
    """The CPU-mesh flagship proxy: a 2-block pre-residual transformer at
    the tier-1 scale (the same shape family the search-perf and overlap
    artifacts measure). tools/comm_audit.py imports this builder so the
    MEM_r* and COMM_r* artifacts stay on one shape family by
    construction."""
    from flexflow_tpu.core import FFModel

    m = FFModel(cfg)
    seq, embed, heads = 16, 64, 4
    x = m.create_tensor([batch, seq, embed], name="x")
    h = x
    for i in range(2):
        attn = m.multihead_attention(
            h, h, h, embed_dim=embed, num_heads=heads, name=f"attn{i}"
        )
        h = m.layer_norm(m.add(h, attn), axes=[-1], name=f"ln{i}a")
        ff = m.dense(h, 4 * embed, name=f"ff{i}a")
        ff = m.gelu(ff)
        ff = m.dense(ff, embed, name=f"ff{i}b")
        h = m.layer_norm(m.add(h, ff), axes=[-1], name=f"ln{i}b")
    m.dense(h, 32, name="head")
    return m


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--round", type=int, default=11)
    ap.add_argument("--out", type=str, default="")
    ap.add_argument("--hbm-gb", type=float, default=16.0)
    ap.add_argument("--search-budget", type=int, default=4)
    args = ap.parse_args(argv)
    out_path = args.out or os.path.join(
        REPO, f"MEM_r{args.round:02d}.json"
    )

    from flexflow_tpu.core import AdamOptimizer, FFConfig

    cfg = FFConfig(
        batch_size=16,
        search_budget=args.search_budget,
        plan_audit=True,  # the cross-check rides the plan-audit gate
        hbm_gb=args.hbm_gb,
    )
    m = build_flagship_proxy(cfg)
    # Adam: the optimizer-slot term (m/v) is part of what is being audited
    m.compile(AdamOptimizer(alpha=1e-3), "sparse_categorical_crossentropy")
    prov = m.search_provenance or {}
    mem = prov.get("memory") or {}
    if "xla" not in mem:
        print(
            "memory cross-check missing from provenance: "
            + str(mem.get("xla_error", "no searched compile ran")),
            file=sys.stderr,
        )
        return 1
    artifact = {
        "schema": ARTIFACT_SCHEMA,
        "round": args.round,
        "subject": "flagship_proxy_2block_transformer_cpu8",
        "machine": {"devices": 8, "backend": "cpu_virtual_mesh"},
        "hbm_gb": args.hbm_gb,
        "memory": mem,
        "verify": prov.get("verify"),
        "search": {
            "estimated_ms": prov.get("estimated_ms"),
            "explored": prov.get("explored"),
            "evaluations": prov.get("evaluations"),
        },
    }
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)
    ratio = (
        mem.get("full_mesh_over_xla_geomean")
        or mem.get("predicted_over_xla_geomean")
    )
    print(
        f"wrote {out_path}: predicted/XLA per-device geomean {ratio} "
        f"(full-mesh peaks "
        f"{sorted(set(mem.get('predicted_peak_bytes_full_mesh', mem['predicted_peak_bytes_per_device']).values()))} B, "
        f"XLA {mem['xla_per_device_bytes']} B)"
    )
    # the acceptance bar: within 1.5x geomean either direction
    if ratio is None or not (1 / 1.5 <= ratio <= 1.5):
        print(
            f"WARNING: geomean {ratio} outside the 1.5x acceptance band",
            file=sys.stderr,
        )
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
