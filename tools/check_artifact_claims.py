"""Claims hygiene: cross-check README.md's numeric claims against the
committed artifacts (AB_r*.json, AUDIT_r*.json, ...).

Every checked claim is anchored to the ROUND NUMBER the README text itself
names ("AB_r05.json", "Round-5 highlights"), so the
checker stays valid when later rounds land: a round-5 claim is forever
checked against the round-5 artifact. A claim whose anchor text disappears
from the README fails too — silently dropping a checked claim is how stale
numbers sneak back in.

Run directly (exit 1 on any mismatch) or via tests/test_artifact_claims.py,
which puts it in the tier-1 suite.
"""

from __future__ import annotations

import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Callable, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # live claims import flexflow_tpu.analysis
    sys.path.insert(0, REPO)


def load_ab(round_no: int) -> Optional[list]:
    path = os.path.join(REPO, f"AB_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_fused_bench(round_no: int) -> Optional[dict]:
    """Fused-dispatch artifact (`bench.py --fused` output, committed as
    BENCH_FUSED_r*.json)."""
    path = os.path.join(REPO, f"BENCH_FUSED_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)


def load_overlap_bench(round_no: int) -> Optional[dict]:
    """Compute/communication-overlap artifact (`bench.py --overlap`
    output, committed as BENCH_OVERLAP_r*.json — its own family like
    BENCH_FUSED_r*, so driver headline captures never collide)."""
    path = os.path.join(REPO, f"BENCH_OVERLAP_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)


def load_costdb(round_no: int) -> Optional[dict]:
    """Persistent cost-database artifact (`bench.py --cost-db` output,
    committed as BENCH_COSTDB_r*.json — its own family like
    BENCH_FUSED_r*, so driver headline captures never collide)."""
    path = os.path.join(REPO, f"BENCH_COSTDB_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)


def load_chaos(round_no: int) -> Optional[dict]:
    """Elastic-runtime artifact (`bench.py --chaos` output, committed as
    CHAOS_r*.json — its own family like BENCH_FUSED_r*, so driver headline
    captures never collide)."""
    path = os.path.join(REPO, f"CHAOS_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)


def load_mem(round_no: int) -> Optional[dict]:
    """Static memory-audit artifact (`tools/memory_audit.py` output,
    committed as MEM_r*.json — its own family like BENCH_FUSED_r*, so
    driver headline captures never collide)."""
    path = os.path.join(REPO, f"MEM_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_comm(round_no: int) -> Optional[dict]:
    """Static communication-audit artifact (`tools/comm_audit.py` output,
    committed as COMM_r*.json — its own family like MEM_r*, so driver
    headline captures never collide)."""
    path = os.path.join(REPO, f"COMM_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_serve(round_no: int) -> Optional[dict]:
    """Serving-engine artifact (`bench.py --serving` output, committed as
    SERVE_r*.json — its own family like MEM_r*/COMM_r*, so driver headline
    captures never collide)."""
    path = os.path.join(REPO, f"SERVE_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)


def load_pipe(round_no: int) -> Optional[dict]:
    """Pipeline-parallelism artifact (`bench.py --pipeline` output,
    committed as PIPE_r*.json — its own family like SERVE_r*/MEM_r*, so
    driver headline captures never collide)."""
    path = os.path.join(REPO, f"PIPE_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)


def load_det(round_no: int) -> Optional[dict]:
    """Execution-contract audit artifact (`tools/exec_audit.py` output,
    committed as DET_r*.json — its own family like MEM_r*/COMM_r*, so
    driver headline captures never collide)."""
    path = os.path.join(REPO, f"DET_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_slice(round_no: int) -> Optional[dict]:
    """Multi-slice search artifact (`bench.py --multislice` output,
    committed as SLICE_r*.json — its own family like PIPE_r*/SERVE_r*, so
    driver headline captures never collide)."""
    path = os.path.join(REPO, f"SLICE_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)


def load_drift(round_no: int) -> Optional[dict]:
    """Drift-telemetry artifact (`bench.py --drift` output, committed as
    DRIFT_r*.json — its own family like PIPE_r*/SLICE_r*, so driver
    headline captures never collide)."""
    path = os.path.join(REPO, f"DRIFT_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        d = json.load(f)
    return d.get("parsed", d)


def load_trn(round_no: int) -> Optional[dict]:
    """Plan-transition audit artifact (`tools/transition_audit.py`
    output, committed as TRN_r*.json — its own family like
    DET_r*/DRIFT_r*, so driver headline captures never collide)."""
    path = os.path.join(REPO, f"TRN_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def load_audit(round_no: int) -> Optional[dict]:
    """Plan-audit + run-health artifact (`bench.py --plan-audit` output,
    committed as AUDIT_r*.json by the round that generated it)."""
    path = os.path.join(REPO, f"AUDIT_r{round_no:02d}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _artifact_field(loader: Callable[[int], Optional[dict]],
                    path_fn: Callable[[dict], object]):
    def get(round_no: int) -> Optional[float]:
        d = loader(round_no)
        if d is None:
            return None  # artifact genuinely absent: claim is skipped
        try:
            v = path_fn(d)
            if v is None:
                raise KeyError("field is null")
        except (KeyError, TypeError, IndexError):
            # the artifact EXISTS but lacks the claimed field (e.g. bench
            # wrote dp_seed_error instead of dp_seed): the README number is
            # unverifiable and must FAIL, not silently skip — NaN compares
            # unequal to everything, so check() reports a mismatch
            return float("nan")
        return float(v)

    return get


def _audit_field(path_fn: Callable[[dict], object]):
    # late-bound loader so tests can monkeypatch load_audit
    return _artifact_field(lambda r: load_audit(r), path_fn)


def _fused_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_fused_bench(r), path_fn)


def _overlap_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_overlap_bench(r), path_fn)


def _chaos_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_chaos(r), path_fn)


def _costdb_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_costdb(r), path_fn)


def _mem_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_mem(r), path_fn)


def _comm_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_comm(r), path_fn)


def _serve_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_serve(r), path_fn)


def _pipe_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_pipe(r), path_fn)


def _det_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_det(r), path_fn)


def _slice_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_slice(r), path_fn)


def _drift_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_drift(r), path_fn)


def _trn_field(path_fn: Callable[[dict], object]):
    return _artifact_field(lambda r: load_trn(r), path_fn)


def ab_subject(ab: list, model: str) -> Optional[dict]:
    for r in ab:
        if isinstance(r, dict) and r.get("model") == model:
            return r
    return None


def ab_decisive_inversions(ab: list) -> int:
    # single source of truth for the decisive count: the same helper the
    # A/B merge uses to write the artifact's narrative note
    from merge_ab import summarize_inversions

    return summarize_inversions(ab)[1]


@dataclass
class Claim:
    """One README numeric claim. `pattern` must expose group 'round' (the
    artifact round the claim is anchored to) and group 'val' (the number);
    `artifact_value(round)` returns the ground truth or None when the
    artifact is missing (claim is then skipped, not failed)."""

    label: str
    pattern: str
    artifact_value: Callable[[int], Optional[float]]


def _ab_speedup(model: str):
    def get(round_no: int) -> Optional[float]:
        ab = load_ab(round_no)
        if ab is None:
            return None
        r = ab_subject(ab, model)
        return None if r is None else float(r["value"])

    return get


def _ab_inversions(round_no: int) -> Optional[float]:
    ab = load_ab(round_no)
    return None if ab is None else float(ab_decisive_inversions(ab))


CLAIMS = [
    Claim(
        "A/B transformer searched win",
        r"Round-(?P<round>\d+) highlights.{0,400}?"
        r"beating measured DP by (?P<val>[\d.]+)x",
        _ab_speedup("transformer"),
    ),
    Claim(
        "A/B dlrm searched win",
        r"Round-(?P<round>\d+) highlights.{0,500}?"
        r"dlrm \(wide embeddings\) (?P<val>[\d.]+)x",
        _ab_speedup("dlrm"),
    ),
    Claim(
        "A/B mlp searched win",
        r"Round-(?P<round>\d+) highlights.{0,600}?"
        r"MLP_Unify (?P<val>[\d.]+)x",
        _ab_speedup("mlp"),
    ),
    Claim(
        "decisive rank-inversion count",
        r"(?P<val>\d+) decisive rank-inversion.{0,200}?"
        r"`AB_r0?(?P<round>\d+)\.json`",
        _ab_inversions,
    ),
    # plan-audit / run-health claims (ISSUE 3): the audit numbers the
    # README quotes must match the committed AUDIT_r*.json they name
    Claim(
        "plan-audit searched op geomean",
        r"searched\s+winner's\s+per-op\s+geomean\s+measured/predicted\s+"
        r"ratio\s+is\s+\*\*(?P<val>[\d.]+)\*\*\s+"
        r"\(`AUDIT_r0?(?P<round>\d+)\.json`",
        _audit_field(
            lambda d: d["searched"]["plan_audit"]["summary"][
                "op_geomean_ratio"
            ]
        ),
    ),
    Claim(
        "plan-audit dp movement geomean",
        r"dp\s+seed's\s+movement\s+edges\s+miss\s+by\s+a\s+geomean\s+of\s+"
        r"\*\*(?P<val>[\d.]+)x\*\*\s+\(`AUDIT_r0?(?P<round>\d+)\.json`",
        _audit_field(
            lambda d: d["dp_seed"]["plan_audit"]["summary"][
                "movement_geomean_ratio"
            ]
        ),
    ),
    Claim(
        "plan-audit worst-op misprediction",
        r"worst-audited\s+op\s+misses\s+by\s+\*\*(?P<val>[\d.]+)x\*\*\s+"
        r"\(`AUDIT_r0?(?P<round>\d+)\.json`",
        _audit_field(
            lambda d: d["dp_seed"]["plan_audit"]["summary"]["worst_ops"][0][
                "ratio"
            ]
        ),
    ),
    Claim(
        "health demo skipped steps",
        r"skipped\s+\*\*(?P<val>\d+)\*\*\s+poisoned\s+step\(s\)\s+"
        r"\(`AUDIT_r0?(?P<round>\d+)\.json`",
        _audit_field(lambda d: d["health_demo"]["skipped_steps"]),
    ),
    # fused-dispatch claims (ISSUE 5): the committed `bench.py --fused`
    # capture backs the step-fusion README numbers — the dispatch-bound
    # proxy's fused speedup and images/s, the per-step dispatch overhead
    # it amortizes, the fused flagship step, and the honest compute-bound
    # counter-example (AlexNet-on-CPU gains nothing from fusing)
    Claim(
        "fused proxy speedup",
        r"dispatch-bound\s+proxy\s+sustains\s+\*\*(?P<val>[\d.]+)x\*\*\s+"
        r"the\s+per-step\s+images/s\s+\(`BENCH_FUSED_r0?(?P<round>\d+)\.json`",
        _fused_field(lambda d: d["proxy_fused_speedup"]),
    ),
    Claim(
        "fused proxy images/s",
        r"\*\*(?P<val>[\d.]+)\s+images/s\*\*\s+fused\s+vs\s+"
        r"\*\*[\d.]+\*\*\s+per-step\s+"
        r"\(`BENCH_FUSED_r0?(?P<round>\d+)\.json`",
        _fused_field(lambda d: d["proxy_fused_images_per_s"]),
    ),
    Claim(
        "per-step proxy images/s",
        r"\*\*[\d.]+\s+images/s\*\*\s+fused\s+vs\s+"
        r"\*\*(?P<val>[\d.]+)\*\*\s+per-step\s+"
        r"\(`BENCH_FUSED_r0?(?P<round>\d+)\.json`",
        _fused_field(lambda d: d["proxy_images_per_s"]),
    ),
    Claim(
        "fused proxy dispatch overhead",
        r"\*\*(?P<val>[\d.]+)\s+ms\*\*\s+of\s+per-step\s+dispatch\s+"
        r"overhead\s+\(`BENCH_FUSED_r0?(?P<round>\d+)\.json`",
        _fused_field(lambda d: d["proxy_dispatch_overhead_ms"]),
    ),
    Claim(
        "fused flagship step ms",
        r"scaled\s+flagship\s+window\s+runs\s+\*\*(?P<val>[\d.]+)\s+ms\*\*"
        r"/step\s+fused\s+vs\s+\*\*[\d.]+\s+ms\*\*\s+per-step\s+"
        r"\(`BENCH_FUSED_r0?(?P<round>\d+)\.json`",
        _fused_field(lambda d: d["fused_flagship"]["fused_step_ms"]),
    ),
    Claim(
        "per-step flagship step ms",
        r"ms\*\*/step\s+fused\s+vs\s+\*\*(?P<val>[\d.]+)\s+ms\*\*\s+"
        r"per-step\s+\(`BENCH_FUSED_r0?(?P<round>\d+)\.json`",
        _fused_field(lambda d: d["fused_flagship"]["step_ms"]),
    ),
    Claim(
        "compute-bound counter-example",
        r"CPU-host\s+AlexNet\s+fuses\s+at\s+\*\*(?P<val>[\d.]+)x\*\*\s+"
        r"\(`BENCH_FUSED_r0?(?P<round>\d+)\.json`",
        _fused_field(lambda d: d["fused_speedup"]),
    ),
    # overlap-lowering claims (ISSUE 6): the committed `bench.py --overlap`
    # capture backs the README's fused collective-matmul numbers — the
    # bandwidth-bound proxy's fused speedup and both sides of the A/B, the
    # dispatch-bound counter-example where the ring loses, and the DP's
    # chosen-overlap edge count on the tp4 flagship seed
    Claim(
        "overlap proxy fused speedup",
        r"bandwidth-bound\s+proxy\s+runs\s+\*\*(?P<val>[\d.]+)x\*\*\s+"
        r"faster\s+fused.{0,140}?\(`BENCH_OVERLAP_r0?(?P<round>\d+)\.json`",
        _overlap_field(lambda d: d["agmm_proxy"]["speedup"]),
    ),
    Claim(
        "overlap proxy fused ms",
        r"\*\*(?P<val>[\d.]+)\s+ms\*\*\s+fused\s+vs\s+\*\*[\d.]+\s+ms\*\*"
        r"\s+serial\s+\(`BENCH_OVERLAP_r0?(?P<round>\d+)\.json`",
        _overlap_field(lambda d: d["agmm_proxy"]["fused_ms"]),
    ),
    Claim(
        "overlap proxy serial ms",
        r"\*\*[\d.]+\s+ms\*\*\s+fused\s+vs\s+\*\*(?P<val>[\d.]+)\s+ms\*\*"
        r"\s+serial\s+\(`BENCH_OVERLAP_r0?(?P<round>\d+)\.json`",
        _overlap_field(lambda d: d["agmm_proxy"]["serial_ms"]),
    ),
    Claim(
        "overlap dispatch-bound counter-example",
        r"dispatch-bound\s+counter-example\s+rings\s+at\s+"
        r"\*\*(?P<val>[\d.]+)x\*\*\s+\(`BENCH_OVERLAP_r0?(?P<round>\d+)\.json`",
        _overlap_field(lambda d: d["agmm_small_counter"]["speedup"]),
    ),
    Claim(
        "overlap DP chosen edges",
        r"selects\s+the\s+overlapped\s+entry\s+for\s+\*\*(?P<val>\d+)\*\*\s+"
        r"movement\s+edges\s+of\s+the\s+tp4\s+flagship\s+seed\s+"
        r"\(`BENCH_OVERLAP_r0?(?P<round>\d+)\.json`",
        _overlap_field(
            lambda d: d["search"]["seeds"]["dp2xtp4xsp1"]["chosen_edges"]
        ),
    ),
    # elastic-runtime claims (ISSUE 7): the committed `bench.py --chaos`
    # capture backs the README's checkpoint-overhead, kill-step, and
    # recovery-wall-clock numbers
    Claim(
        "chaos async checkpoint step ms",
        r"runs\s+\*\*(?P<val>[\d.]+)\s+ms\*\*/step\s+with\s+async\s+"
        r"checkpointing.{0,120}?\(`CHAOS_r0?(?P<round>\d+)\.json`",
        _chaos_field(lambda d: d["checkpoint_overhead"]["async_step_ms"]),
    ),
    Claim(
        "chaos base step ms",
        r"vs\s+\*\*(?P<val>[\d.]+)\s+ms\*\*/step\s+with\s+checkpointing\s+"
        r"off\s+\(`CHAOS_r0?(?P<round>\d+)\.json`",
        _chaos_field(lambda d: d["checkpoint_overhead"]["base_step_ms"]),
    ),
    Claim(
        "chaos sync checkpoint overhead",
        r"blocking\s+synchronous\s+path\s+costs\s+\*\*(?P<val>[\d.]+)%\*\*"
        r".{0,80}?\(`CHAOS_r0?(?P<round>\d+)\.json`",
        _chaos_field(
            lambda d: d["checkpoint_overhead"]["sync_overhead_pct"]
        ),
    ),
    Claim(
        "chaos kill step",
        r"kills\s+the\s+fused\s+run\s+mid-window\s+at\s+step\s+"
        r"\*\*(?P<val>\d+)\*\*\s*\(`CHAOS_r0?(?P<round>\d+)\.json`",
        _chaos_field(lambda d: d["resume"]["killed_at_step"]),
    ),
    Claim(
        "chaos recovery seconds",
        r"re-searches,\s+re-shards,\s+and\s+restarts\s+in\s+"
        r"\*\*(?P<val>[\d.]+)\s+s\*\*\s+\(`CHAOS_r0?(?P<round>\d+)\.json`",
        _chaos_field(lambda d: d["recovery"]["recovery_seconds"]),
    ),
    # fault-domain supervision claims (ISSUE 8): the committed `bench.py
    # --chaos-soak` capture backs the README's schedule count, bitwise
    # recovery tally, watchdog budget, and integrity-fallback step
    Claim(
        "chaos soak schedules per backend",
        r"runs\s+\*\*(?P<val>\d+)\*\*\s+seeded\s+fault\s+schedules\s+per\s+"
        r"backend.{0,400}?\(`CHAOS_r0?(?P<round>\d+)\.json`",
        _chaos_field(lambda d: d["soak"]["dp"]["n_schedules"]),
    ),
    Claim(
        "chaos soak bitwise recoveries",
        r"\*\*(?P<val>\d+)\*\*/10\s+faulted\s+runs\s+recover\s+to\s+"
        r"bitwise-identical.{0,200}?\(`CHAOS_r0?(?P<round>\d+)\.json`,\s*"
        r"`total_bitwise`",
        _chaos_field(lambda d: d["total_bitwise"]),
    ),
    Claim(
        "chaos soak watchdog budget ms",
        r"fires\s+against\s+a\s+\*\*(?P<val>[\d.]+)\s+ms\*\*\s+budget\s+"
        r"\(`CHAOS_r0?(?P<round>\d+)\.json`,\s*`watchdog\.budget_ms`",
        _chaos_field(lambda d: d["watchdog"]["budget_ms"]),
    ),
    Claim(
        "chaos soak integrity fallback step",
        r"falls\s+back\s+to\s+step\s+\*\*(?P<val>\d+)\*\*\s+"
        r"\(`CHAOS_r0?(?P<round>\d+)\.json`\)",
        _chaos_field(lambda d: d["integrity_fallback"]["restored_step"]),
    ),
    # persistent cost-database claims (ISSUE 9): the committed `bench.py
    # --cost-db` capture backs the README's warm-store speedups, the
    # warm-arm measurement count, and the correction-factor calibration
    Claim(
        "cost-db warm search speedup",
        r"warm-store\s+repeat\s+search\s+runs\s+\*\*(?P<val>[\d.]+)x\*\*\s+"
        r"faster\s+end-to-end.{0,160}?"
        r"\(`BENCH_COSTDB_r0?(?P<round>\d+)\.json`",
        _costdb_field(lambda d: d["warm_speedup_total"]),
    ),
    Claim(
        "cost-db warm leaf-cost speedup",
        r"\*\*(?P<val>[\d.]+)x\*\*\s+on\s+the\s+measurement-bound\s+"
        r"leaf-cost\s+phase\s+\(`BENCH_COSTDB_r0?(?P<round>\d+)\.json`",
        _costdb_field(lambda d: d["warm_speedup_leaf_cost"]),
    ),
    Claim(
        "cost-db warm profile calls",
        r"\*\*(?P<val>\d+)\*\*\s+profile_fn\s+calls\s+in\s+the\s+warm\s+"
        r"process\s+\(`BENCH_COSTDB_r0?(?P<round>\d+)\.json`",
        _costdb_field(lambda d: d["warm"]["profile_calls"]),
    ),
    Claim(
        "cost-db audit geomean before correction",
        r"measured/analytic\s+geomean\s+from\s+\*\*(?P<val>[\d.]+)\*\*\s+"
        r"to\s+\*\*[\d.]+\*\*\s+\(`BENCH_COSTDB_r0?(?P<round>\d+)\.json`",
        _costdb_field(
            lambda d: d["correction"]["audit_ratio_geomean_before"]
        ),
    ),
    # static memory-audit claims (ISSUE 10): the committed
    # `tools/memory_audit.py` capture backs the README's predicted-vs-XLA
    # per-device memory calibration numbers
    Claim(
        "memory-audit predicted/XLA geomean",
        r"geomean\s+ratio\s+to\s+XLA's\s+compiled\s+per-device\s+memory\s+"
        r"is\s+\*\*(?P<val>[\d.]+)\*\*\s+\(`MEM_r0?(?P<round>\d+)\.json`",
        _mem_field(lambda d: d["memory"]["full_mesh_over_xla_geomean"]),
    ),
    Claim(
        "memory-audit predicted peak MiB",
        r"full-mesh\s+predicted\s+peak\s+of\s+\*\*(?P<val>[\d.]+)\s+MiB\*\*"
        r"/device.{0,120}?\(`MEM_r0?(?P<round>\d+)\.json`",
        _mem_field(
            lambda d: max(
                d["memory"]["predicted_peak_bytes_full_mesh"].values()
            )
            / 2**20
        ),
    ),
    Claim(
        "memory-audit XLA compiled MiB",
        r"vs\s+\*\*(?P<val>[\d.]+)\s+MiB\*\*\s+compiled"
        r".{0,120}?\(`MEM_r0?(?P<round>\d+)\.json`",
        _mem_field(lambda d: d["memory"]["xla_per_device_bytes"] / 2**20),
    ),
    # static communication-audit claims (ISSUE 11): the committed
    # `tools/comm_audit.py` capture backs the README's census sizes,
    # predicted/lowered bytes geomeans, and the over-eager-replication
    # fixture's unpredicted bytes
    Claim(
        "comm-audit flagship bytes geomean",
        r"searched\s+winner's\s+predicted/lowered\s+bytes\s+geomean\s+is\s+"
        r"\*\*(?P<val>[\d.]+)\*\*.{0,120}?\(`COMM_r0?(?P<round>\d+)\.json`",
        _comm_field(lambda d: d["flagship_searched"]["bytes_geomean"]),
    ),
    Claim(
        "comm-audit forced-tp seed bytes geomean",
        r"forced-tp\s+seed's\s+geomean\s+is\s+\*\*(?P<val>[\d.]+)\*\*\s+"
        r"over\s+\*\*\d+\*\*\s+collectives\s+"
        r"\(`COMM_r0?(?P<round>\d+)\.json`",
        _comm_field(lambda d: d["forced_tp_seed"]["bytes_geomean"]),
    ),
    Claim(
        "comm-audit forced-tp seed collective count",
        r"forced-tp\s+seed's\s+geomean\s+is\s+\*\*[\d.]+\*\*\s+over\s+"
        r"\*\*(?P<val>\d+)\*\*\s+collectives\s+"
        r"\(`COMM_r0?(?P<round>\d+)\.json`",
        _comm_field(lambda d: d["forced_tp_seed"]["num_collectives"]),
    ),
    Claim(
        "comm-audit fixture unpredicted KiB",
        r"trips\s+COMM001\s+on\s+\*\*(?P<val>\d+)\s+KiB\*\*\s+of\s+"
        r"unpredicted\s+gradient\s+all-reduce\s+"
        r"\(`COMM_r0?(?P<round>\d+)\.json`",
        _comm_field(
            lambda d: d["overeager_fixture"]["unmatched_bytes"] / 1024
        ),
    ),
    # serving-engine claims (ISSUE 12): the committed `bench.py --serving`
    # capture backs the README's static-verdict, continuous-vs-static A/B,
    # and open-loop latency/SLO numbers
    Claim(
        "serving static max-sequences verdict",
        r"`static_max_sequences`\s+\*\*(?P<val>\d+)\*\*\s+"
        r"\(`SERVE_r0?(?P<round>\d+)\.json`",
        _serve_field(lambda d: d["verdict"]["static_max_sequences"]),
    ),
    Claim(
        "serving continuous-over-static speedup",
        r"continuous\s+sustains\s+\*\*(?P<val>[\d.]+)x\*\*\s+static\s+"
        r"requests/s\s+\(`SERVE_r0?(?P<round>\d+)\.json`",
        _serve_field(lambda d: d["ab"]["continuous_over_static"]),
    ),
    Claim(
        "serving continuous requests/s",
        r"static\s+requests/s\s+\(`SERVE_r0?(?P<round>\d+)\.json`\)\s+—\s+"
        r"\*\*(?P<val>[\d.]+)\*\*\s+vs\s+\*\*[\d.]+\*\*\s+requests/s",
        _serve_field(lambda d: d["ab"]["continuous"]["requests_per_s"]),
    ),
    Claim(
        "serving static requests/s",
        r"static\s+requests/s\s+\(`SERVE_r0?(?P<round>\d+)\.json`\)\s+—\s+"
        r"\*\*[\d.]+\*\*\s+vs\s+\*\*(?P<val>[\d.]+)\*\*\s+requests/s",
        _serve_field(lambda d: d["ab"]["static"]["requests_per_s"]),
    ),
    Claim(
        "serving open-loop sustained requests/s",
        r"sustained\s+\*\*(?P<val>[\d.]+)\*\*\s+requests/s\s+"
        r"\(`SERVE_r0?(?P<round>\d+)\.json`",
        _serve_field(lambda d: d["open_loop"]["sustained_requests_per_s"]),
    ),
    Claim(
        "serving open-loop p50 ms/token",
        r"p50/p99\s+ms/token\s+of\s+\*\*(?P<val>[\d.]+)\*\*/\*\*[\d.]+\*\*"
        r".{0,120}?\(`SERVE_r0?(?P<round>\d+)\.json`",
        _serve_field(lambda d: d["open_loop"]["p50_ms_per_token"]),
    ),
    Claim(
        "serving open-loop p99 ms/token",
        r"p50/p99\s+ms/token\s+of\s+\*\*[\d.]+\*\*/\*\*(?P<val>[\d.]+)\*\*"
        r".{0,120}?\(`SERVE_r0?(?P<round>\d+)\.json`",
        _serve_field(lambda d: d["open_loop"]["p99_ms_per_token"]),
    ),
    Claim(
        "serving open-loop SLO violations",
        r"\*\*(?P<val>\d+)\*\*\s+SLO\s+violations\s+at\s+the\s+"
        r"50\s+ms/token\s+target\s+\(`SERVE_r0?(?P<round>\d+)\.json`",
        _serve_field(lambda d: d["open_loop"]["slo_violations"]),
    ),
    # pipeline-parallelism claims (ISSUE 13): the committed
    # `bench.py --pipeline` capture backs the README's worked HBM-drop
    # table, the bubble prediction/measurement, and the memory cross-check
    Claim(
        "pipeline seed-table flat-dp step ms",
        r"`seed_table`\s+in\s+`PIPE_r0?(?P<round>\d+)\.json`\):.*?"
        r"\|\s*`dp8xtp1xsp1`[^|]*\|\s*(?P<val>[\d.]+)\s*\|",
        _pipe_field(lambda d: d["seed_table"]["dp8xtp1xsp1"]["estimated_ms"]),
    ),
    Claim(
        "pipeline seed-table flat-dp peak MiB",
        r"`seed_table`\s+in\s+`PIPE_r0?(?P<round>\d+)\.json`\):.*?"
        r"\|\s*`dp8xtp1xsp1`[^|]*\|\s*[\d.]+\s*\|\s*(?P<val>[\d.]+)\s*MiB",
        _pipe_field(
            lambda d: d["seed_table"]["dp8xtp1xsp1"]["peak_mib_per_device"]
        ),
    ),
    Claim(
        "pipeline seed-table flat-tp peak MiB",
        r"`seed_table`\s+in\s+`PIPE_r0?(?P<round>\d+)\.json`\):.*?"
        r"\|\s*`dp1xtp8xsp1`[^|]*\|\s*[\d.]+\s*\|\s*(?P<val>[\d.]+)\s*MiB",
        _pipe_field(
            lambda d: d["seed_table"]["dp1xtp8xsp1"]["peak_mib_per_device"]
        ),
    ),
    Claim(
        "pipeline seed-table pp8 peak MiB",
        r"`seed_table`\s+in\s+`PIPE_r0?(?P<round>\d+)\.json`\):.*?"
        r"\|\s*`pp8m2`[^|]*\|\s*[\d.]+\s*\|\s*\*\*(?P<val>[\d.]+)\s*MiB\*\*",
        _pipe_field(lambda d: d["seed_table"]["pp8m2"]["peak_mib_per_device"]),
    ),
    Claim(
        "pipeline HBM drop vs flat dp",
        r"`seed_table`\s+in\s+`PIPE_r0?(?P<round>\d+)\.json`\):.*?"
        r"peak\s+\*\*(?P<val>[\d.]+)x\*\*\s+vs\s+flat\s+dp",
        _pipe_field(
            lambda d: d["seed_table"]["dp8xtp1xsp1"]["peak_mib_per_device"]
            / d["seed_table"]["pp8m2"]["peak_mib_per_device"]
        ),
    ),
    Claim(
        "pipeline bubble predicted",
        r"bubble\s+is\s+\*\*(?P<val>[\d.]+)\*\*\s+predicted\s+vs\s+"
        r"\*\*[\d.]+\*\*\s+measured\s+\(`PIPE_r0?(?P<round>\d+)\.json`",
        _pipe_field(lambda d: d["bubble"]["predicted"]),
    ),
    Claim(
        "pipeline bubble measured",
        r"bubble\s+is\s+\*\*[\d.]+\*\*\s+predicted\s+vs\s+"
        r"\*\*(?P<val>[\d.]+)\*\*\s+measured\s+\(`PIPE_r0?(?P<round>\d+)\.json`",
        _pipe_field(lambda d: d["bubble"]["measured"]),
    ),
    Claim(
        "pipeline memory predicted-over-XLA geomean",
        r"predicted/XLA\s+peak\s+geomean\s+\*\*(?P<val>[\d.]+)\*\*\s+"
        r"\(`PIPE_r0?(?P<round>\d+)\.json`",
        _pipe_field(lambda d: d["memory"]["predicted_over_xla_geomean"]),
    ),
    Claim(
        "cost-db audit geomean after correction",
        r"measured/analytic\s+geomean\s+from\s+\*\*[\d.]+\*\*\s+to\s+"
        r"\*\*(?P<val>[\d.]+)\*\*\s+\(`BENCH_COSTDB_r0?(?P<round>\d+)\.json`",
        _costdb_field(
            lambda d: d["correction"]["audit_ratio_geomean_after"]
        ),
    ),
    # execution-contract claims (ISSUE 14): template census, donation
    # coverage, and the cross-process fingerprint stability bar, each
    # anchored to the DET round the README text names
    Claim(
        "exec-contract templates clean",
        r"all\s+\*\*(?P<val>\d+)\*\*\s+seed\s+templates.{0,200}?"
        r"verify\s+clean\s+\(`DET_r0?(?P<round>\d+)\.json`\)",
        _det_field(
            lambda d: d["templates"]["clean"]
            if d["templates"]["clean"] == d["templates"]["checked"]
            else float("nan")
        ),
    ),
    Claim(
        "exec-contract template donation coverage",
        r"\*\*(?P<val>\d+)%\*\*\s+donation-alias\s+coverage\s+on\s+every"
        r"\s+donated\s+step\s+program\s+\(`DET_r0?(?P<round>\d+)\.json`\)",
        _det_field(
            lambda d: 100.0 * min(
                d["templates"]["donation_coverage_min"],
                d["flagship_searched"]["donation_coverage"],
                d["pipelined_pp8m2"]["donation_coverage"],
                d["serving"]["prefill"]["donation_coverage"],
                d["serving"]["decode"]["donation_coverage"],
            )
        ),
    ),
    Claim(
        "exec-contract serving decode cache coverage",
        r"decode\s+program\s+aliases\s+\*\*(?P<val>\d+)%\*\*\s+of\s+its"
        r"\s+donated\s+KV-cache\s+bytes\s+\(`DET_r0?(?P<round>\d+)\.json`\)",
        _det_field(
            lambda d: 100.0 * d["serving"]["decode"]["donation_coverage"]
        ),
    ),
    Claim(
        "exec-contract cross-process fingerprint stability",
        r"bitwise-identical\s+across\s+\*\*(?P<val>\d+)\*\*\s+independent"
        r"\s+processes\s+\(`DET_r0?(?P<round>\d+)\.json`\)",
        _det_field(
            lambda d: d["cross_process"]["processes"]
            if d["cross_process"]["stable"]
            else float("nan")
        ),
    ),
    # multi-slice search claims (ISSUE 17): the hierarchical-vs-flat A/B
    # on the emulated 2-slice 4+4 topology
    Claim(
        "multi-slice hierarchical-vs-flat win",
        r"hierarchical\s+winner\s+is\s+\*\*(?P<val>[\d.]+)x\*\*\s+cheaper"
        r".{0,400}?`SLICE_r0?(?P<round>\d+)\.json`",
        _slice_field(lambda d: d["gate"]["flat_over_hier"]),
    ),
    Claim(
        "multi-slice DCN movement-edge count",
        r"\*\*(?P<val>\d+)\*\*\s+of\s+its\s+movement\s+edges\s+cross\s+the"
        r"\s+DCN.{0,300}?`SLICE_r0?(?P<round>\d+)\.json`",
        _slice_field(
            lambda d: d["placement"]["edges_by_link_class"].get("dcn", 0)
        ),
    ),
    Claim(
        "multi-slice comm-census collective count",
        r"census\s+matches\s+all\s+\*\*(?P<val>\d+)\*\*\s+lowered"
        r"\s+collectives.{0,120}?`SLICE_r0?(?P<round>\d+)\.json`",
        _slice_field(lambda d: d["ffcheck_comm"]["collectives"]),
    ),
    # drift-telemetry claims (ISSUE 18): the committed `bench.py --drift`
    # capture backs the README's live-monitor numbers — the seeded
    # slowdown's advisory step and drift factor, the warm re-search's
    # wall-clock, the healthy control's advisory count, and the
    # steady-state monitor overhead against its 5% bar
    Claim(
        "drift advisory trigger step",
        r"ReplanAdvisory\s+at\s+step\s+\*\*(?P<val>\d+)\*\*"
        r".{0,500}?`DRIFT_r0?(?P<round>\d+)\.json`",
        _drift_field(lambda d: d["slowdown"]["advisory"]["step"]),
    ),
    Claim(
        "drift factor at trigger",
        r"\*\*(?P<val>[\d.]+)x\*\*\s+over\s+its\s+calibrated\s+baseline"
        r".{0,500}?`DRIFT_r0?(?P<round>\d+)\.json`",
        _drift_field(lambda d: d["slowdown"]["advisory"]["drift"]),
    ),
    Claim(
        "drift warm re-search seconds",
        r"warm\s+re-search\s+re-prices\s+all\s+candidate\s+plans\s+in\s+"
        r"\*\*(?P<val>[\d.]+)\s*s\*\*.{0,200}?`DRIFT_r0?(?P<round>\d+)\.json`",
        _drift_field(lambda d: d["slowdown"]["advisory"]["research_seconds"]),
    ),
    Claim(
        "drift healthy-control advisories",
        r"healthy\s+control\s+run\s+emits\s+\*\*(?P<val>\d+)\*\*\s+"
        r"advisories.{0,200}?`DRIFT_r0?(?P<round>\d+)\.json`",
        _drift_field(lambda d: d["control"]["advisories"]),
    ),
    Claim(
        "drift monitor steady-state overhead",
        r"steady-state\s+monitor\s+overhead\s+of\s+"
        r"\*\*(?P<val>-?[\d.]+)%\*\*.{0,200}?`DRIFT_r0?(?P<round>\d+)\.json`",
        _drift_field(lambda d: d["overhead"]["overhead_pct"]),
    ),
    # plan-transition claims (ISSUE 19): the committed
    # `tools/transition_audit.py` capture backs the README's static
    # swap-verification numbers — the two 48-pair perturbation sweeps,
    # the seeded per-rule fixtures, and the mappable multi-slice remaps
    Claim(
        "transition degraded-grid swappable pairs",
        r"all\s+\*\*(?P<val>\d+)\*\*\s+seed-template\s+pairs\s+verify\s+"
        r"`swappable`.{0,700}?`TRN_r0?(?P<round>\d+)\.json`",
        _trn_field(lambda d: d["pairs"]["counts"]["degraded_swappable"]),
    ),
    Claim(
        "transition batch-growth blocked pairs",
        r"all\s+\*\*(?P<val>\d+)\*\*\s+batch-growth\s+pairs\s+trip\s+"
        r"TRN003.{0,400}?`TRN_r0?(?P<round>\d+)\.json`",
        _trn_field(lambda d: d["pairs"]["counts"]["batch_growth_blocked"]),
    ),
    Claim(
        "transition seeded fixtures tripped",
        r"\*\*(?P<val>\d+)\*\*\s+seeded\s+fixtures\s+each\s+trip\s+"
        r"exactly\s+their\s+rule\s+id"
        r".{0,400}?`TRN_r0?(?P<round>\d+)\.json`",
        _trn_field(
            lambda d: sum(
                1 for v in d["fixtures"].values() if v.get("tripped")
            )
        ),
    ),
    Claim(
        "transition multi-slice swappable remaps",
        r"\*\*(?P<val>\d+)\*\*\s+mappable\s+multi-slice\s+remaps\s+"
        r"verify\s+`swappable`.{0,400}?`TRN_r0?(?P<round>\d+)\.json`",
        _trn_field(lambda d: d["pairs"]["counts"]["multislice_swappable"]),
    ),
]


# Live claims: README numbers whose ground truth is the CODE, not a
# captured artifact (ISSUE 4 static-verification catalog sizes). Checked
# exactly — a rule added or removed without updating the README fails
# tier-1 the same way a stale benchmark number does.


def _live_verifier_rules() -> float:
    from flexflow_tpu.analysis import PCG_RULE_CATALOG

    return float(len(PCG_RULE_CATALOG))


def _live_rule_audit_checks() -> float:
    from flexflow_tpu.analysis import RULE_AUDIT_CATALOG

    return float(len(RULE_AUDIT_CATALOG))


def _live_source_lints() -> float:
    from flexflow_tpu.analysis import LINT_CATALOG

    return float(len(LINT_CATALOG))


def _live_audited_rule_count() -> float:
    # the 8-device tier-1 gate's rule registry — the SAME helper ffcheck
    # --audit-rules and the tier-1 audit test use, so the README count is
    # checked against the registry the gate actually audits
    from flexflow_tpu.analysis import registered_rules_for_grid

    return float(len(registered_rules_for_grid(8)))


@dataclass
class LiveClaim:
    """A README number checked against the live code (group 'val' only)."""

    label: str
    pattern: str
    actual: Callable[[], float]


LIVE_CLAIMS = [
    LiveClaim(
        "ffcheck verifier rule count",
        r"catalog spans \*\*(?P<val>\d+)\*\* verifier rules",
        _live_verifier_rules,
    ),
    LiveClaim(
        "ffcheck rule-audit check count",
        r"\*\*(?P<val>\d+)\*\* rule-audit checks",
        _live_rule_audit_checks,
    ),
    LiveClaim(
        "ffcheck source lint count",
        r"\*\*(?P<val>\d+)\*\* source lints",
        _live_source_lints,
    ),
    LiveClaim(
        "tier-1 audited substitution rule count",
        r"tier-1 gate audits \*\*(?P<val>\d+)\*\* registered\s+"
        r"substitution rules",
        _live_audited_rule_count,
    ),
]


def claim_tolerance(val_text: str) -> float:
    """Half a unit in the last quoted decimal place (a claim is the
    artifact value correctly rounded to the precision the README uses)."""
    if "." in val_text:
        decimals = len(val_text.split(".")[1])
    else:
        decimals = 0
    return 0.5 * 10 ** (-decimals) + 1e-9


def check(readme_path: Optional[str] = None) -> list:
    """Returns a list of failure strings (empty = all claims verified)."""
    path = readme_path or os.path.join(REPO, "README.md")
    with open(path) as f:
        text = f.read()
    failures = []
    for c in CLAIMS:
        m = re.search(c.pattern, text, re.DOTALL)
        if m is None:
            failures.append(
                f"{c.label}: claim text not found in README "
                f"(pattern {c.pattern!r})"
            )
            continue
        round_no = int(m.group("round"))
        claimed = float(m.group("val"))
        actual = c.artifact_value(round_no)
        if actual is None:
            print(f"SKIP {c.label}: round-{round_no} artifact missing")
            continue
        tol = claim_tolerance(m.group("val"))
        if abs(claimed - actual) <= tol:
            print(
                f"OK   {c.label}: README {claimed} ~ artifact "
                f"{round(actual, 4)} (round {round_no})"
            )
        else:
            failures.append(
                f"{c.label}: README claims {claimed} but round-{round_no} "
                f"artifact says {round(actual, 4)} (tolerance {tol:.3g})"
            )
    for lc in LIVE_CLAIMS:
        m = re.search(lc.pattern, text, re.DOTALL)
        if m is None:
            failures.append(
                f"{lc.label}: claim text not found in README "
                f"(pattern {lc.pattern!r})"
            )
            continue
        claimed = float(m.group("val"))
        actual = lc.actual()
        if claimed == actual:
            print(f"OK   {lc.label}: README {int(claimed)} == live {int(actual)}")
        else:
            failures.append(
                f"{lc.label}: README claims {int(claimed)} but the live "
                f"code says {int(actual)}"
            )
    return failures


def main() -> int:
    failures = check()
    for f in failures:
        print(f"FAIL {f}", file=sys.stderr)
    if failures:
        return 1
    print("all README claims verified against artifacts")
    return 0


if __name__ == "__main__":
    sys.exit(main())
