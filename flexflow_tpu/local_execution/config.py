"""Runtime/search configuration.

Reference: lib/local-execution/include/local-execution/config.h:51-110
(FFConfig/FFIterationConfig) and the legacy CLI flags (README command-line
flags; SURVEY.md §5 config row). Flag names preserved where meaningful;
GPU-isms reinterpreted (workers_per_node = TPU chips per host).
"""

from __future__ import annotations

import argparse
import os
from dataclasses import dataclass, field
from typing import List, Optional


@dataclass
class FFConfig:
    # training (reference -e, -b, -p, -d, --lr, ...)
    epochs: int = 1
    batch_size: int = 64
    print_freq: int = 10
    dataset_path: str = ""
    learning_rate: float = 0.01
    weight_decay: float = 0.0
    # machine (reference -ll:gpu/-ll:cpu/--nodes; TPU: chips per host)
    workers_per_node: int = 1
    cpus_per_node: int = 1
    num_nodes: int = 1
    # profiling / tracing. profiling=True collects per-layer elapsed ms
    # (the reference's profiling_wrapper cudaEvent timing); profile_trace_dir
    # additionally captures an XLA/jax.profiler trace of the fit loop for
    # xprof/tensorboard (the Legion Prof `-lg:prof` analogue, SURVEY §5)
    profiling: bool = False
    profile_trace_dir: str = ""
    # run-health telemetry (observability/metrics.py): when set, fit()
    # appends one JSON event per step (loss, wallclock ms, tokens/s,
    # grad/param global norms, update-to-param ratio, skipped/nonfinite
    # flags) to <metrics_dir>/events.jsonl and a registry snapshot to
    # metrics.json on exit. The norms are fused into the jitted step.
    metrics_dir: str = ""
    # nonfinite-grad/loss policy (observability/health.py): "off" (no
    # detection, zero step overhead), "warn" (log and continue), "skip_step"
    # (drop the poisoned update inside the jitted step — params/optimizer
    # state keep their pre-step values — and keep training), "raise" (stop
    # with the first-bad-op localizer's blame report)
    health_policy: str = "off"
    # plan_audit=True replays the Unity winner after compile() measuring
    # per-op ms and per-movement-edge collective ms against the cost model
    # that picked it (observability/plan_audit.py); recorded in
    # FFModel.search_provenance["plan_audit"]
    plan_audit: bool = False
    # elastic runtime (runtime/checkpoint.py): checkpoint_dir enables
    # fit-loop checkpointing — full-resume snapshots (params, opt state,
    # RNG stream position, dataloader epoch + cursor) every
    # checkpoint_every_n_steps, written by a background thread overlapped
    # with the next step (checkpoint_sync=True forces the blocking save
    # path). fit(resume=True) restores the latest snapshot for a
    # bitwise-identical continuation (chaos-tested via FF_TPU_FAULT_STEP).
    checkpoint_dir: str = ""
    checkpoint_every_n_steps: int = 0
    checkpoint_max_to_keep: int = 3
    checkpoint_sync: bool = False
    # checkpoint serialization backend: "" = auto (orbax when installed,
    # else the raw-.npy "npz" layout). "npz" forces the flat-file layout
    # whose keys.json carries the per-leaf CRC32/dtype/shape integrity
    # manifest (runtime/integrity.py) — corrupt or truncated snapshots are
    # detected at restore, quarantined as step_N.corrupt, and the resume
    # falls back to the newest step that verifies. Orbax restores get the
    # same quarantine/fallback on restore *failure* via its own metadata.
    checkpoint_backend: str = ""
    # window watchdog (runtime/supervisor.py): > 0 arms a deadline of
    # (rolling window-time estimate x this factor, floored at 1 s) around
    # every dispatch window; on expiry a HangDiagnostic (last completed
    # step, in-flight window, live trace-span stack, device kind) lands in
    # the metrics JSONL and the run raises WindowHangError instead of
    # blocking forever. 0 (default) = no watchdog thread at all. The
    # FF_TPU_WATCHDOG env var supplies the factor when this field is 0.
    watchdog_factor: float = 0.0
    # live plan-fidelity drift telemetry (observability/drift.py,
    # ISSUE 18): drift_monitor=True starts a supervised background thread
    # per fit() that tails the metrics event stream (requires
    # metrics_dir) and compares measured window step-ms against the
    # searched winner's predicted cost; when the EMA'd ratio leaves the
    # band for drift_run_length consecutive windows, a ReplanAdvisory
    # (warm re-priced current plan + seed alternatives) lands in
    # search_provenance["drift"] and events.jsonl. Advisory only — no
    # hot-swap.
    drift_monitor: bool = False
    # fractional tolerance: drift outside [1/(1+band), 1+band] of the
    # baseline ratio counts as out-of-band
    drift_band: float = 0.25
    # steps aggregated per drift window
    drift_window_steps: int = 8
    # consecutive out-of-band windows required to trigger an advisory
    drift_run_length: int = 3
    # degraded-grid cap (runtime/recompile.py recover_from_grid_change):
    # compile()/recompile() use at most this many devices when > 0 — the
    # re-entry path after a simulated device failure / slice resize sets it
    # and re-runs the machine-mapping search against the shrunken grid.
    max_devices: int = 0
    # static memory safety (ISSUE 10): per-device HBM capacity in GiB.
    # > 0 turns device memory into a HARD search constraint: the
    # machine-mapping DPs (python + native) prune leaves whose per-device
    # piece residency exceeds it, candidate plans whose full liveness
    # timeline (analysis/memory_analysis.py) peaks above it are
    # INFEASIBLE, and the searched winner's per-device peaks are verified
    # (MEM001-MEM003) into search_provenance["verify"]/["memory"].
    # 0 (default) = no search-side constraint; the winner's peaks are
    # still analyzed against the attached device's reported HBM limit
    # when the backend exposes one (memory_stats()["bytes_limit"]).
    hbm_gb: float = 0.0
    # search (reference --search-budget, --search-alpha, --simulator-*)
    search_budget: int = -1
    search_alpha: float = 1.2
    search_overlap_backward_update: bool = False
    export_strategy_file: str = ""
    import_strategy_file: str = ""
    search_num_nodes: int = -1
    search_num_workers: int = -1
    # search cost model: "analytic" (roofline, no hardware), "measured"
    # (run each op for real — reference local_cost_estimator.cc:29-92 — plus
    # calibrated collective constants), "calibrated" (analytic structure with
    # machine constants measured on the attached backend,
    # compiler/calibration.py), or "auto" (measured on an accelerator,
    # analytic on CPU)
    cost_model: str = "analytic"
    # search algorithm: "unity" (best-first over the rewrite lattice, the
    # new stack's intended algorithm) or "mcmc" (simulated annealing, the
    # legacy stack's strategy_search_task mode — simulator.h:671; budget is
    # interpreted as ~10 cost evaluations per unit)
    search_algorithm: str = "unity"
    # Gradient sync: psum/all-reduce collectives ONLY, by design. The
    # reference additionally offers a parameter-server mode
    # (config.h:38-42 ParameterServer vs NCCL, optimizer_kernels.h:8-50);
    # on TPU every gradient reduction rides ICI as an XLA psum inside the
    # compiled step — a host-side PS would serialize through PCIe/DCN and
    # defeat the SPMD step, so no PS mode exists here (documented parity
    # divergence).
    # parallelism toggles (reference --only-data-parallel etc., config.h:87-89).
    # parameter/attribute parallel default ON: the reference's Unity search
    # explores the full space without these legacy flags (osdi22ae/bert.sh
    # passes neither; its arg_parser.cc:56-62 even maps both flags to the
    # same field). Here they are honored as restrictions: --no-enable-*
    # removes the corresponding rules from the search space.
    only_data_parallel: bool = False
    enable_parameter_parallel: bool = True
    enable_attribute_parallel: bool = True
    enable_inplace_optimizations: bool = False
    # substitutions
    substitution_json_path: str = ""
    # machine model for the analytic cost path (reference machine_model_version)
    machine_model_version: int = 0
    machine_model_file: str = ""
    # fusion (reference perform_fusion)
    perform_fusion: bool = False
    # branch stacking (compiler/branch_stacking.py): rewrite isomorphic
    # parallel branches into a stacked batched form whose branch axis the
    # search can shard onto disjoint device subsets — the SPMD realization
    # of the reference's disjoint-resource operator placement
    # (mapper.h:82-126). Off by default: it changes weight layout (stacked
    # [k, ...] parameters) and therefore checkpoints/param keys.
    branch_stacking: bool = False
    # sub-mesh execution of NON-isomorphic parallel branches
    # (parallel/submesh.py): each branch island of a Split-fork runs on its
    # own disjoint device group with explicit transfers at the fork/join —
    # the runtime counterpart of the reference FFMapper's point-task
    # placement (mapper.h:82-126). This is also what makes the machine-
    # mapping DP's resource-split pricing legal at runtime for this shape
    # (get_optimal_machine_mapping.allow_resource_splits).
    submesh_branches: bool = False
    # compute/communication overlap (ROADMAP item 3): --overlap /
    # FF_TPU_OVERLAP lowers Combine/Reduction movement edges adjacent to
    # dense ops as fused collective matmuls (kernels/collective_matmul.py)
    # and prices the machine-mapping DP's movement tables with an
    # overlapped-cost entry (machine_mapping/overlap.py) so the search can
    # CHOOSE the fused lowering. Tri-state: None (default) defers to the
    # FF_TPU_OVERLAP env var, True forces on, False forces OFF even when
    # the env var is set (the A/B harness's serial arm must stay serial).
    # FF_TPU_OVERLAP_BASELINE=1 force-reverts everything (regression
    # tests).
    overlap: Optional[bool] = None
    # pipeline parallelism (ISSUE 13): --pipeline / FF_TPU_PIPELINE seeds
    # the Unity search with StagePartition/StageMerge stage-partitioned
    # candidates (bubble-aware stage axis in both machine-mapping DPs) and
    # lowers a stage-partitioned winner through the 1F1B microbatch
    # executor (parallel/pipeline.py: shard_map + ppermute over a
    # (stage, data) mesh). Tri-state like overlap: None defers to the
    # FF_TPU_PIPELINE env var, True forces on, False forces OFF.
    # FF_TPU_PIPELINE_BASELINE=1 replaces the 1F1B schedule with the
    # sequential microbatch reference (the bitwise A/B arm).
    pipeline: Optional[bool] = None
    # microbatch count for the pipeline seeds; 0 = auto (the largest of
    # {2S, S, 8, 4, 2} that divides the per-shard batch)
    pipeline_microbatches: int = 0
    # hierarchical multi-slice search (ISSUE 17): --multislice /
    # FF_TPU_MULTISLICE runs the machine-mapping search as the two-level
    # ICI/DCN DP (compiler/machine_mapping/hierarchical.py) — the outer
    # level enumerates which axis KIND (data/replica/stage, or none)
    # crosses the slice boundary, the inner level is the flat per-slice DP
    # with slice-aware view legality (a view may project a tensor-sharded
    # task dim across DCN only never). Tri-state like overlap/pipeline:
    # None defers to the env var, True forces on, False forces off.
    # On a 1-node (single-slice) machine the flag is a no-op beyond view
    # legality masking.
    multislice: Optional[bool] = None
    # persisted measured movement-edge costs (ROADMAP item 5 slice): plan
    # audits write each measured reshard into this JSON table keyed by
    # (edge kind, bytes, shape/view signature, device kind), and later
    # searches prefer the cached measurement over the analytic collective
    # estimate (compiler/movement_store.py). Empty = off.
    movement_cost_store: str = ""
    # persistent cost DATABASE (ROADMAP item 5, the full refactor): a
    # directory (beside the compile cache) holding cost_db.json — measured
    # op-leaf AND movement-edge costs keyed by (op kind + canonical attrs,
    # piece shapes, dtype, machine view, device kind + measurement
    # fingerprint). Estimators fall through analytic -> cached-measured ->
    # measure, write back what they measure, and the analytic estimator
    # applies per-op-class correction factors fitted from the accumulated
    # (analytic, measured) pairs (compiler/cost_store.py); --plan-audit
    # feeds its per-op measured ms into the same store. Empty = off.
    cost_store: str = ""
    # benchmarking/calibration: skip the search and lower the named strategy
    # template verbatim ("dp8xtp1xsp1", "dp1xtp1xsp8-a2a", "dp2xep4", ...),
    # to measure a seed's REAL step time against the cost model's ranking
    force_strategy_seed: str = ""
    # seed
    seed: int = 0

    @staticmethod
    def add_args(p: argparse.ArgumentParser) -> None:
        p.add_argument("-e", "--epochs", type=int, default=1)
        p.add_argument("-b", "--batch-size", type=int, default=64)
        p.add_argument("-p", "--print-freq", type=int, default=10)
        p.add_argument("-d", "--dataset", type=str, default="")
        p.add_argument("--lr", type=float, default=0.01)
        p.add_argument("--weight-decay", type=float, default=0.0)
        p.add_argument("--workers-per-node", type=int, default=1)
        p.add_argument("--nodes", type=int, default=1)
        p.add_argument("--profiling", action="store_true")
        p.add_argument("--profile-trace-dir", type=str, default="")
        p.add_argument(
            "--metrics-dir",
            type=str,
            default="",
            help="write per-step run-health events (JSONL) and a metrics "
            "snapshot into this directory (observability/metrics.py)",
        )
        p.add_argument(
            "--health-policy",
            type=str,
            default="off",
            choices=("off", "warn", "skip_step", "raise"),
            help="reaction to a non-finite loss/gradient: warn logs, "
            "skip_step drops the poisoned update and keeps training, raise "
            "stops with the first bad op named (observability/health.py)",
        )
        p.add_argument(
            "--checkpoint-dir",
            type=str,
            default="",
            help="enable fit-loop checkpointing into this directory "
            "(async background writer; full-resume snapshots)",
        )
        p.add_argument(
            "--checkpoint-every-n-steps",
            type=int,
            default=0,
            help="snapshot interval in training steps (0 = only explicit "
            "save_checkpoint calls)",
        )
        p.add_argument(
            "--checkpoint-max-to-keep",
            type=int,
            default=3,
            help="checkpoint retention: older step dirs are GC'd",
        )
        p.add_argument(
            "--checkpoint-sync",
            action="store_true",
            help="force the blocking (synchronous) checkpoint save path "
            "instead of the background writer",
        )
        p.add_argument(
            "--checkpoint-backend",
            type=str,
            default="",
            choices=("", "npz", "orbax"),
            help="checkpoint serialization backend (default auto): npz = "
            "raw-.npy layout with the per-leaf checksum manifest "
            "(runtime/integrity.py), orbax = orbax.checkpoint",
        )
        p.add_argument(
            "--watchdog-factor",
            type=float,
            default=0.0,
            help="arm a hang watchdog around every dispatch window with a "
            "budget of (rolling window-time estimate x FACTOR); expiry "
            "records a HangDiagnostic and raises WindowHangError (0 = "
            "off; FF_TPU_WATCHDOG supplies the factor when unset)",
        )
        p.add_argument(
            "--drift-monitor",
            action="store_true",
            help="watch the live metrics stream for plan-fidelity drift "
            "(measured vs searched-predicted step ms) and emit "
            "ReplanAdvisories into events.jsonl + "
            "search_provenance['drift'] — advisory only, no hot-swap; "
            "requires --metrics-dir (observability/drift.py)",
        )
        p.add_argument(
            "--drift-band",
            type=float,
            default=0.25,
            help="drift tolerance band: an EMA'd measured/predicted ratio "
            "outside [1/(1+band), 1+band] of the run's baseline counts "
            "as out-of-band",
        )
        p.add_argument(
            "--drift-window-steps",
            type=int,
            default=8,
            help="steps aggregated per drift-detection window",
        )
        p.add_argument(
            "--drift-run-length",
            type=int,
            default=3,
            help="consecutive out-of-band windows required before a "
            "ReplanAdvisory fires (run-length confirmation)",
        )
        p.add_argument(
            "--max-devices",
            type=int,
            default=0,
            help="cap the device grid compile() plans for (>0): the "
            "degraded-grid recovery path's shrunken-mesh knob",
        )
        p.add_argument(
            "--hbm-gb",
            type=float,
            default=0.0,
            help="per-device HBM capacity in GiB (> 0): OOM mappings "
            "become INFEASIBLE in the machine-mapping search and the "
            "winner is statically verified against it (MEM001-MEM003; "
            "analysis/memory_analysis.py)",
        )
        p.add_argument(
            "--plan-audit",
            action="store_true",
            help="after the Unity search, replay the winning plan measuring "
            "per-op and per-movement-edge cost against the model's "
            "predictions (observability/plan_audit.py)",
        )
        p.add_argument(
            "--overlap",
            action=argparse.BooleanOptionalAction,
            default=None,
            help="fused collective-matmul lowering of Combine/Reduction "
            "edges adjacent to dense ops + overlap-aware movement pricing "
            "in the machine-mapping DP (--overlap forces on, --no-overlap "
            "forces off; unset defers to FF_TPU_OVERLAP)",
        )
        p.add_argument(
            "--pipeline",
            action=argparse.BooleanOptionalAction,
            default=None,
            help="pipeline parallelism (ISSUE 13): seed the Unity search "
            "with StagePartition/StageMerge stage-partitioned candidates "
            "(1F1B bubble-aware stage axis in both DPs) and lower a "
            "stage-partitioned winner via the shard_map+ppermute 1F1B "
            "executor (--pipeline forces on, --no-pipeline forces off; "
            "unset defers to FF_TPU_PIPELINE)",
        )
        p.add_argument(
            "--multislice",
            action=argparse.BooleanOptionalAction,
            default=None,
            help="hierarchical multi-slice search (ISSUE 17): two-level "
            "ICI/DCN machine-mapping DP — the outer level picks which "
            "axis kind (data/replica/stage or none) crosses the slice "
            "boundary, the inner per-slice DP enumerates only "
            "slice-contiguous views (--multislice forces on, "
            "--no-multislice forces off; unset defers to "
            "FF_TPU_MULTISLICE)",
        )
        p.add_argument(
            "--pipeline-microbatches",
            type=int,
            default=0,
            help="microbatch count M for the pipeline seeds (0 = auto: "
            "the largest of {2S, S, 8, 4, 2} dividing the per-shard batch)",
        )
        p.add_argument(
            "--movement-cost-store",
            type=str,
            default="",
            help="JSON file persisting measured movement-edge costs from "
            "plan-audit runs; searches prefer these measurements over the "
            "analytic collective estimates",
        )
        p.add_argument(
            "--cost-store-dir",
            type=str,
            default="",
            help="persistent cost database directory (cost_db.json): "
            "searches fall through analytic -> cached-measured -> measure "
            "across sessions, write back new measurements, and fit "
            "per-op-class correction factors from the accumulated "
            "(analytic, measured) pairs (compiler/cost_store.py)",
        )
        p.add_argument("--search-budget", type=int, default=-1)
        p.add_argument("--search-alpha", type=float, default=1.2)
        p.add_argument("--export-strategy", type=str, default="")
        p.add_argument("--import-strategy", type=str, default="")
        p.add_argument("--only-data-parallel", action="store_true")
        p.add_argument(
            "--enable-parameter-parallel",
            action=argparse.BooleanOptionalAction,
            default=True,
        )
        p.add_argument(
            "--enable-attribute-parallel",
            action=argparse.BooleanOptionalAction,
            default=True,
        )
        p.add_argument("--substitution-json", type=str, default="")
        p.add_argument(
            "--perform-fusion",
            action="store_true",
            help="add graph-level fusion rules (sibling/consecutive linear "
            "merge, activation fusion) to the Unity search space",
        )
        p.add_argument(
            "--branch-stacking",
            action="store_true",
            help="stack isomorphic parallel branches so the search can "
            "place them on disjoint device subsets (operator placement)",
        )
        p.add_argument("--search-num-nodes", type=int, default=-1)
        p.add_argument("--search-num-workers", type=int, default=-1)
        p.add_argument(
            "--cost-model",
            type=str,
            default="analytic",
            choices=("analytic", "measured", "calibrated", "auto"),
        )
        p.add_argument(
            "--search-algorithm",
            type=str,
            default="unity",
            choices=("unity", "mcmc"),
            help="best-first (new stack) or simulated-annealing (legacy "
            "strategy_search_task) strategy search",
        )
        p.add_argument("--machine-model-version", type=int, default=0)
        p.add_argument("--machine-model-file", type=str, default="")
        p.add_argument("--seed", type=int, default=0)

    @staticmethod
    def from_args(args: argparse.Namespace) -> "FFConfig":
        return FFConfig(
            epochs=args.epochs,
            batch_size=args.batch_size,
            print_freq=args.print_freq,
            dataset_path=args.dataset,
            learning_rate=args.lr,
            weight_decay=args.weight_decay,
            workers_per_node=args.workers_per_node,
            num_nodes=args.nodes,
            profiling=args.profiling,
            profile_trace_dir=args.profile_trace_dir,
            metrics_dir=getattr(args, "metrics_dir", ""),
            health_policy=getattr(args, "health_policy", "off"),
            plan_audit=getattr(args, "plan_audit", False),
            checkpoint_dir=getattr(args, "checkpoint_dir", ""),
            checkpoint_every_n_steps=getattr(
                args, "checkpoint_every_n_steps", 0
            ),
            checkpoint_max_to_keep=getattr(args, "checkpoint_max_to_keep", 3),
            checkpoint_sync=getattr(args, "checkpoint_sync", False),
            checkpoint_backend=getattr(args, "checkpoint_backend", ""),
            watchdog_factor=getattr(args, "watchdog_factor", 0.0),
            drift_monitor=getattr(args, "drift_monitor", False),
            drift_band=getattr(args, "drift_band", 0.25),
            drift_window_steps=getattr(args, "drift_window_steps", 8),
            drift_run_length=getattr(args, "drift_run_length", 3),
            max_devices=getattr(args, "max_devices", 0),
            hbm_gb=getattr(args, "hbm_gb", 0.0),
            overlap=getattr(args, "overlap", None),
            pipeline=getattr(args, "pipeline", None),
            pipeline_microbatches=getattr(
                args, "pipeline_microbatches", 0
            ),
            multislice=getattr(args, "multislice", None),
            movement_cost_store=getattr(args, "movement_cost_store", ""),
            cost_store=getattr(args, "cost_store_dir", ""),
            search_budget=args.search_budget,
            search_alpha=args.search_alpha,
            export_strategy_file=args.export_strategy,
            import_strategy_file=args.import_strategy,
            only_data_parallel=args.only_data_parallel,
            enable_parameter_parallel=args.enable_parameter_parallel,
            enable_attribute_parallel=args.enable_attribute_parallel,
            substitution_json_path=args.substitution_json,
            perform_fusion=args.perform_fusion,
            branch_stacking=args.branch_stacking,
            search_num_nodes=args.search_num_nodes,
            search_num_workers=args.search_num_workers,
            cost_model=args.cost_model,
            search_algorithm=args.search_algorithm,
            machine_model_version=args.machine_model_version,
            machine_model_file=args.machine_model_file,
            seed=args.seed,
        )


#: where the persistent XLA compilation cache lives when the environment
#: does not place it: one fixed path inside the checkout (the path is part
#: of the cache key, so a directory that moves never hits)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def configure_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory: a second process compiling the identical step program
    loads the cached executable instead of re-running XLA. Where
    `JAX_COMPILATION_CACHE_DIR` is set jax already reads it and no
    directory is set in code; otherwise the cache goes to
    `DEFAULT_COMPILE_CACHE_DIR`. Call before the first compile — jax
    decides once per process whether the cache is used. Idempotent."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    return jax.config.jax_compilation_cache_dir


@dataclass
class FFIterationConfig:
    """reference: FFIterationConfig (seq_length for recurrent-ish models)."""

    seq_length: int = -1
