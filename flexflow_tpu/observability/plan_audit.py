"""Predicted-vs-measured audit of the searched plan.

Unity's premise is that the cost model steers the joint substitution +
machine-mapping search — so the one plan whose predictions actually matter
is the WINNER the search hands to the executor. This module replays that
plan and compares, op by op and movement edge by movement edge, what the
cost model predicted against what the hardware measures:

- compute ops: predicted ms is the estimator's leaf price under the chosen
  machine view (the exact number the DP summed); measured ms reruns the
  op's piece shapes for real through `LocalCostEstimator` (Unity cost model
  v2 discipline — local_cost_estimator.cc:29-92).
- movement edges (Combine / Repartition / Replicate / Reduction): predicted
  ms is the plan's charged collective cost — `parallel_op_cost_ms`, the
  machine model's bandwidth/latency term for this op's resharding — and
  measured ms times the actual reshard: a jitted identity whose input
  carries the op's input sharding and whose output is constrained to the
  op's output sharding, which makes XLA emit exactly the collective the
  plan implies.

Output: per-entry misprediction ratios (measured / predicted) plus a
summary (geometric-mean ratio per class and combined, worst-N ops by
log-distance from 1.0). A geomean of 1.0 means the model is calibrated in
aggregate; a worst-op ratio of 6x names the specific kernel or edge whose
model term is wrong — which turns a single scalar calibration drift into
an attributable work list.

Recorded in `FFModel.search_provenance["plan_audit"]` (opt-in:
`--plan-audit`).
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

AUDIT_SCHEMA_VERSION = 1


def _geomean(ratios: List[float]) -> Optional[float]:
    vals = [r for r in ratios if r is not None and r > 0 and math.isfinite(r)]
    if not vals:
        return None
    return math.exp(sum(math.log(r) for r in vals) / len(vals))


def _ratio(measured: Optional[float], predicted: Optional[float]) -> Optional[float]:
    if (
        measured is None
        or predicted is None
        or predicted <= 0
        or measured <= 0
        or not math.isfinite(predicted)
        or not math.isfinite(measured)
    ):
        return None
    return measured / predicted


def _round(v: Optional[float], nd: int = 4) -> Optional[float]:
    return None if v is None else round(v, nd)


def _measure_movement_ms(
    shape, src_sharding, dst_sharding, mesh, settings
) -> Optional[float]:
    """Time the reshard a parallel op lowers to: a jitted identity from the
    producer's sharding to the consumer's. Returns ms, or None when the
    movement cannot be timed on this mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels.profiling import profile_fn
    from flexflow_tpu.op_attrs.parallel_tensor_shape import get_reduced_shape

    if src_sharding is None or dst_sharding is None:
        # unconstrained endpoint: there is no defined collective to time —
        # reporting some other computation's time here would pollute the
        # movement calibration the audit exists to make trustworthy
        return None
    ts = get_reduced_shape(shape)
    try:
        arr = jnp.asarray(
            np.random.default_rng(0).standard_normal(ts.dims),
            ts.dtype.to_jnp() if ts.dtype.is_floating else jnp.float32,
        )
        arr = jax.device_put(arr, src_sharding)
        fn = jax.jit(lambda x: x, out_shardings=dst_sharding)
        return profile_fn(fn, settings, arr)
    except Exception:
        return None


def _measure_fused_edge_ms(
    pcg, n, kind, shardings, mesh, settings
) -> Optional[float]:
    """Marginal cost of the FUSED lowering of movement edge `n` (an
    overlap site's Combine/Reduction): the fused collective-matmul's wall
    time minus a bare single-device matmul at the same local piece shapes
    — the compute the ring performs anyway — leaving the edge's exposed
    communication. This is what `--plan-audit` reports for edges the
    executor lowers fused: timing the standalone reshard would measure a
    collective the program no longer contains. Returns ms (floored at 0:
    scheduling noise can make the fused program beat its own matmul), or
    None when the edge cannot be measured this way (caller falls back to
    the standalone-reshard measurement, marked unfused)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.kernels.collective_matmul import (
        all_gather_matmul,
        matmul_reduce_scatter,
    )
    from flexflow_tpu.kernels.profiling import profile_fn
    from flexflow_tpu.op_attrs.ops import CombineAttrs, LinearAttrs
    from flexflow_tpu.op_attrs.parallel_tensor_shape import (
        get_piece_shape,
        get_reduced_shape,
    )

    def global_array(tensor, rng_seed):
        ts = get_reduced_shape(pcg.tensor_shape(tensor))
        arr = jnp.asarray(
            np.random.default_rng(rng_seed).standard_normal(ts.dims),
            jnp.float32,
        )
        s = shardings.get(tensor)
        return jax.device_put(arr, s) if s is not None else arr

    def piece_array(tensor, rng_seed):
        ts = get_piece_shape(pcg.tensor_shape(tensor))
        return jnp.asarray(
            np.random.default_rng(rng_seed).standard_normal(ts.dims),
            jnp.float32,
        )

    try:
        if kind == "ag_matmul":
            attrs = pcg.op_attrs(n)
            assert isinstance(attrs, CombineAttrs)
            (xc,) = pcg.outputs_of(n)
            (use,) = pcg.uses_of(xc)
            linear = use.node
            lattrs = pcg.op_attrs(linear)
            assert isinstance(lattrs, LinearAttrs)
            lins = pcg.inputs_of(linear)
            (src,) = pcg.inputs_of(n)
            rank = pcg.tensor_shape(src).num_dims
            g = attrs.combine_dim % rank
            xs = shardings.get(src)
            ws = shardings.get(lins[1])
            if xs is None:
                return None
            x_spec = tuple(xs.spec) + (None,) * (rank - len(xs.spec))
            w_rank = pcg.tensor_shape(lins[1]).num_dims
            w_spec = (
                tuple(ws.spec) + (None,) * (w_rank - len(ws.spec))
                if ws is not None
                else (None,) * w_rank
            )
            x = global_array(src, 0)
            w = global_array(lins[1], 1)

            def fused_fn(xv, wv):
                return all_gather_matmul(
                    xv, wv, mesh, x_spec, w_spec, g
                )

            with mesh:
                fused_ms = profile_fn(jax.jit(fused_fn), settings, x, w)
            # the compute baseline: the same matmul at the fused kernel's
            # per-device shapes (gathered rows x local weight columns)
            xp = piece_array(xc, 0)
            wp = piece_array(lins[1], 1)
            base_ms = profile_fn(jax.jit(jnp.matmul), settings, xp, wp)
            return max(fused_ms - base_ms, 0.0)
        if kind == "matmul_rs":
            # n = Reduction; its producer is the pinned bias-free Linear
            (red_in,) = pcg.inputs_of(n)
            linear = red_in.node
            lattrs = pcg.op_attrs(linear)
            if not isinstance(lattrs, LinearAttrs):
                return None
            lins = pcg.inputs_of(linear)
            x_t, w_t = lins[0], lins[1]
            xs = shardings.get(x_t)
            ws = shardings.get(w_t)
            if xs is None or ws is None:
                return None
            x_rank = pcg.tensor_shape(x_t).num_dims
            w_rank = pcg.tensor_shape(w_t).num_dims
            x_spec = tuple(xs.spec) + (None,) * (x_rank - len(xs.spec))
            w_spec = tuple(ws.spec) + (None,) * (w_rank - len(ws.spec))
            x = global_array(x_t, 0)
            w = global_array(w_t, 1)

            def fused_fn(xv, wv):
                return matmul_reduce_scatter(
                    xv, wv, mesh, x_spec, w_spec
                )

            with mesh:
                fused_ms = profile_fn(jax.jit(fused_fn), settings, x, w)
            xp = piece_array(x_t, 0)
            wp = piece_array(w_t, 1)
            base_ms = profile_fn(jax.jit(jnp.matmul), settings, xp, wp)
            return max(fused_ms - base_ms, 0.0)
    except Exception:
        return None
    return None


def _emulation_scale(estimator) -> float:
    """The constant factor _scale_for_emulated_shards multiplies into every
    compute-op prediction on a calibrated emulated mesh (ndev / measured
    shard speedup). The audit's measured side is a single-piece,
    single-device run, so predictions must be divided back by this factor
    or the ratio would conflate the DELIBERATE emulation scaling with
    model fidelity. 1.0 on real hardware and uncalibrated searches."""
    try:
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            _scale_for_emulated_shards,
        )

        return float(_scale_for_emulated_shards(1.0, estimator))
    except Exception:
        return 1.0


def audit_plan(
    pcg,
    mapping: Dict,
    cost_estimator,
    machine_mesh=None,
    shardings: Optional[Dict] = None,
    settings=None,
    top_n: int = 5,
    optimizer_state_slots: int = 2,
    fused_edges: Optional[Dict[int, str]] = None,
    overlap_predictions: Optional[Dict[int, float]] = None,
    movement_store=None,
    cost_store=None,
    comm_predictions: Optional[Dict[int, int]] = None,
) -> Dict[str, object]:
    """Replay the winning PCG against its cost-model predictions.

    pcg/mapping: the GraphOptimizeResult's graph and per-node MachineView
    dict. cost_estimator: the SAME estimator the search priced with (so
    `predicted_ms` is byte-identical to the DP's leaf terms).
    machine_mesh/shardings: the executor's mesh + per-tensor NamedShardings;
    when given (and the mesh has >1 device) movement edges are measured by
    running their reshard for real, otherwise `measured_ms` stays None.

    fused_edges (node idx -> "ag_matmul"/"matmul_rs"): movement edges the
    executor lowers as fused collective matmuls under --overlap; these are
    measured AS FUSED (the fused kernel's marginal cost over its bare
    matmul) instead of as standalone reshards the program no longer
    contains. overlap_predictions (node idx -> ms) carries the DP's
    overlapped-exposure prediction for those edges, reported alongside.
    movement_store: a compiler.movement_store.MovementCostStore; every
    successfully measured STANDALONE reshard is recorded there (fused
    marginals are not — they price a different lowering).
    cost_store: a compiler.cost_store.CostStore; the audit's per-op
    measured ms flow into it through the replay's LocalCostEstimator
    (an op measured by one audit is never re-timed by a later search or
    audit), and each measured op additionally records the search's
    emulation-descaled prediction as the analytic half of a correction
    pair when the pricing estimator was analytic.
    comm_predictions (node idx -> bytes): the static communication
    model's per-edge predicted collective bytes
    (compiler/machine_mapping/movement_export.py) — recorded beside each
    movement edge's ms measurement so one audit row carries both the
    time and the byte side of the movement cross-checks; the HLO census
    itself lands under the audit's "comm" key at compile time
    (FFModel._comm_cross_check)."""
    from flexflow_tpu.compiler.machine_mapping.problem_tree import (
        _leaf_key,
        map_unmapped_op_cost_estimate_key,
    )
    from flexflow_tpu.kernels.profiling import ProfilingSettings
    from flexflow_tpu.local_execution.cost_estimator import LocalCostEstimator
    from flexflow_tpu.local_execution.training_backing import param_key
    from flexflow_tpu.op_attrs.core import is_parallel_op
    from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs
    from flexflow_tpu.op_attrs.parallel_tensor_shape import get_reduced_shape

    settings = settings or ProfilingSettings(warmup_iters=1, measure_iters=3)
    local = LocalCostEstimator(
        settings, optimizer_state_slots=optimizer_state_slots,
        cost_store=cost_store,
    )
    # pair-recording gate: the audit's predicted side is the pricing
    # estimator's own number; only an ANALYTIC prediction forms a valid
    # (analytic, measured) correction pair — a measured estimator's
    # prediction IS a measurement and would fit every factor to ~1.0
    record_pairs = (
        cost_store is not None
        and type(cost_estimator).__name__ == "AnalyticTPUCostEstimator"
    )
    analytic_sig = getattr(cost_estimator, "_analytic_sig", None)
    # snapshot of the correction factors the SEARCH priced with, frozen
    # BEFORE the audit starts recording pairs: note_analytic refits the
    # factors live, and dividing a later leaf's prediction by a factor
    # fitted mid-audit (instead of the one actually applied at pricing
    # time) would bias every persisted pair of that class
    corrections_at_pricing = {}
    if record_pairs:
        corrections_at_pricing = {
            cls: c["factor"]
            for cls, c in cost_store.fit_corrections(
                analytic_sig=analytic_sig
            ).items()
        }
    mesh = None
    if machine_mesh is not None:
        mesh = getattr(machine_mesh, "mesh", machine_mesh)
        if shardings is None:
            from flexflow_tpu.parallel.sharding import pcg_shardings

            shardings = pcg_shardings(pcg, machine_mesh, mapping)
    can_measure_movement = mesh is not None and mesh.size > 1
    emulation_scale = _emulation_scale(cost_estimator)

    from flexflow_tpu.pcg.pipeline import pipeline_contexts

    pipe_ctx = pipeline_contexts(pcg)
    ops: List[Dict[str, object]] = []
    edges: List[Dict[str, object]] = []
    for n in pcg.topological_ordering():
        attrs = pcg.op_attrs(n)
        if isinstance(attrs, (InputAttrs, WeightAttrs)):
            continue
        la = pcg.layer_attrs(n)
        name = la.name or param_key(n)
        leaf = _leaf_key(pcg, n, pipe_ctx)
        view = mapping.get(n)
        key = map_unmapped_op_cost_estimate_key(leaf, view)
        # was this leaf measured BEFORE this audit replayed it? (a store
        # hit makes the estimator's "prediction" a measurement, which
        # must not be recorded as the analytic half of a correction pair)
        pre_measured = (
            not is_parallel_op(attrs)
            and record_pairs
            and cost_store.peek_op_parallel(attrs, list(leaf.input_shapes))
            is not None
        )
        try:
            predicted = float(cost_estimator.estimate_op_cost(key))
        except Exception:
            predicted = None
        if is_parallel_op(attrs):
            ins = pcg.inputs_of(n)
            outs = pcg.outputs_of(n)
            bytes_moved = (
                get_reduced_shape(pcg.tensor_shape(ins[0])).size_bytes
                if ins
                else 0
            )
            measured = None
            fused_kind = (fused_edges or {}).get(n.idx)
            fused = False
            if can_measure_movement and ins and outs:
                if fused_kind is not None:
                    measured = _measure_fused_edge_ms(
                        pcg, n, fused_kind, shardings or {}, mesh, settings
                    )
                    fused = measured is not None
                if measured is None:
                    measured = _measure_movement_ms(
                        pcg.tensor_shape(ins[0]),
                        shardings.get(ins[0]) if shardings else None,
                        shardings.get(outs[0]) if shardings else None,
                        mesh,
                        settings,
                    )
                    if (
                        measured is not None
                        and movement_store is not None
                        and ins
                    ):
                        # standalone reshard measurements feed the
                        # persistent table searches read back, keyed by
                        # the link class the measured edge actually rode
                        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (  # noqa: E501
                            movement_link_class,
                        )

                        movement_store.put_edge(
                            attrs,
                            [pcg.tensor_shape(v) for v in ins],
                            mapping.get(n),
                            measured,
                            link_class=movement_link_class(
                                attrs,
                                [pcg.tensor_shape(v) for v in ins],
                                mapping.get(n),
                                cost_estimator.machine_spec,
                            ),
                        )
            ratio = _ratio(measured, predicted)
            entry = {
                "name": name,
                "kind": type(attrs).__name__,
                "bytes": int(bytes_moved),
                "predicted_ms": _round(predicted),
                "measured_ms": _round(measured),
                "ratio": _round(ratio),
            }
            if comm_predictions and n.idx in comm_predictions:
                entry["predicted_collective_bytes"] = int(
                    comm_predictions[n.idx]
                )
            if fused_kind is not None:
                # fused edges compare the fused lowering's MEASURED
                # marginal against the serial prediction (the win) and,
                # when the DP recorded one, its overlapped prediction
                entry["fused"] = fused
                entry["fused_kind"] = fused_kind
                ov_pred = (overlap_predictions or {}).get(n.idx)
                if ov_pred is not None:
                    entry["predicted_overlapped_ms"] = _round(ov_pred)
                    entry["overlapped_ratio"] = _round(
                        _ratio(measured, ov_pred)
                    )
            edges.append(entry)
        else:
            if predicted is not None and emulation_scale != 1.0:
                # compare model fidelity, not the emulation-mesh scaling
                predicted = predicted / emulation_scale
            try:
                measured = local.estimate_operator_cost_parallel(
                    attrs, list(leaf.input_shapes)
                ).elapsed_ms
                if not math.isfinite(measured):
                    measured = None
            except Exception:
                measured = None
            if (
                record_pairs
                and not pre_measured
                and measured is not None
                and predicted is not None
                and predicted > 0
                and math.isfinite(predicted)
            ):
                # close the telemetry loop in ONE audit: the analytic
                # estimator priced a fresh leaf (possibly correction-
                # scaled — divided back out) and the replay just measured
                # it, so the pair is complete now rather than on the next
                # session's store hit. Leaves carrying a schedule-internal
                # comm term (seq-parallel attention) are skipped: the comm
                # is ADDED after scaling/correction and cannot be divided
                # back out, so the reconstructed "analytic" side would be
                # inflated by it while the single-device measurement
                # contains no comm at all.
                from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
                    seq_parallel_attention_comm_ms,
                )

                comm = seq_parallel_attention_comm_ms(
                    attrs, list(leaf.input_shapes),
                    cost_estimator.machine_spec,
                    cost_estimator.ici_latency_ms,
                    cost_estimator.dcn_latency_ms,
                    machine_view=view,
                )
                if comm == 0.0:
                    raw = predicted
                    corr = corrections_at_pricing.get(
                        type(attrs).__name__, 1.0
                    )
                    if corr > 0:
                        raw = raw / corr
                    cost_store.note_analytic_parallel(
                        attrs, list(leaf.input_shapes), raw,
                        analytic_sig=analytic_sig,
                    )
            ops.append(
                {
                    "name": name,
                    "op_type": type(attrs).__name__,
                    "predicted_ms": _round(predicted),
                    "measured_ms": _round(measured),
                    "ratio": _round(_ratio(measured, predicted)),
                }
            )

    def log_dist(entry) -> float:
        r = entry.get("ratio")
        if r is None or r <= 0:
            return 0.0
        return abs(math.log(r))

    worst = sorted(ops, key=log_dist, reverse=True)[:top_n]
    op_ratios = [o["ratio"] for o in ops]
    # fused edges compare a DIFFERENT lowering against the serial
    # prediction (the overlap win, not model error) — the fidelity
    # geomean covers only standalone-measured reshards
    edge_ratios = [e["ratio"] for e in edges if not e.get("fused")]
    summary = {
        "op_geomean_ratio": _round(_geomean(op_ratios)),
        "movement_geomean_ratio": _round(_geomean(edge_ratios)),
        "geomean_ratio": _round(_geomean(op_ratios + edge_ratios)),
        "worst_ops": [
            {"name": o["name"], "ratio": o["ratio"]}
            for o in worst
            if o.get("ratio") is not None
        ],
        "num_ops_measured": sum(1 for r in op_ratios if r is not None),
        "num_edges_measured": sum(1 for r in edge_ratios if r is not None),
        "num_fused_edges": sum(1 for e in edges if e.get("fused")),
    }
    return {
        "schema": AUDIT_SCHEMA_VERSION,
        "num_ops": len(ops),
        "num_movement_edges": len(edges),
        "movement_measured": can_measure_movement,
        # the compute predictions were divided by this factor (emulated
        # CPU-mesh scaling, _scale_for_emulated_shards) before the ratio
        "emulation_scale": _round(emulation_scale),
        "ops": ops,
        "movement_edges": edges,
        "summary": summary,
    }
