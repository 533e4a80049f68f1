"""Per-movement-edge prediction export from the machine-mapping DPs
(ISSUE 11).

Both DPs — the Python series-parallel DP in
`get_optimal_machine_mapping.py` and the native `ffc_mm_dp` (whose leaf
tables `native_dp.py` flattens from the identical keys) — price every
parallel op of a candidate through ONE path:
`_leaf_key(pcg, n)` -> `map_unmapped_op_cost_estimate_key(leaf, view)` ->
`estimator.estimate_op_cost(key)` (exact native/Python parity is pinned
by tests/test_machine_mapping.py). This module re-walks a solved plan
through that same path and exports, per movement edge, what the search
charged: the ms, the moved bytes, and — for the static communication
cross-check (`analysis/comm_analysis.py`, `ffcheck --comm`) — the
COLLECTIVES the charge implies, as byte-sized templates the lowered HLO
census is matched against.

The byte templates mirror `parallel_op_cost_ms`'s direction accounting
(cost_estimator.py): training charges BOTH directions, so each edge
exports a forward and a backward template. `predicted_bytes` is the
MATERIALIZED-output bytes the priced collectives stage (the unit the HLO
side measures: an all-gather's gathered result, an all-reduce's reduced
result), not wire traffic — the two sides of the COMM003 ratio must share
units. Weight-resident reshard chains are priced at ~0 recurring ms
(parameters are stored post-reshard from init), but their templates STILL
carry the weight bytes: GSPMD is free to materialize a gathered weight or
reduce a sharded weight's gradient per step, and those collectives are
*accounted-for* lowerings of the chain, not unpredicted resharding.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# template classes the HLO census matches against (comm_analysis):
# "gather" covers all-gather / broadcast-ish data movement, "reduce"
# covers all-reduce / reduce-scatter; collective-permute routing hops
# are compatible with either. "p2p" (ISSUE 13) is the pipeline
# inter-stage microbatch handoff — ONLY collective-permutes realize it
# (the 1F1B schedule's ppermute chain, M hops per direction per step).
GATHER = "gather"
REDUCE = "reduce"
P2P = "p2p"


@dataclass
class MovementEdgePrediction:
    """One movement edge of a solved (PCG, mapping) plan, with the DP's
    charged cost and the collective templates its lowering may realize."""

    node_idx: int
    name: str
    kind: str  # CombineAttrs / RepartitionAttrs / ReplicateAttrs / ReductionAttrs
    degree: int
    bytes_global: int  # global reduced bytes of the moved tensor
    predicted_ms: Optional[float]
    # materialized bytes the PRICED collectives stage (0 when the charge
    # is ~free, e.g. weight-resident repartition) — the COMM003 unit
    predicted_bytes: int
    weight_resident: bool = False
    # the edge's value originates at an Input layer through parallel ops
    # only: its forward replication/slicing is realized by the host feed's
    # device_put, and inputs carry no gradient, so an empty lowering is
    # modeled, not DCE
    input_chain: bool = False
    # (class, bytes) collectives this edge's lowering may realize
    templates: Tuple[Tuple[str, int], ...] = ()
    fused_kind: Optional[str] = None  # PR-6 overlap site lowering, if any
    # producing node of the moved tensor — when that node is itself a
    # movement edge, the two form one reshard CHAIN (GSPMD lowers a chain
    # as one composed resharding, so the census accounts chains jointly)
    input_node_idx: Optional[int] = None
    # link class the DP charged this edge on (ISSUE 17): "ici" intra-slice,
    # "dcn" when the mapped views route the movement across the slice
    # boundary (cost_estimator.movement_link_class — the same derivation
    # that keys the v3 movement store, so multi-slice placement is
    # assertable from search_provenance["comm"] alone)
    link_class: Optional[str] = None
    extra: Dict[str, object] = field(default_factory=dict)

    def to_json(self) -> dict:
        return {
            "node": self.node_idx,
            "name": self.name,
            "kind": self.kind,
            "degree": self.degree,
            "bytes": int(self.bytes_global),
            "predicted_ms": (
                None if self.predicted_ms is None
                else round(float(self.predicted_ms), 6)
            ),
            "predicted_bytes": int(self.predicted_bytes),
            "weight_resident": self.weight_resident,
            "input_chain": self.input_chain,
            "fused_kind": self.fused_kind,
            "link_class": self.link_class,
        }


def _edge_degree(attrs) -> int:
    for a in (
        "repartition_degree",
        "combine_degree",
        "replicate_degree",
        "reduction_degree",
    ):
        d = getattr(attrs, a, None)
        if d is not None:
            return int(d)
    return 1


def _input_chain(pcg, v) -> bool:
    """Does `v` trace back to an Input layer through single-input
    parallel-op wrappers only (the host-feed analogue of
    problem_tree._from_weight)?"""
    from flexflow_tpu.op_attrs.core import is_parallel_op
    from flexflow_tpu.op_attrs.ops import InputAttrs

    while True:
        attrs = pcg.op_attrs(v.node)
        if isinstance(attrs, InputAttrs):
            return True
        if not is_parallel_op(attrs):
            return False
        ins = pcg.inputs_of(v.node)
        if len(ins) != 1:
            return False
        v = ins[0]


def _templates_for(
    kind: str, t_bytes: int, weight_resident: bool
) -> Tuple[Tuple[Tuple[str, int], ...], int]:
    """(templates, predicted_bytes) for one edge kind. Templates name
    every collective the lowering MAY stage; predicted_bytes counts only
    the ones the DP actually charged for (parallel_op_cost_ms)."""
    t = int(t_bytes)
    if kind == "CombineAttrs":
        # fwd all-gather materializes the full tensor; bwd is a local
        # re-slice (XLA's jvp replay may stage the gather again)
        return ((GATHER, t),), t
    if kind == "RepartitionAttrs":
        if weight_resident:
            # priced free (params live sharded from init), but GSPMD may
            # still materialize the gathered weight per step and reduce
            # its gradient pieces back
            return ((GATHER, t), (REDUCE, t)), 0
        # fwd re-slice is local; bwd all-gathers the grad pieces
        return ((GATHER, t),), t
    if kind == "ReplicateAttrs":
        if weight_resident:
            # resident replicas; the recurring collective is the bwd
            # gradient all-reduce (the per-step DP weight sync), lowered
            # as a reduce-scatter into the shard the update runs on; the
            # all-gather of the weight's compute copy that completes it
            # the census holds to the update rule, not to this pool
            # (comm_analysis.cross_check_comm, `weight_gather_bytes`)
            return ((REDUCE, t), (GATHER, t)), t
        # fwd broadcast (often elided when the value is already
        # replicated) + bwd gradient all-reduce
        return ((GATHER, t), (REDUCE, t)), t
    if kind == "ReductionAttrs":
        # fwd all-reduce of the partial sums; bwd broadcast (usually
        # elided — the grad is already replicated)
        return ((REDUCE, t), (GATHER, t)), t
    return (), 0


def _default_estimator(machine_spec):
    import jax

    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
        AnalyticTPUCostEstimator,
    )

    if jax.default_backend() == "cpu":
        return AnalyticTPUCostEstimator(
            machine_spec, peak_flops=5e10, hbm_gbps=10.0,
            ici_latency_ms=0.1, dcn_latency_ms=0.2,
            emulated_mesh=True,
        )
    return AnalyticTPUCostEstimator(machine_spec)


def export_movement_predictions(
    pcg,
    mapping: Optional[dict] = None,
    estimator=None,
    machine_spec=None,
    fused_edges: Optional[Dict[int, str]] = None,
) -> List[MovementEdgePrediction]:
    """Walk a solved plan's movement edges and export the DP's charged
    predictions (see module docstring). `estimator` should be the SAME
    estimator the search priced with so `predicted_ms` is byte-identical
    to the DP's movement terms; pass None to price with the default
    analytic constants for the attached backend (ffcheck's standalone
    mode, where no search ran)."""
    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
        movement_link_class,
    )
    from flexflow_tpu.compiler.machine_mapping.problem_tree import (
        _from_weight,
        _leaf_key,
        map_unmapped_op_cost_estimate_key,
    )
    from flexflow_tpu.op_attrs.core import is_parallel_op
    from flexflow_tpu.op_attrs.parallel_tensor_shape import get_reduced_shape

    if estimator is None:
        if machine_spec is None:
            raise ValueError(
                "export_movement_predictions needs an estimator or a "
                "machine_spec to build the default one from"
            )
        estimator = _default_estimator(machine_spec)
    fused_edges = fused_edges or {}
    from flexflow_tpu.op_attrs.core import is_stage_op
    from flexflow_tpu.op_attrs.ops import StagePartitionAttrs
    from flexflow_tpu.pcg.pipeline import pipeline_contexts

    pipeline_ctx = pipeline_contexts(pcg)
    out: List[MovementEdgePrediction] = []
    for n in pcg.topological_ordering():
        attrs = pcg.op_attrs(n)
        if is_stage_op(attrs):
            # pipeline-stage boundary (new movement kind, ISSUE 13): an
            # interior StagePartition is M point-to-point microbatch hops
            # per direction per step — the census must see its
            # collective-permute chain as accounted-for, and COMM003's
            # unit is the full fwd+bwd activation traffic (2x tensor).
            # Entry (stage 0) and StageMerge are local slicing: priced 0,
            # no templates, and COMM002 never fires on zero-ms edges.
            ins = pcg.inputs_of(n)
            la = pcg.layer_attrs(n)
            t_bytes = (
                get_reduced_shape(pcg.tensor_shape(ins[0])).size_bytes
                if ins
                else 0
            )
            interior = (
                isinstance(attrs, StagePartitionAttrs)
                and attrs.stage_index >= 1
            )
            leaf = _leaf_key(pcg, n, pipeline_ctx)
            view = (mapping or {}).get(n)
            key = map_unmapped_op_cost_estimate_key(leaf, view)
            try:
                predicted_ms = float(estimator.estimate_op_cost(key))
            except Exception:
                predicted_ms = None
            try:
                link = movement_link_class(
                    attrs, [pcg.tensor_shape(v) for v in ins], view,
                    estimator.machine_spec,
                )
            except Exception:
                link = None
            out.append(
                MovementEdgePrediction(
                    node_idx=n.idx,
                    name=la.name or f"n{n.idx}",
                    kind=type(attrs).__name__,
                    degree=int(getattr(attrs, "num_microbatches", 1)),
                    bytes_global=t_bytes,
                    predicted_ms=predicted_ms if interior else 0.0,
                    predicted_bytes=2 * t_bytes if interior else 0,
                    templates=((P2P, 2 * t_bytes),) if interior else (),
                    input_node_idx=ins[0].node.idx if ins else None,
                    link_class=link,
                )
            )
            continue
        if not is_parallel_op(attrs):
            continue
        ins = pcg.inputs_of(n)
        la = pcg.layer_attrs(n)
        kind = type(attrs).__name__
        t_bytes = (
            get_reduced_shape(pcg.tensor_shape(ins[0])).size_bytes
            if ins
            else 0
        )
        weight_resident = bool(ins) and all(_from_weight(pcg, v) for v in ins)
        leaf = _leaf_key(pcg, n, pipeline_ctx)
        view = (mapping or {}).get(n)
        key = map_unmapped_op_cost_estimate_key(leaf, view)
        try:
            predicted_ms = float(estimator.estimate_op_cost(key))
        except Exception:
            predicted_ms = None
        try:
            link = movement_link_class(
                attrs, [pcg.tensor_shape(v) for v in ins], view,
                estimator.machine_spec,
            )
        except Exception:
            link = None
        templates, predicted_bytes = _templates_for(
            kind, t_bytes, weight_resident
        )
        out.append(
            MovementEdgePrediction(
                node_idx=n.idx,
                name=la.name or f"n{n.idx}",
                kind=kind,
                degree=_edge_degree(attrs),
                bytes_global=t_bytes,
                predicted_ms=predicted_ms,
                predicted_bytes=predicted_bytes,
                weight_resident=weight_resident,
                input_chain=bool(ins) and all(_input_chain(pcg, v) for v in ins),
                templates=templates,
                fused_kind=fused_edges.get(n.idx),
                input_node_idx=ins[0].node.idx if ins else None,
                link_class=link,
            )
        )
    return out
