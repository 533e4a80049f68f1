"""Static communication verification of a (PCG, machine mapping) pair
(ISSUE 11): the HLO collective census cross-checked against the plan.

Unity's whole bet is that the search prices communication correctly, yet
nothing verified that the collectives the DP charged for a movement edge
are the collectives XLA actually emits. This module closes that loop the
same way ISSUE 10 closed it for memory: statically lower the plan's
donated train step through the executor's own jit path (lower-only,
never execute — `analysis/lowering.py`), extract the collective census
from the post-partitioning optimized HLO — `all-gather`, `all-reduce`,
`reduce-scatter`, `collective-permute`, `all-to-all`, plus host
transfers — with per-op bytes and replica groups, and cross-check it
against the plan's priced movement edges
(`compiler/machine_mapping/movement_export.py`).

The matcher is a budgeted pool, not a 1:1 map, because GSPMD owns the
lowering: one priced k-way collective may be decomposed into a
collective-permute + hierarchical all-gather chain, replayed in the
backward (jvp recompute), realized on the OTHER side of the op (a
Reduction's all-reduce replaced by gathering the contraction operands),
or elided entirely (a broadcast of an already-replicated value). Each
movement edge therefore exposes byte-sized collective TEMPLATES
(gather-class / reduce-class, from the export) and a slack-scaled byte
pool; each HLO collective is assigned best-fit to a compatible edge with
remaining pool. What survives unmatched is communication the search
never priced; a priced edge whose pool absorbed nothing was silently
DCE'd.

Modeled free lowerings (exempt, reported with a note, never errors):

- the trailing logit reshard chain the executor bypasses (`
  _pre_reshard_value` — loss consumes the pre-reshard value, the chain
  DCEs by design),
- host-feed reshards (edges whose value originates at an Input layer:
  forward replication/slicing happens at `device_put`, and inputs carry
  no gradient, so the step program legitimately contains nothing),
- weight-resident reshard chains fire no COMM002 (priced ~0 by design),
  but their templates stay live so per-step weight gathers / gradient
  reductions are accounted for rather than flagged unpredicted.

Rule ids (catalogued in pcg_verify.PCG_RULE_CATALOG):

COMM001 unpredicted-collective  an HLO collective above the bytes floor
                                matches no priced movement edge —
                                XLA-inserted resharding the search never
                                priced (error)
COMM002 movement-edge-dce       a priced movement edge lowered to no
                                collective at all: the program does not
                                contain the communication the search
                                paid for (error)
COMM003 bytes-band              a matched edge's lowered bytes are
                                outside the acceptance band of its
                                predicted bytes (warning)
COMM004 host-transfer           infeed/outfeed/send/recv or a host
                                callback custom-call inside the donated
                                step program (error)
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.analysis.diagnostics import (
    Diagnostic,
    error,
    human_bytes as _human_bytes,
    warning,
)

COMM_RULE_IDS = ("COMM001", "COMM002", "COMM003", "COMM004")

# defaults shared by ffcheck --comm, FFModel.compile, and comm_audit
DEFAULT_BYTES_FLOOR = 4096
DEFAULT_SLACK = 2.5
DEFAULT_BAND = 4.0

# An all-gather XLA names after a weight node's sharding constraint, or
# after the cast to the compute dtype, gathers the copy of a parameter the
# step computes with: the executor stores a weight's master cut over every
# axis the plan replicates it on (parallel/executor.py `initialize`), and
# the interpreter's constraint on the (cast) weight is what gathers it.
_WEIGHT_GATHER_RE = re.compile(r'op_name="[^"]*[/(]ff\.(?:weight\.|cast/)')

COLLECTIVE_KINDS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

# census matching classes (movement_export.GATHER / REDUCE)
_GATHER_CLASS = frozenset({"all-gather", "all-to-all"})
_REDUCE_CLASS = frozenset({"all-reduce", "reduce-scatter"})
# a permute is a routing hop XLA uses inside either decomposition
_EITHER_CLASS = frozenset({"collective-permute"})

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_INSTR_RE = re.compile(
    r"%(?P<name>[\w.\-]+)\s*=\s*(?P<type>.*?)\s"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|ragged-all-to-all|all-to-all|custom-call|infeed|outfeed"
    r"|send-done|recv-done|send|recv)(?P<start>-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,<=\s]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")
_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_SRC_RE = re.compile(r'source_file="([^"]*)"(?:.*?source_line=(\d+))?')

# custom-call targets that move data to/from the host (COMM004); plain
# partitioning/annotation custom-calls (Sharding, SPMDFullToShardShape,
# TopK, ...) are not communication
_HOST_TARGET_RE = re.compile(
    r"callback|host_to_device|device_to_host|SendToHost|RecvFromHost|"
    r"tpu_host_transfer",
    re.IGNORECASE,
)


@dataclass
class HloCollective:
    """One collective (or host-transfer) instruction of the compiled
    step program."""

    kind: str  # canonical opcode ("all-gather", ... or "host-transfer")
    name: str  # HLO instruction name
    bytes: int  # per-device materialized result bytes
    group_size: int = 1  # participants per replica group (permute: 2)
    op_name: str = ""  # jax op_name metadata tail, when present
    source: str = ""  # source_file:line metadata, when present
    target: str = ""  # custom-call target (host transfers)
    # an all-gather of a parameter's compute copy (`_WEIGHT_GATHER_RE`)
    weight_gather: bool = False

    def to_json(self) -> dict:
        d = {
            "kind": self.kind,
            "name": self.name,
            "bytes": int(self.bytes),
            "group_size": int(self.group_size),
        }
        if self.op_name:
            d["op_name"] = self.op_name
        if self.target:
            d["target"] = self.target
        return d


def _shape_bytes(type_str: str, largest_only: bool = False) -> int:
    """Payload bytes of an HLO result type. `largest_only`: async
    `-start` forms return a tuple carrying the operand alias beside the
    destination (plus u32 context scalars); counting the whole tuple
    would double the materialized unit the predictions are defined in,
    so those take the largest single element (== the destination for
    every async collective: gather grows, reduce/permute preserve)."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(type_str):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue  # token[] / opaque[] carry no payload bytes
        n = 1
        for d in dims.replace("<=", "").split(","):
            d = d.strip()
            if d:
                n *= int(d)
        sizes.append(n * size)
    if not sizes:
        return 0
    return max(sizes) if largest_only else sum(sizes)


def _group_size(line: str) -> int:
    """Participants per replica group; 0 means ALL devices (HLO's empty
    `replica_groups={}` form in replica mode); 1 means a degenerate
    single-participant group (a copy, not communication)."""
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    if "replica_groups={}" in line:
        return 0  # empty groups = one group of every device
    return 1


def _meta(line: str) -> Tuple[str, str]:
    op_name = ""
    m = _OPNAME_RE.search(line)
    if m:
        # keep the informative tail of the jax op path
        op_name = "/".join(m.group(1).split("/")[-2:])
    src = ""
    m = _SRC_RE.search(line)
    if m:
        src = m.group(1).rsplit("/", 1)[-1]
        if m.group(2):
            src += f":{m.group(2)}"
    return op_name, src


def extract_collectives(hlo_text: str) -> List[HloCollective]:
    """Parse the optimized HLO module text into the collective census.
    Async `-start` forms are counted once ( `-done` halves are skipped);
    host transfers (infeed/outfeed/send/recv and host-callback
    custom-calls) are returned as kind "host-transfer"."""
    out: List[HloCollective] = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if m is None:
            continue
        op = m.group("op")
        if op in ("send-done", "recv-done"):
            continue  # counted at their -start/plain halves
        op_name, src = _meta(line)
        if op == "custom-call":
            tm = _TARGET_RE.search(line)
            target = tm.group(1) if tm else ""
            if not _HOST_TARGET_RE.search(target):
                continue  # partitioning/annotation custom-call
            out.append(
                HloCollective(
                    kind="host-transfer",
                    name=m.group("name"),
                    bytes=_shape_bytes(m.group("type")),
                    op_name=op_name,
                    source=src,
                    target=target,
                )
            )
            continue
        if op in ("infeed", "outfeed", "send", "recv"):
            out.append(
                HloCollective(
                    kind="host-transfer",
                    name=m.group("name"),
                    bytes=_shape_bytes(m.group("type")),
                    op_name=op_name,
                    source=src,
                    target=op,
                )
            )
            continue
        kind = "all-to-all" if op == "ragged-all-to-all" else op
        nbytes = _shape_bytes(
            m.group("type"), largest_only=bool(m.group("start"))
        )
        if op == "collective-permute":
            group = 2  # pairwise routing hop
            pm = _PAIRS_RE.search(line)
            if pm:
                pairs = re.findall(r"\{(\d+),(\d+)\}", pm.group(1))
                moving = sum(1 for a, b in pairs if a != b)
                if moving == 0:
                    continue  # identity permute: no data moves
        else:
            group = _group_size(line)
            if group == 1:
                continue  # single-participant collective: a copy
        out.append(
            HloCollective(
                kind=kind,
                name=m.group("name"),
                bytes=nbytes,
                group_size=group,
                op_name=op_name,
                source=src,
                weight_gather=(
                    kind == "all-gather"
                    and _WEIGHT_GATHER_RE.search(line) is not None
                ),
            )
        )
    return out


def census_by_kind(
    collectives: Sequence[HloCollective],
) -> Dict[str, Dict[str, int]]:
    out: Dict[str, Dict[str, int]] = {}
    for c in collectives:
        e = out.setdefault(c.kind, {"count": 0, "bytes": 0})
        e["count"] += 1
        e["bytes"] += c.bytes
    return out


# ---------------------------------------------------------------------------
# cross-check: census vs priced movement edges
# ---------------------------------------------------------------------------


@dataclass
class EdgeMatch:
    """One movement edge's accounting after matching."""

    prediction: object  # MovementEdgePrediction
    pool_bytes: int = 0  # slack-scaled byte budget
    matched_bytes: int = 0
    matched_count: int = 0
    # calibration counter: assigned bytes accumulated only UNTIL the
    # prediction is satisfied — a priced k-way collective often lowers
    # as several pieces (per-projection grad reduces, permute+gather
    # chains), which should all count, while slack absorbed AFTER the
    # prediction is met (jvp replays, attention-internal reductions)
    # measures the matcher, not the byte model
    realized_bytes: int = 0
    exempt: Optional[str] = None  # "bypassed" / "host-feed" / None
    group: int = -1  # reshard-chain id (consecutive movement edges)

    def to_json(self) -> dict:
        d = self.prediction.to_json()
        d["matched_bytes"] = int(self.matched_bytes)
        d["matched_collectives"] = int(self.matched_count)
        d["realized_bytes"] = int(self.realized_bytes)
        d["exempt"] = self.exempt
        pb = d["predicted_bytes"]
        d["bytes_ratio"] = (
            round(self.realized_bytes / pb, 4)
            if pb and self.realized_bytes
            else None
        )
        return d


@dataclass
class CommAnalysis:
    collectives: List[HloCollective]
    edges: List[EdgeMatch]
    unmatched: List[HloCollective]
    host_transfers: List[HloCollective]
    bytes_floor: int = DEFAULT_BYTES_FLOOR
    slack: float = DEFAULT_SLACK
    band: float = DEFAULT_BAND
    # geomean of matched/predicted bytes over edges with both sides > 0
    bytes_geomean: Optional[float] = None
    extra: Dict[str, object] = field(default_factory=dict)
    # the gathers of the parameters' compute copies the update rule
    # predicts (HloCollective.weight_gather), kept out of the movement
    # edges' pools, and the float32 bytes the rule predicts a device
    # gathers once (None: no prediction was given, nothing set aside)
    weight_gathers: List[HloCollective] = field(default_factory=list)
    weight_gather_bytes: Optional[int] = None


def _compatible(collective_kind: str, template_classes: frozenset) -> bool:
    from flexflow_tpu.compiler.machine_mapping.movement_export import (
        GATHER,
        REDUCE,
    )

    if collective_kind in _EITHER_CLASS:
        # a permute is a routing hop inside gather/reduce decompositions
        # AND the sole realization of a p2p stage edge
        return bool(template_classes)
    if collective_kind in _GATHER_CLASS:
        return GATHER in template_classes
    if collective_kind in _REDUCE_CLASS:
        return REDUCE in template_classes
    return False


def trailing_reshard_nodes(pcg, logits=None) -> frozenset:
    """Node indices of the trailing reshard chains the executor bypasses:
    the loss consumes the pre-reshard value
    (`executor._pre_reshard_value`), and a sink nothing consumes is dead
    code, so these Combine/Repartition nodes DCE by design. Walks EVERY
    unconsumed non-weight output (multi-head models have several) plus
    any explicitly-given logit tensors (FFModel passes the instance's
    name-resolved logit, which may differ from the topological sink)."""
    from flexflow_tpu.op_attrs.ops import WeightAttrs
    from flexflow_tpu.parallel.executor import _pre_reshard_value

    sinks = list(logits or [])
    for n in pcg.topological_ordering():
        if isinstance(pcg.op_attrs(n), WeightAttrs):
            continue
        for o in pcg.outputs_of(n):
            if not pcg.uses_of(o) and o not in sinks:
                sinks.append(o)
    from flexflow_tpu.op_attrs.ops import CombineAttrs, RepartitionAttrs

    bypassed = set()
    for sink in sinks:
        try:
            kept = _pre_reshard_value(pcg, sink)
        except (AssertionError, ValueError):
            continue
        t = sink
        while t != kept:
            bypassed.add(t.node.idx)
            (t,) = pcg.inputs_of(t.node)
        # `_pre_reshard_value` keeps a trailing class-dim Combine: the
        # executor's loss code consumes COMBINED logits, so the gather is
        # in the traced step. But the census compares against the
        # COMPILED step, where the loss reads the logits only through
        # class-dim reductions/selects — GSPMD serves those from the
        # sharded operand and the kept gather is dead code in the
        # optimized HLO. Walk past it (and any reshards beneath) for the
        # exemption set; stop at Replicate/Reduction/compute, whose
        # collectives are real. If the lowering ever DOES materialize the
        # gather, its collective lands unmatched and COMM001 reports it.
        while isinstance(
            pcg.op_attrs(t.node), (CombineAttrs, RepartitionAttrs)
        ):
            bypassed.add(t.node.idx)
            (t,) = pcg.inputs_of(t.node)
    return frozenset(bypassed)


def cross_check_comm(
    predictions: Sequence,
    collectives: Sequence[HloCollective],
    bypassed_nodes: frozenset = frozenset(),
    bytes_floor: int = DEFAULT_BYTES_FLOOR,
    slack: float = DEFAULT_SLACK,
    band: float = DEFAULT_BAND,
    weight_gather_bytes: Optional[int] = None,
) -> CommAnalysis:
    """Assign each HLO collective to a priced movement edge (budgeted
    best-fit pools — see module docstring) and compute the per-edge and
    aggregate accounting.

    The all-gathers of the parameters' compute copies (the second half of
    the sharded weight update; the first, the gradient's reduction into a
    shard, is a Replicate edge's reduce-class template) belong to no
    movement edge. With `weight_gather_bytes` (the update rule's own
    prediction, `DistributedTrainingInstance.update_record`: the piece
    bytes of every leaf stored cut finer than the PCG places it) they are
    set aside and held to it: XLA may gather a leaf again in the backward
    rather than keep the copy, so up to `slack` times the prediction; more
    than that and they are matched, or unpredicted, like any other.

    Two passes: priced edges first claim ONE size-appropriate collective
    each (largest-need first), so a spurious COMM002 can never be caused
    by another edge's oversized pool absorbing this edge's lowering; the
    remaining collectives then distribute best-fit across all pools."""
    edges: List[EdgeMatch] = []
    for p in predictions:
        exempt = None
        if p.node_idx in bypassed_nodes:
            exempt = "bypassed"
        elif p.input_chain:
            exempt = "host-feed"
        pool = 0 if exempt else int(
            slack * sum(b for _, b in p.templates)
        )
        edges.append(EdgeMatch(prediction=p, pool_bytes=pool, exempt=exempt))

    # reshard chains: consecutive movement edges lower as ONE composed
    # resharding (and one exempt member makes the whole chain's lowering
    # host-realized/bypassed), so group membership is the COMM002 unit
    by_node = {e.prediction.node_idx: e for e in edges}
    group_of: Dict[int, int] = {}
    for e in edges:
        n = e.prediction.node_idx
        root = n
        seen = {n}
        while True:
            up = by_node[root].prediction.input_node_idx
            if up is None or up not in by_node or up in seen:
                break
            root = up
            seen.add(root)
        group_of[n] = group_of.get(root, root)
    for e in edges:
        e.group = group_of[e.prediction.node_idx]
    # microbatch collective-permute chains (ISSUE 13): a pipelined step's
    # 1F1B schedule lowers EVERY inter-stage edge through one ppermute
    # per tick — M repeats of microbatch-sized collective-permutes that
    # must claim against the stage edges' predictions jointly, exactly
    # like a composed reshard chain. All stage-boundary predictions of
    # the region therefore share ONE chain group (the COMM002 unit).
    stage_edges = [
        e
        for e in edges
        if e.prediction.kind in ("StagePartitionAttrs", "StageMergeAttrs")
    ]
    if stage_edges:
        rep = min(e.group for e in stage_edges)
        for e in stage_edges:
            e.group = rep
    # exemption propagates over the chain: a host-feed head means the
    # whole chain's forward is realized by the feed's device_put
    exempt_groups = {e.group: e.exempt for e in edges if e.exempt}
    for e in edges:
        if e.exempt is None and e.group in exempt_groups:
            e.exempt = exempt_groups[e.group]
            e.pool_bytes = 0

    host = [c for c in collectives if c.kind == "host-transfer"]
    weight_gathers = [c for c in collectives if c.weight_gather]
    if weight_gather_bytes is None or (
        sum(c.bytes for c in weight_gathers) > slack * weight_gather_bytes
    ):
        weight_gathers = []
    aside = {id(c) for c in weight_gathers}
    real = [
        c for c in collectives
        if c.kind != "host-transfer" and id(c) not in aside
    ]
    remaining = {id(e): e.pool_bytes for e in edges}
    assigned: set = set()

    def assign(c: HloCollective, e: EdgeMatch) -> None:
        assigned.add(id(c))
        remaining[id(e)] -= c.bytes
        if e.realized_bytes < e.prediction.predicted_bytes:
            e.realized_bytes += c.bytes
        e.matched_bytes += c.bytes
        e.matched_count += 1

    def compat(c: HloCollective, e: EdgeMatch) -> bool:
        return _compatible(
            c.kind, frozenset(cls for cls, _ in e.prediction.templates)
        )

    # pass 1: every priced edge claims its best single collective
    priced = sorted(
        (
            e
            for e in edges
            if not e.exempt and e.prediction.predicted_bytes >= bytes_floor
        ),
        key=lambda e: (-e.prediction.predicted_bytes, e.prediction.node_idx),
    )
    for e in priced:
        want = e.prediction.predicted_bytes
        pick = None
        for c in real:
            if id(c) in assigned or c.bytes > remaining[id(e)]:
                continue
            if c.bytes < bytes_floor or not compat(c, e):
                continue
            # closest in log-size to the predicted bytes
            d = abs(math.log(max(c.bytes, 1) / max(want, 1)))
            if pick is None or d < pick[0]:
                pick = (d, c)
        if pick is not None:
            assign(pick[1], e)

    # pass 2: distribute the rest best-fit over the remaining pools
    unmatched: List[HloCollective] = []
    for c in sorted(real, key=lambda c: -c.bytes):
        if id(c) in assigned:
            continue
        candidates = [
            e
            for e in edges
            if not e.exempt
            and remaining[id(e)] >= c.bytes
            and compat(c, e)
        ]
        if not candidates:
            unmatched.append(c)
            continue
        best = min(
            candidates,
            key=lambda e: (
                # needy pools first: an edge whose priced bytes are not
                # yet realized is the likelier owner of this piece than
                # an already-satisfied pool with slack left
                e.realized_bytes >= e.prediction.predicted_bytes,
                remaining[id(e)],
                e.prediction.node_idx,
            ),
        )
        assign(c, best)

    # the COMM003/geomean population: every edge the DP charged bytes
    # for whose priced collective found a primary realization — the
    # ratio compares the prediction against THAT collective's
    # materialized bytes (pass-2 absorption is slack accounting and
    # would measure the matcher, not the model)
    ratios = [
        e.realized_bytes / e.prediction.predicted_bytes
        for e in edges
        if not e.exempt
        and e.prediction.predicted_bytes >= bytes_floor
        and e.realized_bytes > 0
    ]
    geomean = (
        math.exp(sum(math.log(r) for r in ratios) / len(ratios))
        if ratios
        else None
    )
    return CommAnalysis(
        collectives=list(collectives),
        edges=edges,
        unmatched=unmatched,
        host_transfers=host,
        bytes_floor=int(bytes_floor),
        slack=float(slack),
        band=float(band),
        bytes_geomean=None if geomean is None else round(geomean, 4),
        weight_gathers=weight_gathers,
        weight_gather_bytes=weight_gather_bytes,
    )




def comm_diagnostics(analysis: CommAnalysis) -> List[Diagnostic]:
    """COMM001-COMM004 over a finished cross-check."""
    diags: List[Diagnostic] = []
    floor = analysis.bytes_floor

    # COMM001: unpredicted collectives above the bytes floor, aggregated
    # by (kind, bytes, op_name) so a replayed chain reads as one finding
    groups: Dict[Tuple[str, int, str], List[HloCollective]] = {}
    for c in analysis.unmatched:
        if c.bytes < floor:
            continue
        groups.setdefault((c.kind, c.bytes, c.op_name), []).append(c)
    for (kind, nbytes, op_name), cs in sorted(
        groups.items(), key=lambda kv: -kv[0][1]
    ):
        where = f" at {op_name}" if op_name else ""
        src = f" ({cs[0].source})" if cs[0].source else ""
        # group_size 0 is the replica_groups={} sentinel: all devices
        group = (
            f"group size {cs[0].group_size}"
            if cs[0].group_size else "group: all devices"
        )
        diags.append(
            error(
                "COMM001",
                f"{len(cs)} unpredicted {kind} of "
                f"{_human_bytes(nbytes)} each ({group}){where}{src}: "
                "XLA inserted resharding the search never priced",
                tensor=cs[0].name,
                hint="the plan's shardings force a reshard no movement "
                "edge models — add the movement op the search should "
                "price, or fix the mapping that makes XLA replicate",
            )
        )

    # COMM002: a priced reshard CHAIN whose pools absorbed nothing.
    # Consecutive movement edges lower as one composed resharding, so the
    # chain is the unit — flagging each member separately would count one
    # missing collective several times.
    chains: Dict[int, List[EdgeMatch]] = {}
    for e in analysis.edges:
        chains.setdefault(e.group, []).append(e)
    for group, members in sorted(chains.items()):
        if any(e.exempt for e in members):
            continue
        if all(e.prediction.weight_resident for e in members):
            continue  # priced ~0 by design; templates only
        priced = sum(
            e.prediction.predicted_bytes
            for e in members
            if not e.prediction.weight_resident
        )
        priced_ms = sum(
            e.prediction.predicted_ms or 0.0
            for e in members
            if not e.prediction.weight_resident
        )
        if priced < floor or priced_ms <= 0:
            continue
        if any(e.matched_bytes > 0 for e in members):
            continue
        names = ", ".join(
            f"{e.prediction.name} ({e.prediction.kind}, degree "
            f"{e.prediction.degree})"
            for e in members
        )
        diags.append(
            error(
                "COMM002",
                f"movement edge chain [{names}] was priced "
                f"{priced_ms:.4f} ms for {_human_bytes(priced)} but "
                "lowered to no collective: the search overpaid for "
                "communication the program does not perform",
                node=members[0].prediction.node_idx,
                hint="the chain was DCE'd (value consumed pre-reshard or "
                "folded into an adjacent op) — the cost model should "
                "price it at zero for this consumer pattern",
            )
        )

    # COMM003: matched edges outside the per-edge acceptance band
    band = analysis.band
    for e in analysis.edges:
        p = e.prediction
        if e.exempt:
            continue  # same population as the geomean (see cross_check)
        if p.predicted_bytes < floor or e.realized_bytes <= 0:
            continue
        ratio = e.realized_bytes / p.predicted_bytes
        if ratio > band or ratio < 1.0 / band:
            diags.append(
                warning(
                    "COMM003",
                    f"movement edge {p.name} ({p.kind}) predicted "
                    f"{_human_bytes(p.predicted_bytes)} of collective "
                    f"traffic but its lowered realization stages "
                    f"{_human_bytes(e.realized_bytes)} "
                    f"({ratio:.2f}x, band {band:.1f}x)",
                    node=p.node_idx,
                    hint="the byte model for this edge kind drifted from "
                    "what GSPMD emits — recalibrate the movement "
                    "templates or investigate the lowering",
                )
            )

    # COMM004: host transfers inside the donated step program
    seen_targets = set()
    for c in analysis.host_transfers:
        key = (c.target, c.op_name)
        if key in seen_targets:
            continue
        seen_targets.add(key)
        diags.append(
            error(
                "COMM004",
                f"host transfer inside the step program: {c.target or c.kind}"
                + (f" at {c.op_name}" if c.op_name else "")
                + (f" ({c.source})" if c.source else ""),
                tensor=c.name,
                hint="a callback/infeed in the donated step serializes "
                "the device against the host every step — move it out "
                "of the jitted step (LINT001 finds the Python side)",
            )
        )
    return diags


def predicted_weight_gather_bytes(instance) -> Optional[int]:
    """The float32 bytes a device receives gathering, once, every parameter
    the executor stores cut finer than the PCG places it, as the update
    rule records them for `instance`; None for a backend without the rule
    (or no instance at hand)."""
    record = getattr(instance, "update_record", None)
    return None if record is None else int(record["gather_bytes_per_device"])


def verify_comm(
    pcg,
    mapping: Optional[dict] = None,
    machine_spec=None,
    estimator=None,
    hlo_text: Optional[str] = None,
    lowered=None,
    fused_edges: Optional[Dict[int, str]] = None,
    bytes_floor: int = DEFAULT_BYTES_FLOOR,
    slack: float = DEFAULT_SLACK,
    band: float = DEFAULT_BAND,
) -> Tuple[CommAnalysis, List[Diagnostic]]:
    """One-call driver: export the plan's movement predictions, obtain
    the compiled step HLO (lowering the plan unless `hlo_text`/`lowered`
    is supplied), and cross-check. Returns (analysis, diagnostics)."""
    from flexflow_tpu.compiler.machine_mapping.movement_export import (
        export_movement_predictions,
    )

    predictions = export_movement_predictions(
        pcg, mapping, estimator=estimator, machine_spec=machine_spec,
        fused_edges=fused_edges,
    )
    if hlo_text is None:
        if lowered is None:
            from flexflow_tpu.analysis.lowering import lower_plan

            lowered = lower_plan(pcg, mapping, machine_spec=machine_spec)
        hlo_text = lowered.hlo_text()
    analysis = cross_check_comm(
        predictions,
        extract_collectives(hlo_text),
        bypassed_nodes=trailing_reshard_nodes(pcg),
        bytes_floor=bytes_floor,
        slack=slack,
        band=band,
        weight_gather_bytes=predicted_weight_gather_bytes(
            getattr(lowered, "instance", None)
        ),
    )
    return analysis, comm_diagnostics(analysis)


# ---------------------------------------------------------------------------
# rendering (ffcheck --comm)
# ---------------------------------------------------------------------------


def format_comm_table(analysis: CommAnalysis) -> str:
    """Human-readable census + per-edge accounting (`ffcheck --comm`)."""
    lines = ["collective census:"]
    for kind, e in sorted(census_by_kind(analysis.collectives).items()):
        lines.append(
            f"  {kind:<20} x{e['count']:<4} {_human_bytes(e['bytes'])}"
        )
    if not analysis.collectives:
        lines.append("  (none)")
    lines.append(
        "edge    kind                 degree  predicted     lowered    note"
    )
    for e in analysis.edges:
        p = e.prediction
        note = e.exempt or (
            "weight-resident" if p.weight_resident else ""
        )
        if p.fused_kind:
            note = (note + " " if note else "") + f"fused:{p.fused_kind}"
        lines.append(
            f"{p.node_idx:>5}  {p.kind:<20} {p.degree:>6}  "
            f"{_human_bytes(p.predicted_bytes):>10}  "
            f"{_human_bytes(e.matched_bytes):>10}  {note}"
        )
    if analysis.unmatched:
        over = [
            c for c in analysis.unmatched if c.bytes >= analysis.bytes_floor
        ]
        lines.append(
            f"unmatched collectives: {len(analysis.unmatched)} "
            f"({len(over)} above the {_human_bytes(analysis.bytes_floor)} "
            "floor)"
        )
    if analysis.bytes_geomean is not None:
        lines.append(
            f"lowered/predicted bytes geomean: {analysis.bytes_geomean}"
        )
    return "\n".join(lines)


def comm_summary_json(analysis: CommAnalysis) -> dict:
    """The `ffcheck --comm --json` per-file summary object (one line per
    file, beside the per-diagnostic lines): stable schema v1 — the field
    tuple is pinned by tests/test_comm_analysis.py."""
    over_floor = [
        c for c in analysis.unmatched if c.bytes >= analysis.bytes_floor
    ]
    return {
        "comm": 1,  # schema version
        "bytes_floor": int(analysis.bytes_floor),
        "slack": analysis.slack,
        "band": analysis.band,
        "census": census_by_kind(analysis.collectives),
        "num_collectives": len(analysis.collectives),
        "num_edges": len(analysis.edges),
        "edges": [e.to_json() for e in analysis.edges],
        "matched_bytes_total": int(
            sum(e.matched_bytes for e in analysis.edges)
        ),
        "predicted_bytes_total": int(
            sum(
                e.prediction.predicted_bytes
                for e in analysis.edges
                if not e.exempt
            )
        ),
        "unmatched_collectives": len(over_floor),
        "unmatched_bytes": int(sum(c.bytes for c in over_floor)),
        "unmatched": [c.to_json() for c in over_floor[:20]],
        "host_transfers": len(analysis.host_transfers),
        "bytes_geomean": analysis.bytes_geomean,
        "weight_gathers": {
            "count": len(analysis.weight_gathers),
            "bytes": int(sum(c.bytes for c in analysis.weight_gathers)),
            "predicted_bytes": analysis.weight_gather_bytes,
        },
    }
