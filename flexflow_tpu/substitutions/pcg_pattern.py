"""PCG pattern + subgraph matching.

Reference: lib/substitutions/include/substitutions/pcg_pattern.h:17
(find_pattern_matches) + unlabelled/find_pattern_matches.h. The reference
matches via recursive pattern splitting; here a backtracking subgraph
isomorphism over the (small) pattern gives the same match set: an injective
map pattern-node -> pcg-node consistent with slot-ordered dataflow edges, with
pattern graph inputs binding to arbitrary host values, and all attribute
constraints satisfied.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from flexflow_tpu.pcg.parallel_computation_graph import ParallelComputationGraph
from flexflow_tpu.substitutions.operator_pattern import (
    OperatorAttributePattern,
    op_attrs_satisfy_pattern,
)
from flexflow_tpu.substitutions.tensor_pattern import (
    TensorAttributePattern,
    tensor_attrs_satisfy_pattern,
)
from flexflow_tpu.utils.graph import (
    DataflowOutput,
    GraphInput,
    Node,
    OpenDataflowGraph,
)


class PCGPattern:
    """An open dataflow graph whose node labels are OperatorAttributePatterns
    and whose value labels are TensorAttributePatterns."""

    def __init__(self) -> None:
        self.graph: OpenDataflowGraph = OpenDataflowGraph()

    def add_input(
        self, pattern: Optional[TensorAttributePattern] = None
    ) -> GraphInput:
        return self.graph.add_graph_input(pattern or TensorAttributePattern.any())

    def add_operator(
        self,
        op_pattern: OperatorAttributePattern,
        inputs,
        num_outputs: int = 1,
        output_patterns=None,
    ) -> Tuple[Node, List[DataflowOutput]]:
        out_patterns = output_patterns or [
            TensorAttributePattern.any() for _ in range(num_outputs)
        ]
        return self.graph.add_node(op_pattern, list(inputs), out_patterns)


@dataclass(frozen=True)
class PatternMatch:
    """reference: unlabelled/pattern_matching (node assignment + input binding)."""

    node_assignment: Tuple[Tuple[Node, Node], ...]  # (pattern node, pcg node)
    input_assignment: Tuple[Tuple[GraphInput, DataflowOutput], ...]

    def node_map(self) -> Dict[Node, Node]:
        return dict(self.node_assignment)

    def input_map(self) -> Dict[GraphInput, DataflowOutput]:
        return dict(self.input_assignment)


def _find_pattern_matches_native(
    pattern: PCGPattern, pcg: ParallelComputationGraph
) -> Optional[List[PatternMatch]]:
    """Native C++ matcher (native/src/ffcore.cc ffc_pattern_match): attribute
    and arity checks are prefiltered into compat matrices here; the native
    core enumerates injective slot-consistent node maps in the same DFS order
    as the Python fallback."""
    from flexflow_tpu import native_lib

    if not native_lib.native_available():
        return None
    pg = pattern.graph
    pattern_nodes = pg.topological_ordering()
    p_id = {n: i for i, n in enumerate(pattern_nodes)}
    gis = pg.graph_inputs
    gi_id = {g: i for i, g in enumerate(gis)}

    # Host STRUCTURAL arrays are rule-independent, and the search loops call
    # this once per rule on the same state (~50x) — cache them on the pcg.
    # DataflowGraph is additions-only structurally (labels can be reset, but
    # compat below re-reads labels every call), so (n nodes, n values) is a
    # sound staleness stamp.
    # O(1) counts, not the nodes property / all_values() (frozenset alloc +
    # sort per call would reintroduce the cost this cache removes)
    stamp = (len(pcg._g._nodes), len(pcg._value_label))
    cached = getattr(pcg, "_match_host_arrays", None)
    if cached is not None and cached[0] == stamp:
        _, host_nodes, host_values, v_id, h_slots = cached
    else:
        host_nodes = sorted(pcg.nodes)
        h_id = {n: i for i, n in enumerate(host_nodes)}
        host_values = [v for n in host_nodes for v in pcg.outputs_of(n)]
        v_id = {v: i for i, v in enumerate(host_values)}
        h_slots = [
            [(h_id[hv.node], hv.idx, v_id[hv]) for hv in pcg.inputs_of(hn)]
            for hn in host_nodes
        ]
        pcg._match_host_arrays = (stamp, host_nodes, host_values, v_id, h_slots)

    p_slots = []
    for pn in pattern_nodes:
        slots = []
        for pv in pg.inputs_of(pn):
            if isinstance(pv, GraphInput):
                slots.append((-1, gi_id[pv]))
            else:
                slots.append((p_id[pv.node], pv.idx))
        p_slots.append(slots)

    # hoist the per-host reads out of the pattern x host double loop (labels
    # are re-read each call on purpose — they are the mutable part)
    host_info = [
        (
            len(pcg.inputs_of(hn)),
            pcg.op_attrs(hn),
            [pcg.tensor_shape(ho) for ho in pcg.outputs_of(hn)],
        )
        for hn in host_nodes
    ]
    compat = []
    for pn in pattern_nodes:
        p_nin = len(pg.inputs_of(pn))
        p_lbl = pg.node_label(pn)
        p_out_lbls = [pg.value_label(po) for po in pg.outputs_of(pn)]
        compat.append(
            [
                n_in == p_nin
                and len(shapes) == len(p_out_lbls)
                and op_attrs_satisfy_pattern(attrs, p_lbl)
                and all(
                    tensor_attrs_satisfy_pattern(s, pl)
                    for pl, s in zip(p_out_lbls, shapes)
                )
                for n_in, attrs, shapes in host_info
            ]
        )
    host_value_shapes = [pcg.tensor_shape(hv) for hv in host_values]
    gi_compat = [
        [
            tensor_attrs_satisfy_pattern(s, pg.value_label(gi))
            for s in host_value_shapes
        ]
        for gi in gis
    ]

    raw = native_lib.pattern_match(
        p_slots, h_slots, len(gis), len(host_values), compat, gi_compat
    )
    if raw is None:
        return None  # capacity exceeded; fall back
    matches = []
    for node_row, gi_row in raw:
        node_map = {
            pattern_nodes[pi]: host_nodes[hi] for pi, hi in enumerate(node_row)
        }
        input_map = {
            gis[g]: host_values[vid]
            for g, vid in enumerate(gi_row)
            if vid >= 0
        }
        matches.append(
            PatternMatch(
                tuple(sorted(node_map.items())),
                tuple(sorted(input_map.items())),
            )
        )
    return matches


def find_pattern_matches(
    pattern: PCGPattern, pcg: ParallelComputationGraph
) -> List[PatternMatch]:
    native = _find_pattern_matches_native(pattern, pcg)
    if native is not None:
        return native
    pg = pattern.graph
    pattern_nodes = pg.topological_ordering()
    matches: List[PatternMatch] = []

    def value_matches(
        pval, hval: DataflowOutput, node_map: Dict[Node, Node], input_map
    ) -> bool:
        """Can pattern value pval (node output or graph input) bind host value hval?"""
        if isinstance(pval, GraphInput):
            if pval in input_map:
                return input_map[pval] == hval
            # constraint check happens at bind time
            return tensor_attrs_satisfy_pattern(
                pcg.tensor_shape(hval), pg.value_label(pval)
            )
        # pattern node output: producer must already be mapped to hval's node
        mapped = node_map.get(pval.node)
        return mapped == hval.node and pval.idx == hval.idx

    def backtrack(i: int, node_map: Dict[Node, Node], input_map) -> None:
        if i == len(pattern_nodes):
            matches.append(
                PatternMatch(
                    tuple(sorted(node_map.items())),
                    tuple(sorted(input_map.items())),
                )
            )
            return
        pnode = pattern_nodes[i]
        p_inputs = pg.inputs_of(pnode)
        used = set(node_map.values())
        for hnode in sorted(pcg.nodes):
            if hnode in used:
                continue
            if not op_attrs_satisfy_pattern(pcg.op_attrs(hnode), pg.node_label(pnode)):
                continue
            h_inputs = pcg.inputs_of(hnode)
            if len(h_inputs) != len(p_inputs):
                continue
            if len(pg.outputs_of(pnode)) != len(pcg.outputs_of(hnode)):
                continue
            # check output tensor constraints
            if not all(
                tensor_attrs_satisfy_pattern(
                    pcg.tensor_shape(ho), pg.value_label(po)
                )
                for po, ho in zip(pg.outputs_of(pnode), pcg.outputs_of(hnode))
            ):
                continue
            if not all(
                value_matches(pv, hv, node_map, input_map)
                for pv, hv in zip(p_inputs, h_inputs)
            ):
                continue
            new_input_map = dict(input_map)
            ok = True
            for pv, hv in zip(p_inputs, h_inputs):
                if isinstance(pv, GraphInput):
                    if pv in new_input_map and new_input_map[pv] != hv:
                        ok = False
                        break
                    new_input_map[pv] = hv
            if not ok:
                continue
            node_map[pnode] = hnode
            backtrack(i + 1, node_map, new_input_map)
            del node_map[pnode]

    backtrack(0, {}, {})
    return matches
