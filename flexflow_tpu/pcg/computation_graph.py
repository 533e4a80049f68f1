"""ComputationGraph: a labelled dataflow graph of operators.

Reference: lib/pcg/include/pcg/computation_graph.h:14-62 (CG =
LabelledDataflowGraph<LayerAttrs, TensorAttrs> + algorithms).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from flexflow_tpu.op_attrs.core import OpAttrs, op_type_of
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.utils.graph import DataflowGraph, DataflowOutput, Node


@dataclass(frozen=True)
class LayerAttrs:
    """Node label: op attrs + optional user-facing name
    (reference: pcg/layer_attrs.struct.toml)."""

    attrs: OpAttrs
    name: Optional[str] = None


@dataclass(frozen=True)
class TensorAttrs:
    """Value label (reference: pcg/tensor_attrs.struct.toml)."""

    shape: TensorShape
    create_grad: bool = True
    initializer: Optional[object] = None  # InitializerAttrs, for weights


class ComputationGraph(DataflowGraph):
    """DataflowGraph[LayerAttrs, TensorAttrs] with CG-specific queries."""

    def __init__(self) -> None:
        super().__init__()
        # groups of op nodes whose forward is NOT kept for the backward pass
        # but computed again there (`ComputationGraphBuilder.recompute`): a
        # plan choice the model's author states. The graph interpreter
        # honours it (`local_execution/training_backing.forward_interpreter`:
        # the one-chip and data-parallel backends); a PCG holds no such
        # groups, so the lift refuses a graph that has any
        # (`parallel_computation_graph.pcg_from_computation_graph`)
        self.recompute_groups: tuple = ()

    def layer_attrs(self, n: Node) -> LayerAttrs:
        return self.node_label(n)

    def op_attrs(self, n: Node) -> OpAttrs:
        return self.node_label(n).attrs

    def tensor_attrs(self, v: DataflowOutput) -> TensorAttrs:
        return self.value_label(v)

    def tensor_shape(self, v: DataflowOutput) -> TensorShape:
        return self.value_label(v).shape

    def layers_by_name(self) -> dict:
        return {
            self.node_label(n).name: n
            for n in self.nodes
            if self.node_label(n).name is not None
        }

    def get_layer_by_name(self, name: str) -> Node:
        matches = [n for n in self.nodes if self.node_label(n).name == name]
        assert len(matches) == 1, f"layer name {name!r} matched {len(matches)} nodes"
        return matches[0]

    def as_dot(self) -> str:
        """Graphviz dot export (reference: as_dot in pcg)."""
        lines = ["digraph computation_graph {"]
        for n in sorted(self.nodes):
            label = self.node_label(n)
            op = op_type_of(label.attrs).value
            name = f"\\n{label.name}" if label.name else ""
            lines.append(f'  {n.idx} [label="{op}{name}"];')
        for e in self.edges():
            lines.append(f"  {e.src.node.idx} -> {e.dst.node.idx};")
        lines.append("}")
        return "\n".join(lines)
