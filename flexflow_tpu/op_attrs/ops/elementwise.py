"""Elementwise ops: ElementUnary, ElementBinary, Cast, Broadcast.

Reference: op-attrs/ops/{element_unary,element_binary,cast,broadcast}.h.

Parallel semantics: elementwise ops preserve shard degrees. sum_degree may only
pass through ops that are linear in their input (scalar multiply, identity,
cast); nonlinear ops require sum_degree == 1 (a Reduction must materialize the
sum first).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Optional, Tuple

from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.op_attrs.parallel_tensor_shape import (
    ParallelTensorShape,
    ParallelTensorDims,
)


class ElementUnaryOpType(enum.Enum):
    EXP = "exp"
    LOG = "log"
    SIN = "sin"
    COS = "cos"
    IDENTITY = "identity"
    SCALAR_MULTIPLY = "scalar_multiply"
    SCALAR_ADD = "scalar_add"
    SCALAR_SUB = "scalar_sub"
    SCALAR_TRUE_DIV = "scalar_true_div"
    RELU = "relu"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    GELU = "gelu"
    SILU = "silu"
    ELU = "elu"
    RSQRT = "rsqrt"
    POW = "pow"
    SQRT = "sqrt"

    @property
    def is_linear(self) -> bool:
        """Linear ops commute with summation, so sum_degree passes through."""
        return self in (
            ElementUnaryOpType.IDENTITY,
            ElementUnaryOpType.SCALAR_MULTIPLY,
            ElementUnaryOpType.SCALAR_TRUE_DIV,
        )


class ElementBinaryOpType(enum.Enum):
    ADD = "add"
    SUB = "sub"
    MUL = "mul"
    DIV = "div"
    MAX = "max"
    MIN = "min"
    POW = "pow"

    @property
    def is_linear(self) -> bool:
        return self in (ElementBinaryOpType.ADD, ElementBinaryOpType.SUB)


@dataclass(frozen=True)
class ElementUnaryAttrs:
    op_type: ElementUnaryOpType
    scalar: Optional[float] = None

    def output_shape(self, input: TensorShape) -> TensorShape:
        return input

    def parallel_output_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        if not self.op_type.is_linear:
            assert input.sum_degree == 1, (
                f"nonlinear unary op {self.op_type} cannot consume a tensor "
                f"with sum_degree={input.sum_degree}; insert a Reduction first"
            )
        return input


@dataclass(frozen=True)
class ElementBinaryAttrs:
    op_type: ElementBinaryOpType
    # Reference carries compute type + broadcast flags; broadcasting is
    # inserted explicitly as Broadcast ops by the builder.

    def output_shape(self, lhs: TensorShape, rhs: TensorShape) -> TensorShape:
        assert lhs.dims == rhs.dims, f"elementwise shape mismatch: {lhs} vs {rhs}"
        return lhs

    def parallel_output_shape(
        self, lhs: ParallelTensorShape, rhs: ParallelTensorShape
    ) -> ParallelTensorShape:
        assert lhs.sizes() == rhs.sizes(), f"shape mismatch: {lhs} vs {rhs}"
        assert lhs.shard_degrees() == rhs.shard_degrees(), (
            f"elementwise binary requires matching shard degrees: {lhs} vs {rhs}"
        )
        if self.op_type.is_linear:  # ADD/SUB commute with summation
            # (Σa_i) ± (Σb_i) only valid as partial sums when degrees match.
            assert lhs.sum_degree == rhs.sum_degree
        else:
            assert lhs.sum_degree == 1 and rhs.sum_degree == 1, (
                f"nonlinear binary op {self.op_type} over partial sums"
            )
        return ParallelTensorShape(
            ParallelTensorDims(
                lhs.dims.shard_dims,
                lhs.sum_degree,
                min(lhs.discard_copy_degree, rhs.discard_copy_degree),
            ),
            lhs.dtype,
        )


@dataclass(frozen=True)
class CastAttrs:
    dtype: DataType

    def output_shape(self, input: TensorShape) -> TensorShape:
        return TensorShape(input.dims, self.dtype)

    def parallel_output_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        return ParallelTensorShape(input.dims, self.dtype)


@dataclass(frozen=True)
class BroadcastAttrs:
    """Broadcast input to target_dims (numpy semantics, trailing-aligned)."""

    target_dims: Tuple[int, ...]

    def output_shape(self, input: TensorShape) -> TensorShape:
        in_dims = input.dims
        t = self.target_dims
        assert len(t) >= len(in_dims)
        for i, d in enumerate(reversed(in_dims)):
            td = t[len(t) - 1 - i]
            assert d == td or d == 1, f"cannot broadcast {in_dims} to {t}"
        return TensorShape(t, input.dtype)

    def parallel_output_shape(self, input: ParallelTensorShape) -> ParallelTensorShape:
        from flexflow_tpu.op_attrs.parallel_tensor_shape import (
            lift_to_parallel_with_degrees,
            get_reduced_shape,
        )

        out = self.output_shape(get_reduced_shape(input))
        n_new = len(self.target_dims) - input.num_dims
        in_degrees = input.shard_degrees()
        for i, (deg, size) in enumerate(zip(in_degrees, input.sizes())):
            if size == 1:
                assert deg == 1
        out_degrees = (1,) * n_new + tuple(
            deg if size != 1 else 1
            for deg, size in zip(in_degrees, input.sizes())
        )
        return lift_to_parallel_with_degrees(
            out, input.sum_degree, input.discard_copy_degree, out_degrees
        )
