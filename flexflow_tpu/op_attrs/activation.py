"""Activation / regularizer attrs (reference: lib/op-attrs activation.enum.toml,
regularizer_attrs)."""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Optional, Union


class Activation(enum.Enum):
    RELU = "relu"
    SIGMOID = "sigmoid"
    TANH = "tanh"
    GELU = "gelu"
    SILU = "silu"
    RELU2 = "relu2"  # relu(x)^2, the squared ReLU of the Primer / Nemotron line

    def apply(self, x):
        import jax

        return {
            Activation.RELU: jax.nn.relu,
            Activation.SIGMOID: jax.nn.sigmoid,
            Activation.TANH: jax.numpy.tanh,
            Activation.GELU: jax.nn.gelu,
            Activation.SILU: jax.nn.silu,
            Activation.RELU2: lambda v: jax.numpy.square(jax.nn.relu(v)),
        }[self](x)


@dataclass(frozen=True)
class L1Regularizer:
    coeff: float


@dataclass(frozen=True)
class L2Regularizer:
    coeff: float


Regularizer = Union[L1Regularizer, L2Regularizer]
