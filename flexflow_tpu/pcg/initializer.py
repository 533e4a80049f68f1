"""Initializer attrs + JAX implementations.

Reference: lib/pcg/include/pcg/initializers/ (GlorotUniform/GlorotNormal/Zero/
Uniform/Norm/TruncatedNormal/Constant) and the CUDA initializer kernels
(lib/kernels/src/cuda/initializer_kernels.cu). On TPU, initialization is pure
jax.random — deterministic per (seed, shape) and shardable by construction.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Union

import numpy as np


@dataclass(frozen=True)
class GlorotUniformAttrs:
    seed: int = 0


@dataclass(frozen=True)
class GlorotNormalAttrs:
    seed: int = 0


@dataclass(frozen=True)
class ZeroInitializerAttrs:
    pass


@dataclass(frozen=True)
class UniformInitializerAttrs:
    seed: int = 0
    min_val: float = -0.05
    max_val: float = 0.05


@dataclass(frozen=True)
class NormInitializerAttrs:
    seed: int = 0
    mean: float = 0.0
    stddev: float = 0.05


@dataclass(frozen=True)
class TruncatedNormalInitializerAttrs:
    """reference: truncated_normal_initializer_attrs (seed/mean/stddev plus
    absolute min/max cutoffs). Cutoffs of None mean ±2σ."""

    seed: int = 0
    mean: float = 0.0
    stddev: float = 0.05
    min_cutoff: Optional[float] = None
    max_cutoff: Optional[float] = None


@dataclass(frozen=True)
class ConstantInitializerAttrs:
    value: float = 0.0


@dataclass(frozen=True)
class LogOfUniformInitializerAttrs:
    """log(u), u uniform in [min_val, max_val]: a rate stored as its log
    (the state-space op's `A_log`)."""

    min_val: float = 1.0
    max_val: float = 16.0


@dataclass(frozen=True)
class InverseSoftplusLogUniformInitializerAttrs:
    """softplus^-1(t), t log-uniform in [min_val, max_val] and at least
    `floor`: a step size stored so that softplus gives it back (the
    state-space op's `dt_bias`)."""

    min_val: float = 1e-3
    max_val: float = 1e-1
    floor: float = 1e-4


@dataclass(frozen=True)
class StackedInitializerAttrs:
    """Initializer of a branch-stacked weight [k, *inner] (see
    compiler/branch_stacking.py): slice i is initialized with `inner` under
    a key folded with i, so each branch keeps the per-branch statistics
    (glorot fans computed on the INNER shape, not the stacked one)."""

    inner: "InitializerAttrs"
    count: int


InitializerAttrs = Union[
    GlorotUniformAttrs,
    GlorotNormalAttrs,
    ZeroInitializerAttrs,
    UniformInitializerAttrs,
    NormInitializerAttrs,
    TruncatedNormalInitializerAttrs,
    ConstantInitializerAttrs,
    LogOfUniformInitializerAttrs,
    InverseSoftplusLogUniformInitializerAttrs,
    StackedInitializerAttrs,
]


def _fan_in_out(shape) -> tuple:
    # Convention matching jax.nn.initializers / the reference's glorot:
    # last two dims are (fan_in, fan_out) for matrices; conv [out,in,kh,kw]
    # uses receptive-field scaling.
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


def initialize(attrs: InitializerAttrs, key, shape, dtype):
    """Materialize a tensor for the given initializer attrs.

    key: jax PRNG key (already folded with the initializer's seed by caller
    or derived here from attrs.seed when used standalone).
    """
    import jax
    import jax.numpy as jnp

    if isinstance(attrs, StackedInitializerAttrs):
        assert shape[0] == attrs.count, (shape, attrs.count)
        slices = [
            initialize(attrs.inner, jax.random.fold_in(key, i), shape[1:], dtype)
            for i in range(attrs.count)
        ]
        return jnp.stack(slices, axis=0)
    if isinstance(attrs, ZeroInitializerAttrs):
        return jnp.zeros(shape, dtype)
    if isinstance(attrs, ConstantInitializerAttrs):
        return jnp.full(shape, attrs.value, dtype)
    if isinstance(attrs, GlorotUniformAttrs):
        fan_in, fan_out = _fan_in_out(shape)
        limit = float(np.sqrt(6.0 / (fan_in + fan_out)))
        return jax.random.uniform(key, shape, dtype, -limit, limit)
    if isinstance(attrs, GlorotNormalAttrs):
        fan_in, fan_out = _fan_in_out(shape)
        std = float(np.sqrt(2.0 / (fan_in + fan_out)))
        return std * jax.random.normal(key, shape, dtype)
    if isinstance(attrs, UniformInitializerAttrs):
        return jax.random.uniform(key, shape, dtype, attrs.min_val, attrs.max_val)
    if isinstance(attrs, LogOfUniformInitializerAttrs):
        u = jax.random.uniform(
            key, shape, jnp.float32, attrs.min_val, attrs.max_val
        )
        return jnp.log(u).astype(dtype)
    if isinstance(attrs, InverseSoftplusLogUniformInitializerAttrs):
        lo, hi = np.log(attrs.min_val), np.log(attrs.max_val)
        t = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        t = jnp.maximum(t, attrs.floor)
        return (t + jnp.log(-jnp.expm1(-t))).astype(dtype)
    if isinstance(attrs, NormInitializerAttrs):
        return attrs.mean + attrs.stddev * jax.random.normal(key, shape, dtype)
    if isinstance(attrs, TruncatedNormalInitializerAttrs):
        # cutoffs are absolute values; convert to standard-normal units
        if attrs.stddev == 0.0:
            return jnp.full(shape, attrs.mean, dtype)
        lo = (
            (attrs.min_cutoff - attrs.mean) / attrs.stddev
            if attrs.min_cutoff is not None
            else -2.0
        )
        hi = (
            (attrs.max_cutoff - attrs.mean) / attrs.stddev
            if attrs.max_cutoff is not None
            else 2.0
        )
        return attrs.mean + attrs.stddev * jax.random.truncated_normal(
            key, lo, hi, shape, dtype
        )
    raise TypeError(f"unknown initializer {attrs!r}")
