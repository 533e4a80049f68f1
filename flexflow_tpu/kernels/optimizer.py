"""Optimizer update kernels (reference: lib/kernels/include/kernels/
optimizer_kernels.h — sgd/adam_{ps,nccl}_update_task_gpu,
src/cuda/optimizer_kernel.cu).

The reference splits updates into PS (sum replica grads on shard 0) vs NCCL
(allreduce in place, update everywhere). On TPU, gradient sync is a
collective baked into the jitted train step by the distributed lowering, so
the update kernels here are the pure per-parameter math, on whatever part of
a parameter the caller hands them:

- the single-device and data-parallel backends hand every device the whole
  leaf after the all-reduce: the NCCL variant's post-allreduce behaviour;
- the searched executor (`parallel/executor.py`) hands each device the
  shard of the leaf that it stores: the float32 master and the optimizer
  slots of a weight live cut over every mesh axis the plan replicates the
  weight on, `apply_optimizer`'s `grads_at` has the gradient reduced into
  that shard (reduce-scatter), the update runs on 1/replicas of the leaf,
  and the next step all-gathers the copy it computes with (in the compute
  dtype). That is the variant XLA's TPU partitioner was built for (Xu et
  al., arXiv:2004.13336), and the third the reference does not have.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs, OptimizerAttrs, SGDOptimizerAttrs


def sgd_update(attrs: SGDOptimizerAttrs, w, g, v):
    """Reference optimizer_kernel.cu sgd_update: weight decay, momentum,
    nesterov. Returns (new_w, new_v)."""
    g = g + attrs.weight_decay * w
    if attrs.momentum > 0.0:
        v = attrs.momentum * v + g
        step = g + attrs.momentum * v if attrs.nesterov else v
    else:
        step = g
    return w - attrs.lr * step, v


def adam_update(attrs: AdamOptimizerAttrs, w, g, m, v, step_count):
    """Bias-corrected Adam (the reference tracks alpha_t/beta_t decays via
    next(); here correction is derived from the step count)."""
    g = g + attrs.weight_decay * w
    m = attrs.beta1 * m + (1.0 - attrs.beta1) * g
    v = attrs.beta2 * v + (1.0 - attrs.beta2) * jnp.square(g)
    t = step_count.astype(jnp.float32)
    alpha_t = (
        attrs.alpha
        * jnp.sqrt(1.0 - jnp.power(attrs.beta2, t))
        / (1.0 - jnp.power(attrs.beta1, t))
    )
    w = w - alpha_t * m / (jnp.sqrt(v) + attrs.epsilon)
    return w, m, v


def make_optimizer_state(attrs: OptimizerAttrs, params: Dict):
    """Allocate optimizer slots per parameter (reference: compile()'s
    sgd_v / adam_m+adam_v allocation, SURVEY.md §3.1)."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    if isinstance(attrs, SGDOptimizerAttrs):
        if attrs.momentum > 0.0:
            return {"v": zeros, "step": jnp.zeros((), jnp.int32)}
        return {"step": jnp.zeros((), jnp.int32)}
    if isinstance(attrs, AdamOptimizerAttrs):
        return {
            "m": zeros,
            "v": jax.tree_util.tree_map(jnp.zeros_like, params),
            "step": jnp.zeros((), jnp.int32),
        }
    raise TypeError(f"unknown optimizer {attrs!r}")


def barrier_grads(grads):
    """Keep XLA from fusing the optimizer's elementwise math into the
    weight-gradient matmuls: fused, the headline bench's wgrad dots run at
    56-67% of peak; separated they run pure and the update becomes a cheap
    HBM pass."""
    return jax.lax.optimization_barrier(grads)


def apply_optimizer(
    attrs: OptimizerAttrs, params: Dict, grads: Dict, state: Dict,
    grads_at=None,
):
    """Apply one update across a parameter pytree. Returns (params, state).

    Applies barrier_grads so every training backend gets the anti-fusion
    barrier (jitted callers; a no-op cost for eager execute_update).
    `grads_at(grads)` places the gradients where the update is computed:
    the searched executor constrains each to the sharding its parameter
    and slots are stored at."""
    grads = barrier_grads(grads)
    if grads_at is not None:
        grads = grads_at(grads)
    step = state["step"] + 1
    if isinstance(attrs, SGDOptimizerAttrs):
        if attrs.momentum > 0.0:
            flat_p, treedef = jax.tree_util.tree_flatten(params)
            flat_g = treedef.flatten_up_to(grads)
            flat_v = treedef.flatten_up_to(state["v"])
            new_p, new_v = [], []
            for w, g, v in zip(flat_p, flat_g, flat_v):
                nw, nv = sgd_update(attrs, w, g, v)
                new_p.append(nw)
                new_v.append(nv)
            return (
                jax.tree_util.tree_unflatten(treedef, new_p),
                {"v": jax.tree_util.tree_unflatten(treedef, new_v), "step": step},
            )
        new_params = jax.tree_util.tree_map(
            lambda w, g: sgd_update(attrs, w, g, None)[0], params, grads
        )
        return new_params, {"step": step}
    if isinstance(attrs, AdamOptimizerAttrs):
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        new_p, new_m, new_v = [], [], []
        for w, g, m, v in zip(flat_p, flat_g, flat_m, flat_v):
            nw, nm, nv = adam_update(attrs, w, g, m, v, step)
            new_p.append(nw)
            new_m.append(nm)
            new_v.append(nv)
        return (
            jax.tree_util.tree_unflatten(treedef, new_p),
            {
                "m": jax.tree_util.tree_unflatten(treedef, new_m),
                "v": jax.tree_util.tree_unflatten(treedef, new_v),
                "step": step,
            },
        )
    raise TypeError(f"unknown optimizer {attrs!r}")
