"""Pure data-parallel execution: batch-dim sharding over a 1D device mesh.

The TPU-native equivalent of the reference's default/fallback strategy
(`get_basic_data_parallel_machine_view`, lib/runtime/src/model.h:38-40, and
the `--only-data-parallel` flag, config.h:87): every weight replicated, every
activation sharded on dim 0, gradient all-reduce inserted by GSPMD where the
reference used NCCL allreduce in the optimizer tasks.

Unlike the searched path (parallel/executor.py, which lowers an explicit PCG),
this wraps the plain ComputationGraph step in `jax.jit` with NamedShardings —
XLA's SPMD partitioner propagates the batch sharding through the whole
program, which is exactly DP for any graph.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu.kernels import context
from flexflow_tpu.local_execution.training_backing import ModelTrainingInstance
from flexflow_tpu.op_attrs.ops.loss_functions import LossAttrs
from flexflow_tpu.pcg.computation_graph import ComputationGraph
from flexflow_tpu.pcg.optimizer import OptimizerAttrs
from flexflow_tpu.utils.graph import DataflowOutput


class DataParallelTrainingInstance(ModelTrainingInstance):
    """ModelTrainingInstance over an N-device 1D mesh, batch dim sharded."""

    def __init__(
        self,
        cg: ComputationGraph,
        logit_tensor: DataflowOutput,
        loss_attrs: LossAttrs,
        optimizer_attrs: OptimizerAttrs,
        metrics: FrozenSet[str] = frozenset(),
        devices=None,
        compute_dtype=None,
        aux_loss_tensors=(),
        collect_step_stats: bool = False,
        guard_nonfinite_updates: bool = False,
    ) -> None:
        super().__init__(
            cg, logit_tensor, loss_attrs, optimizer_attrs,
            metrics=metrics, compute_dtype=compute_dtype,
            aux_loss_tensors=aux_loss_tensors,
            collect_step_stats=collect_step_stats,
            guard_nonfinite_updates=guard_nonfinite_updates,
        )
        import numpy as np

        devices = list(devices if devices is not None else jax.devices())
        self.mesh = Mesh(np.array(devices), ("data",))
        self.replicated = NamedSharding(self.mesh, P())
        self.batch_sharded = NamedSharding(self.mesh, P("data"))

    # -- dataloader hooks --------------------------------------------------

    def input_sharding(self, name: str):
        return self.batch_sharded

    def label_sharding(self):
        return self.batch_sharded

    # -- overrides ---------------------------------------------------------

    def initialize(self, seed: int = 0):
        from flexflow_tpu.runtime.distributed import device_put_global

        params, opt_state = super().initialize(seed)

        def place(x):
            if isinstance(x, jnp.ndarray):
                return device_put_global(x, self.replicated)
            return x

        params = jax.tree_util.tree_map(place, params)
        opt_state = jax.tree_util.tree_map(place, opt_state)
        return params, opt_state

    def compiled_step(self):
        if self._jit_step is None:
            def step_with_mesh_ctx(*args):
                # batch dim rides the "data" axis; heads unsharded in pure DP.
                # The context routes attention through shard_map'd flash
                # (a bare pallas_call cannot be SPMD-partitioned).
                with context.flash_mesh(
                    self.mesh, "data", None, context.interpret_default()
                ):
                    return self._step(*args)

            rep, bat = self.replicated, self.batch_sharded
            self._jit_step = jax.jit(
                step_with_mesh_ctx,
                donate_argnums=(0, 1),
                in_shardings=(
                    rep,  # params (pytree: sharding broadcast over leaves)
                    rep,  # opt_state
                    bat,  # batch inputs
                    bat,  # label
                    rep,  # rng
                ),
                # outputs pinned replicated too: left unconstrained, XLA may
                # hand back a SHARDED weight (seen after a mid-fit recompile
                # to a new batch size), which the next donated call rejects
                # against the replicated in_shardings
                out_shardings=rep,
            )
        return self._jit_step
