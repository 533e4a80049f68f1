"""The FFModel user API: build, compile, fit.

Reference: python/flexflow/core/flexflow_cffi.py — `FFModel` (:883) with ~45
layer methods, `compile` (:2018), `fit` (:2058), `eval`, the stepped
`forward/backward/update/zero_gradients` loop, `Tensor` (:572) /
`Parameter` (:847) numpy round-trips — reimplemented over the TPU stack:

- single device   -> ModelTrainingInstance (one jitted donated step)
- multi device    -> DataParallelTrainingInstance (GSPMD batch sharding), or,
  when `config.search_budget > 0` and `--only-data-parallel` is not set, the
  Unity search (compiler.graph_optimize) + DistributedTrainingInstance over
  the searched PCG + machine mapping.
"""

from __future__ import annotations

import contextlib
import enum
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.core.dataloader import BatchIterator
from flexflow_tpu.core.optimizers import optimizer_attrs_of
from flexflow_tpu.kernels.metrics import PerfMetrics
from flexflow_tpu.local_execution.config import FFConfig
from flexflow_tpu.local_execution.training_backing import (
    LocalTrainingBacking,
    ModelTrainingInstance,
    param_key,
)
from flexflow_tpu.observability.trace import record_span
from flexflow_tpu.op_attrs.core import OpAttrs
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs
from flexflow_tpu.op_attrs.ops.loss_functions import (
    LossFunction,
    loss_attrs_for,
)
from flexflow_tpu.pcg.computation_graph_builder import ComputationGraphBuilder
from flexflow_tpu.utils.graph import DataflowOutput, Node

# Loss/metric name aliases matching the legacy string API
# (flexflow_cffi.py compile(loss_type="sparse_categorical_crossentropy",
# metrics=["accuracy", ...])).
LossType = LossFunction


class CompMode(enum.Enum):
    TRAINING = 0
    INFERENCE = 1


class Tensor:
    """Handle to a dataflow tensor (reference flexflow_cffi.py:572)."""

    def __init__(self, ffmodel: "FFModel", handle: DataflowOutput) -> None:
        self.ffmodel = ffmodel
        self.handle = handle

    @property
    def dims(self) -> Tuple[int, ...]:
        return tuple(self.ffmodel._builder.graph.tensor_shape(self.handle).dims)

    @property
    def dtype(self) -> DataType:
        return self.ffmodel._builder.graph.tensor_shape(self.handle).dtype

    def get_tensor(self, ffmodel: Optional["FFModel"] = None) -> np.ndarray:
        """Current value: weights read from params; activations from the last
        stepped forward (reference inline-mapped regions)."""
        m = ffmodel or self.ffmodel
        return m._read_tensor(self.handle)

    def set_tensor(
        self, ffmodel: Optional["FFModel"], value: np.ndarray
    ) -> None:
        m = ffmodel or self.ffmodel
        m._write_tensor(self.handle, np.asarray(value))

    def inline_map(self, ffmodel=None, ffconfig=None):  # legacy API no-op
        return self

    def inline_unmap(self, ffmodel=None, ffconfig=None):
        return self


class Parameter(Tensor):
    """A weight tensor (reference flexflow_cffi.py:847)."""

    def get_weights(self, ffmodel: Optional["FFModel"] = None) -> np.ndarray:
        return self.get_tensor(ffmodel)

    def set_weights(
        self, ffmodel: Optional["FFModel"], value: np.ndarray
    ) -> None:
        self.set_tensor(ffmodel, value)


class FFModel:
    """Computation-graph builder + trainer (reference FFModel, model.h:41)."""

    def __init__(self, config: Optional[FFConfig] = None) -> None:
        # multi-host entry (reference cpp_driver main, one process per rank):
        # no-op unless FLEXFLOW_TPU_COORDINATOR is configured
        from flexflow_tpu.runtime.distributed import initialize

        initialize()
        self.config = config or FFConfig()
        # persistent XLA compilation cache: installed before any jit so
        # every program this model compiles (step, eval forward) is
        # reusable by the next process
        from flexflow_tpu.local_execution.config import (
            configure_compilation_cache,
        )

        configure_compilation_cache()
        self._builder = ComputationGraphBuilder()
        self._num_inputs = 0
        self._last_tensor: Optional[Tensor] = None
        # set by compile():
        self.instance = None
        self.params = None
        self.opt_state = None
        self.loss_attrs = None
        self.optimizer_attrs = None
        self.metrics: frozenset = frozenset()
        self.comp_mode = CompMode.TRAINING
        self._backing: Optional[LocalTrainingBacking] = None
        self._label_dtype = jnp.int32
        self._step_count = 0
        self._aux_loss_tensors: List[DataflowOutput] = []
        # set by _compile_searched on the searching host: how the winning
        # Unity plan was found. NOT flat floats: holds nested dicts
        # (seed_runtimes, parallel_degrees, phase_ms, telemetry,
        # calibration, plan_audit), strings (cost_model, search_algorithm)
        # and bools — see tests/test_observability.py::test_provenance_schema
        # for the pinned key set.
        self.search_provenance: Optional[Dict[str, object]] = None
        # run-health monitor installed by fit() when config.health_policy
        # is active (observability/health.py)
        self.health_monitor = None

    @classmethod
    def from_computation_graph(
        cls,
        cg,
        logit_tensor: Union["Tensor", DataflowOutput],
        config: Optional[FFConfig] = None,
        aux_loss_tensors=(),
    ) -> "FFModel":
        """Adopt a CG built elsewhere (e.g. the flexflow_tpu.models zoo) so it
        can be compiled/fit through this API.

        `cg` may be either a bare graph or a ComputationGraphBuilder; in the
        latter case any aux-loss outputs the builder recorded (e.g. the MoE
        load-balance loss) are adopted too. Explicit `aux_loss_tensors` are
        appended on top."""
        m = cls(config)
        if isinstance(cg, ComputationGraphBuilder):
            m._builder.graph = cg.graph
            m._aux_loss_tensors.extend(cg.aux_loss_tensors)
        else:
            m._builder.graph = cg
        for t in aux_loss_tensors:
            m._aux_loss_tensors.append(
                t.handle if isinstance(t, Tensor) else t
            )
        m._last_tensor = m._wrap(
            logit_tensor.handle
            if isinstance(logit_tensor, Tensor)
            else logit_tensor
        )
        return m

    # ------------------------------------------------------------------
    # graph access
    # ------------------------------------------------------------------

    @property
    def cg(self):
        return self._builder.graph

    def _wrap(self, h: DataflowOutput) -> Tensor:
        t = Tensor(self, h)
        self._last_tensor = t
        return t

    def _unwrap(self, t: Union[Tensor, DataflowOutput]) -> DataflowOutput:
        return t.handle if isinstance(t, Tensor) else t

    # ------------------------------------------------------------------
    # layer API (the ~45 methods of flexflow_cffi.FFModel)
    # ------------------------------------------------------------------

    def create_tensor(
        self,
        dims: Sequence[int],
        dtype: DataType = DataType.FLOAT,
        create_grad: bool = True,
        name: Optional[str] = None,
    ) -> Tensor:
        # Inputs always get a stable name: name-based batch binding must
        # survive the Unity rewrite (searched PCG node ids differ from CG ids,
        # so positional param_key fallbacks would dangle).
        if name is None:
            name = f"input{self._num_inputs}"
        self._num_inputs += 1
        return self._wrap(self._builder.create_input(dims, dtype, name=name))

    def create_weight(
        self, dims, dtype: DataType = DataType.FLOAT, initializer=None, name=None
    ) -> Parameter:
        h = self._builder.create_weight(dims, dtype, initializer, name=name)
        t = Parameter(self, h)
        return t

    def dense(
        self, input, out_dim, activation=None, use_bias=True,
        kernel_initializer=None, bias_initializer=None, name=None,
    ) -> Tensor:
        return self._wrap(self._builder.dense(
            self._unwrap(input), out_dim, activation=activation,
            use_bias=use_bias, kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer, name=name,
        ))

    def embedding(
        self, input, num_entries, out_dim, aggr=None,
        kernel_initializer=None, name=None,
    ) -> Tensor:
        from flexflow_tpu.op_attrs.ops import AggregateSpec

        return self._wrap(self._builder.embedding(
            self._unwrap(input), num_entries, out_dim,
            aggr=aggr or AggregateSpec.NONE,
            kernel_initializer=kernel_initializer, name=name,
        ))

    def multihead_attention(
        self, query, key, value, embed_dim, num_heads,
        kdim=0, vdim=0, dropout=0.0, bias=False,
        add_bias_kv=False, add_zero_attn=False, initializer=None, name=None,
        causal=False, rope_theta=None, qk_norm_eps=None, window=None,
        rope_scaling=None, softmax_scale=None,
    ) -> Tensor:
        return self._wrap(self._builder.multihead_attention(
            self._unwrap(query), self._unwrap(key), self._unwrap(value),
            embed_dim, num_heads, kdim=kdim, vdim=vdim, dropout=dropout,
            bias=bias, add_bias_kv=add_bias_kv, add_zero_attn=add_zero_attn,
            initializer=initializer, name=name, causal=causal,
            rope_theta=rope_theta, qk_norm_eps=qk_norm_eps, window=window,
            rope_scaling=rope_scaling, softmax_scale=softmax_scale,
        ))

    def conv2d(
        self, input, out_channels, kernel_h, kernel_w, stride_h, stride_w,
        padding_h, padding_w, activation=None, groups=1, use_bias=True,
        kernel_initializer=None, bias_initializer=None, name=None,
    ) -> Tensor:
        return self._wrap(self._builder.conv2d(
            self._unwrap(input), out_channels, (kernel_h, kernel_w),
            (stride_h, stride_w), (padding_h, padding_w), groups=groups,
            activation=activation, use_bias=use_bias,
            kernel_initializer=kernel_initializer,
            bias_initializer=bias_initializer, name=name,
        ))

    def pool2d(
        self, input, kernel_h, kernel_w, stride_h, stride_w,
        padding_h, padding_w, pool_type=None, activation=None, name=None,
    ) -> Tensor:
        from flexflow_tpu.op_attrs.ops import PoolOp

        if isinstance(pool_type, str):
            pool_type = PoolOp(pool_type.lower())
        return self._wrap(self._builder.pool2d(
            self._unwrap(input), (kernel_h, kernel_w), (stride_h, stride_w),
            (padding_h, padding_w), pool_type=pool_type or PoolOp.MAX,
            activation=activation, name=name,
        ))

    def batch_norm(self, input, relu=True, name=None) -> Tensor:
        return self._wrap(
            self._builder.batch_norm(self._unwrap(input), relu=relu, name=name)
        )

    def layer_norm(
        self, input, axes=(-1,), elementwise_affine=True, eps=1e-5, name=None
    ) -> Tensor:
        return self._wrap(self._builder.layer_norm(
            self._unwrap(input), axes=list(axes),
            elementwise_affine=elementwise_affine, eps=eps, name=name,
        ))

    def rms_norm(self, input, eps=1e-5, name=None) -> Tensor:
        return self._wrap(
            self._builder.rms_norm(self._unwrap(input), eps=eps, name=name)
        )

    def flat(self, input, name=None) -> Tensor:
        return self._wrap(self._builder.flat(self._unwrap(input), name=name))

    def softmax(self, input, axis=-1, name=None) -> Tensor:
        return self._wrap(
            self._builder.softmax(self._unwrap(input), dim=axis, name=name)
        )

    def dropout(self, input, rate, seed=0, name=None) -> Tensor:
        return self._wrap(
            self._builder.dropout(self._unwrap(input), rate, seed=seed, name=name)
        )

    def concat(self, tensors, axis, name=None) -> Tensor:
        return self._wrap(self._builder.concat(
            [self._unwrap(t) for t in tensors], axis, name=name
        ))

    def split(self, input, sizes, axis, name=None) -> List[Tensor]:
        outs = self._builder.split(self._unwrap(input), sizes, axis, name=name)
        return [self._wrap(o) for o in outs]

    def reshape(self, input, shape, name=None) -> Tensor:
        return self._wrap(
            self._builder.reshape(self._unwrap(input), shape, name=name)
        )

    def transpose(self, input, perm, name=None) -> Tensor:
        return self._wrap(
            self._builder.transpose(self._unwrap(input), perm, name=name)
        )

    def reverse(self, input, axis, name=None) -> Tensor:
        return self._wrap(
            self._builder.reverse(self._unwrap(input), axis, name=name)
        )

    def gather(self, input, index, dim, name=None) -> Tensor:
        return self._wrap(self._builder.gather(
            self._unwrap(input), self._unwrap(index), dim, name=name
        ))

    def top_k(self, input, k, sorted=True, name=None) -> Tuple[Tensor, Tensor]:
        v, i = self._builder.top_k(self._unwrap(input), k, sorted=sorted, name=name)
        return self._wrap(v), self._wrap(i)

    def cast(self, input, dtype, name=None) -> Tensor:
        return self._wrap(self._builder.cast(self._unwrap(input), dtype, name=name))

    def broadcast(self, input, target_dims, name=None) -> Tensor:
        return self._wrap(
            self._builder.broadcast(self._unwrap(input), target_dims, name=name)
        )

    def batch_matmul(self, a, b, name=None) -> Tensor:
        return self._wrap(
            self._builder.batch_matmul(self._unwrap(a), self._unwrap(b), name=name)
        )

    def reduce_sum(self, input, axes, keepdims=False, name=None) -> Tensor:
        return self._wrap(self._builder.reduce_sum(
            self._unwrap(input), axes, keepdims=keepdims, name=name
        ))

    def mean(self, input, dims, keepdims=False, name=None) -> Tensor:
        return self._wrap(self._builder.reduce_mean(
            self._unwrap(input), dims, keepdims=keepdims, name=name
        ))

    # elementwise binary
    def add(self, x, y, name=None):
        return self._wrap(self._builder.add(self._unwrap(x), self._unwrap(y), name=name))

    def subtract(self, x, y, name=None):
        return self._wrap(self._builder.subtract(self._unwrap(x), self._unwrap(y), name=name))

    def multiply(self, x, y, name=None):
        return self._wrap(self._builder.multiply(self._unwrap(x), self._unwrap(y), name=name))

    def divide(self, x, y, name=None):
        return self._wrap(self._builder.divide(self._unwrap(x), self._unwrap(y), name=name))

    def max(self, x, y, name=None):
        return self._wrap(self._builder.max(self._unwrap(x), self._unwrap(y), name=name))

    def min(self, x, y, name=None):
        return self._wrap(self._builder.min(self._unwrap(x), self._unwrap(y), name=name))

    # elementwise unary
    def exp(self, x, name=None):
        return self._wrap(self._builder.exp(self._unwrap(x), name=name))

    def log(self, x, name=None):
        return self._wrap(self._builder.log(self._unwrap(x), name=name))

    def sin(self, x, name=None):
        return self._wrap(self._builder.sin(self._unwrap(x), name=name))

    def cos(self, x, name=None):
        return self._wrap(self._builder.cos(self._unwrap(x), name=name))

    def relu(self, x, name=None):
        return self._wrap(self._builder.relu(self._unwrap(x), name=name))

    def sigmoid(self, x, name=None):
        return self._wrap(self._builder.sigmoid(self._unwrap(x), name=name))

    def tanh(self, x, name=None):
        return self._wrap(self._builder.tanh(self._unwrap(x), name=name))

    def gelu(self, x, name=None):
        return self._wrap(self._builder.gelu(self._unwrap(x), name=name))

    def silu(self, x, name=None):
        return self._wrap(self._builder.silu(self._unwrap(x), name=name))

    def elu(self, x, name=None):
        return self._wrap(self._builder.elu(self._unwrap(x), name=name))

    def rsqrt(self, x, name=None):
        return self._wrap(self._builder.rsqrt(self._unwrap(x), name=name))

    def identity(self, x, name=None):
        return self._wrap(self._builder.identity(self._unwrap(x), name=name))

    def scalar_multiply(self, x, scalar, name=None):
        return self._wrap(self._builder.scalar_multiply(self._unwrap(x), scalar, name=name))

    def scalar_add(self, x, scalar, name=None):
        return self._wrap(self._builder.scalar_add(self._unwrap(x), scalar, name=name))

    def scalar_sub(self, x, scalar, name=None):
        return self._wrap(self._builder.scalar_sub(self._unwrap(x), scalar, name=name))

    def scalar_true_divide(self, x, scalar, name=None):
        return self._wrap(self._builder.scalar_truediv(self._unwrap(x), scalar, name=name))

    def pow(self, x, exponent, name=None):
        return self._wrap(self._builder.pow(self._unwrap(x), exponent, name=name))

    # -- mixture of experts --------------------------------------------

    def group_by(self, data, assign, n_experts, alpha=1.0, name=None) -> List[Tensor]:
        outs = self._builder.group_by(
            self._unwrap(data), self._unwrap(assign), n_experts, alpha, name=name
        )
        return [self._wrap(o) for o in outs]

    def aggregate(self, gate_preds, gate_assign, exp_preds, name=None) -> Tensor:
        out = self._builder.aggregate(
            self._unwrap(gate_preds),
            self._unwrap(gate_assign),
            [self._unwrap(t) for t in exp_preds],
            name=name,
        )
        return self._wrap(out)

    def moe(
        self,
        input,
        num_exp: int,
        num_select: int,
        hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.0,
        name=None,
    ) -> Tensor:
        """Reference FFModel::moe (examples/cpp/mixture_of_experts/moe.cc:
        ff.moe(input, num_exp, num_select, hidden_size, alpha, lambda))."""
        outs = self._builder.experts(
            self._unwrap(input),
            num_exp,
            num_select,
            hidden_size,
            capacity_factor=alpha,
            lambda_bal=lambda_bal,
            name=name,
        )
        if len(outs) > 1:  # load-balance aux loss joins the training loss
            self._aux_loss_tensors.append(outs[1])
        return self._wrap(outs[0])

    def experts(
        self, input, num_experts: int, num_select: int, hidden_size: int,
        name=None, **attrs,
    ) -> Tensor:
        """The fused MoE FFN with every `ExpertsAttrs` setting
        (`ComputationGraphBuilder.experts`): `gated`, `capacity_factor=None`
        for dropless, `renormalize`, `lambda_bal`, `lambda_z`, ... The
        auxiliary scalar, where there is one, joins the training loss."""
        outs = self._builder.experts(
            self._unwrap(input), num_experts, num_select, hidden_size,
            name=name, **attrs,
        )
        if len(outs) > 1:
            self._aux_loss_tensors.append(outs[1])
        return self._wrap(outs[0])

    # ------------------------------------------------------------------
    # layer/parameter lookup
    # ------------------------------------------------------------------

    def get_layers(self) -> Dict[int, str]:
        cg = self.cg
        return {
            n.idx: (cg.layer_attrs(n).name or f"layer{n.idx}")
            for n in cg.topological_ordering()
        }

    def _find_weight_node(self, name: str) -> Optional[Node]:
        cg = self.cg
        for n in cg.topological_ordering():
            la = cg.layer_attrs(n)
            if isinstance(la.attrs, WeightAttrs) and la.name == name:
                return n
        return None

    def get_parameter_by_name(self, name: str) -> Parameter:
        """`name` is the layer weight name (e.g. "fc1.weight0" for a dense
        layer named "fc1"; bias is ".weight1")."""
        n = self._find_weight_node(name) or self._find_weight_node(
            name + ".weight0"
        )
        if n is None:
            raise KeyError(name)
        (out,) = self.cg.outputs_of(n)
        return Parameter(self, out)

    # ------------------------------------------------------------------
    # tensor value plumbing
    # ------------------------------------------------------------------

    def _weight_node_of(self, handle: DataflowOutput) -> Optional[Node]:
        n = handle.node
        if isinstance(self.cg.op_attrs(n), WeightAttrs):
            return n
        return None

    def _read_tensor(self, handle: DataflowOutput) -> np.ndarray:
        n = self._weight_node_of(handle)
        if n is not None and self.params is not None:
            return np.asarray(self.params[param_key(n)])
        if self._backing is not None and handle in self._backing.env:
            return np.asarray(self._backing.env[handle])
        raise KeyError(
            "tensor has no materialized value; compile() and run forward first"
        )

    def _write_tensor(self, handle: DataflowOutput, value: np.ndarray) -> None:
        n = self._weight_node_of(handle)
        if n is None or self.params is None:
            raise KeyError("set_tensor only supported on weights after compile()")
        k = param_key(n)
        cur = self.params[k]
        assert tuple(cur.shape) == tuple(value.shape), (
            f"shape mismatch: {cur.shape} vs {value.shape}"
        )
        self.params[k] = jnp.asarray(value, cur.dtype)
        if self._backing is not None:
            self._backing.params[k] = self.params[k]

    # ------------------------------------------------------------------
    # compile
    # ------------------------------------------------------------------

    def compile(
        self,
        optimizer=None,
        loss_type: Union[LossFunction, str] = LossFunction.SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics: Sequence[str] = (),
        comp_mode: CompMode = CompMode.TRAINING,
        logit_tensor: Optional[Tensor] = None,
        compute_dtype=None,
    ) -> None:
        """Choose the execution backend, build the train step, init params.

        Reference: FFModel::compile (model.h:85; flexflow_cffi.py:2018) — CG
        -> PCG lift, strategy search, backing init, optimizer state alloc.

        Where its seconds go is named by host spans (observability/trace.py):
        `compile` > `compile/search`, `compile/verify`, `compile/lower_step`,
        `compile/build_instance`, `compile/init_state`.
        """
        with record_span("compile"):
            self._compile(
                optimizer, loss_type, metrics, comp_mode, logit_tensor,
                compute_dtype,
            )

    def _compile(
        self, optimizer, loss_type, metrics, comp_mode, logit_tensor,
        compute_dtype,
    ) -> None:
        if isinstance(loss_type, str):
            loss_type = LossFunction(loss_type)
        # remembered for recompile() (runtime/recompile.py); the batch
        # this program compiles for — the transition verifier's TRN003
        # leg compares it across a recompile (the graph keeps its
        # build-time batch, so config is the only witness)
        self._compiled_batch_size = int(self.config.batch_size)
        self._compile_args = dict(
            optimizer=optimizer,
            loss_type=loss_type,
            metrics=metrics,
            comp_mode=comp_mode,
            logit_tensor=logit_tensor,
            compute_dtype=compute_dtype,
        )
        self.loss_attrs = loss_attrs_for(loss_type)
        self.optimizer_attrs = optimizer_attrs_of(optimizer)
        if self.optimizer_attrs is None:
            from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs

            self.optimizer_attrs = SGDOptimizerAttrs(
                lr=self.config.learning_rate,
                weight_decay=self.config.weight_decay,
            )
        self._validate_config_flags()
        self.metrics = frozenset(metrics)
        self.comp_mode = comp_mode
        # drift re-search hook (ISSUE 18): installed by the searched-compile
        # branch; stays None for imported / forced-seed / mcmc plans, where
        # the monitor falls back to uniform re-pricing of the seed table
        self._drift_research = None
        # drift-advisory transition verifier (ISSUE 19): installed by the
        # searched-compile branch; maps a candidate seed label to the
        # static TRN verdict for swapping the live plan onto it
        self._drift_transition = None
        # exec-contract state (ISSUE 14): the lazy trace-only fingerprint
        # cache for backends the always-on pass does not cover, and the
        # latest resume-time DET002 check result
        self._exec_fp_record = None
        self.exec_resume_check = None
        logit = self._unwrap(logit_tensor or self._last_tensor)
        self._label_dtype = (
            jnp.int32
            if loss_type in (
                LossFunction.SPARSE_CATEGORICAL_CROSSENTROPY,
                LossFunction.LOSS_NODES,
            )
            else jnp.float32
        )

        ndev = len(jax.devices())
        if self.config.max_devices > 0:
            # degraded-grid cap (runtime/recompile.recover_from_grid_change):
            # plan for the surviving sub-grid, not the full host mesh
            ndev = min(ndev, self.config.max_devices)
        # DP shards the batch dim; use the largest device count that divides
        # the model's batch size (reference scales batch WITH devices —
        # multi_gpu_tests.sh batch = N*nodes*64 — so a non-divisible batch
        # means the user wants fewer shards, not a crash)
        batch = None
        cgraph = self.cg
        for n in cgraph.topological_ordering():
            if isinstance(cgraph.layer_attrs(n).attrs, InputAttrs):
                batch = cgraph.tensor_shape(cgraph.outputs_of(n)[0]).dims[0]
                break
        if batch is not None:
            while ndev > 1 and batch % ndev != 0:
                ndev -= 1
        cfg = self.config
        # Experts-op aux losses are recovered structurally after the Unity
        # rewrite (_find_aux_outputs); user-supplied aux tensors from
        # from_computation_graph have no identity across the CG->PCG lift +
        # substitutions, so such graphs keep the DP backend rather than
        # silently training a different objective.
        structural_aux = set(_find_aux_outputs(self.cg))
        # an Experts op with an auxiliary coefficient contributes its scalar
        # however the graph reached this model (a bare graph handed to
        # from_computation_graph carries no builder's list)
        for t in _find_aux_outputs(self.cg):
            if t not in self._aux_loss_tensors:
                self._aux_loss_tensors.append(t)
        custom_aux = [
            t for t in self._aux_loss_tensors if t not in structural_aux
        ]
        if ndev > 1 and cfg.submesh_branches:
            # disjoint sub-mesh placement of non-isomorphic branches
            # (reference FFMapper point-task placement, mapper.h:82-126):
            # each branch island on its own device group, explicit
            # transfers at the fork/join (parallel/submesh.py)
            from flexflow_tpu.parallel.submesh import (
                SubmeshBranchInstance,
                find_branch_partition,
            )

            if structural_aux or custom_aux:
                raise ValueError(
                    "submesh_branches cannot train models with auxiliary "
                    "loss tensors (the sub-mesh step computes the primary "
                    "loss only; dropping aux terms would silently change "
                    "the objective)"
                )
            part = find_branch_partition(self.cg)
            if part is None:
                raise ValueError(
                    "submesh_branches=True but the graph has no Split-fork "
                    "branch partition"
                )
            with record_span("compile/build_instance"):
                self.instance = SubmeshBranchInstance(
                    self.cg, logit, self.loss_attrs, self.optimizer_attrs,
                    devices=jax.devices()[:ndev], partition=part,
                    metrics=self.metrics,
                )
            # the machine-mapping DP's disjoint-resource pricing is legal
            # at runtime for this shape now: price the same graph with
            # resource splits enabled and record the provenance
            try:
                self.search_provenance = self._price_resource_splits(logit)
            except Exception:
                self.search_provenance = None
        elif (
            ndev > 1
            and cfg.search_budget > 0
            and not cfg.only_data_parallel
            and not custom_aux
        ):
            self.instance = self._compile_searched(logit, ndev, compute_dtype)
        elif ndev > 1:
            from flexflow_tpu.parallel.data_parallel import (
                DataParallelTrainingInstance,
            )

            collect, guard = self._step_stats_flags()
            with record_span("compile/build_instance"):
                self.instance = DataParallelTrainingInstance(
                    self.cg, logit, self.loss_attrs, self.optimizer_attrs,
                    metrics=self.metrics, compute_dtype=compute_dtype,
                    devices=jax.devices()[:ndev],
                    aux_loss_tensors=self._aux_loss_tensors,
                    collect_step_stats=collect, guard_nonfinite_updates=guard,
                )
        else:
            collect, guard = self._step_stats_flags()
            with record_span("compile/build_instance"):
                self.instance = ModelTrainingInstance(
                    self.cg, logit, self.loss_attrs, self.optimizer_attrs,
                    metrics=self.metrics, compute_dtype=compute_dtype,
                    aux_loss_tensors=self._aux_loss_tensors,
                    collect_step_stats=collect, guard_nonfinite_updates=guard,
                )
        with record_span("compile/init_state"):
            self.params, self.opt_state = self.instance.initialize(
                seed=cfg.seed
            )
        self._step_count = 0
        prov = (
            self.search_provenance
            if isinstance(self.search_provenance, dict)
            else None
        )
        if prov is not None and getattr(self.instance, "update_record", None):
            # which leaves the executor stores and updates cut over the
            # axes the plan replicates them on, and which not
            prov["update_sharding"] = dict(self.instance.update_record)
        has_mem = prov is not None and "memory" in prov
        has_comm = prov is not None and isinstance(prov.get("comm"), dict)
        can_lower = (
            hasattr(self.instance, "compiled_step")
            and hasattr(self.instance, "machine_mesh")
        )
        # the execution-contract pass (ISSUE 14) runs on EVERY searched
        # winner — not only under --plan-audit: the determinism census +
        # donation/aliasing audit (DET001/DON001/DON002) and the
        # fingerprints DET002 re-verifies on fit(resume=True)/recompile()
        # land in search_provenance["exec"]. FF_TPU_NO_EXEC_CONTRACT=1 is
        # the emergency off-switch (recorded as skipped, dead-flag rule).
        run_exec = prov is not None and can_lower
        if run_exec and os.environ.get("FF_TPU_NO_EXEC_CONTRACT") == "1":
            run_exec = False
            prov["exec"] = {"skipped": "FF_TPU_NO_EXEC_CONTRACT=1"}
        want_audit_checks = (
            cfg.plan_audit and (has_mem or has_comm) and can_lower
        )
        if run_exec or want_audit_checks:
            # ONE shared lowering/compile serves the exec-contract pass
            # AND the --plan-audit cross-checks (ISSUE 11 satellite — the
            # memory and communication checks used to imply two compiles):
            # ISSUE 10 records XLA's own per-device memory accounting
            # beside the static prediction; ISSUE 11 extracts the HLO
            # collective census and cross-checks it against the priced
            # movement edges (COMM001-COMM004), landing in
            # search_provenance["comm"] and beside the plan audit's
            # movement measurements. Each check runs whenever ITS record
            # exists (an imported strategy carries comm predictions but
            # no memory verification), and a failure lands on the record
            # it belongs to — never silently absent. The ratios are what
            # tools/memory_audit.py and tools/comm_audit.py record
            # (MEM_r11.json, COMM_r12.json).
            lowered = None
            try:
                lowered = self._lower_step_program()
            except Exception as e:  # a cross-check failure must not kill
                msg = f"lowering failed: {type(e).__name__}: {e}"[:200]
                if run_exec:
                    prov["exec"] = {"error": msg}
                if cfg.plan_audit and has_mem:
                    prov["memory"]["xla_error"] = msg
                if cfg.plan_audit and has_comm:
                    prov["comm"]["error"] = msg
            if lowered is not None and run_exec:
                try:
                    with record_span("compile/verify", check="exec"):
                        self._exec_contract_check(lowered)
                except Exception as e:
                    prov["exec"] = {
                        "error": f"{type(e).__name__}: {e}"[:200]
                    }
            if lowered is not None and cfg.plan_audit and has_mem:
                try:
                    with record_span("compile/verify", check="memory_xla"):
                        prov["memory"].update(
                            self._xla_memory_cross_check(lowered)
                        )
                except Exception as e:
                    prov["memory"]["xla_error"] = (
                        f"{type(e).__name__}: {e}"[:200]
                    )
            if lowered is not None and cfg.plan_audit and has_comm:
                try:
                    with record_span("compile/verify", check="comm_census"):
                        self._comm_cross_check(lowered)
                except Exception as e:
                    prov["comm"]["error"] = (
                        f"{type(e).__name__}: {e}"[:200]
                    )
        elif cfg.plan_audit and has_comm:
            # dead-flag rule: the comm record must say WHY no census ran
            prov["comm"]["skipped"] = (
                "no distributed step instance to lower "
                f"(backend: {type(self.instance).__name__})"
            )
        if cfg.plan_audit and not (
            isinstance(self.search_provenance, dict)
            and "plan_audit" in self.search_provenance
        ):
            # dead-flag rule (_validate_config_flags): the audit replays a
            # SEARCHED plan, so any dispatch that skipped the Unity search
            # (single/indivisible-batch device count, no budget,
            # --only-data-parallel, custom aux losses, submesh) records
            # nothing — say so instead of silently dropping the flag.
            # Checked HERE, after dispatch, because the predicate is the
            # dispatch itself.
            print(
                "[flexflow_tpu] plan_audit: this compile ran no Unity "
                "search (backend: "
                f"{type(self.instance).__name__}) — no plan audit recorded"
            )

    def _transition_plan(self):
        """The (pcg, mapping, machine_spec) triple describing the CURRENT
        compiled plan, for the static transition verifier (ISSUE 19).
        Backends that are not mapped-PCG executors (the DP and
        single-device instances) fall back to the serial PCG of the
        computation graph with no mapping — the TRN001 leaf-totality and
        TRN003 resume-contract legs still verify; only the mapped
        movement/migration report is empty."""
        inst = getattr(self, "instance", None)
        pcg = getattr(inst, "pcg", None)
        mm = getattr(inst, "machine_mesh", None)
        if pcg is None or mm is None:
            cg = getattr(self, "cg", None)
            if cg is None or getattr(self, "instance", None) is None:
                return None
            from flexflow_tpu.pcg.parallel_computation_graph import (
                pcg_from_computation_graph,
            )

            try:
                return pcg_from_computation_graph(cg), None, None
            except Exception:
                return None
        from flexflow_tpu.pcg.machine_view import MachineSpecification

        nodes = 1
        for _, factor in getattr(mm, "node_axes", ()) or ():
            nodes *= int(factor)
        nodes = max(nodes, 1)
        spec = MachineSpecification(
            num_nodes=nodes,
            num_cpus_per_node=1,
            num_devices_per_node=max(mm.num_devices // nodes, 1),
            inter_node_bandwidth=25.0,
            intra_node_bandwidth=400.0,
        )
        return pcg, getattr(inst, "mapping", None), spec

    def recompile(self, preserve_resume: bool = False) -> None:
        """Rebuild the compiled training step after config/graph alterations
        (reference RecompileState re-mapping, recompile.h:26-41): re-runs
        compile() — backend choice, Unity search, jit — and carries over
        parameter values (and optimizer state whose shapes survive).

        Every mapped-plan recompile is statically verified as a plan
        TRANSITION (ISSUE 19, TRN001-TRN004) and the verdict recorded in
        `search_provenance["transition"]`. A transition that is physically
        unsafe to carry state across — TRN001 reshard totality or TRN002
        migration memory — raises `TransitionError` BEFORE any state moves.
        `preserve_resume=True` is the strict hot-swap contract: ANY tripped
        rule raises, including the bitwise-resume TRN003/TRN004 legs."""
        assert getattr(self, "_compile_args", None) is not None, (
            "recompile() before compile()"
        )
        old_params, old_opt = self.params, self.opt_state
        step_count = self._step_count  # training progress survives recompile
        old_plan = self._transition_plan()
        # the graph carries the BUILD-time batch; the effective batch is
        # whatever the last compile() ran under — config may ALREADY be
        # altered by the time recompile() runs (recompile_on_condition's
        # alter_func fires first), so the old batch is the one compile()
        # stamped, not config's current value
        old_b = int(
            getattr(self, "_compiled_batch_size", None)
            or self.config.batch_size
        )
        # execution-contract fingerprint across the recompile (ISSUE 14,
        # DET002): an unchanged-program recompile must rebuild the SAME
        # program; a changed program_key (batch growth, degraded grid) is
        # a legitimately different program and only recorded as such
        old_exec = None
        if isinstance(self.search_provenance, dict) and isinstance(
            self.search_provenance.get("exec"), dict
        ):
            old_exec = dict(self.search_provenance["exec"])
        self.compile(**self._compile_args)
        self._step_count = step_count
        new_prov = (
            self.search_provenance
            if isinstance(self.search_provenance, dict)
            else None
        )
        if (
            old_exec is not None
            and new_prov is not None
            and isinstance(new_prov.get("exec"), dict)
            and new_prov["exec"].get("program_fingerprint")
        ):
            from flexflow_tpu.analysis.diagnostics import format_diagnostic
            from flexflow_tpu.analysis.exec_contract import (
                compare_contract_records,
            )

            check, diag = compare_contract_records(old_exec, new_prov["exec"])
            if diag is not None:
                print("[flexflow_tpu] WARNING: " + format_diagnostic(diag))
                check["diagnostic"] = diag.to_json()
            new_prov["exec"]["recompile_check"] = check

        # static transition verification (ISSUE 19): old plan -> new plan,
        # BEFORE any state carries over. The new program was already put
        # through the always-on exec-contract pass by compile(), so the
        # TRN004 leg here reflects the DET002 recompile_check rather than
        # paying a second lowering.
        new_plan = self._transition_plan()
        if old_plan is not None and new_plan is not None:
            from flexflow_tpu.analysis.transition_analysis import (
                TransitionError,
                transition_summary_json,
                verify_transition,
            )
            from flexflow_tpu.local_execution.cost_estimator import (
                optimizer_state_slots_of,
            )

            cfg = self.config
            analysis, diags = verify_transition(
                old_plan[0], old_plan[1], new_plan[0], new_plan[1],
                machine_spec=new_plan[2],
                hbm_bytes=(
                    cfg.hbm_gb * 2**30
                    if cfg.hbm_gb and cfg.hbm_gb > 0
                    else None
                ),
                optimizer_state_slots=optimizer_state_slots_of(
                    self.optimizer_attrs
                ),
                batch_size=old_b,
                batch_size_new=int(cfg.batch_size),
            )
            record = transition_summary_json(analysis)
            if (
                new_prov is not None
                and isinstance(new_prov.get("exec"), dict)
                and isinstance(
                    new_prov["exec"].get("recompile_check"), dict
                )
            ):
                check = new_prov["exec"]["recompile_check"]
                record["program_changed"] = bool(
                    check.get("program_changed")
                ) or check.get("match") is False
            if self.search_provenance is None:
                self.search_provenance = {}
            self.search_provenance["transition"] = record
            tripped = list(analysis.rules_tripped)
            fatal = [
                r
                for r in tripped
                if preserve_resume or r in ("TRN001", "TRN002")
            ]
            if fatal:
                from flexflow_tpu.analysis.diagnostics import Severity

                raise TransitionError(
                    fatal,
                    [
                        d
                        for d in diags
                        if d.severity == Severity.ERROR
                        and d.rule_id in fatal
                    ],
                )

        from flexflow_tpu.runtime.recompile import carry

        self.params, self.opt_state = carry(
            old_params, old_opt, self.params, self.opt_state
        )

    def _find_searched_logit(self, pcg, logit: DataflowOutput) -> DataflowOutput:
        """Locate the model output in the post-substitution PCG. Rewrites
        destroy node identity, but layer names survive them (substitution.py
        keeps the matched op's name), so a named logit producer is found by
        name even in multi-output graphs; unnamed single-sink graphs fall
        back to the unique-unconsumed-output rule."""
        src_name = self.cg.layer_attrs(logit.node).name
        want_sizes = self.cg.tensor_shape(logit).dims
        if src_name is not None:
            from flexflow_tpu.op_attrs.core import is_parallel_op
            from flexflow_tpu.op_attrs.parallel_tensor_shape import (
                total_parallel_degree,
            )

            def total_degree(v):
                return total_parallel_degree(pcg.tensor_shape(v))

            def resolve(node, out_idx):
                """Follow the rule's own Combine/Reduction chain back to the
                full-shape value (only degree-REDUCING parallel ops — a
                downstream consumer's Repartition/Replicate re-shards and
                must not be entered); accept only the de-parallelized,
                original-shape value."""
                outs = pcg.outputs_of(node)
                if out_idx >= len(outs):
                    return None
                val = outs[out_idx]
                while True:
                    uses = pcg.uses_of(val)
                    if len(uses) != 1 or not is_parallel_op(
                        pcg.op_attrs(uses[0].node)
                    ):
                        break
                    nxt = pcg.outputs_of(uses[0].node)[0]
                    if total_degree(nxt) > total_degree(val):
                        break
                    val = nxt
                shape = pcg.tensor_shape(val)
                if (
                    shape.sizes() == want_sizes
                    and shape.sum_degree == 1
                    and (
                        all(d == 1 for d in shape.shard_degrees())
                        # a step whose loss is its loss nodes alone reads
                        # the returned logits nowhere: a plan that leaves
                        # them in shards (a data-parallel one has no
                        # Combine after the head) is taken as it is
                        or self.loss_attrs.loss_type == LossFunction.LOSS_NODES
                    )
                ):
                    return val
                return None

            op_nodes = [
                n
                for n in pcg.topological_ordering()
                if not isinstance(pcg.op_attrs(n), (InputAttrs, WeightAttrs))
            ]
            hits = [n for n in op_nodes if pcg.layer_attrs(n).name == src_name]
            if not hits:
                # branch stacking consumed the named merge node: its output
                # now comes from the group's ReduceSum
                # (compiler/branch_stacking.py names it deterministically)
                hits = [
                    n
                    for n in op_nodes
                    if pcg.layer_attrs(n).name == f"branchstack.{src_name}.sum"
                ]
            candidates = [(hits[0], logit.idx)] if len(hits) == 1 else []
            # fused multi-node ops carry "+"-joined compound names
            # (substitution.py); the position of src_name in the compound is
            # the output index of the fusion's Split
            for n in op_nodes:
                nm = pcg.layer_attrs(n).name
                if nm and "+" in nm and src_name in nm.split("+"):
                    candidates.append((n, nm.split("+").index(src_name)))
            for node, out_idx in candidates:
                val = resolve(node, out_idx)
                if val is not None:
                    return val
        # Single-sink fallback is only sound when the sink can actually BE
        # the logit: the CG logit must itself be unconsumed (a consumed
        # logit means the sink is some downstream tensor — silently training
        # against it would optimize the wrong objective) and the shape must
        # match.
        if self.cg.uses_of(logit):
            raise ValueError(
                "cannot identify the model output after the Unity rewrite: "
                f"the logit layer (name={src_name!r}) could not be resolved "
                "by name and the logit tensor has downstream consumers, so "
                "the graph sink is a different tensor — give the "
                "logit-producing layer a unique name"
            )
        try:
            sink = _find_sink_output(pcg)
        except AssertionError:
            raise ValueError(
                "cannot identify the model output after the Unity rewrite: "
                "the graph has multiple unconsumed outputs and the logit "
                "producer could not be resolved by name "
                f"(name={src_name!r}) — give the logit-producing layer a "
                "unique name="
            ) from None
        if pcg.tensor_shape(sink).sizes() != want_sizes:
            raise ValueError(
                "cannot identify the model output after the Unity rewrite: "
                f"the graph sink has shape {pcg.tensor_shape(sink).sizes()} "
                f"but the logit is {want_sizes} — give the logit-producing "
                "layer a unique name"
            )
        return sink

    def _step_stats_flags(self) -> Tuple[bool, bool]:
        """(collect_step_stats, guard_nonfinite_updates) implied by the
        run-health config: an event log or any active health policy needs
        the fused in-jit norms; skip_step/raise additionally guard the
        update so a non-finite step never corrupts the parameters."""
        cfg = self.config
        health_on = cfg.health_policy not in ("", "off")
        collect = bool(cfg.metrics_dir) or health_on
        guard = cfg.health_policy in ("skip_step", "raise")
        return collect, guard

    def _validate_config_flags(self) -> None:
        """Reference flags whose capability XLA subsumes are rejected or
        acknowledged loudly, never silently ignored (round-1 review: dead
        flags lie to users)."""
        cfg = self.config
        from flexflow_tpu.observability.health import HEALTH_POLICIES

        if cfg.health_policy not in HEALTH_POLICIES and cfg.health_policy:
            raise ValueError(
                f"health_policy {cfg.health_policy!r} not in "
                f"{HEALTH_POLICIES}"
            )
        if cfg.max_devices < 0:
            raise ValueError(f"max_devices must be >= 0, got {cfg.max_devices}")
        if cfg.checkpoint_every_n_steps < 0:
            raise ValueError(
                "checkpoint_every_n_steps must be >= 0, got "
                f"{cfg.checkpoint_every_n_steps}"
            )
        if cfg.submesh_branches and self._step_stats_flags()[0]:
            # the sub-mesh backend runs per-island programs without the
            # fused-step stats hook; silently dropping health coverage the
            # user asked for would be worse than refusing
            raise ValueError(
                "metrics_dir/health_policy are not supported with "
                "submesh_branches (no fused step to instrument)"
            )
        if cfg.perform_fusion:
            # The reference's FusedOp packs ops into one Legion task to cut
            # launch overhead — subsumed by XLA (one jitted program). What the
            # flag gates HERE is the algebra-level fusion rule set
            # (substitutions/fusion_rules.py: QKV-style sibling-linear merge,
            # consecutive-linear collapse, activation fusion) explored by the
            # Unity search, which XLA cannot do on its own.
            print(
                "[flexflow_tpu] perform_fusion: graph-level fusion rules "
                "(sibling/consecutive linear merge, activation fusion) added "
                "to the search space; launch-overhead fusion itself is "
                "subsumed by XLA jit"
            )
        if cfg.search_overlap_backward_update:
            print(
                "[flexflow_tpu] search_overlap_backward_update: always on — "
                "backward and optimizer update live in one jitted step, XLA "
                "schedules them overlapped"
            )
        if cfg.enable_inplace_optimizations:
            print(
                "[flexflow_tpu] enable_inplace_optimizations: always on — "
                "parameter/optimizer buffers are donated to the jitted step "
                "(donate_argnums), XLA updates them in place"
            )

    def _forced_seed_result(self, pcg0, ctx, spec, seed_name: str):
        """Lower the named strategy template verbatim (force_strategy_seed):
        lets a caller measure each template's REAL step time against the
        cost model's ranking."""
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingCache,
        )
        from flexflow_tpu.compiler.unity_algorithm import (
            enumerate_seeds,
            evaluate_pcg,
        )

        # one cache for serial + the template: they share most subtrees
        cache = MachineMappingCache()
        serial = evaluate_pcg(pcg0, ctx, spec, cache)
        if seed_name == "serial":
            if serial is None:
                raise ValueError("serial plan is unmappable")
            serial.serial_runtime = serial.runtime
            serial.seed_runtimes = {}
            return serial
        for label, seed_pcg in enumerate_seeds(pcg0, spec.num_devices):
            if label != seed_name:
                continue
            result = evaluate_pcg(seed_pcg, ctx, spec, cache)
            if result is None:
                raise ValueError(f"seed {seed_name} is unmappable")
            result.serial_runtime = (
                serial.runtime if serial else float("nan")
            )
            result.seed_runtimes = {label: result.runtime}
            return result
        if seed_name.startswith("pp"):
            # pipeline templates (ISSUE 13): pp{S}m{M}[xdp{D}] — forced
            # stage-partitioned plans for the A/B harness and the elastic
            # tests, independent of what a budgeted search would pick
            import re as _re

            m = _re.fullmatch(
                r"pp(\d+)m(\d+)(?:xdp(\d+))?", seed_name
            )
            if m:
                from flexflow_tpu.compiler.unity_algorithm import (
                    pipeline_seed,
                )

                seed_pcg = pipeline_seed(
                    pcg0,
                    int(m.group(1)),
                    int(m.group(2)),
                    inner_dp=int(m.group(3) or 1),
                    degree_cap=spec.num_devices,
                )
                result = evaluate_pcg(seed_pcg, ctx, spec, cache)
                if result is None:
                    raise ValueError(f"seed {seed_name} is unmappable")
                result.serial_runtime = (
                    serial.runtime if serial else float("nan")
                )
                result.seed_runtimes = {seed_name: result.runtime}
                return result
        raise ValueError(f"unknown strategy seed {seed_name!r}")

    def _price_resource_splits(self, logit):
        """Price the model's machine mapping WITH disjoint-resource splits
        enabled (reference get_machine_resource_splits + FFMapper point
        placement): legal here because the sub-mesh branch runtime this
        model compiles to executes exactly such placements. Returns the
        provenance dict recorded on search_provenance."""
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            AnalyticTPUCostEstimator,
            make_default_allowed_machine_views,
        )
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingContext,
        )
        from flexflow_tpu.compiler.unity_algorithm import evaluate_pcg
        from flexflow_tpu.pcg.machine_view import MachineSpecification
        from flexflow_tpu.pcg.parallel_computation_graph import (
            pcg_from_computation_graph,
        )

        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingCache,
        )

        ndev = len(jax.devices())
        spec = MachineSpecification(
            max(self.config.num_nodes, 1), 1,
            max(ndev // max(self.config.num_nodes, 1), 1), 25.0, 400.0,
        )
        pcg = pcg_from_computation_graph(self.cg)
        ctx = MachineMappingContext(
            AnalyticTPUCostEstimator(spec),
            make_default_allowed_machine_views(),
            overlap_fraction=0.5,
            allow_resource_splits=True,
        )
        # separate caches on purpose: a MachineMappingCache is only valid
        # for ONE context (the allow_resource_splits flag changes results)
        split = evaluate_pcg(pcg, ctx, spec, MachineMappingCache())
        ctx_flat = MachineMappingContext(
            AnalyticTPUCostEstimator(spec),
            make_default_allowed_machine_views(),
            overlap_fraction=0.5,
            allow_resource_splits=False,
        )
        flat = evaluate_pcg(pcg, ctx_flat, spec, MachineMappingCache())
        return {
            "resource_splits_priced": True,
            "estimated_ms": None if split is None else split.runtime,
            "full_mesh_estimated_ms": None if flat is None else flat.runtime,
        }

    def _lower_step_program(self):
        """ONE shared lowering/compile of the searched instance's donated
        step (analysis/lowering.py): the `--plan-audit` XLA memory
        cross-check and the communication census both read it, so a
        compile with both checks pays the XLA compile once."""
        from flexflow_tpu.analysis.lowering import lower_step_program

        return lower_step_program(
            self.instance, self.params, self.opt_state, self.loss_attrs,
            label_dtype=self._label_dtype,
        )

    def step_account(self) -> Dict[str, object]:
        """Where the bytes of the step `fit` runs are, by node
        (`observability/step_account.account`): XLA's totals with its own
        peak, who holds the peak, what the forward pass leaves for the
        backward pass, what lies in `S(1)`. Made from the step
        `analysis/lowering.py` lowered last for this model's instance,
        lowered here (no second trace: the arguments are `fit`'s) where none
        was; `observability.step_account.report()` is the text of it."""
        from flexflow_tpu.observability import step_account

        if step_account.noted_instance() is not self.instance:
            from flexflow_tpu.analysis.lowering import lower_step_trace

            # every backend's step, and no compile until `last()` asks
            lower_step_trace(
                self.instance, self.loss_attrs,
                label_dtype=self._label_dtype, params=self.params,
                opt_state=self.opt_state,
            )
        return step_account.last()

    def _xla_memory_cross_check(self, lowered) -> Dict[str, object]:
        """Read XLA's `memory_analysis()` off the shared compiled step —
        the compiler's own per-device accounting of the exact program the
        run will execute. Returns the fields merged into
        `search_provenance["memory"]`: the XLA stats, per-device measured
        bytes (arguments + outputs + temps - donated aliases), and the
        geomean predicted/measured ratio across devices.

        Static prediction and XLA measurement model the same step, so the
        ratio is a calibration number, not an identity: XLA aliases
        donated buffers and rematerializes where profitable, while the
        liveness model charges every term it can name."""
        import math as _math

        ma = lowered.memory_analysis()
        xla = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        # per-device live bytes of the compiled step: donated aliases
        # (params/opt state re-used in place) are not double-counted
        measured = max(
            xla["argument_bytes"]
            + xla["output_bytes"]
            + xla["temp_bytes"]
            - xla["alias_bytes"],
            1,
        )
        def _geomean(values):
            ratios = [p / measured for p in values if p and p > 0]
            if not ratios:
                return None
            return round(
                _math.exp(sum(_math.log(r) for r in ratios) / len(ratios)),
                4,
            )

        mem_prov = self.search_provenance["memory"]
        return {
            "xla": xla,
            "xla_per_device_bytes": int(measured),
            # mapped (Unity-semantics) prediction: devices outside the
            # searched views predict 0 and are excluded from the geomean
            "predicted_over_xla_geomean": _geomean(
                mem_prov["predicted_peak_bytes_per_device"].values()
            ),
            # full-mesh (executor-semantics) prediction: every device of
            # the GSPMD lowering — the headline calibration number
            "full_mesh_over_xla_geomean": _geomean(
                mem_prov.get(
                    "predicted_peak_bytes_full_mesh", {}
                ).values()
            ),
        }

    def _comm_cross_check(self, lowered) -> None:
        """Static communication verification of the compiled winner
        (ISSUE 11): extract the collective census from the shared lowered
        step and cross-check it against the movement-edge predictions the
        search exported (`search_provenance["comm"]`). COMM diagnostics
        ride the comm record's own verify summary, and the census +
        bytes geomean are additionally recorded beside the plan audit's
        movement measurements."""
        from flexflow_tpu.analysis.comm_analysis import (
            comm_diagnostics,
            comm_summary_json,
            cross_check_comm,
            extract_collectives,
            predicted_weight_gather_bytes,
        )
        from flexflow_tpu.analysis.diagnostics import (
            summarize as _verify_summarize,
        )

        ctx = getattr(self, "_comm_ctx", None)
        if not ctx:
            # dead-flag rule: say why (the prediction export failed, so
            # its error is already on the record — annotate the census)
            self.search_provenance["comm"].setdefault(
                "skipped", "no movement-prediction context to cross-check"
            )
            return
        analysis = cross_check_comm(
            ctx["predictions"],
            extract_collectives(lowered.hlo_text()),
            bypassed_nodes=ctx["bypassed"],
            weight_gather_bytes=predicted_weight_gather_bytes(self.instance),
        )
        diags = comm_diagnostics(analysis)
        summary = comm_summary_json(analysis)
        self.search_provenance["comm"].update(summary)
        self.search_provenance["comm"]["verify"] = _verify_summarize(diags)
        audit = self.search_provenance.get("plan_audit")
        if isinstance(audit, dict) and "error" not in audit:
            # beside the movement measurements: the census and the
            # predicted/lowered bytes geomean land in the audit record
            audit["comm"] = {
                "census": summary["census"],
                "num_collectives": summary["num_collectives"],
                "bytes_geomean": summary["bytes_geomean"],
                "unmatched_collectives": summary["unmatched_collectives"],
                "host_transfers": summary["host_transfers"],
            }

    def _exec_contract_check(self, lowered) -> None:
        """Static execution-contract verification of the compiled winner
        (ISSUE 14): determinism census + donation/aliasing audit off the
        shared lowered step, recorded in `search_provenance["exec"]`
        with its own verify summary. The fingerprints in the record are
        what DET002 re-verifies on `fit(resume=True)` and
        `recompile()`."""
        from flexflow_tpu.analysis.diagnostics import (
            summarize as _verify_summarize,
        )
        from flexflow_tpu.analysis.exec_contract import (
            analyze_lowered_step,
            exec_diagnostics,
            exec_summary_json,
        )

        analysis = analyze_lowered_step(lowered)
        diags = exec_diagnostics(analysis)
        record = exec_summary_json(analysis)
        record.pop("exec", None)  # the CLI schema key, not provenance
        record["verify"] = _verify_summarize(diags)
        self.search_provenance["exec"] = record

    def _exec_contract_record(self) -> Dict[str, object]:
        """The persistable fingerprint contract for THIS compiled model
        (exec_contract.contract_record shape). Searched winners already
        carry it (`search_provenance["exec"]`, the always-on compile
        pass); DP/single-device backends compute the cheap trace-only
        program fingerprint here, once per compile, when checkpointing
        first asks for it."""
        import jax as _jax

        from flexflow_tpu.analysis.exec_contract import (
            CONTRACT_SCHEMA,
            step_program_fingerprint,
        )

        prov = (
            self.search_provenance
            if isinstance(self.search_provenance, dict)
            else None
        )
        rec = (prov or {}).get("exec")
        if isinstance(rec, dict) and rec.get("program_fingerprint"):
            return {
                "schema": CONTRACT_SCHEMA,
                "program_fingerprint": rec["program_fingerprint"],
                "hlo_fingerprint": rec.get("hlo_fingerprint"),
                "program_key": rec.get("program_key"),
                "jax_version": _jax.__version__,
            }
        if self._exec_fp_record is None:
            self._exec_fp_record = step_program_fingerprint(
                self.instance,
                self.loss_attrs,
                label_dtype=self._label_dtype,
                params=self.params,
                opt_state=self.opt_state,
            )
        return self._exec_fp_record

    def _exec_contract_sync(self, directory: str, resume: bool) -> None:
        """DET002's resume half: persist the step-program contract
        beside the checkpoints (`exec_contract.json`), and under
        `fit(resume=True)` verify the program about to run against the
        recorded one — a drifted fingerprint means the resumed
        trajectory cannot be bitwise and is reported loudly (recorded in
        `exec_resume_check`, and in `search_provenance["exec"]` when the
        searched record exists). A contract failure must never kill a
        fit: errors degrade to a recorded skip."""
        from flexflow_tpu.analysis.diagnostics import format_diagnostic
        from flexflow_tpu.analysis.exec_contract import (
            compare_contract_records,
            read_contract_record,
            write_contract_record,
        )

        if os.environ.get("FF_TPU_NO_EXEC_CONTRACT") == "1":
            self.exec_resume_check = {
                "match": None,
                "reason": "FF_TPU_NO_EXEC_CONTRACT=1",
            }
            return
        try:
            current = self._exec_contract_record()
        except Exception as e:
            self.exec_resume_check = {
                "match": None,
                "reason": f"contract unavailable: "
                f"{type(e).__name__}: {e}"[:200],
            }
            return
        check = None
        if resume:
            stored = read_contract_record(directory)
            check, diag = compare_contract_records(stored, current)
            if stored is None or check.get("program_changed"):
                # anchor (or RE-anchor) the contract: a dir predating the
                # contract, or a legitimately different program (batch
                # growth, degraded grid) — future resumes must be checked
                # against the program that is actually running, or DET002
                # stays permanently disarmed after one legitimate change
                try:
                    write_contract_record(directory, current)
                    if check.get("program_changed"):
                        check["re_anchored"] = True
                except OSError:
                    pass
            if diag is not None:
                print(
                    "[flexflow_tpu] WARNING: "
                    + format_diagnostic(diag)
                )
                check["diagnostic"] = diag.to_json()
        else:
            try:
                write_contract_record(directory, current)
            except OSError as e:
                check = {
                    "match": None,
                    "reason": f"contract not written: {e}"[:200],
                }
        if check is not None:
            self.exec_resume_check = check
            prov = (
                self.search_provenance
                if isinstance(self.search_provenance, dict)
                else None
            )
            if prov is not None and isinstance(prov.get("exec"), dict):
                prov["exec"]["resume_check"] = check

    def _compile_searched(self, logit, ndev: int, compute_dtype):
        """Unity path: lift CG->PCG, search substitutions x machine mappings,
        lower the winner (SURVEY.md §3.1 compile stack)."""
        from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
            AnalyticTPUCostEstimator,
            make_default_allowed_machine_views,
        )
        from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
            MachineMappingContext,
        )
        from flexflow_tpu.compiler.unity_algorithm import (
            OptimizerConfig,
            graph_optimize,
        )
        from flexflow_tpu.parallel.executor import DistributedTrainingInstance
        from flexflow_tpu.parallel.mesh import MachineMesh
        from flexflow_tpu.pcg.machine_view import MachineSpecification
        from flexflow_tpu.pcg.parallel_computation_graph import (
            pcg_from_computation_graph,
        )
        from flexflow_tpu.substitutions.rules import (
            generate_parallelization_rules,
        )

        cfg = self.config
        nodes = max(cfg.num_nodes, 1)
        # machine constants of the attached device kind (an unknown kind
        # raises): a search costed with TPU ICI numbers but executed on the
        # CPU test mesh picks plans whose collectives the emulation cannot
        # afford (and vice versa)
        from flexflow_tpu.compiler.machine_constants import machine_constants

        mc = machine_constants()
        inter_bw, intra_bw = mc.inter_node_gbps, mc.intra_node_gbps
        peak_flops, hbm_gbps = mc.peak_flops, mc.hbm_gbps
        ici_lat_ms, dcn_lat_ms = mc.ici_latency_ms, mc.dcn_latency_ms
        exec_spec = MachineSpecification(
            nodes, max(cfg.cpus_per_node, 1), max(ndev // nodes, 1),
            inter_bw, intra_bw,
        )
        # search-only machine override: plan for a bigger machine than we run
        # on (reference search_num_nodes/search_num_workers, config.h:101-102).
        # The override affects only the search; execution always uses the real
        # machine (an oversized plan is for --export-strategy, not running).
        search_nodes = cfg.search_num_nodes if cfg.search_num_nodes > 0 else nodes
        search_workers = (
            cfg.search_num_workers
            if cfg.search_num_workers > 0
            else exec_spec.num_devices_per_node
        )
        spec = MachineSpecification(
            search_nodes, max(cfg.cpus_per_node, 1), search_workers,
            inter_bw, intra_bw,
        )
        audit_estimator = None  # the estimator the plan audit replays against
        from flexflow_tpu.local_execution.cost_estimator import (
            optimizer_state_slots_of as _opt_slots_of,
        )

        # static memory safety (ISSUE 10): the memory model's parameters
        # for THIS compile — the optimizer actually compiled — plus the
        # per-device budget the search must respect
        # (--hbm-gb; 0 = no search-side constraint, winner analysis only)
        mem_slots = _opt_slots_of(self.optimizer_attrs)
        mem_budget_bytes = (
            cfg.hbm_gb * 2**30 if cfg.hbm_gb and cfg.hbm_gb > 0 else 0.0
        )
        from flexflow_tpu.parallel.executor import overlap_lowering_active

        # fused collective-matmul lowering + overlap-aware movement pricing
        # (--overlap / FF_TPU_OVERLAP; FF_TPU_OVERLAP_BASELINE=1
        # force-reverts): the SEARCH prices what the EXECUTOR will lower.
        # cfg.overlap is tri-state — an explicit False must override the
        # env var (the A/B harness's serial arm)
        overlap_on = overlap_lowering_active(cfg.overlap)
        # pipeline parallelism (ISSUE 13): --pipeline / FF_TPU_PIPELINE
        # seeds the search with stage-partitioned candidates and lowers a
        # stage-partitioned winner through the 1F1B microbatch executor
        from flexflow_tpu.parallel.pipeline import pipeline_execution_active

        pipeline_on = pipeline_execution_active(cfg.pipeline)
        # hierarchical multi-slice search (ISSUE 17): --multislice /
        # FF_TPU_MULTISLICE makes slice-boundary legality a search
        # constraint (slice-aware view masking in both DPs) and, on a
        # multi-node spec, runs the two-level ICI/DCN DP whose outer
        # level picks the boundary-crossing axis kind
        from flexflow_tpu.compiler.machine_mapping.hierarchical import (
            multislice_search_active,
        )

        multislice_on = multislice_search_active(cfg.multislice)
        # persisted measured movement-edge costs (--movement-cost-store):
        # estimators prefer a past audit's measurement over the analytic
        # collective estimate; this run's audit extends the table
        movement_store = None
        if cfg.movement_cost_store:
            from flexflow_tpu.compiler.movement_store import (
                MovementCostStore,
            )

            movement_store = MovementCostStore(cfg.movement_cost_store)
        # persistent cost DATABASE (--cost-store-dir, compiler/cost_store):
        # op leaves measured by past sessions/audits price without
        # re-running, the analytic estimator applies per-op-class
        # correction factors fitted from its (analytic, measured) pairs,
        # and this compile's measurements/audit rows are written back. It
        # also serves movement edges when no dedicated movement store is
        # configured (an explicit --movement-cost-store keeps priority).
        cost_store = None
        if cfg.cost_store:
            from flexflow_tpu.compiler.cost_store import CostStore

            cost_store = CostStore(cfg.cost_store)
        # the estimators themselves fall back to the cost store for
        # movement edges when no dedicated movement store is configured;
        # this is the same priority for the audit's write side
        effective_movement_store = (
            movement_store if movement_store is not None else cost_store
        )
        if cfg.import_strategy_file:
            # reuse a saved plan instead of re-searching (config.h:93-95)
            from flexflow_tpu.runtime.strategy import load_strategy

            pcg, mapping, _ = load_strategy(cfg.import_strategy_file)
            # an imported plan is the externally-supplied input MOST likely
            # to be ill-formed (stale file, hand edits, different grid) —
            # verify it like a searched winner. Structural/SP errors abort
            # compile (the lowering would crash or train a wrong graph);
            # machine-view findings are recorded only, since the views were
            # searched for the EXPORTING machine and this host's grid may
            # legitimately differ (the GSPMD lowering runs on the exec mesh).
            from flexflow_tpu.analysis.diagnostics import (
                errors_of,
                format_diagnostic,
            )
            from flexflow_tpu.analysis.diagnostics import (
                summarize as _verify_summarize,
            )
            from flexflow_tpu.analysis.pcg_verify import verify_pcg

            with record_span("compile/verify", check="imported_pcg"):
                verify_diags = verify_pcg(pcg, machine_spec=spec, mapping=mapping)
            self.search_provenance = {
                "search_algorithm": "imported_strategy",
                "verify": _verify_summarize(verify_diags),
            }
            structural = [
                d
                for d in errors_of(verify_diags)
                if not d.rule_id.startswith("MV")
            ]
            if structural:
                raise ValueError(
                    f"imported strategy {cfg.import_strategy_file!r} is "
                    "ill-formed:\n"
                    + "\n".join(format_diagnostic(d) for d in structural)
                )
        else:
            comm_model = None
            if cfg.machine_model_version > 0 or cfg.machine_model_file:
                from flexflow_tpu.compiler.machine_model import (
                    MachineModelCommModel,
                    machine_model_from_config,
                )

                comm_model = MachineModelCommModel(
                    spec,
                    machine_model_from_config(
                        spec, cfg.machine_model_version, cfg.machine_model_file
                    ),
                )
            use_measured = cfg.cost_model == "measured" or (
                cfg.cost_model == "auto"
                and jax.default_backend() == "tpu"
            )
            # measured / calibrated cost models replace hand-set machine
            # constants with probes of the attached backend (the reference
            # never searches on hand-set constants: simulator.h:161-228
            # caches cudaEvent measurements per op)
            calibration = None
            if use_measured or cfg.cost_model == "calibrated":
                from flexflow_tpu.compiler.calibration import get_calibration

                calibration = get_calibration()
            def _build_mapping_ctx():
                """Fresh estimator + mapping context, one per search. The
                initial compile search and each drift re-search
                (ISSUE 18) call this separately so every search prices
                against its own in-memory memo caches — a re-search under
                `CostStore.live_scale` must re-read every leaf from the
                warm store (zero profile calls), not serve another
                search's cached unscaled totals."""
                if use_measured:
                    # reference cost model v2: run each op for real
                    # (local_cost_estimator.cc:29-92), memoized per
                    # (attrs, piece shapes) with ProfilingSettings
                    # warmup/measure discipline
                    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
                        TPUCostEstimator,
                    )
                    from flexflow_tpu.local_execution.cost_estimator import (
                        LocalCostEstimator,
                        optimizer_state_slots_of,
                    )

                    estimator = TPUCostEstimator(
                        spec,
                        # mem accounting prices the optimizer actually
                        # compiled (Adam m/v vs SGD), not a hardcoded
                        # regime
                        local_cost_estimator=LocalCostEstimator(
                            optimizer_state_slots=optimizer_state_slots_of(
                                self.optimizer_attrs
                            ),
                            cost_store=cost_store,
                        ),
                        ici_latency_ms=ici_lat_ms,
                        dcn_latency_ms=dcn_lat_ms,
                        comm_model=comm_model,
                        emulated_mesh=jax.default_backend() == "cpu",
                        calibration=calibration,
                        movement_store=movement_store,
                        cost_store=cost_store,
                    )
                else:
                    estimator = AnalyticTPUCostEstimator(
                        spec,
                        peak_flops=(
                            calibration.peak_flops
                            if calibration
                            else peak_flops
                        ),
                        hbm_gbps=(
                            calibration.hbm_gbps if calibration else hbm_gbps
                        ),
                        ici_latency_ms=ici_lat_ms,
                        dcn_latency_ms=dcn_lat_ms,
                        comm_model=comm_model,
                        # the CPU "mesh" is virtual: all devices share one
                        # host memory system, which changes what weight
                        # replication costs (see parallel_op_cost_ms)
                        emulated_mesh=jax.default_backend() == "cpu",
                        calibration=calibration,
                        movement_store=movement_store,
                        cost_store=cost_store,
                    )
                return estimator

            def _build_search_ctx():
                est = _build_mapping_ctx()
                c = MachineMappingContext(
                    est,
                    make_default_allowed_machine_views(),
                    # compute/collective overlap: measured on the attached
                    # backend when a calibration ran (calibration.overlap —
                    # round-4 verdict weak #2: "no artifact justifies 0.5");
                    # the uncalibrated analytic mode keeps the 0.5
                    # heuristic (async collectives hide roughly half a
                    # stage's compute, fully hidden only for perfectly
                    # balanced stages)
                    overlap_fraction=(
                        calibration.overlap
                        if calibration is not None
                        and calibration.overlap is not None
                        else 0.5
                    ),
                    # disjoint-resource placement is priced when planning
                    # for a machine we are NOT executing on (strategy
                    # export); the sub-mesh branch runtime
                    # (cfg.submesh_branches) prices its own graph under
                    # resource splits in _price_resource_splits. The GSPMD
                    # lowering this method produces runs every op on the
                    # full mesh.
                    allow_resource_splits=spec != exec_spec,
                    # price the fused collective-matmul lowering only when
                    # the executor will actually perform it (--overlap)
                    overlap_lowering=overlap_on,
                    # --hbm-gb > 0: OOM mappings are INFEASIBLE — the DPs
                    # prune over-budget leaves and evaluate_pcg rejects
                    # plans whose liveness peak exceeds the budget
                    # (ISSUE 10)
                    memory_budget_bytes=mem_budget_bytes,
                    optimizer_state_slots=mem_slots,
                    # --multislice: slice-boundary legality masks every
                    # candidate view (constrained included) and multi-node
                    # specs search through the two-level ICI/DCN DP
                    # (machine_mapping/hierarchical.py)
                    slice_aware=multislice_on,
                    slice_hierarchy=multislice_on,
                )
                return est, c

            estimator, ctx = _build_search_ctx()
            audit_estimator = estimator
            search_ndev = spec.num_devices
            degrees = [
                d for d in range(2, search_ndev + 1) if search_ndev % d == 0
            ]
            rules = generate_parallelization_rules(
                degrees,
                enable_parameter_parallel=cfg.enable_parameter_parallel,
                enable_attribute_parallel=cfg.enable_attribute_parallel,
                enable_pipeline=pipeline_on,
                pipeline_microbatches=cfg.pipeline_microbatches,
            )
            if cfg.perform_fusion:
                from flexflow_tpu.substitutions.fusion_rules import (
                    generate_fusion_rules,
                )

                rules = list(rules) + generate_fusion_rules()
            if cfg.substitution_json_path:
                # legacy TASO rule corpus (reference substitution-generator
                # legacy_rules.h:40-55) extends the generated rule set
                from flexflow_tpu.substitutions.legacy_rules import (
                    load_legacy_substitutions,
                )

                legacy, skipped = load_legacy_substitutions(
                    cfg.substitution_json_path
                )
                print(
                    f"[flexflow_tpu] loaded {len(legacy)} legacy "
                    f"substitutions from {cfg.substitution_json_path} "
                    f"({skipped} outside the convertible vocabulary)"
                )
                rules = rules + legacy
            pcg0 = pcg_from_computation_graph(self.cg)
            if cfg.branch_stacking:
                from flexflow_tpu.compiler.branch_stacking import (
                    stack_isomorphic_branches,
                )

                pcg0, _ = stack_isomorphic_branches(pcg0)

            def do_search():
                import time as _time

                from flexflow_tpu.compiler.unity_algorithm import (
                    parallel_degree_summary,
                    serial_compute_nodes,
                )

                t0 = _time.perf_counter()
                with record_span("compile/search"):
                    if cfg.force_strategy_seed:
                        result = self._forced_seed_result(
                            pcg0, ctx, spec, cfg.force_strategy_seed
                        )
                    elif cfg.search_algorithm == "mcmc":
                        # legacy search mode: simulated annealing over the same
                        # rewrite lattice (reference simulator.h:671
                        # strategy_search_task)
                        from flexflow_tpu.compiler.mcmc_search import (
                            MCMCConfig,
                            mcmc_optimize,
                        )

                        result = mcmc_optimize(
                            pcg0, ctx, spec, rules,
                            # budget<=0 disables the walk, matching the unity
                            # path's sentinel semantics
                            MCMCConfig(
                                budget=max(cfg.search_budget, 0) * 10,
                                rng_seed=cfg.seed,
                            ),
                        )
                    else:
                        result = graph_optimize(
                            pcg0, ctx, spec, rules,
                            OptimizerConfig(
                                alpha=cfg.search_alpha,
                                budget=cfg.search_budget,
                                pipeline_seeds=pipeline_on,
                                pipeline_microbatches=cfg.pipeline_microbatches,
                            ),
                        )
                telem = result.telemetry or {}
                self.search_provenance = {
                    "explored": result.explored,
                    "estimated_ms": result.runtime,
                    "serial_ms": result.serial_runtime,
                    "search_seconds": _time.perf_counter() - t0,
                    "seed_runtimes": dict(result.seed_runtimes or {}),
                    "parallel_degrees": parallel_degree_summary(result.pcg),
                    # ops the winner leaves whole on every device (this
                    # path has ndev > 1): a template or rule that could
                    # not wrap an op says so nowhere else
                    "serial_compute_nodes": serial_compute_nodes(result.pcg),
                    "cost_model": cfg.cost_model,
                    # how the plan was found (observability: evaluation/
                    # dedup counters + the active dedup flags, so A/B
                    # artifacts record the search's actual work and which
                    # collision classes collapsed candidates)
                    "search_algorithm": (
                        "forced_seed"
                        if cfg.force_strategy_seed
                        else cfg.search_algorithm
                    ),
                    "evaluations": telem.get("evaluations"),
                    "infeasible": telem.get("infeasible"),
                    "dedup_hits": telem.get("dedup_hits"),
                    "symmetry_dedup": telem.get("symmetry_dedup"),
                    "signature_version": telem.get("signature_version"),
                    # search-time attribution: shared-cache reuse across
                    # candidates and per-phase wall-clock (tree_build / dp
                    # / leaf_cost / match / seed_build; phases nest)
                    "mm_cache_hits": telem.get("mm_cache_hits"),
                    "mm_cache_misses": telem.get("mm_cache_misses"),
                    "native_dp": telem.get("native_dp"),
                    "phase_ms": telem.get("phase_ms"),
                    # algorithm-specific extras only — the counters above
                    # are the single source of truth
                    "telemetry": {
                        k: v
                        for k, v in telem.items()
                        if k
                        not in (
                            "evaluations",
                            "infeasible",
                            "dedup_hits",
                            "symmetry_dedup",
                            "signature_version",
                            "mm_cache_hits",
                            "mm_cache_misses",
                            "native_dp",
                            "phase_ms",
                        )
                    }
                    or None,
                    "calibration": (
                        calibration.as_dict() if calibration else None
                    ),
                }
                if multislice_on:
                    # two-level DP provenance: per-boundary-axis-kind
                    # runtimes and the winning choice for the FINAL plan
                    # (None on single-node specs, where the hierarchy is
                    # degenerate and only view masking applied)
                    self.search_provenance["multislice"] = {
                        "enabled": True,
                        "hierarchical": getattr(
                            result, "hierarchical", None
                        ),
                        "slices": spec.num_nodes,
                        "devices_per_slice": spec.num_devices_per_node,
                    }
                if cost_store is not None:
                    # fallthrough telemetry: how the persistent cost
                    # database performed for THIS search (hit/miss per
                    # entry family + the fitted correction factors)
                    self.search_provenance["cost_db"] = (
                        cost_store.provenance()
                    )
                if overlap_on:
                    edges = result.overlap_edges or []
                    self.search_provenance["overlap"] = {
                        "enabled": True,
                        "edges": edges,
                        "eligible": len(edges),
                        "chosen": sum(
                            1 for e in edges if e.get("chosen")
                        ),
                        "movement_store_entries": (
                            # movement edges only: a cost store serving as
                            # the movement table also holds op leaves,
                            # which must not inflate this field
                            effective_movement_store.movement_entry_count()
                            if hasattr(
                                effective_movement_store,
                                "movement_entry_count",
                            )
                            else len(effective_movement_store)
                        ) if effective_movement_store is not None else None,
                    }
                with record_span("compile/verify", check="pcg_memory"):
                    # static verification of the WINNER is always on (ISSUE 4):
                    # the plan about to be lowered must satisfy every PCG
                    # invariant and its machine views must fit the search grid.
                    # Candidate-level verification stays behind FF_TPU_VERIFY=1
                    # (apply_substitution); the winner check is cheap (once per
                    # compile) and is the last line before GSPMD lowering.
                    from flexflow_tpu.analysis.diagnostics import (
                        summarize as _verify_summarize,
                    )
                    from flexflow_tpu.analysis.pcg_verify import verify_pcg

                    verify_diags = verify_pcg(
                        result.pcg,
                        machine_spec=spec,
                        mapping=result.machine_mapping,
                    )
                    # static memory verification of the winner (ISSUE 10):
                    # the same liveness analysis `ffcheck --memory` runs, at
                    # the capacity the search was constrained to (--hbm-gb)
                    # or, unconstrained, the backend's reported HBM limit.
                    # MEM diagnostics ride the same verify summary; the
                    # per-device peak timeline lands in
                    # search_provenance["memory"] (the plan audit later adds
                    # XLA's compiled per-device bytes beside it).
                    from flexflow_tpu.analysis.memory_analysis import (
                        detect_device_hbm_bytes,
                        verify_memory,
                    )

                    mem_capacity = mem_budget_bytes or detect_device_hbm_bytes()
                    mem_analysis, mem_diags = verify_memory(
                        result.pcg,
                        machine_spec=spec,
                        mapping=result.machine_mapping,
                        hbm_bytes=mem_capacity or None,
                        optimizer_state_slots=mem_slots,
                    )
                    verify_diags = list(verify_diags) + list(mem_diags)
                    self.search_provenance["verify"] = _verify_summarize(
                        verify_diags
                    )
                    from flexflow_tpu.analysis.memory_analysis import (
                        analyze_memory as _analyze_memory,
                    )

                    # the executor-semantics prediction: the GSPMD lowering
                    # runs every op on the FULL mesh (pieces replicated to
                    # devices outside the searched view), which is what the
                    # compiled program's memory actually looks like — the
                    # mapped analysis above is the Unity-semantics view the
                    # MEM rules verify
                    full_mesh = _analyze_memory(
                        result.pcg,
                        spec,
                        None,
                        optimizer_state_slots=mem_slots,
                    )
                    self.search_provenance["memory"] = {
                        "predicted_peak_bytes_per_device": {
                            str(d): int(v)
                            for d, v in mem_analysis.peak_by_device().items()
                        },
                        "predicted_peak_bytes_full_mesh": {
                            str(d): int(v)
                            for d, v in full_mesh.peak_by_device().items()
                        },
                        "capacity_bytes": (
                            int(mem_capacity) if mem_capacity else None
                        ),
                        "hbm_gb": cfg.hbm_gb or None,
                        "optimizer_state_slots": mem_slots,
                    }
                return result.pcg, result.machine_mapping, result.runtime

            # multi-host determinism (SURVEY §7 hard-part 6): host 0 searches,
            # everyone lowers the identical broadcast plan — measured-cost
            # noise must not let hosts pick mismatched collectives
            from flexflow_tpu.runtime.distributed import (
                process_index,
                run_search_on_host_0,
            )

            pcg, mapping, search_runtime = run_search_on_host_0(do_search)

            # drift-advisory transition verifier (ISSUE 19): candidate
            # seed label -> static TRN verdict for hot-swapping the live
            # plan onto it. 'searched' is the identity transition; seed
            # labels are re-mapped against the same machine with a fresh
            # context (warm caches, zero profile calls). The monitor
            # records an advisory whose candidate fails verification as
            # swap_blocked and never marks it actionable.
            def _drift_transition(label):
                from flexflow_tpu.analysis.transition_analysis import (
                    transition_verdict_record,
                    verify_transition,
                )
                from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
                    MachineMappingCache,
                )
                from flexflow_tpu.compiler.unity_algorithm import (
                    enumerate_seeds,
                    evaluate_pcg,
                )
                from flexflow_tpu.local_execution.cost_estimator import (
                    optimizer_state_slots_of,
                )

                if label == "searched":
                    cand_pcg, cand_mapping = pcg, mapping
                else:
                    cand = None
                    for name, seed_pcg in enumerate_seeds(
                        pcg0, spec.num_devices
                    ):
                        if name == label:
                            cand = seed_pcg
                            break
                    if cand is None:
                        return None
                    _, ctx2 = _build_search_ctx()
                    r = evaluate_pcg(
                        cand, ctx2, spec, MachineMappingCache()
                    )
                    if r is None:
                        return None
                    cand_pcg, cand_mapping = r.pcg, r.machine_mapping
                a, _ = verify_transition(
                    pcg, mapping, cand_pcg, cand_mapping,
                    machine_spec=spec,
                    hbm_bytes=(
                        cfg.hbm_gb * 2**30
                        if cfg.hbm_gb and cfg.hbm_gb > 0
                        else None
                    ),
                    optimizer_state_slots=optimizer_state_slots_of(
                        self.optimizer_attrs
                    ),
                )
                return transition_verdict_record(a)

            self._drift_transition = _drift_transition

            if (
                cost_store is not None
                and not cfg.force_strategy_seed
                and cfg.search_algorithm != "mcmc"
            ):
                # warm re-search hook for the drift monitor (ISSUE 18):
                # re-run the full plan search with every cost-store read
                # scaled by the live correction. _build_search_ctx()
                # constructs fresh estimator/context memo caches, so every
                # leaf re-reads the warm store under the scale — zero
                # profile calls (the PR-7 warm re-search path). The
                # previous live_scale is restored afterwards; the hook is
                # advisory-only and never touches the compiled executable.
                def _drift_research(scale):
                    import time as _time

                    from flexflow_tpu.compiler.unity_algorithm import (
                        parallel_degree_summary,
                    )

                    t0 = _time.perf_counter()
                    prev_scale = cost_store.live_scale
                    try:
                        cost_store.live_scale = scale
                        _, ctx2 = _build_search_ctx()
                        r = graph_optimize(
                            pcg0, ctx2, spec, rules,
                            OptimizerConfig(
                                alpha=cfg.search_alpha,
                                budget=cfg.search_budget,
                                pipeline_seeds=pipeline_on,
                                pipeline_microbatches=(
                                    cfg.pipeline_microbatches
                                ),
                            ),
                        )
                    finally:
                        cost_store.live_scale = prev_scale
                    return {
                        "estimated_ms": r.runtime,
                        "seed_runtimes": dict(r.seed_runtimes or {}),
                        "parallel_degrees": parallel_degree_summary(r.pcg),
                        "research_seconds": _time.perf_counter() - t0,
                    }

                self._drift_research = _drift_research
            if cfg.export_strategy_file and process_index() == 0:
                from flexflow_tpu.runtime.strategy import save_strategy

                save_strategy(
                    cfg.export_strategy_file, pcg, mapping, search_runtime
                )
        searched_logit = self._find_searched_logit(pcg, logit)
        mm = MachineMesh.from_spec(exec_spec)
        collect, guard = self._step_stats_flags()
        instance = None
        if pipeline_on:
            # a stage-partitioned winner lowers through the 1F1B executor
            # when its structure supports it; otherwise (or for flat
            # winners) the GSPMD executor stays the always-correct path —
            # stage ops are value-identity there
            from flexflow_tpu.pcg.pipeline import analyze_pipeline
            from flexflow_tpu.parallel.pipeline import (
                PipelinedTrainingInstance,
                PipelineUnsupported,
            )

            if analyze_pipeline(pcg) is not None:
                try:
                    with record_span("compile/build_instance"):
                        instance = PipelinedTrainingInstance(
                            pcg, searched_logit, self.loss_attrs,
                            self.optimizer_attrs,
                            devices=jax.devices()[:ndev],
                            metrics=self.metrics,
                            compute_dtype=compute_dtype,
                            collect_step_stats=collect,
                            guard_nonfinite_updates=guard,
                        )
                except PipelineUnsupported as e:
                    print(
                        "[flexflow_tpu] pipelined winner falls back to the "
                        f"flat GSPMD executor: {e}"
                    )
                    if self.search_provenance is not None:
                        self.search_provenance["pipeline"] = {
                            "executor": "flat-fallback",
                            "reason": str(e)[:200],
                        }
                    if cfg.hbm_gb and cfg.hbm_gb > 0:
                        # the budget admitted this plan with the 1F1B
                        # stash/submesh discounts; flat execution keeps
                        # every stage resident on every device, so the
                        # admitted verdict no longer describes what runs
                        print(
                            "[flexflow_tpu] WARNING: --hbm-gb admitted "
                            "this plan under 1F1B pipeline memory "
                            "accounting, but execution is flat — the "
                            "memory verdict does not cover the flat "
                            "program (re-run without --pipeline to "
                            "search a flat-feasible plan)"
                        )
                if instance is not None and self.search_provenance is not None:
                    self.search_provenance["pipeline"] = {
                        "num_stages": instance.structure.num_stages,
                        "num_microbatches": (
                            instance.structure.num_microbatches
                        ),
                        "mesh": dict(instance.mesh.shape),
                        "executor": "1f1b",
                    }
        if instance is None:
            with record_span("compile/build_instance"):
                instance = DistributedTrainingInstance(
                    pcg, searched_logit, self.loss_attrs, self.optimizer_attrs,
                    mm, mapping=mapping, metrics=self.metrics,
                    compute_dtype=compute_dtype,
                    aux_loss_tensors=_find_aux_outputs(pcg),
                    collect_step_stats=collect, guard_nonfinite_updates=guard,
                    overlap=cfg.overlap,
                )
        # the fused-lowering annotation: movement-edge node -> fused kind
        # (the Combine feeding each ag_matmul site, the Reduction draining
        # each matmul_rs site). Verified against the PCG adjacency rule
        # (PCG008) before anything consumes it — an annotation the
        # executor cannot honor must fail loudly, not mis-lower.
        fused_edge_map: Dict[int, str] = {}
        for site, kind in instance.overlap_sites.items():
            if kind == "ag_matmul":
                fused_edge_map[pcg.inputs_of(site)[0].node.idx] = kind
            else:
                uses = pcg.uses_of(pcg.outputs_of(site)[0])
                if uses:
                    fused_edge_map[uses[0].node.idx] = kind
        if fused_edge_map:
            from flexflow_tpu.analysis.diagnostics import (
                errors_of,
                format_diagnostic,
            )
            from flexflow_tpu.analysis.pcg_verify import verify_overlap_plan

            with record_span("compile/verify", check="overlap_plan"):
                bad = errors_of(verify_overlap_plan(pcg, fused_edge_map))
            if bad:
                raise ValueError(
                    "fused-overlap annotation failed verification:\n"
                    + "\n".join(format_diagnostic(d) for d in bad)
                )
            if self.search_provenance is not None:
                self.search_provenance.setdefault("overlap", {})[
                    "executor_fused_edges"
                ] = dict(sorted(fused_edge_map.items()))
        # static communication verification of the winner (ISSUE 11): the
        # movement-edge prediction export — the exact leaf-key pricing
        # path both DPs charge movement through — is ALWAYS recorded
        # (cheap, no lowering); under --plan-audit the compile tail
        # additionally extracts the lowered HLO collective census off the
        # shared compiled step and cross-checks it (COMM001-COMM004,
        # _comm_cross_check).
        if self.search_provenance is None:
            self.search_provenance = {}
        try:
            from flexflow_tpu.analysis.comm_analysis import (
                trailing_reshard_nodes,
            )
            from flexflow_tpu.compiler.machine_mapping.movement_export import (
                export_movement_predictions,
            )

            with record_span("compile/verify", check="comm_predictions"):
                comm_predictions = export_movement_predictions(
                    pcg, mapping, estimator=audit_estimator,
                    machine_spec=spec, fused_edges=fused_edge_map,
                )
                self._comm_ctx = {
                    "predictions": comm_predictions,
                    # the executor consumes the NAME-RESOLVED logit (it may
                    # differ from the topological sink in multi-output
                    # graphs), so the bypassed-chain computation must walk
                    # from the same tensor the instance will use
                    "bypassed": trailing_reshard_nodes(
                        pcg, logits=[searched_logit]
                    ),
                }
                # predicted_bytes_total is NOT recorded here: its canonical
                # definition (exempt edges excluded) needs the bypassed/
                # host-feed classification and lands with the census summary
                # under --plan-audit, one definition only
                self.search_provenance["comm"] = {
                    "num_edges": len(comm_predictions),
                    "edges": [p.to_json() for p in comm_predictions],
                }
        except Exception as e:  # prediction export must not kill compile
            self._comm_ctx = None
            self.search_provenance["comm"] = {
                "error": f"{type(e).__name__}: {e}"[:200]
            }
        if cfg.plan_audit and audit_estimator is not None:
            # predicted-vs-measured fidelity of the plan we are about to
            # execute, against the SAME estimator the search priced with
            # (observability/plan_audit.py). Opt-in: the replay reruns
            # every op and movement edge for real.
            from flexflow_tpu.local_execution.cost_estimator import (
                optimizer_state_slots_of,
            )
            from flexflow_tpu.observability.plan_audit import audit_plan

            # overlap sites measure as FUSED (the verified fused_edge_map
            # above), with the DP's overlapped-exposure predictions for
            # those edges carried from the search provenance
            overlap_predictions: Dict[int, float] = {}
            prov_overlap = (self.search_provenance or {}).get("overlap")
            for e in (prov_overlap or {}).get("edges") or []:
                node_idx = (
                    e.get("src_node")
                    if e.get("kind") == "ag_matmul"
                    else e.get("dst_node")
                )
                if node_idx is not None:
                    overlap_predictions[node_idx] = e.get(
                        "overlapped_exposed_ms"
                    )
            try:
                audit = audit_plan(
                    pcg, mapping or {}, audit_estimator,
                    machine_mesh=mm, shardings=instance.shardings,
                    optimizer_state_slots=optimizer_state_slots_of(
                        self.optimizer_attrs
                    ),
                    fused_edges=fused_edge_map,
                    overlap_predictions=overlap_predictions,
                    movement_store=effective_movement_store,
                    cost_store=cost_store,
                    comm_predictions={
                        p.node_idx: p.predicted_bytes
                        for p in (
                            (self._comm_ctx or {}).get("predictions") or []
                        )
                    },
                )
                if movement_store is not None:
                    movement_store.save()  # cost_store saves below
            except Exception as e:  # an audit failure must not kill compile
                audit = {"error": f"{type(e).__name__}: {e}"[:200]}
            if self.search_provenance is None:
                self.search_provenance = {}
            self.search_provenance["plan_audit"] = audit
        elif cfg.plan_audit:
            # imported plan: there is no estimator to audit against, and
            # silently recording nothing would hide that (dead-flag rule)
            if self.search_provenance is None:
                self.search_provenance = {}
            self.search_provenance["plan_audit"] = {
                "skipped": "import_strategy_file: the imported plan "
                "carries no cost estimator to audit against"
            }
        if cost_store is not None:
            # persist everything this compile measured (search-side op
            # leaves AND audit rows) so the next session starts warm;
            # refresh the provenance block with the post-audit state. An
            # unwritable store directory must not kill a successfully
            # compiled model (the cache is an optimization, same policy
            # as the read side's corrupt-store tolerance).
            try:
                cost_store.save()
            except OSError as e:
                print(
                    f"[flexflow_tpu] cost store not saved "
                    f"({cost_store.path}): {type(e).__name__}: {e}"
                )
            if (
                self.search_provenance is not None
                and "cost_db" in self.search_provenance
            ):
                self.search_provenance["cost_db"] = cost_store.provenance()
        return instance

    # ------------------------------------------------------------------
    # training loops
    # ------------------------------------------------------------------

    def _input_names(self) -> List[str]:
        cg = self.cg
        names = []
        for n in cg.topological_ordering():
            la = cg.layer_attrs(n)
            if isinstance(la.attrs, InputAttrs):
                names.append(la.name or param_key(n))
        return names

    def _make_iterator(
        self, x, y, batch_size, shuffle=False, seed_offset: int = 0
    ) -> BatchIterator:
        input_names = self._input_names()
        if isinstance(x, dict):
            inputs = {k: np.asarray(v) for k, v in x.items()}
        elif isinstance(x, (list, tuple)):
            assert len(x) == len(input_names)
            inputs = {k: np.asarray(v) for k, v in zip(input_names, x)}
        else:
            assert len(input_names) == 1, (
                f"model has inputs {input_names}; pass a dict"
            )
            inputs = {input_names[0]: np.asarray(x)}
        shardings = None
        label_sharding = None
        if hasattr(self.instance, "input_sharding"):
            shardings = {}
            for k in inputs:
                try:
                    shardings[k] = self.instance.input_sharding(k)
                except KeyError:
                    shardings[k] = None  # replicated feed; jit reshards
            label_sharding = self.instance.label_sharding()
        label = None
        if y is not None:
            label = np.asarray(y)
            if self._label_dtype == jnp.int32:
                label = label.astype(np.int32)
            else:
                label = label.astype(np.float32)
        return BatchIterator(
            inputs, label, batch_size,
            input_shardings=shardings, label_sharding=label_sharding,
            shuffle=shuffle, seed=self.config.seed + seed_offset,
        )

    def fit(
        self,
        x=None,
        y=None,
        epochs: Optional[int] = None,
        batch_size: Optional[int] = None,
        shuffle: bool = True,
        verbose: bool = True,
        recompile_state=None,
        epoch_offset: int = 0,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every_n_steps: Optional[int] = None,
        resume: bool = False,
    ) -> PerfMetrics:
        """The training loop (reference fit, flexflow_cffi.py:2058: per-iter
        next_batch / forward / zero_gradients / backward / update — here one
        fused jitted step per iteration).

        `recompile_state` (runtime.recompile.RecompileState) is checked after
        every step, mirroring the reference's recompile_on_condition in the
        iteration loop; a fired recompile ends the current epoch early and
        training resumes at the next epoch under the recompiled step (and
        possibly-altered batch size) — batches are never replayed.

        `epoch_offset` decorrelates shuffle order and the step RNG stream
        across SEPARATE fit calls that together form one run (the keras
        callback loop calls fit once per epoch; without the offset every
        epoch would replay the seed-0 permutation and dropout masks).

        `checkpoint_dir`/`checkpoint_every_n_steps` (falling back to the
        config fields) enable the elastic runtime: full-resume snapshots —
        params, optimizer state, RNG stream position, dataloader epoch +
        within-epoch cursor — written by a background thread overlapped
        with the next step (`config.checkpoint_sync` forces the blocking
        path). `resume=True` restores the latest snapshot and
        continues BITWISE-identically to the uninterrupted run: same
        shuffle permutations, same RNG stream, same loss trajectory
        (chaos-pinned in tests/test_elastic.py via FF_TPU_FAULT_STEP).
        With no checkpoint on disk, resume=True cold-starts. Caveat: a
        recompile_state that fires mid-run rebuilds the iterator, so
        resume after an in-run recompile replays a fresh shuffle stream
        (recorded, not bitwise)."""
        assert self.instance is not None, "call compile() first"
        # XLA trace of the whole fit for xprof/tensorboard (the Legion Prof
        # -lg:prof analogue); per-layer ms timing is the separate
        # --profiling flag. The program's host spans (`fit` and what is
        # under it, observability/trace.py) are events of that trace's host
        # plane, on the device planes' clock, and no span waits for the
        # device: the traced fit is the fit.
        if self.config.profile_trace_dir:
            trace_ctx = jax.profiler.trace(self.config.profile_trace_dir)
        else:
            trace_ctx = contextlib.nullcontext()
        with trace_ctx, record_span("fit"):
            return self._fit_loop(x, y, epochs, batch_size, shuffle, verbose,
                                  recompile_state, epoch_offset,
                                  checkpoint_dir=checkpoint_dir,
                                  checkpoint_every_n_steps=(
                                      checkpoint_every_n_steps
                                  ),
                                  resume=resume)

    def _setup_run_health(self):
        """Install the step event log (`--metrics-dir`) and health monitor
        (`--health-policy`) for one fit call. Both are absent (None) unless
        configured, so the hot loop pays nothing by default.

        The registry and monitor persist ACROSS fit calls on this model:
        events.jsonl appends, so metrics.json and the monitor's trip
        counters must accumulate over the same stream (the keras callback
        loop calls fit once per epoch — a per-fit registry would report
        one epoch's counts against a whole run's events)."""
        cfg = self.config
        event_log = None
        monitor = None
        if cfg.metrics_dir:
            from flexflow_tpu.observability.metrics import (
                MetricsRegistry,
                StepEventLog,
            )

            if getattr(self, "_metrics_registry", None) is None:
                self._metrics_registry = MetricsRegistry()
            event_log = StepEventLog(
                cfg.metrics_dir, registry=self._metrics_registry
            )
        if cfg.health_policy not in ("", "off"):
            from flexflow_tpu.observability.health import HealthMonitor

            monitor = self.health_monitor
            if monitor is None or monitor.policy != cfg.health_policy:
                monitor = HealthMonitor(
                    cfg.health_policy, localizer=self._localize_nonfinite,
                )
        self.health_monitor = monitor
        return event_log, monitor

    def _setup_drift_monitor(self, sup):
        """Start the streaming plan-fidelity drift monitor (ISSUE 18) for
        one fit call, or return None when it cannot run: it needs
        `--drift-monitor`, a metrics dir (the event stream it tails), and
        a searched plan with a finite positive predicted step cost to
        compare against. The monitor is a daemon thread supervised
        through the fit's FaultChannel — its crashes surface as
        BackgroundFault at the next step boundary, never as a silent
        stall — and it only ever ADVISES; the compiled executable is
        untouched."""
        import math

        cfg = self.config
        if not (cfg.drift_monitor and cfg.metrics_dir):
            return None
        sp = self.search_provenance
        if not isinstance(sp, dict):
            return None
        try:
            predicted = float(sp.get("estimated_ms"))
        except (TypeError, ValueError):
            return None
        if not math.isfinite(predicted) or predicted <= 0:
            return None
        from flexflow_tpu.observability.drift import DriftMonitor

        return DriftMonitor(
            cfg.metrics_dir,
            predicted,
            seed_runtimes=sp.get("seed_runtimes"),
            band=cfg.drift_band,
            window_steps=cfg.drift_window_steps,
            run_length=cfg.drift_run_length,
            repricer=getattr(self, "_drift_research", None),
            transition_verifier=getattr(self, "_drift_transition", None),
            channel=sup.channel if sup is not None else None,
        ).start()

    def _localize_nonfinite(self, batch, label):
        """First-bad-op blame for the health monitor: replay the failing
        step un-fused over the graph the instance actually executes (the
        searched PCG when there is one, else the CG) with the live
        parameters — which under the skip_step/raise guard are still the
        pre-step values that reproduce the trip."""
        from flexflow_tpu.observability.health import localize_first_nonfinite

        inst = self.instance
        if hasattr(inst, "pcg"):
            graph, logit = inst.pcg, inst.loss_logit_tensor
        else:
            graph, logit = inst.cg, inst.logit_tensor
        return localize_first_nonfinite(
            graph, self.params, batch, logit_tensor=logit,
            label=label, loss_attrs=self.loss_attrs,
            compute_dtype=getattr(inst, "compute_dtype", None),
            # the tripped step's key: train-mode replay with the same
            # per-op folded rng, so stochastic ops (Dropout) compute the
            # same function the fused step did
            rng=getattr(self, "_last_step_rng", None),
        )

    def _record_run_health(
        self, event_log, monitor, loss, batch, label, batch_size, step_t0
    ) -> None:
        """Per-step event emission + policy enforcement (the shared
        observability.health.record_step_health wiring). Reading the stats
        scalars is the one host sync telemetry costs; it happens only when
        an event log or monitor is installed."""
        from flexflow_tpu.observability.health import record_step_health

        tokens = (
            int(np.prod(label.shape))
            if label is not None and getattr(label, "shape", None)
            else batch_size
        )
        record_step_health(
            event_log, monitor, self._step_count, loss,
            getattr(self.instance, "last_step_stats", None),
            batch=batch, label=label, tokens=tokens, step_t0=step_t0,
        )

    def _fit_loop(
        self, x, y, epochs, batch_size, shuffle, verbose, recompile_state,
        epoch_offset: int = 0, checkpoint_dir=None,
        checkpoint_every_n_steps=None, resume: bool = False,
    ) -> PerfMetrics:
        epochs = epochs or self.config.epochs
        batch_size = batch_size or self.config.batch_size
        # `fit/begin`: from here to the loop's first pull
        begin = contextlib.ExitStack()
        # everything below runs under ONE finally: a failure anywhere in
        # the setup (resume restore, metrics dir, health monitor) must
        # still retire the watchdog monitor and the checkpoint writer it
        # may already have spawned — a leaked daemon thread per retried
        # fit call adds up on a preemptible job
        sup = ckpt = event_log = drift = None
        try:
            begin.enter_context(record_span("fit/begin"))
            it = self._make_iterator(
                x, y, batch_size, shuffle=shuffle, seed_offset=epoch_offset
            )
            rng = jax.random.fold_in(
                jax.random.PRNGKey(self.config.seed), epoch_offset
            )
            sup = self._setup_supervision()
            ckpt, start_epoch, skip_batches, rng = self._setup_checkpointing(
                checkpoint_dir, checkpoint_every_n_steps, resume, it, rng,
                epoch_offset, fault_channel=sup.channel,
            )
            event_log, monitor = self._setup_run_health()
            drift = self._setup_drift_monitor(sup)
            if self.config.metrics_dir and self.search_provenance:
                # snapshot the compile-time verdicts beside the stream so
                # ffreport can render a run from its metrics dir alone
                from flexflow_tpu.observability.metrics import (
                    write_provenance,
                )

                write_provenance(
                    self.config.metrics_dir, self.search_provenance
                )
            begin.close()
            return self._fit_epochs(
                x, y, epochs, batch_size, shuffle, verbose, recompile_state,
                epoch_offset, it, rng, event_log, monitor, ckpt=ckpt,
                start_epoch=start_epoch, skip_batches=skip_batches, sup=sup,
            )
        finally:
            begin.close()
            # retire the watchdog FIRST: its deadline must not fire into
            # the (potentially slow) writer drain below
            if sup is not None:
                sup.close()
            if drift is not None:
                # stop the poller and drain the tail on this thread (step
                # events flush per line, so the final drain sees every
                # step even though event_log closes later), then pin the
                # verdict into provenance for ffreport and the caller
                drift.close()
                if isinstance(self.search_provenance, dict):
                    self.search_provenance["drift"] = drift.report()
                    if self.config.metrics_dir:
                        from flexflow_tpu.observability.metrics import (
                            write_provenance,
                        )

                        write_provenance(
                            self.config.metrics_dir, self.search_provenance
                        )
            if ckpt is not None:
                # drain the background writer BEFORE control leaves fit —
                # on a fault too, so the last due snapshot is durable
                # (idempotent with the finalize inside a failed resume)
                ckpt.finalize()
            if event_log is not None:
                event_log.close()

    def _setup_supervision(self):
        """One fit call's supervision bundle (runtime/supervisor.py): the
        fault channel background threads report into, the window watchdog
        (only when a factor is configured — `--watchdog-factor` or
        FF_TPU_WATCHDOG), and the active seeded fault schedule
        (FF_TPU_FAULT_SPEC), if any. A watchdog expiry's HangDiagnostic
        lands in the metrics JSONL stream as an `event: "hang"` line."""
        import os as _os

        from flexflow_tpu.runtime.fault import active_schedule
        from flexflow_tpu.runtime.supervisor import (
            FaultChannel,
            FitSupervision,
            WindowWatchdog,
        )

        factor = float(self.config.watchdog_factor or 0.0)
        if factor <= 0:
            env = _os.environ.get("FF_TPU_WATCHDOG", "")
            factor = float(env) if env else 0.0
        watchdog = None
        if factor > 0:
            metrics_dir = self.config.metrics_dir

            def on_hang(diag):
                if metrics_dir:
                    from flexflow_tpu.observability.metrics import (
                        append_run_event,
                    )

                    append_run_event(metrics_dir, "hang", **diag.to_dict())

            watchdog = WindowWatchdog(factor, on_hang=on_hang)
        return FitSupervision(
            channel=FaultChannel(),
            watchdog=watchdog,
            schedule=active_schedule(),
        )

    def _setup_checkpointing(
        self, checkpoint_dir, checkpoint_every_n_steps, resume, it, rng,
        epoch_offset: int = 0, fault_channel=None,
    ):
        """Build the fit call's TrainingCheckpointer (None when
        checkpointing is off) and, under resume=True, restore the latest
        snapshot: params/opt-state/step onto this model, the RNG carry, and
        the dataloader's shuffle position (permutations burnt + one-shot
        mid-epoch skip). A corrupt latest snapshot falls back to the
        newest one that verifies (runtime/integrity.py); the fallback is
        recorded in search_provenance["recovery"]["checkpoint_fallback"]
        and the metrics JSONL. Returns (ckpt, start_epoch, skip_batches,
        rng)."""
        cfg = self.config
        cdir = checkpoint_dir if checkpoint_dir is not None else cfg.checkpoint_dir
        every = (
            checkpoint_every_n_steps
            if checkpoint_every_n_steps is not None
            else cfg.checkpoint_every_n_steps
        )
        if not cdir:
            if resume:
                raise ValueError(
                    "fit(resume=True) needs checkpoint_dir= (or "
                    "config.checkpoint_dir)"
                )
            return None, 0, 0, rng
        from flexflow_tpu.runtime.checkpoint import (
            CheckpointError,
            TrainingCheckpointer,
        )

        ckpt = TrainingCheckpointer(
            cdir, every_n_steps=every,
            max_to_keep=cfg.checkpoint_max_to_keep,
            sync=cfg.checkpoint_sync,
            backend=cfg.checkpoint_backend or None,
            fault_channel=fault_channel,
        )
        start_epoch = skip_batches = 0
        if resume:
            try:
                template = {"params": self.params}
                if self.opt_state is not None:
                    template["opt_state"] = self.opt_state
                rs = ckpt.resume_state(template=template)
                if rs is not None:
                    if rs.epoch_offset != epoch_offset:
                        # the iterator and rng were seeded with THIS call's
                        # epoch_offset: resuming under a different one would
                        # burn permutations from the wrong shuffle stream —
                        # silently divergent, never bitwise
                        raise CheckpointError(
                            "snapshot was taken under epoch_offset="
                            f"{rs.epoch_offset} but fit(resume=True) was "
                            f"called with epoch_offset={epoch_offset}; "
                            "pass the original epoch_offset to resume "
                            "bitwise",
                            directory=ckpt.manager.directory,
                            step=rs.step,
                        )
                    self.params = rs.params
                    if rs.opt_state is not None:
                        self.opt_state = rs.opt_state
                    self._step_count = rs.step
                    rng = rs.rng
                    start_epoch, skip_batches = rs.epoch, rs.batch_in_epoch
                    it.advance_epochs(start_epoch)
                    it.set_resume_skip(skip_batches)
                    self._record_restore_fallback(rs.restore_report)
            except BaseException:
                # _fit_loop's finally hasn't been entered yet: retire the
                # background writer here or its daemon thread leaks one
                # queue.get-blocked thread per failed resume attempt
                ckpt.finalize()
                raise
        # execution-contract fingerprint (ISSUE 14, DET002): persist the
        # step-program contract beside the checkpoints on a fresh run,
        # verify the program about to run against it under resume=True —
        # "bitwise resume" as a checked invariant, not an empirical claim
        self._exec_contract_sync(cdir, resume)
        return ckpt, start_epoch, skip_batches, rng

    def _record_restore_fallback(self, report) -> None:
        """A resume that had to quarantine corrupt checkpoint steps and
        fall back to an older verified one records the decision — in
        search_provenance["recovery"]["checkpoint_fallback"] (beside the
        degraded-grid recovery record) and as an `event:
        "checkpoint_fallback"` line in the metrics JSONL stream."""
        if not report or not report.get("quarantined"):
            return
        if self.search_provenance is None:
            self.search_provenance = {}
        self.search_provenance.setdefault("recovery", {})[
            "checkpoint_fallback"
        ] = report
        if self.config.metrics_dir:
            from flexflow_tpu.observability.metrics import append_run_event

            append_run_event(
                self.config.metrics_dir, "checkpoint_fallback", **report
            )

    def _fit_epochs(
        self, x, y, epochs, batch_size, shuffle, verbose, recompile_state,
        epoch_offset, it, rng, event_log, monitor, ckpt=None,
        start_epoch: int = 0, skip_batches: int = 0, sup=None,
    ) -> PerfMetrics:
        from flexflow_tpu.runtime.fault import (
            inject_hang_fault,
            inject_kill_fault,
            inject_nonfinite_fault,
            inject_slow_fault,
            maybe_inject_fault,
        )

        watchdog = sup.watchdog if sup is not None else None
        start = time.perf_counter()
        num_samples = 0
        loss = None
        # metric scalars stay on device inside the loop (a float() per step
        # would block async dispatch of the donated jitted step); one
        # conversion after the final block_until_ready. The run-health hook
        # below syncs per step, but only when telemetry is installed.
        macc: Optional[Dict[str, jnp.ndarray]] = None
        throttle = _host_collective_throttle()
        epoch = start_epoch
        while epoch < epochs:
            batch_in_epoch = skip_batches if epoch == start_epoch else 0
            for batch, label in _pulls(it):
                if sup is not None:
                    batch = inject_nonfinite_fault(
                        sup.schedule, self._step_count + 1, batch
                    )
                if watchdog is not None:
                    watchdog.begin_window(self._step_count + 1, 1)
                try:
                    step_t0 = (
                        time.perf_counter()
                        if (event_log is not None or monitor is not None)
                        else None
                    )
                    rng, step_rng = jax.random.split(rng)
                    self._last_step_rng = step_rng  # for the NaN localizer
                    self.params, self.opt_state, loss, mvals = (
                        self.instance.train_step(
                            self.params, self.opt_state, batch, label,
                            step_rng,
                        )
                    )
                    prev_step = self._step_count
                    self._step_count += 1
                    if throttle is not None:
                        throttle(loss)
                    if sup is not None:
                        # seeded "slow" soft-site (ISSUE 18): the sleep
                        # lands INSIDE the timed region (before the
                        # wallclock readout below) so the drift monitor
                        # observes the injected slowdown as step time
                        inject_slow_fault(
                            sup.schedule, prev_step, self._step_count
                        )
                    if step_t0 is not None:
                        self._record_run_health(
                            event_log, monitor, loss, batch, label,
                            batch_size, step_t0,
                        )
                    if sup is not None:
                        # the simulated-hang site rides inside the armed
                        # window (a hung step never reaches the boundary)
                        inject_hang_fault(
                            sup.schedule, prev_step, self._step_count,
                            watchdog=watchdog,
                        )
                finally:
                    # disarm BEFORE the boundary work: a slow-but-healthy
                    # checkpoint commit (or teardown after a raise) must
                    # not be indistinguishable from a hang
                    if watchdog is not None:
                        watchdog.end_window(self._step_count)
                batch_in_epoch += 1
                num_samples += batch_size
                macc = (
                    mvals
                    if macc is None
                    else {k: macc[k] + v for k, v in mvals.items()}
                )
                if verbose and self.config.print_freq and (
                    self._step_count % self.config.print_freq == 0
                ):
                    print(
                        f"epoch {epoch} step {self._step_count}: "
                        f"loss {float(loss):.4f}"
                    )
                if ckpt is not None and ckpt.due(
                    prev_step, self._step_count
                ):
                    # post-step carry `rng` + dataloader cursor = a full
                    # bitwise-resume point (runtime/checkpoint.py)
                    ckpt.snapshot(
                        self._step_count, self.params, self.opt_state,
                        rng, epoch, batch_in_epoch, epoch_offset,
                    )
                if sup is not None:
                    inject_kill_fault(
                        sup.schedule, prev_step, self._step_count
                    )
                    sup.channel.raise_pending()
                maybe_inject_fault(prev_step, self._step_count)
                if recompile_state is not None:
                    from flexflow_tpu.runtime.recompile import (
                        recompile_on_condition,
                    )

                    if recompile_on_condition(self, recompile_state):
                        # the compiled step (and maybe batch size) changed:
                        # rebuild the iterator, metrics carry over
                        batch_size = self.config.batch_size
                        it = self._make_iterator(
                            x, y, batch_size, shuffle=shuffle,
                            seed_offset=epoch_offset,
                        )
                        break
            # a recompile ends the current epoch (the rebuilt iterator can't
            # resume mid-epoch at a new batch size); training continues from
            # the next epoch under the new step, so batches are never
            # replayed and a persistent trigger cannot livelock fit()
            epoch += 1
        with record_span("fit/end"):
            if loss is not None:
                jax.block_until_ready(loss)
            elapsed = time.perf_counter() - start
            perf = (
                _perf_from_metric_values(macc)
                if macc is not None
                else PerfMetrics()
            )
            _publish_routing(self.instance, macc)
            _publish_loss_terms(self.instance, macc)
        if verbose:
            print(
                f"ELAPSED TIME = {elapsed:.4f}s, "
                f"THROUGHPUT = {num_samples / max(elapsed, 1e-9):.2f} samples/s"
            )
        return perf

    def set_learning_rate(self, lr: float) -> None:
        """Update the optimizer's learning rate mid-training (reference:
        Optimizer::set_learning_rate, driven by the keras
        LearningRateScheduler callback). Re-jits the step on next use."""
        import dataclasses

        attrs = self.optimizer_attrs
        assert attrs is not None, "compile the model before setting the lr"
        field = "lr" if hasattr(attrs, "lr") else "alpha"
        if getattr(attrs, field) == lr:
            return  # unchanged: keep the jitted step (no retrace)
        self.optimizer_attrs = dataclasses.replace(attrs, **{field: lr})
        if self.instance is not None:
            if hasattr(self.instance, "set_learning_rate"):
                # submesh backend: attrs baked into cached per-island
                # update programs
                self.instance.set_learning_rate(self.optimizer_attrs)
            else:
                self.instance.optimizer_attrs = self.optimizer_attrs
                self.instance._jit_step = None

    def eval(self, x=None, y=None, batch_size: Optional[int] = None) -> PerfMetrics:
        """Forward-only metric evaluation (reference FFModel.eval)."""
        from flexflow_tpu.kernels.metrics import compute_metrics

        assert self.instance is not None, "call compile() first"
        batch_size = batch_size or self.config.batch_size
        it = self._make_iterator(x, y, batch_size, shuffle=False)
        metrics = self.metrics or frozenset({"accuracy"})
        perf = PerfMetrics()
        for batch, label in it:
            logit = self.instance.forward(self.params, batch)
            mvals = compute_metrics(metrics, logit, label)
            perf.update(_perf_from_metric_values(mvals))
        return perf

    # ------------------------------------------------------------------
    # stepped execution (reference forward/backward/update/zero_gradients)
    # ------------------------------------------------------------------

    def _ensure_backing(self) -> LocalTrainingBacking:
        if self._backing is None:
            self._backing = LocalTrainingBacking(
                self.cg, profiling=self.config.profiling
            )
            if self.params is not None:
                self._backing.params = dict(self.params)
            else:
                self._backing.execute_init(self.config.seed)
                self.params = self._backing.params
        return self._backing

    def init_operators(self) -> None:
        self._ensure_backing()

    def forward(self, inputs: Optional[Dict[str, np.ndarray]] = None) -> np.ndarray:
        b = self._ensure_backing()
        assert inputs is not None, "stepped forward needs an inputs dict"
        b.execute_forward({k: jnp.asarray(v) for k, v in inputs.items()})
        # return the last op's output
        sink = _find_sink_output(self.cg)
        return np.asarray(b.env[sink])

    def zero_gradients(self) -> None:
        b = self._ensure_backing()
        b.grad_env = {}
        b.param_grads = {}

    def backward(self, label: Optional[np.ndarray] = None) -> None:
        """Loss backward + reverse-topo op backward (reference
        loss_functions.cc:33-52 backward_invocation then per-op bwd)."""
        from flexflow_tpu.kernels.loss import loss_forward

        b = self._ensure_backing()
        sink = _find_sink_output(self.cg)
        logit = b.env[sink]
        assert label is not None, "stepped backward needs the label batch"
        lbl = jnp.asarray(label, self._label_dtype)
        grad = jax.grad(lambda lg: loss_forward(self.loss_attrs, lg, lbl))(logit)
        b.execute_backward({sink: grad})

    def update(self) -> None:
        b = self._ensure_backing()
        self.opt_state = b.execute_update(self.optimizer_attrs, self.opt_state)
        self.params = b.params

    # ------------------------------------------------------------------
    # checkpoint / resume (new capability vs the reference, SURVEY.md §5)
    # ------------------------------------------------------------------

    def save_checkpoint(self, directory: str, max_to_keep: int = 3) -> str:
        from flexflow_tpu.runtime.checkpoint import CheckpointManager

        assert self.params is not None, "compile() before checkpointing"
        mgr = CheckpointManager(directory, max_to_keep=max_to_keep)
        return mgr.save(
            self._step_count, self.params, self.opt_state,
            extra={"seed": self.config.seed},
        )

    def load_checkpoint(self, directory: str, step: Optional[int] = None) -> int:
        from flexflow_tpu.runtime.checkpoint import CheckpointManager

        assert self.params is not None, "compile() before restoring"
        mgr = CheckpointManager(directory)
        template = {"params": self.params}
        if self.opt_state is not None:
            template["opt_state"] = self.opt_state
        step, params, opt_state, _ = mgr.restore(step, template=template)
        self.params = params
        if opt_state is not None:
            self.opt_state = opt_state
        self._step_count = step
        if self._backing is not None:
            self._backing.params = dict(params)
        return step


def _find_aux_outputs(graph) -> List[DataflowOutput]:
    """Aux-loss outputs, found structurally (so they survive substitutions
    that rebuild node identity): any secondary output of an Experts op with
    an auxiliary coefficient (lambda_bal, lambda_z) is that scalar, and so
    is the output of a loss node (`LabelCrossEntropyAttrs`,
    `MeanLossAttrs`)."""
    from flexflow_tpu.op_attrs.ops import (
        ExpertsAttrs,
        LabelCrossEntropyAttrs,
        MeanLossAttrs,
    )

    aux = []
    for n in graph.topological_ordering():
        attrs = graph.op_attrs(n)
        if isinstance(attrs, ExpertsAttrs) and attrs.has_aux:
            aux.extend(graph.outputs_of(n)[1:])
        elif isinstance(attrs, (LabelCrossEntropyAttrs, MeanLossAttrs)):
            aux.extend(graph.outputs_of(n))
    return aux


def _find_sink_output(graph) -> DataflowOutput:
    """The model output: the unique dataflow output nobody consumes
    (aux-loss outputs are consumed by the training loss, not the graph,
    and are excluded here)."""
    consumed = set()
    for n in graph.topological_ordering():
        consumed.update(graph.inputs_of(n))
    consumed.update(_find_aux_outputs(graph))
    sinks = [
        o
        for n in graph.topological_ordering()
        for o in graph.outputs_of(n)
        if o not in consumed
        and not isinstance(graph.op_attrs(n), (InputAttrs, WeightAttrs))
    ]
    assert len(sinks) == 1, f"expected one model output, found {len(sinks)}"
    return sinks[0]


def _pulls(batches):
    """`for item in batches`, each pull under a `fit/next_batch` span: the
    fit loop's wait for its input (an epoch's shuffle is drawn by its first
    pull; the pull that finds the epoch over is a span too)."""
    batches = iter(batches)
    done = object()
    while True:
        with record_span("fit/next_batch"):
            item = next(batches, done)
        if item is done:
            return
        yield item


def _host_collective_throttle():
    """None, or (on the CPU backend with more than one device) a function
    to call with each step's loss that waits for the step before last.

    XLA's CPU backend runs every device of a multi-device program on
    threads of one pool and ABORTS THE PROCESS when a collective's
    participants have not all arrived within 40 s (`rendezvous.cc`:
    "Termination timeout ... exceeded"). The fit loop dispatches steps
    asynchronously and never looks at a result, so hundreds of steps queue;
    on a loaded host (the test suite: six workers of eight virtual devices
    each) the pool's threads sit in the rendezvous of later steps while an
    earlier step's last participant waits for a thread, and two runs in ten
    died that way (PR 32: ten concurrent copies of
    `test_ffmodel_api.py::test_fit_reduces_loss`, none of ten with this
    wait). Two steps in flight keep the devices busy and the queue short.
    On an accelerator the queue is the device's own, nothing can starve,
    and the loop is left as it was."""
    if jax.default_backend() != "cpu" or jax.device_count() < 2:
        return None
    pending = []

    def wait_for_step_before_last(loss):
        pending.append(loss)
        if len(pending) > 2:
            jax.block_until_ready(pending.pop(0))

    return wait_for_step_before_last


def _publish_routing(instance, mvals) -> None:
    """Hand a fit call's summed routing counters (observability/routing.py)
    to where a reader finds them; a graph without a held expert share has
    none."""
    from flexflow_tpu.observability import routing

    if mvals is None or routing.ROUTING_KEY not in mvals:
        return
    graph = getattr(instance, "pcg", None) or instance.cg
    routing.publish_recorded(
        mvals[routing.ROUTING_KEY], routing.held_nodes(graph)
    )


def _publish_loss_terms(instance, mvals) -> None:
    """Hand a fit call's summed loss terms (observability/trace.py) to where
    a reader finds them; a graph with one loss has none."""
    from flexflow_tpu.observability import trace

    names = getattr(instance, "loss_term_names", None)
    if mvals is None or not names or trace.LOSS_TERMS_KEY not in mvals:
        return
    trace.publish_loss_terms(names, mvals[trace.LOSS_TERMS_KEY])


def _perf_from_metric_values(mvals: Dict[str, jnp.ndarray]) -> PerfMetrics:
    p = PerfMetrics()
    for k, v in mvals.items():
        if hasattr(p, k):
            cur = getattr(p, k)
            setattr(p, k, type(cur)(cur + (int(v) if isinstance(cur, int) else float(v))))
    return p
