"""Eager ComputationGraph builder with automatic weight creation.

Reference: lib/pcg/include/pcg/computation_graph_builder.h:10-300 (~50-method
API). Each op method infers output shapes via op_attrs, creates weight nodes
automatically (roles from get_incoming_tensor_roles), and returns the output
tensor(s) as DataflowOutput handles.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from flexflow_tpu.op_attrs.activation import Activation
from flexflow_tpu.op_attrs.core import (
    OpAttrs,
    get_output_shapes,
    get_default_weight_initializers,
    get_weight_shapes,
)
from flexflow_tpu.op_attrs.datatype import DataType
from flexflow_tpu.op_attrs.tensor_shape import TensorShape
from flexflow_tpu.op_attrs.ops import (
    BatchMatmulAttrs,
    BatchNormAttrs,
    BroadcastAttrs,
    CastAttrs,
    ConcatAttrs,
    Conv2DAttrs,
    DropoutAttrs,
    ElementBinaryAttrs,
    ElementBinaryOpType,
    ElementUnaryAttrs,
    ElementUnaryOpType,
    EmbeddingAttrs,
    AggregateSpec,
    FlatAttrs,
    GatherAttrs,
    InputAttrs,
    LayerNormAttrs,
    RMSNormAttrs,
    LinearAttrs,
    MultiHeadAttentionAttrs,
    NoopAttrs,
    Pool2DAttrs,
    PoolOp,
    ReduceAttrs,
    ReshapeAttrs,
    ReverseAttrs,
    SoftmaxAttrs,
    SplitAttrs,
    TopKAttrs,
    TransposeAttrs,
    WeightAttrs,
    YarnScaling,
)
from flexflow_tpu.op_attrs.ops.shape_ops import ReduceOpType
from flexflow_tpu.pcg.computation_graph import (
    ComputationGraph,
    LayerAttrs,
    TensorAttrs,
)
from flexflow_tpu.pcg.initializer import (
    GlorotUniformAttrs,
    InitializerAttrs,
    ZeroInitializerAttrs,
)
from flexflow_tpu.utils.graph import DataflowOutput

Tensor = DataflowOutput


class SharedBlock:
    """Layers applied again on the weights their first application made.

        block = b.shared_block()
        for t in range(4):
            with block:
                h = stack(h)        # the same builder calls every time

    The first `with` records, in order, every layer built inside (its attrs,
    its name and its weights' shapes) and every weight created; each later
    one hands those weights back to the same calls through `reuse_weights`
    and refuses a call that differs from the recorded one. The graph stays a
    DAG: an application's nodes are new nodes, named `<name>#<application>`
    (`attn3#2`: layer `attn3`, second pass), reading the weight nodes
    `<name>.weight<i>` of the first. A scope and not a method that takes the
    stack as a function: what a pass hands on (the stream, its logits, its
    gate) stays ordinary Python between the `with`s, and `reuse_weights`,
    which it is built on, is a scope too."""

    def __init__(self, builder: "ComputationGraphBuilder") -> None:
        self.builder = builder
        self.applications = 0
        self.weights: List[Tensor] = []
        self._layers: List[tuple] = []
        self._next = 0

    def check(self, attrs, name, weight_shapes) -> None:
        """One layer of the application being built: recorded on the first,
        held to the record on every later one."""
        call = (attrs, name, tuple(ws.dims for ws in weight_shapes))
        if self.applications == 1:
            self._layers.append(call)
            return
        want = self._layers[self._next] if self._next < len(self._layers) else None
        if call != want:
            raise ValueError(
                f"application {self.applications} of a shared block builds "
                f"{call} as its layer {self._next}, the first built {want}: "
                "a block is applied again only as it was built, on inputs "
                "of the same shape"
            )
        self._next += 1

    def __enter__(self) -> "SharedBlock":
        b = self.builder
        assert b._block is None, "shared blocks do not nest"
        self.applications += 1
        self._next = 0
        self._mark = len(b.weight_log)
        self._outer_queue = b._reuse_queue
        b._block = self
        if self.applications > 1:
            b._reuse_queue = list(self.weights)
        return self

    def __exit__(self, *exc) -> bool:
        b = self.builder
        left, b._reuse_queue = b._reuse_queue, self._outer_queue
        b._block = None
        if self.applications == 1:
            self.weights = b.weight_log[self._mark:]
        elif exc[0] is None and (left or self._next != len(self._layers)):
            raise ValueError(
                f"application {self.applications} of a shared block built "
                f"{self._next} layers and left {len(left)} weight(s) "
                f"unbound, the first built {len(self._layers)}"
            )
        return False


class ComputationGraphBuilder:
    def __init__(self) -> None:
        self.graph = ComputationGraph()
        # scalar outputs training should add to the loss (e.g. the Experts
        # op's load-balance term); training instances read this via their
        # aux_loss_tensors argument
        self.aux_loss_tensors: List[Tensor] = []
        # every weight tensor ever created, in creation order: frontends
        # slice this log to capture which weights one layer build produced
        # (keras weight sharing re-binds them via reuse_weights)
        self.weight_log: List[Tensor] = []
        self._reuse_queue: Optional[List[Tensor]] = None
        # the `SharedBlock` whose application is being built, if any
        self._block: Optional["SharedBlock"] = None
        # the op nodes of the `recompute` scope being built, if any
        self._recompute: Optional[List] = None

    # -- low-level --------------------------------------------------------

    def reuse_weights(self, weights: Sequence[Tensor]):
        """Context manager: ops built inside BIND the given weight tensors
        (in order) instead of creating new ones — the keras functional
        API's shared-layer contract (a layer applied at several call sites
        owns ONE set of parameters; gradients accumulate through the fanned
        -out weight node). Reference:
        python/flexflow/keras/models/base_model.py functional reuse.
        Inside another such scope (a `shared_block`'s later application) the
        inner list is bound first and the outer one goes on after it."""
        import contextlib

        @contextlib.contextmanager
        def scope():
            outer = self._reuse_queue
            self._reuse_queue = list(weights)
            try:
                yield
                assert not self._reuse_queue, (
                    f"{len(self._reuse_queue)} shared weight(s) left unbound"
                )
            finally:
                self._reuse_queue = outer

        return scope()

    def recompute(self):
        """Context manager: the op nodes built inside are ONE group whose
        forward results are not kept for the backward pass but computed
        again there from the group's inputs (activation checkpointing: a
        layer application of a looped model at 8,192 positions keeps a dozen
        [tokens, hidden] tensors, four passes keep four dozen a layer). What
        crosses the group's border is kept; weights are inputs of the
        group. The one-chip and data-parallel backends honour it
        (`forward_interpreter`); the lift to a PCG refuses a graph with a
        group, so a searched plan never drops one silently."""
        import contextlib

        @contextlib.contextmanager
        def scope():
            assert self._recompute is None, "recompute scopes do not nest"
            self._recompute = []
            try:
                yield
            finally:
                nodes, self._recompute = self._recompute, None
            if nodes:
                self.graph.recompute_groups = (
                    *self.graph.recompute_groups, tuple(nodes)
                )

        return scope()

    def shared_block(self) -> "SharedBlock":
        """A stack of layers that is applied more than once on ONE set of
        weights (a looped model's pass): every `with block:` is one
        application. See `SharedBlock`."""
        return SharedBlock(self)

    def add_layer(
        self,
        attrs: OpAttrs,
        inputs: Sequence[Tensor],
        weight_initializers: Sequence[Optional[InitializerAttrs]] = (),
        name: Optional[str] = None,
    ) -> List[Tensor]:
        """Create weight nodes for the op (if any), then the op node itself.
        Inside a reuse_weights scope, weight tensors are taken from the
        scope instead of created."""
        input_shapes = [self.graph.tensor_shape(t) for t in inputs]
        weight_shapes = get_weight_shapes(attrs, input_shapes)
        op_defaults = get_default_weight_initializers(attrs, len(weight_shapes))
        block = self._block
        if block is not None:
            block.check(attrs, name, weight_shapes)
        weight_tensors: List[Tensor] = []
        for i, ws in enumerate(weight_shapes):
            if self._reuse_queue is not None:
                assert self._reuse_queue, "shared-weight queue exhausted"
                w = self._reuse_queue.pop(0)
                have = self.graph.tensor_shape(w)
                assert have.dims == ws.dims, (
                    f"shared weight {i} has shape {have.dims}, op needs "
                    f"{ws.dims} — a layer can only be reused on inputs of "
                    "the same shape"
                )
                weight_tensors.append(w)
                continue
            init = (
                weight_initializers[i]
                if i < len(weight_initializers) and weight_initializers[i] is not None
                else op_defaults[i]
                or (GlorotUniformAttrs() if len(ws.dims) > 1 else ZeroInitializerAttrs())
            )
            # a weight is named after its layer, not after the application
            # that happened to create it
            wname = f"{name}.weight{i}" if name else None
            _, (w,) = self.graph.add_node(
                LayerAttrs(WeightAttrs(ws), wname),
                [],
                [TensorAttrs(ws, create_grad=True, initializer=init)],
            )
            weight_tensors.append(w)
            self.weight_log.append(w)
        out_shapes = get_output_shapes(attrs, input_shapes)
        if block is not None and name is not None:
            name = f"{name}#{block.applications}"
        node, outs = self.graph.add_node(
            LayerAttrs(attrs, name),
            list(inputs) + weight_tensors,
            [TensorAttrs(s) for s in out_shapes],
        )
        if self._recompute is not None:
            self._recompute.append(node)
        return outs

    # -- inputs / weights -------------------------------------------------

    def create_input(
        self,
        dims: Sequence[int],
        dtype: DataType = DataType.FLOAT,
        name: Optional[str] = None,
    ) -> Tensor:
        shape = TensorShape(tuple(dims), dtype)
        _, (t,) = self.graph.add_node(
            LayerAttrs(InputAttrs(shape), name),
            [],
            [TensorAttrs(shape, create_grad=False)],
        )
        return t

    def create_weight(
        self,
        dims: Sequence[int],
        dtype: DataType = DataType.FLOAT,
        initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        shape = TensorShape(tuple(dims), dtype)
        init = initializer or GlorotUniformAttrs()
        _, (t,) = self.graph.add_node(
            LayerAttrs(WeightAttrs(shape), name),
            [],
            [TensorAttrs(shape, create_grad=True, initializer=init)],
        )
        return t

    # -- dense / embedding / attention ------------------------------------

    def dense(
        self,
        input: Tensor,
        out_channels: int,
        activation: Optional[Activation] = None,
        use_bias: bool = True,
        dtype: Optional[DataType] = None,
        kernel_initializer: Optional[InitializerAttrs] = None,
        bias_initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        attrs = LinearAttrs(
            out_channels=out_channels,
            use_bias=use_bias,
            dtype=dtype or self.graph.tensor_shape(input).dtype,
            activation=activation,
        )
        (out,) = self.add_layer(
            attrs, [input], [kernel_initializer, bias_initializer], name
        )
        return out

    def tied_dense(
        self, input: Tensor, weight: Tensor, name: Optional[str] = None
    ) -> Tensor:
        """x W^T on ANOTHER node's weight W [out, in]: a tied head on the
        embedding's [rows, hidden] matrix (`tie_word_embeddings`). One
        weight node, two readers; the gradients sum through the fan-out."""
        attrs = LinearAttrs(
            out_channels=self.graph.tensor_shape(weight).dims[0],
            use_bias=False, dtype=self.graph.tensor_shape(input).dtype,
            weight_transposed=True,
        )
        with self.reuse_weights([weight]):
            (out,) = self.add_layer(attrs, [input], [], name)
        return out

    def embedding(
        self,
        input: Tensor,
        num_entries: int,
        out_channels: int,
        aggr: AggregateSpec = AggregateSpec.NONE,
        dtype: DataType = DataType.FLOAT,
        kernel_initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        attrs = EmbeddingAttrs(num_entries, out_channels, aggr, dtype)
        (out,) = self.add_layer(attrs, [input], [kernel_initializer], name)
        return out

    def multihead_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        kdim: int = 0,
        vdim: int = 0,
        dropout: float = 0.0,
        bias: bool = False,
        add_bias_kv: bool = False,
        add_zero_attn: bool = False,
        initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
        causal: bool = False,
        rope_theta: Optional[float] = None,
        qk_norm_eps: Optional[float] = None,
        num_kv_heads: Optional[int] = None,
        kv_latent_rank: Optional[int] = None,
        shared_key_dim: int = 0,
        kv_latent_norm_eps: float = 1e-5,
        qk_norm_per_head: bool = False,
        rotary_dim: Optional[int] = None,
        output_gate: bool = False,
        qk_norm_zero_centered: bool = False,
        q_latent_rank: Optional[int] = None,
        q_latent_norm_eps: float = 1e-5,
        rope_interleaved: bool = False,
        window: Optional[int] = None,
        rope_scaling: Optional[YarnScaling] = None,
        softmax_scale: Optional[float] = None,
    ) -> Tensor:
        """`causal`, `rope_theta` (rotary positions 0..s-1 on q and k) and
        `qk_norm_eps` (RMS norm of the projected q and k over all heads'
        features, two gain weights; with `qk_norm_per_head` over each head's
        own features, the gains one head wide) are what a decoder adds; a
        causal node is a `RingAttentionAttrs`, the program's causal attention.
        `num_kv_heads` fewer than `num_heads` is grouped-query attention.
        `kv_latent_rank` is latent attention: keys and values from one
        normed low-rank row, the last `shared_key_dim` of a key's `kdim`
        columns one slice for all heads (`MultiHeadAttentionAttrs`).
        `rotary_dim` turns only a head's first columns, `output_gate` puts a
        sigmoid gate from the query projection on the context, and
        `qk_norm_zero_centered` makes the QK-norm gains 1 + w. On latent
        attention `q_latent_rank` gives the query a normed low-rank row of
        its own, and `rope_theta` turns the shared key slice and each query
        head's last `shared_key_dim` columns, pairs (2j, 2j + 1) with
        `rope_interleaved`. On a plain causal node (grouped or equal heads)
        `window` is the keys a query sees, itself included (a sliding
        window: a band in the causal tile kernels, a mask on XLA's
        attention, an error on a route with neither), and `rope_scaling` a
        `YarnScaling` beside `rope_theta`: the node's own frequencies and
        amplitude, so that two layers of one graph turn differently.
        `softmax_scale` is what a plain node's scores are multiplied by where
        that is not kdim ** -0.5 (a muP model's `attention_multiplier`)."""
        fields = (
            embed_dim, num_heads, kdim, vdim, dropout, bias, add_bias_kv,
            add_zero_attn, rope_theta, qk_norm_eps, num_kv_heads,
            kv_latent_rank, shared_key_dim, kv_latent_norm_eps,
            qk_norm_per_head, rotary_dim, output_gate, qk_norm_zero_centered,
            q_latent_rank, q_latent_norm_eps, rope_interleaved,
        )
        if causal:
            from flexflow_tpu.op_attrs.ops import RingAttentionAttrs

            attrs = RingAttentionAttrs(
                *fields, window=window, rope_scaling=rope_scaling,
                softmax_scale=softmax_scale, causal=True,
            )
        else:
            attrs = MultiHeadAttentionAttrs(
                *fields, window=window, rope_scaling=rope_scaling,
                softmax_scale=softmax_scale,
            )
        (out,) = self.add_layer(attrs, [query, key, value], [initializer], name)
        return out

    def differential_attention(
        self,
        query: Tensor,
        key: Tensor,
        value: Tensor,
        embed_dim: int,
        num_heads: int,
        num_kv_heads: int,
        kdim: int,
        vdim: int,
        lambda_init: float,
        window: Optional[int] = None,
        bias: bool = True,
        norm_eps: float = 1e-5,
        kv_outputs: bool = False,
        external_kv: bool = False,
        initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
    ) -> List[Tensor]:
        """Causal differential attention (`MultiHeadAttentionAttrs.
        differential`): `num_heads` query heads of `kdim` in pairs, two
        softmax maps over one 2 * `vdim`-wide value, a learned scalar between
        them and an RMS norm a head after. `window` keys a query sees (a
        sliding window). With `kv_outputs` the result is [out, keys, values]
        as projected; with `external_kv`, `key` and `value` ARE another
        node's such outputs and the node holds no key or value weight."""
        from flexflow_tpu.op_attrs.ops import RingAttentionAttrs

        attrs = RingAttentionAttrs(
            embed_dim, num_heads, kdim, vdim, bias=bias,
            num_kv_heads=num_kv_heads, differential=True,
            lambda_init=lambda_init, diff_norm_eps=norm_eps, window=window,
            kv_outputs=kv_outputs, external_kv=external_kv, causal=True,
        )
        return self.add_layer(attrs, [query, key, value], [initializer], name)

    # -- conv family ------------------------------------------------------

    def conv2d(
        self,
        input: Tensor,
        out_channels: int,
        kernel: Tuple[int, int],
        stride: Tuple[int, int] = (1, 1),
        padding: Tuple[int, int] = (0, 0),
        groups: int = 1,
        activation: Optional[Activation] = None,
        use_bias: bool = True,
        kernel_initializer: Optional[InitializerAttrs] = None,
        bias_initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        attrs = Conv2DAttrs(
            out_channels, kernel[0], kernel[1], stride[0], stride[1],
            padding[0], padding[1], groups, activation, use_bias,
        )
        (out,) = self.add_layer(
            attrs, [input], [kernel_initializer, bias_initializer], name
        )
        return out

    def pool2d(
        self,
        input: Tensor,
        kernel: Tuple[int, int],
        stride: Tuple[int, int] = (1, 1),
        padding: Tuple[int, int] = (0, 0),
        pool_type: PoolOp = PoolOp.MAX,
        activation: Optional[Activation] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        attrs = Pool2DAttrs(
            kernel[0], kernel[1], stride[0], stride[1], padding[0], padding[1],
            pool_type, activation,
        )
        (out,) = self.add_layer(attrs, [input], [], name)
        return out

    def flat(self, input: Tensor, name: Optional[str] = None) -> Tensor:
        (out,) = self.add_layer(FlatAttrs(), [input], [], name)
        return out

    def batch_norm(
        self, input: Tensor, relu: bool = False, affine: bool = True,
        eps: float = 1e-5, momentum: float = 0.1, name: Optional[str] = None,
    ) -> Tensor:
        (out,) = self.add_layer(BatchNormAttrs(relu, affine, eps, momentum), [input], [], name)
        return out

    # -- norms / regularization -------------------------------------------

    def layer_norm(
        self,
        input: Tensor,
        axes: Sequence[int],
        elementwise_affine: bool = True,
        eps: float = 1e-5,
        name: Optional[str] = None,
    ) -> Tensor:
        nd = self.graph.tensor_shape(input).num_dims
        attrs = LayerNormAttrs(
            tuple(a % nd for a in axes), elementwise_affine, eps
        )
        (out,) = self.add_layer(attrs, [input], [], name)
        return out

    def rms_norm(
        self, input: Tensor, eps: float = 1e-5, name: Optional[str] = None,
        zero_centered: bool = False,
    ) -> Tensor:
        """RMS norm over the last dim with a gain (no mean, no shift);
        `zero_centered`: the gain is 1 + w, w from zero."""
        (out,) = self.add_layer(
            RMSNormAttrs(eps, zero_centered), [input], [], name
        )
        return out

    def softmax(self, input: Tensor, dim: int = -1, name: Optional[str] = None) -> Tensor:
        (out,) = self.add_layer(SoftmaxAttrs(dim), [input], [], name)
        return out

    def dropout(self, input: Tensor, rate: float, seed: int = 0, name: Optional[str] = None) -> Tensor:
        (out,) = self.add_layer(DropoutAttrs(rate, seed), [input], [], name)
        return out

    # -- elementwise ------------------------------------------------------

    def _unary(self, op: ElementUnaryOpType, input: Tensor, scalar=None, name=None) -> Tensor:
        (out,) = self.add_layer(ElementUnaryAttrs(op, scalar), [input], [], name)
        return out

    def exp(self, x, name=None):
        return self._unary(ElementUnaryOpType.EXP, x, name=name)

    def log(self, x, name=None):
        return self._unary(ElementUnaryOpType.LOG, x, name=name)

    def sin(self, x, name=None):
        return self._unary(ElementUnaryOpType.SIN, x, name=name)

    def cos(self, x, name=None):
        return self._unary(ElementUnaryOpType.COS, x, name=name)

    def relu(self, x, name=None):
        return self._unary(ElementUnaryOpType.RELU, x, name=name)

    def sigmoid(self, x, name=None):
        return self._unary(ElementUnaryOpType.SIGMOID, x, name=name)

    def tanh(self, x, name=None):
        return self._unary(ElementUnaryOpType.TANH, x, name=name)

    def gelu(self, x, name=None):
        return self._unary(ElementUnaryOpType.GELU, x, name=name)

    def silu(self, x, name=None):
        return self._unary(ElementUnaryOpType.SILU, x, name=name)

    def elu(self, x, name=None):
        return self._unary(ElementUnaryOpType.ELU, x, name=name)

    def rsqrt(self, x, name=None):
        return self._unary(ElementUnaryOpType.RSQRT, x, name=name)

    def sqrt(self, x, name=None):
        return self._unary(ElementUnaryOpType.SQRT, x, name=name)

    def identity(self, x, name=None):
        return self._unary(ElementUnaryOpType.IDENTITY, x, name=name)

    def scalar_multiply(self, x, scalar: float, name=None):
        return self._unary(ElementUnaryOpType.SCALAR_MULTIPLY, x, scalar, name)

    def scalar_add(self, x, scalar: float, name=None):
        return self._unary(ElementUnaryOpType.SCALAR_ADD, x, scalar, name)

    def scalar_sub(self, x, scalar: float, name=None):
        return self._unary(ElementUnaryOpType.SCALAR_SUB, x, scalar, name)

    def scalar_truediv(self, x, scalar: float, name=None):
        return self._unary(ElementUnaryOpType.SCALAR_TRUE_DIV, x, scalar, name)

    def pow(self, x, exponent: float, name=None):
        return self._unary(ElementUnaryOpType.POW, x, exponent, name)

    def _binary(self, op: ElementBinaryOpType, a: Tensor, b: Tensor, name=None) -> Tensor:
        a, b = self._broadcast_align(a, b)
        (out,) = self.add_layer(ElementBinaryAttrs(op), [a, b], [], name)
        return out

    def _broadcast_align(self, a: Tensor, b: Tensor) -> Tuple[Tensor, Tensor]:
        """Insert Broadcast ops when shapes differ (reference: builder's
        broadcast insertion)."""
        sa = self.graph.tensor_shape(a)
        sb = self.graph.tensor_shape(b)
        if sa.dims == sb.dims:
            return a, b
        target = tuple(
            int(d) for d in np.broadcast_shapes(sa.dims, sb.dims)
        )
        if sa.dims != target:
            (a,) = self.add_layer(BroadcastAttrs(target), [a], [])
        if sb.dims != target:
            (b,) = self.add_layer(BroadcastAttrs(target), [b], [])
        return a, b

    def add(self, a, b, name=None):
        return self._binary(ElementBinaryOpType.ADD, a, b, name)

    def subtract(self, a, b, name=None):
        return self._binary(ElementBinaryOpType.SUB, a, b, name)

    def multiply(self, a, b, name=None):
        return self._binary(ElementBinaryOpType.MUL, a, b, name)

    def divide(self, a, b, name=None):
        return self._binary(ElementBinaryOpType.DIV, a, b, name)

    def max(self, a, b, name=None):
        return self._binary(ElementBinaryOpType.MAX, a, b, name)

    def min(self, a, b, name=None):
        return self._binary(ElementBinaryOpType.MIN, a, b, name)

    # -- shape ops --------------------------------------------------------

    def cast(self, input: Tensor, dtype: DataType, name=None) -> Tensor:
        (out,) = self.add_layer(CastAttrs(dtype), [input], [], name)
        return out

    def broadcast(self, input: Tensor, target_dims: Sequence[int], name=None) -> Tensor:
        (out,) = self.add_layer(BroadcastAttrs(tuple(target_dims)), [input], [], name)
        return out

    def batch_matmul(self, a: Tensor, b: Tensor, name=None) -> Tensor:
        (out,) = self.add_layer(BatchMatmulAttrs(), [a, b], [], name)
        return out

    def concat(self, tensors: Sequence[Tensor], axis: int, name=None) -> Tensor:
        (out,) = self.add_layer(ConcatAttrs(axis), list(tensors), [], name)
        return out

    def stack(self, tensors: Sequence[Tensor], name=None) -> Tensor:
        """Stack same-shaped tensors along a new leading axis (branch
        stacking entry; see compiler/branch_stacking.py)."""
        from flexflow_tpu.op_attrs.ops import StackAttrs

        (out,) = self.add_layer(StackAttrs(), list(tensors), [], name)
        return out

    def split(self, input: Tensor, sizes: Sequence[int], axis: int, name=None) -> List[Tensor]:
        return self.add_layer(SplitAttrs(tuple(sizes), axis), [input], [], name)

    def reshape(self, input: Tensor, shape: Sequence[int], name=None) -> Tensor:
        (out,) = self.add_layer(ReshapeAttrs(tuple(shape)), [input], [], name)
        return out

    def transpose(self, input: Tensor, perm: Sequence[int], name=None) -> Tensor:
        (out,) = self.add_layer(TransposeAttrs(tuple(perm)), [input], [], name)
        return out

    def reverse(self, input: Tensor, axis: int, name=None) -> Tensor:
        (out,) = self.add_layer(ReverseAttrs(axis), [input], [], name)
        return out

    def gather(self, input: Tensor, index: Tensor, dim: int, name=None) -> Tensor:
        (out,) = self.add_layer(GatherAttrs(dim), [input, index], [], name)
        return out

    def top_k(self, input: Tensor, k: int, sorted: bool = True, name=None) -> Tuple[Tensor, Tensor]:
        values, indices = self.add_layer(TopKAttrs(k, sorted), [input], [], name)
        return values, indices

    def reduce_sum(self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name=None) -> Tensor:
        (out,) = self.add_layer(
            ReduceAttrs(ReduceOpType.SUM, tuple(axes), keepdims), [input], [], name
        )
        return out

    def reduce_mean(self, input: Tensor, axes: Sequence[int], keepdims: bool = False, name=None) -> Tensor:
        (out,) = self.add_layer(
            ReduceAttrs(ReduceOpType.MEAN, tuple(axes), keepdims), [input], [], name
        )
        return out

    def noop(self, input: Tensor, name=None) -> Tensor:
        (out,) = self.add_layer(NoopAttrs(), [input], [], name)
        return out

    # -- mixture of experts (reference examples/cpp/mixture_of_experts) ---

    def group_by(
        self, data: Tensor, assign: Tensor, n_experts: int, alpha: float = 1.0, name=None
    ) -> List[Tensor]:
        from flexflow_tpu.op_attrs.ops.moe import GroupByAttrs

        return self.add_layer(GroupByAttrs(n_experts, alpha), [data, assign], [], name)

    def aggregate(
        self,
        gate_preds: Tensor,
        gate_assign: Tensor,
        exp_preds: Sequence[Tensor],
        name=None,
    ) -> Tensor:
        from flexflow_tpu.op_attrs.ops.moe import AggregateAttrs

        (out,) = self.add_layer(
            AggregateAttrs(len(exp_preds)),
            [gate_preds, gate_assign, *exp_preds],
            [],
            name,
        )
        return out

    def experts(
        self,
        input: Tensor,
        num_experts: int,
        num_select: int,
        hidden_size: int,
        out_channels: Optional[int] = None,
        activation: Optional[Activation] = Activation.RELU,
        capacity_factor: Optional[float] = 2.0,
        use_bias: bool = True,
        lambda_bal: float = 0.0,
        name=None,
        gated: bool = False,
        renormalize: bool = True,
        lambda_z: float = 0.0,
        initializer: Optional[InitializerAttrs] = None,
        scoring: str = "softmax",
        selection_bias: bool = False,
        routed_scale: float = 1.0,
        shared_hidden_size: int = 0,
        held_experts: Optional[Tuple[int, int]] = None,
        latent_size: Optional[int] = None,
        shared_gate: bool = False,
        held_window_factor: Optional[float] = None,
    ) -> List[Tensor]:
        """Fused MoE FFN (`ExpertsAttrs`); returns [out] or, with an
        auxiliary loss coefficient, [out, aux_loss], the scalar recorded in
        `self.aux_loss_tensors` for the training loss. `initializer`, if
        given, initializes every weight slot but the selection bias, a
        buffer that starts at zero and takes no gradient."""
        from flexflow_tpu.op_attrs.ops.moe import ExpertsAttrs

        attrs = ExpertsAttrs(
            num_experts,
            num_select,
            hidden_size,
            out_channels,
            activation,
            capacity_factor,
            use_bias,
            lambda_bal,
            gated,
            renormalize,
            lambda_z,
            scoring,
            selection_bias,
            routed_scale,
            shared_hidden_size,
            held_experts,
            latent_size,
            shared_gate,
            held_window_factor,
        )
        inits = [initializer] * attrs.num_weights
        if selection_bias:
            inits[1] = None  # a vector's own default: zero
        outs = self.add_layer(attrs, [input], inits, name)
        if len(outs) > 1 and outs[1] not in self.aux_loss_tensors:
            self.aux_loss_tensors.append(outs[1])
        return outs

    def label_cross_entropy(
        self, logits: Tensor, labels: Tensor, weight: float = 1.0,
        name: Optional[str] = None,
        position_weights: Optional[Tensor] = None,
    ) -> Tensor:
        """A loss node (`LabelCrossEntropyAttrs`): `weight` times the mean
        cross-entropy of `logits` [batch..., classes] against `labels`
        [batch...], an integer tensor of the graph, over the positions whose
        label is not negative; with `position_weights`, a float tensor of
        the graph [batch...], each position's cross-entropy under its own
        weight, which takes a gradient. The scalar [1] is recorded in
        `self.aux_loss_tensors`: training adds it to its loss."""
        from flexflow_tpu.op_attrs.ops import LabelCrossEntropyAttrs

        inputs = [logits, labels]
        if position_weights is not None:
            inputs.append(position_weights)
        (out,) = self.add_layer(
            LabelCrossEntropyAttrs(weight, position_weights is not None),
            inputs, [], name,
        )
        self.aux_loss_tensors.append(out)
        return out

    def mean_loss(
        self, value: Tensor, weight: float = 1.0, name: Optional[str] = None
    ) -> Tensor:
        """A loss node without labels (`MeanLossAttrs`): `weight` times the
        mean of the float tensor `value`, a term of the training loss under
        the node's name."""
        from flexflow_tpu.op_attrs.ops import MeanLossAttrs

        (out,) = self.add_layer(MeanLossAttrs(weight), [value], [], name)
        self.aux_loss_tensors.append(out)
        return out

    def state_space(
        self,
        input: Tensor,
        num_heads: int,
        head_dim: int,
        state_size: int,
        num_groups: int = 1,
        conv_kernel: int = 4,
        chunk_size: int = 128,
        norm_eps: float = 1e-5,
        initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        """The selective state-space mixer (`StateSpaceAttrs`) on
        [batch, seq, channel]. `initializer`, if given, initializes the two
        projections; the convolution, `dt_bias`, `A_log`, `D` and the norm's
        gain take the op's own defaults."""
        from flexflow_tpu.op_attrs.ops.ssm import StateSpaceAttrs

        attrs = StateSpaceAttrs(
            num_heads, head_dim, state_size, num_groups, conv_kernel,
            chunk_size, norm_eps,
        )
        inits = [None] * attrs.num_weights
        inits[0] = inits[-1] = initializer
        (out,) = self.add_layer(attrs, [input], inits, name)
        return out

    def gated_delta(
        self,
        input: Tensor,
        num_heads: int,
        key_dim: int,
        value_dim: int,
        conv_kernel: int = 4,
        gate_rank: int = 128,
        chunk_size: int = 64,
        norm_eps: float = 1e-5,
        initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
        num_key_heads: Optional[int] = None,
        decay: str = "channel",
    ) -> Tensor:
        """The gated delta-rule linear-attention mixer (`GatedDeltaAttrs`) on
        [batch, seq, channel]: a log-decay a key channel and a low-rank gate,
        or (`decay` "head") one a value head, `num_key_heads` key heads
        under `num_heads` value heads and a full-width SiLU gate.
        `initializer`, if given, initializes the projections (in, out and
        the two low-rank gates' up-projections, or in, b | a and out); the
        convolution, `dt_bias`, `A_log`, the gate's bias and the norm's gain
        take the op's own defaults."""
        from flexflow_tpu.op_attrs.ops.kda import GatedDeltaAttrs

        attrs = GatedDeltaAttrs(
            num_heads, key_dim, value_dim, conv_kernel, gate_rank,
            chunk_size, norm_eps, num_key_heads, decay,
        )
        inits = [None] * attrs.num_weights
        for slot in (0, 1, 6) if attrs.per_head_decay else (0, 2, 5, 8):
            inits[slot] = initializer
        (out,) = self.add_layer(attrs, [input], inits, name)
        return out

    def selective_scan(
        self,
        input: Tensor,
        channels: int,
        state_size: int = 16,
        dt_rank: int = 0,
        conv_kernel: int = 4,
        memory_output: bool = False,
        initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
    ) -> List[Tensor]:
        """The Mamba-1 mixer (`SelectiveScanAttrs`) on [batch, seq, channel]:
        [its output] and, with `memory_output`, the scan's result before its
        gate beside it. `initializer`, if given, initializes the in, x and
        out projections; the step's projection starts uniform in
        +-dt_rank^-0.5 and the other slots take the op's own defaults."""
        from flexflow_tpu.op_attrs.ops.selective_scan import SelectiveScanAttrs
        from flexflow_tpu.pcg.initializer import UniformInitializerAttrs

        attrs = SelectiveScanAttrs(
            channels, state_size, dt_rank, conv_kernel, memory_output
        )
        bound = attrs.rank_for(self.graph.tensor_shape(input).dims[-1]) ** -0.5
        inits = [None] * attrs.num_weights
        inits[0] = inits[3] = inits[8] = initializer
        inits[4] = UniformInitializerAttrs(min_val=-bound, max_val=bound)
        return self.add_layer(attrs, [input], inits, name)

    def short_conv(
        self,
        input: Tensor,
        width: int,
        conv_kernel: int = 3,
        initializer: Optional[InitializerAttrs] = None,
        name: Optional[str] = None,
    ) -> Tensor:
        """The double-gated short-convolution mixer (`ShortConvAttrs`) on
        [batch, seq, channel]. `initializer`, if given, initializes the two
        projections; the convolution takes the op's own default."""
        from flexflow_tpu.op_attrs.ops.short_conv import ShortConvAttrs

        attrs = ShortConvAttrs(width, conv_kernel)
        (out,) = self.add_layer(
            attrs, [input], [initializer, None, initializer], name
        )
        return out

    def moe(
        self,
        input: Tensor,
        num_exp: int,
        num_select: int,
        hidden_size: int,
        alpha: float = 2.0,
        lambda_bal: float = 0.0,
        name=None,
    ) -> Tensor:
        """Reference FFModel::moe signature (moe.cc: ff.moe(input, num_exp,
        num_select, hidden_size, alpha, lambda)) over the fused experts op.
        The load-balance aux output (lambda_bal > 0) is recorded in
        self.aux_loss_tensors for the training instance to add to the loss."""
        outs = self.experts(
            input,
            num_exp,
            num_select,
            hidden_size,
            capacity_factor=alpha,
            lambda_bal=lambda_bal,
            name=name,
        )
        return outs[0]
