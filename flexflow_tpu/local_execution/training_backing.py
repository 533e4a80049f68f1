"""Single-host training backing: graph interpreter + jitted train step.

Reference: lib/local-execution/src/local_training_backing.cc:9-120
(execute_init/forward/backward/update) — including execute_update, which the
reference left NOT_IMPLEMENTED (line 107); here it is complete.

Two execution styles:

1. `LocalTrainingBacking` — per-op stepped execution mirroring the reference
   API: execute_init allocates parameters, execute_forward/backward walk the
   graph one op at a time recording per-layer elapsed ms (the
   PerLayerElapsedTime map the cost model consumes).
2. `ModelTrainingInstance` — the TPU-idiomatic path: the full
   forward+loss+backward+update composes into ONE jitted XLA program with
   donated buffers (the analogue of Legion trace capture/replay,
   SURVEY.md §3.1 hot loop), which is what examples and bench use.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from flexflow_tpu.kernels import (
    apply_optimizer,
    compute_metrics,
    forward as kernel_forward,
    loss_forward,
    make_optimizer_state,
)
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.core import (
    IncomingTensorRole,
    OpAttrs,
    OperatorType,
    get_incoming_tensor_roles,
    op_type_of,
)
from flexflow_tpu.op_attrs.ops import InputAttrs, WeightAttrs
from flexflow_tpu.op_attrs.ops.loss_functions import (
    LabelCrossEntropyAttrs,
    LossAttrs,
    LossFunction,
)
from flexflow_tpu.pcg.computation_graph import ComputationGraph
from flexflow_tpu.pcg.initializer import InitializerAttrs, initialize
from flexflow_tpu.pcg.optimizer import OptimizerAttrs
from flexflow_tpu.utils.graph import DataflowOutput, Node

# Parameters are keyed by weight-node index ("n3") so pytrees stay stringly.
ParamKey = str


_BARRIER_OK: Optional[bool] = None


def optimization_barrier(x):
    """`jax.lax.optimization_barrier` when the installed jax can
    differentiate it; identity otherwise (some jax builds ship the
    primitive without an AD rule, and the barrier is a fusion HINT —
    dropping it costs the fusion-split performance win, never
    correctness). Probed once per process via an abstract trace."""
    global _BARRIER_OK
    if _BARRIER_OK is None:
        try:
            jax.eval_shape(
                jax.grad(lambda y: jax.lax.optimization_barrier(y * 1.0)),
                jnp.zeros((), jnp.float32),
            )
            _BARRIER_OK = True
        except NotImplementedError:
            _BARRIER_OK = False
    return jax.lax.optimization_barrier(x) if _BARRIER_OK else x


def slot_roles(attrs: OpAttrs, n_slots: int):
    """Effective per-slot roles for an op with n_slots wired inputs: the
    op's declared IncomingTensorRole order, or all-INPUT when the counts
    mismatch (variadic ops like Concat). The single definition shared by
    split_slot_values and the executor's grad/optimizer fusion barrier so
    the two can never disagree about which slots are weights."""
    roles = get_incoming_tensor_roles(attrs)
    if len(roles) != n_slots:
        return [IncomingTensorRole.INPUT] * n_slots
    return list(roles)


def split_slot_values(attrs: OpAttrs, slot_values):
    """Split an op node's input-slot values into (data inputs, weights) using
    the op's IncomingTensorRole order (the builder wires weights after data
    inputs; variadic ops like Concat have all-INPUT roles)."""
    roles = slot_roles(attrs, len(slot_values))
    inputs = [v for v, r in zip(slot_values, roles) if r == IncomingTensorRole.INPUT]
    weights = [v for v, r in zip(slot_values, roles) if r == IncomingTensorRole.WEIGHT]
    return inputs, weights


def param_key(n: Node) -> ParamKey:
    return f"n{n.idx}"


def init_params(
    cg: ComputationGraph, rng: jax.Array, dtype_override=None
) -> Dict[ParamKey, jnp.ndarray]:
    """Materialize every weight node via its initializer attrs
    (reference: execute_init + initializer kernels)."""
    params: Dict[ParamKey, jnp.ndarray] = {}
    for n in cg.topological_ordering():
        attrs = cg.op_attrs(n)
        if isinstance(attrs, WeightAttrs):
            (out,) = cg.outputs_of(n)
            ta = cg.tensor_attrs(out)
            key = jax.random.fold_in(rng, n.idx)
            init = ta.initializer
            assert init is not None, f"weight node {n} missing initializer"
            dtype = dtype_override or ta.shape.dtype.to_jnp()
            params[param_key(n)] = initialize(init, key, ta.shape.dims, dtype)
    return params


def forward_interpreter(
    cg: ComputationGraph,
    params: Dict[ParamKey, jnp.ndarray],
    inputs: Dict[str, jnp.ndarray],
    *,
    train: bool = False,
    rng: Optional[jax.Array] = None,
    barrier_nodes: FrozenSet[Node] = frozenset(),
) -> Dict[DataflowOutput, jnp.ndarray]:
    """Evaluate the CG: returns every tensor value keyed by DataflowOutput.

    inputs: keyed by input-layer name (or param_key of the input node).
    barrier_nodes: ops whose DATA inputs pass through an
    optimization_barrier — the barrier's transpose stops XLA from fusing
    the op's input-gradient matmul with the upstream backward reductions
    (the LM-head dX matmul fused with the final layer-norm grads ran at
    145 TF/s vs 178 standalone; profiled ~1.5 ms/step on the headline
    bench).

    The nodes of one of the graph's `recompute_groups` are evaluated
    together under ONE `jax.checkpoint`, when the first of them is reached:
    what they compute inside is computed again in the backward pass.
    """
    env: Dict[DataflowOutput, jnp.ndarray] = {}

    def run(n, slot_vals, rng):
        """Node `n` on its slot values: its results, in output order."""
        attrs = cg.op_attrs(n)
        data_vals, weight_vals = split_slot_values(attrs, slot_vals)
        # everything this node lowers to carries its name in the device
        # trace (observability/trace.py)
        with trace.node_scope(cg, n):
            if n in barrier_nodes:
                data_vals = [optimization_barrier(x) for x in data_vals]
            op_rng = jax.random.fold_in(rng, n.idx) if rng is not None else None
            return kernel_forward(
                attrs, data_vals, weight_vals, train=train, rng=op_rng
            )

    def run_group(members):
        """The group's nodes under one checkpoint. Everything a member reads
        from outside is an argument; every member's outputs come back, and
        so do the loss terms its loss nodes recorded (a value recorded
        inside would be a tracer of the checkpointed function)."""
        inside = {o for m in members for o in cg.outputs_of(m)}
        outer = [
            v for m in members for v in cg.inputs_of(m) if v not in inside
        ]
        missing = [v for v in outer if v not in env]
        assert not missing, (
            f"a recompute group reads {missing} before they are computed"
        )
        recorded = []  # (scope, weight, has a mass): static facts

        def body(vals, rng):
            from flexflow_tpu.observability import routing

            vals = iter(vals)
            local: Dict[DataflowOutput, jnp.ndarray] = {}
            with trace.collecting_loss_terms() as terms, \
                    routing.collecting() as held_rows:
                for m in members:
                    results = run(m, [
                        local[v] if v in inside else next(vals)
                        for v in cg.inputs_of(m)
                    ], rng)
                    local.update(zip(cg.outputs_of(m), results))
            assert not held_rows, (
                "a held expert share inside a recompute group: its routing "
                "counters would leave the group as tracers"
            )
            recorded[:] = [(name, w, mass is not None) for name, w, _, mass in terms]
            values = [(v, mass) for _, _, v, mass in terms]
            return [local[o] for m in members for o in cg.outputs_of(m)], values

        outs, values = jax.checkpoint(body)([env[v] for v in outer], rng)
        env.update(zip((o for m in members for o in cg.outputs_of(m)), outs))
        for (name, weight, _), (value, mass) in zip(recorded, values):
            trace.record_loss_term(weight, value, scope=name, mass=mass)

    order = cg.topological_ordering()
    group_of: Dict[Node, List[Node]] = {}
    if cg.recompute_groups:
        position = {n: i for i, n in enumerate(order)}
        for members in cg.recompute_groups:
            members = sorted(members, key=position.__getitem__)
            group_of.update((n, members) for n in members)
    # the sources first: a group reads the weights of ALL its members when
    # its first is reached
    ops = []
    for n in order:
        la = cg.layer_attrs(n)
        if isinstance(la.attrs, InputAttrs):
            key = la.name if la.name is not None and la.name in inputs else param_key(n)
            assert key in inputs, f"missing input binding for {la.name or key}"
            env[cg.outputs_of(n)[0]] = inputs[key]
        elif isinstance(la.attrs, WeightAttrs):
            env[cg.outputs_of(n)[0]] = params[param_key(n)]
        else:
            ops.append(n)
    for n in ops:
        outs = cg.outputs_of(n)
        if n in group_of:
            if outs[0] not in env:
                run_group(group_of[n])
        else:
            results = run(n, [env[v] for v in cg.inputs_of(n)], rng)
            for o, r in zip(outs, results):
                env[o] = r
    return env


class ModelTrainingInstance:
    """CG + loss + optimizer + metrics -> one jitted, donated train step.

    Reference: include/runtime/model_training_instance.h:14-33 (CG + optimizer
    + TrainingPCG + loss/metrics) and FFModel::fit's
    forward/zero_gradients/backward/update loop — here fused into a single
    XLA program per step.
    """

    def __init__(
        self,
        cg: ComputationGraph,
        logit_tensor: DataflowOutput,
        loss_attrs: LossAttrs,
        optimizer_attrs: OptimizerAttrs,
        metrics: FrozenSet[str] = frozenset(),
        train_rng: bool = False,
        compute_dtype=None,
        aux_loss_tensors: Sequence[DataflowOutput] = (),
        collect_step_stats: bool = False,
        guard_nonfinite_updates: bool = False,
    ) -> None:
        """compute_dtype: mixed-precision policy — params/optimizer state stay
        f32 but forward/backward compute casts float tensors to this dtype
        (bf16 on TPU doubles MXU throughput); loss math stays f32.

        collect_step_stats fuses the run-health scalars (grad/param global
        norms, update ratio, finiteness flag — observability/metrics.py
        step_statistics) into the jitted step and exposes them as
        `last_step_stats` after each train_step; guard_nonfinite_updates
        additionally keeps the pre-step params/optimizer state whenever the
        step goes non-finite (the skip_step / raise health policies)."""
        self.cg = cg
        self.logit_tensor = logit_tensor
        self.loss_attrs = loss_attrs
        self.optimizer_attrs = optimizer_attrs
        self.metrics = metrics
        self.train_rng = train_rng
        self.compute_dtype = compute_dtype
        self.collect_step_stats = collect_step_stats or guard_nonfinite_updates
        self.guard_nonfinite_updates = guard_nonfinite_updates
        # device-scalar dict from the latest train_step (collect_step_stats)
        self.last_step_stats = None
        # Extra scalar loss terms from the graph (e.g. the Experts op's
        # load-balance output, reference MoE lambda — moe.cc)
        self.aux_loss_tensors = tuple(aux_loss_tensors)
        # barrier the logit producer's inputs (see forward_interpreter):
        # its dX matmul reads the huge [tokens, vocab] dlogits and must not
        # share a fusion with the upstream norm's backward reductions
        # (the same for the logits a loss node of the graph reads: a
        # multi-token-prediction module's second use of the head)
        self._barrier_nodes = frozenset({logit_tensor.node}) | frozenset(
            cg.inputs_of(n)[0].node for n in cg.topological_ordering()
            if isinstance(cg.op_attrs(n), LabelCrossEntropyAttrs)
        )
        if loss_attrs.loss_type == LossFunction.LOSS_NODES:
            assert self.aux_loss_tensors, (
                "loss_type loss_nodes on a graph without a loss node: the "
                "step would have no loss"
            )
        # [(scope, weight, has a mass)] of the step's loss terms, as the
        # last trace of the step recorded them; empty in a graph with one
        # loss
        self.loss_term_names = []
        self._jit_step = None
        self._jit_fwd = None

    def _cast_for_compute(self, tree):
        from flexflow_tpu.kernels.precision import cast_for_compute

        return cast_for_compute(tree, self.compute_dtype)

    # -- setup ------------------------------------------------------------

    def initialize(self, seed: int = 0):
        rng = jax.random.PRNGKey(seed)
        params = init_params(self.cg, rng)
        opt_state = make_optimizer_state(self.optimizer_attrs, params)
        return params, opt_state

    # -- step -------------------------------------------------------------

    def loss_fn(self, params, batch_inputs, label, rng=None):
        loss, (logit, _) = self._loss_and_routing(
            params, batch_inputs, label, rng
        )
        return loss, logit

    def _loss_and_routing(self, params, batch_inputs, label, rng=None):
        """(loss, (logits, the step's counters by metric key: the held
        expert nodes' routing counts stacked [nodes, held + 3] as
        `routing.record` lays a row out, and the loss terms
        (`trace.LOSS_TERMS_KEY`) of a graph with a loss node; a graph with
        neither has none))."""
        from flexflow_tpu.observability import routing

        with trace.step_scope("cast"):
            params = self._cast_for_compute(params)
            batch_inputs = self._cast_for_compute(batch_inputs)
        with routing.collecting() as held_rows, \
                trace.collecting_loss_terms() as terms:
            env = forward_interpreter(
                self.cg,
                params,
                batch_inputs,
                train=True,
                rng=rng,
                barrier_nodes=self._barrier_nodes,
            )
        logit = env[self.logit_tensor]
        with trace.step_scope("loss"):
            loss = loss_forward(self.loss_attrs, logit, label)
            # a graph with a loss node: the main loss is the first term,
            # where the step has one
            if terms and self.loss_attrs.loss_type != LossFunction.LOSS_NODES:
                terms.insert(0, ("ff.loss", 1.0, loss, None))
            for t in self.aux_loss_tensors:
                loss = loss + jnp.sum(env[t].astype(loss.dtype))
        counters = {}
        if held_rows:
            counters[routing.ROUTING_KEY] = jnp.stack(held_rows)
        if terms:
            self.loss_term_names, counters[trace.LOSS_TERMS_KEY] = (
                trace.loss_terms_vector(terms)
            )
        return loss, (logit, counters)

    def _step(self, params, opt_state, batch_inputs, label, rng):
        trace.count(trace.STEP_TRACE)  # this body runs when JAX traces it
        (loss, (logit, counters)), grads = jax.value_and_grad(
            self._loss_and_routing, has_aux=True
        )(params, batch_inputs, label, rng)
        with trace.step_scope("optimizer"):
            new_params, new_opt_state = apply_optimizer(
                self.optimizer_attrs, params, grads, opt_state
            )
        with trace.step_scope("metrics"):
            metric_vals = compute_metrics(self.metrics, logit, label)
            metric_vals.update(counters)
        # run-health scalars, fused into this same XLA program: each global
        # norm is one reduction over the pytree, not a host trip per leaf;
        # under skip_step/raise a non-finite update never reaches the
        # parameters or optimizer state
        from flexflow_tpu.observability.metrics import finalize_step

        with trace.step_scope("health"):
            new_params, new_opt_state, stats = finalize_step(
                self.collect_step_stats, self.guard_nonfinite_updates,
                params, new_params, grads, loss, opt_state, new_opt_state,
            )
        if stats is None:
            return new_params, new_opt_state, loss, metric_vals
        return new_params, new_opt_state, loss, metric_vals, stats

    def compiled_step(self):
        """The hot-loop step function (donated params/opt_state)."""
        if self._jit_step is None:
            self._jit_step = jax.jit(self._step, donate_argnums=(0, 1))
        return self._jit_step

    def _record_stats(self, out):
        """Split the optional stats tail off the step result, keeping the
        public 4-tuple contract."""
        if self.collect_step_stats:
            self.last_step_stats = out[4]
            return out[:4]
        return out

    def train_step(self, params, opt_state, batch_inputs, label, rng=None):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        # the host sees one thing of a step, the enqueue of its one XLA
        # program; when the step ended is read off the device plane of the
        # same trace (observability/trace.py), never waited for here
        with trace.record_span("step", backend=type(self).__name__):
            with trace.record_span("dispatch"):
                out = self.compiled_step()(
                    params, opt_state, batch_inputs, label, rng
                )
        return self._record_stats(out)

    def forward(self, params, batch_inputs):
        if self._jit_fwd is None:
            def fwd(params, batch_inputs):
                env = forward_interpreter(self.cg, params, batch_inputs)
                return env[self.logit_tensor]

            self._jit_fwd = jax.jit(fwd)
        return self._jit_fwd(params, batch_inputs)


PerLayerElapsedTime = Dict[Node, float]


class LocalTrainingBacking:
    """Stepped per-op execution with per-layer timing (reference API parity:
    local_training_backing.cc execute_init/forward/backward/update)."""

    def __init__(self, cg: ComputationGraph, profiling: bool = False) -> None:
        self.cg = cg
        self.profiling = profiling
        self.params: Dict[ParamKey, jnp.ndarray] = {}
        self.env: Dict[DataflowOutput, jnp.ndarray] = {}
        self.grad_env: Dict[DataflowOutput, jnp.ndarray] = {}
        self.param_grads: Dict[ParamKey, jnp.ndarray] = {}
        self.fwd_elapsed: PerLayerElapsedTime = {}
        self.bwd_elapsed: PerLayerElapsedTime = {}
        # per-node jitted kernels, built once (jax.jit objects cache traces)
        self._fwd_fns: Dict[Node, object] = {}
        self._bwd_fns: Dict[Node, object] = {}

    def execute_init(self, seed: int = 0) -> None:
        self.params = init_params(self.cg, jax.random.PRNGKey(seed))

    def _timed(self, node: Node, table: PerLayerElapsedTime, fn, *args):
        if not self.profiling:
            return fn(*args)
        phase = "bwd" if table is self.bwd_elapsed else "fwd"
        name = self.cg.layer_attrs(node).name or param_key(node)
        out = fn(*args)
        jax.block_until_ready(out)
        start = time.perf_counter()
        with trace.record_span(f"{phase}/{name}"):
            out = fn(*args)
            jax.block_until_ready(out)
        table[node] = (time.perf_counter() - start) * 1000.0
        return out

    def execute_forward(self, inputs: Dict[str, jnp.ndarray]) -> None:
        self.env = {}
        for n in self.cg.topological_ordering():
            la = self.cg.layer_attrs(n)
            attrs = la.attrs
            outs = self.cg.outputs_of(n)
            if isinstance(attrs, InputAttrs):
                key = la.name if la.name in inputs else param_key(n)
                self.env[outs[0]] = inputs[key]
            elif isinstance(attrs, WeightAttrs):
                self.env[outs[0]] = self.params[param_key(n)]
            else:
                slot_vals = [self.env[v] for v in self.cg.inputs_of(n)]
                if n not in self._fwd_fns:

                    def fn(*xs, a=attrs):
                        data, w = split_slot_values(a, list(xs))
                        return kernel_forward(a, data, w)

                    self._fwd_fns[n] = jax.jit(fn)
                results = self._timed(
                    n, self.fwd_elapsed, self._fwd_fns[n], *slot_vals
                )
                for o, r in zip(outs, results):
                    self.env[o] = r

    def execute_backward(self, output_grads: Dict[DataflowOutput, jnp.ndarray]) -> None:
        """Reverse-topo per-op VJP walk (reference :88: reversed topo order
        with infer_bwd_binding).

        Weight gradients ACCUMULATE across calls until zeroed (reference
        zero_gradients semantics — micro-batch accumulation works); the
        activation grad env is per-call."""
        self.grad_env = dict(output_grads)
        order = self.cg.topological_ordering()
        for n in reversed(order):
            attrs = self.cg.op_attrs(n)
            if isinstance(attrs, (InputAttrs, WeightAttrs)):
                if isinstance(attrs, WeightAttrs):
                    (out,) = self.cg.outputs_of(n)
                    if out in self.grad_env:
                        k = param_key(n)
                        g = self.grad_env[out]
                        self.param_grads[k] = (
                            self.param_grads[k] + g
                            if k in self.param_grads
                            else g
                        )
                continue
            outs = self.cg.outputs_of(n)
            out_grads = tuple(
                self.grad_env.get(o, jnp.zeros_like(self.env[o])) for o in outs
            )
            in_vals = [self.env[v] for v in self.cg.inputs_of(n)]
            if n not in self._bwd_fns:

                def op_fn(*xs, a=attrs):
                    data, w = split_slot_values(a, list(xs))
                    return tuple(kernel_forward(a, data, w))

                def vjp_fn(out_grads, *args):
                    _, pullback = jax.vjp(op_fn, *args)
                    return pullback(out_grads)

                self._bwd_fns[n] = jax.jit(vjp_fn)
            in_grads = self._timed(
                n, self.bwd_elapsed, self._bwd_fns[n], out_grads, *in_vals
            )
            for v, g in zip(self.cg.inputs_of(n), in_grads):
                if v in self.grad_env:
                    self.grad_env[v] = self.grad_env[v] + g
                else:
                    self.grad_env[v] = g

    def execute_update(self, optimizer_attrs: OptimizerAttrs, opt_state=None):
        """Completes the reference's NOT_IMPLEMENTED execute_update
        (local_training_backing.cc:107)."""
        if opt_state is None:
            opt_state = make_optimizer_state(optimizer_attrs, self.params)
        grads = {
            k: self.param_grads.get(k, jnp.zeros_like(v))
            for k, v in self.params.items()
        }
        self.params, opt_state = apply_optimizer(
            optimizer_attrs, self.params, grads, opt_state
        )
        return opt_state
