"""1F1B pipelined execution of a stage-partitioned PCG (ISSUE 13).

Lowers a PCG carrying StagePartition/StageMerge ops to a single donated
XLA step program whose core is a `lax.scan` over the static 1F1B schedule
(`pcg.pipeline.one_f_one_b_schedule`) inside one `shard_map` over a
(stage, data) mesh:

- the S stages live on disjoint submeshes along the "stage" axis, their
  parameters stacked [S, ...] and sharded over it (the praxis/GSPMD
  pipelining idiom — the ring patterns of kernels/ring_attention.py and
  kernels/collective_matmul.py are the template);
- each schedule tick moves the forward activation one stage up and the
  backward gradient one stage down via `lax.ppermute` point-to-point
  hops — exactly the transfers `stage_transfer_cost_ms` prices;
- in-flight microbatch activations are stashed in a min(S, M)-slot
  modular arrival buffer; backwards REMATERIALIZE the stage forward from
  the stashed stage input (per-stage activation checkpointing), which is
  what keeps the stash the 1F1B bound the static memory model charges.

Numerics contract (pinned by tests/test_pipeline.py): the pipelined step
is BITWISE-identical — loss trajectory and final params — to the
sequential microbatch reference (`FF_TPU_PIPELINE_BASELINE=1`), which
runs the same per-(stage, microbatch) computations in plain microbatch
order. Both paths share `_stage_unit_fwd` / `_stage_unit_vjp`, so they
cannot diverge by construction; versus a full-batch unpipelined step the
result is allclose (microbatching reassociates the batch reduction).

Executability (PipelineUnsupported otherwise; the flat GSPMD executor
remains the always-correct fallback since stage ops are value-identity):

- stages must be structurally isomorphic (equal op/weight-shape
  signature per stage) so parameters stack along the stage axis,
- in-stage parallelism is restricted to batch sharding (dim-0
  Repartition/Combine, weight Replicate) — identity on the per-device
  values the shard_map body manipulates,
- nothing but Input layers and their reshard wrappers may precede the
  region entry, and only pure reshard ops may follow the StageMerge
  (the trailing chain the executor bypasses anyway).
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.kernels import (
    apply_optimizer,
    compute_metrics,
    forward as kernel_forward,
    loss_forward,
    make_optimizer_state,
)
from flexflow_tpu.local_execution.training_backing import split_slot_values
from flexflow_tpu.observability import trace
from flexflow_tpu.op_attrs.core import is_parallel_op, is_stage_op
from flexflow_tpu.op_attrs.ops import (
    CombineAttrs,
    InputAttrs,
    ReductionAttrs,
    RepartitionAttrs,
    ReplicateAttrs,
    WeightAttrs,
)
from flexflow_tpu.op_attrs.ops.loss_functions import LossAttrs
from flexflow_tpu.op_attrs.parallel_tensor_shape import get_reduced_shape
from flexflow_tpu.pcg.initializer import initialize
from flexflow_tpu.pcg.optimizer import OptimizerAttrs
from flexflow_tpu.pcg.pipeline import (
    analyze_pipeline,
    one_f_one_b_schedule,
    sequential_microbatch_schedule,
)
from flexflow_tpu.parallel.mesh import MachineMesh
from flexflow_tpu.utils.graph import DataflowOutput, Node
from flexflow_tpu.utils.shard_map_compat import shard_map_compat as _shard_map


class PipelineUnsupported(ValueError):
    """The PCG's stage structure cannot lower to the 1F1B executor (the
    flat GSPMD path remains correct — stage ops are value-identity)."""


def pipeline_execution_active(flag: Optional[bool] = None) -> bool:
    """Is the 1F1B lowering on? Mirrors `overlap_lowering_active`: an
    explicit flag (--pipeline/--no-pipeline) wins, else FF_TPU_PIPELINE."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("FF_TPU_PIPELINE", "") not in ("", "0")


def param_key(n: Node) -> str:
    return f"n{n.idx}"


# ---------------------------------------------------------------------------
# Structure extraction
# ---------------------------------------------------------------------------


@dataclass
class ExecutablePipeline:
    """A stage-partitioned PCG validated for 1F1B execution."""

    num_stages: int
    num_microbatches: int
    # per stage, its nodes in topological order (stage ops excluded)
    stage_nodes: List[List[Node]]
    # per stage, the value the stage consumes (the StagePartition output)
    entry_values: List[DataflowOutput]
    # per stage, the value it produces (the next boundary's/merge's input)
    exit_values: List[DataflowOutput]
    # template (stage 0) weight nodes in topo order; stage s's k-th weight
    # corresponds to the template's k-th
    weight_nodes: List[List[Node]]
    input_node: Node  # the single Input layer feeding the region


def _stage_signature(pcg, nodes: Sequence[Node], binding: Dict) -> tuple:
    """Structural signature of one stage: op attrs + wiring (relative to
    the stage's own node list) + weight shapes. Equal signatures across
    stages = parameters stack."""
    pos = {n: i for i, n in enumerate(nodes)}
    sig = []
    for n in nodes:
        attrs = pcg.op_attrs(n)
        ins = []
        for v in pcg.inputs_of(n):
            if v.node in pos:
                ins.append(("n", pos[v.node], v.idx))
            else:
                ins.append(("x", binding.get(v, "entry")))
        shapes = tuple(pcg.tensor_shape(o) for o in pcg.outputs_of(n))
        sig.append((type(attrs).__name__, attrs, tuple(ins), shapes))
    return tuple(sig)


def extract_executable_pipeline(pcg) -> ExecutablePipeline:
    """Validate + extract the stage structure (see module docstring)."""
    region = analyze_pipeline(pcg)
    if region is None:
        raise PipelineUnsupported("PCG carries no stage ops")
    if not region.ok:
        raise PipelineUnsupported(
            f"malformed stage structure: {region.issues}"
        )
    S, M = region.num_stages, region.num_microbatches
    if S < 2:
        raise PipelineUnsupported("need at least 2 stages")

    sp_nodes = region.partition_nodes
    merge = region.merge_node
    entry_values = [pcg.outputs_of(n)[0] for n in sp_nodes]
    exit_values = [pcg.inputs_of(n)[0] for n in sp_nodes[1:]] + [
        pcg.inputs_of(merge)[0]
    ]

    # uniform boundary/entry shapes (the ppermute carry is ONE buffer)
    shapes = {
        (
            get_reduced_shape(pcg.tensor_shape(v)).dims,
            pcg.tensor_shape(v).dtype,
        )
        for v in entry_values + exit_values
    }
    if len(shapes) != 1:
        raise PipelineUnsupported(
            f"stage boundary values disagree on shape/dtype: "
            f"{sorted(shapes, key=repr)}"
        )

    stage_nodes: List[List[Node]] = [[] for _ in range(S)]
    boundary = set(sp_nodes) | {merge}
    for n in pcg.topological_ordering():
        s = region.stage_of.get(n)
        if s is None or n in boundary:
            continue
        attrs = pcg.op_attrs(n)
        if isinstance(attrs, ReductionAttrs):
            raise PipelineUnsupported(
                "in-stage Reduction (tensor parallelism inside a stage) "
                "is not supported by the 1F1B executor"
            )
        if isinstance(attrs, (RepartitionAttrs, CombineAttrs)):
            d = (
                attrs.repartition_dim
                if isinstance(attrs, RepartitionAttrs)
                else attrs.combine_dim
            )
            rank = pcg.tensor_shape(pcg.inputs_of(n)[0]).num_dims
            if d % rank != 0 and not _feeds_from_weight(pcg, n):
                raise PipelineUnsupported(
                    "in-stage activation resharding on a non-batch dim is "
                    "not supported by the 1F1B executor"
                )
        stage_nodes[s].append(n)

    # everything outside the region must be the input feed (Input layers +
    # reshard wrappers before the entry) or trailing reshards of the merge
    outside = [
        n
        for n in pcg.topological_ordering()
        if n not in region.stage_of and n not in boundary
    ]
    input_node = None
    merge_out = pcg.outputs_of(merge)[0]
    trailing = _reshard_descendants(pcg, merge_out)
    for n in outside:
        attrs = pcg.op_attrs(n)
        if isinstance(attrs, InputAttrs):
            if input_node is not None:
                raise PipelineUnsupported(
                    "multiple Input layers feed the pipeline region"
                )
            input_node = n
        elif is_parallel_op(attrs) and (
            n in trailing or _feeds_from_input(pcg, n)
        ):
            continue  # input-feed wrapper or trailing reshard: identity
        else:
            raise PipelineUnsupported(
                f"op outside the pipeline region: "
                f"{type(attrs).__name__} (node {n.idx})"
            )
    if input_node is None:
        raise PipelineUnsupported("no Input layer feeds the pipeline region")

    # stage isomorphism: equal signatures -> parameters stack [S, ...]
    weight_nodes = []
    sigs = []
    for s in range(S):
        binding = {entry_values[s]: "entry"}
        sigs.append(_stage_signature(pcg, stage_nodes[s], binding))
        weight_nodes.append(
            [
                n
                for n in stage_nodes[s]
                if isinstance(pcg.op_attrs(n), WeightAttrs)
            ]
        )
    for s in range(1, S):
        if sigs[s] != sigs[0]:
            raise PipelineUnsupported(
                f"stage {s} is not isomorphic to stage 0 — parameters "
                "cannot stack along the stage axis"
            )
    return ExecutablePipeline(
        num_stages=S,
        num_microbatches=M,
        stage_nodes=stage_nodes,
        entry_values=entry_values,
        exit_values=exit_values,
        weight_nodes=weight_nodes,
        input_node=input_node,
    )


def _feeds_from_weight(pcg, n) -> bool:
    from flexflow_tpu.compiler.machine_mapping.problem_tree import _from_weight

    ins = pcg.inputs_of(n)
    return bool(ins) and all(_from_weight(pcg, v) for v in ins)


def _feeds_from_input(pcg, n) -> bool:
    while True:
        attrs = pcg.op_attrs(n)
        if isinstance(attrs, InputAttrs):
            return True
        if not is_parallel_op(attrs):
            return False
        ins = pcg.inputs_of(n)
        if len(ins) != 1:
            return False
        n = ins[0].node


def _reshard_descendants(pcg, value) -> set:
    out = set()
    frontier = [value]
    while frontier:
        v = frontier.pop()
        for u in pcg.uses_of(v):
            if is_parallel_op(pcg.op_attrs(u.node)):
                out.add(u.node)
                frontier.extend(pcg.outputs_of(u.node))
    return out


# ---------------------------------------------------------------------------
# The shared per-(stage, microbatch) units — ONE implementation for the
# pipelined schedule and the sequential reference (bitwise by construction)
# ---------------------------------------------------------------------------


def _make_stage_fn(pcg, structure: ExecutablePipeline, train: bool):
    """stage_fn(params, x, rng) -> y interpreting the TEMPLATE (stage 0)
    subgraph on local values; `params` is keyed by the template's weight
    nodes (leading stage dim already sliced away)."""
    nodes = structure.stage_nodes[0]
    entry = structure.entry_values[0]
    exit_value = structure.exit_values[0]

    def stage_fn(params, x, rng):
        env = {entry: x}
        for n in nodes:
            attrs = pcg.op_attrs(n)
            outs = pcg.outputs_of(n)
            if isinstance(attrs, WeightAttrs):
                env[outs[0]] = params[param_key(n)]
                continue
            if is_parallel_op(attrs):
                (src,) = pcg.inputs_of(n)
                env[outs[0]] = env[src]
                continue
            slot_vals = [env[v] for v in pcg.inputs_of(n)]
            data_vals, weight_vals = split_slot_values(attrs, slot_vals)
            op_rng = (
                jax.random.fold_in(rng, n.idx) if rng is not None else None
            )
            results = kernel_forward(
                attrs, data_vals, weight_vals, train=train, rng=op_rng
            )
            for o, r in zip(outs, results):
                env[o] = r
        return env[exit_value]

    return stage_fn


def _stage_unit_fwd(stage_fn, loss_attrs, params, x, label_mb, rng):
    """One forward unit: (y, local-mean loss). The loss term is consumed
    only at the last stage, but EVERY stage computes it so the pipelined
    and sequential paths trace one identical computation."""
    y = stage_fn(params, x, rng)
    loss = loss_forward(loss_attrs, y, label_mb)
    return y, loss


def _stage_unit_vjp(
    stage_fn, loss_attrs, params, x, label_mb, rng, cot_y, cot_loss
):
    """One backward unit: rematerialize the stage forward from the stashed
    stage input and pull back (cot_y, cot_loss). The last stage seeds
    (0, 1) — gradient of its own local-mean loss; interior stages seed
    (dy, 0). Returns (dparams, dx)."""

    def F(p, xx):
        return _stage_unit_fwd(stage_fn, loss_attrs, p, xx, label_mb, rng)

    _, vjp = jax.vjp(F, params, x)
    dparams, dx = vjp((cot_y, cot_loss))
    return dparams, dx


# ---------------------------------------------------------------------------
# The training instance
# ---------------------------------------------------------------------------


class PipelinedTrainingInstance:
    """Stage-partitioned PCG + loss + optimizer -> 1F1B jitted train step.

    Duck-types the training-instance surface (`initialize` / `_step` /
    `train_step` / `compiled_step` / run-health stats), so the fit loop
    and the PR-7 checkpoint/resume machinery drive it unchanged."""

    def __init__(
        self,
        pcg,
        logit_tensor: DataflowOutput,
        loss_attrs: LossAttrs,
        optimizer_attrs: OptimizerAttrs,
        devices: Optional[Sequence[object]] = None,
        metrics: FrozenSet[str] = frozenset(),
        compute_dtype=None,
        collect_step_stats: bool = False,
        guard_nonfinite_updates: bool = False,
        unroll_schedule: bool = False,
    ) -> None:
        self.pcg = pcg
        self.structure = extract_executable_pipeline(pcg)
        S = self.structure.num_stages
        self.loss_attrs = loss_attrs
        self.optimizer_attrs = optimizer_attrs
        self.metrics = metrics
        self.compute_dtype = compute_dtype
        self.collect_step_stats = collect_step_stats or guard_nonfinite_updates
        self.guard_nonfinite_updates = guard_nonfinite_updates
        self.last_step_stats = None
        self.unroll_schedule = bool(unroll_schedule)
        # lowering-compat surface (plan-audit/census helpers): the loss
        # consumes the region exit (pre-trailing-reshard, like the flat
        # executor's _pre_reshard_value), and batches stage unsharded
        self.logit_tensor = logit_tensor
        self.loss_logit_tensor = self.structure.exit_values[-1]
        self.shardings: Dict = {}
        self.overlap_sites: Dict = {}  # no fused-collective sites here

        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if len(devices) % S:
            # shrink to the largest multiple of S (mirrors FFModel's
            # batch-divisibility device cap)
            devices = devices[: (len(devices) // S) * S]
        if len(devices) < S:
            raise PipelineUnsupported(
                f"{S} stages need at least {S} devices, have {len(devices)}"
            )
        dp = len(devices) // S
        from jax.sharding import Mesh

        mesh = Mesh(
            np.asarray(devices).reshape(S, dp), ("stage", "data")
        )
        self.machine_mesh = MachineMesh(
            mesh, (("stage", S),), (("data", dp),)
        )
        self.dp = dp
        self._schedule = one_f_one_b_schedule(
            S, self.structure.num_microbatches
        )
        # the unpipelined reference (FF_TPU_PIPELINE_BASELINE=1): same scan
        # body, sequential action table — bitwise parity by construction
        self._seq_schedule = sequential_microbatch_schedule(
            S, self.structure.num_microbatches
        )
        self._jit_step = None
        self._jit_fwd = None
        # what the `step` span says of this backend, spelled once
        self._step_span_args = {
            "backend": type(self).__name__,
            "mesh": str(dict(mesh.shape)),
            "pipeline_stages": S,
            "pipeline_microbatches": self.structure.num_microbatches,
        }

    # -- setup -------------------------------------------------------------

    @property
    def mesh(self):
        return self.machine_mesh.mesh

    def _stacked_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P("stage"))

    def initialize(self, seed: int = 0):
        """Stacked parameter init: the template's k-th weight key holds
        jnp.stack over the S stages' k-th weights (each initialized from
        its OWN node's initializer + fold_in(rng, node.idx), so the values
        match the flat executor's init of the same PCG), sharded over the
        stage axis."""
        rng = jax.random.PRNGKey(seed)
        S = self.structure.num_stages
        stacked: Dict[str, jnp.ndarray] = {}
        tmpl = self.structure.weight_nodes[0]
        for k, tn in enumerate(tmpl):
            per_stage = []
            for s in range(S):
                n = self.structure.weight_nodes[s][k]
                (out,) = self.pcg.outputs_of(n)
                ta = self.pcg.tensor_attrs(out)
                assert ta.initializer is not None, n
                key = jax.random.fold_in(rng, n.idx)
                ts = get_reduced_shape(ta.shape)
                per_stage.append(
                    initialize(
                        ta.initializer, key, ts.dims, ts.dtype.to_jnp()
                    )
                )
            stacked[param_key(tn)] = jax.device_put(
                jnp.stack(per_stage), self._stacked_sharding()
            )
        opt_state = make_optimizer_state(self.optimizer_attrs, stacked)
        opt_state = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._stacked_sharding())
            if hasattr(a, "ndim") and a.ndim >= 1
            else a,
            opt_state,
        )
        return stacked, opt_state

    def input_sharding(self, name: str):
        return None  # batches stage unsharded; shard_map partitions them

    def label_sharding(self):
        return None

    def _cast_for_compute(self, tree):
        from flexflow_tpu.kernels.precision import cast_for_compute

        return cast_for_compute(tree, self.compute_dtype)

    # -- the 1F1B core -----------------------------------------------------

    def _batch_value(self, batch_inputs):
        if isinstance(batch_inputs, dict):
            la = self.pcg.layer_attrs(self.structure.input_node)
            key = (
                la.name
                if la.name is not None and la.name in batch_inputs
                else param_key(self.structure.input_node)
            )
            assert key in batch_inputs, (
                f"missing input binding for {la.name or key}"
            )
            return batch_inputs[key]
        return batch_inputs

    def _microbatched(self, arr):
        M = self.structure.num_microbatches
        b = arr.shape[0]
        assert b % M == 0, (b, M)
        return arr.reshape((M, b // M) + arr.shape[1:])

    def _pipeline_grads(self, params, batch, label, rng, train=True):
        """(grads, loss, logits) of one step via the 1F1B schedule (or the
        sequential microbatch reference under FF_TPU_PIPELINE_BASELINE=1)."""
        S = self.structure.num_stages
        M = self.structure.num_microbatches
        stage_fn = _make_stage_fn(self.pcg, self.structure, train)
        x_mb = self._microbatched(batch)
        y_mb = self._microbatched(label)
        sequential = bool(os.environ.get("FF_TPU_PIPELINE_BASELINE"))
        from jax.sharding import PartitionSpec as P

        fwd_np, bwd_np = (
            self._seq_schedule if sequential else self._schedule
        )
        prev_f = np.vstack([np.full((1, S), -1, np.int32), fwd_np[:-1]])
        prev_b = np.vstack([np.full((1, S), -1, np.int32), bwd_np[:-1]])
        fwd_a, bwd_a = jnp.asarray(fwd_np), jnp.asarray(bwd_np)
        prev_f_a, prev_b_a = jnp.asarray(prev_f), jnp.asarray(prev_b)
        B = max(min(S, M), 1)
        T = fwd_np.shape[0]
        loss_attrs = self.loss_attrs
        dp = self.dp
        scale = 1.0 / (M * dp)

        def local_params(stacked_local):
            return {k: v[0] for k, v in stacked_local.items()}

        def pipeline_body(stacked_local, x_local, y_local, rng):
            stage = jax.lax.axis_index("stage")
            p_local = local_params(stacked_local)
            # boundary values share the entry's shape AND dtype (extraction
            # contract), so the ppermute carry is microbatch-shaped
            zero_b = jnp.zeros(x_local.shape[1:], x_local.dtype)
            stash = jnp.zeros((B,) + zero_b.shape, zero_b.dtype)
            dybuf = jnp.zeros_like(stash)
            grad_acc = jax.tree_util.tree_map(jnp.zeros_like, p_local)
            loss_acc = jnp.zeros((), jnp.float32)
            logits = jnp.zeros((M,) + zero_b.shape, zero_b.dtype)
            fwd_perm = [(i, i + 1) for i in range(S - 1)]
            bwd_perm = [(i + 1, i) for i in range(S - 1)]
            is_last = stage == S - 1
            is_first = stage == 0

            def tick(carry, xs):
                y_send, dx_send, stash, dybuf, grad_acc, loss_acc, logits = carry
                f_row, b_row, pf_row, pb_row = xs
                x_in = jax.lax.ppermute(y_send, "stage", fwd_perm)
                dy_in = jax.lax.ppermute(dx_send, "stage", bwd_perm)
                # arrival buffers: what the neighbor sent LAST tick is this
                # microbatch's stage input / boundary gradient — stash on
                # arrival (the consuming unit may run several ticks later)
                up_m = pf_row[jnp.maximum(stage - 1, 0)]
                up_ok = jnp.logical_and(stage > 0, up_m >= 0)
                uslot = jnp.maximum(up_m, 0) % B
                stash = jnp.where(up_ok, stash.at[uslot].set(x_in), stash)
                dn_m = pb_row[jnp.minimum(stage + 1, S - 1)]
                dn_ok = jnp.logical_and(stage < S - 1, dn_m >= 0)
                dslot = jnp.maximum(dn_m, 0) % B
                dybuf = jnp.where(dn_ok, dybuf.at[dslot].set(dy_in), dybuf)

                # forward unit
                f = f_row[stage]
                f_ok = f >= 0
                fs = jnp.maximum(f, 0)
                x_f = jnp.where(is_first, x_local[fs], stash[fs % B])
                rng_f = jax.random.fold_in(jax.random.fold_in(rng, fs), stage)
                y, loss_f = _stage_unit_fwd(
                    stage_fn, loss_attrs, p_local, x_f, y_local[fs], rng_f
                )
                take_loss = jnp.logical_and(f_ok, is_last)
                loss_acc = jnp.where(
                    take_loss, loss_acc + loss_f.astype(jnp.float32), loss_acc
                )
                logits = jnp.where(take_loss, logits.at[fs].set(y), logits)
                y_send_new = jnp.where(f_ok, y, jnp.zeros_like(y))

                # backward unit (rematerializing vjp from the stashed input)
                b = b_row[stage]
                b_ok = b >= 0
                bs = jnp.maximum(b, 0)
                x_b = jnp.where(is_first, x_local[bs], stash[bs % B])
                rng_b = jax.random.fold_in(jax.random.fold_in(rng, bs), stage)
                cot_y = jnp.where(is_last, jnp.zeros_like(y), dybuf[bs % B])
                cot_l = jnp.where(is_last, 1.0, 0.0).astype(loss_f.dtype)
                dparams, dx = _stage_unit_vjp(
                    stage_fn, loss_attrs, p_local, x_b, y_local[bs], rng_b,
                    cot_y, cot_l,
                )
                grad_acc = jax.tree_util.tree_map(
                    lambda g, d: jnp.where(b_ok, g + d, g), grad_acc, dparams
                )
                dx_send_new = jnp.where(b_ok, dx, jnp.zeros_like(dx))
                return (
                    y_send_new, dx_send_new, stash, dybuf, grad_acc,
                    loss_acc, logits,
                ), None

            init = (
                zero_b, zero_b, stash, dybuf, grad_acc, loss_acc, logits
            )
            (y_s, dx_s, stash, dybuf, grad_acc, loss_acc, logits), _ = (
                jax.lax.scan(
                    tick,
                    init,
                    (fwd_a, bwd_a, prev_f_a, prev_b_a),
                    unroll=T if self.unroll_schedule else 1,
                )
            )
            # grads: sum the data shards, scale by the microbatch/shard
            # mean factor, restore the [1, ...] stage-local slice
            grads = jax.tree_util.tree_map(
                lambda g: (jax.lax.psum(g, "data") * scale)[None],
                grad_acc,
            )
            loss = (
                jax.lax.psum(jax.lax.psum(loss_acc, "stage"), "data") * scale
            )
            logits = jax.lax.psum(logits, "stage")
            return grads, loss, logits

        body = pipeline_body
        in_specs = (
            {k: P("stage") for k in params},
            P(None, "data"),
            P(None, "data"),
            P(),
        )
        out_specs = (
            {k: P("stage") for k in params},
            P(),
            P(None, "data"),
        )
        grads, loss, logits = _shard_map(
            body, self.mesh, in_specs, out_specs
        )(params, x_mb, y_mb, rng)
        flat_logits = logits.reshape((-1,) + logits.shape[2:])
        return grads, loss, flat_logits

    # -- step --------------------------------------------------------------

    def _step(self, params, opt_state, batch_inputs, label, rng):
        trace.count(trace.STEP_TRACE)  # this body runs when JAX traces it
        batch = self._batch_value(self._cast_for_compute(batch_inputs))
        grads, loss, logits = self._pipeline_grads(
            self._cast_for_compute(params), batch, label, rng
        )
        new_params, new_opt_state = apply_optimizer(
            self.optimizer_attrs, params, grads, opt_state
        )
        metric_vals = compute_metrics(self.metrics, logits, label)
        from flexflow_tpu.observability.metrics import finalize_step

        new_params, new_opt_state, stats = finalize_step(
            self.collect_step_stats, self.guard_nonfinite_updates,
            params, new_params, grads, loss, opt_state, new_opt_state,
        )
        if stats is None:
            return new_params, new_opt_state, loss, metric_vals
        return new_params, new_opt_state, loss, metric_vals, stats

    def compiled_step(self):
        if self._jit_step is None:
            self._jit_step = jax.jit(self._step, donate_argnums=(0, 1))
        return self._jit_step

    def _record_stats(self, out):
        if self.collect_step_stats:
            self.last_step_stats = out[4]
            return out[:4]
        return out

    def train_step(self, params, opt_state, batch_inputs, label, rng=None):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        with trace.record_span("step", **self._step_span_args):
            with self.mesh, trace.record_span("dispatch"):
                out = self.compiled_step()(
                    params, opt_state, batch_inputs, label, rng
                )
        return self._record_stats(out)

    def forward(self, params, batch_inputs):
        """Inference: the sequential microbatch forward (no schedule)."""
        if self._jit_fwd is None:
            stage_fn = _make_stage_fn(self.pcg, self.structure, False)
            S = self.structure.num_stages

            def fwd(params, batch):
                x = batch
                for s in range(S):
                    p_s = {k: v[s] for k, v in params.items()}
                    x = stage_fn(p_s, x, None)
                return x

            self._jit_fwd = jax.jit(fwd)
        return self._jit_fwd(params, self._batch_value(batch_inputs))
