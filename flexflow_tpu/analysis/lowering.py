"""Shared (PCG, machine mapping) -> lowered step program helper (ISSUE 11).

Both static cross-checks that need the COMPILED donated train step — the
`--plan-audit` XLA memory cross-check (`FFModel._xla_memory_cross_check`,
ISSUE 10) and the communication census (`analysis/comm_analysis.py`,
`ffcheck --comm`) — used to each lower and compile the step themselves,
paying the XLA compile twice per plan. This module factors the one step:
build (or reuse) a `DistributedTrainingInstance`, stage zero-filled
example arguments under the plan's shardings, `lower(...).compile()`
ONCE, and hand back a `LoweredStepProgram` whose HLO text and
`memory_analysis()` both consumers read. Lower-only: nothing here ever
executes the program.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional


def find_logit_tensor(pcg):
    """The model output: the last unconsumed non-weight dataflow output in
    topological order (the same unique-sink rule FFModel falls back to
    when layer names are absent)."""
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    sink = None
    for n in pcg.topological_ordering():
        if isinstance(pcg.op_attrs(n), WeightAttrs):
            continue
        for o in pcg.outputs_of(n):
            if not pcg.uses_of(o):
                sink = o
    if sink is None:
        raise ValueError("PCG has no unconsumed output to treat as logits")
    return sink


def build_step_instance(
    pcg,
    mapping: Optional[dict] = None,
    machine_spec=None,
    loss_attrs=None,
    optimizer_attrs=None,
    seed: int = 0,
):
    """Standalone-instance path (ffcheck: no FFModel exists): a
    `DistributedTrainingInstance` over the plan with a default SCCE loss
    and SGD optimizer, initialized parameters included. The optimizer
    choice does not change which movement-edge collectives lower — the
    gradient syncs live in the backward pass — it only adds the
    elementwise update."""
    import jax

    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance
    from flexflow_tpu.parallel.mesh import MachineMesh
    from flexflow_tpu.pcg.machine_view import MachineSpecification
    from flexflow_tpu.pcg.optimizer import SGDOptimizerAttrs

    if machine_spec is None:
        ndev = len(jax.devices())
        machine_spec = MachineSpecification(1, 1, ndev, 25.0, 400.0)
    if machine_spec.num_devices > len(jax.devices()):
        raise ValueError(
            f"machine spec wants {machine_spec.num_devices} devices but "
            f"only {len(jax.devices())} are attached (set "
            "--xla_force_host_platform_device_count before jax imports)"
        )
    la = loss_attrs or SparseCategoricalCrossEntropyLossAttrs()
    oa = optimizer_attrs or SGDOptimizerAttrs(lr=0.01)
    from flexflow_tpu.pcg.pipeline import analyze_pipeline

    region = analyze_pipeline(pcg)
    if region is not None and region.ok:
        # stage-partitioned plan: the program whose collectives the census
        # must count is the 1F1B schedule's (the flat lowering is identity
        # on stage ops and would show NO inter-stage traffic). The
        # schedule scan is UNROLLED so the census sees every microbatch's
        # collective-permute hop — the M-repeats pattern the matcher pools
        # against the stage-edge predictions.
        from flexflow_tpu.parallel.pipeline import (
            PipelinedTrainingInstance,
            PipelineUnsupported,
        )

        try:
            inst = PipelinedTrainingInstance(
                pcg,
                find_logit_tensor(pcg),
                la,
                oa,
                devices=jax.devices()[: machine_spec.num_devices],
                unroll_schedule=True,
            )
        except PipelineUnsupported:
            # not 1F1B-executable (and a malformed region above skips
            # this branch entirely): execution falls back to the flat
            # GSPMD program — stage ops are value-identity — so THAT is
            # the program whose collectives the census must count; the
            # priced stage edges then rightly read as overpaid (COMM002)
            inst = None
        if inst is not None:
            params, opt_state = inst.initialize(seed=seed)
            return inst, params, opt_state
    mm = MachineMesh.from_spec(machine_spec)
    inst = DistributedTrainingInstance(
        pcg,
        find_logit_tensor(pcg),
        la,
        oa,
        mm,
        mapping=mapping,
    )
    params, opt_state = inst.initialize(seed=seed)
    return inst, params, opt_state


def _example_label(logit_dims, loss_attrs, label_dtype):
    """Zero-filled label derived from the logit shape — class indices
    (sparse CE; a step whose loss is its loss nodes alone, which `fit` hands
    the same integer labels) drop the class dim and default to int32, dense
    losses mirror the logits (shared by the PCG and CG example-argument
    builders)."""
    import jax.numpy as jnp

    from flexflow_tpu.op_attrs.ops.loss_functions import LossFunction

    sparse = loss_attrs.loss_type in (
        LossFunction.SPARSE_CATEGORICAL_CROSSENTROPY, LossFunction.LOSS_NODES
    )
    label_dims = logit_dims[:-1] if sparse else logit_dims
    if label_dtype is None:
        label_dtype = jnp.int32 if sparse else jnp.float32
    return jnp.zeros(tuple(label_dims), label_dtype)


def step_example_args(instance, loss_attrs, label_dtype=None):
    """Zero-filled (batch, label, rng) staged under the instance's
    shardings — the example arguments the step program lowers against
    (exactly what `FFModel._xla_memory_cross_check` built inline)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.op_attrs.ops import InputAttrs
    from flexflow_tpu.op_attrs.parallel_tensor_shape import get_reduced_shape
    from flexflow_tpu.parallel.executor import param_key

    pcg = instance.pcg
    batch: Dict[str, object] = {}
    for n in pcg.topological_ordering():
        la = pcg.layer_attrs(n)
        if not isinstance(la.attrs, InputAttrs):
            continue
        (out,) = pcg.outputs_of(n)
        ts = get_reduced_shape(pcg.tensor_shape(out))
        arr = jnp.zeros(ts.dims, ts.dtype.to_jnp())
        s = instance.shardings.get(out)
        key = la.name or param_key(n)
        batch[key] = jax.device_put(arr, s) if s is not None else arr
    logit_ts = get_reduced_shape(
        pcg.tensor_shape(instance.loss_logit_tensor)
    )
    label = _example_label(logit_ts.dims, loss_attrs, label_dtype)
    ls = instance.label_sharding()
    if ls is not None:
        label = jax.device_put(label, ls)
    return batch, label, jax.random.PRNGKey(0)


@dataclass
class LoweredStepProgram:
    """One compiled donated train step, shared by the memory,
    communication, and execution-contract cross-checks."""

    instance: object
    compiled: object  # jax.stages.Compiled
    # the pre-compile jax.stages.Lowered: the execution-contract pass
    # (analysis/exec_contract.py) reads its args_info (donation spec) and
    # canonical StableHLO fingerprint
    lowered: object = None
    _hlo_text: Optional[str] = field(default=None, repr=False)

    def hlo_text(self) -> str:
        """The post-partitioning optimized HLO module — the program whose
        collectives the comm census counts (GSPMD inserts them during
        compile, so the pre-compile StableHLO would show only sharding
        custom-calls)."""
        if self._hlo_text is None:
            self._hlo_text = self.compiled.as_text()
        return self._hlo_text

    def memory_analysis(self):
        return self.compiled.memory_analysis()


def lower_step_program(
    instance,
    params,
    opt_state,
    loss_attrs,
    label_dtype=None,
) -> LoweredStepProgram:
    """Lower + compile the instance's donated step ONCE (never execute)."""
    from flexflow_tpu.observability import step_account
    from flexflow_tpu.observability.trace import record_span

    with record_span("compile/lower_step", compiled=True):
        batch, label, rng = step_example_args(
            instance, loss_attrs, label_dtype=label_dtype
        )
        with instance.machine_mesh.mesh:
            lowered = instance.compiled_step().lower(
                params, opt_state, batch, label, rng
            )
            compiled = lowered.compile()
    # the step `step_account.last()` accounts, if anyone asks
    step_account.note_step(instance, compiled)
    return LoweredStepProgram(
        instance=instance, compiled=compiled, lowered=lowered
    )


def step_example_args_cg(instance, loss_attrs, label_dtype=None):
    """Zero-filled (batch, label, rng) for a ComputationGraph-backed
    instance (ModelTrainingInstance / DataParallelTrainingInstance) —
    the trace-only fingerprint path's example arguments. They are placed
    as the dataloader places real batches (the DP instance's input/label
    shardings): jit keys its trace on argument placement, so arguments
    placed any other way trace the step a second time, under other
    private function names, and the executable compiled from this
    lowering is then not the one fit() reuses."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.op_attrs.ops import InputAttrs
    from flexflow_tpu.parallel.executor import param_key

    def place(arr, sharding):
        return arr if sharding is None else jax.device_put(arr, sharding)

    sharded = hasattr(instance, "input_sharding")
    cg = instance.cg
    batch: Dict[str, object] = {}
    for n in cg.topological_ordering():
        la = cg.layer_attrs(n)
        if not isinstance(la.attrs, InputAttrs):
            continue
        (out,) = cg.outputs_of(n)
        ts = cg.tensor_shape(out)
        key = la.name or param_key(n)
        batch[key] = place(
            jnp.zeros(tuple(ts.dims), ts.dtype.to_jnp()),
            instance.input_sharding(key) if sharded else None,
        )
    logit_ts = cg.tensor_shape(instance.logit_tensor)
    label = place(
        _example_label(logit_ts.dims, loss_attrs, label_dtype),
        instance.label_sharding() if sharded else None,
    )
    return batch, label, jax.random.PRNGKey(0)


def lower_step_trace(
    instance, loss_attrs, label_dtype=None, params=None, opt_state=None
):
    """Trace + lower (NO XLA compile) the instance's donated step against
    zero-filled example arguments — the cheap path behind the
    exec-contract `program_fingerprint` on backends whose compile never
    lowers statically (DP / single-device). Returns the
    `jax.stages.Lowered`."""
    import contextlib

    from flexflow_tpu.observability import step_account
    from flexflow_tpu.observability.trace import record_span

    if params is None:
        params, opt_state = instance.initialize(seed=0)
    with record_span("compile/lower_step", compiled=False):
        example_args = (
            step_example_args if hasattr(instance, "pcg")
            else step_example_args_cg
        )
        batch, label, rng = example_args(
            instance, loss_attrs, label_dtype=label_dtype
        )
        mesh = getattr(instance, "machine_mesh", None)
        with mesh.mesh if mesh is not None else contextlib.nullcontext():
            lowered = instance.compiled_step().lower(
                params, opt_state, batch, label, rng
            )
    # a reference and nothing else: JAX's lowering cache holds the module
    # anyway, and `step_account.last()` compiles it only if someone asks
    step_account.note_step(instance, lowered)
    return lowered


def lower_plan(
    pcg,
    mapping: Optional[dict] = None,
    machine_spec=None,
    loss_attrs=None,
    optimizer_attrs=None,
) -> LoweredStepProgram:
    """ffcheck's standalone path: (PCG, mapping) -> compiled step in one
    call (instance built here, zero-init parameters)."""
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )

    la = loss_attrs or SparseCategoricalCrossEntropyLossAttrs()
    inst, params, opt_state = build_step_instance(
        pcg, mapping, machine_spec=machine_spec,
        loss_attrs=la, optimizer_attrs=optimizer_attrs,
    )
    return lower_step_program(inst, params, opt_state, la)
