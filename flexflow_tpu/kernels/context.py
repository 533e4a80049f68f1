"""What a kernel may ask of the trace it is lowered in, and what it chose.

The lowest layer of the program: nothing here imports another module of
`flexflow_tpu`. Three facts of the lowering in progress on this thread, each
set by the graph walkers (`local_execution/training_backing.py`,
`parallel/executor.py`, `parallel/data_parallel.py`) around a node's
`kernel_forward` and read by the kernels' route rules:

- the mesh declared for the node (`flash_mesh`, read by `declared_mesh`):
  under one, a kernel is mapped over the shards instead of being called bare;
- whether bare Pallas calls are refused (`no_flash`, read by
  `bare_calls_refused`): a `pallas_call` has no SPMD partitioning rule;
- the node being lowered (`lowering_node`, which
  `observability/trace.node_scope` enters; read by `lowering_scope`).

Two facts of the process: the backend (`on_tpu`) and interpret mode
(`interpret_default`). ONE rule over them, `admits_bare_pallas_call`, for
every route rule whose kernels exist only as a bare call; attention's gates
(`flash_attention_supported`, `ops.mha_core_route`) are asked under a declared
mesh too and read the accessors themselves.

What the rules chose is the answer to those questions and is kept beside
them: `note(kind, value)` files `value` under (the node being lowered, kind),
`choices(kind)` reads the table. A kind's vocabulary is documented where it is
noted (`kernels/ops._note_*`, `kernels/moe._note_*`, `kernels/kda.py`,
`kernels/ssm.py`); `observability/trace.kernel_choices` is the view a reader
outside `kernels/` takes.

`described_tpu` is for tests and tools that lower for the described chip on a
host without one; nothing under `flexflow_tpu/` enters it.
"""

import contextlib
import copy
import os
import threading
from typing import Dict, Optional

import jax

_tls = threading.local()
# nesting depth of `described_tpu`: a fact of the process, as the backend is
_described_tpu = 0
# {kind: {scope: value}} as each node was lowered last in this process
_CHOICES: Dict[str, Dict[str, object]] = {}


@contextlib.contextmanager
def _fact(name: str, value):
    previous = getattr(_tls, name, None)
    setattr(_tls, name, value)
    try:
        yield
    finally:
        setattr(_tls, name, previous)


def flash_mesh(mesh, batch_axes, head_axes, interpret: bool = False):
    """Declare the SPMD context for kernels traced within: the mesh plus the
    PartitionSpec entries of the node's batch and head dims. `_mha_forward`
    consults this to map its kernels over the shards (shard_map) instead of
    emitting a bare (unpartitionable) pallas_call: the one-chip fused-row
    dispatch per batch shard when heads are whole (`head_axes is None`),
    sharded_flash_attention on [b, h, s, d] when they are split."""
    return _fact("mesh", (mesh, batch_axes, head_axes, interpret))


def declared_mesh():
    """The `(mesh, batch_axes, head_axes, interpret)` of the innermost open
    `flash_mesh`, or None."""
    return getattr(_tls, "mesh", None)


def no_flash():
    """Refuse a bare pallas_call within this trace (used by the distributed
    executor: a pallas_call has no SPMD partitioning rule). What admits a
    kernel to a sharded global-view program is a declared `flash_mesh`,
    under which the kernel is mapped over the shards; a node lowered with
    none declared keeps XLA's form."""
    return _fact("refused", True)


def bare_calls_refused() -> bool:
    return bool(getattr(_tls, "refused", None))


def lowering_node(scope: Optional[str]):
    """`scope` (`ff.<kind>.<name>`) is the node being lowered on this thread
    within: what `note` files a choice under. None closes it."""
    return _fact("scope", scope)


def lowering_scope() -> Optional[str]:
    return getattr(_tls, "scope", None)


def on_tpu(allow_interpret: bool = False) -> bool:
    """Is the program being lowered for a TPU (or, where the caller can run
    its kernels in interpret mode, the CPU). A backend that fails to
    initialise raises here: taking XLA's form instead would hide the device
    from the run."""
    if _described_tpu:
        return True
    backend = jax.default_backend()
    return backend == "tpu" or (allow_interpret and backend == "cpu")


def interpret_default() -> bool:
    """Pallas interpret mode: only for CPU-mesh tests, opted in via env."""
    return (
        jax.default_backend() == "cpu"
        and os.environ.get("FLEXFLOW_TPU_FLASH_INTERPRET", "0") == "1"
    )


def admits_bare_pallas_call(allow_interpret: bool = False) -> bool:
    """THE rule of a route whose kernels exist only as a bare `pallas_call`:
    on a TPU (`on_tpu(allow_interpret)`, asked first so that a backend that
    fails to initialise raises whatever else holds), no mesh declared (a
    sharded form is a separate route or none) and not under `no_flash()`.
    Asked after the caller's own shape tests."""
    return (
        on_tpu(allow_interpret)
        and declared_mesh() is None
        and not bare_calls_refused()
    )


@contextlib.contextmanager
def described_tpu():
    """`on_tpu()` is true within, whatever the backend: how a test or a tool
    lowers for the described chip on a host without one. Not an option of
    the program."""
    global _described_tpu
    _described_tpu += 1
    try:
        yield
    finally:
        _described_tpu -= 1


def note(kind: str, value) -> None:
    """File what the node being lowered chose under (its scope, `kind`);
    dropped where no node's scope is open (a kernel called by itself)."""
    scope = lowering_scope()
    if scope is not None:
        _CHOICES.setdefault(kind, {})[scope] = copy.deepcopy(value)


def choices(kind: Optional[str] = None) -> dict:
    """`{scope: value}` of `kind`, or `{scope: {kind: value}}` of every kind,
    for every node this process has lowered, as it was lowered last; fresh
    copies."""
    if kind is not None:
        return copy.deepcopy(_CHOICES.get(kind, {}))
    by_scope: Dict[str, dict] = {}
    for k, noted in _CHOICES.items():
        for scope, value in noted.items():
            by_scope.setdefault(scope, {})[k] = copy.deepcopy(value)
    return by_scope
