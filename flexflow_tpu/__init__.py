"""flexflow_tpu: a TPU-native distributed DNN training framework.

A ground-up rebuild of the capabilities of FlexFlow-train (Unity, OSDI'22):
models are computation graphs, lifted into parallel computation graphs whose
tensors carry explicit shard/replica degrees and whose parallelization is
expressed by first-class repartition/combine/replicate/reduction operators,
then automatically parallelized by a joint search over graph substitutions and
machine mappings driven by a measured cost model.

Where the reference (see /root/reference, surveyed in SURVEY.md) executes on
Legion with CUDA/cuDNN kernels and NCCL collectives, this framework is
TPU-first: JAX/XLA/Pallas kernels, pjit/shard_map execution over ICI/DCN
device meshes, with searched strategies lowering to XLA collectives.

Layer map (mirrors SURVEY.md §1, re-architected for TPU):
  utils       -- graph library, SP decomposition, containers
  op_attrs    -- operator attributes + dual (sequential/parallel) shape inference
  pcg         -- ComputationGraph / ParallelComputationGraph + builders,
                 MachineView/MachineSpecification for TPU meshes
  kernels     -- JAX/XLA/Pallas per-op forward/backward; collectives
  local_execution -- single-host training backing + measured cost estimator
  substitutions   -- PCG rewrite engine (pattern match + apply)
  compiler    -- machine-mapping DP + Unity joint search
  runtime     -- PCG -> pjit/shard_map lowering, distributed training driver
  models      -- model zoo (transformer, bert, candle-uno, inception-v3, ...)
"""

import os

__version__ = "0.1.0"


def _process_age_s(proc: str = "/proc"):
    """Seconds since this process started, from its start time in
    `<proc>/self/stat` (clock ticks after boot, 10 ms) against
    `<proc>/uptime`; None where they cannot be read."""
    try:
        with open(os.path.join(proc, "self", "stat")) as f:
            # the fields after the command's closing bracket start at the
            # third; the start time is the 22nd
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open(os.path.join(proc, "uptime")) as f:
            uptime = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return None
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


# what the process spent before the program's first line: interpreter start,
# the caller's imports (`import jax`), the backend's start-up where the caller
# asked for `jax.devices()` first (`observability.trace.pre_program_s`)
PROCESS_AGE_AT_IMPORT_S = _process_age_s()
