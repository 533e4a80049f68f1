"""The lowered StableHLO text of one benchmark cell's train step, and its
sha256 two ways: to check that a change left a cell's program alone without
running it on the chip (PR 28's method, `PERF.md` section 4).

    python tools/lowered_step_text.py <cell> [--root DIR] [--out FILE]

Builds the cell's model from `<root>/BENCHMARK.json` through
`FFModel.compile` on the CPU mesh (as many virtual devices as the cell has
chips; the kernel gates told a TPU is there) and lowers the step for the TPU
platform, Pallas kernels included, without compiling it. Prints one JSON
line:

- `sha256`: of the text as it is. A serialized Mosaic kernel body holds the
  file names and LINE NUMBERS of the Python frames it was traced under
  (`kernels/`, `local_execution/`, `parallel/`), so this one moves when a
  line is added above any call site on the way to a kernel, and when the
  checkout's path differs: compare two checkouts under ONE path (a symlink
  switched between them, given as `--root`).
- `sha256_without_locations`: of the text with every kernel body parsed
  and printed again without its debug locations. Equal for two checkouts
  exactly when their step programs, kernels included, are the same
  program; this is the one to compare.

One process a call: the platform and the device count are fixed at import.
"""

import argparse
import base64
import contextlib
import hashlib
import json
import os
import re
import sys

_BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)')


def without_locations(text: str) -> str:
    """`text` with each serialized kernel body replaced by the sha256 of its
    MLIR printed without debug info."""
    import jax._src.interpreters.mlir as jax_mlir
    from jaxlib.mlir import ir

    def plain(match):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return match.group(1) + hashlib.sha256(asm.encode()).hexdigest()

    return _BODY.sub(plain, text)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--out", help="write the text here")
    args = ap.parse_args()
    root = args.root
    sys.path[:0] = [root, os.path.join(root, "benchmark")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"

    import run as bench

    spec = bench.load_cell(os.path.join(root, "BENCHMARK.json"), args.cell)
    job, config = spec["job"], spec["config"]
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={job['chips']}"
    )

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_compilation_cache", False)
    from flexflow_tpu.analysis import lowering
    from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.kernels import context

    # the gates ask `jax.default_backend()`; steer them here, not by an option
    with context.described_tpu():
        module = bench.load_module(spec["module_path"])
        training = config["training"]
        batch = job["batch_per_chip"] * job["chips"]
        graph, logits = module.build(config, batch, job["seq"])
        model = FFModel.from_computation_graph(
            graph, logits,
            FFConfig(batch_size=batch, seed=1, print_freq=0,
                     max_devices=job["chips"], **job.get("ffconfig", {})),
        )
        model.compile(
            AdamOptimizer(
                alpha=training["alpha"], beta1=training["beta1"],
                beta2=training["beta2"], epsilon=training["epsilon"],
                weight_decay=training["weight_decay"],
            ),
            training["loss"], compute_dtype=jnp.dtype(training["compute_dtype"]),
        )
        instance = model.instance
        example = (
            lowering.step_example_args if hasattr(instance, "pcg")
            else lowering.step_example_args_cg
        )(instance, model.loss_attrs)
        mesh = (
            instance.machine_mesh.mesh if hasattr(instance, "machine_mesh")
            else contextlib.nullcontext()
        )
        with mesh:
            text = instance.compiled_step().trace(
                model.params, model.opt_state, *example
            ).lower(lowering_platforms=("tpu",)).as_text()
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps({
        "cell": args.cell,
        "root": os.path.realpath(root),
        "sha256": hashlib.sha256(text.encode()).hexdigest(),
        "sha256_without_locations": hashlib.sha256(
            without_locations(text).encode()
        ).hexdigest(),
        "chars": len(text),
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))


if __name__ == "__main__":
    main()
