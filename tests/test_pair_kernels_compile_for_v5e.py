"""The d=64 head-pair attention kernels, compiled at the benchmark cells' call
shapes for a DESCRIBED TPU v5e (the TPU's compiler is installed here; no chip
is attached and nothing runs). Interpret mode, which every other kernel test
uses, does not see what Mosaic refuses: more scoped VMEM than a program may
hold (`_batch_block` at s = 128 folded 32 rows, PR 31), a shape cast or a
transpose it cannot lay out. A compile that passes is not a chip run and
says nothing of speed; the chip's numbers are in PERF.md, section 6.

Describing the topology loads the TPU's library, which a process keeps until
it exits, and a described-chip executable must stay out of the persistent
compile cache (it is written there but cannot be read back without a chip).
So every compile runs in ONE child process, pinned to the CPU like every
child this repo starts: `python tests/test_pair_kernels_compile_for_v5e.py`
prints one JSON object, case -> "ok" or the compiler's complaint, and the
test worker's own JAX is left as it was. The tests are skipped only where
the TPU's library is not installed; anything else the child fails on fails
them."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

# rows and length of one call: the seq-512 cells (24 rows on one chip, 16 a
# chip on four), the seq-128 cell, the longest one tile holds, and the
# lengths 256 does not divide, where a causal program takes 128 queries at a
# time
CALLS = [
    (24, 512), (16, 512), (64, 128), (8, 1024), (8, 384), (4, 640), (4, 896),
]
CASES = [
    f"{entry}-{rows}x{s}-{'causal' if causal else 'full'}"
    for entry, calls in (("fused_qkv", CALLS), ("separate", [(24, 512)]))
    for rows, s in calls
    for causal in (False, True)
]


def compile_cases():
    """The child's part: compile every case for the described chip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from flexflow_tpu.kernels import flash_attention as fa

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    chip = SingleDeviceSharding(topo.devices[0])
    results = {}
    for case in CASES:
        entry, shape, mask = case.split("-")
        rows, s = map(int, shape.split("x"))
        causal = mask == "causal"
        if entry == "fused_qkv":
            # bf16 [rows, s, 3072], 16 heads of 64: the forward and, through
            # its lse, the fused backward, at the fold _batch_block gives
            args = [
                jax.ShapeDtypeStruct(
                    (rows, s, 3072), jnp.bfloat16, sharding=chip
                )
            ]
            fn = jax.grad(
                lambda x: jnp.sum(
                    fa.flash_attention_bshf_qkv(x, 16, causal=causal).astype(
                        jnp.float32
                    )
                )
            )
            want = 2
        else:
            # the entry a plan takes when it cannot fuse the projection
            args = 3 * [
                jax.ShapeDtypeStruct(
                    (rows, s, 1024), jnp.bfloat16, sharding=chip
                )
            ]
            fn = lambda q, k, v: fa.flash_attention_bshf(
                q, k, v, 16, causal=causal
            )
            want = 1
        try:
            text = jax.jit(fn).lower(*args).compile().as_text()
            n = text.count("tpu_custom_call")
            results[case] = "ok" if n >= want else f"{n} kernels, want {want}"
        except Exception as e:  # noqa: BLE001 - the complaint is the result
            results[case] = f"{type(e).__name__}: {e}"[:2000]
    return results


@pytest.fixture(scope="module")
def compiled():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU's compiler (libtpu) is not installed here")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
        ALLOW_MULTIPLE_LIBTPU_LOAD="1",
    )
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env, timeout=900,
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert child.returncode == 0, child.stderr[-4000:]
    return json.loads(child.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_pair_kernels_compile_for_the_described_chip(compiled, case):
    assert compiled[case] == "ok"


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    print(json.dumps(compile_cases()))
