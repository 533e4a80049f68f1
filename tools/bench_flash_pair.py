"""Microbench: flash_attention_bshf fwd / fwd+bwd at the reference-default
heads=16 (d=64, head-pair kernels) vs the headline heads=8 (d=128), same
total width — isolates the pair-kernel efficiency gap from the rest of the
step (dev tool for the heads=16 MFU work)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.kernels.flash_attention import flash_attention_bshf
from flexflow_tpu.kernels.profiling import force_sync


def timeit(f, *args, iters=30):
    r = f(*args)
    force_sync(r)

    def run(n):
        t0 = time.perf_counter()
        for _ in range(n):
            r = f(*args)
        force_sync(r)
        return time.perf_counter() - t0

    # median of five two-point measurements (cancels the fixed
    # per-window dispatch and sync cost; see bench.py)
    meas = []
    for _ in range(5):
        t1 = run(3)
        t2 = run(3 + iters)
        meas.append((t2 - t1) / iters * 1000)
    meas.sort()
    return meas[2], meas[3] - meas[1]


def main():
    b, s, f = 64, 512, 1024
    causal = "--causal" in sys.argv
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(b, s, f), jnp.bfloat16)
    k = jnp.asarray(rs.randn(b, s, f), jnp.bfloat16)
    v = jnp.asarray(rs.randn(b, s, f), jnp.bfloat16)

    flops_fwd = 2 * 2 * b * s * s * f  # qk + pv, mult-add
    for h in (8, 16):
        fwd = jax.jit(
            lambda q, k, v, h=h: flash_attention_bshf(q, k, v, h, causal=causal)
        )

        def loss(q, k, v, h=h):
            return jnp.sum(
                flash_attention_bshf(q, k, v, h, causal=causal).astype(
                    jnp.float32
                )
            )

        both = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        t_fwd, s_fwd = timeit(fwd, q, k, v)
        t_both, s_both = timeit(both, q, k, v)
        print(
            f"h={h:2d} d={f // h:3d}: fwd {t_fwd:6.3f}±{s_fwd:5.3f} ms "
            f"({flops_fwd / t_fwd / 1e9:6.1f} TF/s)  "
            f"fwd+bwd {t_both:6.3f}±{s_both:5.3f} ms "
            f"({(3.5 * flops_fwd) / t_both / 1e9:6.1f} TF/s)"
        )


if __name__ == "__main__":
    main()
