"""Do the Pallas attention kernels compile and agree with XLA on the chip?

`chip_smoke.py` proves the flagship's kernels; this covers the other entry
points `kernels/ops._mha_forward` dispatches to, each at the width the
model runs it (d=1024; batch cut), forward and backward, against the dense
XLA attention the same op lowers to under FLEXFLOW_TPU_FLASH=0:

- `bshf_d128`          8 heads of 128, seq 512 — the flagship kernels
- `bshf_d128_s2048`    the same at seq 2048
- `pair_qkv_d64`       16 heads of 64, self-attention: head-pair kernels
                       fed by the fused QKV projection
- `pair_d64`           16 heads of 64, distinct q/k/v operands
- `pair_qkv_shard_map` the head-pair kernels mapped over batch shards: what
                       the data-parallel backend and a batch-only searched
                       plan lower to
- `per_head_shard_map` the [b, h, s, d] entry under shard_map that a
                       head-sharded searched plan uses

With four or more devices it then trains the seq-2048 flagship (2 layers,
batch 16) through `FFModel` under the two sequence-parallel templates whose
kernels only ever ran in interpret mode: `dp1xtp1xsp4-ring`
(kernels/ring_flash.py) and `dp1xtp1xsp4-a2a` (kernels/ulysses_attention.py),
with the same checks as a `chip_smoke.py` phase. `--calibrate` also runs the
machine-calibration probes and says whether they measured the device.

One process; refuses to run without a TPU. A kernel Mosaic refuses raises
with the case name and shapes — nothing here falls back to dense attention.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

EMBED = 1024
# bf16 inputs, f32 accumulation: flash and dense differ by rounding only
REL_TOL = 3e-2

CASES = {
    "bshf_d128": dict(batch=8, seq=512, heads=8, same_qkv=True),
    "bshf_d128_s2048": dict(batch=2, seq=2048, heads=8, same_qkv=True),
    "pair_qkv_d64": dict(batch=8, seq=512, heads=16, same_qkv=True),
    "pair_d64": dict(batch=8, seq=512, heads=16, same_qkv=False),
    "pair_qkv_shard_map": dict(
        batch=8, seq=512, heads=16, same_qkv=True, mesh="batch"
    ),
    "per_head_shard_map": dict(
        batch=8, seq=512, heads=8, same_qkv=True, mesh="heads"
    ),
}
SP_SHAPES = dict(batch=16, seq=2048, embed=EMBED, heads=8, layers=2, vocab=32000)


def run_case(name, batch, seq, heads, same_qkv, mesh=None):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flexflow_tpu.kernels.context import flash_mesh
    from flexflow_tpu.kernels.ops import forward
    from flexflow_tpu.op_attrs.ops import MultiHeadAttentionAttrs
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape
    from flexflow_tpu.op_attrs.datatype import DataType

    attrs = MultiHeadAttentionAttrs(EMBED, heads)
    rs = np.random.RandomState(0)
    ts = TensorShape((batch, seq, EMBED), DataType.FLOAT)
    w_shape = attrs.weights_shape(ts, ts, ts).dims
    x = jnp.asarray(rs.randn(batch, seq, EMBED), jnp.bfloat16)
    w = jnp.asarray(rs.randn(*w_shape) / np.sqrt(EMBED), jnp.bfloat16)
    cot = jnp.asarray(rs.randn(batch, seq, EMBED), jnp.float32)
    devices = jax.devices()
    if mesh:
        # every device on the one sharded dim: the batch or the heads
        shape = (-1, 1) if mesh == "batch" else (1, -1)
        m = Mesh(np.array(devices).reshape(shape), ("data", "heads"))
        if (batch if mesh == "batch" else heads) % len(devices):
            raise ValueError(f"{name}: {mesh} over {len(devices)} devices")
        x = jax.device_put(x, NamedSharding(m, P("data")))
        cot = jax.device_put(cot, NamedSharding(m, P("data")))
        w = jax.device_put(w, NamedSharding(m, P(None, "heads")))

    def loss(x, w):
        # `x + 0` gives distinct operands: _mha_forward takes the fused-QKV
        # path only when q, k and v are one array
        q, k, v = (x, x, x) if same_qkv else (x, x + 0, x + 0)
        if mesh:
            with flash_mesh(m, "data", "heads" if mesh == "heads" else None):
                (out,) = forward(attrs, [q, k, v], [w])
        else:
            (out,) = forward(attrs, [q, k, v], [w])
        return jnp.sum(out.astype(jnp.float32) * cot)

    results = {}
    for variant, flag in (("flash", "1"), ("dense", "0")):
        # read by _mha_forward at trace time: one jit per variant
        os.environ["FLEXFLOW_TPU_FLASH"] = flag
        try:
            f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
            t0 = time.perf_counter()
            compiled = f.lower(x, w).compile()
            compile_s = time.perf_counter() - t0
            (val, (dx, dw)) = jax.block_until_ready(compiled(x, w))
        finally:
            os.environ.pop("FLEXFLOW_TPU_FLASH")
        results[variant] = dict(
            val=val, dx=dx, dw=dw, compile_s=compile_s,
            custom_calls=compiled.as_text().count("tpu_custom_call"),
        )

    def rel(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))

    fl, de = results["flash"], results["dense"]
    record = {
        "shape": dict(batch=batch, seq=seq, heads=heads, d=EMBED // heads),
        "tpu_custom_calls": fl["custom_calls"],
        "compile_s": round(fl["compile_s"], 2),
        "rel_err": {
            "loss": rel(fl["val"], de["val"]),
            "dx": rel(fl["dx"], de["dx"]),
            "dw": rel(fl["dw"], de["dw"]),
        },
    }
    print(f"[chip_kernels] {name}: {json.dumps(record)}", flush=True)
    if fl["custom_calls"] == 0 or de["custom_calls"] != 0:
        raise AssertionError(
            f"{name} {record['shape']}: flash variant has "
            f"{fl['custom_calls']} tpu_custom_call, dense has "
            f"{de['custom_calls']}"
        )
    bad = {
        k: v
        for k, v in record["rel_err"].items()
        if not (np.isfinite(v) and v < REL_TOL)
    }
    if bad:
        raise AssertionError(
            f"{name} {record['shape']}: disagrees with dense XLA: {bad}"
        )
    return record


def train_phase(name, ndev, shapes, cfg_kwargs, cache):
    """One searched-backend `chip_smoke.py` phase at other shapes."""
    import numpy as np

    import chip_smoke

    rs = np.random.RandomState(0)
    x = rs.randn(shapes["batch"], shapes["seq"], shapes["embed"]).astype(
        np.float32
    )
    y = rs.randint(0, shapes["vocab"], (shapes["batch"], shapes["seq"]))
    return chip_smoke.run_phase(
        name, "DistributedTrainingInstance", ndev, cfg_kwargs, shapes, x, y,
        cache, on_tpu=True,
    )


def run_calibration():
    """Run the machine-calibration probes and say whether each two-point
    window measured the device: a non-positive slope (the `noisy fallback`
    in kernels/profiling.profile_fn) means the windows were too short to
    rise over dispatch noise."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.compiler.calibration import _probe_sizes, calibrate
    from flexflow_tpu.kernels import profiling

    windows = []
    timed_run = profiling._timed_run

    def recording(fn, iters, args, kwargs):
        t = timed_run(fn, iters, args, kwargs)
        windows.append((iters, t))
        return t

    profiling._timed_run = recording
    try:
        cal = calibrate()
    finally:
        profiling._timed_run = timed_run
    pairs = list(zip(windows[0::2], windows[1::2]))
    fallbacks = sum(1 for (n1, t1), (n2, t2) in pairs if t2 <= t1)
    # the compute probe's matmul over one long window
    sizes = _probe_sizes()
    n = sizes.matmul_n
    a = jnp.ones((n, n), sizes.compute_dtype)
    f = jax.jit(lambda a, b: a @ b)
    jax.block_until_ready(f(a, a))
    iters = 50
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = f(a, a)
    jax.block_until_ready(out)
    long_flops = 2 * n**3 * iters / (time.perf_counter() - t0)
    record = {
        "calibration": cal.as_dict(),
        "probe_windows": len(pairs),
        "noisy_fallbacks": fallbacks,
        "shortest_long_window_ms": round(
            min(t2 for _, (_, t2) in pairs) * 1e3, 3
        ),
        "matmul_flops_probe": cal.peak_flops,
        "matmul_flops_long_window": long_flops,
    }
    print(f"[chip_kernels] calibration: {json.dumps(record)}", flush=True)
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calibrate", action="store_true")
    ap.add_argument(
        "--only", nargs="*", default=None,
        help="case names to run (default: all that fit the host)",
    )
    args = ap.parse_args()

    import jax

    import chip_smoke

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_kernels: no accelerator — platform is {dev.platform!r}",
            file=sys.stderr,
        )
        return 2
    ndev = len(jax.devices())

    from flexflow_tpu.local_execution.config import (
        configure_compilation_cache,
    )

    configure_compilation_cache()
    cache = chip_smoke._CacheCounter()
    wanted = set(args.only) if args.only is not None else None
    records = {}
    for name, case in CASES.items():
        if wanted is None or name in wanted:
            records[name] = run_case(name, **case)
    if ndev >= 4:
        for seed in ("dp1xtp1xsp4-ring", "dp1xtp1xsp4-a2a"):
            if wanted is None or seed in wanted:
                records[seed] = train_phase(
                    seed, 4, SP_SHAPES,
                    {"search_budget": 1, "force_strategy_seed": seed}, cache,
                )
    if args.calibrate:
        records["calibration"] = run_calibration()
        if ndev >= 2:
            # the search priced with the probed constants instead of the
            # machine-constants table, compiled and trained as in chip_smoke
            records["searched_calibrated"] = train_phase(
                "searched_calibrated", ndev,
                dict(SP_SHAPES, batch=64, seq=512),
                {"search_budget": 8, "cost_model": "calibrated"}, cache,
            )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": ndev,
                },
                "records": records,
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
