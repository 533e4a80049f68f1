"""Capture an XLA profile of the headline bench step and print the top HLO ops
by self time (dev tool; analyzes where the MFU gap goes)."""

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def build_instance(seq=512, batch=64, vocab=32000, layers=12, embed=1024, heads=8):
    from flexflow_tpu.kernels.metrics import METRIC_ACCURACY  # noqa: F401
    from flexflow_tpu.local_execution import ModelTrainingInstance
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
    from flexflow_tpu.pcg import ComputationGraphBuilder

    b = ComputationGraphBuilder()
    x = b.create_input([batch, seq, embed], name="x")
    h = x
    for i in range(layers):
        # dense layers bias-free, matching the bench model (bench.py: the
        # reference Transformer passes `false /*bias*/` on every dense)
        attn = b.multihead_attention(h, h, h, embed, heads, name=f"attn{i}")
        h = b.add(h, attn)
        h = b.layer_norm(h, axes=[-1], name=f"ln1_{i}")
        ff = b.dense(h, 4 * embed, use_bias=False, name=f"ff1_{i}")
        ff = b.gelu(ff)
        ff = b.dense(ff, embed, use_bias=False, name=f"ff2_{i}")
        h = b.add(h, ff)
        h = b.layer_norm(h, axes=[-1], name=f"ln2_{i}")
    logits = b.dense(h, vocab, use_bias=False, name="head")
    inst = ModelTrainingInstance(
        b.graph,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-4),
        compute_dtype=jnp.bfloat16,
    )
    return inst, batch, seq, embed, vocab


def print_top_ops(outdir: str, steps: int, top: int = 25) -> None:
    """Parse the captured xplane with xprof and print per-op self time."""
    from xprof.convert import raw_to_tool_data as rtd

    xplanes = glob.glob(os.path.join(outdir, "plugins/profile/*/*.xplane.pb"))
    if not xplanes:
        print("no xplane.pb found under", outdir)
        return
    data, _ = rtd.xspace_to_tool_data([sorted(xplanes)[-1]], "hlo_stats", {})
    js = json.loads(data)
    cols = [c["id"] for c in js["cols"]]
    idx = {k: i for i, k in enumerate(cols)}
    rows = [[x.get("v") for x in r["c"]] for r in js["rows"]]
    rows.sort(key=lambda c: -(c[idx["total_self_time"]] or 0))
    total_ms = sum((c[idx["total_self_time"]] or 0) for c in rows) / steps / 1000
    print(f"device total: {total_ms:.1f} ms/step over {steps} steps")
    print(f"{'ms/step':>8} {'TF/s':>7} {'GB/s':>7} {'bound':<8} expression")
    for c in rows[:top]:
        ms = (c[idx["total_self_time"]] or 0) / steps / 1000
        fl = (c[idx["model_flop_rate"]] or 0) / 1000
        bw = c[idx["measured_memory_bw"]] or 0
        expr = (c[idx["hlo_op_expression"]] or "")[:90]
        print(f"{ms:8.2f} {fl:7.1f} {bw:7.1f} {str(c[idx['bound_by']]):<8} {expr}")


def main():
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    outdir = args[0] if args else "/tmp/ff_profile"
    heads = 8
    for a in sys.argv[1:]:
        if a.startswith("--heads="):
            heads = int(a.split("=")[1])
    steps = 3
    inst, batch, seq, embed, vocab = build_instance(heads=heads)
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(batch, seq, embed), jnp.float32)
    yv = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)

    # warmup/compile
    params, opt_state, loss, _ = inst.train_step(params, opt_state, {"x": xv}, yv)
    jax.block_until_ready(loss)

    with jax.profiler.trace(outdir):
        for _ in range(steps):
            params, opt_state, loss, _ = inst.train_step(
                params, opt_state, {"x": xv}, yv
            )
        jax.block_until_ready(loss)
    print("trace written to", outdir)
    print_top_ops(outdir, steps)


if __name__ == "__main__":
    main()
