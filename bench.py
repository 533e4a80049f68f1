"""Benchmark entry: prints ONE JSON line with the headline metric.

Current headline: GPT-style Transformer (reference examples/cpp/Transformer
config family, scaled to fit one chip) training step — reports MFU on the
real TPU chip. vs_baseline is measured against the 35% MFU target from
BASELINE.md (vs_baseline = achieved_mfu / 0.35).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np


def peak_flops_per_device() -> float:
    """Peak bf16 matmul FLOP/s of the attached device kind, from the
    package's one sourced table (an unknown kind raises)."""
    from flexflow_tpu.compiler.machine_constants import machine_constants

    return machine_constants().peak_flops


def build_flagship_cg(
    batch=64, seq=512, embed=1024, heads=8, layers=12, vocab=32000
):
    """The headline 12-layer transformer (reference
    examples/cpp/Transformer/transformer.cc:80-100 family). Single source
    of truth for both the chip bench and the search-time measurement."""
    from flexflow_tpu.pcg import ComputationGraphBuilder

    b = ComputationGraphBuilder()
    x = b.create_input([batch, seq, embed], name="x")
    h = x
    for i in range(layers):
        # MHA bias on (the reference builder's default,
        # computation_graph_builder.h:236); dense layers bias-FREE — every
        # dense in the reference Transformer passes `false /*bias*/`
        # (examples/cpp/Transformer/transformer.cc:41-74,158)
        attn = b.multihead_attention(h, h, h, embed, heads, name=f"attn{i}")
        h = b.add(h, attn)
        h = b.layer_norm(h, axes=[-1], name=f"ln1_{i}")
        ff = b.dense(h, 4 * embed, use_bias=False, name=f"ff1_{i}")
        ff = b.gelu(ff)
        ff = b.dense(ff, embed, use_bias=False, name=f"ff2_{i}")
        h = b.add(h, ff)
        h = b.layer_norm(h, axes=[-1], name=f"ln2_{i}")
    logits = b.dense(h, vocab, use_bias=False, name="head")
    return b.graph, logits


def build_flagship_pcg(
    batch=64, seq=512, embed=1024, heads=8, layers=12, vocab=32000
):
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )

    graph, _ = build_flagship_cg(batch, seq, embed, heads, layers, vocab)
    return pcg_from_computation_graph(graph)


def _model_step_flops(batch, seq, embed, heads, layers, vocab):
    d_ff = 4 * embed
    per_layer = (
        2 * batch * seq * embed * embed * 4
        + 2 * batch * heads * seq * seq * (embed // heads) * 2
        + 2 * batch * seq * embed * d_ff * 2
    )
    return 3 * (layers * per_layer + 2 * batch * seq * embed * vocab)


def _measure(batch, seq, embed, heads, layers, vocab, samples=3):
    """Build the flagship at the given shapes and two-point-measure one
    training step; returns mfu / step_ms / tokens_per_s."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import time

    from flexflow_tpu.local_execution import ModelTrainingInstance
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
    from flexflow_tpu.kernels.profiling import force_sync

    graph, logits = build_flagship_cg(batch, seq, embed, heads, layers, vocab)
    inst = ModelTrainingInstance(
        graph,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-4),
        compute_dtype=jnp.bfloat16,
    )
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(batch, seq, embed), jnp.float32)
    yv = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)

    def run(iters, params, opt_state):
        start = time.perf_counter()
        loss = None
        for _ in range(iters):
            params, opt_state, loss, _ = inst.train_step(
                params, opt_state, {"x": xv}, yv
            )
        force_sync(loss)
        return time.perf_counter() - start, params, opt_state

    _, params, opt_state = run(1, params, opt_state)  # compile
    meas = []
    for _ in range(samples):
        t1, params, opt_state = run(2, params, opt_state)
        t2, params, opt_state = run(10, params, opt_state)
        s = (t2 - t1) / 8
        meas.append(s if s > 0 else t2 / 10)
    step = sorted(meas)[len(meas) // 2]
    flops = _model_step_flops(batch, seq, embed, heads, layers, vocab)
    return {
        "mfu": round(flops / step / peak_flops_per_device(), 4),
        "step_ms": round(step * 1000, 3),
        "tokens_per_s": round(batch * seq / step, 1),
    }


def _graph_fwd_flops(cg) -> int:
    """Analytic forward FLOPs of a computation graph: sum of
    op_forward_flops over every node at its full (serial) tensor shapes —
    the same counter the analytic cost model prices plans with."""
    from flexflow_tpu.kernels.ops import op_forward_flops
    from flexflow_tpu.local_execution.training_backing import (
        split_slot_values,
    )

    total = 0
    for n in cg.topological_ordering():
        attrs = cg.op_attrs(n)
        in_shapes = [cg.tensor_shape(t) for t in cg.inputs_of(n)]
        out_shapes = [cg.tensor_shape(t) for t in cg.outputs_of(n)]
        data, weights = split_slot_values(attrs, in_shapes)
        try:
            total += op_forward_flops(
                attrs, data, out_shapes, weight_shapes=weights or None
            )
        except (AssertionError, IndexError, TypeError, ValueError):
            continue
    return total


def _alexnet_model(batch, image, classes):
    """Compiled AlexNet FFModel (reference examples/cpp/AlexNet/alexnet.cc:
    94-116) — the shared build of the per-step, fused, and roofline
    measurements."""
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "examples"))
    from alexnet import build_alexnet

    m = FFModel(FFConfig(batch_size=batch, seed=0))
    _, logits = build_alexnet(m, batch, image, classes)
    m.compile(
        SGDOptimizer(lr=0.01, momentum=0.9),
        "sparse_categorical_crossentropy",
        logit_tensor=logits,
        compute_dtype=jnp.bfloat16,
    )
    return m


def _measure_alexnet(batch=64, image=229, classes=1000, samples=5,
                     n1=5, n2=45):
    """Conv-net chip number (round-4 verdict next-step #5): AlexNet
    fwd+bwd+SGD single-chip (reference examples/cpp/AlexNet/alexnet.cc:
    94-116 network at its 229 image size)."""
    import time

    from flexflow_tpu.kernels.profiling import force_sync

    m = _alexnet_model(batch, image, classes)
    rs = np.random.RandomState(0)
    xv = rs.randn(batch, 3, image, image).astype(np.float32)
    yv = rs.randint(0, classes, batch).astype(np.int32)
    it = m._make_iterator(xv, yv, batch, shuffle=False)
    batch_dev, label_dev = next(iter(it))
    rng = jax.random.PRNGKey(0)

    def run(iters):
        nonlocal rng
        start = time.perf_counter()
        loss = None
        for _ in range(iters):
            rng, srng = jax.random.split(rng)
            m.params, m.opt_state, loss, _ = m.instance.train_step(
                m.params, m.opt_state, batch_dev, label_dev, srng
            )
        force_sync(loss)
        return time.perf_counter() - start

    run(1)  # compile
    # steps are a few ms, so a short window is mostly host dispatch
    # jitter. Long two-point windows amortize the per-dispatch cost;
    # contention only ever ADDS
    # time to a window, so the mins are taken over the t1 and t2 windows
    # SEPARATELY before subtracting (min of the differences would select
    # exactly the sample whose t1 window caught a jitter burst).
    t1s, t2s = [], []
    for _ in range(samples):
        t1s.append(run(n1))
        t2s.append(run(n2))
    step = (min(t2s) - min(t1s)) / (n2 - n1)
    if step <= 0:
        step = min(t2s) / n2
    flops = 3 * _graph_fwd_flops(m.cg)
    return {
        "mfu": round(flops / step / peak_flops_per_device(), 4),
        "step_ms": round(step * 1000, 3),
        "images_per_s": round(batch / step, 1),
    }


def _measure_alexnet_fused(batch=64, image=229, classes=1000, k=8,
                           samples=5, n1=5, n2=45):
    """AlexNet under fused multi-step dispatch (steps_per_dispatch=k): the
    same network and two-point window discipline as _measure_alexnet, but
    each dispatch is ONE donated XLA program covering k steps
    (instance.multi_train_step over a stacked [k, batch, ...] window).
    n1/n2 are STEP counts matching the per-step measurement; they round up
    to whole windows so both measurements amortize over comparable work."""
    import time

    from flexflow_tpu.kernels.profiling import force_sync

    m = _alexnet_model(batch, image, classes)
    rs = np.random.RandomState(0)
    xw = jnp.asarray(
        rs.randn(k, batch, 3, image, image).astype(np.float32)
    )
    yw = jnp.asarray(rs.randint(0, classes, (k, batch)), jnp.int32)
    rng = jax.random.PRNGKey(0)

    def run(windows):
        nonlocal rng
        start = time.perf_counter()
        losses = None
        for _ in range(windows):
            m.params, m.opt_state, rng, losses, _, _ = (
                m.instance.multi_train_step(
                    m.params, m.opt_state, {"image": xw}, yw, rng
                )
            )
        force_sync(losses)
        return time.perf_counter() - start

    w1, w2 = max(1, n1 // k), max(2, -(-n2 // k))
    run(1)  # compile
    t1s, t2s = [], []
    for _ in range(samples):
        t1s.append(run(w1))
        t2s.append(run(w2))
    step = (min(t2s) - min(t1s)) / ((w2 - w1) * k)
    if step <= 0:
        step = min(t2s) / (w2 * k)
    flops = 3 * _graph_fwd_flops(m.cg)
    return {
        "mfu": round(flops / step / peak_flops_per_device(), 4),
        "step_ms": round(step * 1000, 3),
        "images_per_s": round(batch / step, 1),
        "steps_per_dispatch": k,
    }


def _measure_flagship_fused(batch, seq, embed, heads, layers, vocab,
                            k=4, samples=3, n1=2, n2=10):
    """Fused flagship block: the headline transformer driven through
    instance.multi_train_step at steps_per_dispatch=k, per-step and fused
    step time from the same build so the delta is pure dispatch."""
    import time

    from flexflow_tpu.kernels.profiling import force_sync
    from flexflow_tpu.local_execution import ModelTrainingInstance
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

    graph, logits = build_flagship_cg(batch, seq, embed, heads, layers, vocab)
    inst = ModelTrainingInstance(
        graph,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-4),
        compute_dtype=jnp.bfloat16,
    )
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(batch, seq, embed), jnp.float32)
    yv = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)
    xw = jnp.asarray(rs.randn(k, batch, seq, embed), jnp.float32)
    yw = jnp.asarray(rs.randint(0, vocab, (k, batch, seq)), jnp.int32)
    rng = jax.random.PRNGKey(0)

    def run_steps(iters, params, opt_state):
        start = time.perf_counter()
        loss = None
        for _ in range(iters):
            params, opt_state, loss, _ = inst.train_step(
                params, opt_state, {"x": xv}, yv
            )
        force_sync(loss)
        return time.perf_counter() - start, params, opt_state

    def run_windows(windows, params, opt_state, rng):
        start = time.perf_counter()
        losses = None
        for _ in range(windows):
            params, opt_state, rng, losses, _, _ = inst.multi_train_step(
                params, opt_state, {"x": xw}, yw, rng
            )
        force_sync(losses)
        return time.perf_counter() - start, params, opt_state, rng

    _, params, opt_state = run_steps(1, params, opt_state)  # compile
    meas = []
    for _ in range(samples):
        t1, params, opt_state = run_steps(n1, params, opt_state)
        t2, params, opt_state = run_steps(n2, params, opt_state)
        s = (t2 - t1) / (n2 - n1)
        meas.append(s if s > 0 else t2 / n2)
    step = sorted(meas)[len(meas) // 2]
    _, params, opt_state, rng = run_windows(1, params, opt_state, rng)
    w1, w2 = max(1, n1 // k), max(2, -(-n2 // k))
    meas = []
    for _ in range(samples):
        t1, params, opt_state, rng = run_windows(w1, params, opt_state, rng)
        t2, params, opt_state, rng = run_windows(w2, params, opt_state, rng)
        s = (t2 - t1) / ((w2 - w1) * k)
        meas.append(s if s > 0 else t2 / (w2 * k))
    fused_step = sorted(meas)[len(meas) // 2]
    flops = _model_step_flops(batch, seq, embed, heads, layers, vocab)
    return {
        "steps_per_dispatch": k,
        "shapes": {
            "batch": batch, "seq": seq, "embed": embed,
            "heads": heads, "layers": layers, "vocab": vocab,
        },
        "step_ms": round(step * 1000, 3),
        "fused_step_ms": round(fused_step * 1000, 3),
        "dispatch_overhead_ms": round((step - fused_step) * 1000, 3),
        "mfu": round(flops / step / peak_flops_per_device(), 4),
        "fused_mfu": round(
            flops / fused_step / peak_flops_per_device(), 4
        ),
        "tokens_per_s": round(batch * seq / step, 1),
        "fused_tokens_per_s": round(batch * seq / fused_step, 1),
    }


def _measure_proxy_fit(k=8, batch=32, dim=64, steps=384):
    """Dispatch-bound proxy through the REAL fit loop (the same subject as
    the slow regression test in tests/test_fused_dispatch.py): a tiny MLP
    whose per-step XLA program costs far less than its dispatch, trained
    per-step and fused-K on this host. The per-step-minus-fused step time
    is the dispatch overhead the fused engine amortizes."""
    import time

    from flexflow_tpu.core import FFConfig, FFModel
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

    rs = np.random.RandomState(0)
    xv = rs.randn(batch * steps, dim).astype(np.float32)
    yv = rs.randint(0, 10, batch * steps)

    def run(kk):
        cfg = FFConfig(
            batch_size=batch, seed=0, steps_per_dispatch=kk, print_freq=0
        )
        m = FFModel(cfg)
        x = m.create_tensor([batch, dim], name="x")
        h = m.dense(x, dim, use_bias=False, name="fc1")
        h = m.relu(h)
        logits = m.dense(h, 10, use_bias=False, name="head")
        m.compile(
            AdamOptimizerAttrs(alpha=1e-3),
            "sparse_categorical_crossentropy",
            logit_tensor=logits,
        )
        # warmup epoch compiles the step/window programs
        m.fit(xv[: batch * 16], yv[: batch * 16], epochs=1, shuffle=False,
              verbose=False)
        t0 = time.perf_counter()
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
        return batch * steps / (time.perf_counter() - t0)

    base_ips = run(1)
    fused_ips = run(k)
    return {
        "batch": batch, "dim": dim, "steps": steps,
        "steps_per_dispatch": k,
        "images_per_s": round(base_ips, 1),
        "fused_images_per_s": round(fused_ips, 1),
        "speedup": round(fused_ips / base_ips, 3),
        "dispatch_overhead_ms": round(
            batch * 1000.0 / base_ips - batch * 1000.0 / fused_ips, 3
        ),
    }


def run_fused(args):
    """`bench.py --fused`: the fused-dispatch block — AlexNet per-step vs
    fused K (the dispatch-bound subject the tentpole targets), the derived
    dispatch_overhead_ms, and the fused flagship block. On the CPU host
    shapes scale down (recorded in the JSON) so the capture stays
    tractable; on the chip the reference shapes stand."""
    on_cpu = jax.default_backend() == "cpu"
    k = args.fused_k
    if on_cpu:
        ashapes = dict(batch=16, image=67, classes=100)
        fshapes = dict(batch=2, seq=32, embed=64, heads=4, layers=2,
                       vocab=128)
        samples, n1, n2 = 3, 3, 19
    else:
        ashapes = dict(batch=64, image=229, classes=1000)
        fshapes = dict(batch=64, seq=512, embed=1024, heads=8, layers=12,
                       vocab=32000)
        samples, n1, n2 = 5, 5, 45
    base = _measure_alexnet(**ashapes, samples=samples, n1=n1, n2=n2)
    fused = _measure_alexnet_fused(
        **ashapes, k=k, samples=samples, n1=n1, n2=n2
    )
    result = {
        "metric": "fused_dispatch",
        "backend": jax.default_backend(),
        "steps_per_dispatch": k,
        "alexnet_shapes": ashapes,
        "alexnet_step_ms": base["step_ms"],
        "alexnet_images_per_s": base["images_per_s"],
        "alexnet_fused_step_ms": fused["step_ms"],
        "alexnet_fused_images_per_s": fused["images_per_s"],
        "dispatch_overhead_ms": round(
            base["step_ms"] - fused["step_ms"], 3
        ),
        "fused_speedup": round(
            fused["images_per_s"] / base["images_per_s"], 3
        ),
    }
    proxy = _measure_proxy_fit(k=k)
    result["proxy"] = proxy
    result["proxy_images_per_s"] = proxy["images_per_s"]
    result["proxy_fused_images_per_s"] = proxy["fused_images_per_s"]
    result["proxy_fused_speedup"] = proxy["speedup"]
    result["proxy_dispatch_overhead_ms"] = proxy["dispatch_overhead_ms"]
    try:
        result["fused_flagship"] = _measure_flagship_fused(
            **fshapes, k=k, samples=samples,
            n1=(2 if on_cpu else 3), n2=(10 if on_cpu else 15),
        )
    except Exception as e:
        result["fused_flagship_error"] = f"{type(e).__name__}: {e}"[:200]
    return result


def _reexec_on_virtual_mesh(mode_flag, extra_args=(), timeout=3600, ndev=8):
    """Re-exec THIS bench mode in a child process pinned to the virtual
    `ndev`-device CPU mesh (XLA host-platform device count) and return the
    child's JSON result line. The one shared implementation of the
    "single-device host re-execs onto the 8-dev mesh" discipline every
    multi-device mode uses (--overlap/--plan-audit/--chaos/--chaos-soak/
    --serving/--pipeline) — it was copy-pasted per mode before ISSUE 13.
    `extra_args` are forwarded verbatim (the CHILD does the measured work,
    so per-mode knobs and --profile-trace-dir must ride along)."""
    import re
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={ndev}"
    ).strip()
    cmd = [
        sys.executable, os.path.abspath(__file__), mode_flag,
        *map(str, extra_args),
    ]
    out = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=timeout,
    )
    for line in reversed(out.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"{mode_flag} subprocess produced no JSON: {out.stderr[-500:]}"
    )


def _bench_callable(fn, *args, iters=3, reps=2):
    """Best-of-reps mean ms over `iters` calls (compile excluded)."""
    from flexflow_tpu.kernels.profiling import force_sync

    out = fn(*args)
    force_sync(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        force_sync(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1000.0


def _overlap_kernel_proxy(m, k, n, iters=3):
    """Fused vs serial all-gather-matmul on one row-sharded activation
    into a thin matmul — the bandwidth-bound proxy: the serial lowering
    materializes the full gathered tensor per device, the ring streams
    chunks."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from flexflow_tpu.kernels.collective_matmul import all_gather_matmul

    mesh = Mesh(np.array(jax.devices()), ("d",))
    rs = np.random.RandomState(0)
    x = jax.device_put(
        jnp.asarray(rs.randn(m, k), jnp.float32),
        NamedSharding(mesh, P("d", None)),
    )
    w = jnp.asarray(rs.randn(k, n), jnp.float32)

    def make(fused):
        return jax.jit(
            lambda x, w: all_gather_matmul(
                x, w, mesh, P("d", None), P(None, None), 0, fused=fused
            )
        )

    fused_ms = _bench_callable(make(True), x, w, iters=iters)
    serial_ms = _bench_callable(make(False), x, w, iters=iters)
    return {
        "shape": {"m": m, "k": k, "n": n},
        "shards": len(jax.devices()),
        "fused_ms": round(fused_ms, 3),
        "serial_ms": round(serial_ms, 3),
        "speedup": round(serial_ms / fused_ms, 3),
    }


def _overlap_executor_subject(shapes, seed_name, iters=3):
    """Fused vs serial STEP time of the flagship-family transformer lowered
    from a forced strategy seed (the tp seeds carry the Linear->Reduction
    and Combine->head edges the overlap lowering fuses). Same build both
    ways; only the lowering differs."""
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    def build(overlap):
        graph, logits = build_flagship_cg(**shapes)
        cfg = FFConfig(
            batch_size=shapes["batch"], seed=0, search_budget=1,
            force_strategy_seed=seed_name, overlap=overlap,
        )
        m = FFModel.from_computation_graph(graph, logits, cfg)
        m.compile(
            SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy"
        )
        return m

    rs = np.random.RandomState(0)
    xv = rs.randn(shapes["batch"], shapes["seq"], shapes["embed"]).astype(
        np.float32
    )
    yv = rs.randint(
        0, shapes["vocab"], (shapes["batch"], shapes["seq"])
    ).astype(np.int32)

    def step_ms(m):
        it = m._make_iterator(xv, yv, shapes["batch"], shuffle=False)
        batch_dev, label_dev = next(iter(it))
        rng = jax.random.PRNGKey(0)
        state = {"p": m.params, "o": m.opt_state}

        def one():
            # the step donates params/opt state: thread the new buffers
            p, o, loss, _ = m.instance.train_step(
                state["p"], state["o"], batch_dev, label_dev, rng
            )
            state["p"], state["o"] = p, o
            return loss

        return _bench_callable(one, iters=iters)

    fused_m = build(True)
    serial_m = build(False)
    fused_ms = step_ms(fused_m)
    serial_ms = step_ms(serial_m)
    return {
        "seed": seed_name,
        "shapes": shapes,
        "fused_sites": {
            str(n.idx): kind
            for n, kind in sorted(
                fused_m.instance.overlap_sites.items(),
                key=lambda kv: kv[0].idx,
            )
        },
        "fused_step_ms": round(fused_ms, 3),
        "serial_step_ms": round(serial_ms, 3),
        "speedup": round(serial_ms / fused_ms, 3),
    }


def _overlap_search_block():
    """The DP-selection acceptance block: the flagship family priced with
    the TPU machine constants at the reference-strict overlap fraction
    (0.0 — the uncalibrated 0.5 haircut already hides sub-ms edges under a
    hundreds-of-ms downstream stage, see README). Records the eligible/
    chosen overlap edges of each seed's winner and pins native == Python
    DP cost agreement."""
    from flexflow_tpu.compiler import (
        AnalyticTPUCostEstimator,
        MachineMappingCache,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingContext,
        get_optimal_machine_mapping_python,
    )
    from flexflow_tpu.compiler.machine_mapping.native_dp import (
        NATIVE_MISS,
        try_native_dp,
    )
    from flexflow_tpu.compiler.machine_mapping.problem_tree import (
        get_machine_mapping_problem_tree,
    )
    from flexflow_tpu.compiler.unity_algorithm import (
        enumerate_seeds,
        evaluate_pcg,
    )
    from flexflow_tpu.pcg.machine_view import MachineSpecification

    pcg = build_flagship_pcg(
        batch=64, seq=512, embed=1024, heads=8, layers=2, vocab=32000
    )
    spec = MachineSpecification(1, 1, 8, 25.0, 400.0)
    est = AnalyticTPUCostEstimator(
        spec, peak_flops=197e12, hbm_gbps=820.0,
        ici_latency_ms=0.001, dcn_latency_ms=0.01,
    )
    ctx = MachineMappingContext(
        est, make_default_allowed_machine_views(),
        overlap_fraction=0.0, overlap_lowering=True,
    )
    out = {
        "machine": "1x8 (TPU constants)",
        "overlap_fraction": 0.0,
        "seeds": {},
    }
    cache = MachineMappingCache()
    for label, s in enumerate_seeds(pcg, 8):
        if label not in ("dp2xtp4xsp1", "dp1xtp8xsp1"):
            continue
        r = evaluate_pcg(s, ctx, spec, cache)
        if r is None:
            continue
        edges = r.overlap_edges or []
        chosen = [e for e in edges if e.get("chosen")]
        tree, _ = get_machine_mapping_problem_tree(s)
        nat = try_native_dp(MachineMappingCache(), ctx, tree, spec)
        py = get_optimal_machine_mapping_python(
            MachineMappingCache(), ctx, tree, spec
        )
        out["seeds"][label] = {
            "estimated_ms": round(r.runtime, 4),
            "eligible_edges": len(edges),
            "chosen_edges": len(chosen),
            "native_python_cost_equal": bool(
                nat is not NATIVE_MISS
                and nat is not None
                and py is not None
                and nat.runtime == py.runtime
            ),
            "chosen": [
                {
                    k: e[k]
                    for k in (
                        "kind", "edge_op", "adjacent_op", "roofline_class",
                        "chunks", "comm_ms", "serial_exposed_ms",
                        "overlapped_exposed_ms", "src_name", "dst_name",
                    )
                }
                for e in chosen[:4]
            ],
        }
    return out


def run_overlap(args):
    """`bench.py --overlap`: the compute/communication-overlap block —
    fused vs serial A/B on the bandwidth-bound kernel proxy, the flagship
    and seq-2048 executor subjects (forced tp seed, fused sites recorded),
    a small dispatch-bound counter-example where the ring LOSES, and the
    DP-selection acceptance block (eligible/chosen overlap edges + native
    == Python cost agreement)."""
    on_cpu = jax.default_backend() == "cpu"
    result = {
        "metric": "overlap",
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
    }
    if len(jax.devices()) < 2:
        # single-device host: re-exec onto the virtual 8-device CPU mesh
        return _reexec_on_virtual_mesh("--overlap")
    try:
        result["agmm_proxy"] = _overlap_kernel_proxy(8192, 2048, 8)
    except Exception as e:
        result["agmm_proxy_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        # honest counter-example: at small shapes the per-hop dispatch
        # dominates and the ring loses to the one-shot all-gather
        result["agmm_small_counter"] = _overlap_kernel_proxy(1024, 512, 8)
    except Exception as e:
        result["agmm_small_error"] = f"{type(e).__name__}: {e}"[:200]
    if on_cpu:
        # batch divisible by the 8-device mesh (FFModel caps ndev at the
        # largest divisor of the batch)
        fshapes = dict(batch=8, seq=64, embed=256, heads=4, layers=2,
                       vocab=1024)
        lshapes = dict(batch=8, seq=2048, embed=128, heads=4, layers=1,
                       vocab=256)
    else:
        fshapes = dict(batch=64, seq=512, embed=1024, heads=8, layers=12,
                       vocab=32000)
        lshapes = dict(batch=16, seq=2048, embed=1024, heads=8, layers=12,
                       vocab=32000)
    ndev = len(jax.devices())

    def tp_seed(shapes):
        # head-parallel attention needs heads % tp == 0
        tp = ndev
        while tp > 1 and shapes["heads"] % tp:
            tp //= 2
        return f"dp{ndev // tp}xtp{tp}xsp1"

    try:
        result["flagship"] = _overlap_executor_subject(
            fshapes, tp_seed(fshapes)
        )
    except Exception as e:
        result["flagship_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        result["longctx_seq2048"] = _overlap_executor_subject(
            lshapes, tp_seed(lshapes)
        )
    except Exception as e:
        result["longctx_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        result["search"] = _overlap_search_block()
    except Exception as e:
        result["search_error"] = f"{type(e).__name__}: {e}"[:200]
    return result


_ROOFLINE_CONSTANTS = None


def _roofline_constants():
    """Measured single-device machine constants (compiler/calibration.py)
    for the roofline classification; calibrated once per process (every
    subject block classifies against the same device)."""
    global _ROOFLINE_CONSTANTS
    if _ROOFLINE_CONSTANTS is None:
        from flexflow_tpu.compiler.calibration import calibrate

        cal = calibrate(devices=jax.devices()[:1])
        _ROOFLINE_CONSTANTS = (cal.peak_flops, cal.hbm_gbps)
    return _ROOFLINE_CONSTANTS


def _roofline_transformer(batch, seq, embed, heads, layers, vocab,
                          samples=3):
    """Roofline block for the transformer subject: measured step time +
    per-op stepped ms + XLA cost-analysis totals -> per-op {flops, bytes,
    measured_ms, bound} and whole-step MFU."""
    import time

    from flexflow_tpu.kernels.profiling import force_sync
    from flexflow_tpu.local_execution import ModelTrainingInstance
    from flexflow_tpu.observability import (
        attribute_costs,
        measure_per_op_ms,
        roofline_report,
        step_cost_analysis,
    )
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

    graph, logits = build_flagship_cg(batch, seq, embed, heads, layers, vocab)
    inst = ModelTrainingInstance(
        graph,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-4),
        compute_dtype=jnp.bfloat16,
    )
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(batch, seq, embed), jnp.float32)
    yv = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)
    rng = jax.random.PRNGKey(0)

    # program totals BEFORE any donated step runs (lowering needs live args)
    program = step_cost_analysis(
        inst._step, params, opt_state, {"x": xv}, yv, rng
    )

    def run(iters, params, opt_state):
        start = time.perf_counter()
        loss = None
        for _ in range(iters):
            params, opt_state, loss, _ = inst.train_step(
                params, opt_state, {"x": xv}, yv
            )
        force_sync(loss)
        return time.perf_counter() - start, params, opt_state

    _, params, opt_state = run(1, params, opt_state)  # compile
    on_cpu = jax.default_backend() == "cpu"
    n1, n2 = (1, 3) if on_cpu else (3, 15)
    meas = []
    for _ in range(samples):
        t1, params, opt_state = run(n1, params, opt_state)
        t2, params, opt_state = run(n2, params, opt_state)
        s = (t2 - t1) / (n2 - n1)
        meas.append(s if s > 0 else t2 / n2)
    step_ms = sorted(meas)[len(meas) // 2] * 1000.0

    per_op = measure_per_op_ms(graph, {"x": xv}, logits)
    att = attribute_costs(graph, step_ms, per_op_ms=per_op, program=program)
    peak, hbm = _roofline_constants()
    return roofline_report(
        att, peak, hbm,
        top_n=24,
        extra={
            "subject": "transformer",
            "shapes": {
                "batch": batch, "seq": seq, "embed": embed,
                "heads": heads, "layers": layers, "vocab": vocab,
            },
            "backend": jax.default_backend(),
            "datasheet_flops_per_s": peak_flops_per_device(),
        },
    )


def run_roofline(args):
    """`bench.py --roofline`: the `roofline` result dict mapping each
    subject to its attribution block (main prints it as one JSON line). On
    the CPU mesh shapes scale down (recorded in each block) so the stepped
    per-op measurement stays tractable; on the chip the flagship shapes
    stand."""
    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:
        shapes = dict(batch=2, seq=32, embed=64, heads=4, layers=2,
                      vocab=128)
    else:
        shapes = dict(batch=64, seq=args.seq, embed=1024,
                      heads=args.heads or 8, layers=12, vocab=32000)
    blocks = {"transformer": _roofline_transformer(**shapes)}
    if not on_cpu and (args.heads or 8) == 8:
        # the VERDICT "done =" artifacts: the reference-default heads=16
        # config and the AlexNet conv subject get their own blocks
        try:
            blocks["ref_heads16"] = _roofline_transformer(
                **{**shapes, "heads": 16}
            )
            blocks["ref_heads16"]["subject"] = "ref_heads16"
        except Exception as e:
            blocks["ref_heads16_error"] = f"{type(e).__name__}: {e}"[:200]
        try:
            blocks["alexnet"] = _roofline_alexnet()
        except Exception as e:
            blocks["alexnet_error"] = f"{type(e).__name__}: {e}"[:200]
    return {"metric": "roofline", "roofline": blocks}


def _roofline_alexnet(batch=64, image=229, classes=1000):
    """AlexNet roofline block (the 26.8%-MFU blocker the VERDICT stalls
    on): same FFModel build as _measure_alexnet, attributed per conv/pool/
    dense op."""
    import time

    from flexflow_tpu.kernels.profiling import force_sync
    from flexflow_tpu.observability import (
        attribute_costs,
        measure_per_op_ms,
        roofline_report,
    )

    m = _alexnet_model(batch, image, classes)
    logits = m._last_tensor
    rs = np.random.RandomState(0)
    xv = rs.randn(batch, 3, image, image).astype(np.float32)
    yv = rs.randint(0, classes, batch).astype(np.int32)
    it = m._make_iterator(xv, yv, batch, shuffle=False)
    batch_dev, label_dev = next(iter(it))
    rng = jax.random.PRNGKey(0)

    def run(iters):
        nonlocal rng
        start = time.perf_counter()
        loss = None
        for _ in range(iters):
            rng, srng = jax.random.split(rng)
            m.params, m.opt_state, loss, _ = m.instance.train_step(
                m.params, m.opt_state, batch_dev, label_dev, srng
            )
        force_sync(loss)
        return time.perf_counter() - start

    run(1)  # compile
    t1s, t2s = [], []
    for _ in range(3):
        t1s.append(run(5))
        t2s.append(run(45))
    step = (min(t2s) - min(t1s)) / 40
    if step <= 0:
        step = min(t2s) / 45
    logit_handle = logits.handle if hasattr(logits, "handle") else logits
    per_op = measure_per_op_ms(
        m.cg, {"image": jnp.asarray(xv)}, logit_handle
    )
    att = attribute_costs(m.cg, step * 1000.0, per_op_ms=per_op)
    peak, hbm = _roofline_constants()
    return roofline_report(
        att, peak, hbm,
        top_n=24,
        extra={
            "subject": "alexnet",
            "shapes": {"batch": batch, "image": image, "classes": classes},
            "backend": jax.default_backend(),
            "datasheet_flops_per_s": peak_flops_per_device(),
        },
    )


def _audit_subject(shapes, budget, seed_name=""):
    """Compile the transformer subject through the Unity search with
    plan_audit=True and return {estimated_ms, plan_audit} (the provenance
    block observability/plan_audit.py recorded). seed_name forces a
    strategy template instead of searching (the dp seed's
    Replicate/Combine movement edges are the per-step weight-sync
    collectives, so its audit always has movement rows)."""
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    graph, logits = build_flagship_cg(**shapes)
    cfg = FFConfig(
        batch_size=shapes["batch"], seed=0, search_budget=budget,
        plan_audit=True, force_strategy_seed=seed_name,
    )
    m = FFModel.from_computation_graph(graph, logits, cfg)
    m.compile(SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy")
    prov = m.search_provenance or {}
    return {
        "estimated_ms": prov.get("estimated_ms"),
        "plan_audit": prov.get("plan_audit"),
    }


def _health_demo(batch=16, hidden=32, classes=10, steps=4):
    """Forced-NaN run-health demo for the artifact: a poisoned batch under
    the skip_step policy must be detected, blamed on its first bad op, and
    dropped without corrupting the parameters."""
    import tempfile

    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.observability.metrics import read_events

    d = tempfile.mkdtemp(prefix="ffhealth_")
    m = FFModel(FFConfig(
        batch_size=batch, seed=0, metrics_dir=d, health_policy="skip_step",
    ))
    x = m.create_tensor([batch, hidden], name="x")
    h = m.dense(x, hidden, name="fc1")
    h = m.relu(h)
    logits = m.dense(h, classes, name="head")
    m.compile(
        SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    rs = np.random.RandomState(0)
    xv = rs.randn(batch * steps, hidden).astype(np.float32)
    xv[batch:2 * batch] = np.nan  # poison step 2
    yv = rs.randint(0, classes, batch * steps)
    m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
    events = read_events(d)
    mon = m.health_monitor
    return {
        "steps": len(events),
        "nonfinite_steps": mon.nonfinite_steps,
        "skipped_steps": mon.skipped_steps,
        "first_bad_op": mon.summary()["first_bad_op"],
        "params_finite": bool(all(
            np.all(np.isfinite(np.asarray(v))) for v in m.params.values()
        )),
        "events_skipped": sum(1 for e in events if e["skipped"]),
    }


def run_plan_audit(args):
    """`bench.py --plan-audit`: predicted-vs-measured plan audit on the
    transformer subject (ISSUE 3 acceptance block) + the forced-NaN health
    demo. Needs a multi-device mesh to search over and reshard on; a
    single-device host re-execs itself onto the virtual 8-device CPU mesh
    (same discipline as the search-seconds subprocess in main)."""
    if len(jax.devices()) < 2:
        extra = ["--plan-audit-budget", args.plan_audit_budget]
        if args.profile_trace_dir:
            # forward the flag: the CHILD is the process doing the audited
            # work, so its trace is the one worth keeping (dead-flag rule)
            extra += ["--profile-trace-dir", args.profile_trace_dir]
        return _reexec_on_virtual_mesh(
            "--plan-audit", extra, timeout=1800
        )
    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:
        shapes = dict(batch=8, seq=16, embed=32, heads=2, layers=2, vocab=64)
    else:
        shapes = dict(batch=64, seq=512, embed=1024, heads=8, layers=12,
                      vocab=32000)
    ndev = len(jax.devices())
    result = {
        "metric": "plan_audit",
        "subject": "transformer",
        "shapes": shapes,
        "budget": args.plan_audit_budget,
        "backend": jax.default_backend(),
        "num_devices": ndev,
    }
    result["searched"] = _audit_subject(shapes, args.plan_audit_budget)
    try:
        result["dp_seed"] = _audit_subject(
            shapes, 1, seed_name=f"dp{ndev}xtp1xsp1"
        )
    except Exception as e:
        result["dp_seed_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        result["health_demo"] = _health_demo()
    except Exception as e:
        result["health_demo_error"] = f"{type(e).__name__}: {e}"[:200]
    return result


_COSTDB_CHILD = """
import json, sys, time
sys.path.insert(0, {repo!r})
import jax
jax.config.update('jax_platforms', 'cpu')

import flexflow_tpu.local_execution.cost_estimator as lce
_calls = [0]
_orig = lce.profile_fn
def _counting(fn, settings, *a, **k):
    _calls[0] += 1
    return _orig(fn, settings, *a, **k)
lce.profile_fn = _counting

from flexflow_tpu.compiler import (
    MachineMappingContext, OptimizerConfig, TPUCostEstimator,
    graph_optimize, make_default_allowed_machine_views)
from flexflow_tpu.compiler.cost_store import CostStore
from flexflow_tpu.kernels.profiling import ProfilingSettings
from flexflow_tpu.local_execution.cost_estimator import LocalCostEstimator
from flexflow_tpu.pcg.machine_view import MachineSpecification
from flexflow_tpu.substitutions.rules import generate_parallelization_rules
from bench import build_flagship_pcg

pcg = build_flagship_pcg(**{shapes!r})
spec = MachineSpecification(1, 1, 8, 1.0, 2.0)
store = CostStore({store_dir!r})
est = TPUCostEstimator(
    spec,
    local_cost_estimator=LocalCostEstimator(
        ProfilingSettings(warmup_iters=1, measure_iters=2)),
    ici_latency_ms=0.1, dcn_latency_ms=0.2,
    cost_store=store,
)
ctx = MachineMappingContext(est, make_default_allowed_machine_views())
rules = generate_parallelization_rules([2, 4, 8])
t0 = time.perf_counter()
r = graph_optimize(pcg, ctx, spec, rules,
                   OptimizerConfig(alpha=1.2, budget={budget}))
seconds = time.perf_counter() - t0
store.save()
print('RESULT ' + json.dumps({{
    'seconds': round(seconds, 3),
    'leaf_cost_ms': round(
        (r.telemetry or {{}}).get('phase_ms', {{}}).get('leaf_cost', 0.0), 1),
    'runtime': r.runtime,
    'profile_calls': _calls[0],
    'store_entries': len(store),
}}))
"""


def _costdb_search_child(store_dir, shapes, budget):
    """One measured-cost search session (its own process: the store is the
    only state the warm arm may inherit — the point being measured)."""
    import re
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
    code = _COSTDB_CHILD.format(
        repo=os.path.dirname(os.path.abspath(__file__)),
        store_dir=store_dir, shapes=shapes, budget=budget,
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=1800,
    )
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(
        f"cost-db search child produced no RESULT: {out.stderr[-800:]}"
    )


def run_cost_db(args):
    """`bench.py --cost-db`: the persistent cost database's two headline
    effects on the 12-layer proxy (ISSUE 9 acceptance block):

    1. cold vs warm-store search time — two fresh processes sharing one
       store directory; the warm one must price every previously measured
       op leaf without a single profile_fn call;
    2. audit-ratio calibration — an analytic pass over the populated store
       completes (analytic, measured) pairs, per-op-class correction
       factors are fitted, and the measured/analytic geomean is reported
       before and after applying them.
    """
    import math as _math
    import tempfile

    from flexflow_tpu.compiler import (
        MachineMappingContext,
        OptimizerConfig,
        graph_optimize,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler import AnalyticTPUCostEstimator
    from flexflow_tpu.compiler.cost_store import CostStore
    from flexflow_tpu.pcg.machine_view import MachineSpecification
    from flexflow_tpu.substitutions.rules import (
        generate_parallelization_rules,
    )

    # CPU-measurable 12-layer proxy: the flagship topology with every
    # layer's leaf family cheap enough to measure for real on the host
    shapes = dict(batch=8, seq=32, embed=64, heads=2, layers=12, vocab=256)
    budget = args.cost_db_budget
    store_dir = tempfile.mkdtemp(prefix="ffcostdb_bench_")
    result = {
        "metric": "cost_db",
        "subject": "transformer_12l_proxy",
        "shapes": shapes,
        "budget": budget,
        "backend": "cpu",
        "store_dir": store_dir,
    }
    cold = _costdb_search_child(store_dir, shapes, budget)
    warm = _costdb_search_child(store_dir, shapes, budget)
    result["cold"] = cold
    result["warm"] = warm
    result["warm_speedup_total"] = round(
        cold["seconds"] / max(warm["seconds"], 1e-9), 3
    )
    result["warm_speedup_leaf_cost"] = round(
        cold["leaf_cost_ms"] / max(warm["leaf_cost_ms"], 1e-9), 2
    )
    result["identical_winner"] = warm["runtime"] == cold["runtime"]
    result["zero_profile_calls_warm"] = warm["profile_calls"] == 0

    # correction calibration: an analytic search over the SAME store hits
    # every measured leaf and records the raw roofline beside it — the
    # pair set the per-op-class factors are fitted from
    # the children force the CPU backend; the in-process pass must read
    # their device-kind family even when bench itself holds a TPU
    store = CostStore(store_dir, device_kind="cpu:cpu")
    spec = MachineSpecification(1, 1, 8, 1.0, 2.0)
    est = AnalyticTPUCostEstimator(
        spec, peak_flops=5e10, hbm_gbps=10.0,
        ici_latency_ms=0.1, dcn_latency_ms=0.2, cost_store=store,
    )
    ctx = MachineMappingContext(est, make_default_allowed_machine_views())
    graph_optimize(
        build_flagship_pcg(**shapes), ctx, spec,
        generate_parallelization_rules([2, 4, 8]),
        OptimizerConfig(alpha=1.2, budget=budget),
    )
    store.save()
    fits = store.fit_corrections()
    before_logs, after_logs = [], []
    for e in store._table.values():
        if e.get("kind") != "op" or e.get("unrunnable"):
            continue
        a, m = e.get("analytic_ms"), e.get("ms")
        if not a or not m or a <= 0 or m <= 0:
            continue
        f = fits.get(e.get("op_class"), {}).get("factor", 1.0)
        before_logs.append(_math.log(m / a))
        after_logs.append(_math.log(m / (a * f)))
    result["correction"] = {
        "pairs": len(before_logs),
        "classes_fitted": len(fits),
        "factors": {k: v["factor"] for k, v in sorted(fits.items())},
        "audit_ratio_geomean_before": (
            round(_math.exp(sum(before_logs) / len(before_logs)), 3)
            if before_logs else None
        ),
        "audit_ratio_geomean_after": (
            round(_math.exp(sum(after_logs) / len(after_logs)), 3)
            if after_logs else None
        ),
    }
    result["cost_db_stats"] = store.stats()
    return result


def _chaos_ckpt_base_dir() -> str:
    """tmpfs when available: the overhead block measures the RUNTIME's
    cost, not the mount's — this container's /tmp is a 9p network mount
    whose per-file metadata round-trips would dominate the small proxy
    saves. The chosen filesystem is recorded in the artifact."""
    return "/dev/shm" if os.access("/dev/shm", os.W_OK) else None


def _chaos_proxy_model(k, batch, dim, ckpt_dir, every, sync):
    from flexflow_tpu.core import FFConfig, FFModel
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

    cfg = FFConfig(
        batch_size=batch, seed=0, steps_per_dispatch=k, print_freq=0,
        checkpoint_dir=ckpt_dir or "", checkpoint_every_n_steps=every,
        checkpoint_sync=sync,
    )
    m = FFModel(cfg)
    x = m.create_tensor([batch, dim], name="x")
    h = m.dense(x, dim, use_bias=False, name="fc1")
    h = m.relu(h)
    logits = m.dense(h, 10, use_bias=False, name="head")
    m.compile(
        AdamOptimizerAttrs(alpha=1e-3),
        "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    return m


def _chaos_checkpoint_overhead(k=8, batch=32, dim=512, steps=256, every=64,
                               reps=8):
    """Async-vs-sync-vs-none checkpoint overhead on the fused proxy: the
    acceptance bar is async <= 5% of steady-state step time at the default
    cadence, with the synchronous baseline recorded for honesty and an
    aggressive-cadence row (every=32) recorded too. The proxy's width is
    scaled (dim=512, ~20 ms steps) so the 2-core CPU host's scheduling
    noise (+-2 ms bursts per step at the dim-64 shape) doesn't swamp a 5%
    question; one model per arm (compiled once), measured epochs run
    INTERLEAVED and best-of-reps — drift only ever ADDS time, so the
    per-arm minimum over interleaved reps is the least-contended
    estimate (base_step_ms_spread records the observed burst band)."""
    import tempfile

    rs = np.random.RandomState(0)
    xv = rs.randn(batch * steps, dim).astype(np.float32)
    yv = rs.randint(0, 10, batch * steps)
    base_dir = _chaos_ckpt_base_dir()
    arms = {
        "base": dict(every=0, sync=False),
        "async": dict(every=every, sync=False),
        "sync": dict(every=every, sync=True),
        "async_e32": dict(every=32, sync=False),
    }
    models = {}
    for a, kw in arms.items():
        d = (
            tempfile.mkdtemp(prefix="ffchaos_ck_", dir=base_dir)
            if kw["every"]
            else None
        )
        models[a] = _chaos_proxy_model(k, batch, dim, d, **kw)
        # warmup epoch compiles the window programs (checkpointing off so
        # warmup saves don't pollute the measured cadence)
        models[a].fit(xv[: batch * 16], yv[: batch * 16], epochs=1,
                      shuffle=False, verbose=False, checkpoint_dir="")
    times = {a: [] for a in arms}
    for _ in range(reps):
        for a, m in models.items():
            t0 = time.perf_counter()
            m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
            times[a].append(time.perf_counter() - t0)
    best = {a: min(ts) for a, ts in times.items()}
    step_ms = {a: t / steps * 1000.0 for a, t in best.items()}
    pct = lambda a: round(  # noqa: E731
        (step_ms[a] - step_ms["base"]) / step_ms["base"] * 100.0, 2
    )
    return {
        "proxy": {"batch": batch, "dim": dim, "steps": steps},
        "steps_per_dispatch": k,
        "checkpoint_every_n_steps": every,
        "checkpoints_per_run": steps // every,
        "checkpoint_fs": base_dir or "default-tmp",
        "host_cores": os.cpu_count(),
        "reps": reps,
        "base_images_per_s": round(batch * steps / best["base"], 1),
        "async_images_per_s": round(batch * steps / best["async"], 1),
        "sync_images_per_s": round(batch * steps / best["sync"], 1),
        "base_step_ms": round(step_ms["base"], 4),
        "async_step_ms": round(step_ms["async"], 4),
        "sync_step_ms": round(step_ms["sync"], 4),
        "base_step_ms_spread": round(
            (max(times["base"]) - min(times["base"])) / steps * 1000.0, 4
        ),
        "async_overhead_pct": pct("async"),
        "sync_overhead_pct": pct("sync"),
        # honesty row: 4x the checkpoint rate on a 2-core host where
        # writer work cannot hide — the cadence knob's real cost curve
        "async_every32_overhead_pct": pct("async_e32"),
    }


def _chaos_resume_block(k=4, batch=16, dim=32, steps_per_epoch=8,
                        fault_step=10):
    """Kill-mid-window + fit(resume=True) fidelity: the resumed loss
    trajectory must be BITWISE the uninterrupted run's, final params
    bitwise too (the tests/test_elastic.py contract, measured here so the
    artifact records it on this host)."""
    import tempfile

    from flexflow_tpu.core import FFConfig, FFModel
    from flexflow_tpu.observability.metrics import read_events
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
    from flexflow_tpu.runtime.fault import FAULT_STEP_ENV, SimulatedFault

    n = batch * steps_per_epoch
    rs = np.random.RandomState(0)
    xv = rs.randn(n, dim).astype(np.float32)
    yv = rs.randint(0, 10, n)

    def build(mdir, cdir):
        cfg = FFConfig(
            batch_size=batch, seed=0, steps_per_dispatch=k, print_freq=0,
            metrics_dir=mdir, checkpoint_dir=cdir,
            checkpoint_every_n_steps=8,
        )
        m = FFModel(cfg)
        x = m.create_tensor([batch, dim], name="x")
        h = m.dense(x, dim, use_bias=False, name="fc1")
        h = m.relu(h)
        h = m.dropout(h, 0.1)  # the RNG stream position is load-bearing
        logits = m.dense(h, 10, use_bias=False, name="head")
        m.compile(
            AdamOptimizerAttrs(alpha=1e-2),
            "sparse_categorical_crossentropy",
            logit_tensor=logits,
        )
        return m

    def losses(mdir):
        return {
            e["step"]: e["loss"]
            for e in read_events(mdir)
            if "step" in e
        }

    d1, c1 = tempfile.mkdtemp(), tempfile.mkdtemp()
    m1 = build(d1, c1)
    m1.fit(xv, yv, epochs=2, shuffle=True, verbose=False)

    d2, c2 = tempfile.mkdtemp(), tempfile.mkdtemp()
    m2 = build(d2, c2)
    os.environ[FAULT_STEP_ENV] = str(fault_step)
    fault_fired = False
    try:
        m2.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
    except SimulatedFault:
        fault_fired = True
    finally:
        os.environ.pop(FAULT_STEP_ENV, None)
    resume_step = m2._step_count
    m2b = build(d2, c2)
    m2b.fit(xv, yv, epochs=2, shuffle=True, verbose=False, resume=True)

    ref, got = losses(d1), losses(d2)
    bitwise = sorted(ref) == sorted(got) and all(
        ref[s] == got[s] for s in ref
    )
    params_bitwise = all(
        np.array_equal(np.asarray(m1.params[p]), np.asarray(m2b.params[p]))
        for p in m1.params
    )
    return {
        "backend": type(m1.instance).__name__,
        "steps_per_dispatch": k,
        "total_steps": 2 * steps_per_epoch,
        "fault_step": fault_step,
        "fault_fired": fault_fired,
        "killed_at_step": resume_step,
        "bitwise_loss_trajectory": bool(bitwise),
        "final_params_bitwise": bool(params_bitwise),
    }


def _chaos_recovery_block(budget=3, batch=16, dim=32, steps_per_epoch=8):
    """Degraded-grid recovery wall-clock: searched compile on the full
    grid, train an epoch, fail half the devices, re-search + re-shard +
    continue. recovery_seconds is the number that matters on a pod (the
    hash-consed search caches and compile cache are what keep it small)."""
    import tempfile

    from flexflow_tpu.core import FFConfig, FFModel
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
    from flexflow_tpu.runtime.recompile import (
        active_num_devices,
        recover_from_grid_change,
    )

    n = batch * steps_per_epoch
    rs = np.random.RandomState(0)
    xv = rs.randn(n, dim).astype(np.float32)
    yv = rs.randint(0, 10, n)
    mdir, cdir = tempfile.mkdtemp(), tempfile.mkdtemp()
    cfg = FFConfig(
        batch_size=batch, seed=0, search_budget=budget, print_freq=0,
        metrics_dir=mdir, checkpoint_dir=cdir, checkpoint_every_n_steps=4,
    )
    m = FFModel(cfg)
    x = m.create_tensor([batch, dim], name="x")
    h = m.dense(x, dim, use_bias=False, name="fc1")
    h = m.relu(h)
    logits = m.dense(h, 10, use_bias=False, name="head")
    m.compile(
        AdamOptimizerAttrs(alpha=1e-2),
        "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    old_ndev = active_num_devices(m)
    m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
    rec = recover_from_grid_change(
        m, max(old_ndev // 2, 1), checkpoint_dir=cdir,
        reason="simulated_device_failure",
    )
    m.fit(xv, yv, epochs=1, shuffle=False, verbose=False, epoch_offset=1)
    verify = (m.search_provenance or {}).get("verify") or {}
    return {
        "backend": type(m.instance).__name__,
        "old_devices": rec["old_grid"]["num_devices"],
        "new_devices": rec["new_grid"]["num_devices"],
        "re_searched": rec["re_searched"],
        "restored_step": rec["restored_step"],
        "recovery_seconds": rec["recovery_seconds"],
        "verify_clean": verify.get("clean"),
        "continued_to_step": m._step_count,
    }


def run_chaos(args):
    """`bench.py --chaos`: the elastic-runtime block — checkpoint overhead
    % on the fused proxy (async vs the sync baseline vs none), kill+resume
    fidelity (bitwise loss trajectory + params), and degraded-grid
    recovery wall-clock. Committed as CHAOS_r*.json. A single-device host
    re-execs onto the virtual 8-device CPU mesh (same discipline as
    run_plan_audit) so the recovery block has a grid to shrink."""
    if len(jax.devices()) < 2:
        extra = ["--chaos-every", args.chaos_every,
                 "--chaos-reps", args.chaos_reps]
        if args.profile_trace_dir:
            # the CHILD does the measured work, so its trace is the one
            # worth keeping (same dead-flag discipline as run_plan_audit)
            extra += ["--profile-trace-dir", args.profile_trace_dir]
        return _reexec_on_virtual_mesh("--chaos", extra)
    result = {
        "metric": "chaos",
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
    }
    try:
        result["checkpoint_overhead"] = _chaos_checkpoint_overhead(
            every=args.chaos_every, reps=args.chaos_reps
        )
    except Exception as e:
        result["checkpoint_overhead_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        result["resume"] = _chaos_resume_block()
    except Exception as e:
        result["resume_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        result["recovery"] = _chaos_recovery_block()
    except Exception as e:
        result["recovery_error"] = f"{type(e).__name__}: {e}"[:200]
    return result


def _soak_build(backend, mdir, cdir, watchdog, batch=16, dim=32):
    """The soak proxy model factory — DP (with dropout, so the restored
    RNG stream position is load-bearing) or searched-PCG backend, fused
    k=4, health policy `raise` (the nonfinite site's detector), watchdog
    armed only when the schedule needs one (see runtime/chaos.py)."""
    from flexflow_tpu.core import FFConfig, FFModel
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

    cfg = FFConfig(
        batch_size=batch, seed=0, steps_per_dispatch=4, print_freq=0,
        search_budget=2 if backend == "searched" else -1,
        metrics_dir=mdir, checkpoint_dir=cdir,
        checkpoint_every_n_steps=4, health_policy="raise",
        watchdog_factor=3.0 if watchdog else 0.0,
        # npz: exercise the checksum-manifest integrity path, not orbax
        checkpoint_backend="npz",
    )
    m = FFModel(cfg)
    x = m.create_tensor([batch, dim], name="x")
    h = m.dense(x, dim, use_bias=False, name="fc1")
    h = m.relu(h)
    if backend == "dp":
        h = m.dropout(h, 0.1)
    logits = m.dense(h, 10, use_bias=False, name="head")
    m.compile(
        AdamOptimizerAttrs(alpha=1e-2),
        "sparse_categorical_crossentropy",
        metrics=["accuracy"],
        logit_tensor=logits,
    )
    return m


def _soak_data(batch=16, steps_per_epoch=8, dim=32):
    n = batch * steps_per_epoch
    rs = np.random.RandomState(0)
    return rs.randn(n, dim).astype(np.float32), rs.randint(0, 10, n)


def _watchdog_block():
    """Dedicated watchdog-fires capture: a hang schedule under an armed
    watchdog must raise WindowHangError within the budget and land the
    HangDiagnostic in the metrics JSONL as an `event: "hang"` line."""
    import tempfile

    from flexflow_tpu.observability.metrics import read_run_events
    from flexflow_tpu.runtime import fault as fault_mod
    from flexflow_tpu.runtime.chaos import schedule_for_site
    from flexflow_tpu.runtime.supervisor import WindowHangError

    xv, yv = _soak_data()
    mdir, cdir = tempfile.mkdtemp(), tempfile.mkdtemp()
    m = _soak_build("dp", mdir, cdir, watchdog=True)
    schedule = schedule_for_site("hang", 16, 4)
    fault_mod.install_schedule(schedule)
    diag = None
    raised = False
    try:
        m.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
    except WindowHangError as e:
        raised = True
        diag = e.diagnostic.to_dict() if e.diagnostic else None
    finally:
        fault_mod.install_schedule(None)
    events = read_run_events(mdir, "hang")
    return {
        "schedule": schedule.canonical_spec(),
        "watchdog_factor": 3.0,
        "raised_within_budget": bool(raised),
        "diagnostic": diag,
        "budget_ms": (diag or {}).get("budget_ms"),
        "elapsed_ms": (diag or {}).get("elapsed_ms"),
        "hang_events_in_jsonl": len(events),
    }


def _integrity_fallback_block():
    """Truncated-checkpoint capture: zero out a leaf of the NEWEST
    snapshot, resume, and record the automatic fallback to the previous
    verified step (quarantine + provenance + JSONL event)."""
    import tempfile

    from flexflow_tpu.observability.metrics import read_run_events
    from flexflow_tpu.runtime.checkpoint import CheckpointManager

    xv, yv = _soak_data()
    mdir, cdir = tempfile.mkdtemp(), tempfile.mkdtemp()
    m = _soak_build("dp", mdir, cdir, watchdog=False)
    m.fit(xv, yv, epochs=2, shuffle=True, verbose=False)
    newest = CheckpointManager(cdir, backend="npz").latest_step()
    with open(os.path.join(cdir, f"step_{newest}", "arr_0.npy"), "w"):
        pass  # truncate to zero bytes
    m2 = _soak_build("dp", mdir, cdir, watchdog=False)
    m2.fit(xv, yv, epochs=2, shuffle=True, verbose=False, resume=True)
    report = ((m2.search_provenance or {}).get("recovery") or {}).get(
        "checkpoint_fallback"
    ) or {}
    events = read_run_events(mdir, "checkpoint_fallback")
    return {
        "corrupted_step": newest,
        "restored_step": report.get("restored_step"),
        "quarantined": report.get("quarantined"),
        "recorded_in_provenance": bool(report),
        "fallback_events_in_jsonl": len(events),
        "resumed_to_step": m2._step_count,
    }


def run_chaos_soak(args):
    """`bench.py --chaos-soak`: the fault-domain supervision block — one
    seeded FaultSchedule per site (ckpt-write IO fault, producer death,
    injected NaN, simulated hang, kill+resume) on BOTH the DP and
    searched-PCG backends, each required to end with bitwise-identical
    final params + Adam moments vs the fault-free run; plus the
    watchdog-fires capture and the truncated-checkpoint auto-fallback.
    Committed as CHAOS_r*.json (the same artifact family as --chaos). A
    single-device host re-execs onto the virtual 8-device CPU mesh so
    the searched backend has a grid."""
    if len(jax.devices()) < 2:
        return _reexec_on_virtual_mesh("--chaos-soak")
    from flexflow_tpu.runtime.chaos import soak_sites

    xv, yv = _soak_data()
    result = {
        "metric": "chaos_soak",
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
        "steps_per_dispatch": 4,
        "total_steps": 16,
        "checkpoint_every_n_steps": 4,
    }
    soak = {}
    for backend in ("dp", "searched"):
        try:
            soak[backend] = soak_sites(
                lambda mdir, cdir, watchdog=False, b=backend: _soak_build(
                    b, mdir, cdir, watchdog
                ),
                xv, yv, total_steps=16, checkpoint_every=4,
            )
        except Exception as e:
            soak[backend] = {"error": f"{type(e).__name__}: {e}"[:200]}
    result["soak"] = soak
    result["total_bitwise"] = sum(
        s.get("n_bitwise", 0) for s in soak.values()
    )
    result["total_schedules"] = sum(
        s.get("n_schedules", 0) for s in soak.values()
    )
    try:
        result["watchdog"] = _watchdog_block()
    except Exception as e:
        result["watchdog_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        result["integrity_fallback"] = _integrity_fallback_block()
    except Exception as e:
        result["integrity_fallback_error"] = f"{type(e).__name__}: {e}"[:200]
    return result


# ---------------------------------------------------------------------------
# --drift (ISSUE 18): live plan-fidelity drift telemetry
# ---------------------------------------------------------------------------


def _drift_slow_schedule(start):
    """A fault schedule whose `slow` soft-site fires on EVERY step from
    `start` on: the drift block needs a SUSTAINED slowdown after a
    healthy baseline, which the hash-rate decision cannot express. The
    schedule still runs through install_schedule/fire_once, so fired_log
    is real evidence of what was injected."""
    from flexflow_tpu.runtime.fault import FaultSchedule

    class _StepGated(FaultSchedule):
        def should_fire(self, site, step):
            return site in self.sites and step >= start

    return _StepGated(seed=0, sites=frozenset({"slow"}), rate=1.0)


def _drift_model(mdir, store_path, *, drift=True, batch=16, dim=256,
                 budget=2, window=8, run_length=3, band=0.25,
                 cost_model="measured", k=1):
    """The drift proxy: a searched 2-layer dense model with a metrics dir
    (the stream the monitor tails) and a persistent cost store (the warm
    table the re-search prices against). dim=256 keeps steps ~10 ms so
    the 2-core host's scheduling bursts stay well inside the band."""
    from flexflow_tpu.core import FFConfig, FFModel, SGDOptimizer

    cfg = FFConfig(
        batch_size=batch, seed=0, print_freq=0, metrics_dir=mdir,
        cost_store=store_path or "", cost_model=cost_model,
        search_budget=budget, drift_monitor=drift, drift_band=band,
        drift_window_steps=window, drift_run_length=run_length,
        steps_per_dispatch=k,
    )
    m = FFModel(cfg)
    x = m.create_tensor([batch, dim], name="x")
    h = m.dense(x, dim, use_bias=False, name="fc1")
    h = m.relu(h)
    logits = m.dense(h, 10, use_bias=False, name="head")
    m.compile(
        SGDOptimizer(lr=0.01), "sparse_categorical_crossentropy",
        logit_tensor=logits,
    )
    return m


def _drift_data(batch, steps, dim, seed=0):
    rs = np.random.RandomState(seed)
    xv = rs.randn(batch * steps, dim).astype(np.float32)
    yv = rs.randint(0, 10, batch * steps)
    return xv, yv


def _drift_slowdown_block(steps=96, slow_start=33, slow_ms=60.0):
    """The headline case: a seeded sustained slowdown (every step from
    `slow_start` sleeps `slow_ms` inside the timed region) after a
    healthy baseline. Acceptance: >= 1 ReplanAdvisory with cause
    "slowdown", re-priced through the warm store with ZERO profile
    calls, whose candidate plan matches a COLD search under the same
    perturbed costs (FF_TPU_COST_SCALE seeding CostStore.live_scale)."""
    import tempfile

    from flexflow_tpu.runtime.fault import SLOW_MS_ENV, install_schedule

    base = _chaos_ckpt_base_dir()
    mdir = tempfile.mkdtemp(prefix="ffdrift_slow_", dir=base)
    store = os.path.join(mdir, "cost_db.json")
    batch, dim = 16, 256
    m = _drift_model(mdir, store, batch=batch, dim=dim)
    xv, yv = _drift_data(batch, steps, dim)
    prev_env = os.environ.get(SLOW_MS_ENV)
    os.environ[SLOW_MS_ENV] = str(slow_ms)
    sched = _drift_slow_schedule(slow_start)
    install_schedule(sched)
    try:
        m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
    finally:
        install_schedule(None)
        if prev_env is None:
            os.environ.pop(SLOW_MS_ENV, None)
        else:
            os.environ[SLOW_MS_ENV] = prev_env
    report = (m.search_provenance or {}).get("drift") or {}
    advisories = report.get("advisories") or []
    adv = advisories[0] if advisories else None
    out = {
        "metrics_dir": mdir,
        "steps": steps,
        "slow_from_step": slow_start,
        "slow_ms": slow_ms,
        "slow_steps_fired": len(sched.fired_log),
        "estimated_ms": (m.search_provenance or {}).get("estimated_ms"),
        "windows": report.get("windows"),
        "baseline_ratio": report.get("baseline_ratio"),
        "advisories": len(advisories),
        "advisory": adv,
    }
    if adv is None:
        return out
    out["cause"] = adv["cause"]
    out["repriced"] = adv["repriced"]
    # zero-profile evidence: re-run the same warm repricer with
    # profile_fn counted — the warm store must serve every leaf
    import flexflow_tpu.local_execution.cost_estimator as lce

    calls = [0]
    orig = lce.profile_fn

    def counting(fn, settings, *a, **k):
        calls[0] += 1
        return orig(fn, settings, *a, **k)

    lce.profile_fn = counting
    try:
        re2 = m._drift_research(float(adv["ema_ratio"]))
    finally:
        lce.profile_fn = orig
    out["research_profile_calls"] = calls[0]
    out["research_seconds"] = round(re2["research_seconds"], 3)
    # cold search under the SAME perturbed costs: a fresh compile whose
    # CostStore.live_scale is seeded from the env — its winner is the
    # ground truth the advisory's candidate must match
    os.environ["FF_TPU_COST_SCALE"] = str(float(adv["ema_ratio"]))
    try:
        cold = _drift_model(
            tempfile.mkdtemp(prefix="ffdrift_cold_", dir=base), store,
            drift=False, batch=batch, dim=dim,
        )
    finally:
        os.environ.pop("FF_TPU_COST_SCALE", None)
    cold_deg = (cold.search_provenance or {}).get("parallel_degrees")
    out["cold_parallel_degrees"] = cold_deg
    out["advisory_parallel_degrees"] = adv.get("parallel_degrees")
    out["candidate_matches_cold_search"] = (
        adv.get("parallel_degrees") == cold_deg
    )
    return out


def _drift_batch_growth_block(steps=96, batch=16, grow=8, dim=256):
    """The workload grows out from under the plan: a healthy run at the
    searched batch establishes the stream, then a `grow`x-batch model
    CONTINUES the same metrics dir. Its monitor re-reads the whole
    stream (events.jsonl accumulates across fits by design), so the
    baseline is fitted from the small-batch steps and the out-of-band
    windows carry the tokens-per-step growth the cause classifier keys
    on — the advisory must say `batch_growth`, not `slowdown`: the plan
    is stale, the machine is fine."""
    import tempfile

    base = _chaos_ckpt_base_dir()
    mdir = tempfile.mkdtemp(prefix="ffdrift_grow_", dir=base)
    store = os.path.join(mdir, "cost_db.json")
    m1 = _drift_model(mdir, store, batch=batch, dim=dim)
    xv, yv = _drift_data(batch, steps, dim)
    m1.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
    rep1 = (m1.search_provenance or {}).get("drift") or {}
    big = batch * grow
    m2 = _drift_model(mdir, store, batch=big, dim=dim)
    xv2, yv2 = _drift_data(big, steps, dim, seed=1)
    m2.fit(xv2, yv2, epochs=1, shuffle=False, verbose=False)
    rep2 = (m2.search_provenance or {}).get("drift") or {}
    advisories = rep2.get("advisories") or []
    causes = sorted({a["cause"] for a in advisories})
    return {
        "metrics_dir": mdir,
        "batch": batch,
        "grown_batch": big,
        "steps_per_fit": steps,
        "first_fit_advisories": len(rep1.get("advisories") or []),
        "advisories": len(advisories),
        "causes": causes,
        "batch_growth_detected": "batch_growth" in causes,
        "advisory": advisories[0] if advisories else None,
    }


def _drift_control_block(steps=96):
    """Healthy control: the same proxy, monitor config, and step count
    with NO injected fault — zero advisories is the false-positive bar
    the band/run-length defaults must clear on a noisy 2-core host."""
    import tempfile

    mdir = tempfile.mkdtemp(
        prefix="ffdrift_ctl_", dir=_chaos_ckpt_base_dir()
    )
    store = os.path.join(mdir, "cost_db.json")
    batch, dim = 16, 256
    m = _drift_model(mdir, store, batch=batch, dim=dim)
    xv, yv = _drift_data(batch, steps, dim, seed=2)
    m.fit(xv, yv, epochs=1, shuffle=False, verbose=False)
    report = (m.search_provenance or {}).get("drift") or {}
    return {
        "steps": steps,
        "windows": report.get("windows"),
        "baseline_ratio": report.get("baseline_ratio"),
        "ema_ratio": report.get("ema_ratio"),
        "advisories": len(report.get("advisories") or []),
    }


def _drift_overhead_block(steps=96, batch=32, dim=1024, reps=24):
    """Monitor-on vs monitor-off, metrics dir ON in both arms — the
    monitor's marginal cost is the poller thread + incremental tail, not
    the event stream PR-3 already priced. The 1-core host's contention
    comes in multi-second bursts, so per-arm min-of-reps can land the
    two arms in different host epochs and report huge phantom deltas in
    either direction. Instead each rep runs the two fits back-to-back
    (alternating order) and records their PAIRED ratio — adjacent fits
    share the epoch — and the verdict is the median ratio across reps,
    robust to the reps a burst still managed to split. Many SHORT pairs
    (~1-2 s fits x 24 reps) beat few long ones: a multi-second burst
    splits at most a couple of pairs and the median shrugs them off.
    dim=1024 puts steps near 15-20 ms so scheduling jitter (absolute,
    ~1-2 ms) stays under the 5% bar."""
    import tempfile

    base = _chaos_ckpt_base_dir()
    xv, yv = _drift_data(batch, steps, dim)
    models = {}
    for arm, on in (("off", False), ("on", True)):
        mdir = tempfile.mkdtemp(prefix=f"ffdrift_ovh_{arm}_", dir=base)
        store = os.path.join(mdir, "cost_db.json")
        # band=8: the bar prices STEADY-STATE monitoring (tail + window +
        # detect). This 1-core host's contention bursts swing window means
        # by +-80%, which crosses any production band and fires replan
        # re-searches inside the measured fit — real monitor work, but a
        # deliberate-and-rare event priced separately by the slowdown
        # block's research_seconds. on_advisories below proves the arms
        # stayed steady-state.
        models[arm] = _drift_model(
            mdir, store, drift=on, batch=batch, dim=dim, band=8.0
        )
        # warmup epoch compiles the step program outside the measurement
        models[arm].fit(
            xv[: batch * 16], yv[: batch * 16], epochs=1, shuffle=False,
            verbose=False,
        )
    times = {arm: [] for arm in models}
    ratios = []
    for rep in range(reps):
        rep_t = {}
        order = ("off", "on") if rep % 2 == 0 else ("on", "off")
        for arm in order:
            t0 = time.perf_counter()
            models[arm].fit(xv, yv, epochs=1, shuffle=False, verbose=False)
            rep_t[arm] = time.perf_counter() - t0
            times[arm].append(rep_t[arm])
        ratios.append(rep_t["on"] / rep_t["off"])
    ratios.sort()
    n = len(ratios)
    median_ratio = (
        ratios[n // 2]
        if n % 2
        else (ratios[n // 2 - 1] + ratios[n // 2]) / 2.0
    )
    best = {arm: min(ts) for arm, ts in times.items()}
    step_ms = {arm: t / steps * 1000.0 for arm, t in best.items()}
    overhead = (median_ratio - 1.0) * 100.0
    on_drift = (
        models["on"].search_provenance.get("drift") or {}
    )
    return {
        # nonzero would mean the measurement paid for replan re-searches,
        # not steady-state monitoring (see the band=8 note above)
        "on_advisories": len(on_drift.get("advisories") or []),
        "proxy": {"batch": batch, "dim": dim, "steps": steps},
        "reps": reps,
        "host_cores": os.cpu_count(),
        "off_step_ms": round(step_ms["off"], 4),
        "on_step_ms": round(step_ms["on"], 4),
        "paired_ratio_min": round(ratios[0], 4),
        "paired_ratio_median": round(median_ratio, 4),
        "paired_ratio_max": round(ratios[-1], 4),
        "overhead_pct": round(overhead, 2),
        "bar_pct": 5.0,
        "within_bar": bool(overhead <= 5.0),
    }


def _drift_ffreport_block(mdir):
    """Round-trip through the committed inspector: `ffreport --json` over
    the slowdown run's metrics dir must exit 0 and reproduce the
    advisory (verdict "drifting", same cause); a malformed (empty) dir
    must exit 1 — the CLI exit contract tier-1 smokes."""
    import subprocess
    import tempfile

    tool = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools", "ffreport.py"
    )
    out = subprocess.run(
        [sys.executable, tool, "--json", mdir],
        capture_output=True, text=True, timeout=300,
    )
    sections = [
        json.loads(line)
        for line in out.stdout.splitlines()
        if line.strip()
    ]
    drift = next(
        (s for s in sections if s.get("section") == "drift"), {}
    )
    empty = tempfile.mkdtemp(prefix="ffdrift_bad_")
    bad = subprocess.run(
        [sys.executable, tool, empty],
        capture_output=True, text=True, timeout=120,
    )
    return {
        "exit_code": out.returncode,
        "sections": sorted(
            s.get("section") for s in sections if s.get("section")
        ),
        "verdict": drift.get("verdict"),
        "advisories": drift.get("advisories"),
        "last_advisory_cause": (
            (drift.get("last_advisory") or {}).get("cause")
        ),
        "malformed_dir_exit_code": bad.returncode,
    }


def run_drift(args):
    """`bench.py --drift` (ISSUE 18): the live plan-fidelity drift block —
    a seeded sustained slowdown fires a ReplanAdvisory whose re-priced
    candidate matches the cold-search winner under the same perturbed
    costs (zero profile calls), the batch-growth case classifies the
    cause correctly, the healthy control raises nothing, the monitor
    costs <= 5% of step time, and ffreport round-trips the advisory.
    Committed as DRIFT_r*.json. A single-device host re-execs onto the
    virtual 8-device CPU mesh (same discipline as run_chaos)."""
    if len(jax.devices()) < 2:
        return _reexec_on_virtual_mesh("--drift", timeout=7200)
    result = {
        "metric": "drift",
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
    }
    # the overhead A/B runs FIRST: the later blocks leave models, XLA
    # buffers, and /dev/shm streams behind, and on a 1-core container
    # that ambient pressure inflates BOTH arms' step times past what
    # min-of-reps can cancel — a 5% question needs the quiet host
    try:
        result["overhead"] = _drift_overhead_block()
    except Exception as e:
        result["overhead_error"] = f"{type(e).__name__}: {e}"[:200]
    slow = None
    try:
        slow = _drift_slowdown_block()
        result["slowdown"] = slow
    except Exception as e:
        result["slowdown_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        result["batch_growth"] = _drift_batch_growth_block()
    except Exception as e:
        result["batch_growth_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        result["control"] = _drift_control_block()
    except Exception as e:
        result["control_error"] = f"{type(e).__name__}: {e}"[:200]
    if slow and slow.get("metrics_dir"):
        try:
            result["ffreport"] = _drift_ffreport_block(
                slow["metrics_dir"]
            )
        except Exception as e:
            result["ffreport_error"] = f"{type(e).__name__}: {e}"[:200]
    return result


def _serving_requests(rng, n, prompt_len, vocab, slo_ms_per_token=None):
    """The synthetic request population: fixed-length prompts, skewed
    generation lengths (three short readers per long writer — the regime
    continuous batching exists for)."""
    from flexflow_tpu.serving import ServeRequest

    reqs = []
    for i in range(n):
        gen = 4 if i % 4 else 24
        reqs.append(
            ServeRequest(
                rid=f"r{i}",
                prompt=rng.integers(0, vocab, prompt_len).astype(np.int32),
                max_new_tokens=gen,
                slo_ms_per_token=slo_ms_per_token,
            )
        )
    return reqs


def _serving_engine(prog, mode, cap, metrics_dir=None, window_steps=4):
    from flexflow_tpu.serving import ServingEngine

    return ServingEngine(
        prog,
        mode=mode,
        window_steps=window_steps,
        max_concurrent=cap,
        metrics_dir=metrics_dir,
    )


def _latency_histogram(records, edges_ms=(10, 20, 50, 100, 200, 500, 1000)):
    """Request-latency histogram: counts per total-ms bucket, the last
    bucket open-ended."""
    counts = [0] * (len(edges_ms) + 1)
    for r in records:
        t = r.total_ms
        for j, e in enumerate(edges_ms):
            if t < e:
                counts[j] += 1
                break
        else:
            counts[-1] += 1
    labels = ["<%dms" % edges_ms[0]]
    labels += [
        "%d-%dms" % (a, b) for a, b in zip(edges_ms, edges_ms[1:])
    ]
    labels.append(">=%dms" % edges_ms[-1])
    return {"edges_ms": list(edges_ms), "labels": labels, "counts": counts}


def _serving_ab(prog, cap, n_requests, prompt_len, vocab, reps=3):
    """Continuous-vs-static A/B on a saturated backlog: best-of-`reps`
    sustained requests/s per mode, arms interleaved so host-load drift
    hits both equally (the chaos-overhead protocol)."""
    best = {"static": float("inf"), "continuous": float("inf")}
    for _ in range(reps):
        for mode in ("static", "continuous"):
            eng = _serving_engine(prog, mode, cap)
            rng = np.random.default_rng(11)
            for r in _serving_requests(rng, n_requests, prompt_len, vocab):
                eng.submit(r)
            t0 = time.perf_counter()
            recs = eng.run()
            elapsed = time.perf_counter() - t0
            assert len(recs) == n_requests
            best[mode] = min(best[mode], elapsed)
    out = {
        mode: {
            "requests_per_s": n_requests / best[mode],
            "elapsed_s": best[mode],
        }
        for mode in best
    }
    out["continuous_over_static"] = (
        out["continuous"]["requests_per_s"]
        / out["static"]["requests_per_s"]
    )
    return out


def _serving_open_loop(prog, cap, n_requests, prompt_len, vocab,
                       rate_rps, slo_ms_per_token, metrics_dir):
    """The open-loop load generator: requests arrive on a fixed-rate
    wall-clock schedule REGARDLESS of completions (arrival pressure is
    never gated on the server — the open-loop property), the continuous
    engine drains window-by-window, and queue time is real waiting."""
    eng = _serving_engine(
        prog, "continuous", cap, metrics_dir=metrics_dir
    )
    rng = np.random.default_rng(5)
    reqs = _serving_requests(
        rng, n_requests, prompt_len, vocab, slo_ms_per_token
    )
    interarrival = 1.0 / rate_rps
    t0 = time.perf_counter()
    nxt = 0
    while True:
        now = time.perf_counter() - t0
        while nxt < len(reqs) and nxt * interarrival <= now:
            eng.submit(reqs[nxt])
            nxt += 1
        busy = bool(eng.queue) or any(
            r.active_mask().any() for r in eng.replicas if not r.shed
        )
        if busy:
            eng.run(max_windows=1)
        elif nxt < len(reqs):
            # idle until the next scheduled arrival — open-loop: the
            # schedule, not the server, decides when requests appear
            time.sleep(
                max(nxt * interarrival - (time.perf_counter() - t0), 0)
            )
        else:
            break
    elapsed = time.perf_counter() - t0
    s = eng.summary()
    recs = eng.completed
    return {
        "offered_rate_rps": rate_rps,
        "sustained_requests_per_s": len(recs) / elapsed,
        "elapsed_s": elapsed,
        "completed": s["completed"],
        "tokens_generated": s["tokens_generated"],
        "p50_ms_per_token": s["p50_ms_per_token"],
        "p99_ms_per_token": s["p99_ms_per_token"],
        "slo_ms_per_token": slo_ms_per_token,
        "slo_violations": s["slo_violations"],
        "mean_queue_ms": float(
            np.mean([r.queue_ms for r in recs])
        ),
        "max_observed_concurrent": s["max_observed_concurrent"],
        "latency_histogram": _latency_histogram(recs),
    }


def run_serving(args):
    """`bench.py --serving`: the serving-engine block (ISSUE 12) — a
    searched forward-only plan on the 8-device virtual CPU mesh serving a
    synthetic load through the continuous-batching engine. Emits the
    continuous-vs-static A/B (saturated backlog, best-of-reps
    interleaved), the open-loop latency/SLO block, and the MEM005 static
    max-concurrent-sequences verdict beside the observed OOM-free
    admission, plus the search/ffcheck agreement check (a budgeted
    serving search must never select a plan `ffcheck --memory --serving`
    rejects). Committed as SERVE_r*.json. A single-device host re-execs
    onto the virtual 8-device CPU mesh."""
    if len(jax.devices()) < 2:
        return _reexec_on_virtual_mesh("--serving")

    import tempfile

    from flexflow_tpu.analysis.diagnostics import has_errors
    from flexflow_tpu.analysis.memory_analysis import (
        serving_verdict,
        verify_memory,
    )
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingCache,
    )
    from flexflow_tpu.compiler.unity_algorithm import evaluate_pcg
    from flexflow_tpu.observability.metrics import read_run_events
    from flexflow_tpu.parallel.mesh import MachineMesh
    from flexflow_tpu.pcg.machine_view import MachineSpecification
    from flexflow_tpu.pcg.parallel_computation_graph import (
        pcg_from_computation_graph,
    )
    from flexflow_tpu.serving import (
        ServingLMConfig,
        ServingProgram,
        ServingWorkload,
        build_serving_lm,
        optimize_serving_plan,
        serving_search_context,
    )
    from flexflow_tpu.serving.kv_cache import (
        attention_layers,
        per_device_cache_bytes,
    )

    spec = MachineSpecification(1, 1, 8, 1.0, 2.0)
    cfg = ServingLMConfig()
    prompt_len, gen_len, slots = 6, 24, 8
    wl = ServingWorkload(
        prompt_len=prompt_len, gen_len=gen_len, max_concurrent=slots
    )

    def builder(b, s):
        return build_serving_lm(cfg, b, s)

    # an hbm budget the SERIAL plan's cache busts but a sharded one fits:
    # the search must shard the cache, and the pruner/verdict agreement
    # below is exercised at a budget that actually discriminates
    cache_spec = wl.cache_spec(max_seq_len=512)
    serial_pcg = pcg_from_computation_graph(builder(slots, 1)[0])
    analysis, _ = verify_memory(serial_pcg, spec, None, serving=cache_spec)
    serial_peak = max(d.peak_bytes for d in analysis.per_device.values())
    serial_cache = per_device_cache_bytes(
        serial_pcg, attention_layers(serial_pcg), cache_spec
    )
    hbm_gb = (serial_peak - serial_cache // 2) / 2**30

    t0 = time.perf_counter()
    plan = optimize_serving_plan(
        builder, spec, wl, hbm_gb=hbm_gb, budget=4, max_seq_len=512
    )
    search_s = time.perf_counter() - t0

    # agreement: the serial plan is INFEASIBLE to the DP at this budget...
    ctx, _ = serving_search_context(spec, cache_spec, hbm_gb=hbm_gb)
    serial_rejected = (
        evaluate_pcg(serial_pcg, ctx, spec, MachineMappingCache()) is None
    )
    # ...and the winner passes the same verifier ffcheck --memory
    # --serving runs, at the same capacity (MEM005-clean)
    winner_clean = True
    for phase in (plan.decode, plan.prefill):
        _, diags = verify_memory(
            phase.pcg, spec, phase.machine_mapping,
            hbm_bytes=hbm_gb * 2**30, serving=cache_spec,
        )
        winner_clean = winner_clean and not has_errors(diags)
    win_analysis, _ = verify_memory(
        plan.decode.pcg, spec, plan.decode.machine_mapping,
        serving=cache_spec,
    )
    verdict = serving_verdict(win_analysis, hbm_gb * 2**30)

    mm = MachineMesh.from_spec(spec)
    prog = ServingProgram(
        plan.decode.pcg, plan.cache_spec,
        mapping=plan.decode.machine_mapping, machine_mesh=mm,
        params_seed=0,
    )
    # warm the prefill/decode programs so the load blocks measure
    # serving, not XLA compilation
    scratch = prog.init_cache()
    scratch, tok, _ = prog.prefill(
        scratch, np.zeros((slots, prompt_len), np.int32),
        np.full(slots, prompt_len, np.int32), np.ones(slots, bool),
    )
    prog.decode_window(
        scratch, np.asarray(tok), np.full(slots, prompt_len, np.int32),
        np.ones(slots, bool), 4,
    )

    cap = min(verdict.max_sequences, slots)
    ab = _serving_ab(prog, cap, 32, prompt_len, cfg.vocab_size)

    metrics_dir = tempfile.mkdtemp(prefix="ffserve_")
    # offer ~60% of the measured continuous capacity so the open-loop
    # block exercises queue dynamics without unbounded backlog growth
    rate = max(ab["continuous"]["requests_per_s"] * 0.6, 0.5)
    open_loop = _serving_open_loop(
        prog, cap, 32, prompt_len, cfg.vocab_size,
        rate_rps=rate, slo_ms_per_token=50.0, metrics_dir=metrics_dir,
    )
    n_events = len(read_run_events(metrics_dir, "serve_request"))

    return {
        "metric": "serving",
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
        "model": {
            "vocab": cfg.vocab_size, "embed": cfg.embed_dim,
            "heads": cfg.num_heads, "layers": cfg.num_layers,
            "prompt_len": prompt_len, "gen_len": gen_len,
            "slots": slots,
        },
        "search": {
            "seconds": search_s,
            "hbm_gb": hbm_gb,
            "ms_per_token": plan.ms_per_token,
            "decode_ms": plan.decode_ms,
            "prefill_ms": plan.prefill_ms,
            "serial_plan_rejected_by_dp": serial_rejected,
            "winner_passes_ffcheck_serving": winner_clean,
            "provenance": {
                k: plan.provenance[k]
                for k in ("objective", "forward_only", "decode", "prefill")
            },
        },
        "verdict": {
            "requested_sequences": cache_spec.max_concurrent_seqs,
            "static_max_sequences": verdict.max_sequences,
            "limiting_device": verdict.limiting_device,
            "admission_cap": cap,
            "max_observed_concurrent": open_loop[
                "max_observed_concurrent"
            ],
            # the acceptance cross-check: admission never exceeded the
            # static verdict and every request completed OOM-free
            "observed_within_verdict": (
                open_loop["max_observed_concurrent"] <= cap
            ),
        },
        "ab": ab,
        "open_loop": open_loop,
        "request_events_written": n_events,
    }


def _pipeline_proxy_pcg(L=16, d=256, B=64):
    """The deep-model proxy (ISSUE 13): a uniform L-layer dense chain —
    deep enough that flat SPMD prices badly under a memory budget, uniform
    enough that the 1F1B executor's stage-isomorphism holds."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.parallel_tensor_shape import lift_to_parallel
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape
    from flexflow_tpu.pcg.parallel_computation_graph_builder import (
        ParallelComputationGraphBuilder,
    )

    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(
        lift_to_parallel(TensorShape((B, d), DataType.FLOAT)), name="x"
    )
    h = x
    for i in range(L):
        h = b.dense(h, d, activation=Activation.RELU, name=f"l{i}")
    return b.graph


def _pipeline_estimator_ctx(budget_bytes=0.0):
    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
        AnalyticTPUCostEstimator,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingContext,
    )
    from flexflow_tpu.pcg.machine_view import MachineSpecification

    spec = MachineSpecification(1, 1, 8, 1.0, 2.0)
    est = AnalyticTPUCostEstimator(
        spec, peak_flops=5e10, hbm_gbps=10.0,
        ici_latency_ms=0.1, dcn_latency_ms=0.2, emulated_mesh=True,
    )
    ctx = MachineMappingContext(
        est, make_default_allowed_machine_views(),
        overlap_fraction=0.5, memory_budget_bytes=budget_bytes,
        optimizer_state_slots=2, steps_per_dispatch=1,
    )
    return spec, est, ctx


def _pipeline_instance(pcg, lr=1e-3):
    from flexflow_tpu.analysis.lowering import find_logit_tensor
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.parallel.pipeline import PipelinedTrainingInstance
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

    return PipelinedTrainingInstance(
        pcg,
        find_logit_tensor(pcg),
        SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=lr),
    )


def _pipeline_step_ms(inst, params, opt_state, xv, yv, iters=8, reps=3):
    from flexflow_tpu.kernels.profiling import force_sync

    rng = jax.random.PRNGKey(0)
    # warmup/compile
    params, opt_state, loss, _ = inst.train_step(
        params, opt_state, {"x": xv}, yv, rng
    )
    force_sync(loss)
    best = None
    for _ in range(reps):
        start = time.perf_counter()
        for _ in range(iters):
            rng, srng = jax.random.split(rng)
            params, opt_state, loss, _ = inst.train_step(
                params, opt_state, {"x": xv}, yv, srng
            )
        force_sync(loss)
        ms = (time.perf_counter() - start) * 1000.0 / iters
        best = ms if best is None else min(best, ms)
    return best, params, opt_state


def run_pipeline(args):
    """`bench.py --pipeline` (ISSUE 13): the pipeline-parallelism block on
    the 8-dev virtual mesh — committed as PIPE_r*.json.

    1. search: under a binding --hbm-gb-equivalent budget the flat SPMD
       plans (serial and every dp/tp/sp seed) are MEM-INFEASIBLE, the
       search selects a stage-partitioned plan, and the winner passes
       `ffcheck --memory` + `ffcheck --comm` semantics (verify_memory /
       verify_comm on the pipelined step program), with native == python
       DP cost agreement.
    2. execution A/B: the searched pipelined plan's 1F1B step vs the flat
       SPMD winner of the SAME proxy searched without the budget.
    3. bubble: predicted (S-1)/(S-1+M) vs measured from a two-point
       microbatch sweep (step(M) = ideal x (1 + (S-1)/M), so two M values
       identify the ideal and the measured bubble fraction).
    4. memory: predicted per-device peak (the mapped liveness analysis)
       vs XLA `memory_analysis()` of the compiled 1F1B step."""
    if len(jax.devices()) < 2:
        extra = []
        if args.profile_trace_dir:
            # forward the flag: the CHILD is the process doing the
            # measured work, so its trace is the one worth keeping
            extra += ["--profile-trace-dir", args.profile_trace_dir]
        return _reexec_on_virtual_mesh("--pipeline", extra, timeout=7200)
    import math

    from flexflow_tpu.analysis.diagnostics import has_errors
    from flexflow_tpu.analysis.memory_analysis import (
        analyze_memory,
        verify_memory,
    )
    from flexflow_tpu.compiler.unity_algorithm import (
        OptimizerConfig,
        evaluate_pcg,
        graph_optimize,
    )
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingCache,
    )
    from flexflow_tpu.pcg.pipeline import (
        analyze_pipeline,
        pipeline_bubble_fraction,
    )
    from flexflow_tpu.substitutions.rules import (
        generate_parallelization_rules,
    )

    L, d, B = 8, 256, 64
    budget_bytes = int(1.7 * 2**20)  # binds: every flat plan peaks above it
    # seed microbatch count: the census cross-check compiles the winner's
    # schedule UNROLLED (T = 2(M+S-1) ticks) and XLA's optimization time
    # on that program is strongly superlinear in T (T=46 blows past 80 GB
    # host RAM; T=22 compiles for tens of minutes) — M=2 keeps the same
    # winner stage count (the budget forces S=8 either way) at T=18,
    # which compiles in ~a minute on the virtual mesh
    M_seed = 2
    result = {
        "metric": "pipeline",
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
        "proxy": {"layers": L, "hidden": d, "batch": B},
        "hbm_budget_mib": budget_bytes / 2**20,
    }

    # -- 1. budgeted search selects a pipelined plan ----------------------
    pcg = _pipeline_proxy_pcg(L, d, B)
    spec, est, ctx = _pipeline_estimator_ctx(budget_bytes)
    rules = generate_parallelization_rules([2, 4, 8], enable_pipeline=True)
    t0 = time.perf_counter()
    print("[pipeline] search...", file=sys.stderr, flush=True)
    res = graph_optimize(
        pcg, ctx, spec, rules,
        OptimizerConfig(
            budget=2, pipeline_seeds=True, pipeline_microbatches=M_seed
        ),
    )
    region = analyze_pipeline(res.pcg)
    mem = analyze_memory(res.pcg, spec, res.machine_mapping)
    _, mem_diags = verify_memory(
        res.pcg, spec, res.machine_mapping, hbm_bytes=budget_bytes
    )
    # native/python DP cost parity on the pipelined winner
    os.environ["FF_TPU_NO_NATIVE"] = "1"
    try:
        py = evaluate_pcg(res.pcg, ctx, spec, MachineMappingCache())
    finally:
        os.environ.pop("FF_TPU_NO_NATIVE", None)
    nat = evaluate_pcg(res.pcg, ctx, spec, MachineMappingCache())
    from flexflow_tpu.analysis.comm_analysis import verify_comm

    print("[pipeline] comm census (unrolled)...", file=sys.stderr, flush=True)
    try:
        comm_analysis, comm_diags = verify_comm(
            res.pcg, mapping=None, machine_spec=spec, estimator=est
        )
        comm_block = {
            "errors": has_errors(comm_diags),
            "collectives": len(comm_analysis.collectives),
            "bytes_geomean": comm_analysis.bytes_geomean,
        }
    except Exception as e:
        comm_block = {"error": f"{type(e).__name__}: {e}"[:200]}
    result["search"] = {
        "search_seconds": round(time.perf_counter() - t0, 3),
        "flat_serial_infeasible": res.serial_runtime is None,
        "winner_is_pipelined": bool(region is not None and region.ok),
        "num_stages": None if region is None else region.num_stages,
        "num_microbatches": (
            None if region is None else region.num_microbatches
        ),
        "winner_estimated_ms": res.runtime,
        "seed_runtimes": {
            k: round(v, 3) for k, v in (res.seed_runtimes or {}).items()
        },
        "winner_peak_mib_per_device": round(
            mem.max_peak_bytes() / 2**20, 4
        ),
        "ffcheck_memory_errors": has_errors(mem_diags),
        "ffcheck_comm": comm_block,
        "native_equals_python_cost": (
            py is not None
            and nat is not None
            and py.runtime == nat.runtime
        ),
    }

    # seed table (the README's worked HBM-drop table): every flat +
    # pipeline seed of the proxy priced WITHOUT the budget, so the
    # artifact records the full race the budget then prunes
    from flexflow_tpu.compiler.unity_algorithm import (
        enumerate_pipeline_seeds,
        enumerate_seeds,
    )

    _, _, free_ctx = _pipeline_estimator_ctx(0.0)
    seed_table = {}
    for label, seed in list(enumerate_seeds(pcg, spec.num_devices)) + list(
        enumerate_pipeline_seeds(
            pcg, spec.num_devices, microbatches=M_seed
        )
    ):
        r = evaluate_pcg(seed, free_ctx, spec, MachineMappingCache())
        if r is None:
            continue
        m = analyze_memory(seed, spec, r.machine_mapping)
        seed_table[label] = {
            "estimated_ms": round(r.runtime, 3),
            "peak_mib_per_device": round(m.max_peak_bytes() / 2**20, 4),
        }
    result["seed_table"] = seed_table

    # -- 2/3/4. execution: pipelined 1F1B vs flat SPMD winner -------------
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(B, d), jnp.float32)
    yv = jnp.asarray(rs.randint(0, d, (B,)), jnp.int32)

    S = result["search"]["num_stages"] or 8
    M = result["search"]["num_microbatches"] or M_seed
    print("[pipeline] 1F1B step timing...", file=sys.stderr, flush=True)
    # never lose the search/seed-table data already in `result`: a flat
    # or non-1F1B-executable winner is an honest (gate-failing) artifact,
    # not a crash — same error-block pattern as the other bench modes
    from flexflow_tpu.parallel.pipeline import PipelineUnsupported

    try:
        inst = _pipeline_instance(res.pcg)
    except PipelineUnsupported as e:
        result["error"] = (
            "searched winner is not 1F1B-executable: "
            f"{type(e).__name__}: {e}"[:300]
        )
        return result
    params, opt_state = inst.initialize(seed=0)
    pipe_ms, params, opt_state = _pipeline_step_ms(
        inst, params, opt_state, xv, yv
    )

    # flat SPMD winner of the same proxy (no budget, no pipeline seeds)
    from flexflow_tpu.analysis.lowering import find_logit_tensor
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.parallel.executor import DistributedTrainingInstance
    from flexflow_tpu.parallel.mesh import MachineMesh
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

    _, _, flat_ctx = _pipeline_estimator_ctx(0.0)
    flat = graph_optimize(
        pcg, flat_ctx, spec, rules, OptimizerConfig(budget=2)
    )
    flat_inst = DistributedTrainingInstance(
        flat.pcg,
        find_logit_tensor(flat.pcg),
        SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-3),
        MachineMesh.from_spec(spec),
        mapping=flat.machine_mapping,
    )
    fp, fo = flat_inst.initialize(seed=0)
    flat_ms, fp, fo = _pipeline_step_ms(flat_inst, fp, fo, xv, yv)
    result["step_ms"] = {
        "pipelined_1f1b": round(pipe_ms, 3),
        "flat_spmd_winner": round(flat_ms, 3),
        "flat_winner_estimated_ms": flat.runtime,
        "pipelined_over_flat": round(pipe_ms / flat_ms, 4),
    }

    print("[pipeline] bubble measurement...", file=sys.stderr, flush=True)
    # bubble: the 1F1B step vs the SEQUENTIAL-schedule reference (same
    # scan body, same M, different tick table — the bitwise-parity
    # baseline), which isolates the per-tick cost model on this host:
    #   t_pipe = T*o      + W*u        (T = 2(M+S-1) ticks, W = 2MS units)
    #   t_seq  = T_seq*o  + W*u        (T_seq = 2MS, one unit per tick)
    # solve (o, u) = (per-tick overhead, per-unit work), then integrate
    # the idle share over the EXECUTED action table: tick t with a_t
    # active stages leaves S - a_t stages idle for its whole duration
    # tau_t = o + a_t*u, so
    #   measured = sum_t (S - a_t)*tau_t / (S * sum_t tau_t)
    # On real hardware idle devices idle in wall-clock; on the shared-core
    # virtual mesh the same integral prices idle slots at the measured
    # tick durations — either way it converges to the structural
    # (S-1)/(S-1+M) only if the executor really runs the 1F1B table.
    from flexflow_tpu.pcg.pipeline import one_f_one_b_schedule

    seq_inst = _pipeline_instance(res.pcg)
    sp, so = seq_inst.initialize(seed=0)
    os.environ["FF_TPU_PIPELINE_BASELINE"] = "1"
    try:
        seq_ms, _, _ = _pipeline_step_ms(seq_inst, sp, so, xv, yv)
    finally:
        os.environ.pop("FF_TPU_PIPELINE_BASELINE", None)
    fwd_tab, bwd_tab = one_f_one_b_schedule(S, M)
    act = ((fwd_tab >= 0) | (bwd_tab >= 0)).sum(axis=1)  # a_t, [T]
    T_ticks, W = int(fwd_tab.shape[0]), int(act.sum())
    T_seq = 2 * M * S
    o_ms = max((seq_ms - pipe_ms) / (T_seq - T_ticks), 0.0)
    u_ms = max((pipe_ms - T_ticks * o_ms) / W, 0.0)
    tau = o_ms + act * u_ms  # per-tick durations, [T]
    measured = float(((S - act) * tau).sum() / max(S * tau.sum(), 1e-9))
    predicted = pipeline_bubble_fraction(S, M)
    result["bubble"] = {
        "predicted": round(predicted, 4),
        "measured": round(measured, 4),
        "measured_over_predicted": round(measured / max(predicted, 1e-9), 4),
        "schedule": {
            "ticks_1f1b": T_ticks,
            "ticks_sequential": T_seq,
            "work_units": W,
            "step_ms_sequential": round(seq_ms, 3),
            "tick_overhead_ms": round(o_ms, 4),
            "unit_ms": round(u_ms, 4),
        },
    }

    print("[pipeline] memory cross-check...", file=sys.stderr, flush=True)
    # memory: predicted per-device peak vs XLA's compiled accounting
    from flexflow_tpu.analysis.lowering import lower_step_program

    try:
        lowered = lower_step_program(
            inst, params, opt_state, inst.loss_attrs
        )
        ma = lowered.memory_analysis()
        xla_bytes = max(
            int(ma.argument_size_in_bytes)
            + int(ma.output_size_in_bytes)
            + int(ma.temp_size_in_bytes)
            - int(ma.alias_size_in_bytes),
            1,
        )
        peaks = [v for v in mem.peak_by_device().values() if v > 0]
        geo = (
            math.exp(
                sum(math.log(p / xla_bytes) for p in peaks) / len(peaks)
            )
            if peaks
            else None
        )
        result["memory"] = {
            "predicted_peak_mib_per_device": round(
                mem.max_peak_bytes() / 2**20, 4
            ),
            "xla_per_device_mib": round(xla_bytes / 2**20, 4),
            "predicted_over_xla_geomean": (
                None if geo is None else round(geo, 4)
            ),
        }
    except Exception as e:
        result["memory"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    return result


# --------------------------------------------------------------------------
# hierarchical multi-slice search (ISSUE 17) — SLICE_r17.json


def _multislice_proxy_pcg(L=4, d=1024, B=512):
    """The multi-slice proxy: a uniform weight-heavy dense chain whose
    dp-hybrid plan replicates d x d weight blocks across the slice (DCN)
    boundary every step. The shapes sit in the disagreement band the A/B
    needs: under FLAT (uniform-constant) pricing the full-machine
    dp-over-the-boundary hybrid wins (the 2x compute advantage beats
    uniformly-priced weight replication), while under the TRUE 10x
    ICI/DCN gap those same replicate edges dominate and the optimum
    stays inside the slice."""
    from flexflow_tpu.op_attrs.activation import Activation
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.op_attrs.parallel_tensor_shape import lift_to_parallel
    from flexflow_tpu.op_attrs.tensor_shape import TensorShape
    from flexflow_tpu.pcg.parallel_computation_graph_builder import (
        ParallelComputationGraphBuilder,
    )

    b = ParallelComputationGraphBuilder()
    x = b.create_input_tensor(
        lift_to_parallel(TensorShape((B, d), DataType.FLOAT)), name="x"
    )
    h = x
    for i in range(L):
        h = b.dense(h, d, activation=Activation.RELU, name=f"l{i}")
    return b.graph


def _multislice_spec(gap=10.0, ici_gbps=2.0):
    """The 2-slice 4+4 virtual machine: slices are the node axis (INTER =
    DCN at ici/gap GB/s, INTRA = ICI). gap=1.0 is the uniform-bandwidth
    machine of the counter-example — identical constants on every link,
    i.e. exactly what the flat (slice-blind) cost model assumes the
    machine always looks like."""
    from flexflow_tpu.pcg.machine_view import MachineSpecification

    return MachineSpecification(2, 1, 4, ici_gbps / gap, ici_gbps)


def _multislice_ctx(spec, slice_aware=False, hierarchy=False, flat=False):
    """Estimator + mapping context on `spec`. `flat=True` builds the
    slice-BLIND arm: the same machine geometry priced with one constant
    per link class pair (dcn latency = ici latency; the spec passed in
    should carry uniform bandwidths) — the pre-slice-aware worldview the
    tentpole replaces."""
    from flexflow_tpu.compiler.machine_mapping.cost_estimator import (
        AnalyticTPUCostEstimator,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingContext,
    )

    est = AnalyticTPUCostEstimator(
        spec, peak_flops=5e10, hbm_gbps=10.0,
        ici_latency_ms=0.1,
        dcn_latency_ms=0.1 if flat else 0.2,
        emulated_mesh=True,
    )
    ctx = MachineMappingContext(
        est, make_default_allowed_machine_views(),
        overlap_fraction=0.5,
        slice_aware=slice_aware, slice_hierarchy=hierarchy,
    )
    return est, ctx


def run_multislice(args):
    """`bench.py --multislice` (ISSUE 17): the hierarchical two-level
    ICI/DCN search vs the flat (slice-blind) search on the emulated
    2-slice 4+4 machine — committed as SLICE_r17.json.

    A/B semantics: the FLAT arm searches under the uniform-constant
    machine model (every link priced alike — the model the tentpole
    replaces), and its winner's mapping is then re-priced, views pinned,
    under the TRUE 10x-gap model via `price_mapped_plan` — the cost that
    plan actually incurs on the real machine. The HIERARCHICAL arm
    searches the true model directly with the two-level DP. The gate is
    flat_true_ms / hier_ms >= 1.2. The honest counter-example runs the
    same two arms on the uniform-bandwidth machine, where the flat
    model's assumption is CORRECT, and must find identical winners."""
    if len(jax.devices()) < 2:
        extra = []
        if args.profile_trace_dir:
            extra += ["--profile-trace-dir", args.profile_trace_dir]
        return _reexec_on_virtual_mesh("--multislice", extra, timeout=7200)
    from flexflow_tpu.analysis.comm_analysis import verify_comm
    from flexflow_tpu.analysis.diagnostics import has_errors
    from flexflow_tpu.analysis.pcg_verify import verify_pcg
    from flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping import (
        MachineMappingCache,
    )
    from flexflow_tpu.compiler.machine_mapping.hierarchical import (
        HierarchicalMachineMappingCache,
    )
    from flexflow_tpu.compiler.machine_mapping.movement_export import (
        export_movement_predictions,
    )
    from flexflow_tpu.compiler.unity_algorithm import (
        OptimizerConfig,
        enumerate_seeds,
        evaluate_pcg,
        graph_optimize,
        parallel_degree_summary,
        price_mapped_plan,
    )
    from flexflow_tpu.substitutions.rules import (
        generate_parallelization_rules,
    )

    L, d, B = 4, 1024, 512
    gap = 10.0
    pcg = _multislice_proxy_pcg(L, d, B)
    rules = generate_parallelization_rules([2, 4, 8])
    spec_true = _multislice_spec(gap)
    spec_uni = _multislice_spec(1.0)
    est_true, ctx_true = _multislice_ctx(spec_true)
    _, ctx_hier = _multislice_ctx(spec_true, slice_aware=True, hierarchy=True)
    result = {
        "metric": "multislice",
        "backend": jax.default_backend(),
        "num_devices": len(jax.devices()),
        "topology": {
            "slices": spec_true.num_nodes,
            "devices_per_slice": spec_true.num_devices_per_node,
            "ici_gbps": spec_true.intra_node_bandwidth,
            "dcn_gbps": spec_true.inter_node_bandwidth,
            "gap": gap,
        },
        "proxy": {"layers": L, "hidden": d, "batch": B},
    }

    # -- flat arm: slice-blind search, winner re-priced truthfully --------
    t0 = time.perf_counter()
    print("[multislice] flat (slice-blind) search...", file=sys.stderr,
          flush=True)
    _, ctx_flat = _multislice_ctx(spec_uni, flat=True)
    res_flat = graph_optimize(
        pcg, ctx_flat, spec_uni, rules, OptimizerConfig(budget=2)
    )
    flat_true_ms = price_mapped_plan(
        res_flat.pcg, res_flat.machine_mapping, ctx_true, spec_true
    )
    flat_diags = verify_pcg(
        res_flat.pcg, machine_spec=spec_true,
        mapping=res_flat.machine_mapping,
    )
    result["flat"] = {
        "winner_degrees": parallel_degree_summary(res_flat.pcg),
        "blind_estimated_ms": res_flat.runtime,
        "true_ms": flat_true_ms,
        "seed_runtimes_blind": {
            k: round(v, 4) for k, v in (res_flat.seed_runtimes or {}).items()
        },
        # the verifier's slice-straddle rule, pointed at the blind plan on
        # the true machine: every MV004 here is a tensor-sharded axis the
        # flat model happily routed across DCN
        "mv004_on_true_machine": sum(
            1 for dg in flat_diags if dg.rule_id == "MV004"
        ),
    }

    # -- hierarchical arm: the two-level DP on the true machine -----------
    print("[multislice] hierarchical search...", file=sys.stderr, flush=True)
    res_hier = graph_optimize(
        pcg, ctx_hier, spec_true, rules, OptimizerConfig(budget=2)
    )
    hier_diags = verify_pcg(
        res_hier.pcg, machine_spec=spec_true,
        mapping=res_hier.machine_mapping,
    )
    ratio = (
        None if flat_true_ms is None or not res_hier.runtime
        else flat_true_ms / res_hier.runtime
    )
    result["hierarchical"] = {
        "winner_degrees": parallel_degree_summary(res_hier.pcg),
        "estimated_ms": res_hier.runtime,
        "outer": res_hier.hierarchical,
        "seed_runtimes": {
            k: round(v, 4) for k, v in (res_hier.seed_runtimes or {}).items()
        },
        "verify_errors": has_errors(hier_diags),
    }
    result["gate"] = {
        "flat_true_ms": flat_true_ms,
        "hier_ms": res_hier.runtime,
        "flat_over_hier": None if ratio is None else round(ratio, 4),
        "passes_1p2x": ratio is not None and ratio >= 1.2,
    }

    # -- placement census: where did the winner's movement land? ----------
    preds = export_movement_predictions(
        res_hier.pcg, res_hier.machine_mapping,
        estimator=est_true, machine_spec=spec_true,
    )
    by_class = {}
    dcn_kinds = set()
    for p in preds:
        lc = p.link_class or "unknown"
        by_class[lc] = by_class.get(lc, 0) + 1
        if lc == "dcn":
            dcn_kinds.add(p.kind)
    result["placement"] = {
        "edges_by_link_class": by_class,
        "dcn_edge_kinds": sorted(dcn_kinds),
        # the acceptance claim: tensor-parallel movement (partial-sum
        # Combine/Reduction) rides ICI only; anything crossing DCN is
        # data/replica/stage movement
        "tensor_parallel_all_ici": not (
            {"CombineAttrs", "ReductionAttrs"} & dcn_kinds
        ),
    }

    # -- native == python parity on the hierarchical winner ---------------
    os.environ["FF_TPU_NO_NATIVE"] = "1"
    try:
        py = evaluate_pcg(
            res_hier.pcg, ctx_hier, spec_true,
            HierarchicalMachineMappingCache(),
        )
    finally:
        os.environ.pop("FF_TPU_NO_NATIVE", None)
    nat = evaluate_pcg(
        res_hier.pcg, ctx_hier, spec_true, HierarchicalMachineMappingCache()
    )
    result["native_equals_python_cost"] = (
        py is not None and nat is not None and py.runtime == nat.runtime
    )

    # -- ffcheck --comm census on the winner ------------------------------
    print("[multislice] comm census...", file=sys.stderr, flush=True)
    try:
        comm_analysis, comm_diags = verify_comm(
            res_hier.pcg, mapping=res_hier.machine_mapping,
            machine_spec=spec_true, estimator=est_true,
        )
        result["ffcheck_comm"] = {
            "errors": has_errors(comm_diags),
            "collectives": len(comm_analysis.collectives),
            "bytes_geomean": comm_analysis.bytes_geomean,
        }
    except Exception as e:
        result["ffcheck_comm"] = {"error": f"{type(e).__name__}: {e}"[:200]}

    # -- counter-example: uniform bandwidth => identical winners ----------
    # On the uniform machine the flat model's assumption is TRUE, so the
    # slice-blind search above IS the honest search of that machine; the
    # hierarchical arm must find the same winner at the same cost.
    print("[multislice] uniform counter-example...", file=sys.stderr,
          flush=True)
    _, ctx_hier_uni = _multislice_ctx(
        spec_uni, slice_aware=True, hierarchy=True, flat=True
    )
    res_uni = graph_optimize(
        pcg, ctx_hier_uni, spec_uni, rules, OptimizerConfig(budget=2)
    )
    flat_uni_ms = price_mapped_plan(
        res_flat.pcg, res_flat.machine_mapping,
        _multislice_ctx(spec_uni, flat=True)[1], spec_uni,
    )
    same_degrees = (
        parallel_degree_summary(res_flat.pcg)
        == parallel_degree_summary(res_uni.pcg)
    )
    result["uniform_counter_example"] = {
        "flat_ms": flat_uni_ms,
        "hier_ms": res_uni.runtime,
        "hier_winner_degrees": parallel_degree_summary(res_uni.pcg),
        "identical_winners": bool(
            same_degrees
            and flat_uni_ms is not None
            and res_uni.runtime is not None
            and abs(flat_uni_ms - res_uni.runtime)
            <= 1e-9 * max(abs(flat_uni_ms), 1.0)
        ),
    }
    result["search_seconds"] = round(time.perf_counter() - t0, 3)
    return result


def main():
    import argparse

    from flexflow_tpu.kernels.metrics import METRIC_ACCURACY
    from flexflow_tpu.local_execution import ModelTrainingInstance
    from flexflow_tpu.op_attrs.ops.loss_functions import (
        SparseCategoricalCrossEntropyLossAttrs,
    )
    from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
    from flexflow_tpu.pcg import ComputationGraphBuilder

    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512,
                    help="sequence length (512 = the reference headline "
                         "config; 2048 exercises the flash-attention path, "
                         "min_seq gate permitting)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--heads", type=int, default=None,
                    help="attention heads (8 = the headline config; 16 = "
                         "the reference TransformerConfig default, d=64)")
    ap.add_argument("--roofline", action="store_true",
                    help="emit the per-op roofline attribution JSON "
                         "instead of the headline bench (observability/)")
    ap.add_argument("--fused", action="store_true",
                    help="emit the fused-dispatch JSON block (AlexNet "
                         "per-step vs fused K, dispatch_overhead_ms, fused "
                         "flagship) instead of the headline bench")
    ap.add_argument("--fused-k", type=int, default=8,
                    help="steps_per_dispatch for the --fused block and the "
                         "headline's fused fields")
    ap.add_argument("--overlap", action="store_true",
                    help="emit the compute/communication-overlap JSON "
                         "block: fused vs serial collective-matmul A/B on "
                         "the bandwidth-bound proxy + flagship/seq-2048 "
                         "subjects, and the DP overlap-selection block")
    ap.add_argument("--plan-audit", action="store_true",
                    help="emit the predicted-vs-measured plan-audit JSON "
                         "for the transformer subject plus the forced-NaN "
                         "health demo (observability/plan_audit.py)")
    ap.add_argument("--plan-audit-budget", type=int, default=4,
                    help="Unity search budget for the --plan-audit subject")
    ap.add_argument("--chaos", action="store_true",
                    help="emit the elastic-runtime JSON block: async vs "
                         "sync checkpoint overhead %% on the fused proxy, "
                         "kill+resume bitwise fidelity, degraded-grid "
                         "recovery wall-clock (runtime/checkpoint.py)")
    ap.add_argument("--chaos-every", type=int, default=64,
                    help="checkpoint interval (steps) for the --chaos "
                         "overhead measurement")
    ap.add_argument("--chaos-reps", type=int, default=8,
                    help="interleaved measurement reps per --chaos arm "
                         "(min-of-reps; more reps tighten the noise floor)")
    ap.add_argument("--cost-db", action="store_true",
                    help="emit the persistent cost-database JSON block: "
                         "cold vs warm-store measured search on the "
                         "12-layer CPU proxy (fresh process per arm) and "
                         "the audit-ratio geomean before/after fitted "
                         "per-op-class corrections (compiler/cost_store)")
    ap.add_argument("--cost-db-budget", type=int, default=2,
                    help="search budget for the --cost-db proxy searches")
    ap.add_argument("--chaos-soak", action="store_true",
                    help="emit the fault-domain supervision JSON block: "
                         "one seeded FaultSchedule per site on the DP and "
                         "searched backends (bitwise recovery required), "
                         "the watchdog-fires capture, and the truncated-"
                         "checkpoint auto-fallback (runtime/supervisor.py)")
    ap.add_argument("--pipeline", action="store_true",
                    help="emit the pipeline-parallelism JSON block "
                         "(ISSUE 13): budgeted search selects a "
                         "stage-partitioned plan on the deep proxy "
                         "(flat SPMD MEM-INFEASIBLE), 1F1B step vs the "
                         "flat winner, predicted-vs-measured bubble "
                         "fraction, per-device peak HBM vs XLA "
                         "memory_analysis() (parallel/pipeline.py)")
    ap.add_argument("--multislice", action="store_true",
                    help="emit the hierarchical multi-slice search JSON "
                         "block (ISSUE 17): flat (slice-blind) vs "
                         "two-level ICI/DCN search on the emulated "
                         "2-slice 4+4 machine under a 10x bandwidth gap, "
                         "with the uniform-bandwidth counter-example "
                         "(machine_mapping/hierarchical.py)")
    ap.add_argument("--drift", action="store_true",
                    help="emit the live drift-telemetry JSON block "
                         "(ISSUE 18): a seeded sustained slowdown fires "
                         "a ReplanAdvisory whose warm re-priced candidate "
                         "matches the cold-search winner under the same "
                         "perturbed costs, the batch-growth case names "
                         "its cause, the healthy control stays silent, "
                         "monitor overhead <= 5%%, and tools/ffreport.py "
                         "round-trips the advisory "
                         "(observability/drift.py)")
    ap.add_argument("--serving", action="store_true",
                    help="emit the serving-engine JSON block: a searched "
                         "forward-only plan on the 8-dev virtual mesh "
                         "under a synthetic open-loop load generator — "
                         "continuous-vs-static A/B, latency histogram, "
                         "p50/p99 ms/token, SLO counter, and the MEM005 "
                         "static max-sequences verdict vs observed "
                         "admission (serving/engine.py)")
    ap.add_argument("--profile-trace-dir", type=str, default="",
                    help="write a Chrome-trace span timeline of the "
                         "measured steps into this directory")
    args = ap.parse_args()
    if args.fused_k < 1:
        ap.error("--fused-k must be >= 1")

    from flexflow_tpu.local_execution.config import (
        configure_compilation_cache,
    )

    configure_compilation_cache()

    trace_rec = None
    if args.profile_trace_dir:
        from flexflow_tpu.observability.trace import (
            TraceRecorder,
            set_recorder,
        )

        trace_rec = TraceRecorder()
        set_recorder(trace_rec)

    if args.roofline:
        result = run_roofline(args)
        if trace_rec is not None:
            set_recorder(None)
            result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.fused:
        result = run_fused(args)
        if trace_rec is not None:
            set_recorder(None)
            result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.overlap:
        result = run_overlap(args)
        if trace_rec is not None:
            set_recorder(None)
            result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.cost_db:
        result = run_cost_db(args)
        if trace_rec is not None:
            set_recorder(None)
            result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.pipeline:
        result = run_pipeline(args)
        if trace_rec is not None:
            set_recorder(None)
            if "trace_file" not in result:
                result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.multislice:
        result = run_multislice(args)
        if trace_rec is not None:
            set_recorder(None)
            if "trace_file" not in result:
                result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.drift:
        result = run_drift(args)
        if trace_rec is not None:
            set_recorder(None)
            if "trace_file" not in result:
                result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.serving:
        result = run_serving(args)
        if trace_rec is not None:
            set_recorder(None)
            if "trace_file" not in result:
                result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.chaos_soak:
        result = run_chaos_soak(args)
        if trace_rec is not None:
            set_recorder(None)
            if "trace_file" not in result:
                result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.chaos:
        result = run_chaos(args)
        if trace_rec is not None:
            set_recorder(None)
            if "trace_file" not in result:
                result["trace_file"] = trace_rec.save(args.profile_trace_dir)
        print(json.dumps(result))
        return

    if args.plan_audit:
        result = run_plan_audit(args)
        if trace_rec is not None:
            set_recorder(None)
            # a re-exec'd run already carries the child's trace_file; the
            # parent recorder saw none of the work and must not clobber it
            if "trace_file" not in result:
                result["trace_file"] = trace_rec.save(
                    args.profile_trace_dir
                )
        print(json.dumps(result))
        return

    # Transformer config matching the reference's headline example
    # (examples/cpp/Transformer/transformer.cc:80-100: hidden 1024, 12
    # layers, 8 heads, seq 512; batch 64 per device as in the reference
    # multi-gpu scripts)
    seq = args.seq
    batch, embed, heads, layers, vocab = 64, 1024, 8, 12, 32000
    if args.heads is not None:
        heads = args.heads
    if args.batch is not None:
        batch = args.batch
    elif seq > 512:
        batch = max(1, 64 * 512 // seq)  # keep tokens/step constant

    graph, logits = build_flagship_cg(
        batch, seq, embed, heads, layers, vocab
    )

    inst = ModelTrainingInstance(
        graph,
        logits,
        SparseCategoricalCrossEntropyLossAttrs(),
        AdamOptimizerAttrs(alpha=1e-4),
        compute_dtype=jnp.bfloat16,
    )
    params, opt_state = inst.initialize(seed=0)
    rs = np.random.RandomState(0)
    xv = jnp.asarray(rs.randn(batch, seq, embed), jnp.float32)
    yv = jnp.asarray(rs.randint(0, vocab, (batch, seq)), jnp.int32)

    # analytic model FLOPs per step (fwd + bwd ~= 3x fwd)
    step_flops = _model_step_flops(batch, seq, embed, heads, layers, vocab)

    from flexflow_tpu.kernels.profiling import force_sync

    # warmup/compile
    params, opt_state, loss, _ = inst.train_step(params, opt_state, {"x": xv}, yv)
    force_sync(loss)

    def run(iters, params, opt_state):
        start = time.perf_counter()
        loss = None
        for _ in range(iters):
            params, opt_state, loss, _ = inst.train_step(
                params, opt_state, {"x": xv}, yv
            )
        force_sync(loss)
        return time.perf_counter() - start, params, opt_state

    # two-point measurement cancels the fixed per-window dispatch and
    # sync cost. Five samples at a 12-iteration denominator; the median is
    # the reported value and the spread of the middle three samples is the
    # reported noise band.
    n1, n2 = 3, 15
    samples = []
    for _ in range(5):
        t1, params, opt_state = run(n1, params, opt_state)
        t2, params, opt_state = run(n2, params, opt_state)
        s = (t2 - t1) / (n2 - n1)
        samples.append(s if s > 0 else t2 / n2)
    samples.sort()
    step_time = samples[len(samples) // 2]

    # search wall-clock on the SAME 12-layer flagship over the virtual
    # 8-device mesh (search cost is a first-class concern: reference
    # --search-budget, config.h:82-84; reference A/B budgets are 20-30,
    # scripts/osdi22ae/bert.sh:3-7, hence the budget-30 timing too). Runs on
    # host CPU in a child pinned to the CPU (the parent holds the chip, so
    # a child must never need it).
    result_errors = {}
    search_seconds = None
    search_seconds_b30 = None
    search_telemetry_b8 = None
    search_telemetry_b30 = None
    try:
        import subprocess

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        code = (
            "import json, sys, time, jax; jax.config.update('jax_platforms','cpu');"
            "sys.path.insert(0, %r);"
            "from flexflow_tpu.compiler import ("
            "AnalyticTPUCostEstimator, MachineMappingContext, OptimizerConfig,"
            "graph_optimize, make_default_allowed_machine_views);"
            "from flexflow_tpu.pcg.machine_view import MachineSpecification;"
            "from flexflow_tpu.substitutions.rules import generate_parallelization_rules;"
            "from bench import build_flagship_pcg;"
            "pcg = build_flagship_pcg();"
            "spec = MachineSpecification(1, 1, 8, 1.0, 2.0);"
            "est = AnalyticTPUCostEstimator(spec, peak_flops=5e10, hbm_gbps=10.0,"
            "ici_latency_ms=0.1, dcn_latency_ms=0.2, emulated_mesh=True);"
            "ctx = MachineMappingContext(est, make_default_allowed_machine_views(),"
            "overlap_fraction=0.5);"
            "rules = generate_parallelization_rules([2, 4, 8]);"
            "keys = ('mm_cache_hits', 'mm_cache_misses', 'native_dp', 'phase_ms');"
            "t0 = time.perf_counter();"
            "r = graph_optimize(pcg, ctx, spec, rules, OptimizerConfig(alpha=1.2, budget=8));"
            "print('SEARCH_SECONDS', time.perf_counter() - t0, flush=True);"
            "print('SEARCH_TELEMETRY_B8', json.dumps({k: (r.telemetry or {}).get(k) for k in keys}), flush=True);"
            "t0 = time.perf_counter();"
            "r = graph_optimize(pcg, ctx, spec, rules, OptimizerConfig(alpha=1.2, budget=30));"
            "print('SEARCH_SECONDS_B30', time.perf_counter() - t0, flush=True);"
            "print('SEARCH_TELEMETRY_B30', json.dumps({k: (r.telemetry or {}).get(k) for k in keys}), flush=True)"
        ) % os.path.dirname(os.path.abspath(__file__))
        out = None
        try:
            out = subprocess.run(
                [sys.executable, "-c", code], env=env, capture_output=True,
                text=True, timeout=600,
            )
            stdout = out.stdout
        except subprocess.TimeoutExpired as te:
            # keep whatever the child printed before the cap (a budget-30
            # overrun must not null the already-measured budget-8 field)
            stdout = (te.stdout or b"")
            if isinstance(stdout, bytes):
                stdout = stdout.decode(errors="replace")
        for line in stdout.splitlines():
            if line.startswith("SEARCH_SECONDS_B30"):
                search_seconds_b30 = round(float(line.split()[1]), 1)
            elif line.startswith("SEARCH_SECONDS"):
                search_seconds = round(float(line.split()[1]), 1)
            elif line.startswith("SEARCH_TELEMETRY_B8"):
                search_telemetry_b8 = json.loads(line.split(None, 1)[1])
            elif line.startswith("SEARCH_TELEMETRY_B30"):
                search_telemetry_b30 = json.loads(line.split(None, 1)[1])
        if search_seconds is None:
            raise RuntimeError(
                "search child printed no SEARCH_SECONDS: "
                + (out.stderr[-300:] if out is not None else "timed out")
            )
    except Exception as e:
        result_errors["search_error"] = f"{type(e).__name__}: {e}"[:400]

    # -- estimate <-> measured calibration on the REAL chip (round-3 verdict
    # next-step #5): the analytic cost model prices the serial flagship plan
    # with the datasheet constants; the headline measurement IS that plan
    # executed, so their ratio is the model's end-to-end error on this chip,
    # and the effective constants derived from the measurement replace the
    # hand-set ones for anyone consuming this JSON.
    calibration = None
    try:
        from flexflow_tpu.compiler import (
            AnalyticTPUCostEstimator,
            MachineMappingContext,
            make_default_allowed_machine_views,
        )
        from flexflow_tpu.compiler.unity_algorithm import evaluate_pcg
        from flexflow_tpu.pcg.machine_view import MachineSpecification

        from flexflow_tpu.compiler import MachineMappingCache

        from flexflow_tpu.compiler.machine_constants import (
            machine_constants,
        )

        mc = machine_constants()
        spec = MachineSpecification(
            1, 1, 1, mc.inter_node_gbps, mc.intra_node_gbps
        )
        est = AnalyticTPUCostEstimator(
            spec, peak_flops=mc.peak_flops, hbm_gbps=mc.hbm_gbps
        )
        ctx = MachineMappingContext(
            est, make_default_allowed_machine_views(), overlap_fraction=0.5
        )
        pcg = build_flagship_pcg(batch, seq, embed, heads, layers, vocab)
        r = evaluate_pcg(pcg, ctx, spec, MachineMappingCache())
        if r is not None:
            est_ms = r.runtime
            meas_ms = step_time * 1000
            calibration = {
                "serial_estimated_ms": round(est_ms, 3),
                "serial_measured_ms": round(meas_ms, 3),
                "measured_over_estimated": round(meas_ms / est_ms, 3),
                # effective chip constants implied by the measurement
                "effective_flops_per_s": round(step_flops / step_time),
                "datasheet_flops_per_s": peak_flops_per_device(),
            }
    except Exception as e:
        result_errors["calibration_error"] = (
            f"{type(e).__name__}: {e}"[:400]
        )

    # -- long-context second metric (round-3 verdict next-step #9): the
    # flash/ring work gets a chip number, not just CPU tests. Token count
    # is held constant (batch scales down) so tokens/s is comparable.

    def _measure_recorded(result, err_key, **kw):
        """A failed secondary metric is recorded under its error key (and
        turns the exit code non-zero) instead of vanishing from the
        artifact."""
        try:
            return _measure(**kw)
        except Exception as e:
            result[err_key] = f"{type(e).__name__}: {e}"[:400]
        return None

    longctx = None
    if seq == 512:
        longctx = _measure_recorded(
            result_errors, "longctx_error",
            batch=max(1, batch * seq // 2048), seq=2048,
            embed=embed, heads=heads, layers=layers, vocab=vocab,
        )

    # -- reference-default config (TransformerConfig num_heads=16, d=64):
    # the headline uses 8 heads (d=128 fills the MXU contraction); this
    # second number is the same model at the reference's own default,
    # riding the head-pair flash kernels
    ref16 = None
    if seq == 512 and heads == 8:
        ref16 = _measure_recorded(
            result_errors, "ref_heads16_error",
            batch=batch, seq=seq, embed=embed, heads=16,
            layers=layers, vocab=vocab,
        )

    mfu = step_flops / step_time / peak_flops_per_device()
    result = {
        "metric": "transformer_train_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / 0.35, 4),
        "step_time_ms": round(step_time * 1000, 3),
        "step_time_spread_ms": round(
            (samples[-2] - samples[1]) * 1000, 3
        ),
        "tokens_per_s": round(batch * seq / step_time, 1),
        "search_seconds_12l_budget8": search_seconds,
        "search_seconds_12l_budget30": search_seconds_b30,
        "search_telemetry_b8": search_telemetry_b8,
        "search_telemetry_b30": search_telemetry_b30,
        "search_mm_cache_hit_rate_b30": (
            round(
                search_telemetry_b30["mm_cache_hits"]
                / max(
                    search_telemetry_b30["mm_cache_hits"]
                    + search_telemetry_b30["mm_cache_misses"],
                    1,
                ),
                4,
            )
            if search_telemetry_b30
            and search_telemetry_b30.get("mm_cache_hits") is not None
            else None
        ),
        "calibration": calibration,
    }
    if longctx is not None:
        result["longctx_seq2048_mfu"] = longctx["mfu"]
        result["longctx_seq2048_step_ms"] = longctx["step_ms"]
        result["longctx_seq2048_tokens_per_s"] = longctx["tokens_per_s"]
    if ref16 is not None:
        result["ref_heads16_mfu"] = ref16["mfu"]
        result["ref_heads16_step_ms"] = ref16["step_ms"]

    # -- conv-net chip number (round-4 verdict next-step #5): AlexNet at the
    # reference network/image size — conv/pool/dense MFU was previously
    # unmeasured on TPU
    if seq == 512 and heads == 8:
        try:
            conv = _measure_alexnet()
            result["alexnet_mfu"] = conv["mfu"]
            result["alexnet_step_ms"] = conv["step_ms"]
            result["alexnet_images_per_s"] = conv["images_per_s"]
        except Exception as e:
            result_errors["alexnet_error"] = f"{type(e).__name__}: {e}"[:200]
        # fused multi-step dispatch on the dispatch-bound subject: the K=1
        # vs K=8 delta IS the per-step dispatch overhead the fused engine
        # amortizes (ISSUE 5; README "Step fusion and the input pipeline")
        try:
            fusedc = _measure_alexnet_fused(k=args.fused_k)
            result["alexnet_fused_step_ms"] = fusedc["step_ms"]
            result["alexnet_fused_images_per_s"] = fusedc["images_per_s"]
            if "alexnet_step_ms" in result:
                result["dispatch_overhead_ms"] = round(
                    result["alexnet_step_ms"] - fusedc["step_ms"], 3
                )
                result["fused_speedup"] = round(
                    fusedc["images_per_s"] / result["alexnet_images_per_s"],
                    3,
                )
        except Exception as e:
            result_errors["alexnet_fused_error"] = (
                f"{type(e).__name__}: {e}"[:200]
            )
        try:
            result["fused_flagship"] = _measure_flagship_fused(
                batch=batch, seq=seq, embed=embed, heads=heads,
                layers=layers, vocab=vocab, k=4,
            )
        except Exception as e:
            result_errors["fused_flagship_error"] = (
                f"{type(e).__name__}: {e}"[:200]
            )
    result.update(result_errors)
    if trace_rec is not None:
        from flexflow_tpu.observability.trace import set_recorder

        set_recorder(None)
        result["trace_file"] = trace_rec.save(args.profile_trace_dir)
    print(json.dumps(result))
    if result_errors:
        # every phase that raised is in the JSON under its *_error key;
        # a run that lost a phase is not a passing run
        sys.exit(1)


if __name__ == "__main__":
    main()
