"""One run of one benchmark cell, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in `BENCHMARK.json`: a model configuration
(`benchmark/configs/<config>.json` + `.py`) under a training job
(`benchmark/jobs/<traffic>.json`). The run builds the model through the
program's public entry points (`FFModel.from_computation_graph` -> `compile`
-> `fit`, telemetry off), checks it against the configuration's float32
reference, warms the one step shape up, and then repeats whole `fit` chunks
over a seeded token set until `--seconds` is spent. With `--trace 0` it
reports the cell's end-to-end metrics; with `--trace 1` it profiles a few
chunks instead and reports the cell's per-layer metrics, each computed by its
own reader `benchmark/layer_metrics/<metric>.py`.

The last line of standard output is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, and `breakdown` when traced). Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints no
result; only a job file marked `"rehearsal": true` runs on the CPU mesh, and
then says `"platform": "cpu"` and reports no device metric.

Everything that belongs to one configuration, job or per-layer metric is a
file found by the name in `BENCHMARK.json`; adding a cell edits no file here.
"""

import time

_PROCESS_T0 = time.perf_counter()

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# traces and other run-time output; listed in .gitignore
OUT = os.path.join(ROOT, ".bench_out")

EXIT_NO_DEVICE = 3


def load_module(path):
    name = "bench_" + os.path.basename(path)[:-3].replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(manifest_path, workload):
    """The cell's manifest entry, configuration, job and metric lists."""
    manifest = load_json(manifest_path)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"run.py: no workload {workload!r} in {manifest_path}; it has "
            f"{sorted(cells)}"
        )
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config_path = os.path.join(ROOT, entry["file"])
    config = load_json(config_path)
    module_path = os.path.join(
        os.path.dirname(config_path),
        config.get("py", os.path.basename(config_path)[:-5] + ".py"),
    )
    job = load_json(os.path.join(BENCH, "jobs", cell["traffic"] + ".json"))
    if job["chips"] != cell["chips"]:
        raise SystemExit(
            f"run.py: job {cell['traffic']} is for {job['chips']} chips, the "
            f"cell asks for {cell['chips']}"
        )

    def in_cell(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": config,
        "module_path": module_path,
        "job": job,
        "end_to_end": [m for m in manifest["end_to_end"] if in_cell(m)],
        "per_layer": [m for m in manifest["per_layer"] if in_cell(m)],
    }


class Counters:
    """What jax.monitoring says about compilation, by phase. The listeners
    stay registered for the life of the process; `phase` says where the
    next event is booked."""

    def __init__(self, jax):
        self.phase = "setup"
        self.compiles = {"setup": 0, "window": 0}
        self.compile_seconds = {"setup": 0.0, "window": 0.0}
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles[self.phase] += 1
            self.compile_seconds[self.phase] += duration


class Spans:
    """Host-clock spans around the benchmark's own calls into the program."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = (
                self.seconds.get(name, 0.0) + time.perf_counter() - t0
            )


def named_parameters(instance, params):
    """The live parameters under their layers' names (`<layer>.weight<i>`),
    which is how a configuration's reference asks for them."""
    from flexflow_tpu.op_attrs.ops import WeightAttrs

    graph = instance.pcg if hasattr(instance, "pcg") else instance.cg
    named = {}
    for n in graph.topological_ordering():
        if isinstance(graph.op_attrs(n), WeightAttrs):
            name = graph.layer_attrs(n).name
            if name is None or name in named:
                raise RuntimeError(
                    f"weight node {n} has no unique name ({name!r}): the "
                    "compiled plan renamed or merged the model's weights, "
                    "so the reference cannot be given them"
                )
            # the reference works in float32 whatever the system keeps
            named[name] = params[f"n{n.idx}"].astype("float32")
    return named


def place_batch(instance, inputs, labels):
    """One host batch on the devices, as the program's dataloader places it."""
    import jax

    if not hasattr(instance, "input_sharding"):
        return (
            {k: jax.device_put(v) for k, v in inputs.items()},
            jax.device_put(labels),
        )
    return (
        {
            k: jax.device_put(v, instance.input_sharding(k))
            for k, v in inputs.items()
        },
        jax.device_put(labels, instance.label_sharding()),
    )


def make_loss_reader(instance):
    """The system's loss on a batch with given parameters: the instance's
    own loss function (the step's compute dtype, forward only), jitted once.
    `PerfMetrics` carries a bf16-rounded sum only when `compile(metrics=)`
    asks for it, and the timed job asks for no metric."""
    import jax

    fn = jax.jit(lambda p, b, y: instance.loss_fn(p, b, y)[0])
    mesh = getattr(instance, "machine_mesh", None)

    def read(params, batch, label):
        with mesh.mesh if mesh is not None else contextlib.nullcontext():
            return float(fn(params, batch, label))

    return read


def step_program_bytes(model):
    """XLA's own account of the compiled train step `fit` runs, per chip."""
    from flexflow_tpu.analysis.lowering import lower_step_trace

    compiled = lower_step_trace(
        model.instance, model.loss_attrs,
        params=model.params, opt_state=model.opt_state,
    ).compile()
    mem = compiled.memory_analysis()
    parts = {
        "arguments": int(mem.argument_size_in_bytes),
        "outputs": int(mem.output_size_in_bytes),
        "aliased": int(mem.alias_size_in_bytes),
        "temp": int(mem.temp_size_in_bytes),
        "code": int(mem.generated_code_size_in_bytes),
    }
    # donated state comes back in place: outputs that alias arguments are
    # counted once
    parts["total"] = (
        parts["arguments"] + parts["outputs"] - parts["aliased"] + parts["temp"]
    )
    return parts, compiled.as_text()


def peak_memory(devices):
    """Largest `peak_bytes_in_use` over the devices, or None on a backend
    that reports no memory statistics (the CPU rehearsal)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats is None:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument(
        "--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
        help="the list of cells (default: BENCHMARK.json at the root; "
        "benchmark/rehearsal.json lists the tiny cells for the CPU mesh)",
    )
    args = ap.parse_args()
    spec = load_cell(args.manifest, args.workload)
    job, config = spec["job"], spec["config"]
    rehearsal = bool(job.get("rehearsal"))

    # before the first jax import
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache")
    )
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={job['chips']}"
        )
        os.environ["FLEXFLOW_TPU_FLASH_INTERPRET"] = "1"
        os.environ["FLEXFLOW_TPU_FLASH_MIN_SEQ"] = "128"
    sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if (platform != "tpu" and not rehearsal) or len(devices) < job["chips"]:
        print(
            f"run.py: cell {args.workload} needs {job['chips']} TPU chip(s); "
            f"jax reports {len(devices)} device(s) of platform {platform!r}",
            file=sys.stderr,
        )
        return EXIT_NO_DEVICE
    devices = devices[: job["chips"]]
    # every program this process compiles goes to the persistent cache, so
    # that set-up after a cell's first run loads and compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # and stays there: a cap on the cache's size (the chip tool's machines
    # come with JAX_COMPILATION_CACHE_MAX_SIZE of 192 MiB) evicts a cell's
    # large programs as fast as they are written, and every run compiles
    jax.config.update("jax_compilation_cache_max_size", -1)

    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel

    counters = Counters(jax)
    spans = Spans()
    module = load_module(spec["module_path"])
    training = config["training"]
    seq = job["seq"]
    batch = job["batch_per_chip"] * job["chips"]
    n_batches = job["dataset_batches"]

    # -- set-up: data, model, compile, reference check, warm-up ------------
    with spans.span("data"):
        rs = np.random.RandomState(args.seed)
        inputs, labels = module.make_data(rs, config, n_batches * batch, seq)
    with spans.span("build"):
        graph, logits = module.build(config, batch, seq)
        ffconfig = FFConfig(
            batch_size=batch, seed=args.seed, print_freq=0,
            max_devices=job["chips"], **job.get("ffconfig", {}),
        )
        model = FFModel.from_computation_graph(graph, logits, ffconfig)
    with spans.span("compile_call"):
        model.compile(
            AdamOptimizer(
                alpha=training["alpha"], beta1=training["beta1"],
                beta2=training["beta2"], epsilon=training["epsilon"],
                weight_decay=training["weight_decay"],
            ),
            training["loss"],
            compute_dtype=jnp.dtype(training["compute_dtype"]),
        )
    instance = model.instance
    state_devices, state_dtypes = set(), set()
    for leaf in jax.tree_util.tree_leaves((model.params, model.opt_state)):
        state_devices |= set(leaf.sharding.device_set)
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            state_dtypes.add(str(leaf.dtype))
    with spans.span("step_program"):
        step_bytes, step_hlo = step_program_bytes(model)

    first = slice(0, batch)
    inputs0 = {k: v[first] for k, v in inputs.items()}
    labels0 = labels[first]
    read_loss = make_loss_reader(instance)
    with spans.span("reference_check"):
        batch0, label0 = place_batch(instance, inputs0, labels0)
        loss_before = read_loss(model.params, batch0, label0)
        ref_before, ref_after = module.reference_losses(
            named_parameters(instance, model.params), inputs0, labels0,
            config, training,
        )
    with spans.span("warm_up"):
        # one step on batch 0 through fit: the step the reference took, and
        # the only shape the window uses
        model.fit(inputs0, labels0, epochs=1, shuffle=False, verbose=False)
        loss_after = read_loss(model.params, batch0, label0)
        # a fit of two steps also runs what only a second step runs (the
        # loop's metric accumulation), with the window's own arguments
        two = slice(0, 2 * batch)
        model.fit(
            {k: v[two] for k, v in inputs.items()}, labels[two], epochs=1,
            shuffle=job["shuffle"], verbose=False, epoch_offset=1,
        )
        all_finite = jax.jit(
            lambda tree: jnp.all(
                jnp.stack([jnp.all(jnp.isfinite(x)) for x in
                           jax.tree_util.tree_leaves(tree)])
            )
        )
        bool(all_finite(model.params))

    attempted = failed = 0
    chunk_spans = []

    def run_chunk(i):
        nonlocal attempted, failed
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("fit_chunk"):
                attempted += n_batches
                model.fit(
                    inputs, labels, epochs=1, shuffle=job["shuffle"],
                    verbose=False, epoch_offset=i,
                )
                jax.block_until_ready(model.params)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("between_chunks"):
                finite = bool(all_finite(model.params))
        except Exception as e:  # a failed step is a result, not a crash
            print(f"run.py: chunk {i} raised {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed += n_batches
            return False
        if not finite:
            failed += n_batches
            return False
        chunk_spans.append((t0, t1))
        return True

    # -- the measured window ------------------------------------------------
    counters.phase = "window"
    trace_dir = None
    t_window = time.perf_counter()
    setup_s = t_window - _PROCESS_T0
    if args.trace:
        trace_dir = os.path.join(OUT, "trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        # device events and the benchmark's annotations, but no Python call
        # stacks: tracing every Python call slows the host that the trace
        # is there to observe
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for i in range(job.get("trace_chunks", 2)):
                if not run_chunk(i):
                    break
        finally:
            jax.profiler.stop_trace()
    else:
        i = 0
        while run_chunk(i):
            i += 1
            elapsed = chunk_spans[-1][1] - t_window
            longest = max(t1 - t0 for t0, t1 in chunk_spans)
            # only whole chunks count, so none is started that would end
            # after the window
            if elapsed + 1.05 * longest > args.seconds:
                break
    window_end = chunk_spans[-1][1] if chunk_spans else time.perf_counter()
    counters.phase = "setup"
    loss_end = read_loss(model.params, batch0, label0)

    # -- correct -------------------------------------------------------------
    # the toy rehearsal configurations state a wider one: a mean over a
    # thousand positions averages less rounding noise away than one over 8,192
    tol = config.get("loss_tolerance", module.LOSS_TOLERANCE)
    checks = {
        "a_loss_matches_reference": abs(loss_before - ref_before) <= tol,
        "b_loss_after_step_matches_reference": (
            abs(loss_after - ref_after) <= tol
        ),
        "c_loss_fell_over_window": (
            math.isfinite(loss_end) and loss_end < loss_before
        ),
        "d_no_compile_in_window": counters.compiles["window"] == 0,
        "e_backend_devices_and_state_dtype": (
            type(instance).__name__ == job["backend"]
            and state_devices == set(devices)
            and state_dtypes == {training["state_dtype"]}
        ),
    }
    correct = all(checks.values()) and failed == 0 and bool(chunk_spans)

    # -- metrics ---------------------------------------------------------------
    tokens_per_step = batch * seq
    steps_done = len(chunk_spans) * n_batches
    step_seconds = [(t1 - t0) / n_batches for t0, t1 in chunk_spans]
    alloc_peak = peak_memory(devices)
    on_chip = platform == "tpu"
    values = {"setup_s": setup_s}
    if on_chip and not args.trace and chunk_spans:
        values["tokens_per_s"] = (
            steps_done * tokens_per_step / (window_end - t_window)
        )
        values["step_hbm_gb"] = step_bytes["total"] / 1e9
    device = {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        # the step program's own bytes are on the chip while it runs, whether
        # or not the allocator's counter shows them (PERF.md section 6)
        "memory_peak_bytes": max(alloc_peak or 0, step_bytes["total"]),
    }
    breakdown = None
    if args.trace:
        import trace_reduce

        # the CPU mesh has no device plane to reduce
        reduced = trace_reduce.reduce_trace_dir(trace_dir) if on_chip else None
        if on_chip:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = reduced["breakdown"]
        context = {
            "spans": spans.seconds,
            "counters": counters,
            "trace": reduced,
            "provenance": model.search_provenance,
            "config": config,
            "job": job,
            "module": module,
            "chips": len(devices),
            "device_kind": devices[0].device_kind,
            "on_chip": on_chip,
            "steps_traced": steps_done,
            "step_seconds": step_seconds,
            "flops_per_step": (
                module.flops_per_token(config, seq) * tokens_per_step
            ),
            "step_bytes": step_bytes,
            "alloc_peak_bytes": alloc_peak,
        }
        values = {}
        for metric in spec["per_layer"]:
            reader = load_module(
                os.path.join(BENCH, "layer_metrics", metric["name"] + ".py")
            )
            value = reader.read(context)
            if value is not None:
                values[metric["name"]] = value
        wanted = spec["per_layer"]
    else:
        wanted = spec["end_to_end"]
    units = {m["name"]: m["unit"] for m in wanted}
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": {
            name: {"value": value, "unit": units[name]}
            for name, value in values.items()
            if name in units
        },
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    # for the reader; the driver ignores these keys
    result["checks"] = checks
    result["losses"] = {
        "system_before": loss_before, "reference_before": ref_before,
        "system_after_step": loss_after, "reference_after_step": ref_after,
        "system_after_window": loss_end, "tolerance": tol,
    }
    result["run"] = {
        "workload": args.workload, "seed": args.seed,
        "backend": type(instance).__name__,
        "steps": steps_done, "chunks": len(chunk_spans),
        "median_step_ms": (
            1e3 * statistics.median(step_seconds) if step_seconds else None
        ),
        "setup_spans_s": spans.seconds,
        "compile_cache": counters.cache,
        "compiles": counters.compiles,
        "compile_seconds": counters.compile_seconds,
        "step_program_bytes": step_bytes,
        "alloc_peak_bytes": alloc_peak,
        "tpu_custom_calls": step_hlo.count("tpu_custom_call"),
        "all_reduces": step_hlo.count(" all-reduce("),
        "all_gathers": step_hlo.count(" all-gather("),
    }
    if isinstance(model.search_provenance, dict):
        result["run"]["search"] = {
            k: model.search_provenance.get(k)
            for k in ("parallel_degrees", "estimated_ms", "search_seconds",
                      "evaluations", "native_dp")
        }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
