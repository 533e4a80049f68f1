"""Proof that the training main path starts on the chip.

One process drives the 12-layer d=1024 flagship
(`models.flagship.build_flagship_cg`: 8 heads of 128, seq 512, batch 64,
vocab 32,000, bf16 compute, Adam, random weights and data from seed 0)
through the entry points a user calls —
`FFModel.from_computation_graph` -> `compile` -> `fit` — once per backend the
attached host can run:

- `single`   one device (`max_devices=1`)          -> ModelTrainingInstance
- `searched` all devices, `search_budget=8`        -> DistributedTrainingInstance
- `dp`       all devices, `only_data_parallel`     -> DataParallelTrainingInstance

(`searched` and `dp` need two or more devices.) Each phase checks what it
printed: the backend class and the devices its parameters live on, a finite
first loss near ln(vocab) that falls on the repeated batch, memory in use on
every device it was given, the Pallas custom calls and the collectives in
the compiled step, and — for the searched plan — a provenance free of
recorded errors (its static verify and exec-contract verdicts are printed
beside what the run measured). A check that fails raises; no phase sits in
a try/except.

Without a TPU the script exits non-zero before compiling anything.
`--rehearse-on-cpu` runs the same phases at toy width on the 8-device
virtual CPU mesh with interpret-mode kernels; it prints `"platform": "cpu"`
and is a check of the script, never of the chip.

The last line of standard output is one JSON object with exactly these keys,
the device as JAX reports it:
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
The line before it, `[chip_smoke] report: {...}`, is the full record (jax and
libtpu versions, the compile cache and its hits, every phase). Step times and
MFU in it are information for the reader, not gates.
"""

import argparse
import importlib.metadata
import json
import math
import os
import shutil
import statistics
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

FLAGSHIP = dict(batch=64, seq=512, embed=1024, heads=8, layers=12, vocab=32000)
# toy width: one 128-wide head so the flash kernels still engage (under
# FLEXFLOW_TPU_FLASH_MIN_SEQ=128), batch divisible by the 8 virtual devices
REHEARSAL = dict(batch=8, seq=128, embed=128, heads=1, layers=2, vocab=256)
STEPS = 7
SEARCH_BUDGET = 8
# where the per-step event streams land (chiprun_out/ is ignored by git and
# is what the chip tool brings back)
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")


class _CacheCounter:
    """Counts jax's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"hits": self.hits, "misses": self.misses}


def _devices_of(tree):
    """The devices a pytree of arrays actually lives on."""
    import jax

    devs = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "sharding"):
            devs |= set(leaf.sharding.device_set)
    return devs


def _memory(devices):
    """Per-device live and peak bytes (None where the backend reports no
    memory statistics, i.e. the CPU rehearsal)."""
    out = {}
    for d in devices:
        stats = d.memory_stats()
        out[str(d.id)] = (
            None
            if stats is None
            else {
                "bytes_in_use": int(stats["bytes_in_use"]),
                "peak_bytes_in_use": int(stats["peak_bytes_in_use"]),
            }
        )
    return out


def _recorded_errors(prov):
    """What compile() caught into the provenance and carried on from."""
    errors = {}
    for key in ("exec", "memory", "comm"):
        rec = prov.get(key) or {}
        err = rec.get("error") or rec.get("xla_error")
        if err:
            errors[key] = err
    return errors


def run_phase(name, backend, ndev, cfg_kwargs, shapes, x, y, cache, on_tpu):
    """One compile -> fit through the public entry points; returns the
    phase record after printing it and checking it."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.models.flagship import (
        build_flagship_cg,
        flagship_step_flops as _model_step_flops,
    )
    from flexflow_tpu.analysis.comm_analysis import extract_collectives
    from flexflow_tpu.analysis.lowering import lower_step_trace
    from flexflow_tpu.compiler.machine_constants import machine_constants
    from flexflow_tpu.core import AdamOptimizer, FFConfig, FFModel
    from flexflow_tpu.observability.metrics import read_events

    metrics_dir = os.path.join(OUT_DIR, name)
    shutil.rmtree(metrics_dir, ignore_errors=True)
    cache_before = cache.snapshot()
    devices = jax.devices()[:ndev]

    cg, logits = build_flagship_cg(**shapes)
    cfg = FFConfig(
        batch_size=shapes["batch"], seed=0, print_freq=0,
        metrics_dir=metrics_dir, max_devices=ndev, **cfg_kwargs,
    )
    model = FFModel.from_computation_graph(cg, logits, cfg)
    t0 = time.perf_counter()
    model.compile(
        AdamOptimizer(alpha=1e-4),
        "sparse_categorical_crossentropy",
        compute_dtype=jnp.bfloat16,
    )
    compile_call_s = time.perf_counter() - t0

    # the step program fit() is about to run, compiled ahead of time for its
    # HLO text (cold unless the persistent cache already holds it; fit's own
    # jit of the same program then loads it from the cache)
    t0 = time.perf_counter()
    compiled = lower_step_trace(
        model.instance, model.loss_attrs,
        params=model.params, opt_state=model.opt_state,
    ).compile()
    step_compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    xla_mem = compiled.memory_analysis()

    t0 = time.perf_counter()
    model.fit(x, y, epochs=STEPS, shuffle=False, verbose=False)
    fit_s = time.perf_counter() - t0

    events = read_events(metrics_dir)
    losses = [e["loss"] for e in events]
    # every step after the first; each ends in a host read of the step's
    # statistics, so these are device times plus one sync
    steady = [e["wallclock_ms"] for e in events[1:]]
    step_ms = statistics.median(steady) if steady else None
    used = _devices_of((model.params, model.opt_state))
    # a dict only where the Unity path compiled the plan
    prov = model.search_provenance
    record = {
        "backend": type(model.instance).__name__,
        "devices_asked": ndev,
        "devices_used": len(used),
        "compile_call_s": round(compile_call_s, 2),
        "step_compile_s": round(step_compile_s, 2),
        "first_step_s": round(events[0]["wallclock_ms"] / 1e3, 2),
        "fit_s": round(fit_s, 2),
        "steady_step_ms": None if step_ms is None else round(step_ms, 2),
        "slowest_steady_step_ms": round(max(steady), 2) if steady else None,
        "mfu_info": (
            round(
                _model_step_flops(**shapes) / (step_ms / 1e3)
                / (len(used) * machine_constants().peak_flops),
                4,
            )
            if step_ms
            else None
        ),
        "loss_first": losses[0],
        "loss_last": losses[-1],
        "losses": losses,
        "memory": _memory(devices),
        # XLA's own per-device accounting of the compiled step, beside
        # the allocator's counters above
        "xla_step_bytes": {
            "arguments": int(xla_mem.argument_size_in_bytes),
            "temp": int(xla_mem.temp_size_in_bytes),
            "aliased": int(xla_mem.alias_size_in_bytes),
        },
        "tpu_custom_calls": hlo.count("tpu_custom_call"),
        "collectives": len(extract_collectives(hlo)),
        "cache": {
            k: v - cache_before[k] for k, v in cache.snapshot().items()
        },
    }
    if prov is not None:
        mem = prov.get("memory") or {}
        verify = prov.get("verify") or {}
        record["search"] = {
            "algorithm": prov.get("search_algorithm"),
            "native_dp": prov.get("native_dp"),
            "search_seconds": round(prov.get("search_seconds", 0.0), 2),
            "estimated_ms": prov.get("estimated_ms"),
            "parallel_degrees": prov.get("parallel_degrees"),
            # the winner's static verdict (PCG invariants, machine views,
            # MEM rules against the device's real bytes_limit): recorded
            # beside what the run measured, not gated — compile() lowers
            # the winner whatever the static memory model predicts
            "verify": {
                "clean": verify.get("clean"),
                "findings": [
                    f"{d['rule_id']}: {d['message']}"
                    for d in verify.get("diagnostics", ())
                ],
            },
            "predicted_peak_bytes_full_mesh": mem.get(
                "predicted_peak_bytes_full_mesh"
            ),
            "capacity_bytes": mem.get("capacity_bytes"),
            "errors": _recorded_errors(prov),
            "exec_clean": ((prov.get("exec") or {}).get("verify") or {}).get(
                "clean"
            ),
        }
    print(f"[chip_smoke] {name}: {json.dumps(record)}", flush=True)

    failures = []

    def check(ok, what):
        if not ok:
            failures.append(what)

    check(record["backend"] == backend, f"backend is not {backend}")
    check(
        len(used) == ndev and used == set(devices),
        f"state lives on {sorted(d.id for d in used)}, asked for {ndev} "
        "devices (compile() lowers the count until it divides the batch)",
    )
    check(len(events) == STEPS, f"{len(events)} step events, not {STEPS}")
    check(
        all(isinstance(v, float) and math.isfinite(v) for v in losses),
        f"non-finite loss in {losses}",
    )
    check(
        isinstance(losses[0], float)
        and abs(losses[0] - math.log(shapes["vocab"])) < 1.0,
        f"first loss {losses[0]} is not near ln(vocab) = "
        f"{math.log(shapes['vocab']):.3f}",
    )
    check(losses[-1] < losses[0], "loss did not fall on the repeated batch")
    if on_tpu:
        # nothing may compile inside the steady window (on the CPU mesh a
        # loaded host alone can triple a 25 ms step, so no gate there)
        check(
            max(steady) < 3 * step_ms,
            f"a step after the first took {max(steady):.0f} ms against a "
            f"median of {step_ms:.0f} ms: something compiled after step 1",
        )
        # a virtual mesh forgives putting everything on device 0; real
        # devices report what they hold
        check(
            all(
                m and m["bytes_in_use"] > 0 and m["peak_bytes_in_use"] > 0
                for m in record["memory"].values()
            ),
            "a device this phase was given holds no memory",
        )
        if prov is None or prov.get("search_algorithm") == "forced_seed":
            # a searched winner may rightly be sequence-parallel with a
            # local block under 512, which uses the XLA ring: there the
            # count is printed beside the winner's degrees, not gated
            check(
                record["tpu_custom_calls"] > 0,
                "no tpu_custom_call in the compiled step: the Pallas flash "
                "kernel was not taken",
            )
    if ndev > 1:
        check(record["collectives"] > 0, "no collective in the compiled step")
    if prov is not None:
        from flexflow_tpu import native_lib

        check(
            record["search"]["native_dp"] is True
            # a forced template is priced once, not searched
            or prov.get("search_algorithm") == "forced_seed",
            "the search ran the Python DP (native core: "
            f"{native_lib.load_error()})",
        )
        check(
            not record["search"]["errors"],
            f"provenance records errors: {record['search']['errors']}",
        )
    if failures:
        raise AssertionError(f"phase {name}: " + "; ".join(failures))
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="toy-width rehearsal on the 8-device virtual CPU mesh with "
        "interpret-mode kernels; prints platform cpu and proves nothing "
        "about the chip",
    )
    args = ap.parse_args()
    if args.rehearse_on_cpu:
        # before the first jax import
        from flexflow_tpu.utils.virtual_mesh_env import (
            force_virtual_device_count,
        )

        force_virtual_device_count(8, cpu_platform=True)
        os.environ["FLEXFLOW_TPU_FLASH_INTERPRET"] = "1"
        os.environ["FLEXFLOW_TPU_FLASH_MIN_SEQ"] = "128"

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse_on_cpu:
        print(
            "chip_smoke: no accelerator — jax.devices()[0].platform is "
            f"{dev.platform!r}, not 'tpu' (--rehearse-on-cpu rehearses the "
            "script on the CPU mesh)",
            file=sys.stderr,
        )
        return 2

    import numpy as np

    from flexflow_tpu.local_execution.config import (
        configure_compilation_cache,
    )

    cache_dir = configure_compilation_cache()
    cache = _CacheCounter()
    shapes = FLAGSHIP if on_tpu else REHEARSAL
    ndev = len(jax.devices())
    rs = np.random.RandomState(0)
    x = rs.randn(shapes["batch"], shapes["seq"], shapes["embed"]).astype(
        np.float32
    )
    y = rs.randint(0, shapes["vocab"], (shapes["batch"], shapes["seq"]))

    phases = {
        "single": run_phase(
            "single", "ModelTrainingInstance", 1, {}, shapes, x, y, cache,
            on_tpu,
        )
    }
    if ndev >= 2:
        phases["searched"] = run_phase(
            "searched", "DistributedTrainingInstance", ndev,
            {"search_budget": SEARCH_BUDGET}, shapes, x, y, cache, on_tpu,
        )
        phases["dp"] = run_phase(
            "dp", "DataParallelTrainingInstance", ndev,
            {"only_data_parallel": True}, shapes, x, y, cache, on_tpu,
        )
    # same seed and data in every phase; the CPU tests pin same-PCG parity
    # only, so a difference across backends is recorded, not gated
    print(
        "[chip_smoke] first losses: "
        + "  ".join(f"{k}={v['loss_first']:.6f}" for k, v in phases.items()),
        flush=True,
    )
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": ndev,
    }
    report = {
        "device": device,
        "jax": jax.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "model": shapes,
        "compile_cache": {"dir": cache_dir, **cache.snapshot()},
        "phases": phases,
    }
    print(f"[chip_smoke] report: {json.dumps(report)}", flush=True)
    # the result line: these keys and no others; every phase above raised
    # on a failed check, so reaching it means all of them passed
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
