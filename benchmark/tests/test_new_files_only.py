"""A configuration, a job and a per-layer metric added as files of their own,
with entries in `BENCHMARK.json` and no edit to any file the benchmark has,
are found and run by `run.py`: shown on a copy of `benchmark/`, at tiny width
on the CPU mesh."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import run as bench

CONFIG_PY = '''
"""A two-layer perceptron over token embeddings: nothing like the other
configurations, to show that a new `.py` needs nothing from them."""
import jax
import jax.numpy as jnp
import numpy as np

LOSS_TOLERANCE = 5e-4
INPUT_NAMES = ("input_ids",)


def build(sizes, batch, seq):
    from flexflow_tpu.op_attrs.datatype import DataType
    from flexflow_tpu.pcg import ComputationGraphBuilder

    b = ComputationGraphBuilder()
    ids = b.create_input([batch, seq], DataType.INT32, name="input_ids")
    h = b.embedding(ids, sizes["vocab_size"], sizes["width"], name="emb")
    h = b.gelu(b.dense(h, sizes["width"], name="fc"))
    return b.graph, b.dense(h, sizes["vocab_size"], name="head")


def make_data(rs, sizes, n, seq):
    ids = rs.randint(0, sizes["vocab_size"], (n, seq)).astype(np.int32)
    return {"input_ids": ids}, np.roll(ids, -1, axis=1)


def _loss(p, ids, labels):
    h = p["emb.weight0"][ids]
    h = jax.nn.gelu(h @ p["fc.weight0"] + p["fc.weight1"], approximate=True)
    logp = jax.nn.log_softmax(h @ p["head.weight0"] + p["head.weight1"])
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def reference_losses(params, inputs, labels, sizes, adam):
    ids, labels = jnp.asarray(inputs["input_ids"]), jnp.asarray(labels)
    with jax.default_matmul_precision("highest"):
        before, grad = jax.value_and_grad(_loss)(params, ids, labels)
        scale = adam["alpha"] * np.sqrt(1 - adam["beta2"]) / (1 - adam["beta1"])

        def step(w, g):
            g = g + adam["weight_decay"] * w
            return w - scale * (1 - adam["beta1"]) * g / (
                jnp.sqrt((1 - adam["beta2"]) * g * g) + adam["epsilon"]
            )

        after = _loss(jax.tree_util.tree_map(step, params, grad), ids, labels)
    return float(before), float(after)


def flops_per_token(sizes, seq):
    return 6.0 * (sizes["width"] ** 2 + sizes["width"] * sizes["vocab_size"])


def kernel_costs(sizes, batch, seq):
    return {}
'''

METRIC_PY = '''
"""How many chunks of fit the traced window held."""
LAYER = "entry points"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(ctx):
    return ctx["steps_traced"] // ctx["job"]["dataset_batches"]
'''


def test_cell_from_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(
        bench.BENCH, root / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    before = {
        str(p.relative_to(root)): p.read_bytes()
        for p in (root / "benchmark").rglob("*") if p.is_file()
    }
    configs, jobs, metrics = (
        root / "benchmark" / d for d in ("configs", "jobs", "layer_metrics")
    )
    (configs / "throwaway-mlp.py").write_text(textwrap.dedent(CONFIG_PY))
    (configs / "throwaway-mlp.json").write_text(json.dumps({
        "name": "throwaway-mlp", "source": "none: a test fixture",
        "width": 64, "vocab_size": 128, "reduced": {},
        "training": {
            "optimizer": "adam", "alpha": 1e-3, "beta1": 0.9, "beta2": 0.999,
            "epsilon": 1e-8, "weight_decay": 0.0,
            "compute_dtype": "bfloat16", "state_dtype": "float32",
            "loss": "sparse_categorical_crossentropy",
        },
    }))
    (jobs / "throwaway_job.json").write_text(json.dumps({
        "rehearsal": True, "seq": 16, "batch_per_chip": 4, "chips": 1,
        "backend": "ModelTrainingInstance", "ffconfig": {},
        "dataset_batches": 3, "shuffle": True, "trace_chunks": 2,
    }))
    (metrics / "chunks_traced.py").write_text(textwrap.dedent(METRIC_PY))
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    manifest["configs"].append({
        "name": "throwaway-mlp", "source": "none",
        "file": "benchmark/configs/throwaway-mlp.json", "reduced": [],
        "why": "test fixture",
    })
    manifest["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway-mlp",
        "traffic": "throwaway_job", "chips": 1, "why": "test fixture",
    })
    manifest["per_layer"].append({
        "name": "chunks_traced", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry points",
        "moves": "tokens_per_s", "workloads": ["throwaway_cell"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, PYTHONPATH=bench.ROOT, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    results = {}
    for trace in (0, 1):
        done = subprocess.run(
            [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
             "throwaway_cell", "--seed", "5", "--seconds", "1",
             "--trace", str(trace)],
            env=env, capture_output=True, text=True, timeout=300, cwd=root,
        )
        assert done.returncode == 0, done.stderr[-2000:]
        results[trace] = json.loads(done.stdout.strip().splitlines()[-1])
    for result in results.values():
        assert result["correct"], result["checks"]
        assert result["failed"] == 0 and result["attempted"] > 0
        assert result["device"]["platform"] == "cpu"
    # a CPU run reports set-up and no device metric
    assert set(results[0]["metrics"]) == {"setup_s"}
    traced = results[1]["metrics"]
    assert traced["chunks_traced"] == {"value": 2, "unit": "count"}
    assert traced["compiles_in_window"]["value"] == 0
    assert "device_idle_pct" not in traced and "busy_mfu_pct" not in traced
    # and no file the benchmark had was touched
    after = {
        k: (root / k).read_bytes() for k in before
    }
    assert after == before


def test_real_cell_refuses_to_run_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    done = subprocess.run(
        [sys.executable, os.path.join(bench.BENCH, "run.py"), "--workload",
         "bertlarge_s128_1chip", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
