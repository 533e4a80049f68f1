"""chip_smoke.py on a machine without a chip: it must refuse to run, and
its CPU rehearsal must pass while never reading as a pass on the chip."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, **env):
    return subprocess.run(
        [sys.executable, SMOKE, *args],
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=600,
    )


def test_refuses_to_run_without_an_accelerator():
    out = _run()
    assert out.returncode != 0
    assert out.stdout == ""  # no result line that could be mistaken for one
    assert "no accelerator" in out.stderr


def test_cpu_rehearsal_passes_and_names_the_cpu(tmp_path):
    cache_dir = str(tmp_path / "cache")
    out = _run(
        "--rehearse-on-cpu",
        JAX_COMPILATION_CACHE_DIR=cache_dir,
        # jax skips programs that compile in under a second: at toy width
        # that could be all of them
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.splitlines()
    # the result line carries these keys and no others
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    tag = "[chip_smoke] report: "
    assert lines[-2].startswith(tag)
    report = json.loads(lines[-2][len(tag):])
    assert {k: v["backend"] for k, v in report["phases"].items()} == {
        "single": "ModelTrainingInstance",
        "searched": "DistributedTrainingInstance",
        "dp": "DataParallelTrainingInstance",
    }
    assert report["phases"]["searched"]["search"]["native_dp"] is True
    # the environment placed the cache and the program used it there
    assert report["compile_cache"]["dir"] == cache_dir
    assert os.listdir(cache_dir)
