"""Persistent XLA compilation cache tests.

Every FFModel turns the cache on (`configure_compilation_cache`): where
`JAX_COMPILATION_CACHE_DIR` is set the cache lives there and the code sets
no directory, otherwise at one fixed path inside the checkout. A SECOND
process compiling the identical step program then loads the cached
executable instead of re-running XLA. The pinned behavior is cross-process:
the child script below compiles one FFModel train step with the environment
variable set; run twice against one cache directory, the first process must
populate the cache and the second must record a persistent-cache HIT for
the step program (asserted on jax's own compiler log line, not on file
counts — a hit for an unrelated helper program must not satisfy the test).
"""

import os
import re
import subprocess
import sys
import tempfile

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import io, logging, sys
sys.path.insert(0, {repo!r})

# capture jax's compiler/compilation-cache DEBUG stream: the persistent-
# cache hit/miss decision is logged there
buf = io.StringIO()
handler = logging.StreamHandler(buf)
logging.getLogger("jax").addHandler(handler)
logging.getLogger("jax").setLevel(logging.DEBUG)

import numpy as np
from flexflow_tpu.core import FFConfig, FFModel
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs

cfg = FFConfig(batch_size=8, seed=0, print_freq=0)
m = FFModel(cfg)
x = m.create_tensor([8, 16], name="x")
h = m.dense(x, 16, use_bias=False, name="fc1")
logits = m.dense(h, 4, use_bias=False, name="head")
m.compile(AdamOptimizerAttrs(alpha=1e-2), "sparse_categorical_crossentropy",
          logit_tensor=logits)
rs = np.random.RandomState(0)
m.fit(rs.randn(16, 16).astype(np.float32), rs.randint(0, 4, 16),
      epochs=1, shuffle=False, verbose=False)
log = buf.getvalue()
hits = [l for l in log.splitlines()
        if "Persistent compilation cache hit" in l]
print("CACHE_LOG_BEGIN")
for l in hits:
    print(l)
print("CACHE_LOG_END")
"""


def _run_child(cache_dir: str) -> list:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    # jax's floors skip programs that compile in under a second, which is
    # all of this toy model
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(repo=REPO)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.splitlines()
    assert "CACHE_LOG_BEGIN" in lines, out.stdout
    lo, hi = lines.index("CACHE_LOG_BEGIN"), lines.index("CACHE_LOG_END")
    return lines[lo + 1 : hi]


def test_second_process_hits_the_step_program_cache():
    """Two processes, one cache dir: the second must load the jitted
    `_step` executable from the persistent cache (a cold recompile would
    log no hit for it)."""
    cache_dir = tempfile.mkdtemp(prefix="ffcompilecache_")
    first_hits = _run_child(cache_dir)
    assert not any("_step" in l for l in first_hits), (
        f"cold cache must not hit the step program: {first_hits}"
    )
    assert os.listdir(cache_dir), "first process wrote no cache entries"
    second_hits = _run_child(cache_dir)
    assert any("_step" in l for l in second_hits), (
        "second process recompiled the step program instead of hitting "
        f"the persistent cache: {second_hits}"
    )


def test_configure_compilation_cache_directory(monkeypatch):
    """The environment places the cache; code only supplies the fixed
    in-checkout default when the environment says nothing."""
    import jax

    from flexflow_tpu.local_execution.config import (
        DEFAULT_COMPILE_CACHE_DIR,
        configure_compilation_cache,
    )

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jax.config.update("jax_compilation_cache_dir", "/from/the/env")
        assert configure_compilation_cache() == "/from/the/env"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert configure_compilation_cache() == DEFAULT_COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
