"""Seeded chaos-schedule soak (ISSUE 8 acceptance): four distinct
FaultSchedules — ckpt-write IO fault, injected NaN, simulated hang,
kill+resume — each must end with BITWISE-identical final
params and Adam moments versus the fault-free run, on both the DP and
searched-PCG backends (runtime/chaos.py is the harness)."""

import numpy as np
import pytest

from flexflow_tpu.core import FFConfig, FFModel
from flexflow_tpu.pcg.optimizer import AdamOptimizerAttrs
from flexflow_tpu.runtime.chaos import soak_sites
from flexflow_tpu.runtime.fault import FAULT_SITES

BATCH = 16
STEPS_PER_EPOCH = 8
TOTAL_STEPS = 2 * STEPS_PER_EPOCH
EVERY = 4
N = BATCH * STEPS_PER_EPOCH

# outcome each site's faulted run must end with BEFORE recovery: the
# detection half of the contract (the bitwise comparison is the recovery
# half)
EXPECTED_OUTCOMES = {
    "ckpt_write": "completed",       # transient absorbed by retry backoff
    "nonfinite": "NonFiniteError",   # health policy raise stops the run
    "hang": "WindowHangError",       # watchdog budget expiry
    "kill": "SimulatedFault",        # preemption between steps
}


def _data():
    rs = np.random.RandomState(0)
    return rs.randn(N, 32).astype(np.float32), rs.randint(0, 10, N)


def _builder(budget):
    def build(mdir, cdir, watchdog=False):
        cfg = FFConfig(
            batch_size=BATCH, seed=0, print_freq=0,
            search_budget=budget, metrics_dir=mdir, checkpoint_dir=cdir,
            checkpoint_every_n_steps=EVERY, checkpoint_backend="npz",
            health_policy="raise",
            watchdog_factor=3.0 if watchdog else 0.0,
        )
        m = FFModel(cfg)
        x = m.create_tensor([BATCH, 32], name="x")
        h = m.dense(x, 32, use_bias=False, name="fc1")
        h = m.relu(h)
        if budget <= 0:
            # stochastic op on the DP backend: the restored RNG stream
            # position is load-bearing in the bitwise comparison
            h = m.dropout(h, 0.1)
        logits = m.dense(h, 10, use_bias=False, name="head")
        m.compile(
            AdamOptimizerAttrs(alpha=1e-2),
            "sparse_categorical_crossentropy",
            metrics=["accuracy"],
            logit_tensor=logits,
        )
        return m

    return build


@pytest.mark.parametrize(
    "budget", [-1, 2], ids=["dp-backend", "searched-backend"]
)
def test_all_sites_recover_bitwise(budget):
    assert set(EXPECTED_OUTCOMES) == set(FAULT_SITES)
    xv, yv = _data()
    result = soak_sites(
        _builder(budget), xv, yv,
        total_steps=TOTAL_STEPS, checkpoint_every=EVERY, epochs=2,
    )
    assert result["n_schedules"] == len(FAULT_SITES)
    by_site = {r["sites"][0]: r for r in result["schedules"]}
    for site, record in by_site.items():
        assert record["fired"], f"{site}: schedule never fired"
        assert record["fired"][0][0] == site
        assert record["outcome"] == EXPECTED_OUTCOMES[site], (
            f"{site}: expected {EXPECTED_OUTCOMES[site]}, got "
            f"{record['outcome']} ({record['error']})"
        )
        assert record["resumed"] == (
            EXPECTED_OUTCOMES[site] != "completed"
        ), f"{site}: resume leg mismatch"
        assert record["bitwise_params"], f"{site}: params diverged"
        assert record["bitwise_opt_state"], (
            f"{site}: Adam moments diverged"
        )
    assert result["n_bitwise"] == len(FAULT_SITES)
    assert result["n_fired"] == len(FAULT_SITES)
