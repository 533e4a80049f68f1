"""Phase-level timing of the Unity search on the flagship transformer.

Answers "where does budget-N wall time go": seed construction, seed
evaluation, and — inside the budget loop — pattern matching, substitution
application, normalization, dedup keying, and machine-mapping evaluation.
Monkeypatches the phase functions with timing wrappers; search behavior is
unchanged. Run on the virtual CPU mesh:

    JAX_PLATFORMS=cpu python tools/profile_search.py --budget 8
"""

import argparse
import os
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TIMES = defaultdict(float)
COUNTS = defaultdict(int)
# stack of per-frame child time, so each bucket records EXCLUSIVE time
# (seed construction internally drives the wrapped match/apply/normalize;
# without self-time accounting those seconds would be double-counted and
# the "(unaccounted)" line could go negative)
_STACK = [0.0]


def _account(name, elapsed):
    child = _STACK.pop()
    TIMES[name] += elapsed - child
    COUNTS[name] += 1
    _STACK[-1] += elapsed


def timed(name, fn):
    def wrapper(*a, **k):
        _STACK.append(0.0)
        t0 = time.perf_counter()
        try:
            return fn(*a, **k)
        finally:
            _account(name, time.perf_counter() - t0)

    return wrapper


def timed_gen(name, fn):
    """Wrap a generator function: accounts iteration time, not just call."""

    def wrapper(*a, **k):
        _STACK.append(0.0)
        t0 = time.perf_counter()
        it = iter(fn(*a, **k))
        while True:
            try:
                item = next(it)
            except StopIteration:
                _account(name, time.perf_counter() - t0)
                return
            _account(name, time.perf_counter() - t0)
            yield item
            _STACK.append(0.0)
            t0 = time.perf_counter()

    return wrapper


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--layers", type=int, default=12)
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import flexflow_tpu.compiler.unity_algorithm as ua
    import flexflow_tpu.compiler.machine_mapping.get_optimal_machine_mapping as gm
    import flexflow_tpu.compiler.machine_mapping.problem_tree as pt
    import flexflow_tpu.substitutions.pcg_pattern as pp
    import flexflow_tpu.substitutions.substitution as ss

    # instrument the phase boundaries (all module globals in ua; the real
    # evaluate_pcg runs unmodified and calls the two timed callees below)
    ua.find_pattern_matches = timed_gen("match", pp.find_pattern_matches)
    ua.apply_substitution = timed("apply", ss.apply_substitution)
    ua._normalize = timed("normalize", ua._normalize)
    ua._canonical_key = timed("canonical_key", ua._canonical_key)
    ua.get_machine_mapping_problem_tree = timed(
        "eval:tree_build", pt.get_machine_mapping_problem_tree
    )
    ua.get_optimal_machine_mapping = timed(
        "eval:dp", gm.get_optimal_machine_mapping
    )
    ua.enumerate_seeds = timed_gen("seed_construction", ua.enumerate_seeds)

    from flexflow_tpu.compiler import (
        AnalyticTPUCostEstimator,
        MachineMappingContext,
        OptimizerConfig,
        make_default_allowed_machine_views,
    )
    from flexflow_tpu.pcg.machine_view import MachineSpecification
    from flexflow_tpu.substitutions.rules import generate_parallelization_rules
    from flexflow_tpu.models.flagship import build_flagship_pcg

    pcg = build_flagship_pcg(layers=args.layers)
    spec = MachineSpecification(1, 1, 8, 1.0, 2.0)
    est = AnalyticTPUCostEstimator(
        spec, peak_flops=5e10, hbm_gbps=10.0, ici_latency_ms=0.1,
        dcn_latency_ms=0.2, emulated_mesh=True,
    )
    ctx = MachineMappingContext(
        est, make_default_allowed_machine_views(), overlap_fraction=0.5
    )
    rules = generate_parallelization_rules([2, 4, 8])
    t0 = time.perf_counter()
    r = ua.graph_optimize(
        pcg, ctx, spec, rules, OptimizerConfig(alpha=1.2, budget=args.budget)
    )
    total = time.perf_counter() - t0
    print(f"total: {total:.1f}s  explored={r.explored} runtime={r.runtime:.3f}")
    accounted = 0.0
    for name in sorted(TIMES, key=TIMES.get, reverse=True):
        print(f"  {name:20s} {TIMES[name]:8.1f}s  x{COUNTS[name]}")
        accounted += TIMES[name]
    print(f"  {'(unaccounted)':20s} {total - accounted:8.1f}s")


if __name__ == "__main__":
    main()
