"""XLA's own peak of the compiled train step `fit` runs
(`memory_analysis().peak_memory_in_bytes`), per chip, from the program's
account of that step: `flexflow_tpu/observability/step_account.py`,
`account()["memory"]["xla_peak"]`, made once a run under the span
`step_account` when the window has ended. `step_hbm_gb` adds arguments,
outputs less aliases and temporaries; what it counts and this peak does not
(`total_less_xla_peak`) goes to standard error with the rest of the
program's `step_account.report()`: who holds the walk's peak, what the
forward pass leaves for the backward pass, memory space 1 by kind, what the
walk did not enter. Absent where the program has no such module."""

import json
import sys

LAYER = "lowering and backends"
UNIT = "GB"
SOURCE = "program_counter"
MOVES = "step_hbm_gb"

_KEY = "_step_account"


def for_context(ctx):
    """The program's account of the step this run timed, made once and kept
    in the readers' shared `ctx`, with its report on standard error; None
    for a program without the module, and where it cannot be made (a reader
    finds nothing; it never fails a run)."""
    if _KEY not in ctx:
        ctx[_KEY] = None
        try:
            from flexflow_tpu.observability import step_account, trace
        except ImportError:
            return None
        try:
            ctx[_KEY] = step_account.last()
        except Exception as e:
            print(f"step_account: no account: {type(e).__name__}: {e}",
                  file=sys.stderr)
        if ctx[_KEY] is not None:
            print("step_account: the compiled step by node\n"
                  + step_account.report(top=12), file=sys.stderr)
            print("step_account: " + json.dumps(summary(
                ctx[_KEY], step_account.made_by_kind(ctx[_KEY]),
                trace.span_totals().get("step_account"),
            )), file=sys.stderr)
    return ctx[_KEY]


def summary(account, by_kind, span):
    """One line of the account for a table a row a cell."""
    walk = account["walk"]
    return {
        "seconds": span and span["total_s"],
        "memory": account["memory"],
        "walk_peak_bytes": walk["peak_bytes"],
        "walk_over_xla": walk["walk_over_xla"],
        "peak_at": walk["peak_at"],
        "holders": walk["held_at_peak"][:3],
        "kept_for_backward_bytes": sum(
            r["bytes"] for r in walk["kept_for_backward"]
        ),
        "kept_for_backward": walk["kept_for_backward"][:3],
        "s1_by_kind": sorted(
            ((kind, made[0]) for kind, made in by_kind.items()),
            key=lambda kv: -kv[1],
        )[:3],
        "not_walked": {
            "fusions": account["not_walked"]["fusions"],
            "computations": len(account["not_walked"]["computations"]),
        },
    }


def read(ctx):
    account = for_context(ctx)
    if account is None or account["memory"]["xla_peak"] is None:
        return None
    return account["memory"]["xla_peak"] / 1e9
