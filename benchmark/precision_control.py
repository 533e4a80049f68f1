"""The control behind a configuration's `LOSS_TOLERANCE`: one run of a cell
through `run.py` itself, with the plain reference's matmul operands rounded to
a lower precision.

    python3 benchmark/precision_control.py --operands float8_e4m3fn \\
        --workload <cell> --seed <n> --seconds <s> --trace 0 [--manifest <m>]

Every other argument is `run.py`'s, and so is the last line of standard
output: `correct` there is the harness's own comparison of the system (bf16
operands, as the configuration states) with a reference in the precision
given. With the nearest precision below the configuration's
(`float8_e4m3fn`) the line must say `correct` false, by the cell's limit and
the check that holds it; with `bfloat16` it says how much of the limit the
system's own precision uses. Only configurations whose `.py` rounds its
operands through a module-level `OPERANDS` can be asked (the `nemotron_h`
files); for any other the run stops before it starts.
"""

import argparse
import sys

import run as bench


def rounding(dtype_name):
    """x -> x rounded to `dtype_name` and back, floating arrays only."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def rounded(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        return x.astype(dtype).astype(x.dtype)

    return rounded


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--operands", required=True,
                    help="a jax.numpy dtype name: float8_e4m3fn, bfloat16")
    args, rest = ap.parse_known_args()
    load = bench.load_module

    def load_with_rounded_operands(path):
        module = load(path)
        if not hasattr(module, "OPERANDS"):
            raise SystemExit(
                f"precision_control.py: {path} has no OPERANDS to round"
            )
        module.OPERANDS = rounding(args.operands)
        return module

    bench.load_module = load_with_rounded_operands
    sys.argv = [bench.__file__] + rest
    return bench.main()


if __name__ == "__main__":
    sys.exit(main())
