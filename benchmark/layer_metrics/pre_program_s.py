"""Seconds the process was old at the first import of the program:
interpreter start, `import jax` and the backend's start-up, which `run.py`
asks for (`jax.devices()`) before it imports `flexflow_tpu`
(`observability/trace.py`: `pre_program_s()`, the process's start time in
`/proc` against the uptime). The part of `setup_s` no code of the program
or of the benchmark's cell runs in. Absent where the program keeps no such
reading, or the machine no `/proc`."""

from host_spans import program_trace

LAYER = "entry points"
UNIT = "s"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(ctx):
    trace = program_trace()
    if trace is None or not hasattr(trace, "pre_program_s"):
        return None
    return trace.pre_program_s()
