"""The benchmark's own tests (`python3 -m pytest benchmark/tests -q`). They
run on the CPU; none of them measures anything."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)
