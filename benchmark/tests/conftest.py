"""CPU rehearsal of the benchmark: tiny fleets, the CPU backend, no chip.

Run with `python -m pytest benchmark/tests -q` from the repository root.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
