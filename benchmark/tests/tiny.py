"""Tiny cell definitions for the CPU rehearsal (64 nodes, seconds-long).

A cell is built from BENCHMARK.json and its configuration and traffic files
by name, as a run builds it, and then shrunk."""
import time

import harness


def definition(cell: str) -> dict:
    d = harness.cell_definition(cell)
    d["config"].update(nodes=64)
    d["traffic"].update(warmup_s=2, outstanding_tasks=60)
    d["traffic"]["sizes"]["max"] = 16
    return d


def run(cell: str, seed: int = 2**33 + 5, seconds: float = 5.0,
        trace: bool = False, definition_=None, **kw) -> dict:
    harness.CHECK_WAIT_S = 8.0       # tiny fleets converge in a second
    return harness.run_cell(cell, seed, seconds, trace,
                            t_process=time.perf_counter(), require_tpu=False,
                            definition=definition_ or definition(cell), **kw)
