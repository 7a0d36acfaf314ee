#!/usr/bin/env python3
"""Benchmark entry: one run of one cell on the chip.

    python benchmark/run.py --workload fleet10k.rollout --seed 7 \
        --seconds 30 --trace 0

Prints the set-up phases and the window's counts on earlier lines, and as
the last line of standard output one JSON object: correct, attempted,
failed, metrics (the cell's end-to-end metrics, or with --trace 1 its
per-layer metrics), device, with --trace 1 breakdown, and last the numbers
the check compared, each with its limit (also the last lines of standard
error). Exits non-zero, printing no result, without a TPU or without the
system under test beside the benchmark.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str, code: int) -> int:
    print(f"benchmark: FAILED: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "swarmkit_tpu")):
        return fail("the system under test (swarmkit_tpu/) is not beside "
                    "the benchmark", 2)
    sys.path.insert(0, ROOT)
    # one fixed cache inside the checkout, whatever the environment says:
    # the path is part of the cache key, and the two sides of a comparison
    # must not share one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # small programs too: every later run finds every program in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        return fail(f"no TPU found: jax.devices()[0].platform is "
                    f"{d0.platform!r}", 3)

    import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_process=T_PROCESS)
    except Exception as exc:
        traceback.print_exc()
        return fail(str(exc), 1)
    for k, v in result["checks"].items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the stopped manager's daemon threads must not hold the process open
    os._exit(code)
