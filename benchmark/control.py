#!/usr/bin/env python3
"""The control of the check, and the readings its limits are set from. Not
part of the benchmark's own runs.

    python benchmark/control.py --workload fleet1k.rollout --seconds 50 \
        --seed 7

Runs the cell once in this process, as `run.py` does (one process per
seed: the chip belongs to one process at a time). After the window it
checks two placements of the same services on the same fleet: the
program's, read back from its store, and the control's. The control is a
plain placer put in the program's place that breaks one of the
configuration's guarantees: it honours every node's capacity but ignores
placement constraints, spread preferences and host-mode ports, choosing
among the nodes with room at random. It keeps the program's addresses and
ports, so where the configuration's kinds publish host-mode ports it has to
come out not correct on `host_port_conflicts` too. The program's numbers
give a limit's lower reading; the control's give its upper one, and the
control has to come out as not correct. Prints, as its last line, one JSON
object with both sets of numbers and both verdicts; exits 0 only where the
program is correct and the control is not.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def random_placer(nodes: dict, services: dict, tasks: list, due: set,
                  seed: int) -> list:
    """Re-place every task of the due services at random among the READY,
    ACTIVE nodes that still have room; other tasks stay where they are."""
    rng = np.random.default_rng(seed)
    ids = [nid for nid, n in nodes.items() if n["ready"] and n["active"]]
    room = {nid: [nodes[nid]["cpu"], nodes[nid]["mem"]] for nid in ids}
    keep, move = [], []
    for t in tasks:
        (move if t[1] in due else keep).append(t)
    for tid, sid, nid, state in keep:
        spec = services.get(sid)
        if spec is not None and nid in room:
            room[nid][0] -= spec["cpu"]
            room[nid][1] -= spec["mem"]
    out = list(keep)
    for tid, sid, _, _ in move:
        spec = services[sid]
        for _ in range(1000):
            nid = ids[int(rng.integers(len(ids)))]
            r = room[nid]
            if r[0] >= spec["cpu"] and r[1] >= spec["mem"]:
                r[0] -= spec["cpu"]
                r[1] -= spec["mem"]
                out.append((tid, sid, nid, 192))
                break
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU found", file=sys.stderr)
        return 3
    import harness

    r = harness.run_cell(args.workload, args.seed, args.seconds, False,
                         t_process=T_PROCESS, control=random_placer)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "correct": r["correct"],
                      "control_correct": r["control_correct"],
                      "program": {k: v["value"]
                                  for k, v in r["checks"].items()},
                      "control": r["control"], "metrics": r["metrics"],
                      "device": r["device"]}), flush=True)
    return 0 if r["correct"] and not r["control_correct"] else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
