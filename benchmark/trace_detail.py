#!/usr/bin/env python3
"""One traced run of a cell, as `run.py --trace 1` makes it, and what the
result line leaves out: the window's throughput, the host breakdown by leaf
span, the clock check against the device trace and the program shapes first
dispatched in the window.

    python benchmark/trace_detail.py --workload fleet1k.rollout \
        --seed 7 --seconds 50 [--recorder 0] [--keep DIR]

Prints run.py's lines, then one line `detail: {json}`:

  assigned_per_s  tasks assigned in the window over its length
  host_spans      [name, self wall s, self cpu s], the 10 names with most
                  self-time (span_records.host_spans)
  clock_slack     span_records.clock_slack over the window's resident ticks
  new_shapes      attrs of the window's tick.dispatch spans with new_shape

--recorder 0 runs the profiler without arming the program's recorder (what
the profiler alone costs). --keep DIR keeps what the clock check reads: the
device trace cut to its "Task Environment" plane and the device planes'
"XLA Modules" lines as DIR/window.xplane.pb.gz, and the tick.dispatch and
tick.device_sync records with the window's bounds as DIR/window_spans.json.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402
import span_records  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")      # as run.py keeps it
CLOCK_SPANS = ("tick.dispatch", "tick.device_sync")


# ------------------------------------------ cutting an xplane.pb down
# XSpace: planes = field 1; XPlane: name = 2, lines = 3; XLine: name = 2.
def _varint(b: bytes, i: int) -> tuple[int, int]:
    n = shift = 0
    while True:
        c = b[i]
        i += 1
        n |= (c & 0x7F) << shift
        if c < 0x80:
            return n, i
        shift += 7


def _fields(b: bytes):
    """(field number, payload of a length-delimited field or None, raw
    bytes of the whole field) for each field of a protobuf message."""
    i = 0
    while i < len(b):
        start = i
        tag, i = _varint(b, i)
        wire, val = tag & 7, None
        if wire == 0:
            _, i = _varint(b, i)
        elif wire == 1:
            i += 8
        elif wire == 2:
            n, i = _varint(b, i)
            val, i = b[i:i + n], i + n
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield tag >> 3, val, b[start:i]


def _name(msg: bytes) -> str:
    return next((v.decode() for f, v, _ in _fields(msg) if f == 2), "")


def _len_prefix(n: int) -> bytes:
    out = bytearray()
    while True:
        c, n = n & 0x7F, n >> 7
        out.append(c | 0x80 if n else c)
        if not n:
            return bytes(out)


def keep_clock_planes(src: str, dst: str) -> None:
    """Write src's "Task Environment" plane and its device planes with
    only their "XLA Modules" line, gzipped, to dst."""
    with open(src, "rb") as f:
        space = f.read()
    out = bytearray()
    for field, plane, raw in _fields(space):
        if field == 1:
            name = _name(plane)
            if name.startswith("/device:"):
                kept = b"".join(r for f, v, r in _fields(plane)
                                if f != 3 or _name(v) == "XLA Modules")
                raw = b"\x0a" + _len_prefix(len(kept)) + kept
            elif name != "Task Environment":
                continue
        out += raw
    with gzip.open(dst, "wb") as f:
        f.write(bytes(out))


def detail_run(name: str, seed: int, seconds: float, recorder: bool = True,
               keep: str | None = None, **run_kw) -> tuple[dict, dict]:
    """One traced run (harness.run_cell with trace on, run_kw passed on);
    returns its result and the detail described above."""
    seen = {}
    tr = harness.trace_reduce
    find, reduce, load_reader = tr.find_xspace, tr.reduce, harness.load_reader

    def find_and_keep(log_dir):
        path = find(log_dir)
        if path is not None and keep:
            os.makedirs(keep, exist_ok=True)
            keep_clock_planes(path, os.path.join(keep, "window.xplane.pb.gz"))
        return path

    def reduce_and_keep(planes, t0_ns, t1_ns, spans=(), **kw):
        seen["planes"], seen["t_ns"] = planes, (t0_ns, t1_ns)
        return reduce(planes, t0_ns, t1_ns, spans, **kw)

    def reader_and_ctx(metric):
        read = load_reader(metric)

        def wrapped(ctx):
            seen["ctx"] = ctx
            return read(ctx)
        return wrapped

    from swarmkit_tpu.utils import trace

    arm = trace.arm
    tr.find_xspace, tr.reduce = find_and_keep, reduce_and_keep
    harness.load_reader = reader_and_ctx
    if not recorder:
        # a recorder that is never installed: every site stays disarmed
        trace.arm = lambda capacity=0, clock=None: trace.FlightRecorder(
            capacity)
    try:
        result = harness.run_cell(name, seed, seconds, True, **run_kw)
    finally:
        tr.find_xspace, tr.reduce = find, reduce
        harness.load_reader = load_reader
        trace.arm = arm
    ctx = seen["ctx"]
    detail = {"assigned_per_s": ctx.assigned_in_window / (ctx.t1 - ctx.t0),
              "recorder": bool(recorder)}
    recs = span_records.window_records(ctx)
    if recs:
        detail["host_spans"] = span_records.host_spans(recs)
        detail["new_shapes"] = [
            a for n, _, _, _, _, a in recs
            if n == "tick.dispatch" and a.get("new_shape")]
        if "planes" in seen:
            t0_ns, t1_ns = seen["t_ns"]
            detail["clock_slack"] = span_records.clock_slack(
                span_records.tick_executions(seen["planes"], t0_ns, t1_ns),
                recs)
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, "window_spans.json"), "w") as f:
                json.dump({"t0_ns": int(ctx.t0 * 1e9),
                           "t1_ns": int(ctx.t1 * 1e9),
                           "records": [r[:5] for r in recs
                                       if r[0] in CLOCK_SPANS]}, f)
    return result, detail


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--recorder", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result, detail = detail_run(args.workload, args.seed, args.seconds,
                                bool(args.recorder), args.keep,
                                t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    print("detail: " + json.dumps(detail, default=list), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the stopped manager's daemon threads must not hold the process open
    os._exit(code)
