"""The program's span records of a traced window, and reductions of them.

A record here is a plain tuple

    (name, start_ns, end_ns, thread, cpu, attrs)

on the host's wall clock (`time.time()`, the clock of the device trace's
`profile_start_time`): `thread` is the thread that opened the span, `cpu`
its thread CPU seconds (None where the program measured none), `attrs` the
span's attributes.

`window_records(ctx)` reads them from the program's trace plane: the
recorder the window's close retired (`swarmkit_tpu.utils.trace.retired`).
A program without one gives None, and every reader built on it then
reports nothing.

  wall_per_ktask   wall seconds of named spans per 1,000 tasks assigned
  host_spans       the span names with most self-time (duration less the
                   spans nested in it on its own thread), wall and cpu
  clock_slack      how far the device trace's executions of the resident
                   tick break causality with the host spans that launch
                   and wait for them: a check that both share one clock
"""
from __future__ import annotations

import bisect
from collections import defaultdict

TICK_PROGRAM = "jit__resident_tick_impl"
MODULES_LINE = "XLA Modules"


def program_records(t0: float, t1: float) -> list | None:
    """Records of the spans that ended inside [t0, t1] (seconds), from the
    recorder the program's trace plane last retired; None without one."""
    try:
        from swarmkit_tpu.utils import trace
    except ImportError:
        return None
    retired = getattr(trace, "retired", None)
    rec = retired() if retired is not None else None
    if rec is None:
        return None
    out = []
    for r in rec.snapshot():
        end = r["t0"] + r["dur"]
        if t0 <= end <= t1:
            out.append((r["name"], int(r["t0"] * 1e9), int(end * 1e9),
                        r["thread"], r.get("cpu"), r["attrs"]))
    return out


def window_records(ctx) -> list | None:
    """The window's records: `ctx.records` where the harness provides it,
    else read once from the program and kept there for the next reader."""
    recs = getattr(ctx, "records", None)
    if recs is None:
        recs = program_records(ctx.t0, ctx.t1)
        ctx.records = recs
    return recs


def wall_per_ktask(ctx, names) -> float | None:
    """Wall seconds of the spans named in `names` (a set, or a callable
    on the name) per 1,000 tasks assigned in the window."""
    recs = window_records(ctx)
    if not recs or not ctx.assigned_in_window:
        return None
    match = names if callable(names) else names.__contains__
    durs = [e - s for n, s, e, *_ in recs if match(n)]
    if not durs:
        return None
    return sum(durs) / 1e9 / (ctx.assigned_in_window / 1000.0)


def host_spans(records, top: int = 10) -> list:
    """[name, self wall s, self cpu s] for the `top` names by self wall
    time. Nesting is by interval on the span's own thread; a span that
    overlaps another without nesting in it counts as top-level. Self cpu
    is the span's cpu less its nested spans' (spans without cpu add
    none)."""
    by_thread = defaultdict(list)
    for n, s, e, thread, cpu, _ in records:
        by_thread[thread].append((s, -e, n, cpu))
    wall = defaultdict(float)
    cpu_s = defaultdict(float)
    for spans in by_thread.values():
        spans.sort()
        stack = []              # [end, name]
        for s, neg_e, n, cpu in spans:
            e = -neg_e
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack and e <= stack[-1][0]:
                parent = stack[-1][1]
                wall[parent] -= (e - s) / 1e9
                if cpu is not None:
                    cpu_s[parent] -= cpu
            wall[n] += (e - s) / 1e9
            if cpu is not None:
                cpu_s[n] += cpu
            stack.append([e, n])
    ranked = sorted(wall, key=wall.get, reverse=True)[:top]
    return [[n, wall[n], cpu_s[n]] for n in ranked]


def tick_executions(planes: dict, t0_ns: int, t1_ns: int) -> list:
    """(start_ns, end_ns) of every resident tick execution inside the
    window, from `trace_reduce.extract`'s plain form."""
    out = []
    for lines in planes.values():
        for n, s, d in lines.get(MODULES_LINE, ()):
            if n.split("(", 1)[0] == TICK_PROGRAM and t0_ns <= s \
                    and s + d <= t1_ns:
                out.append((s, s + d))
    return sorted(out)


def clock_slack(executions, records) -> dict:
    """How far the resident tick's executions break causality with the
    host spans: each must start after the `tick.dispatch` span that
    launched it started, and end before the `tick.device_sync` span that
    waited for it ended.

    Each dispatch launches one execution: the one that starts nearest its
    interval (inside it when the clocks agree; a dispatch that compiles
    launches near its end). Its sync is the first `tick.device_sync` to
    start after the dispatch ended (the pipelined tick pulls a wave's
    counts at the start of the next tick). Only dispatches with such a
    sync recorded are checked: their execution then lies inside the
    window. A device clock off by more than a dispatch's margin puts an
    execution before its dispatch or after its sync, or leaves the
    dispatch paired with a neighbour's execution, far off. Returns
    {"slack_s": the worst of (dispatch start - execution start) and
    (execution end - sync end) over the checked dispatches, seconds: above
    0 is a violation, below 0 the tightest margin, None with nothing
    checked; "checked"; "unpaired": dispatches without a recorded sync
    after them or without any execution}."""
    syncs = sorted((s, e) for n, s, e, *_ in records
                   if n == "tick.device_sync")
    sync_starts = [s for s, _ in syncs]
    worst = None
    checked = unpaired = 0
    for n, ds, de, *_ in records:
        if n != "tick.dispatch":
            continue
        j = bisect.bisect_left(sync_starts, de)
        if j == len(syncs) or not executions:
            unpaired += 1
            continue
        xs, xe = min(executions,
                     key=lambda x: max(ds - x[0], x[0] - de, 0))
        v = max(ds - xs, xe - syncs[j][1]) / 1e9
        worst = v if worst is None else max(worst, v)
        checked += 1
    return {"slack_s": worst, "checked": checked, "unpaired": unpaired}
