"""Thread CPU seconds of the served path's host threads per 1,000 tasks
assigned in the window: the `cpu` of each thread's top-level spans that end
inside the window (the scheduler's ticks and event drains, the commit
worker's heavy commits, every event-loop component's bursts, the raft ready
flushes and the dispatcher's flushes). Wall time less this is waiting."""
import span_records

ROOTS = frozenset({"sched.tick", "sched.drain", "tick.commit_heavy",
                   "raft.ready", "dispatcher.flush"})


def read(ctx):
    recs = span_records.window_records(ctx)
    if not recs or not ctx.assigned_in_window:
        return None
    cpu = [c for name, _, _, _, c, _ in recs
           if c is not None and (name in ROOTS or name.startswith("loop."))]
    if not cpu:
        return None
    return sum(cpu) / (ctx.assigned_in_window / 1000.0)
