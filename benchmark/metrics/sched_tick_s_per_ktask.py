"""Scheduler seconds per 1,000 tasks assigned in the window: the program's
`sched.tick` spans (a whole tick, its stage spans inside) plus the async
commit plane's `tick.commit_heavy` spans, from the traced window."""

ROOTS = ("sched.tick", "tick.commit_heavy")


def read(ctx):
    if not ctx.spans or not ctx.assigned_in_window:
        return None
    busy = sum((min(e, ctx.t1 * 1e9) - max(s, ctx.t0 * 1e9)) / 1e9
               for name, s, e in ctx.spans if name in ROOTS)
    return busy / (ctx.assigned_in_window / 1000.0)
