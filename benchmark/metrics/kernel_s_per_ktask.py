"""Device seconds of the placement programs (ops/resident.py's resident
tick and its counts pull) per 1,000 tasks assigned in the window, from the
profiler trace."""

PROGRAMS = ("jit__resident_tick_impl", "jit__sparse_counts",
            "jit__slice_counts")


def read(ctx):
    if ctx.device is None or not ctx.assigned_in_window:
        return None
    progs = ctx.device["programs"]
    if not any(p in progs for p in PROGRAMS):
        return None
    busy = sum(progs.get(p, 0.0) for p in PROGRAMS)
    return busy / (ctx.assigned_in_window / 1000.0)
