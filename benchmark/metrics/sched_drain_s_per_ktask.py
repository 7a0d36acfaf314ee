"""Seconds the scheduler spent handling store events between its ticks, per
1,000 tasks assigned in the window: the wall time of its `sched.drain` spans
(one per burst) that end inside the window."""
import span_records


def read(ctx):
    return span_records.wall_per_ktask(ctx, {"sched.drain"})
