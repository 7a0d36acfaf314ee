"""Seconds of raft ready flushes per 1,000 tasks assigned in the window: the
wall time of the `raft.ready` spans (one per flush: WAL append and fsync,
commit advance, apply) that end inside the window."""
import span_records


def read(ctx):
    return span_records.wall_per_ktask(ctx, {"raft.ready"})
