"""WAL fsyncs in the window per task assigned in it (the raft storage's
`wal_fsyncs` counter)."""


def read(ctx):
    if not ctx.assigned_in_window:
        return None
    n = ctx.after["wal_fsyncs"] - ctx.before["wal_fsyncs"]
    return n / ctx.assigned_in_window
