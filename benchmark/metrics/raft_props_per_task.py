"""Raft entries committed and applied in the window per task assigned in
it (the raft node's `commits_applied` counter)."""


def read(ctx):
    if not ctx.assigned_in_window:
        return None
    n = ctx.after["commits_applied"] - ctx.before["commits_applied"]
    return n / ctx.assigned_in_window
