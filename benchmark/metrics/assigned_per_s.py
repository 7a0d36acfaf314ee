"""Tasks whose ASSIGNED state was committed inside the window, over the
window's length (closed loop: the backlog never runs dry)."""


def read(ctx):
    return ctx.assigned_in_window / (ctx.t1 - ctx.t0)
