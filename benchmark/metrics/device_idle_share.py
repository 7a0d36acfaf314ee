"""Share of the window in which no operation ran on the device, from the
profiler trace (100 - busy share)."""


def read(ctx):
    if ctx.device is None:
        return None
    return 100.0 * (1.0 - ctx.device["busy_s"] / ctx.device["window_s"])
