"""Set-up time: process start to the window's start (imports and JAX
initialisation, manager start, fleet generation, seeding, warm-up)."""


def read(ctx):
    return ctx.setup_s
