"""XLA backend compiles inside the window (jax.monitoring's
backend_compile_duration events); persistent-cache hits are not compiles."""


def read(ctx):
    return ctx.after["compiles"] - ctx.before["compiles"]
