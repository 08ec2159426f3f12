"""XLA backend compilations (persistent-cache loads included) inside the
window, counted from jax.monitoring's compile events."""


def read(ctx):
    return ctx.window_compiles
