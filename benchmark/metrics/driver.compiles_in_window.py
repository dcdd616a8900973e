"""Programs compiled or loaded from the cache inside the measured window
(jax.monitoring backend-compile events); should read 0."""


def read(ctx):
    return ctx.compiles_in_window
