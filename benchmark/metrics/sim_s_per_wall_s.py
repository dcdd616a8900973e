"""Simulated seconds completed in the window over its wall time, from
window start to the block_until_ready of the last whole chain or run."""


def read(ctx):
    return ctx.run["sim_s"] / ctx.run["wall_s"]
