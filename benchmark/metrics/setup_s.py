"""Process start to window start: imports, device, world, compile or
cache load, warm-up."""


def read(ctx):
    return ctx.setup_s
