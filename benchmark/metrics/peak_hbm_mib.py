"""Peak device memory in use (MiB), from the allocator after the window."""


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 2**20
