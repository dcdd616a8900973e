"""Share of the traced window (%) in which no op ran on the device."""

from benchmark import tracing


def read(ctx):
    if ctx.trace is None or ctx.window is None:
        return None
    got = tracing.device_busy(ctx.trace, ctx.window)
    if got is None:
        return None
    busy_s, window_s = got
    return 100.0 * (1.0 - busy_s / window_s)
