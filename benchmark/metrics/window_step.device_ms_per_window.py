"""Device time of the PHOLD chain program (window_step + respawn +
ingest_rows, one XLA module) per simulated window, from the trace."""

from benchmark import tracing

MODULE = "phold_chain"


def read(ctx):
    if ctx.trace is None or ctx.window is None or not ctx.run.get("windows"):
        return None
    s = tracing.module_seconds(ctx.trace, ctx.window, MODULE)
    if s is None:
        return None
    return 1e3 * s / ctx.run["windows"]
