"""Host-device syncs per run of the flow driver: completion probes,
saturation reads and result reads (SimStats.flow_counters)."""


def read(ctx):
    return ctx.run.get("syncs_per_run")
