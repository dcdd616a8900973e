"""Least time for one run's bytes at the chip's HBM peak, as a share (%)
of the flow engine's device time per run. The least bytes
(`flowscopes.least_bytes`) are every connection's TCP state and segment
ring at the config's `queue_slots`, read once and written once per
lookahead window up to its bucket's last completion (the reference's),
counted from the engine's contract by hand (`flowscopes.CONN_WORDS`),
so the work is the same whatever implements the engine."""

from benchmark import flowscopes

_device_ms = flowscopes.reader(__file__)


def share(queue_slots: int, buckets, bytes_per_s: float,
          ms_per_run: float):
    """Least time over device time, in %."""
    return (100.0 * flowscopes.least_bytes(queue_slots, buckets)
            / bytes_per_s / (ms_per_run / 1e3))


def read(ctx):
    if ctx.peaks is None or not ctx.run.get("buckets"):
        return None
    ms = _device_ms(ctx)
    if not ms:
        return None
    return share(ctx.cfg["engine"]["queue_slots"], ctx.run["buckets"],
                 ctx.peaks["hbm_bytes_per_s"], ms)
