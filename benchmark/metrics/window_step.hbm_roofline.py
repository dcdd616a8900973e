"""Least time for one window's bytes at the chip's HBM peak, as a share
(%) of the chain program's device time per window. The least bytes are
the state pytree's leaf bytes read once and written once, counted from
shapes (`least_bytes`), so the work is the same whatever implements
the step."""

import importlib.util
import os

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def least_bytes(shapes) -> int:
    """Leaf bytes read once and written once: 2 x sum(size x itemsize)
    over (shape, dtype) pairs."""
    return 2 * int(sum(int(np.prod(s, dtype=np.int64))
                       * np.dtype(d).itemsize for s, d in shapes))


def share(shapes, bytes_per_s: float, ms_per_window: float) -> float:
    """Least time over device time, in %."""
    return 100.0 * least_bytes(shapes) / bytes_per_s / (ms_per_window / 1e3)


def read(ctx):
    if ctx.peaks is None or not ctx.run.get("state_shapes"):
        return None
    path = os.path.join(_HERE, "window_step.device_ms_per_window.py")
    spec = importlib.util.spec_from_file_location("bench_m_dev_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ms = mod.read(ctx)
    if not ms:
        return None
    return share(ctx.run["state_shapes"], ctx.peaks["hbm_bytes_per_s"], ms)
