"""The TPU network plane.

Everything from "socket emits packet" down — rate limiting, latency/loss
sampling, cross-host delivery, queueing — runs as batched JAX ops over
hosts-as-SoA arrays (SURVEY.md §7). The CPU planes (sockets, syscalls,
processes) stay object-level; this plane carries packet *metadata* at scale.
Payload bytes never leave the host: the (src, seq) pair correlates delivered
metadata back to payloads buffered CPU-side.
"""

import logging as _logging
import os as _os
from pathlib import Path as _Path

from .flows import (FlowState, FlowTables, make_flow_state,
                    make_flow_tables)
from .mesh import host_sharding, make_mesh, shard_state
from .plane import (NetPlaneParams, NetPlaneState, chain_windows, ingest,
                    ingest_rows, make_params, make_state, unpack_planes,
                    window_step)


_REPO_ROOT = _Path(__file__).resolve().parents[2]
_log = _logging.getLogger("shadow_tpu.tpu")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache (idempotent) and return
    its directory: `$JAX_COMPILATION_CACHE_DIR` when set, else the fixed
    `<repo>/.jax_cache` (gitignored). The path is part of the cache key,
    so it never holds a temp name, a pid or a time."""
    import jax

    path = _os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        _REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path


_device_logged = False


def _cpu_requested() -> bool:
    import jax

    return (jax.config.jax_platforms or "") == "cpu"


def require_device(plane: str) -> None:
    """Where a device plane starts: turn on the compilation cache, log
    the device once, and refuse to run anywhere but a TPU unless the CPU
    was asked for explicitly (`JAX_PLATFORMS=cpu` / `jax_platforms`) —
    JAX picks the CPU backend silently when it finds no chip, and a
    device-plane run there measures nothing the plane is for. The
    refusal is a ConfigError: the configuration asks for a plane this
    machine cannot run."""
    global _device_logged
    import jax

    from ..core.config import ConfigError

    enable_compilation_cache()
    backend = jax.default_backend()
    if not _device_logged:
        dev = jax.devices()[0]
        _log.info("device plane on %s (%s) x%d", dev.platform,
                  dev.device_kind, jax.device_count())
        _device_logged = True
    if backend != "tpu" and not _cpu_requested():
        raise ConfigError(
            f"{plane}: JAX found no TPU (backend {backend!r}); this "
            f"device plane runs on a TPU. Set JAX_PLATFORMS=cpu to run "
            f"it on the CPU on purpose.")


def donating_jit(fun=None, donate_argnums=(0,), **jit_kwargs):
    """`jax.jit` that donates the state-pytree argument(s) so XLA aliases
    the ~20 [N, C] state buffers in place across window dispatches instead
    of re-materializing them — on every backend, so the CPU tests hold
    callers to the same contract the chip does. DONATION CONTRACT:
    callers must treat the donated argument as consumed — rebind the
    returned state and never touch the input again (see
    docs/performance.md)."""
    import functools

    import jax

    if fun is None:
        return functools.partial(donating_jit,
                                 donate_argnums=donate_argnums, **jit_kwargs)
    return jax.jit(fun, donate_argnums=donate_argnums, **jit_kwargs)


__all__ = [
    "FlowState",
    "FlowTables",
    "NetPlaneParams",
    "NetPlaneState",
    "chain_windows",
    "donating_jit",
    "enable_compilation_cache",
    "require_device",
    "make_flow_state",
    "make_flow_tables",
    "ingest",
    "ingest_rows",
    "make_params",
    "make_state",
    "unpack_planes",
    "window_step",
    "make_mesh",
    "host_sharding",
    "shard_state",
]
