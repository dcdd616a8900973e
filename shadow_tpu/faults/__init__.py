"""The fault plane: deterministic fault injection, checkpoint/restore,
and self-healing execution (docs/robustness.md).

- `schedule`   — the `faults:` config block compiled into a seeded,
  virtual-time event schedule with CPU mask state + device arrays.
- `plane`      — the `FaultArrays` SoA masks `tpu/plane.window_step`
  threads as a static presence switch (faults=None compiles out).
- `checkpoint` — atomic, checksummed checkpoints: bitwise device-plane
  restore, flow-engine bucket resume, Manager diagnostic snapshots,
  and the shared single-file npz format (`write_npz_checkpoint`).
- `runstate`   — the full-run checkpointer: the ENTIRE chained-driver
  carry (every plane + schedule position + memo cache) in one atomic
  file, resumable to a byte-identical final artifact.
- `watchdog`   — the round watchdog: hung managed processes become a
  structured `WatchdogError` with per-host blame.
- `healing`    — transient-device-error retry (deterministic seeded
  backoff).
"""

from .checkpoint import (CheckpointError, load_checkpoint,  # noqa: F401
                         load_npz_checkpoint, load_plane_checkpoint,
                         prune_checkpoints, save_plane_checkpoint,
                         write_checkpoint, write_npz_checkpoint)
from .healing import (backoff_schedule,  # noqa: F401
                      is_transient_device_error, retry_transient)
from .plane import FaultArrays, neutral_faults  # noqa: F401
from .runstate import (RUNSTATE_SCHEMA, RunCheckpointer,  # noqa: F401
                       flatten_carry, latest_checkpoint, load_runstate,
                       restore_carry, resume_carry)
from .schedule import (FaultEvent, FaultSchedule,  # noqa: F401
                       compile_schedule)
from .watchdog import HostBlame, RoundWatchdog, WatchdogError  # noqa: F401

__all__ = [
    "CheckpointError", "FaultArrays", "FaultEvent", "FaultSchedule",
    "HostBlame", "RUNSTATE_SCHEMA", "RoundWatchdog",
    "RunCheckpointer", "WatchdogError", "backoff_schedule",
    "compile_schedule", "flatten_carry", "is_transient_device_error",
    "latest_checkpoint", "load_checkpoint", "load_npz_checkpoint",
    "load_plane_checkpoint", "load_runstate", "neutral_faults",
    "prune_checkpoints", "restore_carry", "resume_carry",
    "retry_transient", "save_plane_checkpoint", "write_checkpoint",
    "write_npz_checkpoint",
]
