"""Self-healing execution: transient-error retry.

`retry_transient` retries a device dispatch of the Manager with
backoff (docs/robustness.md). Only errors that LOOK transient
(resource exhaustion, a runtime reporting UNAVAILABLE or ABORTED) are
retried; anything else — and exhaustion of the retry budget —
re-raises so a real bug still fails the run. The backoff sleeps WALL
time, which can only change performance, never results — but the
*schedule* of sleeps is itself deterministic: `backoff_schedule`
derives the exact delay sequence (exponential from the base, capped,
seeded jitter) as a pure function of (attempts, base, cap, jitter,
seed, what), so two runs of the same config retry on the same wall
cadence and a postmortem can reproduce the timing it is reading about.
"""

from __future__ import annotations

import hashlib
import logging
import time as _walltime
from typing import Callable, Tuple

log = logging.getLogger("shadow_tpu.faults")

#: substrings that mark a device error as plausibly transient
#: (XlaRuntimeError messages carry the grpc/absl status name)
_TRANSIENT_MARKERS = (
    "RESOURCE_EXHAUSTED", "UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED",
)


def is_transient_device_error(exc: BaseException) -> bool:
    """Heuristic classifier for retryable device/runtime errors. Python
    errors (TypeError, ValueError, tracer leaks) are NEVER transient."""
    if isinstance(exc, (TypeError, ValueError, KeyError, AssertionError)):
        return False
    text = f"{type(exc).__name__}: {exc}"
    return any(marker in text for marker in _TRANSIENT_MARKERS)


def backoff_schedule(attempts: int, *, base_s: float = 0.05,
                     cap_s: float = 2.0, jitter: float = 0.5,
                     seed: int = 0,
                     what: str = "device dispatch") -> Tuple[float, ...]:
    """The deterministic retry-delay sequence: delay k starts at
    `min(cap_s, base_s * 2**k)` and seeded jitter shaves up to a
    `jitter` fraction off it (de-synchronizing a fleet of workers all
    retrying the same stalled link, without ever sleeping LONGER than
    the unjittered exponential). Pure function of its arguments — the
    k-th jitter draw is sha256(seed, what, k) mapped to [0, 1), no
    PRNG object and no global stream, so two runs of the same config
    sleep the same schedule and the seed-pinned tests assert the
    exact floats."""
    if attempts < 0:
        raise ValueError(f"attempts must be >= 0, got {attempts}")
    if not 0.0 <= jitter <= 1.0:
        raise ValueError(f"jitter must be in [0, 1], got {jitter}")
    out = []
    for k in range(attempts):
        digest = hashlib.sha256(f"{seed}|{what}|{k}".encode()).digest()
        u = int.from_bytes(digest[:8], "big") / 2.0 ** 64
        delay = min(cap_s, base_s * (2.0 ** k))
        out.append(delay * (1.0 - jitter * u))
    return tuple(out)


def retry_transient(fn: Callable, *args, attempts: int = 3,
                    backoff_s: float = 0.05, cap_s: float = 2.0,
                    jitter: float = 0.5, seed: int = 0,
                    classify=is_transient_device_error,
                    what: str = "device dispatch", **kwargs):
    """Call `fn`; on a transient error retry up to `attempts` more
    times, sleeping the `backoff_schedule` delay sequence (exponential
    from `backoff_s`, capped at `cap_s`, seeded jitter). Non-transient
    errors and budget exhaustion re-raise the ORIGINAL error."""
    delays = backoff_schedule(attempts, base_s=backoff_s, cap_s=cap_s,
                              jitter=jitter, seed=seed, what=what)
    for attempt in range(attempts + 1):
        try:
            return fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — classified + re-raised
            if attempt >= attempts or not classify(e):
                raise
            delay = delays[attempt]
            log.warning(
                "transient error in %s (attempt %d/%d, retrying in "
                "%.2fs): %s", what, attempt + 1, attempts, delay, e)
            _walltime.sleep(delay)

