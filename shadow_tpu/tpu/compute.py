"""The device compute plane: per-host service occupancy (`compute_step`).

The network planes answer "when does the packet arrive"; this module
answers the other half of the serving question — "when has the host
actually *processed* it". Each host is modeled as a single FIFO
service station in the SCALE-Sim/DCSim tradition (PAPERS.md: arxiv
2603.22535 supplies validated per-op TPU timings, arxiv 2411.13809 the
integrated compute+network host model): a busy-until clock, a bounded
FIFO queue with a depth counter, and a per-request service cost
``svc_ns`` drawn from the checked-in op-timing table
(`workloads/op_timings.json`) lowered at compile time into the traffic
program's per-(host, phase) ``compute_service_ns`` table.

Like every presence plane (docs/observability.md, docs/robustness.md),
the compute plane is a static compile-out switch on `window_step`
(``compute=None`` removes the section entirely) and is **bitwise-invisible to the
simulation state**: `compute_step` reads the delivered dict the step
already materialized and writes ONLY its own `ComputeState` — the
SL501 full-invisibility obligation ``window_step[compute]``
(analysis/proofs.py) proves no compute taint can reach the lead
outputs. The *coupling* — "a phase completes only when network
delivery AND host service time are both done" — lives in the scenario
runner's credit path (`gate_credits`), never inside the step.

Determinism + dtype discipline (docs/determinism.md):

- everything is int32 with I32_MAX-free closed-form arithmetic; the
  spec compiler bounds ``svc_ns * (ingress_cap + queue_cap + 1)``
  inside the int32 quarter-budget so no completion time can overflow;
- the FIFO is solved in closed form per window, no per-request scan:
  with constant per-host service cost ``s`` inside a window,
  completions obey ``c_j = max(c_{j-1}, a_j) + s``, and substituting
  ``d_j = c_j - s*j`` turns the recurrence into a running cummax —
  one `lax.cummax` over the delivered row (already in deterministic
  (deliver_t, src, seq) order, front-packed ascending);
- arrivals the bounded queue cannot hold are REFUSED from the tail of
  the window (the latest arrivals are exactly the ones still
  incomplete at window end, so trimming the suffix keeps the closed
  form exact): refused requests never complete, never credit a phase,
  and count in ``n_overflow`` — load shedding, not a silent clamp;
- queueing delay and request sojourn accumulate into the same
  log2-bucket histograms the PR-10 latency plane uses
  (`telemetry/histo.py`), kept INSIDE `ComputeState` so the existing
  `PlaneHistograms` record keys (and every golden byte) are untouched.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import histo
from .plane import I32_MAX


class ComputeTables(NamedTuple):
    """The lowered service-cost tables (read-only on device).

    ``service_ns[h, p]`` is host h's per-request service cost while in
    phase p — nonzero only for dep-bearing phases (a phase that waits
    on deliveries services them; emission-only phases cost nothing),
    lowered by `workloads/serve.lower_service_table` from the
    checked-in op-timing table. ``queue_cap`` is the static bound on
    requests still owed service at a window boundary."""

    service_ns: jnp.ndarray  # [N, P] int32
    queue_cap: int  # static


class ComputeState(NamedTuple):
    """Mutable per-host service-station state, axis 0 = host.

    Clocks are window-relative like the net plane's (`busy_rel` is the
    backlog-end instant relative to the current window start, rebased
    by ``shift_ns`` each step). Counters are modular int32 like every
    telemetry counter; the [N, B] histograms follow the
    `telemetry/histo.py` bucket scheme."""

    busy_rel: jnp.ndarray  # [N] int32 backlog end, rel to window start
    svc_ns: jnp.ndarray  # [N] int32 current phase's service cost
    q_depth: jnp.ndarray  # [N] int32 admitted-not-complete at window end
    served_win: jnp.ndarray  # [N] int32 completions within last window
    n_served: jnp.ndarray  # [N] int32 cumulative completions
    n_queued: jnp.ndarray  # [N] int32 cumulative arrivals that waited
    n_overflow: jnp.ndarray  # [N] int32 cumulative refused (queue full)
    n_credit_raw: jnp.ndarray  # [N] int32 raw credits offered (gate)
    n_granted: jnp.ndarray  # [N] int32 credits granted (gate)
    hist_wait_ns: jnp.ndarray  # [N, B] int32 queueing delay
    hist_sojourn_ns: jnp.ndarray  # [N, B] int32 wait + service


def make_compute_tables(service_ns, queue_cap: int) -> ComputeTables:
    """Upload the [N, P] service table (copies, like
    `workloads/device.to_device`). ``queue_cap`` must be >= 1: a
    zero-capacity queue would refuse every arrival that cannot start
    inside its own window, which is a config error, not a model."""
    if queue_cap < 1:
        raise ValueError(
            f"compute queue_cap={queue_cap} must be >= 1 (a bounded "
            "FIFO needs at least one waiting slot)")
    return ComputeTables(
        service_ns=jnp.array(np.asarray(service_ns), jnp.int32),
        queue_cap=int(queue_cap))


def make_compute_state(ct: ComputeTables) -> ComputeState:
    """Zeroed state; ``svc_ns`` pre-armed from phase 0's costs (hosts
    start IN phase 0, `workloads/device.make_workload_state`)."""
    n = ct.service_ns.shape[0]
    z = lambda: jnp.zeros((n,), jnp.int32)
    zb = lambda: jnp.zeros((n, histo.HIST_BUCKETS), jnp.int32)
    return ComputeState(
        busy_rel=z(), svc_ns=ct.service_ns[:, 0], q_depth=z(),
        served_win=z(), n_served=z(), n_queued=z(), n_overflow=z(),
        n_credit_raw=z(), n_granted=z(),
        hist_wait_ns=zb(), hist_sojourn_ns=zb())


def _ceil_div(x, y):
    """ceil(x / y) for non-negative int32 x, guarded for y == 0 (a
    zero-cost host has no backlog by construction)."""
    return jnp.where(y > 0, (x + jnp.maximum(y, 1) - 1)
                     // jnp.maximum(y, 1), 0)


def compute_step(ct: ComputeTables, cs: ComputeState, delivered,
                 shift_ns, window_ns) -> ComputeState:
    """Service one window's deliveries through each host's FIFO.

    `delivered` is `window_step`'s released dict for THIS window
    (front-packed per host in ascending (deliver_t, src, seq) order —
    the FIFO arrival order). Pure reads of the dict; writes only the
    returned `ComputeState`. Semantics per window:

    1. rebase the backlog clock by ``shift_ns`` (like every stored
       relative time);
    2. closed-form FIFO: completion ``c_j = s*(j+1) + max(busy,
       cummax_j(a_j - s*j))`` over the row's arrivals;
    3. bounded queue: if more than ``queue_cap`` admitted requests
       would still be incomplete at window end, the LAST excess
       arrivals of the window are refused (counted in ``n_overflow``,
       their service cancelled — they are exactly the tail of the
       completion order, so earlier completions are untouched);
    4. ``served_win`` = carried-backlog completions falling in this
       window + this window's arrivals completing in it — the count
       `gate_credits` meters phase credits against;
    5. queueing delay (service start - arrival) and sojourn
       (completion - arrival) of every ADMITTED arrival accumulate
       into the log2 histograms at admission (completion is already
       determined — the FIFO is deterministic).
    """
    mask = delivered["mask"]
    s = cs.svc_ns
    sN = s[:, None]
    cap = jnp.int32(ct.queue_cap)
    win = jnp.int32(window_ns)
    busy0 = jnp.maximum(cs.busy_rel - jnp.int32(shift_ns), 0)

    # -- carried backlog: the q_depth requests admitted earlier finish
    # at busy0, busy0 - s, ... (the last q service slots); those past
    # window end remain, the rest complete this window
    backlog = jnp.maximum(busy0 - win, 0)
    carried_rem = jnp.minimum(cs.q_depth, _ceil_div(backlog, s))
    carried_done = cs.q_depth - carried_rem

    # -- closed-form FIFO over this window's arrivals ------------------
    a = jnp.where(mask, delivered["deliver_rel"], 0)
    k = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1  # service rank
    base = jnp.where(mask, a - sN * k, -I32_MAX)
    d = jnp.maximum(busy0[:, None], jax.lax.cummax(base, axis=1))
    c = d + sN * (k + 1)  # completion time (valid where mask)

    # -- bounded queue: refuse the tail the depth bound cannot hold ----
    n_arr = mask.sum(axis=1, dtype=jnp.int32)
    incomplete = mask & (c > win)
    depth_all = carried_rem + incomplete.sum(axis=1, dtype=jnp.int32)
    over = jnp.maximum(depth_all - cap, 0)
    kept = mask & (k < (n_arr - over)[:, None])
    done_now = (kept & (c <= win)).sum(axis=1, dtype=jnp.int32)
    busy_end = jnp.maximum(
        busy0, jnp.max(jnp.where(kept, c, -I32_MAX), axis=1))

    wait = jnp.where(kept, c - sN - a, 0)
    sojourn = jnp.where(kept, c - a, 0)

    return cs._replace(
        busy_rel=busy_end,
        q_depth=depth_all - over,
        served_win=carried_done + done_now,
        n_served=cs.n_served + carried_done + done_now,
        n_queued=cs.n_queued
        + (kept & (wait > 0)).sum(axis=1, dtype=jnp.int32),
        n_overflow=cs.n_overflow + over,
        hist_wait_ns=histo.accum_rows(
            cs.hist_wait_ns, histo.bucket_index(wait), kept),
        hist_sojourn_ns=histo.accum_rows(
            cs.hist_sojourn_ns, histo.bucket_index(sojourn), kept))


def phase_service(ct: ComputeTables, cs: ComputeState,
                  phase) -> ComputeState:
    """Re-arm each host's per-request cost from its CURRENT phase's
    table entry (the runner calls this after `workload_step` advances
    the phase machine — `window_step` itself never sees phases)."""
    P = ct.service_ns.shape[1]
    idx = jnp.clip(phase, 0, P - 1)[:, None]
    return cs._replace(
        svc_ns=jnp.take_along_axis(ct.service_ns, idx, axis=1)[:, 0])


def gate_credits(cs: ComputeState, raw_credits):
    """Meter phase credits through service completion: the k-th credit
    is granted only when BOTH the k-th network credit (raw delivery
    count on the direct transport, ACKED in-order segment under
    ``transport: flows``) AND the k-th service completion have
    happened — ``granted = min(cum_raw, cum_served)``, delta'd against
    what was already granted. Hosts with ``svc_ns == 0`` serve
    instantly (``cum_served`` tracks raw arrivals), so the gate passes
    their credits through bitwise-unchanged. Returns (cs', got)."""
    cum_raw = cs.n_credit_raw + raw_credits
    granted = jnp.minimum(cum_raw, cs.n_served)
    got = granted - cs.n_granted
    return cs._replace(n_credit_raw=cum_raw, n_granted=granted), got
