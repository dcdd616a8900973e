"""Per-section cost profiler for the device-plane window step.

The r5 verdict's core complaint was that the general device plane had "no
live win" and nobody could say WHERE the per-window budget goes. This
module answers that: it rebuilds the PHOLD bench world (`bench.py`) at a
given shape, warms it to steady-state occupancy, then times every section
of `plane.window_step` as an ISOLATED jitted micro-kernel — the same
section helpers `window_step` itself composes (`plane._refill_tokens`,
`plane._egress_order`, ...), called with realistic intermediates and timed
with `block_until_ready` around every repetition. The output is a JSON
cost breakdown per section, so every optimization claim against the
window step is a measured before/after, not a guess.

Sections (superset of the window step's numbered stages):

- ``rebase_refill``   — clock rebase + token refill (section 1)
- ``rr_tensors``      — the RR qdisc's [N, CE, CE] rank tensors (2a)
- ``qdisc_sort``      — the egress qdisc row sort (2b)
- ``token_gate``      — prefix-sum bandwidth gate (2c)
- ``loss_latency``    — loss draw + latency table gathers (3)
- ``ingress_compact`` — surviving-ingress compaction sort (4)
- ``routing_scatter`` — the full routing stage (5): rank + placement
- ``routing_rank``    — routing sub-section 5a: the bucketed order
  (row seq-rank + the diet flat sort, which carries the payload
  columns, + bucket bounds; on the legacy path, the variadic flat sort
  + grouped ranks)
- ``routing_place``   — routing sub-section 5b: landing the payload
  columns into the destination ingress rows (one stacked gather; on
  the legacy path, the per-column scatters)
- ``release_due``     — due split/presentation sort (5b, direct mode)
- ``codel_drain``     — the router CoDel/relay micro-step (5b, AQM mode)
- ``egress_compact``  — leftover-egress compaction sort (6)
- ``ingest_rows``     — the bench/respawn row-merge append
- ``window_step``     — the full composed step (sanity anchor: section
  times should roughly sum to it; XLA fusion makes the sum an upper
  bound)
- ``window_step_telemetry`` — the full step with the PlaneMetrics
  telemetry counters threaded (docs/observability.md). The CI
  perf-smoke job fails when this drifts past the no-host-sync budget
  relative to ``window_step`` — the harvester may never add a device
  sync (or material compute) to the hot path.
- ``window_step_faults`` — the full step with NEUTRAL FaultArrays
  masks threaded (docs/robustness.md). The CI chaos-smoke job gates on
  its ratio against ``window_step`` the same way (local bar: 5%): the
  fault plane's presence switch must stay cheap when nothing fails.
- ``window_step_guards`` — the full step with a clean GuardState
  threaded (the runtime invariant plane, docs/robustness.md). Gated in
  CI chaos-smoke against ``window_step`` like telemetry and faults:
  self-verification may never cost the hot path more than the presence
  switches before it.
- ``window_step_elastic`` — the full step plus the per-ring overflow
  deltas the elastic capacity driver reads back every window
  (`tpu/elastic.run_elastic_window`, docs/robustness.md "Elastic
  capacity"). Gated in CI chaos-smoke against ``window_step`` at the
  same 1.35x budget: an idle elastic run (nothing overflows) must cost
  essentially nothing over the plain step.
- ``window_step_trace`` — the full step with BOTH halves of the
  distribution/flight-recorder observability plane threaded
  (`telemetry/histo.PlaneHistograms` + `telemetry/flightrec.
  FlightRecArrays` at sample_every=64, docs/observability.md
  "Distributions and the flight recorder"). The CI perf-smoke job
  GATES on its ratio against ``window_step`` (<= 1.35) like the
  telemetry section: histogram one-hot sums, the sampling threefry,
  and the trace-ring compaction may never cost the hot path a sync or
  material compute.
- ``window_chain8`` — EIGHT window steps as one compiled
  `lax.scan` chain (the shared driver's device-resident unit,
  `tpu/elastic.drive_chained_windows`): divide by 8×``window_step``
  for the chain amortization ratio — what a host sync per window was
  costing. bench.py records the companion ``windows_per_sync`` ratio
  in its JSON `driver` field.
- ``window_step_workload`` — the full step plus the workload plane's
  `workload_step` (`shadow_tpu/workloads/device.py`, an onoff traffic
  program at the bench shape): phase-pointer advance + table-driven
  emission + the ingest_rows append, i.e. the per-window cost a
  scenario driver pays over the bare step. Gated in CI like the
  other plane sections (ratio vs ``window_step`` <= 1.35,
  docs/workloads.md).

- ``window_step_flows`` — the full step with the device flow plane
  threaded (`tpu/flows.py`: ack/credit classification over the
  delivered dict, the vmapped Reno/RTO handlers, and the masked
  emission append) over one IDLE flow per host — the neutral
  presence cost, exactly how the faults/guards sections price their
  planes. Gated in CI (ratio vs ``window_step`` <= 1.35,
  docs/robustness.md "Flow plane").

- ``window_step_compute`` — the full step with the compute plane
  threaded (`tpu/compute.py`: the closed-form FIFO cummax over the
  delivered dict + the bounded-queue tail trim + the wait/sojourn
  histogram folds) over an IDLE zero-backlog ComputeState — the
  neutral presence cost, priced exactly like the flows/faults/guards
  sections. Gated in CI (ratio vs ``window_step`` <= 1.35,
  docs/workloads.md "Serving load & the compute plane").

Drive it from the CLI: ``python tools/profile_plane.py --hosts 1024,32768``.

These are isolated micro-kernels timed by the host clock, not self times
of the real chain: per-section device time of the compiled chain comes
from the `jax.named_scope` each `window_step` section carries, read from
a profiler trace (`benchmark/scopes.py`). The sections here stay for the
CI presence-cost gates, which compare whole-step variants.
"""

from __future__ import annotations

import time as _walltime

import numpy as np

from ..workloads.phold import respawn_batch  # noqa: F401 — back-compat
# re-export: PHOLD moved to the workload plane (workloads/phold.py);
# bench.py / chaos_smoke import the new home, older callers keep
# finding `profiling.respawn_batch` here.

MS = 1_000_000

#: sections timed by default (codel_drain is representative of AQM mode
#: even though the bench's direct mode never runs it)
DEFAULT_SECTIONS = (
    "rebase_refill", "rr_tensors", "qdisc_sort", "token_gate",
    "loss_latency", "ingress_compact", "routing_scatter", "routing_rank",
    "routing_place", "release_due", "codel_drain", "egress_compact",
    "ingest_rows", "window_step", "window_chain8",
    "window_step_telemetry",
    "window_step_faults", "window_step_guards", "window_step_elastic",
    "window_step_trace", "window_step_workload", "window_step_flows",
    "window_step_compute",
)


def _time_call(fn, args, reps: int) -> dict:
    """Median/min wall time of a jitted section, blocking every rep.

    Wall-clock here is pure measurement output (the profiler never feeds
    sim state), hence the SL101 suppressions."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)  # compile + first run outside the timing
    times = []
    for _ in range(reps):
        t0 = _walltime.perf_counter()  # shadowlint: disable=SL101 -- profiler measurement
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(_walltime.perf_counter() - t0)  # shadowlint: disable=SL101 -- profiler measurement
    times.sort()
    return {
        "min_ms": round(times[0] * 1e3, 4),
        "median_ms": round(times[len(times) // 2] * 1e3, 4),
        "reps": reps,
    }


def build_world(n_hosts: int, *, n_nodes: int = 64, egress_cap: int = 16,
                ingress_cap: int = 32, seed: int = 0,
                warmup_windows: int = 3):
    """The bench.py PHOLD world at steady state: node-level path tables,
    4 seed packets per host, `warmup_windows` full windows executed so
    egress/ingress occupancy matches what the bench's scan body sees."""
    import jax
    import jax.numpy as jnp

    from . import ingest, make_params, make_state
    from .plane import window_step

    N, M = n_hosts, n_nodes
    rng = np.random.default_rng(seed)
    lat = rng.integers(1 * MS, 50 * MS, size=(M, M), dtype=np.int32)
    lat = np.minimum(lat, lat.T)
    loss = np.full((M, M), 0.01, np.float32)
    host_node = (np.arange(N) % M).astype(np.int32)
    bw = np.full((N,), 10_000_000_000, np.int64)
    params = make_params(lat, loss, bw, host_node=host_node)
    state = make_state(N, egress_cap=egress_cap, ingress_cap=ingress_cap,
                       initial_tokens=np.asarray(params.tb_cap))
    k = 4
    src0 = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    dst0 = (src0 * 1566083941
            + jnp.tile(jnp.arange(k, dtype=jnp.int32), N) * 40503 + 1) % N
    b0 = src0.shape[0]
    state = ingest(
        state, src0, dst0,
        jnp.full((b0,), 1400, jnp.int32),
        jnp.arange(b0, dtype=jnp.int32),
        jnp.arange(b0, dtype=jnp.int32),
        jnp.zeros((b0,), bool),
    )
    rng_root = jax.random.key(1)
    window = jnp.int32(10 * MS)
    step = jax.jit(lambda st, sh: window_step(
        st, params, rng_root, sh, window, rr_enabled=False))
    shift = jnp.int32(0)
    delivered = None
    for _ in range(warmup_windows):
        state, delivered, _next = step(state, shift)
        shift = window
    jax.block_until_ready(state)
    return {
        "state": state, "params": params, "rng_root": rng_root,
        "shift": window, "window": window, "delivered": delivered,
        "egress_cap": egress_cap, "ingress_cap": ingress_cap,
    }


def profile_sections(n_hosts: int, *, reps: int = 20,
                     sections=None, rr_enabled: bool = False,
                     packed_sort: bool = True, n_nodes: int = 64, egress_cap: int = 16,
                     ingress_cap: int = 32, seed: int = 0) -> dict:
    """Time each window-step section at the given bench shape. Returns a
    JSON-ready dict. `packed_sort=False` times the pre-diet variadic
    sorts (the before/after comparison the PR-level claims quote)."""
    import jax
    import jax.numpy as jnp

    from . import codel
    from .plane import (I32_MAX, NO_CLAMP, _compact_egress,
                        _compact_ingress, _egress_order, _loss_latency,
                        _qdisc_keys, _refill_tokens, _release_due,
                        _route_scatter, _routing_place,
                        _routing_place_legacy, _routing_rank,
                        _routing_rank_legacy, _row_sort, _token_gate,
                        ingest_rows, window_step)

    from ..faults.plane import neutral_faults as _neutral_faults
    from ..guards.plane import make_guards as _clean_guards
    from ..telemetry import make_flightrec as _fresh_flightrec
    from ..telemetry import make_histograms as _zero_hist
    from ..telemetry import make_metrics as _zero_metrics

    wanted = tuple(sections) if sections is not None else DEFAULT_SECTIONS
    world = build_world(n_hosts, n_nodes=n_nodes, egress_cap=egress_cap,
                        ingress_cap=ingress_cap, seed=seed)
    state, params = world["state"], world["params"]
    rng_root, shift, window = world["rng_root"], world["shift"], \
        world["window"]
    N = n_hosts
    CI = ingress_cap

    # precompute each section's inputs ONCE (jitted, materialized) so the
    # timed call measures exactly one section
    def rebase_refill(state, shift):
        in_deliver = jnp.where(state.in_valid,
                               state.in_deliver_rel - shift, I32_MAX)
        balance, rem = _refill_tokens(state, params, shift)
        eg_tsend_rb = jnp.where(state.eg_valid, state.eg_tsend - shift, 0)
        eg_clamp_rb = jnp.where(
            state.eg_valid & (state.eg_clamp != NO_CLAMP),
            state.eg_clamp - shift, state.eg_clamp)
        return in_deliver, balance, rem, eg_tsend_rb, eg_clamp_rb

    pre = jax.jit(rebase_refill)(state, shift)
    in_deliver, balance, _rem, eg_tsend_rb, eg_clamp_rb = \
        jax.block_until_ready(pre)
    qk1, qk2, _aux = jax.jit(
        lambda st: _qdisc_keys(st, params, rr_enabled=rr_enabled))(state)
    order = jax.jit(lambda st, a, b, c, d: _egress_order(
        st, a, b, c, d, rr_enabled=rr_enabled, packed_sort=packed_sort))
    (eg_prio, eg_sock, eg_dst, eg_bytes, eg_seq, eg_ctrl, eg_tsend,
     eg_clamp, eg_valid) = jax.block_until_ready(
        order(state, qk1, qk2, eg_tsend_rb, eg_clamp_rb))
    sendable, _bal2 = jax.jit(_token_gate)(eg_valid, eg_bytes, balance)
    loss_fn = jax.jit(lambda st, dsts, ctrl, ts, cl, snd: _loss_latency(
        st, params, rng_root, dsts, ctrl, ts, cl, snd, window,
        no_loss=False))
    sent, _lost, _rc, deliver_rel = jax.block_until_ready(
        loss_fn(state, eg_dst, eg_ctrl, eg_tsend, eg_clamp, sendable))
    compact = jax.jit(lambda st, ind: _compact_ingress(
        st, ind, packed_sort=packed_sort))
    (in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c, in_valid_c,
     n_valid_in) = jax.block_until_ready(compact(state, in_deliver))
    route = jax.jit(lambda *a: _route_scatter(*a, packed_sort=packed_sort))
    (in_src_m, in_seq_m, in_sock_m, in_bytes_m, in_deliver_m, in_valid_m,
     _ovf) = jax.block_until_ready(route(
        sent, eg_dst, eg_seq, eg_bytes, eg_sock, deliver_rel, in_deliver_c,
        in_src_c, in_seq_c, in_sock_c, in_bytes_c, in_valid_c, n_valid_in))

    # the routing sub-sections (5a rank / 5b place) per sort mode; the
    # place inputs are the rank outputs, precomputed untimed
    if packed_sort:
        route_rank = jax.jit(lambda s, d, q, k, b, dl, nv: _routing_rank(
            s, d, q, k, b, dl, nv, CI))
        rank_args = (sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel,
                     n_valid_in)
        rank_out = jax.block_until_ready(route_rank(*rank_args))
        route_place = jax.jit(_routing_place)
        place_args = (*rank_out[:3], n_valid_in, in_deliver_c, in_src_c,
                      in_seq_c, in_sock_c, in_bytes_c, in_valid_c)
    else:
        route_rank = jax.jit(lambda s, d, q, b, k, dl, nv:
                             _routing_rank_legacy(s, d, q, b, k, dl, nv,
                                                  CI))
        rank_out = jax.block_until_ready(route_rank(
            sent, eg_dst, eg_seq, eg_bytes, eg_sock, deliver_rel,
            n_valid_in))
        route_place = jax.jit(_routing_place_legacy)
        place_args = (*rank_out[:7], in_deliver_c, in_src_c, in_seq_c,
                      in_sock_c, in_bytes_c, in_valid_c)
        rank_args = (sent, eg_dst, eg_seq, eg_bytes, eg_sock, deliver_rel,
                     n_valid_in)
    eg_valid_left = jax.block_until_ready(
        jax.jit(lambda v, s: v & ~s)(eg_valid, sendable))

    # AQM-mode inputs for the codel micro-step: arrival-ordered ingress +
    # a rebased router state (built once, untimed)
    def aqm_presort(valid_m, deliver_m, src_m, seq_m, sock_m, bytes_m):
        inv_m = (~valid_m).astype(jnp.int32)
        arr_key = jnp.where(valid_m, deliver_m, I32_MAX)
        return _row_sort(inv_m, arr_key, src_m, seq_m, sock_m, bytes_m,
                         valid_m, keys=4)
    (_, arr_s, _src_s, _seq_s, _sock_s, bytes_s, _valid_s) = \
        jax.block_until_ready(jax.jit(aqm_presort)(
            in_valid_m, in_deliver_m, in_src_m, in_seq_m, in_sock_m,
            in_bytes_m))
    rt = jax.block_until_ready(jax.jit(
        lambda st, sh: codel.rebase_router_state(
            st.router, sh, params.dn_rate, params.dn_cap))(state, shift))

    # the bench's respawn batch for ingest_rows, shaped from the warmup
    # window's delivered set (spawn_seq/round_idx pinned to the bench's
    # first respawning round)
    deliv = world["delivered"]
    spawn_seq = jnp.full((N,), 10_000, jnp.int32)
    mask, new_dst, row_bytes, seq_vals, row_ctrl = jax.block_until_ready(
        jax.jit(lambda d: respawn_batch(d, spawn_seq, jnp.int32(1), N, CI))(
            deliv))

    def _elastic_probe(st, sh):
        out = window_step(st, params, rng_root, sh, window,
                          rr_enabled=rr_enabled, packed_sort=packed_sort)
        ovf = out[0].n_overflow_dropped - st.n_overflow_dropped
        return (*out, ovf, ovf.sum())

    def _make_workload_probe():
        # the workload plane's per-window cost: the step + a
        # table-driven workload_step (an onoff program over the full
        # fleet at the bench shape — phase advance, emission gathers,
        # ingest_rows append). Built only when the section is wanted,
        # so profiles that skip it never pay the program compile.
        from ..workloads import compile_program, parse_scenario
        from ..workloads import device as _wdevice

        prog = compile_program(parse_scenario({
            "name": "profile-onoff", "hosts": n_hosts,
            "egress_cap": egress_cap, "ingress_cap": ingress_cap,
            "patterns": [{"kind": "onoff", "burst": 2, "rounds": 4,
                          "gap_ns": 200_000, "off_mean_ns": 2_000_000}],
        }))
        wl = _wdevice.to_device(prog)

        def probe(st, ws, sh):
            st, delivered, nxt = window_step(
                st, params, rng_root, sh, window,
                rr_enabled=rr_enabled, packed_sort=packed_sort)
            st, ws = _wdevice.workload_step(wl, ws, st, delivered,
                                            jnp.int32(1), window)
            return st, ws, nxt

        return jax.jit(probe), _wdevice.make_workload_state(prog)

    def _chain8(st, sh):
        """Eight windows as one compiled scan — the shared driver's
        device-resident chain unit at its smallest realistic length."""
        def body(carry, _ridx):
            st, sh = carry
            st, delivered, _nxt = window_step(
                st, params, rng_root, sh, window, rr_enabled=rr_enabled,
                packed_sort=packed_sort)
            return (st, window), delivered["mask"].sum(dtype=jnp.int32)
        (st, _sh), outs = jax.lax.scan(
            body, (st, sh), jnp.arange(8, dtype=jnp.int32))
        return st, outs.sum()

    section_calls = {
        "window_chain8": (jax.jit(_chain8), (state, shift)),
        "rebase_refill": (jax.jit(rebase_refill), (state, shift)),
        "rr_tensors": (
            jax.jit(lambda st: _qdisc_keys(st, params, rr_enabled=True)),
            (state,)),
        "qdisc_sort": (order, (state, qk1, qk2, eg_tsend_rb, eg_clamp_rb)),
        "token_gate": (jax.jit(_token_gate), (eg_valid, eg_bytes, balance)),
        "loss_latency": (
            loss_fn, (state, eg_dst, eg_ctrl, eg_tsend, eg_clamp, sendable)),
        "ingress_compact": (compact, (state, in_deliver)),
        "routing_scatter": (route, (
            sent, eg_dst, eg_seq, eg_bytes, eg_sock, deliver_rel,
            in_deliver_c, in_src_c, in_seq_c, in_sock_c, in_bytes_c,
            in_valid_c, n_valid_in)),
        "routing_rank": (route_rank, rank_args),
        "routing_place": (route_place, place_args),
        "release_due": (
            jax.jit(lambda *a: _release_due(
                *a, window, packed_sort=packed_sort)),
            (in_deliver_m, in_src_m, in_seq_m, in_sock_m, in_bytes_m,
             in_valid_m)),
        "codel_drain": (
            jax.jit(lambda a, b, r: codel.router_drain(
                a, b, window, params.dn_rate, params.dn_cap, r)),
            (arr_s, bytes_s, rt)),
        "egress_compact": (
            jax.jit(lambda *a: _compact_egress(
                *a, packed_sort=packed_sort)),
            (eg_prio, eg_dst, eg_bytes, eg_seq, eg_ctrl, eg_tsend,
             eg_clamp, eg_sock, eg_valid_left)),
        "ingest_rows": (
            jax.jit(lambda st, d, b, p, s, c, v: ingest_rows(
                st, d, b, p, s, c, v, packed_sort=packed_sort)),
            (state, new_dst, row_bytes, seq_vals, seq_vals, row_ctrl,
             mask)),
        "window_step": (
            jax.jit(lambda st, sh: window_step(
                st, params, rng_root, sh, window, rr_enabled=rr_enabled,
                packed_sort=packed_sort)),
            (state, shift)),
        "window_step_telemetry": (
            jax.jit(lambda st, m, sh: window_step(
                st, params, rng_root, sh, window, rr_enabled=rr_enabled,
                packed_sort=packed_sort, metrics=m)),
            (state, _zero_metrics(n_hosts), shift)),
        "window_step_faults": (
            jax.jit(lambda st, f, sh: window_step(
                st, params, rng_root, sh, window, rr_enabled=rr_enabled,
                packed_sort=packed_sort, faults=f)),
            (state, _neutral_faults(n_hosts, n_nodes), shift)),
        "window_step_guards": (
            jax.jit(lambda st, g, sh: window_step(
                st, params, rng_root, sh, window, rr_enabled=rr_enabled,
                packed_sort=packed_sort, guards=g)),
            (state, _clean_guards(n_hosts), shift)),
        "window_step_trace": (
            # the flight recorder + histograms (docs/observability.md
            # "Distributions and the flight recorder")
            jax.jit(lambda st, h, f, sh: window_step(
                st, params, rng_root, sh, window, rr_enabled=rr_enabled,
                packed_sort=packed_sort, hist=h, flightrec=f)),
            (state, _zero_hist(n_hosts),
             _fresh_flightrec(0, sample_every=64, ring=4096), shift)),
        "window_step_elastic": (
            # the elastic driver's per-window cost: the step + the
            # per-ring overflow deltas it reads back to decide growth
            # (the read-back itself is the same tiny D2H every timed
            # rep already pays in block_until_ready)
            jax.jit(lambda st, sh: _elastic_probe(st, sh)),
            (state, shift)),
    }
    if "window_step_workload" in wanted:
        _probe, _wstate = _make_workload_probe()
        section_calls["window_step_workload"] = (
            _probe, (state, _wstate, shift))
    if "window_step_flows" in wanted:
        # the flow plane's presence cost: one idle flow per host
        # (active endpoints, nothing left to send) — the recv
        # classification, the vmapped ack/RTO handlers, and the
        # masked emission all run at fleet width, like the neutral
        # fault masks / clean guards the sibling sections thread
        from . import flows as _flows

        _ftab = _flows.make_flow_tables(
            np.arange(n_hosts, dtype=np.int32),
            (np.arange(n_hosts, dtype=np.int32) + 1) % n_hosts,
            np.full(n_hosts, 1400, np.int32))
        _fstate = _flows.make_flow_state(n_hosts)
        section_calls["window_step_flows"] = (
            jax.jit(lambda st, fst, sh: window_step(
                st, params, rng_root, sh, window,
                rr_enabled=rr_enabled, packed_sort=packed_sort,
                flows=(_ftab, fst))),
            (state, _fstate, shift))
    if "window_step_compute" in wanted:
        # the compute plane's presence cost: a one-phase uniform
        # service table, zero backlog — the closed-form FIFO and the
        # histogram folds run at full delivered width, like the idle
        # flow / neutral fault sections above
        from . import compute as _compute

        _ctab = _compute.make_compute_tables(
            np.full((n_hosts, 1), 25_000, np.int32), 64)
        _cstate = _compute.make_compute_state(_ctab)
        section_calls["window_step_compute"] = (
            jax.jit(lambda st, cst, sh: window_step(
                st, params, rng_root, sh, window,
                rr_enabled=rr_enabled, packed_sort=packed_sort,
                compute=(_ctab, cst))),
            (state, _cstate, shift))

    out_sections = {}
    for name in wanted:
        fn, args = section_calls[name]
        out_sections[name] = _time_call(fn, args, reps)

    return {
        "hosts": n_hosts,
        "egress_cap": egress_cap,
        "ingress_cap": ingress_cap,
        "nodes": n_nodes,
        "backend": jax.default_backend(),
        "rr_enabled": rr_enabled,
        "packed_sort": packed_sort,
        "sections": out_sections,
    }
