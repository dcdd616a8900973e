#!/usr/bin/env python3
"""Benchmark: TPU network-plane packet throughput vs the CPU object plane.

Workload: a PHOLD-style closed loop (the classic PDES benchmark Shadow
ships configs for, `src/test/phold/`) — every delivered packet spawns a new
packet to a pseudorandom destination, so the event population is constant
and every round does real routing/loss/rate-limit work.

- TPU side: N_HOSTS hosts as SoA arrays; R rounds of `window_step` +
  on-device respawn, driven by one `jax.lax.scan` (a single compiled
  program; no host transfers inside the loop).
- Baseline: the same PHOLD logic on the CPU object plane (Host/Worker/
  EventQueue, the faithful Shadow-architecture path) — the stand-in for the
  reference's per-packet CPU cost on this machine.

Prints ONE JSON line:
  {"metric": "packet_events_per_sec", "value": ..., "unit": "events/s",
   "vs_baseline": <tpu_rate / cpu_object_plane_rate>}
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

MS = 1_000_000

N_HOSTS = int(os.environ.get("BENCH_HOSTS", "32768"))
ROUNDS = int(os.environ.get("BENCH_ROUNDS", "192"))
N_NODES = int(os.environ.get("BENCH_NODES", "64"))  # graph nodes (GML-like)
# BENCH_TELEMETRY=1 threads the PlaneMetrics counters through every
# window and harvests heartbeat JSONL + a Perfetto trace into
# BENCH_TELEMETRY_DIR every BENCH_HARVEST_EVERY windows
# (docs/observability.md; the acceptance bar is throughput within 5%
# of the metrics-off path)
TELEMETRY = os.environ.get("BENCH_TELEMETRY", "0") == "1"
# BENCH_HIST=1 (telemetry mode only) additionally threads the
# log2-bucketed latency/depth histograms (telemetry/histo.py) through
# the scan carry: heartbeats gain per-host `hist` bucket vectors and
# the JSON records fleet latency percentiles
# (docs/observability.md "Distributions and the flight recorder")
HIST = os.environ.get("BENCH_HIST", "0") == "1"
# BENCH_FAULTS=1 threads NEUTRAL FaultArrays masks through every window
# (docs/robustness.md): the chaos-smoke CI job compares this against the
# faults-off run — the fault plane's presence switch must stay within 5%
# when nothing fails (same bar as telemetry). Neutral masks are
# bitwise-identity, so the measured delta is pure mask arithmetic.
FAULTS = os.environ.get("BENCH_FAULTS", "0") == "1"
TELEMETRY_DIR = os.environ.get("BENCH_TELEMETRY_DIR", "telemetry-bench")
HARVEST_EVERY = int(os.environ.get("BENCH_HARVEST_EVERY", "32"))
EGRESS_CAP = int(os.environ.get("BENCH_EGRESS_CAP", "16"))
INGRESS_CAP = int(os.environ.get("BENCH_INGRESS_CAP", "32"))
# BENCH_CAPACITY=elastic|strict drives the capacity policy plane
# (docs/robustness.md "Elastic capacity"): the run proceeds in chunks of
# BENCH_GROW_EVERY windows; a chunk with ring-full overflow is DISCARDED,
# the offending ring doubles (next power of two, bounded by
# BENCH_MAX_DOUBLINGS), and the chunk re-executes from its start
# snapshot — so a run started with tiny rings ends bitwise-identical to
# one pre-provisioned at the final capacity. strict raises CapacityError
# on the first overflow instead. The JSON records the trajectory.
CAPACITY_MODE = os.environ.get("BENCH_CAPACITY", "fixed")
MAX_DOUBLINGS = int(os.environ.get("BENCH_MAX_DOUBLINGS", "4"))
GROW_EVERY = int(os.environ.get("BENCH_GROW_EVERY", "16"))
# BENCH_WORLDS=W (0=off) appends an ensemble rep after the solo run:
# the SAME PHOLD chain vmapped over W worlds with per-world fold_in
# keys (tpu/elastic.drive_ensemble — the SL701/SL702-proven driver,
# docs/determinism.md "Worlds are theorems"), one host sync per chain
# for the whole ensemble. The JSON gains a `worlds` record with the
# summed events/s and the amortization ratio vs W sequential solo
# runs (docs/performance.md "Ensemble amortization"). Fixed capacity, no telemetry — per-world ring growth would diverge
# array shapes across the batch, so the modes are exclusive by
# construction.
N_WORLDS = int(os.environ.get("BENCH_WORLDS", "0"))
# BENCH_MEMO=1 appends a steady-state memoization rep (tpu/memo.py,
# docs/performance.md "Steady-state memoization"): a ring-allreduce
# scenario whose window budget runs far past collective completion,
# timed cold vs memoized in the same process (shared jit cache, both
# pre-warmed, fresh memo table per run). The JSON gains a `memo`
# record with both wall times, the EFFECTIVE events/s of each (same
# event total, so the ratio is pure fast-forward win), the cache
# stats, and the canonical-digest parity bit — a memo speedup with
# parity=false is a bug report, not a result. Sized by
# BENCH_MEMO_HOSTS/BENCH_MEMO_WINDOWS/BENCH_MEMO_CHAIN so CI smokes
# can run a small twin of the recorded number.
MEMO = os.environ.get("BENCH_MEMO", "0") == "1"
MEMO_HOSTS = int(os.environ.get("BENCH_MEMO_HOSTS", "16"))
MEMO_WINDOWS = int(os.environ.get("BENCH_MEMO_WINDOWS", "4096"))
MEMO_CHAIN = int(os.environ.get("BENCH_MEMO_CHAIN", "64"))
# BENCH_TRACE=PATH writes the shadowscope run ledger (telemetry/
# tracer.RunTracer JSONL, docs/observability.md "Run ledger") for the
# TIMED solo run; a BENCH_WORLDS rep appends its own ensemble ledger
# next to it at PATH.worlds.jsonl. The tracer only samples the clock
# at the chain-boundary host syncs the driver already takes, so the
# measured rate is the same rate CI gates untraced (the <=1.05x
# traced-overhead gate pins that claim).
TRACE_PATH = os.environ.get("BENCH_TRACE", "")
SPAWN_PER_DELIVERY = 1


def phold_chain(world: dict, *, faults=None, donate: bool = True):
    """The bench's PHOLD window chain over `world`
    (`profiling.build_world`): one compiled `lax.scan` of
    `window_step` + on-device respawn over the round ids, returning
    `chain(state, spawn_seq, metrics, hist, round_ids) -> (state,
    spawn_seq, metrics, hist, eg_overflow, in_overflow, delivered)`.
    bench.py and chip_smoke.py both drive it."""
    import jax
    import jax.numpy as jnp

    from shadow_tpu.tpu import (donating_jit, ingest_rows, unpack_planes,
                                window_step)
    from shadow_tpu.workloads.phold import respawn_batch

    params, key, window = world["params"], world["rng_root"], world["window"]
    N = world["state"].eg_dst.shape[0]

    # ONE window body for every mode (fixed / telemetry / elastic): the
    # presence planes ride the scan carry, the per-ring overflow deltas
    # the capacity policy reads accumulate alongside (idle cost gated in
    # CI as window_step_elastic), and the whole thing is driven in
    # device-resident chains by the SHARED driver loop
    # (`shadow_tpu.tpu.elastic.drive_chained_windows`) — the same loop
    # tools/chaos_smoke.py and the scenario corpus runner use, so every
    # kernel fusion lands in all three at once.
    def round_fn(carry, round_idx):
        state, spawn_seq, metrics, hist, eg_acc, in_acc = carry
        state0 = state
        shift = jnp.where(round_idx == 0, jnp.int32(0), window)
        out = window_step(state, params, key, shift, window,
                          rr_enabled=False, faults=faults,
                          metrics=metrics, hist=hist)
        ((state, delivered, _next_ev), metrics, _g, hist,
         _fr) = unpack_planes(out, metrics=metrics, hist=hist)
        # ingress-ring overflow (the routing stage's drops) — the
        # elastic capacity driver reads this back per chain
        in_acc = in_acc + (state.n_overflow_dropped
                           - state0.n_overflow_dropped)
        state1 = state
        # respawn: each delivered packet triggers one new packet from
        # the receiving host to a hashed destination (deterministic).
        # The delivered arrays are already row-shaped (row =
        # receiving host), so the row-local ingest needs no flat
        # cross-host sort.
        mask, new_dst, nbytes, seq_vals, ctrl = respawn_batch(
            delivered, spawn_seq, round_idx, N,
            state.in_src.shape[1])
        out = ingest_rows(
            state, new_dst, nbytes,
            seq_vals,  # priority: reuse seq (FIFO-ish)
            seq_vals, ctrl,
            valid=mask,
            metrics=metrics,
            hist=hist,
        )
        (state,), metrics, _g, hist, _fr = unpack_planes(
            out, metrics=metrics, hist=hist, n_lead=1)
        # egress-ring overflow (the respawn append's drops)
        eg_acc = eg_acc + (state.n_overflow_dropped
                           - state1.n_overflow_dropped)
        spawn_seq = spawn_seq + mask.sum(axis=1, dtype=jnp.int32)
        carry = (state, spawn_seq, metrics, hist, eg_acc, in_acc)
        return carry, mask.sum(dtype=jnp.int32)

    # the state pytree is donated in fixed/telemetry mode: XLA reuses
    # the input buffers for the scan carry instead of materializing a
    # second copy of ~20 [N, C] arrays (donation contract: `state` /
    # `state2` are dead after the call). The elastic driver compiles
    # WITHOUT donation — the chain-start snapshot must stay valid so an
    # overflowing chain can be discarded and re-executed against grown
    # rings (jit retraces once per ring shape, log2-bounded by the
    # power-of-two growth).
    wrap = donating_jit if donate else jax.jit

    @wrap
    def chain(state, spawn_seq, metrics, hist, round_ids):
        zeros = jnp.zeros((N,), jnp.int32)
        carry, delivered_counts = jax.lax.scan(
            round_fn,
            (state, spawn_seq, metrics, hist, zeros, zeros),
            round_ids)
        state, spawn_seq, metrics, hist, eg, inn = carry
        return (state, spawn_seq, metrics, hist, eg, inn,
                delivered_counts.sum())
    return chain


def bench_tpu() -> tuple[float, int, dict | None, dict | None, dict]:
    import jax
    import jax.numpy as jnp

    from shadow_tpu.tpu import profiling, require_device

    require_device("bench")

    if CAPACITY_MODE not in ("fixed", "strict", "elastic"):
        raise SystemExit(
            f"BENCH_CAPACITY={CAPACITY_MODE!r}: expected "
            f"fixed|strict|elastic")
    N, M = N_HOSTS, N_NODES
    # ONE definition of the PHOLD world, shared with the per-section
    # profiler (tpu/profiling.build_world): node-level path tables, 4 seed
    # packets per host — so profiler section times correspond to this
    # bench's end-to-end line by construction
    world = profiling.build_world(N, n_nodes=M, egress_cap=EGRESS_CAP,
                                  ingress_cap=INGRESS_CAP, seed=0,
                                  warmup_windows=0)
    state, params = world["state"], world["params"]
    key = world["rng_root"]
    CI = INGRESS_CAP
    window = world["window"]

    # neutral fault masks when BENCH_FAULTS=1 (bitwise-identity; the
    # measured delta is the presence-switch cost, docs/robustness.md)
    _faults = None
    if FAULTS:
        from shadow_tpu.faults import neutral_faults

        _faults = neutral_faults(N, M)

    chain_call = phold_chain(world, faults=_faults,
                             donate=CAPACITY_MODE == "fixed")
    capacity_info: dict | None = None
    if CAPACITY_MODE != "fixed" and TELEMETRY:
        raise SystemExit(
            "BENCH_CAPACITY=elastic/strict and BENCH_TELEMETRY=1 "
            "are mutually exclusive (each owns the chain cadence); "
            "run them separately")
    # windows per host sync: the whole run in fixed mode, the harvest
    # cadence under telemetry, the growth-snapshot cadence under the
    # capacity policy (recorded in the JSON `driver` field)
    CHAIN_LEN = (HARVEST_EVERY if TELEMETRY
                 else GROW_EVERY if CAPACITY_MODE != "fixed" else ROUNDS)

    def run_driver(state, harvester=None, collect=None, tracer=None):
        nonlocal capacity_info
        from shadow_tpu.telemetry import make_histograms, make_metrics
        from shadow_tpu.tpu import elastic

        policy = None
        if CAPACITY_MODE != "fixed":
            policy = elastic.RingPolicy(
                mode=CAPACITY_MODE, max_doublings=MAX_DOUBLINGS,
                egress_cap=EGRESS_CAP, ingress_cap=INGRESS_CAP,
                plane="bench")
        spawn_seq = jnp.full((N,), 10_000, jnp.int32)
        metrics = make_metrics(N) if TELEMETRY else None
        hist = make_histograms(N) if (TELEMETRY and HIST) else None

        def chain_fn(state, extras, rids, _pr):
            spawn_seq, metrics, hist, total = extras
            state, spawn_seq, metrics, hist, eg, inn, nd = chain_call(
                state, spawn_seq, metrics, hist, rids)
            return state, (spawn_seq, metrics, hist, total + nd), eg, inn

        def on_chain(r1, state, extras):
            if harvester is not None:
                if tracer is not None:
                    tracer.annotate("harvest", r=int(r1),
                                    time_ns=int(r1) * int(window))
                _sp, metrics, hist, _t = extras
                device = (dict(metrics._asdict(), **hist._asdict())
                          if hist is not None else metrics)
                harvester.tick(r1 * int(window), device=device)

        state, extras = elastic.drive_chained_windows(
            state, (spawn_seq, metrics, hist, jnp.int32(0)), chain_fn,
            n_rounds=ROUNDS, chain_len=CHAIN_LEN, policy=policy,
            window_ns=int(window),
            on_chain=on_chain if harvester is not None else None,
            tracer=tracer)
        _spawn_seq, metrics, hist, total = extras
        if collect is not None and hist is not None:
            collect["hist"] = hist
        if policy is not None:
            capacity_info = policy.trajectory.as_dict()
            capacity_info["initial"] = {"egress_cap": EGRESS_CAP,
                                        "ingress_cap": INGRESS_CAP}
            capacity_info["final"] = {"egress_cap": policy.egress_cap,
                                      "ingress_cap": policy.ingress_cap}
        return state, total

    driver = run_driver

    # compile
    t0 = time.monotonic()
    state_out, ndel = driver(state)
    jax.block_until_ready(state_out)
    compile_and_first = time.monotonic() - t0

    # timed run (fresh state, compiled): rebuild the identical world —
    # the first state was donated into the compile run
    state2 = profiling.build_world(N, n_nodes=M, egress_cap=EGRESS_CAP,
                                   ingress_cap=INGRESS_CAP, seed=0,
                                   warmup_windows=0)["state"]
    jax.block_until_ready(state2)
    tracer = None
    if TRACE_PATH:
        from shadow_tpu.telemetry import RunTracer

        # the ledger covers the TIMED run only — the compile run's
        # wall time is already reported as compile_and_first
        tracer = RunTracer(
            "bench", backend=backend_fingerprint(),
            meta={"hosts": N, "rounds": ROUNDS, "chain_len": CHAIN_LEN,
                  "capacity": CAPACITY_MODE,
                  "telemetry": TELEMETRY})
    telemetry_info = None
    if TELEMETRY:
        from shadow_tpu.telemetry import TelemetryHarvester

        os.makedirs(TELEMETRY_DIR, exist_ok=True)
        sink = os.path.join(TELEMETRY_DIR, "heartbeats.jsonl")
        harvester = TelemetryHarvester(
            interval_ns=HARVEST_EVERY * int(window), sink=sink,
            slot_capacity=N * (EGRESS_CAP + INGRESS_CAP))
        collect: dict = {}
        t0 = time.monotonic()
        state_out, ndel = driver(state2, harvester, collect, tracer)
        ndel = int(ndel)
        jax.block_until_ready(state_out)
        wall = time.monotonic() - t0
        # harvest bookkeeping happens OUTSIDE the timed loop's budget
        # claims but inside the wall measurement above — the 5% bar is
        # end-to-end, including the async pulls
        harvester.finalize()
        from shadow_tpu.telemetry import export

        trace = export.write_perfetto_trace(
            harvester.heartbeats,
            os.path.join(TELEMETRY_DIR, "trace.json"))
        telemetry_info = {
            "heartbeats": harvester.emitted,
            "harvests": harvester.harvests,
            "sink": sink,
            "trace": trace["path"],
            "trace_events": trace["events"],
        }
        if "hist" in collect:
            from shadow_tpu.telemetry.histo import (HIST_PREFIX,
                                                    percentiles)

            h = jax.device_get(collect["hist"])
            telemetry_info["latency"] = {
                name[len(HIST_PREFIX):]: percentiles(
                    np.asarray(arr, np.int64).sum(axis=0))
                for name, arr in h._asdict().items()}
    else:
        t0 = time.monotonic()
        state_out, ndel = driver(state2, tracer=tracer)
        ndel = int(ndel)
        jax.block_until_ready(state_out)
        wall = time.monotonic() - t0
    if tracer is not None:
        tracer.close(wall_s=round(wall, 6))
        tracer.write(TRACE_PATH)

    sent = int(np.asarray(state_out.n_sent).sum())
    events = ndel + sent  # send + deliver events, like Shadow's event count
    # the chained-driver amortization record (docs/performance.md "The
    # driver loop"): how many windows execute device-resident per host
    # sync — the satellite metric next to the headline events/s
    from shadow_tpu.tpu.elastic import chain_spans

    n_chains = len(chain_spans(ROUNDS, CHAIN_LEN))
    driver_info = {
        "loop": "drive_chained_windows",
        "chain_len": CHAIN_LEN,
        "chains": n_chains,
        "windows_per_sync": round(ROUNDS / max(n_chains, 1), 2),
    }
    return events / wall, events, telemetry_info, capacity_info, \
        driver_info


def bench_tpu_worlds(solo_rate: float) -> dict:
    """The BENCH_WORLDS ensemble rep: the PHOLD chain vmapped over
    N_WORLDS worlds via `drive_ensemble`, per-world keys from the
    proven `world_keys` fold chain, one compiled batched program per
    chain. Returns the `worlds` JSON record — summed delivered+sent
    events/s across the ensemble and the amortization ratio vs
    running the same W worlds as sequential solo runs (approximated
    by W x the solo run's measured rate on this container)."""
    import jax
    import jax.numpy as jnp

    from shadow_tpu.tpu import (ingest_rows, profiling, unpack_planes,
                                window_step)
    from shadow_tpu.tpu import elastic
    from shadow_tpu.workloads.phold import respawn_batch

    W, N, M = N_WORLDS, N_HOSTS, N_NODES
    world = profiling.build_world(N, n_nodes=M, egress_cap=EGRESS_CAP,
                                  ingress_cap=INGRESS_CAP, seed=0,
                                  warmup_windows=0)
    state, params = world["state"], world["params"]
    window = world["window"]
    keys = elastic.world_keys(world["rng_root"],
                              jnp.arange(W, dtype=jnp.int32))
    chain_len = min(GROW_EVERY, ROUNDS) if CAPACITY_MODE != "fixed" \
        else ROUNDS

    def chain_fn(state, extras, rids, _pr):
        key, spawn_seq, total = extras

        def round_fn(carry, round_idx):
            state, spawn_seq = carry
            shift = jnp.where(round_idx == 0, jnp.int32(0), window)
            out = window_step(state, params, key, shift, window,
                              rr_enabled=False)
            (state, delivered, _nx), _m, _g, _h, _fr = \
                unpack_planes(out)
            mask, new_dst, nbytes, seq_vals, ctrl = respawn_batch(
                delivered, spawn_seq, round_idx, N,
                state.in_src.shape[1])
            out = ingest_rows(state, new_dst, nbytes, seq_vals,
                              seq_vals, ctrl, valid=mask)
            (state,), _m, _g, _h, _fr = unpack_planes(out, n_lead=1)
            spawn_seq = spawn_seq + mask.sum(axis=1, dtype=jnp.int32)
            return (state, spawn_seq), mask.sum(dtype=jnp.int32)

        (state, spawn_seq), nd = jax.lax.scan(
            round_fn, (state, spawn_seq), rids)
        zeros = jnp.zeros((N,), jnp.int32)
        return state, (key, spawn_seq, total + nd.sum()), zeros, zeros

    def stacked(tree):
        return jax.tree.map(lambda x: jnp.stack([x] * W), tree)

    def run(states, tracer=None):
        extras = (keys, stacked(jnp.full((N,), 10_000, jnp.int32)),
                  jnp.zeros((W,), jnp.int32))
        states, extras = elastic.drive_ensemble(
            states, extras, chain_fn, n_rounds=ROUNDS,
            chain_len=chain_len, tracer=tracer)
        return states, extras[2]

    # compile run, then the timed run on a fresh replicated state
    states_out, totals = run(stacked(state))
    jax.block_until_ready(states_out)
    state2 = profiling.build_world(
        N, n_nodes=M, egress_cap=EGRESS_CAP, ingress_cap=INGRESS_CAP,
        seed=0, warmup_windows=0)["state"]
    states2 = stacked(state2)
    jax.block_until_ready(states2)
    tracer = None
    if TRACE_PATH:
        from shadow_tpu.telemetry import RunTracer

        tracer = RunTracer(
            "bench-worlds", backend=backend_fingerprint(),
            meta={"worlds": W, "hosts": N, "rounds": ROUNDS,
                  "chain_len": chain_len})
    t0 = time.monotonic()
    states_out, totals = run(states2, tracer)
    totals = np.asarray(jax.device_get(totals), np.int64)
    jax.block_until_ready(states_out)
    wall = time.monotonic() - t0
    if tracer is not None:
        tracer.close(wall_s=round(wall, 6))
        tracer.write(TRACE_PATH + ".worlds.jsonl")

    sent = np.asarray(jax.device_get(states_out.n_sent),
                      np.int64).sum(axis=tuple(range(
                          1, states_out.n_sent.ndim)))
    per_world_events = (totals + sent).tolist()
    events = int(sum(per_world_events))
    rate = events / wall
    return {
        "n_worlds": W,
        "driver": "drive_ensemble",
        "chain_len": chain_len,
        "events": events,
        "min_world_events": int(min(per_world_events)),
        "events_per_sec_sum": round(rate, 1),
        # summed ensemble throughput vs W sequential solo runs (which
        # deliver solo_rate in aggregate): >1 means the world axis
        # amortizes dispatch + compilation across the ensemble
        "amortization_vs_solo": (round(rate / solo_rate, 2)
                                 if solo_rate > 0 else None),
    }


def bench_memo() -> dict:
    """The BENCH_MEMO rep: the same compiled chain driven cold vs
    memoized through `drive_chained_windows`.

    The workload is a MEMO_HOSTS-host ring allreduce (the corpus
    family, real workload plane) driven for MEMO_WINDOWS windows —
    the collective completes early and the drained steady-state tail
    dominates, exactly the traffic shape arxiv 2602.10615 targets.
    Both runs share ONE jitted chain at the SAME span length
    (MEMO_CHAIN) and run after a warm-up pass, so the timed delta is
    execution vs replay — not compilation, not dispatch-pattern
    skew. The memo table is rebuilt from scratch inside the timed
    memoized run: key digests and recording cost are IN the
    measurement, replay hits pay for them."""
    import jax
    import jax.numpy as jnp

    from shadow_tpu.telemetry import make_metrics
    from shadow_tpu.tpu import elastic, unpack_planes, window_step
    from shadow_tpu.workloads import device as wdevice
    from shadow_tpu.workloads import runner as wrunner
    from shadow_tpu.workloads.compile import compile_program
    from shadow_tpu.workloads.spec import parse_scenario

    spec = parse_scenario({
        "name": f"memo-bench-ring-{MEMO_HOSTS}",
        "family": "ring_allreduce",
        "seed": 7,
        "hosts": MEMO_HOSTS,
        "windows": MEMO_WINDOWS,
        "patterns": [{"kind": "ring_allreduce", "first": 0,
                      "count": MEMO_HOSTS, "bytes": 4096,
                      "rounds": 1}],
    })
    prog = compile_program(spec)
    state0, params = wrunner.build_scenario_world(spec)
    wl = wdevice.to_device(prog)
    ws0 = wdevice.make_workload_state(prog)
    metrics0 = make_metrics(spec.n_hosts)
    state0, ws0, metrics0 = wdevice.prime(wl, ws0, state0,
                                          metrics=metrics0)
    rng_root = jax.random.key(spec.seed)
    window = jnp.int32(spec.window_ns)

    def round_fn(carry, ridx):
        state, ws, metrics = carry
        shift = jnp.where(ridx == 0, jnp.int32(0), window)
        out = window_step(state, params, rng_root, shift, window,
                          rr_enabled=False, metrics=metrics)
        (state, delivered, _nx), metrics, _g, _h, _fr = \
            unpack_planes(out, metrics=metrics)
        state, ws, metrics = wdevice.workload_step(
            wl, ws, state, delivered, ridx, window,
            max_advance=wdevice.MAX_ADVANCE, metrics=metrics)
        return (state, ws, metrics), None

    @jax.jit
    def chain(state, ws, metrics, rids):
        carry, _ = jax.lax.scan(round_fn, (state, ws, metrics), rids)
        return carry

    def chain_fn(state, extras, rids, _pr):
        ws, metrics = extras[0], extras[1]
        state, ws, metrics = chain(state, ws, metrics, rids)
        # runner-shaped extras (6 slots) so the runner's memo
        # key_extra indexes the workload/flow planes the same way
        return state, (ws, metrics, None, None, None, None), 0, 0

    def drive(memo_obj):
        state, extras = elastic.drive_chained_windows(
            state0, (ws0, metrics0, None, None, None, None), chain_fn,
            n_rounds=spec.windows, chain_len=MEMO_CHAIN,
            window_ns=spec.window_ns, memo=memo_obj)
        jax.block_until_ready(state)
        return state, extras

    def fresh_memo():
        memo_obj, _salt, _cl = wrunner._build_memo(
            {"chain_len": MEMO_CHAIN}, spec=spec, prog=prog,
            schedule=None, mesh_devices=None,
            adv=wdevice.MAX_ADVANCE, emit_cap=0, recv_wnd=0,
            guards=False, histograms=False, sample_every=None,
            trace_ring=0)
        return memo_obj

    drive(None)  # warm-up: compiles the one shared chain trace
    t0 = time.monotonic()
    state_c, extras_c = drive(None)
    cold_s = time.monotonic() - t0
    memo_obj = fresh_memo()
    t0 = time.monotonic()
    state_m, extras_m = drive(memo_obj)
    memo_s = time.monotonic() - t0

    events = int(np.asarray(jax.device_get(extras_c[1].events)))
    parity = (wrunner.digest_pytrees(
        elastic.canonical_state(state_c), extras_c[0])
        == wrunner.digest_pytrees(
            elastic.canonical_state(state_m), extras_m[0]))
    return {
        "scenario": spec.name,
        "hosts": MEMO_HOSTS,
        "windows": MEMO_WINDOWS,
        "chain_len": MEMO_CHAIN,
        "events": events,
        "cold_s": round(cold_s, 3),
        "memo_s": round(memo_s, 3),
        "effective_evps_cold": round(events / cold_s, 1),
        "effective_evps_memo": round(events / memo_s, 1),
        # same event total on both sides, so this IS the effective
        # ev/s multiplier on steady-state traffic
        "speedup": round(cold_s / memo_s, 2),
        "digest_parity": parity,
        "memo": memo_obj.stats(),
    }


def bench_cpu_baseline() -> float:
    """PHOLD on the object plane (Host/EventQueue/Worker path)."""
    from shadow_tpu.core.config import load_config_str
    from shadow_tpu.core.event import TaskRef
    from shadow_tpu.core.manager import Manager
    from shadow_tpu.net.packet import Packet, Protocol

    n_hosts = 64
    hosts_yaml = "\n".join(
        f"  peer{i}:\n    network_node_id: 0" for i in range(n_hosts)
    )
    cfg = load_config_str(
        f"general:\n  stop_time: 2s\n  seed: 1\n"
        f"network:\n  graph:\n    type: 1_gbit_switch\nhosts:\n{hosts_yaml}"
    )
    mgr = Manager(cfg)
    peer_ips = [h.ip for h in mgr.hosts]
    events = [0]

    class App:
        PORT = 9000

        def __init__(self, host):
            self.host = host
            self.outq = []
            host.netns.associate(self, Protocol.UDP, "0.0.0.0", self.PORT)

        def pull_out_packet(self):
            return self.outq.pop(0) if self.outq else None

        def peek_next_priority(self):
            return self.outq[0].priority if self.outq else None

        def push_in_packet(self, packet):
            events[0] += 1
            self.send_one()

        def send_one(self):
            events[0] += 1
            dst = peer_ips[self.host.rng.randrange(0, len(peer_ips))]
            self.outq.append(
                Packet(Protocol.UDP, (self.host.ip, self.PORT), (dst, self.PORT),
                       b"x" * 1400, priority=self.host.get_next_packet_priority())
            )
            self.host.notify_socket_has_packets(self.host.ip, self)

        def start(self, host):
            for _ in range(4):
                self.send_one()

    for host in mgr.hosts:
        app = App(host)
        host.add_application(MS, app.start)
    t0 = time.monotonic()
    mgr.run()
    wall = time.monotonic() - t0
    return events[0] / wall


def bench_compiled_baseline() -> float:
    """Compiled-Shadow-class per-event floor: build and run the ~120-line
    C++ PHOLD microbench (tools/phold_compiled.cc). Optimistic for the
    reference (no sockets/qdiscs/refcounting), so vs_compiled can only
    UNDERSTATE this rebuild. Returns events/s, or 0.0 when no g++."""
    import shutil
    import subprocess

    try:
        if shutil.which("g++") is None:
            return 0.0
        here = os.path.dirname(os.path.abspath(__file__))
        src = os.path.join(here, "tools", "phold_compiled.cc")
        # repo-local (self-owned) build target — never a fixed name in a
        # shared world-writable tempdir
        exe = os.path.join(here, "tools", ".phold_compiled")
        if not os.path.exists(exe) or \
                os.path.getmtime(exe) < os.path.getmtime(src):
            subprocess.run(["g++", "-O2", "-o", exe, src], check=True,
                           capture_output=True)
        out = subprocess.run([exe, "64", "64", "20"], check=True,
                             capture_output=True, text=True).stdout
        return float(json.loads(out)["events_per_sec"])
    except (OSError, subprocess.CalledProcessError, ValueError, KeyError):
        # auxiliary baseline: never let it eat the primary metric
        return 0.0


def backend_fingerprint() -> dict:
    """The container/backend identity a throughput number is only
    comparable within: JAX platform + device kind. PR 7's false
    regression — a CPU container measured against the accelerator-
    backed BENCH_r05 — is exactly the comparison this stamp makes
    impossible to repeat silently (both the `prior_round` guard below
    and `tools/compare_runs.py --bench` refuse to gate across
    mismatched fingerprints)."""
    import jax

    dev = jax.devices()[0]
    return {
        "platform": str(dev.platform),
        "device_kind": str(getattr(dev, "device_kind", dev.platform)),
    }


def _regression_guard(value: float, fingerprint: dict):
    """Compare against the newest recorded BENCH_r*.json (same shape
    only): a silent -7% crept through round 4 unbisected; now any drop
    past 20% is flagged in the output.

    A prior record whose backend fingerprint differs from this run's —
    or predates the stamp — is NOT comparable: the guard then warns
    loudly on stderr and reports `skipped_mismatched_backend` instead
    of a regression verdict (the PR-7 false-regression rule)."""
    import glob
    import re
    import sys

    best = None
    for path in glob.glob(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)", path)
        if not m:
            continue
        try:
            with open(path) as fh:
                rec = json.load(fh)
        except (OSError, ValueError):
            continue
        rec = rec.get("parsed", rec)  # driver wraps the JSON line
        if not rec or rec.get("hosts") != N_HOSTS:
            continue
        rnd = int(m.group(1))
        if best is None or rnd > best[0]:
            best = (rnd, rec.get("value"), rec.get("backend"))
    if best is None or not best[1] or float(best[1]) <= 0:
        return None
    best = (best[0], float(best[1]), best[2])
    prior_backend = best[2]
    if prior_backend != fingerprint:
        print(
            f"bench: WARNING: prior round BENCH_r{best[0]:02d} was "
            f"measured on backend {prior_backend} but this run is on "
            f"{fingerprint} — cross-container throughput ratios are "
            f"meaningless, so the prior_round regression gate is "
            f"SKIPPED. Re-measure both rounds on one container "
            f"(docs/performance.md).", file=sys.stderr)
        return {"vs_round": best[0],
                "skipped_mismatched_backend": True,
                "prior_backend": prior_backend,
                "regressed": False}
    ratio = value / best[1]
    return {"vs_round": best[0], "ratio": round(ratio, 3),
            "regressed": ratio < 0.8}


def main():
    (tpu_rate, events, telemetry_info, capacity_info,
     driver_info) = bench_tpu()
    worlds_info = bench_tpu_worlds(tpu_rate) if N_WORLDS > 0 else None
    memo_info = bench_memo() if MEMO else None
    cpu_rate = bench_cpu_baseline()
    compiled_rate = bench_compiled_baseline()
    fingerprint = backend_fingerprint()
    guard = _regression_guard(tpu_rate, fingerprint)
    print(
        json.dumps(
            {
                "metric": "packet_events_per_sec",
                "value": round(tpu_rate, 1),
                "unit": "events/s",
                "backend": fingerprint,
                "driver": driver_info,
                "telemetry": telemetry_info,
                "faults_threaded": FAULTS,
                "capacity": capacity_info,
                "worlds": worlds_info,
                "memo": memo_info,
                "vs_baseline": round(tpu_rate / cpu_rate, 2),
                "vs_compiled": (round(tpu_rate / compiled_rate, 3)
                                if compiled_rate else None),
                "compiled_events_per_sec": round(compiled_rate, 1),
                "hosts": N_HOSTS,
                "prior_round": guard,
                "baseline": (
                    "vs_baseline: this repo's Python object plane (64-host "
                    "PHOLD on the Host/EventQueue path). vs_compiled: the "
                    "in-tree C++ PHOLD microbench (tools/phold_compiled.cc) "
                    "pricing compiled-Shadow-class per-event cost on one "
                    "core — an optimistic floor for the reference, so the "
                    "ratio understates this rebuild; methodology in "
                    "BASELINE.md. See tools/bench_ladder.py for the "
                    "end-to-end rung measurements"
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
