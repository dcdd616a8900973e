#!/usr/bin/env python3
"""Chaos smoke: a fault-injected device-plane sim that survives a kill.

The CI end-to-end for the fault plane (docs/robustness.md): run the
PHOLD bench world with an ACTIVE fault schedule (host crash + reboot,
link degradation, corruption burst, iface flap) threaded through
`window_step(..., faults=)`, checkpointing every few windows; kill the
process mid-run; resume from the checkpoint and prove the final state
is BITWISE-identical to an uninterrupted run of the same seed.

Usage:
  python tools/chaos_smoke.py --hosts 256 --windows 48 \
      --checkpoint-dir chaos/ --checkpoint-every 8        # full run
  python tools/chaos_smoke.py ... --kill-at 20            # dies at w20
  python tools/chaos_smoke.py ... --resume chaos/ckpt-000000000016
                                                          # continues
Each invocation prints ONE JSON line with the final state digest,
drop-bucket totals, and fault bookkeeping; CI compares the digest of
the resumed run against the uninterrupted one.

`--guards warn|abort` threads the guard plane (`shadow_tpu/guards/`,
docs/robustness.md) through every window: the JSON gains a `guards`
summary, a clean fault-injected run must report zero violations, and
under `abort` any violation exits with the CLI guard code (5).
`--tamper-at W` deliberately corrupts the device state after window W
(a phantom ring slot) — the guards-catch-it proof CI runs.

`--capacity elastic` (with `--egress-cap/--ingress-cap/--max-doublings`)
drives the elastic capacity plane (docs/robustness.md "Elastic
capacity"): each window snapshots the pre-window state, and on
ring-full overflow the offending ring doubles and the window
RE-EXECUTES from the snapshot — the JSON gains the capacity trajectory,
`drops.ring_full` must be ZERO, and `canonical_digest` must equal a
run pre-provisioned at the final capacity (the CI proof). `--capacity
strict` exits with the CLI capacity code (6) on the first overflow.

`--telemetry DIR` (matching tools/run_scenarios.py) threads the
log2 latency/depth histograms and writes heartbeat JSONL +
`trace.json` into DIR every `--harvest-every` windows — fault-injected
runs emit the same observability surface as bench-driven ones.
`--sample-every K` additionally threads the flight recorder
(docs/observability.md "Distributions and the flight recorder"):
sampled per-packet hops land in DIR/hops.jsonl and as Perfetto flow
spans in the trace; the JSON gains `telemetry` (recorded hops,
ring-overwrite count, fleet latency percentiles). Histogram and
trace-ring state ride checkpoints, so a resumed run keeps its
distributions; under `--capacity elastic` a drain that reports
overwritten hops doubles the trace ring (bounded by --max-doublings).

`--memo` threads the steady-state memo plane (tpu/memo.py) through
the fault-injected driver — the SAFETY smoke, not a perf feature:
PHOLD respawn traffic is round-indexed, so every key folds the
absolute start round and a single run can never hit its own cache
(expect hits=0, misses=spans). What the run proves is the opt-out
discipline: every span key carries the fault schedule's
`span_fingerprint`, so a fault-injected span can only ever replay
against a recording whose masks AND in-span events match exactly —
and the final digest must equal the non-memo twin's byte-for-byte
(the CI assertion). Refused with --capacity elastic/strict (a hit
would skip the overflow readback the growth decision reads).

`--memo` composes with checkpoint/resume: the checkpoint's fault
masks are recomputed from the schedule position at the cut (sound on
the execute AND memo-replay paths), the recorded spans spill into the
checkpoint and are absorbed on restore, and a run killed mid-flight
and resumed must report the same final digest as the uninterrupted
memoized run — the resumed-memoized smoke CI gates on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MS = 1_000_000


def default_schedule(n_hosts: int, n_windows: int, window_ns: int):
    """The built-in chaos scenario, scaled to the run length: crash one
    host for the middle quarter, degrade a link 4x, corrupt another
    host's egress, flap a third's NIC. Compiled through the REAL
    `faults:` schedule path (config dataclass -> compile_schedule)."""
    from shadow_tpu.core.config import FaultsOptions
    from shadow_tpu.faults.schedule import compile_schedule

    w = lambda k: f"{max(1, k) * window_ns}ns"
    q = max(2, n_windows // 4)
    events = [
        {"at": w(q), "kind": "host_crash", "host": "h1"},
        {"at": w(2 * q), "kind": "host_reboot", "host": "h1"},
        {"at": w(q // 2), "kind": "link_degrade", "src_node": 0,
         "dst_node": 1, "latency_mult": 4, "duration": w(2 * q)},
        {"at": w(q), "kind": "corrupt_burst", "host": f"h{n_hosts - 1}",
         "p": 0.3, "duration": w(q)},
        {"at": w(2 * q), "kind": "iface_down", "host": "h2"},
        {"at": w(2 * q + q // 2), "kind": "iface_up", "host": "h2"},
        {"at": w(q), "kind": "host_degrade", "host": "h0",
         "bandwidth_div": 8, "duration": w(q)},
    ]
    opts = FaultsOptions(events=events)
    return compile_schedule(
        opts, host_names=[f"h{i}" for i in range(n_hosts)],
        n_nodes=64, seed=1234, stop_time_ns=(n_windows + 1) * window_ns)


def state_digest(*pytrees) -> str:
    # ONE digest definition for every bitwise-parity contract (the
    # golden scenario corpus shares it): shadow_tpu/workloads/runner.py
    from shadow_tpu.workloads.runner import digest_pytrees

    return digest_pytrees(*pytrees)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--hosts", type=int, default=256)
    ap.add_argument("--windows", type=int, default=48)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=8)
    ap.add_argument("--kill-at", type=int, default=None,
                    help="exit abruptly (no cleanup) after this window")
    ap.add_argument("--resume", default=None,
                    help="checkpoint directory to restore and continue")
    ap.add_argument("--no-faults", action="store_true",
                    help="neutral masks only (the overhead-gate twin)")
    ap.add_argument("--guards", choices=["off", "warn", "abort"],
                    default="off",
                    help="thread the runtime invariant plane through "
                         "every window (abort: violations exit 5)")
    ap.add_argument("--tamper-at", type=int, default=None,
                    help="corrupt the device state after this window "
                         "(a phantom ring slot) — guards must catch it")
    ap.add_argument("--capacity", choices=["fixed", "strict", "elastic"],
                    default="fixed",
                    help="ring-capacity policy (docs/robustness.md "
                         "'Elastic capacity'): elastic grows + "
                         "re-executes overflowing windows; strict exits "
                         "6 on the first ring-full drop")
    ap.add_argument("--egress-cap", type=int, default=16)
    ap.add_argument("--ingress-cap", type=int, default=32)
    ap.add_argument("--max-doublings", type=int, default=4)
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="write heartbeats.jsonl + trace.json (and "
                         "hops.jsonl with --sample-every) into DIR; "
                         "threads the latency/depth histograms")
    ap.add_argument("--harvest-every", type=int, default=8,
                    help="windows between telemetry harvests "
                         "(default 8)")
    ap.add_argument("--sample-every", type=int, default=None,
                    metavar="K",
                    help="thread the flight recorder: tag ~1/K packets "
                         "and trace their hops (requires --telemetry)")
    ap.add_argument("--trace-ring", type=int, default=2048,
                    help="flight-recorder trace-ring capacity "
                         "(default 2048)")
    ap.add_argument("--chain-len", type=int, default=8,
                    help="windows per device-resident chain (the "
                         "shared driver's host-sync cadence; harvest/"
                         "checkpoint/tamper/kill instants cut chains "
                         "regardless). MUST match across runs whose "
                         "digests are compared under --capacity "
                         "elastic: the chain is the growth-decision "
                         "unit (default 8)")
    ap.add_argument("--memo", action="store_true",
                    help="thread the steady-state memo plane "
                         "(tpu/memo.py) — the fault-plane safety "
                         "smoke: span keys fold the schedule "
                         "fingerprint (fault spans never replay "
                         "against different masks/events) and the "
                         "final digest must match a non-memo run")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="shadowscope run ledger: write the per-span "
                         "JSONL (wall split, span modes, capacity "
                         "growth, fault-span fingerprints, tamper/"
                         "harvest/checkpoint annotations) to PATH; "
                         "presence-invisible — digests are unchanged")
    args = ap.parse_args(argv)
    if args.sample_every is not None and not args.telemetry:
        ap.error("--sample-every requires --telemetry DIR (the hop "
                 "drain needs somewhere to land)")
    if args.memo and args.capacity != "fixed":
        ap.error("--memo requires --capacity fixed: a memo hit skips "
                 "the chain execution whose overflow readback the "
                 "capacity policy decides growth from")
    import jax
    import jax.numpy as jnp

    from shadow_tpu.faults import (load_plane_checkpoint, neutral_faults,
                                   save_plane_checkpoint)
    from shadow_tpu.guards import make_guards, summarize
    from shadow_tpu.guards.plane import GuardState
    from shadow_tpu.telemetry import make_metrics
    from shadow_tpu.tpu import elastic, ingest_rows, profiling
    from shadow_tpu.tpu.elastic import CapacityError
    from shadow_tpu.tpu.plane import unpack_planes, window_step
    from shadow_tpu.workloads.phold import respawn_batch

    EXIT_GUARD = 5  # shadow_tpu.cli.EXIT_GUARD (docs/robustness.md)
    EXIT_CAPACITY = 6  # shadow_tpu.cli.EXIT_CAPACITY

    N, R = args.hosts, args.windows
    tracer = None
    if args.trace:
        from shadow_tpu.telemetry import RunTracer

        tracer = RunTracer(
            "chaos_smoke",
            meta={"hosts": N, "windows": R, "capacity": args.capacity,
                  "chain_len": args.chain_len,
                  "faults": not args.no_faults,
                  "memo": bool(args.memo)})
    world = profiling.build_world(N, warmup_windows=0,
                                  egress_cap=args.egress_cap,
                                  ingress_cap=args.ingress_cap)
    window = world["window"]
    window_ns = int(window)
    schedule = (None if args.no_faults
                else default_schedule(N, R, window_ns))
    use_guards = args.guards != "off"
    policy = None
    if args.capacity != "fixed":
        policy = elastic.RingPolicy(
            mode=args.capacity, max_doublings=args.max_doublings,
            egress_cap=args.egress_cap, ingress_cap=args.ingress_cap,
            plane="chaos_smoke")

    def build_chain():
        def round_fn(carry, xs):
            (state, metrics, guards, hist, fr, spawn_seq, eg_acc,
             in_acc) = carry
            round_idx, faults = xs
            ci = state.in_src.shape[1]
            state0 = state
            shift = jnp.where(round_idx == 0, jnp.int32(0), window)
            out = window_step(state, world["params"], world["rng_root"],
                              shift, window, rr_enabled=False,
                              faults=faults, metrics=metrics,
                              guards=guards, hist=hist, flightrec=fr)
            (state, delivered, _next), metrics, guards, hist, fr = \
                unpack_planes(out, metrics=metrics, guards=guards,
                              hist=hist, flightrec=fr)
            # ingress-ring overflow: the routing stage's ring-full drops
            in_acc = in_acc + (state.n_overflow_dropped
                               - state0.n_overflow_dropped)
            state1 = state
            mask, dst, nbytes, seq, ctrl = respawn_batch(
                delivered, spawn_seq, round_idx, N, ci)
            # dead/flapped hosts generate no respawn traffic
            mask = mask & (faults.host_alive & faults.link_up)[:, None]
            out = ingest_rows(
                state, dst, nbytes, seq, seq, ctrl, valid=mask,
                metrics=metrics, guards=guards, hist=hist, flightrec=fr)
            (state,), metrics, guards, hist, fr = unpack_planes(
                out, metrics=metrics, guards=guards, hist=hist,
                flightrec=fr, n_lead=1)
            # egress-ring overflow: the respawn append's ring-full drops
            eg_acc = eg_acc + (state.n_overflow_dropped
                               - state1.n_overflow_dropped)
            return ((state, metrics, guards, hist, fr,
                     spawn_seq + mask.sum(axis=1, dtype=jnp.int32),
                     eg_acc, in_acc), None)

        @jax.jit
        def chain(state, metrics, guards, hist, fr, spawn_seq, rids,
                  faults_stack):
            # K windows device-resident per dispatch: the fault masks
            # ride as PER-ROUND scan inputs (so a schedule transition
            # mid-chain is bitwise-identical to the per-window loop it
            # replaced), every presence plane rides the carry, and the
            # per-ring overflow the capacity policy reads accumulates
            # alongside. Ring shapes come from the state itself
            # (trace-time), so elastic growth retraces this chain per
            # ring size — bounded at log2 by the power-of-two growth,
            # asserted in CI via the jit cache size (the PR-1 recompile
            # discipline).
            zeros = jnp.zeros((N,), jnp.int32)
            carry, _ = jax.lax.scan(
                round_fn,
                (state, metrics, guards, hist, fr, spawn_seq, zeros,
                 zeros),
                (rids, faults_stack))
            return carry
        return chain

    driver = build_chain()

    start_w = 0
    state = world["state"]
    metrics = make_metrics(N)
    guards = make_guards(N) if use_guards else None
    hist = fr = harvester = recorder = None
    if args.telemetry:
        from shadow_tpu.telemetry import (TelemetryHarvester,
                                          make_histograms)
        from shadow_tpu.telemetry import flightrec as frmod

        os.makedirs(args.telemetry, exist_ok=True)
        hist = make_histograms(N)
        harvester = TelemetryHarvester(
            interval_ns=args.harvest_every * window_ns,
            sink=os.path.join(args.telemetry, "heartbeats.jsonl"))
        if args.sample_every:
            # seeded like the fault schedule: the sampling mask is a
            # pure function of (seed, src, seq) — two identical runs
            # record byte-identical hop streams
            fr = frmod.make_flightrec(
                1234, sample_every=args.sample_every,
                ring=args.trace_ring)
            recorder = frmod.FlightRecorder(
                window_ns=window_ns,
                sink=os.path.join(args.telemetry, "hops.jsonl"))
    spawn_seq = jnp.full((N,), 10_000, jnp.int32)
    memo_obj = memo_salt_fn = None
    if args.memo:
        from shadow_tpu.tpu import memo as memomod

        # the static salt folds everything the chain closure captures
        # that the carry cannot show: world shape/caps (the params +
        # rng root are pure functions of them) and the respawn
        # constants
        memo_obj = memomod.ChainMemo(salt="|".join([
            "chaos-memo-v1", f"hosts={N}", f"egcap={args.egress_cap}",
            f"incap={args.ingress_cap}", f"faults={int(schedule is not None)}",
        ]).encode())  # default key_extra: folds r0 ALWAYS — respawn
        # traffic is round-indexed, so round translation is never safe

        if schedule is not None:
            def memo_salt_fn(r0, r1):
                # keep the schedule position current across hits
                # (per_round, which normally advances it, is skipped);
                # advancing to r0 is a no-op on the miss path
                schedule.advance(r0 * window_ns)
                return schedule.span_fingerprint(
                    r0 * window_ns, r1 * window_ns).encode()
        else:
            memo_salt_fn = lambda r0, r1: b"neutral"
    if args.resume:
        restored = load_plane_checkpoint(
            args.resume, state_template=state,
            faults_template=neutral_faults(N, 64),
            metrics_template=metrics)
        state = restored["state"]
        metrics = restored["metrics"]
        spawn_seq = jnp.asarray(restored["extra"]["spawn_seq"])
        if use_guards and "guards.violations" in restored["extra"]:
            guards = GuardState(**{
                f: jnp.asarray(restored["extra"][f"guards.{f}"])
                for f in GuardState._fields})
        if hist is not None and "hist.hist_qdepth" in restored["extra"]:
            # the distributions ride the checkpoint: a resumed run
            # reports the same histograms an uninterrupted one would
            from shadow_tpu.telemetry.histo import PlaneHistograms

            hist = PlaneHistograms(**{
                f: jnp.asarray(restored["extra"][f"hist.{f}"])
                for f in PlaneHistograms._fields})
        if fr is not None and "flightrec.cursor" in restored["extra"]:
            from shadow_tpu.telemetry.flightrec import FlightRecArrays

            fr = FlightRecArrays(**{
                f: jnp.asarray(restored["extra"][f"flightrec.{f}"])
                for f in FlightRecArrays._fields})
            # the prior run drained everything up to the checkpointed
            # cursor; the resumed recorder starts its window there
            recorder.seed_cursor(int(np.asarray(fr.cursor)))
        start_w = int(restored["meta"]["window_index"])
        if policy is not None and "capacity" in restored["meta"]:
            # the growth history rides the checkpoint: a resumed
            # elastic run continues from the grown capacity (the state
            # arrays already restored at their grown shapes) with the
            # same remaining growth budget, drop dedup, and trajectory
            policy.restore_meta(restored["meta"]["capacity"])
        got = state_digest(state, spawn_seq)
        want = restored["meta"].get("state_digest")
        if want and got != want:
            raise SystemExit(
                f"chaos_smoke: restored state digest {got[:12]} != "
                f"checkpointed {want[:12]} — restore is not faithful")
        if schedule is not None:
            # replay the schedule's mask state up to the restore point
            # (the schedule is a pure function of config — cheap)
            schedule.advance(start_w * window_ns)
        if memo_obj is not None and "memo" in restored["meta"]:
            # the recorded spans outlive the kill: absorb the spilled
            # cache so the resumed run reports the same memo census
            # (salt mismatch — a different world — is refused)
            n = memo_obj.absorb(restored["meta"]["memo"],
                                restored["extra"], prefix="memo.",
                                source=args.resume, restore=True)
            print(f"chaos_smoke: absorbed {n} memoized span(s)",
                  file=sys.stderr)
        print(f"chaos_smoke: resumed at window {start_w} from "
              f"{args.resume}", file=sys.stderr)

    checkpoints = []
    # the shared chained-window driver (the ONE loop bench.py and the
    # scenario corpus runner also use): K windows device-resident per
    # dispatch with the fault-mask stack riding as per-round scan
    # inputs; the host regains control only at chain ends — the
    # harvest/checkpoint cadences and the tamper/kill instants, which
    # register as explicit boundaries below
    last_faults = [neutral_faults(N, 64)]
    neutral_stacks: dict[int, object] = {}

    def per_round(r0, r1):
        if schedule is None:
            # schedule-less runs feed the SAME neutral masks to every
            # window: build one stack per span length, not per chain
            k = r1 - r0
            if k not in neutral_stacks:
                neutral_stacks[k] = jax.tree.map(
                    lambda x: jnp.stack([x] * k), last_faults[0])
            return neutral_stacks[k]
        stack = []
        for r in range(r0, r1):
            schedule.advance((r + 1) * window_ns)
            stack.append(schedule.device_arrays())
        last_faults[0] = stack[-1]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *stack)

    def chain_fn(state, extras, rids, faults_stack):
        metrics, guards, hist, fr, spawn_seq = extras
        (state, metrics, guards, hist, fr, spawn_seq, eg, inn) = driver(
            state, metrics, guards, hist, fr, spawn_seq, rids,
            faults_stack)
        return state, (metrics, guards, hist, fr, spawn_seq), eg, inn

    if tracer is not None and memo_salt_fn is None \
            and schedule is not None:
        # trace-only runs still stamp fault-span fingerprints on the
        # ledger; advance-to-r0 is a no-op mid-run (per_round already
        # moved the schedule there), so digests are untouched
        def memo_salt_fn(r0, r1):
            schedule.advance(r0 * window_ns)
            return schedule.span_fingerprint(
                r0 * window_ns, r1 * window_ns).encode()

    def on_chain(r1, state, extras):
        metrics, guards, hist, fr, spawn_seq = extras
        replaced = False
        if args.tamper_at is not None and r1 == args.tamper_at:
            # deliberate corruption: a phantom valid slot at the back
            # of one ingress ring (carrying the idle sentinel) — the
            # exact single-slot damage batched execution would hide
            print(f"chaos_smoke: tampering with the device state at "
                  f"window {r1}", file=sys.stderr)
            state = state._replace(
                in_valid=state.in_valid.at[
                    1, state.in_src.shape[1] - 1].set(True))
            replaced = True
            if tracer is not None:
                tracer.annotate("tamper", r=int(r1))
        if harvester is not None and r1 % args.harvest_every == 0:
            if tracer is not None:
                tracer.annotate("harvest", r=int(r1),
                                time_ns=int(r1) * window_ns)
            harvester.tick(r1 * window_ns,
                           device={**metrics._asdict(),
                                   **hist._asdict()})
            if recorder is not None:
                recorder.tick(fr)
                if args.capacity == "elastic" and recorder.want_growth():
                    # the trace ring participates in elastic growth:
                    # an overwriting drain doubles it (power of two,
                    # bounded like every ring by --max-doublings)
                    from shadow_tpu.telemetry import flightrec as frmod

                    cur = fr.ev_kind.shape[0]
                    cap_max = args.trace_ring << args.max_doublings
                    if cur < cap_max:
                        fr = frmod.grow_ring(fr, min(cur * 2, cap_max))
                        recorder.note_grown()
                        replaced = True
                        print(f"chaos_smoke: trace ring grown to "
                              f"{fr.ev_kind.shape[0]}", file=sys.stderr)
        if args.checkpoint_dir and args.checkpoint_every \
                and r1 % args.checkpoint_every == 0 and r1 < R:
            path = os.path.join(args.checkpoint_dir,
                                f"ckpt-{r1:012d}")
            extra = {"spawn_seq": spawn_seq}
            if use_guards:
                # the guard accumulator rides the checkpoint so a
                # resumed run reports the same violation history
                extra.update({f"guards.{f}": getattr(guards, f)
                              for f in GuardState._fields})
            if hist is not None:
                # distributions + trace ring ride checkpoints too: a
                # resumed run keeps its histograms and hop stream
                extra.update({f"hist.{f}": getattr(hist, f)
                              for f in hist._fields})
            if fr is not None:
                extra.update({f"flightrec.{f}": getattr(fr, f)
                              for f in fr._fields})
            meta = {"window_index": r1, "hosts": N,
                    "state_digest": state_digest(state, spawn_seq)}
            if hist is not None:
                from shadow_tpu.telemetry import flightrec as frmod

                meta["telemetry"] = {
                    "histograms": True,
                    "flight_recorder": (frmod.flightrec_meta(fr)
                                        if fr is not None else None),
                }
            if policy is not None:
                meta["capacity"] = policy.to_meta()
            if memo_obj is not None:
                # the cache rides the checkpoint: spill the recorded
                # spans alongside the plane arrays so a resumed run
                # absorbs them (ChainMemo.spill/absorb)
                memo_meta, memo_arrays = memo_obj.spill(prefix="memo.")
                meta["memo"] = memo_meta
                extra.update(memo_arrays)
            if schedule is not None:
                # recompute the masks AT the cut from the schedule —
                # last_faults is only maintained by per_round, which a
                # memo hit skips; advance() is a no-op on the execute
                # path (per_round already walked the cursor to r1)
                schedule.advance(r1 * window_ns)
                faults_now = schedule.device_arrays()
            else:
                faults_now = last_faults[0]
            save_plane_checkpoint(
                path, state=state, clock_ns=r1 * window_ns,
                rng_key_data=jax.random.key_data(world["rng_root"]),
                faults=faults_now, metrics=metrics,
                extra_arrays=extra, meta=meta)
            checkpoints.append(path)
            if tracer is not None:
                tracer.annotate("checkpoint", r=int(r1), path=path)
        if args.kill_at is not None and r1 >= args.kill_at:
            if tracer is not None:
                tracer.annotate("kill", r=int(r1))
            print(f"chaos_smoke: simulating a crash at window {r1}",
                  file=sys.stderr)
            sys.stderr.flush()
            os._exit(137)  # abrupt: no atexit, like a SIGKILL'd run
        if replaced:
            return state, (metrics, guards, hist, fr, spawn_seq)

    boundaries = set()
    if harvester is not None:
        boundaries.update(range(args.harvest_every, R,
                                args.harvest_every))
    if args.checkpoint_dir and args.checkpoint_every:
        boundaries.update(range(args.checkpoint_every, R,
                                args.checkpoint_every))
    if args.tamper_at is not None:
        boundaries.add(args.tamper_at)
    if args.kill_at is not None:
        boundaries.add(args.kill_at)
    try:
        state, extras = elastic.drive_chained_windows(
            state, (metrics, guards, hist, fr, spawn_seq), chain_fn,
            n_rounds=R, chain_len=args.chain_len, start_round=start_w,
            boundaries=boundaries, per_round=per_round, policy=policy,
            window_ns=window_ns,
            host_names=[f"h{i}" for i in range(N)],
            on_chain=on_chain,
            memo=memo_obj, memo_span_salt=memo_salt_fn,
            tracer=tracer)
    except CapacityError as e:
        print(f"chaos_smoke: capacity abort: {e}", file=sys.stderr)
        # the driver stamps the failing chain [r0, r1) on the error:
        # under chained execution overflow is observed per chain, so
        # the span is the precise blame unit (the offending window is
        # somewhere inside it)
        span = getattr(e, "chain_span", None)
        if tracer is not None:
            # the partial ledger is the abort postmortem: every span
            # that completed before the blamed chain is on it
            tracer.annotate("capacity-abort", error=str(e),
                            chain_span=list(span) if span else None)
            tracer.close()
            tracer.write(args.trace)
        print(json.dumps({
            "capacity_error": str(e),
            "mode": policy.mode,
            "window": span[0] if span else None,
            "chain_span": list(span) if span else None,
            "egress_cap": policy.egress_cap,
            "ingress_cap": policy.ingress_cap,
        }))
        return EXIT_CAPACITY
    metrics, guards, hist, fr, spawn_seq = extras

    jax.block_until_ready(state)
    telemetry_out = None
    if harvester is not None:
        from shadow_tpu.telemetry import export
        from shadow_tpu.telemetry.histo import HIST_PREFIX, percentiles

        if R % args.harvest_every != 0:
            # the loop's cadence did not harvest the final instant
            harvester.tick(R * window_ns,
                           device={**metrics._asdict(),
                                   **hist._asdict()})
            if recorder is not None:
                recorder.tick(fr)
        harvester.finalize()
        if recorder is not None:
            recorder.tick(fr)
            recorder.finalize()
        trace_path = os.path.join(args.telemetry, "trace.json")
        trace_info = export.write_perfetto_trace(
            harvester.heartbeats, trace_path,
            hops=recorder.hops if recorder is not None else None)
        h = jax.device_get(hist)
        telemetry_out = {
            "dir": args.telemetry,
            "heartbeats": harvester.emitted,
            "trace": trace_info,
            "latency": {
                name[len(HIST_PREFIX):]: percentiles(
                    np.asarray(arr, np.int64).sum(axis=0))
                for name, arr in h._asdict().items()},
        }
        if recorder is not None:
            telemetry_out["flight_recorder"] = recorder.summary()
            telemetry_out["trace_ring"] = int(fr.ev_kind.shape[0])
    m = jax.device_get(metrics)
    out = {
        "hosts": N,
        "windows": R,
        "resumed_from": args.resume,
        "faults_active": schedule is not None,
        "state_digest": state_digest(state, spawn_seq),
        # dead-lane payload differs between a mid-run-grown world and a
        # pre-provisioned one (each permuted its own history's
        # compaction garbage); the canonical digest normalizes those
        # don't-care lanes, so elastic-vs-pre-provisioned parity is
        # canonical_digest equality (docs/determinism.md "Growth is
        # bitwise-invisible")
        "canonical_digest": state_digest(
            elastic.canonical_state(state), spawn_seq),
        "egress_cap": int(state.eg_dst.shape[1]),
        "ingress_cap": int(state.in_src.shape[1]),
        "drops": {
            "ring_full": int(np.asarray(m.drop_ring_full).sum()),
            "qdisc": int(np.asarray(m.drop_qdisc).sum()),
            "loss": int(np.asarray(m.drop_loss).sum()),
            "fault": int(np.asarray(m.drop_fault).sum()),
        },
        "events": int(np.asarray(m.events)),
        "checkpoints": checkpoints,
    }
    if telemetry_out is not None:
        out["telemetry"] = telemetry_out
    if memo_obj is not None:
        out["memo"] = memo_obj.stats()
    if policy is not None:
        # the jit cache size of the step IS the compile count: one
        # entry per ring shape stepped, so elastic recompiles must stay
        # within 1 + growth events (the log2 bound CI gates on)
        cache_size = getattr(driver, "_cache_size", lambda: None)()
        out["capacity"] = {
            "mode": policy.mode,
            "initial": {"egress_cap": args.egress_cap,
                        "ingress_cap": args.ingress_cap},
            "final": {"egress_cap": policy.egress_cap,
                      "ingress_cap": policy.ingress_cap},
            "growth_events": len(policy.trajectory.growth_events()),
            "events": list(policy.trajectory.events),
            "step_recompiles": cache_size,
        }
    if use_guards:
        gsum = summarize(guards)
        out["guards"] = gsum
    if tracer is not None:
        if memo_obj is not None:
            tracer.memo_close(memo_obj)
        if use_guards:
            # the end-of-run guard pull rides the ledger: the delta
            # from a clean run is the per-class violation census
            tracer.annotate("guards", summary=out["guards"])
        tracer.close()
        tracer.write(args.trace)
        out["trace"] = args.trace
    if use_guards and not out["guards"]["clean"]:
        print("chaos_smoke: guard violations: "
              + json.dumps(out["guards"]["by_class"]), file=sys.stderr)
        if args.guards == "abort":
            print(json.dumps(out))
            return EXIT_GUARD
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
