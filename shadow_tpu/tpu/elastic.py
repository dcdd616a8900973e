"""Elastic ring growth for the device plane: the bitwise repack kernels.

The device half of the capacity policy plane (`core/capacity.py`,
docs/robustness.md "Elastic capacity"): pure, donation-friendly repack
functions that migrate a SoA world into larger power-of-two rings —
every live ring column, every I32_MAX/NO_CLAMP idle sentinel, every
counter moves bitwise; the new trailing columns carry exactly the
`make_state` defaults. Growth is therefore invisible to the step
kernels (docs/determinism.md "Growth is bitwise-invisible"):

- live lanes are front-packed, so they occupy the same columns before
  and after a grow;
- every sort in `window_step` is stable with invalid-last keys, so the
  extra all-invalid columns sort behind the live lanes and never
  change their order;
- every consumer masks by validity, so the dead-lane payload
  ("compaction garbage", `plane._routing_place`) can never feed back
  into live state.

The one thing growth does NOT preserve is that garbage itself: a run
grown mid-flight carries different dead-lane payload than a run
pre-provisioned at the final capacity (each permuted its own history's
garbage). `canonical_state` normalizes those don't-care lanes to the
`make_state` defaults so two such runs compare bitwise — the contract
the elastic parity matrix in tests/test_elastic.py pins is
``canonical_state(elastic) == canonical_state(pre-provisioned)`` plus
identical delivered streams, counters, RNG, and metrics.

`drive_chained_windows` is THE driver loop (bench.py,
tools/chaos_smoke.py, workloads/runner.py — pinned by the
inspect-source gate in tests/test_chain_driver.py): K consecutive
windows execute device-resident per dispatch, the host syncs only at
chain ends, and under the capacity policy the chain is the
growth-decision unit — `run_elastic_window` underneath attempts the
chain, reads the per-ring overflow it reported, and grows + re-executes
from the chain-start snapshot (`jax.jit` retraces per ring shape, so
recompiles are bounded at log2 by the power-of-two growth; the PR-1
recompile harness asserts it).

jax imports are lazy (function-local) so `core/` consumers of the
re-exported :class:`CapacityError` never pull the device stack.
"""

from __future__ import annotations

import numpy as np

from ..core.capacity import (CAPACITY_MODES, CapacityError,  # noqa: F401
                             CapacityTrajectory, RingPolicy, next_pow2)

__all__ = [
    "CAPACITY_MODES", "CapacityError", "CapacityTrajectory", "RingPolicy",
    "canonical_state", "chain_spans", "drive_chained_windows",
    "drive_ensemble", "grow_state", "grow_transport_state", "next_pow2",
    "ring_dims", "run_elastic_window", "world_key", "world_keys",
]


def ring_dims(state) -> tuple[int, int]:
    """(egress_cap, ingress_cap) of a `plane.NetPlaneState`."""
    return int(state.eg_dst.shape[1]), int(state.in_src.shape[1])


def _pad_cols(arr, width: int, fill):
    """Widen a [N, C] ring to [N, width] with `fill` in the new lanes."""
    import jax.numpy as jnp

    n, c = arr.shape
    if width == c:
        return arr
    block = jnp.full((n, width - c), fill, arr.dtype)
    return jnp.concatenate([arr, block], axis=1)


def grow_state(state, new_egress_cap: int, new_ingress_cap: int):
    """Repack a `plane.NetPlaneState` into larger rings, bitwise.

    Pure and donation-friendly (jnp concatenations only — wrap in
    `tpu.donating_jit` to repack in place on device). Every existing
    column migrates unchanged; new trailing lanes carry exactly the
    `make_state` defaults (-1 dst/src, I32_MAX priority/deliver
    sentinels, NO_CLAMP clamps, zeros elsewhere, invalid), so the next
    `window_step` sees a state indistinguishable from one that was
    front-packed at the larger capacity all along. Scalars, RR
    counters, router state, and the per-host counters pass through
    untouched. Shrinking is refused — dropping lanes could drop live
    packets, the exact silent divergence this plane exists to prevent.
    """
    from .plane import I32_MAX, NO_CLAMP

    ce, ci = ring_dims(state)
    if new_egress_cap < ce or new_ingress_cap < ci:
        raise ValueError(
            f"grow_state cannot shrink rings: have (CE={ce}, CI={ci}), "
            f"asked for (CE={new_egress_cap}, CI={new_ingress_cap})")
    if (new_egress_cap, new_ingress_cap) == (ce, ci):
        return state
    return state._replace(
        eg_dst=_pad_cols(state.eg_dst, new_egress_cap, -1),
        eg_bytes=_pad_cols(state.eg_bytes, new_egress_cap, 0),
        eg_prio=_pad_cols(state.eg_prio, new_egress_cap, I32_MAX),
        eg_seq=_pad_cols(state.eg_seq, new_egress_cap, 0),
        eg_ctrl=_pad_cols(state.eg_ctrl, new_egress_cap, False),
        eg_tsend=_pad_cols(state.eg_tsend, new_egress_cap, 0),
        eg_clamp=_pad_cols(state.eg_clamp, new_egress_cap, NO_CLAMP),
        eg_sock=_pad_cols(state.eg_sock, new_egress_cap, 0),
        eg_valid=_pad_cols(state.eg_valid, new_egress_cap, False),
        in_src=_pad_cols(state.in_src, new_ingress_cap, -1),
        in_bytes=_pad_cols(state.in_bytes, new_ingress_cap, 0),
        in_seq=_pad_cols(state.in_seq, new_ingress_cap, 0),
        in_sock=_pad_cols(state.in_sock, new_ingress_cap, 0),
        in_deliver_rel=_pad_cols(state.in_deliver_rel, new_ingress_cap,
                                 I32_MAX),
        in_valid=_pad_cols(state.in_valid, new_ingress_cap, False),
    )


def grow_transport_state(state, new_ingress_cap: int):
    """Repack a `transport.TransportState` into larger per-destination
    in-flight rings. Transport slots are sparse (never compacted) and
    the ingest kernel fills the LOWEST free columns first, so as long
    as no packet was ever overflow-dropped the grown state is bitwise
    identical — including dead-lane payload — to a run pre-provisioned
    at the larger capacity: lanes < CI carry the identical history,
    lanes >= CI carry the construction defaults in both."""
    ci = int(state.in_src.shape[1])
    if new_ingress_cap < ci:
        raise ValueError(
            f"grow_transport_state cannot shrink: have CI={ci}, asked "
            f"for {new_ingress_cap}")
    if new_ingress_cap == ci:
        return state
    I32_MAX = np.int32(2**31 - 1)
    return state._replace(
        in_src=_pad_cols(state.in_src, new_ingress_cap, 0),
        in_seq=_pad_cols(state.in_seq, new_ingress_cap, 0),
        in_tag=_pad_cols(state.in_tag, new_ingress_cap, 0),
        in_deliver=_pad_cols(state.in_deliver, new_ingress_cap, I32_MAX),
        in_valid=_pad_cols(state.in_valid, new_ingress_cap, False),
    )


def canonical_state(state):
    """Normalize a `NetPlaneState`'s dead lanes to `make_state`
    defaults, leaving live lanes and every scalar/counter untouched.

    Dead-lane payload is outside the determinism contract (every
    consumer masks by validity; the stable sorts only shuffle it), and
    it is the ONE thing a mid-run grow cannot reproduce bitwise — so
    the elastic-vs-pre-provisioned parity gate compares canonical
    states. Two runs whose canonical states AND delivered streams match
    are behaviorally identical forever after (live content determines
    every future output)."""
    import jax.numpy as jnp

    from .plane import I32_MAX, NO_CLAMP

    ev, iv = state.eg_valid, state.in_valid
    w = lambda mask, arr, fill: jnp.where(
        mask, arr, jnp.asarray(fill, dtype=arr.dtype))
    return state._replace(
        eg_dst=w(ev, state.eg_dst, -1),
        eg_bytes=w(ev, state.eg_bytes, 0),
        eg_prio=w(ev, state.eg_prio, I32_MAX),
        eg_seq=w(ev, state.eg_seq, 0),
        eg_ctrl=state.eg_ctrl & ev,
        eg_tsend=w(ev, state.eg_tsend, 0),
        eg_clamp=w(ev, state.eg_clamp, NO_CLAMP),
        eg_sock=w(ev, state.eg_sock, 0),
        in_src=w(iv, state.in_src, -1),
        in_bytes=w(iv, state.in_bytes, 0),
        in_seq=w(iv, state.in_seq, 0),
        in_sock=w(iv, state.in_sock, 0),
        in_deliver_rel=w(iv, state.in_deliver_rel, I32_MAX),
    )


def chain_spans(n_rounds: int, chain_len: int, *, start_round: int = 0,
                boundaries=()) -> list[tuple[int, int]]:
    """The driver's chain partition: [start_round, n_rounds) split at
    every ABSOLUTE `chain_len` multiple and at every explicit boundary
    round. Boundaries are where the host MUST regain control between
    windows — checkpoint instants, tamper/kill points — on top of the
    regular sync cadence. Empty spans collapse; spans are returned as
    [r0, r1) pairs.

    Cuts are aligned to round 0 (not to `start_round`) on purpose:
    under the elastic capacity policy the chain IS the growth-decision
    unit (one snapshot + one overflow read per span), so a run resumed
    from a checkpoint must partition the remaining rounds exactly like
    the uninterrupted run did or the two could grow different ring
    trajectories — the kill/resume bitwise-parity contract
    (docs/determinism.md "Chain length is bitwise-invisible" covers the
    state stream; the ABSOLUTE alignment covers the capacity
    trajectory). Chain lengths stay as regular as the boundary set
    allows, which is what bounds the per-length scan retraces (one
    compile per distinct span length)."""
    if chain_len < 1:
        raise ValueError(f"chain_len must be >= 1, got {chain_len}")
    if start_round >= n_rounds:
        # nothing left to run (a resume at or past the horizon) — the
        # unguarded cut set would invert into a phantom
        # (n_rounds, start_round) span and drive windows PAST the
        # requested end
        return []
    cuts = {start_round, n_rounds}
    first = ((start_round // chain_len) + 1) * chain_len
    cuts.update(range(first, n_rounds, chain_len))
    cuts.update(b for b in boundaries if start_round < b < n_rounds)
    edges = sorted(cuts)
    return [(a, b) for a, b in zip(edges[:-1], edges[1:]) if b > a]


def drive_chained_windows(state, extras, chain_fn, *, n_rounds: int,
                          chain_len: int, start_round: int = 0,
                          boundaries=(), per_round=None,
                          policy: RingPolicy | None = None,
                          window_ns: int = 0, host_names=None,
                          on_chain=None, memo=None, memo_span_salt=None,
                          tracer=None, checkpointer=None):
    """THE driver loop. bench.py, tools/chaos_smoke.py, and the
    scenario corpus runner (workloads/runner.py) all drive their
    windows through this one function (pinned by the inspect-source
    test in tests/test_chain_driver.py so the three loops cannot
    silently fork again): K consecutive windows execute device-resident
    per dispatch, and the host regains control only at chain ends —
    once per harvest/checkpoint/growth boundary instead of once per
    window.

    `chain_fn(state, extras, round_ids, per_round_slice)` is the
    caller's compiled chain — typically one `jax.lax.scan` of its
    window body (window_step + respawn/workload emission, with
    metrics/guards/hist/flight-recorder presence switches riding the
    carry exactly as they ride `plane.chain_windows`' while_loop) —
    and returns ``(state', extras', eg_overflow, in_overflow)`` with
    the per-ring overflow the capacity policy reads ([N] arrays or
    scalars; zeros when untracked). `round_ids` is the span's
    jnp.int32 round-index vector; `per_round_slice` is
    ``per_round(r0, r1)`` (None when per_round is None) — the hook
    time-varying per-window inputs (the fault schedule's mask stack)
    ride in on, as scan inputs rather than per-window host dispatches.

    Under ``policy`` (elastic/strict capacity, docs/robustness.md),
    every chain runs through :func:`run_elastic_window`: the snapshot
    the policy re-executes from is the CHAIN-start state — one
    snapshot per chain, not per window — and a chain that overflows is
    discarded and re-executed against grown rings, so the committed
    stream stays bitwise-identical to a pre-provisioned run. The
    caller's `chain_fn` must then be a pure non-donating function of
    its inputs.

    ``on_chain(r1, state, extras)`` fires after every committed chain
    (the host-sync point: harvester ticks, checkpoints, kill/tamper
    hooks); returning a (state, extras) pair replaces the carried
    values (how chaos_smoke's tamper writes corrupted device state),
    returning None keeps them. Returns the final ``(state, extras)``.

    The flow plane (docs/robustness.md "Flow plane") rides `extras`
    like every other non-NetPlaneState pytree: the scenario runner's
    chain carries its FlowState next to the workload/metrics/guards
    states, so under ``policy`` a discarded overflowing chain replays
    the flow machine from the chain-start snapshot too — retransmit
    schedules stay bitwise-reproducible through elastic growth.

    ``memo`` (a `tpu/memo.ChainMemo`, docs/performance.md
    "Steady-state memoization") makes the chain span the memo unit: at
    every span boundary the carry is snapshotted to host and keyed; a
    hit REPLAYS the recorded post-chain carry (keyed substitution +
    modular counter deltas, bitwise-equal to execution) instead of
    dispatching, and consecutive hits with no `on_chain` hook
    fast-forward entirely on host — no device round-trip at all. A
    miss executes normally (including under ``policy``) and records.
    ``memo_span_salt(r0, r1) -> bytes`` folds per-span external inputs
    into the key — the fault schedule's span fingerprint — and is
    REQUIRED whenever ``per_round`` is set: time-varying inputs the
    key cannot see would otherwise replay across non-equivalent spans,
    so that combination raises instead of guessing.

    ``tracer`` (a `telemetry/tracer.RunTracer`, docs/observability.md
    "Run ledger") records one ledger record per committed span AT the
    chain-boundary sync the loop already owns: the wall-time split
    (dispatch / memo bookkeeping / on_chain hook), the span mode
    (execute, or a memo `replay`/host-only `ffwd`), the capacity
    trajectory events the span committed, and the span-salt
    fingerprint when one exists. The tracer reads host wall clocks and
    values this loop already materialized — zero new device syncs
    (`costmodel.DRIVER_MODULES` re-proves that statically), and
    presence-invisible: tracer-on and tracer-off runs are
    digest-identical (the trace-parity CI gate).

    Profiler spans (docs/observability.md "Profiler spans and scopes"):
    each chain span runs inside a `chain.span` profiler span, and its
    phases inside `chain.memo` (snapshot, key, lookup, record),
    `chain.launch` (the chain dispatch, with the overflow read-back
    under ``policy``), `chain.upload`, `chain.hook` and
    `chain.checkpoint`, each with ``r0``/``r1`` as arguments
    (`telemetry/tracer.phase`). They cost nothing measurable when no
    `jax.profiler` trace runs. The ledger's `dispatch_ms` / `memo_ms` /
    `hook_ms` are the wall times of the same `chain.launch` /
    `chain.memo` / `chain.hook` phases.

    ``checkpointer`` (a `faults/runstate.RunCheckpointer`,
    docs/robustness.md "Resumable runs") spills the FULL carry to an
    atomic file at its own cadence: its checkpoint instants join the
    boundary set (extra cuts are bitwise-invisible — the chain-length
    theorem), and the save fires AFTER the span's on_chain hook so a
    resume replays nothing the hook already observed. On the memo
    fast-forward path the checkpoint is written straight from the host
    mirror — a crash-survivable run costs zero extra device syncs.
    A checkpointed run SIGKILLed at any boundary and resumed is
    byte-identical to its uninterrupted twin (the kill/resume CI
    gate).
    """
    import jax.numpy as jnp

    from ..telemetry.tracer import phase

    if memo is not None and per_round is not None and memo_span_salt is None:
        raise ValueError(
            "drive_chained_windows: memo with per_round inputs needs a "
            "memo_span_salt folding them into the key (e.g. the fault "
            "schedule's span_fingerprint) — refusing to memoize spans "
            "whose external inputs the key cannot see")
    if checkpointer is not None:
        boundaries = tuple(boundaries) + checkpointer.cut_rounds(n_rounds)

    host_carry = None  # memo's host mirror of (state, extras)
    stale = False      # device carry behind host_carry (hits pending)

    def _upload(r0, r1):
        nonlocal state, extras, stale
        with phase("chain.upload", r0=r0, r1=r1):
            state, extras = memo.to_device(host_carry)
        stale = False

    def _maybe_checkpoint(r0, r1):
        # fires at the span end, after on_chain: the carry saved is
        # exactly the carry the next span starts from. The memo host
        # mirror, when authoritative, is saved as-is (no device sync).
        if checkpointer is None or not checkpointer.due(r1, n_rounds):
            return
        carry = host_carry if host_carry is not None else (state, extras)
        with phase("chain.checkpoint", r0=r0, r1=r1):
            checkpointer.save(r1, carry, host=host_carry is not None,
                              tracer=tracer)

    for r0, r1 in chain_spans(n_rounds, chain_len,
                              start_round=start_round,
                              boundaries=boundaries):
        with phase("chain.span", tracer, r0=r0, r1=r1) as whole:
            salt_hex = None
            pre_walk = None
            salt = b""
            if memo_span_salt is not None \
                    and (memo is not None or tracer is not None):
                salt = memo_span_salt(r0, r1)
                if tracer is not None:
                    # the span's external-input fingerprint (the fault
                    # schedule's) — host bytes, hashed before the loop ran
                    salt_hex = salt.hex()
            memo_ms = 0.0
            if memo is not None:
                with phase("chain.memo", tracer, r0=r0, r1=r1) as pm:
                    if host_carry is None:
                        host_carry = memo.snapshot(state, extras)
                    key, pre_walk = memo.key(host_carry, r0, r1,
                                             span_salt=salt)
                    entry = memo.lookup(key)
                    if entry is not None:
                        host_carry = memo.replay(entry, host_carry)
                        stale = True
                memo_ms = pm.ms
                if entry is not None:
                    mode, hook_ms = "ffwd", 0.0
                    if on_chain is not None:
                        mode = "replay"
                        _upload(r0, r1)
                        with phase("chain.hook", tracer, r0=r0,
                                   r1=r1) as ph:
                            replaced = on_chain(r1, state, extras)
                        hook_ms = ph.ms
                        if replaced is not None:
                            state, extras = replaced
                            host_carry = None  # device is authoritative
                    if tracer is not None:
                        tracer.span(r0, r1, mode=mode, t0=whole.t0,
                                    memo_ms=memo_ms, hook_ms=hook_ms,
                                    span_salt=salt_hex)
                    _maybe_checkpoint(r0, r1)
                    continue
                if stale:
                    _upload(r0, r1)
            growth = None
            with phase("chain.launch", tracer, r0=r0, r1=r1) as pl:
                rids = jnp.arange(r0, r1, dtype=jnp.int32)
                pr = per_round(r0, r1) if per_round is not None else None
                if policy is None:
                    state, extras, _eg, _in = chain_fn(state, extras, rids,
                                                       pr)
                else:
                    def attempt(st, _ex=extras, _rids=rids, _pr=pr):
                        st2, ex2, eg, inn = chain_fn(st, _ex, _rids, _pr)
                        return (st2, ex2), eg, inn

                    n_events = len(policy.trajectory.events)
                    try:
                        out, _used = run_elastic_window(
                            state, attempt, policy,
                            time_ns=r0 * int(window_ns),
                            host_names=host_names)
                    except CapacityError as e:
                        # under chained execution the overflow is
                        # observed per CHAIN, so the span is the precise
                        # blame unit — attach it here so every driver's
                        # error report names it without a side channel
                        e.chain_span = (r0, r1)
                        raise
                    state, extras = out
                    # the span's committed capacity decisions (growth /
                    # drop / exhaustion) — already-host trajectory
                    # dicts, by slice
                    growth = policy.trajectory.events[n_events:]
            if memo is not None:
                with phase("chain.memo", tracer, r0=r0, r1=r1) as pm:
                    host_carry = memo.snapshot(state, extras)
                    memo.record(key, pre_walk, host_carry,
                                span_len=r1 - r0)
                memo_ms += pm.ms
            hook_ms = 0.0
            if on_chain is not None:
                with phase("chain.hook", tracer, r0=r0, r1=r1) as ph:
                    replaced = on_chain(r1, state, extras)
                hook_ms = ph.ms
                if replaced is not None:
                    state, extras = replaced
                    host_carry = None
            if tracer is not None:
                tracer.span(r0, r1, mode="execute", t0=whole.t0,
                            dispatch_ms=pl.ms, memo_ms=memo_ms,
                            hook_ms=hook_ms, growth=growth,
                            span_salt=salt_hex)
            _maybe_checkpoint(r0, r1)
    if stale:
        _upload(r0, r1)
    return state, extras


def world_key(rng_root, seed):
    """THE per-world RNG key derivation — and the registered SL702
    obligation (``analysis/batchdim.rng_obligations``).

    ``fold_in(root, seed)`` is one threefry invocation with the ROOT
    key fixed: a block cipher keyed by a constant is a bijection of
    its counter block, so distinct 32-bit seeds yield distinct derived
    key blocks, and every subsequent device draw is
    ``threefry(derived_key, counter)`` — two worlds with distinct
    derived keys can never issue the same cipher invocation. That
    chain (seed -> bijective widen -> fold_in under a fixed key) is
    exactly what the SL702 prover walks symbolically; changing this
    derivation to anything non-injective (``seed % k``, ``seed * 2``)
    fails the proof gate, not a 2x-run parity sweep."""
    import jax

    return jax.random.fold_in(rng_root, seed)


def world_keys(rng_root, seeds):
    """Vector of per-world keys for :func:`drive_ensemble` — the
    vmapped :func:`world_key` chain over a batch of world seeds."""
    import jax

    return jax.vmap(lambda s: world_key(rng_root, s))(seeds)


def drive_ensemble(states, extras, chain_fn, *, n_rounds: int,
                   chain_len: int, start_round: int = 0,
                   boundaries=(), per_round=None, per_round_axis=None,
                   on_chain=None, tracer=None, checkpointer=None):
    """The PROVEN vmap ensemble driver (ROADMAP item 4): W independent
    worlds execute the same chained-window schedule as ONE batched
    program, with one host sync per chain for the whole ensemble.

    ``chain_fn`` is the identical per-world step
    :func:`drive_chained_windows` drives solo —
    ``chain_fn(state, extras, round_ids, per_round_slice) ->
    (state', extras', eg_overflow, in_overflow)`` — vmapped ONCE over
    the leading world axis of ``states``/``extras``. Per-world inputs
    (the :func:`world_keys` RNG keys, fault schedules, workload
    parameters) ride ``extras`` (or ``per_round`` with
    ``per_round_axis=0``) as batched leaves; ``round_ids`` is shared
    (in_axes=None), so every world sees the same round schedule and
    the chain partition is bitwise-identical to the solo run's
    (:func:`chain_spans` ABSOLUTE alignment).

    Why this is trustworthy without running every world twice: the
    SL701 world-isolation proofs (analysis/batchdim.py) show the
    batched step's jaxpr has NO primitive that reduces, gathers,
    scatters, or concatenates across the world axis, and SL702 proves
    the per-world RNG streams disjoint — so world b of a W-world run
    is the solo run of world b by theorem, and the worlds-parity test
    (tests/test_ensemble.py) pins the canonical digests as the
    runtime witness.

    Deliberately NOT supported: a capacity ``policy``. Ring growth is
    per-world (one world's overflow would re-shape every world's
    arrays), so ensemble runs must be provisioned at fixed capacity —
    the per-chain overflow totals are surfaced to ``on_chain`` via
    ``extras`` untouched instead. ``on_chain(r1, states, extras)`` is
    the ONE host-sync point per chain (harvest/checkpoint cadence for
    the whole ensemble); returning a (states, extras) pair replaces
    the carried values, returning None keeps them. ``tracer`` records
    one ``mode="ensemble"`` run-ledger span per batched chain (same
    zero-sync contract as :func:`drive_chained_windows`), and each chain
    opens the same `chain.span` / `chain.launch` / `chain.hook` /
    `chain.checkpoint` profiler spans.
    ``checkpointer`` spills the batched per-world carries into ONE
    runstate file per cadence (docs/robustness.md "Resumable runs" —
    ensemble kill/resume parity is the solo theorem applied
    worldwise). Returns the final batched ``(states, extras)``.
    """
    import jax
    import jax.numpy as jnp

    from ..telemetry.tracer import phase

    # jit OUTSIDE the vmap: one compiled batched program per chain
    # length (the final partial chain retraces once), dispatched W
    # worlds at a time — the amortization BENCH_WORLDS measures
    vchain = jax.jit(jax.vmap(chain_fn,
                              in_axes=(0, 0, None, per_round_axis)))
    if checkpointer is not None:
        # per-world batched carries spill to ONE file: the leading
        # world axis is just another array dimension to the flattener,
        # and chain_spans' absolute alignment makes the resumed
        # ensemble partition identical (the solo parity argument,
        # batched)
        boundaries = tuple(boundaries) + checkpointer.cut_rounds(n_rounds)
    for r0, r1 in chain_spans(n_rounds, chain_len,
                              start_round=start_round,
                              boundaries=boundaries):
        with phase("chain.span", tracer, r0=r0, r1=r1) as whole:
            with phase("chain.launch", tracer, r0=r0, r1=r1) as pl:
                rids = jnp.arange(r0, r1, dtype=jnp.int32)
                pr = per_round(r0, r1) if per_round is not None else None
                states, extras, _eg, _in = vchain(states, extras, rids, pr)
            hook_ms = 0.0
            if on_chain is not None:
                with phase("chain.hook", tracer, r0=r0, r1=r1) as ph:
                    replaced = on_chain(r1, states, extras)
                hook_ms = ph.ms
                if replaced is not None:
                    states, extras = replaced
            if tracer is not None:
                tracer.span(r0, r1, mode="ensemble", t0=whole.t0,
                            dispatch_ms=pl.ms, hook_ms=hook_ms)
            if checkpointer is not None and checkpointer.due(r1, n_rounds):
                with phase("chain.checkpoint", r0=r0, r1=r1):
                    checkpointer.save(r1, (states, extras), tracer=tracer)
    return states, extras


def run_elastic_window(state, attempt_fn, policy: RingPolicy, *,
                       time_ns: int, host_names=None):
    """One window (or chunk of windows) under the capacity policy.

    `attempt_fn(state)` runs the window against `state` and returns
    ``(out, eg_overflow, in_overflow)`` where `out` is whatever the
    driver commits (its first element being the post-window state is
    conventional but not required here) and the overflow values are
    per-host [N] arrays (or scalars) of ring-full drops the attempt
    incurred — egress-ring (ingest-side) and ingress-ring
    (routing-side) respectively. The attempt must be a pure function of
    `state` plus snapshots the closure holds (metrics, guards, fault
    masks, respawn counters): under the elastic policy an overflowing
    attempt is DISCARDED, the offending ring dimension doubles
    (`grow_state` on the pre-attempt snapshot), and the window
    re-executes — so the committed stream is bitwise identical to a
    run pre-provisioned at the final capacity, and the discarded
    attempt's drops never happened.

    fixed: commit the attempt; a first drop lands a structured
    trajectory event. strict: raise :class:`CapacityError` with
    per-host blame. elastic: grow + re-execute, bounded by the
    policy's ``max_doublings`` per dimension (exhaustion commits the
    overflowing attempt, recorded loudly).

    Returns ``(out, state_used)`` — `state_used` is the (possibly
    grown) pre-window state the committed attempt consumed, which is
    what drivers must snapshot/checkpoint against."""
    while True:
        out, eg_ovf, in_ovf = attempt_fn(state)
        eg_arr = np.atleast_1d(np.asarray(eg_ovf))
        in_arr = np.atleast_1d(np.asarray(in_ovf))
        eg_total, in_total = int(eg_arr.sum()), int(in_arr.sum())
        if eg_total == 0 and in_total == 0:
            return out, state
        if policy.mode == "strict":
            blame = sorted(set(np.nonzero(eg_arr)[0].tolist())
                           | set(np.nonzero(in_arr)[0].tolist()))
            if host_names:
                blame = [host_names[i] if i < len(host_names) else i
                         for i in blame]
            ring = ("egress" if eg_total and not in_total else
                    "ingress" if in_total and not eg_total else
                    "egress+ingress")
            raise CapacityError(
                f"ring-full overflow under capacity.mode=strict: "
                f"{eg_total} egress + {in_total} ingress drop(s) in the "
                f"window at t={time_ns} ns (caps CE={policy.egress_cap}, "
                f"CI={policy.ingress_cap}); raise the ring capacities or "
                f"run capacity.mode=elastic", ring=ring, blame=blame)
        if policy.mode != "elastic":
            if eg_total:
                policy.note_drop(ring="egress", overflow=eg_total,
                                 time_ns=time_ns)
            if in_total:
                policy.note_drop(ring="ingress", overflow=in_total,
                                 time_ns=time_ns)
            return out, state
        target = policy.plan_growth(eg_overflow=eg_total,
                                    in_overflow=in_total,
                                    time_ns=time_ns)
        if target is None:  # growth budget exhausted: the drops are real
            return out, state
        state = grow_state(state, *target)
