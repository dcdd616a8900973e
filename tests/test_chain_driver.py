"""The shared chained-window driver: parity, boundaries, unification.

Pins the PR-11 driver-loop contract (docs/performance.md "The driver
loop", docs/determinism.md "Chain length is bitwise-invisible"):

- K windows driven through `tpu.elastic.drive_chained_windows` (the
  scan-chain default loop) end bitwise-identical to K single-window
  `window_step` calls — canonical state, delivered streams, metrics,
  guards accumulators, and histogram buckets — across the
  rr × aqm × no_loss compile matrix, including an elastic growth
  event mid-chain;
- the chain partition (chain_len, boundaries, resume offsets) is
  invisible to every digest;
- `plane.chain_windows` threads its presence switches without
  perturbing the stream;
- bench.py, tools/chaos_smoke.py, and the scenario corpus runner all
  route through the ONE driver (the inspect-source gate, so the three
  loops cannot silently fork again).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shadow_tpu.guards import make_guards  # noqa: E402
from shadow_tpu.telemetry import make_histograms, make_metrics  # noqa: E402
from shadow_tpu.tpu import elastic, profiling  # noqa: E402
from shadow_tpu.tpu.plane import chain_windows, window_step  # noqa: E402
from shadow_tpu.workloads.phold import respawn_batch  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 32
K = 6


def _world(egress_cap=8, ingress_cap=16):
    return profiling.build_world(N, n_nodes=8, egress_cap=egress_cap,
                                 ingress_cap=ingress_cap, seed=3,
                                 warmup_windows=1)


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def _build_round_fn(params, rng_root, window, *, rr, aqm, no_loss):
    def round_fn(carry, rid):
        state, metrics, guards, hist = carry
        shift = jnp.where(rid == 0, jnp.int32(0), window)
        out = window_step(state, params, rng_root, shift, window,
                          rr_enabled=rr, router_aqm=aqm,
                          no_loss=no_loss, metrics=metrics,
                          guards=guards, hist=hist)
        state, delivered, _next = out[:3]
        rest = list(out[3:])
        if metrics is not None:
            metrics = rest.pop(0)
        if guards is not None:
            guards = rest.pop(0)
        if hist is not None:
            hist = rest.pop(0)
        return (state, metrics, guards, hist), delivered["mask"].sum(
            dtype=jnp.int32)
    return round_fn


@pytest.mark.slow  # CI's shared-driver gate runs this file unfiltered
@pytest.mark.parametrize("rr,aqm,no_loss",
                         [(False, False, False), (True, False, False),
                          (False, True, False), (True, True, True)])
def test_chained_matches_single_window_matrix(rr, aqm, no_loss):
    """K chained windows == K single-window dispatches, bitwise:
    canonical state, per-window delivered counts, metrics, guards
    accumulators, and histogram buckets, across rr × aqm × no_loss."""
    world = _world()
    params, rng_root, window = (world["params"], world["rng_root"],
                                world["window"])
    round_fn = _build_round_fn(params, rng_root, window,
                               rr=rr, aqm=aqm, no_loss=no_loss)

    # reference: one dispatch per window (the PR-10 driver shape)
    step = jax.jit(lambda c, r: round_fn(c, r))
    carry_ref = (world["state"], make_metrics(N), make_guards(N),
                 make_histograms(N))
    counts_ref = []
    for r in range(K):
        carry_ref, ndel = step(carry_ref, jnp.int32(r))
        counts_ref.append(int(ndel))

    # the chained default loop: all K windows in one scan dispatch
    @jax.jit
    def chain(state, metrics, guards, hist, rids, _pr):
        carry, counts = jax.lax.scan(
            round_fn, (state, metrics, guards, hist), rids)
        return carry, counts

    def chain_fn(state, extras, rids, pr):
        metrics, guards, hist, _counts = extras
        (state, metrics, guards, hist), counts = chain(
            state, metrics, guards, hist, rids, pr)
        return state, (metrics, guards, hist, counts), 0, 0

    state, extras = elastic.drive_chained_windows(
        world["state"], (make_metrics(N), make_guards(N),
                         make_histograms(N), None), chain_fn,
        n_rounds=K, chain_len=K)
    metrics, guards, hist, counts = extras

    ref_state, ref_metrics, ref_guards, ref_hist = carry_ref
    assert _leaves_equal(elastic.canonical_state(state),
                         elastic.canonical_state(ref_state))
    assert _leaves_equal(metrics, ref_metrics)
    assert _leaves_equal(guards, ref_guards)
    assert _leaves_equal(hist, ref_hist)
    assert [int(c) for c in np.asarray(counts)] == counts_ref


@pytest.mark.slow  # CI's shared-driver gate runs this file unfiltered
def test_chain_partition_is_bitwise_invisible():
    """chain_len 1 / 2 / K (and a ragged boundary set) all produce the
    identical final state — the chain is a dispatch schedule, not a
    semantic unit (docs/determinism.md)."""
    world = _world()
    params, rng_root, window = (world["params"], world["rng_root"],
                                world["window"])
    round_fn = _build_round_fn(params, rng_root, window,
                               rr=False, aqm=False, no_loss=False)

    @jax.jit
    def chain(state, rids):
        carry, _ = jax.lax.scan(round_fn, (state, None, None, None),
                                rids)
        return carry[0]

    def chain_fn(state, extras, rids, _pr):
        return chain(state, rids), extras, 0, 0

    outs = []
    for chain_len, boundaries in ((1, ()), (2, ()), (K, ()),
                                  (K, (1, 4))):
        state, _ = elastic.drive_chained_windows(
            world["state"], (), chain_fn, n_rounds=K,
            chain_len=chain_len, boundaries=boundaries)
        outs.append(state)
    for other in outs[1:]:
        assert _leaves_equal(outs[0], other)


def test_chain_spans_absolute_alignment():
    # resumed partitions must continue the absolute grid (the elastic
    # growth-decision unit), not restart relative to the resume point
    assert elastic.chain_spans(10, 4) == [(0, 4), (4, 8), (8, 10)]
    assert elastic.chain_spans(10, 4, start_round=5) == [(5, 8), (8, 10)]
    assert elastic.chain_spans(10, 4, boundaries=(6,)) == [
        (0, 4), (4, 6), (6, 8), (8, 10)]
    assert elastic.chain_spans(3, 8) == [(0, 3)]
    # a resume at or past the horizon runs NOTHING (the unguarded cut
    # set would invert into a span past the requested end)
    assert elastic.chain_spans(10, 4, start_round=10) == []
    assert elastic.chain_spans(10, 4, start_round=16) == []


@pytest.mark.slow  # growth mid-chain parity (~12s); CI's
# shared-driver gate runs this file unfiltered
def test_elastic_growth_mid_chain_matches_preprovisioned():
    """A PHOLD chain started on deliberately tiny rings under the
    elastic policy grows mid-chain (snapshot + re-execute per CHAIN)
    and ends canonically identical to a run pre-provisioned at the
    final capacity, with zero committed drops."""
    def phold_chain_fn(world):
        params, rng_root, window = (world["params"], world["rng_root"],
                                    world["window"])

        def round_fn(carry, rid):
            state, spawn_seq, eg, inn = carry
            state0 = state
            shift = jnp.where(rid == 0, jnp.int32(0), window)
            state, delivered, _next = window_step(
                state, params, rng_root, shift, window,
                rr_enabled=False)
            inn = inn + (state.n_overflow_dropped
                         - state0.n_overflow_dropped)
            state1 = state
            mask, dst, nbytes, seq, ctrl = respawn_batch(
                delivered, spawn_seq, rid, N, state.in_src.shape[1])
            from shadow_tpu.tpu import ingest_rows

            state = ingest_rows(state, dst, nbytes, seq, seq, ctrl,
                                valid=mask)
            eg = eg + (state.n_overflow_dropped
                       - state1.n_overflow_dropped)
            return (state, spawn_seq
                    + mask.sum(axis=1, dtype=jnp.int32), eg, inn), None

        @jax.jit
        def chain(state, spawn_seq, rids):
            zeros = jnp.zeros((N,), jnp.int32)
            carry, _ = jax.lax.scan(
                round_fn, (state, spawn_seq, zeros, zeros), rids)
            return carry

        def chain_fn(state, extras, rids, _pr):
            (state, spawn_seq, eg, inn) = chain(state, extras[0], rids)
            return state, (spawn_seq,), eg, inn

        return chain_fn

    # elastic from tiny rings: the 4 seed packets per host fit the
    # egress exactly (drops at world build would be committed before
    # the driver runs), the deliberately tiny ingress overflows
    # mid-chain and must grow
    tiny = profiling.build_world(N, n_nodes=8, egress_cap=4,
                                 ingress_cap=4, seed=3,
                                 warmup_windows=0)
    policy = elastic.RingPolicy(mode="elastic", max_doublings=4,
                                egress_cap=4, ingress_cap=4,
                                plane="test")
    spawn0 = jnp.full((N,), 10_000, jnp.int32)
    state_el, (spawn_el,) = elastic.drive_chained_windows(
        tiny["state"], (spawn0,), phold_chain_fn(tiny), n_rounds=K,
        chain_len=K, policy=policy, window_ns=int(tiny["window"]))
    growths = [e for e in policy.trajectory.events
               if e.get("kind") == "capacity-growth"]
    assert growths, "tiny rings never grew — dead test"
    assert int(np.asarray(state_el.n_overflow_dropped).sum()) == 0
    final_ce, final_ci = elastic.ring_dims(state_el)

    # pre-provisioned twin at the final capacity, single-window driven
    pre = profiling.build_world(N, n_nodes=8, egress_cap=final_ce,
                                ingress_cap=final_ci, seed=3,
                                warmup_windows=0)
    state_pre, (spawn_pre,) = elastic.drive_chained_windows(
        pre["state"], (spawn0,), phold_chain_fn(pre), n_rounds=K,
        chain_len=1)
    assert _leaves_equal(elastic.canonical_state(state_el),
                         elastic.canonical_state(state_pre))
    assert np.array_equal(np.asarray(spawn_el), np.asarray(spawn_pre))


@pytest.mark.slow  # presence-switch parity sweep (~9s); CI's
# shared-driver gate runs this file unfiltered
def test_chain_windows_presence_switches_are_invisible():
    """The while_loop idle chain with metrics/guards threaded ends in
    the same state as the bare chain, and the accumulators count every
    chained window (the jaxpr-audited carry variants)."""
    world = _world()
    params, rng_root = world["params"], world["rng_root"]
    w = jnp.int32(1_000_000)
    horizon = jnp.int32(200_000_000)

    base = jax.jit(lambda st: chain_windows(
        st, params, rng_root, jnp.int32(0), w, w, horizon, horizon,
        rr_enabled=False))(world["state"])
    st_b, dl_b, off_b, next_b, n_b = base

    withm = jax.jit(lambda st, m, g: chain_windows(
        st, params, rng_root, jnp.int32(0), w, w, horizon, horizon,
        rr_enabled=False, metrics=m, guards=g))(
        world["state"], make_metrics(N), make_guards(N))
    st_m, dl_m, off_m, next_m, n_m, metrics, guards = withm

    assert _leaves_equal((st_b, dl_b, off_b, next_b, n_b),
                         (st_m, dl_m, off_m, next_m, n_m))
    assert int(np.asarray(metrics.windows)) == int(np.asarray(n_m))
    from shadow_tpu.guards import summarize

    assert summarize(guards)["clean"]


def test_three_drivers_route_through_the_shared_loop():
    """bench.py, tools/chaos_smoke.py, and workloads/runner.py must
    all drive their windows through
    `tpu.elastic.drive_chained_windows` — the inspect-source gate that
    keeps the three loops from forking again (each hand-rolled its own
    attempt/snapshot/grow loop before PR 11)."""
    for rel in ("bench.py", os.path.join("tools", "chaos_smoke.py"),
                os.path.join("shadow_tpu", "workloads", "runner.py")):
        with open(os.path.join(REPO, rel)) as fh:
            src = fh.read()
        assert "drive_chained_windows" in src, (
            f"{rel} no longer routes through the shared chained-window "
            f"driver (tpu/elastic.drive_chained_windows)")
        assert "run_elastic_window" not in src.replace(
            "drive_chained_windows", ""), (
            f"{rel} grew a direct run_elastic_window loop again — "
            f"route it through drive_chained_windows")


class _Annotations:
    """Logs the profiler spans the driver enters: (name, r0, r1)."""

    def __init__(self):
        self.entered = []

    def __call__(self, name, **args):
        entered = self.entered

        class _Span:
            def __enter__(self):
                entered.append((name, args.get("r0"), args.get("r1")))

            def __exit__(self, *exc):
                return False

        return _Span()


class _Memo:
    """A chain memo keyed by span length alone: the first chain of a
    length misses and records, the next one of that length hits."""

    def __init__(self):
        self.store = {}

    def snapshot(self, state, extras):
        return np.asarray(state), extras

    def key(self, carry, r0, r1, span_salt=b""):
        return r1 - r0, None

    def lookup(self, key):
        return self.store.get(key)

    def record(self, key, pre_walk, post, span_len):
        self.store[key] = post

    def replay(self, entry, pre):
        return entry

    def to_device(self, carry):
        return jnp.asarray(carry[0]), carry[1]


@pytest.mark.parametrize("memo,hook", [(False, False), (False, True),
                                       (True, False), (True, True)])
def test_driver_phases_are_profiler_spans(monkeypatch, memo, hook):
    """Each chain opens `chain.span` and its phases as profiler spans
    carrying the chain's r0/r1: `chain.launch` once per executed chain,
    `chain.memo` only with a memo, `chain.hook` only with `on_chain`,
    `chain.upload` when a replayed carry goes back to the device."""
    from shadow_tpu.telemetry import tracer

    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    ledger = tracer.RunTracer("t", backend={"platform": "cpu"})

    def chain_fn(state, extras, rids, _pr):
        return state + rids.shape[0], extras, None, None

    state, _ = elastic.drive_chained_windows(
        jnp.int32(0), (), chain_fn, n_rounds=8, chain_len=4,
        memo=_Memo() if memo else None,
        on_chain=(lambda r1, st, ex: None) if hook else None,
        tracer=ledger)
    # the stub memo's replay hands back the recorded carry as it was
    assert int(state) == (4 if memo else 8)
    first = [("chain.span", 0, 4)]
    first += [("chain.memo", 0, 4)] if memo else []
    first += [("chain.launch", 0, 4)]
    first += [("chain.memo", 0, 4)] if memo else []
    first += [("chain.hook", 0, 4)] if hook else []
    if memo:
        # the second chain replays the first one's record
        second = [("chain.span", 4, 8), ("chain.memo", 4, 8)]
        second += ([("chain.upload", 4, 8), ("chain.hook", 4, 8)] if hook
                   else [("chain.upload", 4, 8)])
        modes = ["execute", "replay" if hook else "ffwd"]
    else:
        second = [(n, 4, 8) for n, _r0, _r1 in first]
        modes = ["execute", "execute"]
    assert ann.entered == first + second
    spans = [r for r in ledger.records if r.get("kind") == "span"]
    assert [r["mode"] for r in spans] == modes


def test_driver_spans_reach_a_profiler_trace(tmp_path):
    """On a real `jax.profiler` trace (the CPU here), each chain's
    `chain.launch` span is recorded once, with r0/r1 as its arguments."""
    from jax.profiler import ProfileData

    step = jax.jit(lambda st, rids: st + rids.sum())

    def chain_fn(state, extras, rids, _pr):
        return step(state, rids), extras, None, None

    jax.block_until_ready(step(jnp.int32(0), jnp.arange(4)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        state, _ = elastic.drive_chained_windows(
            jnp.int32(0), (), chain_fn, n_rounds=12, chain_len=4)
        jax.block_until_ready(state)
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _s, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    launches = sorted(
        (dict(e.stats)["r0"], dict(e.stats)["r1"])
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name == "chain.launch")
    assert launches == [(0, 4), (4, 8), (8, 12)]
    assert int(state) == sum(range(12))


def test_unpack_planes_shapes_and_mismatch():
    """The shared presence-output unpacker every driver uses: lead
    splits, declaration-order plane outputs, the bare-NamedTuple-state
    return of a plane-less ingest_rows (NetPlaneState IS a tuple — the
    exact-type check is the trap), and a loud mismatch."""
    from shadow_tpu.tpu import make_params, make_state
    from shadow_tpu.tpu.plane import unpack_planes

    params = make_params(np.full((4, 4), 5, np.int32),
                         np.zeros((4, 4), np.float32),
                         np.full((4,), 1_000, np.int64))
    state = make_state(4, egress_cap=4, ingress_cap=4, params=params)

    # bare state (ingest_rows, no planes): NOT unpacked as a tuple
    (st,), m, g, h, fr = unpack_planes(state, n_lead=1)
    assert st is state and (m, g, h, fr) == (None, None, None, None)

    # subset presence in declaration order, n_lead=3 (window_step)
    lead, m, g, h, fr = unpack_planes(
        ("s", "d", "n", "M", "H"), metrics="yes", hist="yes")
    assert lead == ("s", "d", "n") and (m, h) == ("M", "H")
    assert g is None and fr is None

    # unclaimed outputs fail loudly, never silently mis-assign
    with pytest.raises(TypeError, match="unclaimed"):
        unpack_planes(("s", "d", "n", "M", "H"), metrics="yes")


def test_fused_kernel_chain_parity():
    """K FIFO windows through the chained driver on the packed path ==
    the same chain on the `packed_sort=False` reference, bitwise: the
    sort diet's parity contract under the default loop."""
    world = _world(egress_cap=8, ingress_cap=16)
    params, rng_root, window = (world["params"], world["rng_root"],
                                world["window"])

    def make_chain_fn(packed_sort):
        def round_fn(carry, rid):
            state = carry
            shift = jnp.where(rid == 0, jnp.int32(0), window)
            state, delivered, _next = window_step(
                state, params, rng_root, shift, window,
                rr_enabled=False, packed_sort=packed_sort)
            return state, delivered["mask"].sum(dtype=jnp.int32)

        @jax.jit
        def chain(state, rids):
            return jax.lax.scan(round_fn, state, rids)

        def chain_fn(state, extras, rids, _pr):
            state, counts = chain(state, rids)
            return state, (counts,), 0, 0
        return chain_fn

    out = {}
    for packed_sort in (True, False):
        state, (counts,) = elastic.drive_chained_windows(
            world["state"], (None,), make_chain_fn(packed_sort),
            n_rounds=K, chain_len=3)
        out[packed_sort] = (state, counts)
    assert _leaves_equal(out[True], out[False])
