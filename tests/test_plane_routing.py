"""Routing-plane sort diet (bucketed route-scatter + packed flat ingest).

The PR-2 sort diet left one variadic sort standing: the flat [N*CE]
4-key routing sort in `_route_scatter`. The bucketed rebuild replaces it
with counting placement over a diet sort; this file pins what
tests/test_plane_sortdiet.py's base matrix does not reach:

- the metrics/faults/guards static presence switches thread through the
  bucketed path with bitwise-identical state AND identical accumulator
  contents vs the `packed_sort=False` reference (overflow attribution,
  fault dst-blocking, routed-arrivals conservation);
- the packed flat `ingest` append (bucketed counting placement) against
  its 9-array variadic reference, including overflow and guards;
- the profiler's routing_rank/routing_place split and the bench
  `sections` plumbing (tools/compare_runs.py --bench);
- the routing stage alone against the variadic reference over the
  ring widths the plane compiles and elastic growth reaches, with the
  deliver column recovered from the sort key at the int32 extremes,
  and under vmap.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shadow_tpu.faults.plane import FaultArrays  # noqa: E402
from shadow_tpu.guards.plane import make_guards, summarize  # noqa: E402
from shadow_tpu.telemetry import make_metrics  # noqa: E402
from shadow_tpu.tpu import ingest, make_params, make_state  # noqa: E402
from shadow_tpu.tpu.plane import window_step  # noqa: E402

MS = 1_000_000
N = 8


def busy_world(rr_mix=True, *, ingress_cap=8, seed=7):
    """The test_plane_sortdiet busy world: starved buckets, real loss,
    duplicate priorities — every tiebreak path exercised."""
    rng = np.random.default_rng(seed)
    lat = rng.integers(1 * MS, 20 * MS, size=(N, N)).astype(np.int32)
    loss = np.full((N, N), 0.3, np.float32)
    qrr = (np.arange(N) % 2 == 0) if rr_mix else np.zeros(N, bool)
    params = make_params(lat, loss, np.full((N,), 80_000, np.int64),
                         qdisc_rr=qrr, down_bw_bps=np.full((N,), 400_000))
    state = make_state(N, egress_cap=8, ingress_cap=ingress_cap,
                       params=params,
                       initial_tokens=np.asarray(params.tb_cap))
    b = 48
    state = ingest(
        state,
        jnp.asarray(rng.integers(0, N, b), jnp.int32),
        jnp.asarray(rng.integers(0, N, b), jnp.int32),
        jnp.asarray(rng.integers(100, 1500, b), jnp.int32),
        jnp.asarray(rng.integers(0, 6, b), jnp.int32),
        jnp.arange(b, dtype=jnp.int32),
        jnp.asarray(rng.integers(0, 3, b) == 0),
        sock=jnp.asarray(rng.integers(0, 40, b), jnp.int32),
    )
    return state, params


def active_faults():
    """A genuinely-active mask set: two dead/blocked hosts (their queued
    egress purges, routing toward them drops — the dst-blocking leg),
    degraded links and bandwidth, some corruption."""
    lat_mult = np.ones((N, N), np.int32)
    lat_mult[1, :] = 3
    return FaultArrays(
        host_alive=jnp.asarray(np.arange(N) != 2),
        link_up=jnp.asarray(np.arange(N) != 5),
        lat_mult=jnp.asarray(lat_mult),
        bw_div=jnp.asarray(np.where(np.arange(N) == 3, 4, 1)
                           .astype(np.int32)),
        corrupt_p=jnp.asarray(np.where(np.arange(N) == 1, 0.5, 0.0)
                              .astype(np.float32)),
    )


def run_windows(state, params, *, windows=4, extra=None, **kw):
    """Chain windows; `extra` (metrics/faults/guards pytrees) rides
    through every step. Returns [(state, delivered, next, extra_out)]."""
    key = jax.random.key(3)
    out = []
    shift = jnp.int32(0)
    for _ in range(windows):
        res = window_step(state, params, key, shift, jnp.int32(10 * MS),
                          **kw, **(extra or {}))
        if extra and "metrics" in extra:
            state, delivered, nxt, extra["metrics"] = res
            extra_out = extra["metrics"]
        elif extra and "guards" in extra:
            state, delivered, nxt, extra["guards"] = res
            extra_out = extra["guards"]
        else:
            state, delivered, nxt = res
            extra_out = None
        out.append((state, delivered, nxt, extra_out))
        shift = jnp.int32(10 * MS)
    return out


def assert_runs_equal(a, b, ctx):
    for w, ((sa, da, na, xa), (sb, db, nb, xb)) in enumerate(zip(a, b)):
        for la, lb in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), (ctx, w)
        for k in da:
            assert np.array_equal(np.asarray(da[k]),
                                  np.asarray(db[k])), (ctx, w, k)
        assert int(na) == int(nb), (ctx, w)
        if xa is not None or xb is not None:
            for la, lb in zip(jax.tree.leaves(xa), jax.tree.leaves(xb)):
                assert np.array_equal(np.asarray(la),
                                      np.asarray(lb)), (ctx, w, "extra")


# -- threading: the presence switches flow through the bucketed path ------

@pytest.mark.slow  # faulted reference-vs-bucketed sweep (~20s both
# cells); stays GATING in CI's tier-1-overflow unfiltered step
@pytest.mark.parametrize("router_aqm", [False, True])
def test_bucketed_routing_with_active_faults_matches_reference(router_aqm):
    """Fault dst-blocking, egress purge, latency/bw degradation and
    corruption all thread through the bucketed route-scatter unchanged:
    state, delivered sets, and the n_fault_dropped attribution are
    bitwise the packed_sort=False reference's."""
    state, params = busy_world(rr_mix=False)
    kw = dict(rr_enabled=False, router_aqm=router_aqm,
              faults=active_faults())
    packed = run_windows(state, params, packed_sort=True, **kw)
    ref = run_windows(state, params, packed_sort=False, **kw)
    assert_runs_equal(packed, ref, ("faults", router_aqm))
    # the fault plane actually did something (dead test guard)
    assert int(packed[-1][0].n_fault_dropped.sum()) > 0


@pytest.mark.slow  # guarded reference-vs-bucketed sweep (~16s both
# cells); stays GATING in CI's tier-1-overflow unfiltered step
@pytest.mark.parametrize("router_aqm", [False, True])
def test_bucketed_routing_with_guards_matches_reference(router_aqm):
    """The guards' routed-arrivals conservation term (ingress occupancy
    + arrivals == drops + deliveries + exit occupancy) holds over the
    bucketed scatter, accumulates identically to the reference, and
    stays clean on a healthy world."""
    state, params = busy_world()
    kw = dict(rr_enabled=True, router_aqm=router_aqm)
    packed = run_windows(state, params, packed_sort=True,
                         extra={"guards": make_guards(N)}, **kw)
    ref = run_windows(state, params, packed_sort=False,
                      extra={"guards": make_guards(N)}, **kw)
    assert_runs_equal(packed, ref, ("guards", router_aqm))
    report = summarize(packed[-1][3])
    assert report["clean"], report


def test_bucketed_routing_with_metrics_matches_reference():
    """Overflow attribution (drop_ring_full), traffic counters, and the
    depth high-water marks come out of the bucketed path bit-identical
    to the reference — with ring overflow actually forced: fat pipes,
    tiny ingress rings, and everything routed at two hot hosts."""
    rng = np.random.default_rng(3)
    lat = np.full((N, N), 2 * MS, np.int32)
    params = make_params(lat, np.zeros((N, N), np.float32),
                         np.full((N,), 10_000_000_000, np.int64))
    state = make_state(N, egress_cap=8, ingress_cap=2, params=params,
                       initial_tokens=np.asarray(params.tb_cap))
    b = 64
    state = ingest(
        state,
        jnp.asarray(rng.integers(0, N, b), jnp.int32),
        jnp.asarray(rng.integers(0, 2, b), jnp.int32),  # hot dsts
        jnp.full((b,), 200, jnp.int32),
        jnp.asarray(rng.integers(0, 6, b), jnp.int32),
        jnp.arange(b, dtype=jnp.int32),
        jnp.zeros((b,), bool),
    )
    kw = dict(rr_enabled=False, no_loss=True)
    packed = run_windows(state, params, packed_sort=True,
                         extra={"metrics": make_metrics(N)}, **kw)
    ref = run_windows(state, params, packed_sort=False,
                      extra={"metrics": make_metrics(N)}, **kw)
    assert_runs_equal(packed, ref, ("metrics",))
    assert int(packed[-1][3].drop_ring_full.sum()) > 0  # overflow seen


# -- packed flat ingest ---------------------------------------------------

def test_packed_ingest_matches_variadic():
    """The bucketed counting-placement ingest == the 9-array 2-key
    variadic reference: same rings, same overflow, same guard
    accumulator — including an overflowing batch and duplicate
    (src, seq) pairs (stability must break ties by batch order)."""
    state, params = busy_world()
    rng = np.random.default_rng(11)
    for b, hi in ((40, N), (200, 3)):  # second batch overflows rows
        src = jnp.asarray(rng.integers(0, hi, b), jnp.int32)
        dst = jnp.asarray(rng.integers(0, N, b), jnp.int32)
        nbytes = jnp.asarray(rng.integers(100, 1500, b), jnp.int32)
        prio = jnp.asarray(rng.integers(0, 6, b), jnp.int32)
        seq = jnp.asarray(rng.integers(0, 8, b), jnp.int32)  # dup seqs
        ctrl = jnp.zeros((b,), bool)
        valid = jnp.asarray(rng.integers(0, 4, b) > 0)
        got, g1 = ingest(state, src, dst, nbytes, prio, seq, ctrl,
                         valid=valid, guards=make_guards(N))
        ref, g2 = ingest(state, src, dst, nbytes, prio, seq, ctrl,
                         valid=valid, packed_sort=False,
                         guards=make_guards(N))
        for la, lb in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), b
        for la, lb in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), b
        assert summarize(g1)["clean"]
    assert int(got.n_overflow_dropped.sum()) > 0  # the b=200 batch


# -- profiler split + bench sections plumbing -----------------------------

@pytest.mark.parametrize("packed", [True, False])
def test_profiler_routing_split_times_both_paths(packed):
    """routing_rank + routing_place time on both sort modes, and the
    composed routing_scatter section still exists for before/after
    tables."""
    from shadow_tpu.tpu import profiling

    rep = profiling.profile_sections(
        8, reps=1, rr_enabled=False, packed_sort=packed, n_nodes=4,
        egress_cap=8, ingress_cap=8,
        sections=("routing_scatter", "routing_rank", "routing_place"))
    for name in ("routing_scatter", "routing_rank", "routing_place"):
        assert rep["sections"][name]["min_ms"] >= 0


def test_bench_sections_subset_and_compare_runs_bench_mode(tmp_path,
                                                           capsys):
    """compare_runs --bench prints headline + per-section deltas for
    two bench JSONs (one wrapped the way the PR driver wraps them)."""
    from tools import compare_runs

    before = {"value": 1_000_000.0, "hosts": 1024,
              "sections": {"routing_scatter": 20.0, "window_step": 30.0}}
    after = {"value": 2_000_000.0, "hosts": 1024,
             "sections": {"routing_scatter": 8.0, "window_step": 18.0,
                          "routing_rank": 5.0}}
    a = tmp_path / "before.json"
    b = tmp_path / "after.json"
    a.write_text(json.dumps({"parsed": before}))  # driver-wrapped form
    b.write_text(json.dumps(after))
    assert compare_runs.main(["--bench", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "2.00x" in out and "routing_scatter" in out
    assert "2.50x" in out  # 20.0 -> 8.0 section ratio


def test_bench_backend_fingerprint_refuses_cross_container(
        tmp_path, capsys, monkeypatch):
    """The PR-7 false-regression rule: a prior BENCH_r*.json measured
    on a different backend (or predating the stamp) makes the
    prior_round guard SKIP with `skipped_mismatched_backend`, and
    compare_runs --bench prints a meaningless-comparison banner
    instead of a speedup verdict."""
    import bench
    from tools import compare_runs

    fp = {"platform": "cpu", "device_kind": "cpu"}
    prior = tmp_path / "BENCH_r91.json"

    def write_prior(backend):
        rec = {"value": 5_000_000.0, "hosts": bench.N_HOSTS}
        if backend is not None:
            rec["backend"] = backend
        prior.write_text(json.dumps(rec))

    monkeypatch.setattr("glob.glob", lambda pat: [str(prior)])

    # prior predates the stamp (no backend field): not comparable
    write_prior(None)
    guard = bench._regression_guard(1_000_000.0, fp)
    assert guard["skipped_mismatched_backend"] is True
    assert guard["regressed"] is False
    assert "SKIPPED" in capsys.readouterr().err

    # prior from another container: not comparable either
    write_prior({"platform": "gpu", "device_kind": "other"})
    guard = bench._regression_guard(1_000_000.0, fp)
    assert guard["skipped_mismatched_backend"] is True
    assert guard["prior_backend"]["platform"] == "gpu"
    assert "SKIPPED" in capsys.readouterr().err

    # matched fingerprint: the 20% gate applies as before
    write_prior(fp)
    guard = bench._regression_guard(1_000_000.0, fp)
    assert guard == {"vs_round": 91, "ratio": 0.2, "regressed": True}

    # compare_runs --bench: mismatched fingerprints warn loudly and
    # withhold the speedup verdict
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps({"value": 1.0, "hosts": 64, "backend": fp}))
    b.write_text(json.dumps({
        "value": 9.0, "hosts": 64,
        "backend": {"platform": "gpu", "device_kind": "other"}}))
    assert compare_runs.main(["--bench", str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "MISMATCHED BACKENDS" in out and "MEANINGLESS" in out


def test_routing_rank_seq_tiebreak_vs_row_position():
    """The regression the bucketed path must not reintroduce: two
    same-src packets to the same dst with the same (clamped) deliver
    time but qdisc order opposite to seq order must land in seq order —
    the (deliver, src, seq) contract, not (deliver, src, row-position).
    Compared against the variadic reference on a world built to hit it."""
    rng = np.random.default_rng(1)
    lat = np.full((N, N), 2 * MS, np.int32)  # uniform: deliver ties
    params = make_params(lat, np.zeros((N, N), np.float32),
                         np.full((N,), 10_000_000, np.int64))
    state = make_state(N, egress_cap=8, ingress_cap=8, params=params,
                       initial_tokens=np.asarray(params.tb_cap))
    b = 32
    # priorities DESCEND while seqs ascend: the qdisc row order inverts
    # seq order, and the uniform latency + window clamp makes every
    # same-(src,dst) pair tie on deliver time
    state = ingest(
        state,
        jnp.asarray(rng.integers(0, N, b), jnp.int32),
        jnp.asarray(rng.integers(0, N, b), jnp.int32),
        jnp.full((b,), 200, jnp.int32),
        jnp.asarray(np.arange(b)[::-1].copy(), jnp.int32),
        jnp.arange(b, dtype=jnp.int32),
        jnp.zeros((b,), bool),
    )
    kw = dict(rr_enabled=False, no_loss=True)
    key = jax.random.key(0)
    step = lambda ps: window_step(state, params, key, jnp.int32(0),
                                  jnp.int32(10 * MS), packed_sort=ps,
                                  **kw)
    got, ref = step(True), step(False)
    for la, lb in zip(jax.tree.leaves(got[0]), jax.tree.leaves(ref[0])):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    for k in got[1]:
        assert np.array_equal(np.asarray(got[1][k]), np.asarray(ref[1][k]))


# -- the routing stage alone against the variadic reference ---------------

I32 = np.iinfo(np.int32)


def routing_inputs(rng, n, ce, ci):
    """One routing stage's inputs: sent and unsent slots (the unsent
    with the dead-lane dst -1 or any value), sent packets to hosts past
    the last (never placed), hot destinations whose rows fill past
    capacity, some rows already full, duplicate seqs in a row, and
    deliver times over the whole int32 range, extremes included."""
    sent = rng.integers(0, 4, (n, ce)) > 0
    eg_dst = rng.integers(0, 3, (n, ce))  # hot: buckets 0-2 overflow
    wide = rng.integers(0, 2, (n, ce)) == 0
    eg_dst = np.where(wide, rng.integers(0, n + 3, (n, ce)), eg_dst)
    eg_dst = np.where(sent | (rng.integers(0, 2, (n, ce)) == 0), eg_dst,
                      -1)
    eg_seq = rng.integers(0, max(ce // 2, 1), (n, ce))  # duplicate seqs
    deliver = rng.integers(-5 * MS, 15 * MS, (n, ce))
    ties = rng.integers(0, 4, (n, ce)) == 0  # equal deliver times
    deliver = np.where(ties, 2 * MS, deliver)
    extreme = rng.integers(0, 10, (n, ce))
    deliver = np.where(extreme == 0, I32.min, deliver)
    deliver = np.where(extreme == 1, I32.max, deliver)
    deliver = np.where(extreme == 2, -1, deliver)
    n_valid = rng.integers(0, ci + 1, n)
    n_valid[: n // 4] = ci  # full rows: every arrival overflows
    in_valid = np.arange(ci)[None, :] < n_valid[:, None]
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    garbage = lambda: i32(rng.integers(-100, 1 << 20, (n, ci)))
    return (jnp.asarray(sent), i32(eg_dst), i32(eg_seq),
            i32(rng.integers(100, 1500, (n, ce))),
            i32(rng.integers(0, 40, (n, ce))), i32(deliver),
            garbage(), garbage(), garbage(), garbage(), garbage(),
            jnp.asarray(in_valid), i32(n_valid))


def route(packed_sort=True):
    from shadow_tpu.tpu.plane import _route_scatter

    return lambda *a: _route_scatter(*a, packed_sort=packed_sort)


@pytest.mark.parametrize("ci", [8, 32, 256])
@pytest.mark.parametrize("ce", [1, 8, 16, 32, 64, 128])
def test_packed_route_matches_variadic_reference(ce, ci):
    """The bucketed route, whose sort carries the payload and whose
    deliver column comes back from the sort key, is bitwise the
    `packed_sort=False` reference — merged columns, valid mask and
    per-host overflow — over every ring width the plane compiles and
    `elastic.grow_state` reaches (egress up to 128, ingress up to 256),
    with overflowing rows, unplaceable destinations and int32-extreme
    deliver times."""
    rng = np.random.default_rng(1000 * ce + ci)
    args = routing_inputs(rng, 16, ce, ci)
    got = jax.jit(route())(*args)
    ref = jax.jit(route(packed_sort=False))(*args)
    for k, (la, lb) in enumerate(zip(got, ref)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), k
    assert int(got[-1].sum()) > 0  # overflow exercised
    placed = np.asarray(got[5]) & ~np.asarray(args[11])
    deliver = np.asarray(got[4])[placed]
    # extremes placed, so the key round trip is exercised: I32.min sorts
    # first in every bucket; -1 lands too unless the rows are so narrow
    # against the egress width that only each bucket's earliest fit
    want = {I32.min, -1} if 8 * ci >= ce else {I32.min}
    if ce >= 8:
        assert want.issubset(set(deliver.tolist())), deliver


@pytest.mark.parametrize("ce,ci", [(16, 8), (128, 32)])
def test_packed_route_under_vmap_matches_reference(ce, ci):
    """Over a batch of worlds (`drive_ensemble`'s vmap), the bucketed
    route equals the reference world by world, at the default egress
    ring and at a grown one."""
    rng = np.random.default_rng(5)
    worlds = [routing_inputs(rng, 16, ce, ci) for _ in range(3)]
    batched = [jnp.stack(col) for col in zip(*worlds)]
    got = jax.jit(jax.vmap(route()))(*batched)
    ref = jax.jit(route(packed_sort=False))
    for w, args in enumerate(worlds):
        for k, (la, lb) in enumerate(zip(got, ref(*args))):
            assert np.array_equal(np.asarray(la)[w], np.asarray(lb)), (w, k)
