"""Sort-diet parity for the device plane.

The packed-key row sorts, the routing sort's recovered `sent` column
and the `ingest_rows` single-key merge + idle gate must all be
BITWISE-identical to the pre-change variadic paths (kept compiled-in
as `packed_sort=False`): same `NetPlaneState` (every leaf, including
compacted-slot contents), same delivered sets, same next-event scalar
— across the RR/FIFO x router_aqm x no_loss matrix, on power-of-two
and non-power-of-two rings, over multiple chained windows.

Also pins the window step's one kernel (any other name is refused) and
the trace-time bit-budget assertion for packed sort keys.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shadow_tpu.tpu import (ingest, ingest_rows, make_params, make_state,
                            plane)
from shadow_tpu.tpu.plane import window_step

MS = 1_000_000
N = 8


def busy_world(rr_mix=True, ingress_cap=8, egress_cap=8):
    """A small world with starved token buckets (leftover egress every
    window), real loss, mixed qdiscs, duplicate priorities and colliding
    socket slots — every tiebreak path of the sorts gets exercised."""
    rng = np.random.default_rng(7)
    lat = rng.integers(1 * MS, 20 * MS, size=(N, N)).astype(np.int32)
    loss = np.full((N, N), 0.3, np.float32)
    qrr = (np.arange(N) % 2 == 0) if rr_mix else np.zeros(N, bool)
    params = make_params(lat, loss, np.full((N,), 80_000, np.int64),
                         qdisc_rr=qrr, down_bw_bps=np.full((N,), 400_000))
    state = make_state(N, egress_cap=egress_cap, ingress_cap=ingress_cap,
                       params=params,
                       initial_tokens=np.asarray(params.tb_cap))
    b = 48
    state = ingest(
        state,
        jnp.asarray(rng.integers(0, N, b), jnp.int32),
        jnp.asarray(rng.integers(0, N, b), jnp.int32),
        jnp.asarray(rng.integers(100, 1500, b), jnp.int32),
        # duplicate priorities on purpose: stability must break the ties
        jnp.asarray(rng.integers(0, 6, b), jnp.int32),
        jnp.arange(b, dtype=jnp.int32),
        jnp.asarray(rng.integers(0, 3, b) == 0),
        # socket ids beyond RR_SOCK_SLOTS: slot collisions merge flows
        sock=jnp.asarray(rng.integers(0, 40, b), jnp.int32),
    )
    return state, params


def run_windows(state, params, *, windows=4, **kw):
    key = jax.random.key(3)
    step = jax.jit(lambda s, sh: window_step(
        s, params, key, sh, jnp.int32(10 * MS), **kw))
    shift = jnp.int32(0)
    out = []
    for _ in range(windows):
        state, delivered, nxt = step(state, shift)
        out.append((state, delivered, nxt))
        shift = jnp.int32(10 * MS)
    return out


def assert_runs_equal(a, b, ctx):
    for w, ((sa, da, na), (sb, db, nb)) in enumerate(zip(a, b)):
        for la, lb in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), (ctx, w)
        for k in da:
            assert np.array_equal(np.asarray(da[k]),
                                  np.asarray(db[k])), (ctx, w, k)
        assert int(na) == int(nb), (ctx, w)


@pytest.mark.parametrize("caps", [(8, 8), (6, 6)],
                         ids=["caps8x8", "caps6x6"])
@pytest.mark.parametrize("rr_enabled", [False, True])
@pytest.mark.parametrize("router_aqm", [False, True])
@pytest.mark.parametrize("no_loss", [False, True])
def test_packed_sort_matches_variadic(rr_enabled, router_aqm, no_loss,
                                      caps):
    """The packed path equals the variadic reference on the default
    power-of-two rings and on non-power-of-two (egress, ingress) rings
    of 6 slots, whose packed keys and select chains have no power-of-two
    width to lean on."""
    egress_cap, ingress_cap = caps
    state, params = busy_world(rr_mix=rr_enabled, egress_cap=egress_cap,
                               ingress_cap=ingress_cap)
    kw = dict(rr_enabled=rr_enabled, router_aqm=router_aqm,
              no_loss=no_loss)
    packed = run_windows(state, params, packed_sort=True, **kw)
    ref = run_windows(state, params, packed_sort=False, **kw)
    assert_runs_equal(packed, ref, kw)


@pytest.mark.parametrize("kernel", ["pallas", "pallas_fused"])
def test_window_step_refuses_removed_kernels(kernel):
    """The window step has one kernel: the names of the removed Pallas
    kernels are refused at trace time, never run as the XLA path under
    another name."""
    state, params = busy_world(rr_mix=False)
    with pytest.raises(ValueError, match="unknown plane kernel"):
        window_step(state, params, jax.random.key(0), jnp.int32(0),
                    jnp.int32(MS), rr_enabled=False, kernel=kernel)


def test_pallas_fused_overflow_parity():
    """A deliberately tiny ingress ring: the counting placement's
    take/overflow arithmetic must be bitwise the variadic reference
    exactly where buckets overflow their free slots — merged columns,
    valid mask, AND the per-host overflow counter the capacity policy
    reads."""
    rng = np.random.default_rng(11)
    lat = rng.integers(1 * MS, 5 * MS, size=(N, N)).astype(np.int32)
    params = make_params(lat, np.zeros((N, N), np.float32),
                         np.full((N,), 10_000_000, np.int64))
    state = make_state(N, egress_cap=8, ingress_cap=4, params=params,
                       initial_tokens=np.asarray(params.tb_cap))
    b = 40
    # a hot destination so at least one bucket overflows its 4 slots
    state = ingest(
        state,
        jnp.asarray(rng.integers(0, N, b), jnp.int32),
        jnp.asarray(rng.integers(0, 3, b), jnp.int32),
        jnp.asarray(rng.integers(100, 1500, b), jnp.int32),
        jnp.asarray(rng.integers(0, 6, b), jnp.int32),
        jnp.arange(b, dtype=jnp.int32),
        jnp.asarray(rng.integers(0, 3, b) == 0),
    )
    kw = dict(rr_enabled=False)
    packed = run_windows(state, params, windows=3, packed_sort=True, **kw)
    ref = run_windows(state, params, windows=3, packed_sort=False, **kw)
    assert_runs_equal(packed, ref, kw)
    drops = int(np.asarray(ref[-1][0].n_overflow_dropped).sum())
    assert drops > 0, "ingress never overflowed — dead test"


def test_pallas_fused_non_power_of_two_ingress_matches_xla():
    """A FIFO world on a non-power-of-two ingress ring (6 slots): the
    counting placement lands it bitwise like the variadic reference."""
    state, params = busy_world(rr_mix=False, ingress_cap=6)
    kw = dict(rr_enabled=False)
    packed = run_windows(state, params, packed_sort=True, **kw)
    ref = run_windows(state, params, packed_sort=False, **kw)
    assert_runs_equal(packed, ref, kw)


def test_ingest_rows_packed_and_gate_match_reference():
    """The single-key merge and the idle gate are bitwise the 10-array
    variadic merge — with new entries, and with an all-invalid batch
    (the gate's skip branch must equal the reference's identity merge,
    garbage columns included)."""
    state, params = busy_world()
    rng = np.random.default_rng(5)
    K = 4
    dst = jnp.asarray(rng.integers(0, N, (N, K)), jnp.int32)
    nbytes = jnp.asarray(rng.integers(100, 900, (N, K)), jnp.int32)
    prio = jnp.asarray(rng.integers(0, 30, (N, K)), jnp.int32)
    seq = jnp.asarray(rng.integers(100, 200, (N, K)), jnp.int32)
    ctrl = jnp.zeros((N, K), bool)
    for valid in (jnp.asarray(rng.integers(0, 2, (N, K)) == 0),
                  jnp.ones((N, K), bool),
                  jnp.zeros((N, K), bool)):
        got = ingest_rows(state, dst, nbytes, prio, seq, ctrl, valid)
        ref = ingest_rows(state, dst, nbytes, prio, seq, ctrl, valid,
                          packed_sort=False, gate_idle=False)
        for la, lb in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
            assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_ingest_rows_overflow_counts_match():
    """Overflow accounting survives the diet: overfill a row past the
    egress capacity through ingest_rows and compare both paths."""
    state, params = busy_world()
    K = 12  # 48 seeded packets over 8 hosts + 12 more can overflow CE=8
    dst = jnp.zeros((N, K), jnp.int32)
    nbytes = jnp.full((N, K), 500, jnp.int32)
    prio = jnp.arange(N * K, dtype=jnp.int32).reshape(N, K)
    valid = jnp.ones((N, K), bool)
    got = ingest_rows(state, dst, nbytes, prio, prio,
                      jnp.zeros((N, K), bool), valid)
    ref = ingest_rows(state, dst, nbytes, prio, prio,
                      jnp.zeros((N, K), bool), valid,
                      packed_sort=False, gate_idle=False)
    assert int(got.n_overflow_dropped.sum()) > 0
    assert np.array_equal(np.asarray(got.n_overflow_dropped),
                          np.asarray(ref.n_overflow_dropped))


def test_pack_key_bit_budget_asserts_at_trace_time():
    """The packed-key helpers refuse budgets past 32 bits while TRACING
    (static capacities), not at runtime."""
    plane._assert_bit_budget((1, "validity"), (31, "key"))  # exactly fits
    with pytest.raises(ValueError, match="bit-budget overflow"):
        plane._assert_bit_budget((1, "validity"), (32, "key"))

    # _pack_rank_key's rank width is derived from the static column
    # count: an impossible capacity must die inside jit TRACING
    def over_budget():
        valid = jnp.ones((4,), bool)
        rank = jnp.zeros((4,), jnp.int32)
        return plane._pack_rank_key(valid, rank, width=2**32)

    with pytest.raises(ValueError, match="bit-budget overflow"):
        jax.jit(over_budget)()


def test_pack_time_key_orders_full_int32_range():
    """_pack_time_key must order legitimately-negative rebased times
    before positive ones and keep invalid slots last."""
    valid = jnp.array([True, True, True, False])
    t = jnp.array([-5, 3, -(2**30), 0], jnp.int32)
    key = plane._pack_time_key(valid, t)
    order = np.argsort(np.asarray(key), kind="stable")
    assert order.tolist() == [2, 0, 1, 3]
