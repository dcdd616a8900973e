"""Fault plane correctness: determinism contracts, checkpoint/restore,
watchdog, self-healing (docs/robustness.md).

The load-bearing guarantees, per ISSUE acceptance:

- faults=None is bitwise-identical to the pre-fault plane, and NEUTRAL
  FaultArrays are bitwise-identical to faults=None, across the
  rr x aqm x no_loss matrix (the tests/test_plane_sortdiet.py pattern);
- a fault schedule is a pure function of (config, seed): two compiles
  are byte-identical, two Manager runs of the same faulted config are
  result-identical;
- checkpoint -> restore -> continue is bitwise-identical to an
  uninterrupted run (device plane), and corrupt checkpoints are
  REFUSED, not half-loaded;
- the round watchdog converts a wedged managed process into a
  structured WatchdogError with per-host blame within the timeout,
  after SIGKILLing the wedged native process so the round can finish;
- the Pallas kernel degrades to XLA on failure and the run completes.
"""

import os
import subprocess
import time as _walltime

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shadow_tpu.core.config import (ConfigError, FaultsOptions,  # noqa: E402
                                    load_config_str)
from shadow_tpu.faults import (CheckpointError,  # noqa: E402
                               WatchdogError, compile_schedule,
                               load_checkpoint, load_plane_checkpoint,
                               neutral_faults, prune_checkpoints,
                               retry_transient, save_plane_checkpoint,
                               write_checkpoint)
from shadow_tpu.faults.watchdog import HostBlame, RoundWatchdog  # noqa: E402
from shadow_tpu.telemetry import make_metrics  # noqa: E402
from shadow_tpu.tpu import ingest, make_params, make_state  # noqa: E402
from shadow_tpu.tpu.plane import window_step  # noqa: E402

MS = 1_000_000
N = 8


def busy_world(rr_mix=True):
    """The telemetry-test busy world: starved buckets, real loss, mixed
    qdiscs — every fault-gate path gets exercised."""
    rng = np.random.default_rng(7)
    lat = rng.integers(1 * MS, 20 * MS, size=(N, N)).astype(np.int32)
    loss = np.full((N, N), 0.3, np.float32)
    qrr = (np.arange(N) % 2 == 0) if rr_mix else np.zeros(N, bool)
    params = make_params(lat, loss, np.full((N,), 80_000, np.int64),
                         qdisc_rr=qrr, down_bw_bps=np.full((N,), 400_000))
    state = make_state(N, egress_cap=8, ingress_cap=8, params=params,
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


def run_windows(state, params, *, windows=4, faults=None, **kw):
    key = jax.random.key(3)
    if faults is not None:
        step = jax.jit(lambda s, f, sh: window_step(
            s, params, key, sh, jnp.int32(10 * MS), faults=f, **kw))
    else:
        step = jax.jit(lambda s, sh: window_step(
            s, params, key, sh, jnp.int32(10 * MS), **kw))
    shift = jnp.int32(0)
    out = []
    for _ in range(windows):
        if faults is not None:
            state, delivered, nxt = step(state, faults, shift)
        else:
            state, delivered, nxt = step(state, shift)
        out.append((state, delivered, nxt))
        shift = jnp.int32(10 * MS)
    return out


# -- parity: faults=None == neutral masks, bitwise, across the matrix ----

@pytest.mark.parametrize("rr_enabled", [False, True])
@pytest.mark.parametrize("router_aqm", [False, True])
@pytest.mark.parametrize("no_loss", [False, True])
def test_neutral_faults_bitwise_invisible(rr_enabled, router_aqm, no_loss):
    state, params = busy_world(rr_mix=rr_enabled)
    kw = dict(rr_enabled=rr_enabled, router_aqm=router_aqm,
              no_loss=no_loss)
    with_f = run_windows(state, params, faults=neutral_faults(N, N), **kw)
    without = run_windows(state, params, **kw)
    for w, ((sa, da, na), (sb, db, nb)) in enumerate(zip(with_f, without)):
        for la, lb in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
            assert np.array_equal(np.asarray(la), np.asarray(lb)), (kw, w)
        for k in da:
            assert np.array_equal(np.asarray(da[k]),
                                  np.asarray(db[k])), (kw, w, k)
        assert int(na) == int(nb), (kw, w)
    assert int(np.asarray(with_f[-1][0].n_fault_dropped).sum()) == 0


# -- fault semantics on device -------------------------------------------

def test_crashed_host_neither_sends_nor_receives():
    state, params = busy_world()
    f = neutral_faults(N, N)._replace(
        host_alive=jnp.asarray(np.arange(N) != 0))
    runs = run_windows(state, params, faults=f, windows=3,
                       rr_enabled=True, router_aqm=False, no_loss=False)
    final = runs[-1][0]
    fd = np.asarray(final.n_fault_dropped)
    # host 0 had queued egress: purged and counted against it; packets
    # routed toward it count against it too
    assert fd.sum() > 0
    assert int(np.asarray(final.n_sent)[0]) == 0
    # nothing in this run is ever misattributed to the loss sample for
    # the crashed host's purge
    neutral = run_windows(state, params, faults=neutral_faults(N, N),
                          windows=3, rr_enabled=True, router_aqm=False,
                          no_loss=False)
    assert int(np.asarray(final.n_loss_dropped).sum()) <= \
        int(np.asarray(neutral[-1][0].n_loss_dropped).sum())


def test_corruption_drops_are_fault_not_loss():
    state, params = busy_world()
    kw = dict(rr_enabled=True, router_aqm=False, no_loss=False, windows=3)
    neutral = run_windows(state, params, faults=neutral_faults(N, N), **kw)
    f = neutral_faults(N, N)._replace(
        corrupt_p=jnp.full((N,), 0.999, jnp.float32))
    corrupted = run_windows(state, params, faults=f, **kw)
    sn, sc = neutral[0][0], corrupted[0][0]
    # the corruption stream is independent: the FIRST window's loss
    # draws are identical (same rng_counter start), so n_loss_dropped
    # matches bitwise while fault drops appear
    assert np.array_equal(np.asarray(sn.n_loss_dropped),
                          np.asarray(sc.n_loss_dropped))
    assert int(np.asarray(sc.n_fault_dropped).sum()) > 0
    assert int(np.asarray(sn.n_fault_dropped).sum()) == 0


def test_latency_degradation_delays_delivery():
    state, params = busy_world()
    kw = dict(rr_enabled=True, router_aqm=False, no_loss=True, windows=1)
    base = run_windows(state, params, faults=neutral_faults(N, N), **kw)
    f = neutral_faults(N, N)._replace(
        lat_mult=jnp.full((N, N), 8, jnp.int32))
    slow = run_windows(state, params, faults=f, **kw)
    # deliveries in the first window shrink (or stay) when every path
    # is 8x slower, and pending deliver times move later
    d_base = int(np.asarray(base[0][1]["mask"]).sum())
    d_slow = int(np.asarray(slow[0][1]["mask"]).sum())
    assert d_slow <= d_base
    assert int(slow[0][2]) >= int(base[0][2])


def test_bandwidth_division_throttles_egress():
    state, params = busy_world()
    kw = dict(rr_enabled=True, router_aqm=False, no_loss=True, windows=2)
    base = run_windows(state, params, faults=neutral_faults(N, N), **kw)
    f = neutral_faults(N, N)._replace(
        bw_div=jnp.full((N,), 64, jnp.int32))
    # start from an empty bucket so the degraded REFILL is what gates
    state2 = state._replace(tb_balance=jnp.zeros((N,), jnp.int32))
    throttled = run_windows(state2, params, faults=f, **kw)
    assert int(np.asarray(throttled[-1][0].n_sent).sum()) < \
        int(np.asarray(base[-1][0].n_sent).sum())


def test_crashed_host_on_fifo_step_neither_sends_nor_receives():
    """The lean FIFO step (rr_enabled=False, the bench's program) gates
    a crashed, link-down host like the RR step: it sends nothing, and
    every packet of its own or routed toward it is a fault drop."""
    state, params = busy_world(rr_mix=False)
    down = jnp.asarray(np.arange(N) != 0)
    f = neutral_faults(N, N)._replace(host_alive=down, link_up=down)
    kw = dict(windows=3, rr_enabled=False, router_aqm=False,
              no_loss=True)
    final = run_windows(state, params, faults=f, **kw)[-1][0]
    neutral = run_windows(state, params, faults=neutral_faults(N, N),
                          **kw)[-1][0]
    assert int(np.asarray(final.n_sent)[0]) == 0
    assert int(np.asarray(neutral.n_sent)[0]) > 0
    assert int(np.asarray(final.n_fault_dropped).sum()) > 0
    assert int(np.asarray(final.n_loss_dropped).sum()) == 0


# -- schedule compile: seeded, deterministic, validated ------------------

HOSTS = [f"h{i}" for i in range(6)]


def _opts(**kw):
    return FaultsOptions(**kw)


def compile_(opts, seed=11, n_nodes=4):
    return compile_schedule(opts, host_names=HOSTS, n_nodes=n_nodes,
                            seed=seed, stop_time_ns=10_000 * MS)


def test_schedule_compile_deterministic():
    opts = _opts(
        events=[{"at": "1s", "kind": "host_crash", "host": "h1"},
                {"at": "2s", "kind": "host_reboot", "host": "h1"}],
        random={"host_crashes": {"count": 3, "window": ["1s", "8s"],
                                 "downtime": "500ms"},
                "iface_flaps": {"count": 2, "window": ["2s", "9s"],
                                "downtime": "250ms"}})
    a, b = compile_(opts), compile_(opts)
    assert a.fingerprint() == b.fingerprint()
    assert [e.__dict__ for e in a.events] == [e.__dict__ for e in b.events]
    c = compile_(opts, seed=12)
    assert c.fingerprint() != a.fingerprint()
    # explicit events don't move with the seed — only generator draws do
    explicit = [e for e in c.events if e.time_ns == 1_000 * MS]
    assert any(e.kind == "host_crash" and e.host == "h1" for e in explicit)


def test_schedule_masks_evolve():
    opts = _opts(events=[
        {"at": "1s", "kind": "host_crash", "host": "h2"},
        {"at": "2s", "kind": "host_reboot", "host": "h2"},
        {"at": "1s", "kind": "link_degrade", "src_node": 0, "dst_node": 1,
         "latency_mult": 4, "until": "3s"},
        {"at": "1s", "kind": "corrupt_burst", "host": "h0", "p": 0.25,
         "duration": "1s"},
        {"at": "1s", "kind": "host_degrade", "host": "h3",
         "bandwidth_div": 2, "duration": "500ms"},
    ])
    s = compile_(opts)
    s.advance(1_000 * MS)
    assert not s.host_alive[2]
    assert s.lat_mult[0, 1] == 4 and s.lat_mult[1, 0] == 4  # symmetric
    assert s.corrupt_p[0] == pytest.approx(0.25)
    assert s.bw_div[3] == 2
    s.advance(2_000 * MS)
    assert s.host_alive[2]
    assert s.corrupt_p[0] == 0.0
    assert s.bw_div[3] == 1
    assert s.lat_mult[0, 1] == 4
    s.advance(3_000 * MS)
    assert s.lat_mult[0, 1] == 1
    assert s.remaining == 0


def test_device_arrays_are_isolated_from_schedule_mutation():
    """jnp.asarray may zero-copy alias a numpy buffer on CPU; the
    schedule mutates its masks in place on the next advance(), so the
    uploaded FaultArrays MUST be private copies (this was an observed
    cross-process nondeterminism bug, fixed in faults/plane.py)."""
    opts = _opts(events=[
        {"at": "1s", "kind": "host_crash", "host": "h2"},
        {"at": "2s", "kind": "host_reboot", "host": "h2"}])
    s = compile_(opts)
    s.advance(1_000 * MS)
    arrays = s.device_arrays()
    before = np.asarray(arrays.host_alive).copy()
    s.advance(2_000 * MS)  # mutates s.host_alive in place
    assert np.array_equal(np.asarray(arrays.host_alive), before)


@pytest.mark.parametrize("bad,msg", [
    ([{"at": "1s", "kind": "meteor", "host": "h0"}], "unknown kind"),
    ([{"kind": "host_crash", "host": "h0"}], "missing required"),
    ([{"at": "1s", "kind": "host_crash", "host": "nope"}],
     "not a configured host"),
    ([{"at": "1s", "kind": "corrupt_burst", "host": "h0", "p": 1.5,
       "duration": "1s"}], "probability"),
    ([{"at": "1s", "kind": "corrupt_burst", "host": "h0", "p": 0.5}],
     "requires duration"),
    ([{"at": "1s", "kind": "link_degrade", "src_node": 0, "dst_node": 1,
       "latency_mult": 0}], "latency_mult"),
    ([{"at": "1s", "kind": "host_crash", "host": "h0", "bogus": 1}],
     "unknown field"),
    ([{"at": "0s", "kind": "host_crash", "host": "h0"}], "at > 0"),
])
def test_schedule_validation_errors(bad, msg):
    with pytest.raises(ConfigError, match=msg):
        compile_(_opts(events=bad))


def test_faults_config_block_parses():
    cfg = load_config_str("""
general: {stop_time: 5s, seed: 3}
network: {graph: {type: 1_gbit_switch}}
faults:
  watchdog: 30s
  device_retries: 2
  checkpoint: {interval: 2s, keep: 3}
  events:
    - {at: 1s, kind: host_crash, host: a}
hosts:
  a: {network_node_id: 0}
""")
    assert cfg.faults.watchdog == 30 * 1_000_000_000
    assert cfg.faults.device_retries == 2
    assert cfg.faults.checkpoint.interval == 2 * 1_000_000_000
    assert cfg.faults.checkpoint.keep == 3
    assert cfg.faults.any_injection()


def test_faults_config_validation():
    base = ("general: {stop_time: 5s}\n"
            "network: {graph: {type: 1_gbit_switch}}\n"
            "hosts: {a: {network_node_id: 0}}\n")
    with pytest.raises(ConfigError, match="watchdog"):
        load_config_str(base + "faults: {watchdog: 0s}")
    with pytest.raises(ConfigError, match="keep"):
        load_config_str(base + "faults: {checkpoint: {keep: 0}}")
    with pytest.raises(ConfigError, match="interval"):
        load_config_str(base + "faults: {checkpoint: {interval: 0s}}")


# -- checkpoints: atomic, checksummed, bitwise restore -------------------

def test_checkpoint_roundtrip_and_checksum_guard(tmp_path):
    path = str(tmp_path / "ck")
    meta = {"kind": "plane", "clock_ns": 5}
    arrays = {"a": np.arange(10, dtype=np.int32),
              "b": np.ones((3, 3), np.float32)}
    write_checkpoint(path, meta=meta, arrays=arrays)
    m2, a2 = load_checkpoint(path)
    assert m2 == meta
    assert np.array_equal(a2["a"], arrays["a"])
    # corrupt one payload byte -> refused loudly
    target = os.path.join(path, "arrays.npz")
    blob = bytearray(open(target, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(target, "wb").write(bytes(blob))
    with pytest.raises(CheckpointError, match="checksum mismatch"):
        load_checkpoint(path)


def test_checkpoint_overwrite_is_atomic(tmp_path):
    path = str(tmp_path / "ck")
    write_checkpoint(path, meta={"kind": "flow", "v": 1}, arrays={})
    write_checkpoint(path, meta={"kind": "flow", "v": 2}, arrays={})
    meta, _ = load_checkpoint(path)
    assert meta["v"] == 2
    assert not [e for e in os.listdir(tmp_path)
                if ".tmp-" in e or ".old-" in e]


def test_checkpoint_prune(tmp_path):
    for i in range(5):
        write_checkpoint(str(tmp_path / f"ckpt-{i:012d}"),
                         meta={"kind": "manager"}, arrays={})
    os.makedirs(tmp_path / "ckpt-x.tmp-123")
    prune_checkpoints(str(tmp_path), keep=2)
    left = sorted(os.listdir(tmp_path))
    assert left == ["ckpt-000000000003", "ckpt-000000000004"]


def test_plane_checkpoint_resume_bitwise(tmp_path):
    """Kill/resume parity: run 8 faulted windows; snapshot after 4;
    restore and run the rest; final state bitwise == uninterrupted."""
    state0, params = busy_world()
    key = jax.random.key(3)
    f_live = neutral_faults(N, N)._replace(
        host_alive=jnp.asarray(np.arange(N) != 1),
        corrupt_p=jnp.full((N,), 0.2, jnp.float32))

    def advance(state, metrics, windows, first_shift):
        step = jax.jit(lambda s, m, fa, sh: window_step(
            s, params, key, sh, jnp.int32(10 * MS), rr_enabled=True,
            faults=fa, metrics=m))
        shift = first_shift
        for _ in range(windows):
            state, _d, _n, metrics = step(state, metrics, f_live, shift)
            shift = jnp.int32(10 * MS)
        return state, metrics

    full_s, full_m = advance(state0, make_metrics(N), 8, jnp.int32(0))
    half_s, half_m = advance(state0, make_metrics(N), 4, jnp.int32(0))
    path = str(tmp_path / "mid")
    save_plane_checkpoint(
        path, state=half_s, clock_ns=4 * 10 * MS,
        rng_key_data=jax.random.key_data(key), faults=f_live,
        metrics=half_m, extra_arrays={"cursor": np.int64(4)})
    restored = load_plane_checkpoint(
        path, state_template=half_s, faults_template=f_live,
        metrics_template=half_m)
    assert int(restored["extra"]["cursor"]) == 4
    res_s, res_m = advance(restored["state"], restored["metrics"], 4,
                           jnp.int32(10 * MS))
    for la, lb in zip(jax.tree.leaves(full_s), jax.tree.leaves(res_s)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))
    for la, lb in zip(jax.tree.leaves(full_m), jax.tree.leaves(res_m)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


# -- self-healing: transient-error retry ---------------------------------

def test_retry_transient_retries_then_succeeds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")
        return "ok"

    assert retry_transient(flaky, attempts=3, backoff_s=0.001) == "ok"
    assert len(calls) == 3


def test_retry_transient_never_retries_real_bugs():
    calls = []

    def buggy():
        calls.append(1)
        raise ValueError("RESOURCE_EXHAUSTED looks transient but is not")

    with pytest.raises(ValueError):
        retry_transient(buggy, attempts=3, backoff_s=0.001)
    assert len(calls) == 1


def test_backoff_schedule_seed_pinned():
    """The exact delay floats for one (seed, what): the schedule is a
    pure function of its arguments, so these values are FROZEN — a
    drift here means retry timing silently changed for every run."""
    from shadow_tpu.faults.healing import backoff_schedule

    got = backoff_schedule(4, base_s=0.05, cap_s=2.0, jitter=0.5,
                           seed=0, what="device dispatch")
    assert got == backoff_schedule(4, base_s=0.05, cap_s=2.0,
                                   jitter=0.5, seed=0,
                                   what="device dispatch")
    assert len(got) == 4
    for k, d in enumerate(got):
        unjittered = min(2.0, 0.05 * 2.0 ** k)
        # jitter only SHAVES: (1 - 0.5) * base <= delay <= base
        assert unjittered * 0.5 <= d <= unjittered
    # pin the first draw to 12 decimal places: sha256("0|device
    # dispatch|0")[:8] mapped to [0,1) is a frozen constant
    assert round(got[0], 12) == round(0.045871920679567496, 12)


def test_backoff_schedule_seed_and_what_sensitivity():
    from shadow_tpu.faults.healing import backoff_schedule

    base = backoff_schedule(3, seed=0)
    assert backoff_schedule(3, seed=1) != base
    assert backoff_schedule(3, seed=0, what="checkpoint write") != base


def test_backoff_schedule_cap_and_zero_jitter():
    from shadow_tpu.faults.healing import backoff_schedule

    # jitter=0 is the pure capped exponential, exactly
    got = backoff_schedule(8, base_s=0.05, cap_s=0.4, jitter=0.0)
    assert got == (0.05, 0.1, 0.2, 0.4, 0.4, 0.4, 0.4, 0.4)
    assert backoff_schedule(0) == ()
    with pytest.raises(ValueError, match="attempts"):
        backoff_schedule(-1)
    with pytest.raises(ValueError, match="jitter"):
        backoff_schedule(2, jitter=1.5)


def test_retry_transient_sleeps_the_pinned_schedule(monkeypatch):
    """The sleeps retry_transient performs ARE the backoff_schedule
    floats, in order — no other randomness sneaks in."""
    from shadow_tpu.faults import healing

    slept = []
    monkeypatch.setattr(healing._walltime, "sleep", slept.append)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 4:
            raise RuntimeError("UNAVAILABLE: link reset")
        return "ok"

    assert retry_transient(flaky, attempts=3, backoff_s=0.05,
                           cap_s=2.0, jitter=0.5, seed=7) == "ok"
    want = healing.backoff_schedule(3, base_s=0.05, cap_s=2.0,
                                    jitter=0.5, seed=7)
    assert tuple(slept) == want[:3]


def test_retry_config_wires_cap_jitter_seed_to_transport():
    """faults.retry_cap / retry_jitter / the seed convention reach the
    transport attrs the Manager dispatches through."""
    from shadow_tpu.core.manager import Manager

    cfg = load_config_str("""
general: {stop_time: 1s, seed: 9}
network: {graph: {type: 1_gbit_switch}}
experimental: {scheduler: serial, use_tpu_transport: true}
faults: {device_retries: 2, retry_backoff: 10ms, retry_cap: 3s,
         retry_jitter: 0.25}
hosts: {a: {network_node_id: 0, processes: []}}
""")
    mgr = Manager(cfg)
    tr = mgr.transport
    assert tr.retry_attempts == 2
    assert tr.retry_backoff_s == pytest.approx(0.01)
    assert tr.retry_cap_s == pytest.approx(3.0)
    assert tr.retry_jitter == pytest.approx(0.25)
    assert tr.retry_seed == 9  # faults.seed unset -> general.seed


def test_retry_cap_below_backoff_refused():
    with pytest.raises(ConfigError, match="retry_cap"):
        load_config_str("""
general: {stop_time: 1s}
network: {graph: {type: 1_gbit_switch}}
faults: {retry_backoff: 2s, retry_cap: 1s}
hosts: {a: {network_node_id: 0, processes: []}}
""")


# -- the round watchdog ---------------------------------------------------

def test_watchdog_converts_wedge_into_structured_error():
    """A round wedged on a live native process: the watchdog fires
    within the timeout, collects blame, SIGKILLs the wedged pid, the
    round completes, and the strike carries the blame."""
    dummy = subprocess.Popen(["sleep", "300"])
    try:
        def collect(_round_start):
            return [HostBlame("hostA", ["hostA.wedge.0"], [dummy.pid],
                              [dummy.pid])]

        wd = RoundWatchdog(0.3, collect)
        t0 = _walltime.monotonic()
        with wd.guard(round_start_ns=123):
            # the "round": blocked until the wedged process dies —
            # exactly what a worker stuck in recv_from_shim does
            dummy.wait(timeout=30)
        assert wd.strike is not None
        elapsed = _walltime.monotonic() - t0
        assert elapsed < 10  # fired within the timeout, not the 300s
        err = wd.strike
        assert isinstance(err, WatchdogError)
        assert err.killed == [dummy.pid]
        assert "hostA" in str(err)
        assert "wedge" in str(err)
    finally:
        if dummy.poll() is None:
            dummy.kill()
        dummy.wait()


def test_watchdog_disarms_on_healthy_round():
    fired = []
    wd = RoundWatchdog(0.2, lambda t: fired.append(t) or [])
    with wd.guard(round_start_ns=1):
        pass
    _walltime.sleep(0.35)
    assert not fired and wd.strike is None


def test_watchdog_timeout_must_be_positive():
    for bad in (0, -1.5):
        with pytest.raises(ValueError, match="positive"):
            RoundWatchdog(bad, lambda t: [])


def test_kill_blamed_skips_dead_pids_kills_live_ones():
    """A blamed pid that already exited (raced its own death) is
    skipped silently; the live wedged one is SIGKILLed and reported."""
    from shadow_tpu.faults.watchdog import kill_blamed

    dead = subprocess.Popen(["true"])
    dead.wait()  # reaped: its pid no longer resolves
    live = subprocess.Popen(["sleep", "300"])
    try:
        blame = [HostBlame("hostA", ["hostA.gone.0"], [dead.pid],
                           []),
                 HostBlame("hostB", ["hostB.wedge.0"], [live.pid],
                           [live.pid])]
        killed = kill_blamed(blame)
        assert killed == [live.pid]
        live.wait(timeout=10)  # really dead, not just signalled
    finally:
        if live.poll() is None:
            live.kill()
            live.wait()


def test_watchdog_blame_collection_failure_still_strikes():
    """A collect_blame that itself dies must not lose the strike: the
    round still fails structured, attributed as 'no live blame'."""

    def broken(_round_start):
        raise RuntimeError("process table scan exploded")

    wd = RoundWatchdog(0.15, broken)
    wd.arm(round_start_ns=77)
    deadline = _walltime.monotonic() + 10
    while wd.strike is None and _walltime.monotonic() < deadline:
        _walltime.sleep(0.02)
    assert isinstance(wd.strike, WatchdogError)
    assert wd.strike.killed == []
    assert "no live blame" in str(wd.strike)
    assert wd.strike.round_start_ns == 77


def _manager_watchdog_sim(monkeypatch):
    """A Manager round wedged by an app that spins until a real native
    process dies; a stub managed-process entry routes the watchdog's
    blame (and SIGKILL) at that pid."""
    from shadow_tpu import apps as app_registry
    from shadow_tpu.core.manager import Manager

    dummy = subprocess.Popen(["sleep", "300"])

    def wedge(api):
        # poll()/wait() reap the child; a bare os.kill(pid, 0) probe
        # would see the SIGKILLed zombie as alive forever
        while dummy.poll() is None:
            _walltime.sleep(0.02)  # wall block, like a wedged shim read
        return 0
        yield  # pragma: no cover - makes this a generator function

    monkeypatch.setitem(app_registry.APP_REGISTRY, "wedge-app", wedge)
    cfg = load_config_str("""
general: {stop_time: 3s, seed: 5}
network: {graph: {type: 1_gbit_switch}}
experimental: {scheduler: serial}
faults: {watchdog: 1s}
hosts:
  a:
    network_node_id: 0
    processes:
    - {path: wedge-app, start_time: 1s, expected_final_state: running}
""")
    mgr = Manager(cfg)

    class StubProc:
        is_alive = True
        proc = dummy

    mgr._respawn_by_host["a"].append(("a.wedge.native", None,
                                     {"proc": StubProc()}, None))
    return mgr, dummy


def test_manager_watchdog_end_to_end(monkeypatch):
    mgr, dummy = _manager_watchdog_sim(monkeypatch)
    try:
        with pytest.raises(WatchdogError) as ei:
            mgr.run()
        assert "a.wedge.native" in str(ei.value)
        assert dummy.pid in ei.value.killed
        assert dummy.poll() is not None  # the wedged native was killed
    finally:
        if dummy.poll() is None:
            dummy.kill()
        dummy.wait()


# -- Manager-level fault injection ----------------------------------------

FAULT_SIM = """
general: {{stop_time: 5s, seed: 11}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{scheduler: serial}}
faults:
  events:
    - {{at: 3s, kind: host_crash, host: server}}
    - {{at: 4s, kind: host_reboot, host: server}}
    - {{at: 1500ms, kind: corrupt_burst, host: client, p: 1.0,
       duration: 4s}}
{extra}
hosts:
  server:
    network_node_id: 0
    processes:
    - {{path: udp-echo-server, args: ["9000"], start_time: 1s,
       expected_final_state: running}}
  client:
    network_node_id: 0
    processes:
    - {{path: udp-client, args: ["server", "9000", "5", "50"],
       start_time: 2s, expected_final_state: running}}
"""


def _run_fault_sim(extra=""):
    from shadow_tpu.core.manager import Manager

    cfg = load_config_str(FAULT_SIM.format(extra=extra))
    mgr = Manager(cfg)
    stats = mgr.run()
    return mgr, stats


def test_manager_fault_sim_injects_and_recovers():
    mgr, stats = _run_fault_sim()
    hosts = mgr.host_stats()
    # the corruption burst (p=1.0 from 1.5s) eats the client's pings:
    # bucketed as FAULT drops — in the tracker counters AND the final
    # SimStats — never in the wire-loss packets_dropped
    assert hosts["client"]["packets_dropped_fault"] > 0
    assert stats.packets_dropped_fault > 0
    assert stats.packets_dropped == 0  # no wire loss on this graph
    # the server crashed at 3s and its respawn left it RUNNING when the
    # expected-final-state check ran (before teardown): both processes
    # met their expectations, so the fault round-trip recovered fully
    assert stats.process_failures == []


def test_manager_fault_sim_deterministic():
    _m1, s1 = _run_fault_sim()
    _m2, s2 = _run_fault_sim()
    a, b = s1.as_dict(), s2.as_dict()
    a.pop("wall_seconds"), b.pop("wall_seconds")
    assert a == b
    assert _m1.host_stats() == _m2.host_stats()
    assert _m1.fault_schedule.fingerprint() == \
        _m2.fault_schedule.fingerprint()


def test_manager_periodic_and_emergency_checkpoints(tmp_path):
    from shadow_tpu.core.manager import Manager

    cfg = load_config_str(FAULT_SIM.format(
        extra=f"  checkpoint: {{interval: 1s, directory: "
              f"{tmp_path / 'ck'}, keep: 2}}"))
    mgr = Manager(cfg)
    mgr.run()
    names = sorted(os.listdir(tmp_path / "ck"))
    assert names and all(n.startswith("ckpt-") for n in names)
    assert len(names) <= 2  # pruned to keep
    meta, _arrays = load_checkpoint(str(tmp_path / "ck" / names[-1]))
    assert meta["kind"] == "manager" and meta["resumable"] is False
    assert "server" in meta["hosts"]

    # crash path: wedge the scheduler to raise mid-run -> emergency
    cfg2 = load_config_str(FAULT_SIM.format(
        extra=f"  checkpoint: {{directory: {tmp_path / 'ck2'}}}"))
    mgr2 = Manager(cfg2)
    orig = mgr2.scheduler.run_round
    calls = []

    def boom(active, end):
        if calls:
            raise RuntimeError("injected crash")
        calls.append(1)
        return orig(active, end)

    mgr2.scheduler.run_round = boom
    with pytest.raises(RuntimeError, match="injected crash"):
        mgr2.run()
    meta, _ = load_checkpoint(str(tmp_path / "ck2" / "emergency"))
    assert meta["reason"] == "emergency"


def test_round_loop_resume_refused():
    from shadow_tpu.core.manager import Manager

    cfg = load_config_str(FAULT_SIM.format(extra=""))
    mgr = Manager(cfg)
    mgr.resume_from = "/nonexistent/ckpt"
    with pytest.raises(ConfigError, match="flow-engine"):
        mgr.run()
