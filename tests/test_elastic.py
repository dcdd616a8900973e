"""Elastic capacity plane tests (docs/robustness.md "Elastic capacity",
docs/determinism.md "Growth is bitwise-invisible"):

- the parity matrix (rr x aqm x no_loss): a run that starts with tiny
  rings and grows on demand ends canonically bitwise-identical to a run
  pre-provisioned at the final capacity — same delivered stream, same
  counters, clean guards on both;
- grow_state migrates columns/sentinels bitwise and refuses to shrink;
- strict mode raises CapacityError with per-host blame; fixed mode
  records a structured once-per-run drop event; an exhausted growth
  budget commits the overflowing attempt loudly;
- plane checkpoints store ring dims and restore across a resize
  (CE=32 -> CE=64) with digest-verified state equivalence;
- the device transport grows its in-flight rings without perturbing
  the packet-status trace (sync + mirrored), and promotes drops to
  CapacityError under strict;
- the flow engine's queue-slot re-runs land in the unified capacity
  trajectory; strict refuses them;
- the `capacity:` config block and the device ring caps parse/fail at
  config time.
"""

import hashlib
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from shadow_tpu.core.capacity import (CapacityError,  # noqa: E402
                                      CapacityTrajectory, RingPolicy,
                                      next_pow2)
from shadow_tpu.core.config import (ConfigError,  # noqa: E402
                                    load_config_str)
from shadow_tpu.guards import make_guards, summarize  # noqa: E402
from shadow_tpu.tpu import elastic, profiling  # noqa: E402
from shadow_tpu.tpu.plane import (ingest, make_params,  # noqa: E402
                                  make_state, window_step)

MS = 1_000_000
N = 24


def _assert_trees_equal(a, b):
    for name, la, lb in zip(a._fields, a, b):
        la_l = jax.tree.leaves(la)
        lb_l = jax.tree.leaves(lb)
        for x, y in zip(la_l, lb_l):
            assert np.array_equal(np.asarray(x), np.asarray(y)), name


def _params(rr=False):
    rng = np.random.default_rng(3)
    m = 4
    lat = rng.integers(1 * MS, 30 * MS, size=(m, m)).astype(np.int64)
    lat = np.minimum(lat, lat.T)
    loss = np.full((m, m), 0.02, np.float32)
    host_node = (np.arange(N) % m).astype(np.int32)
    qdisc_rr = (np.arange(N) % 2 == 0) if rr else None
    return make_params(
        lat, loss, np.full((N,), 1_000_000_000, np.int64),
        host_node=host_node, qdisc_rr=qdisc_rr,
        down_bw_bps=np.full((N,), 500_000_000, np.int64))


def _init_state(params, ce, ci):
    return make_state(N, egress_cap=ce, ingress_cap=ci, params=params,
                      initial_tokens=np.asarray(params.tb_cap))


def _batches(n_windows, per_window=96, seed=7):
    """Capacity-independent injection batches: flat [B] arrays whose
    content never references a ring shape."""
    rng = np.random.default_rng(seed)
    out, seq0 = [], 0
    for _ in range(n_windows):
        src = rng.integers(0, N, per_window).astype(np.int32)
        dst = rng.integers(0, N, per_window).astype(np.int32)
        seq = np.arange(seq0, seq0 + per_window, dtype=np.int32)
        seq0 += per_window
        out.append((src, dst,
                    np.full(per_window, 1200, np.int32),
                    seq.copy(), seq,
                    np.zeros(per_window, bool)))
    return out


def _drive(params, state, batches, *, rr, aqm, no_loss, policy=None,
           guards=None, expect_clean=False):
    """Run len(batches) windows of ingest + window_step under the
    capacity policy; returns (state, delivered-stream, guards).
    The delivered stream collects masked entries in presentation order
    — the capacity-independent witness of what the hosts saw."""
    key = jax.random.key(5)
    window = jnp.int32(10 * MS)
    step = jax.jit(lambda st, sh, g: window_step(
        st, params, key, sh, window, rr_enabled=rr, router_aqm=aqm,
        no_loss=no_loss, guards=g))
    stream = []
    shift = jnp.int32(0)
    for w, (src, dst, nbytes, prio, seq, ctrl) in enumerate(batches):
        def attempt(st, _g=guards, _sh=shift, _b=(src, dst, nbytes, prio,
                                                  seq, ctrl)):
            bsrc, bdst, bbytes, bprio, bseq, bctrl = map(jnp.asarray, _b)
            st1 = ingest(st, bsrc, bdst, bbytes, bprio, bseq, bctrl)
            eg = st1.n_overflow_dropped - st.n_overflow_dropped
            res = step(st1, _sh, _g)
            if _g is not None:
                st2, deliv, _nx, g2 = res
            else:
                st2, deliv, _nx = res
                g2 = None
            inn = st2.n_overflow_dropped - st1.n_overflow_dropped
            return (st2, deliv, g2), eg, inn

        if policy is None:
            out, eg, inn = attempt(state)
            if expect_clean:
                assert int(np.asarray(eg).sum()) == 0
                assert int(np.asarray(inn).sum()) == 0
        else:
            out, _ = elastic.run_elastic_window(
                state, attempt, policy, time_ns=(w + 1) * 10 * MS)
        state, deliv, guards = out
        mask = np.asarray(deliv["mask"])
        cols = {k: np.asarray(deliv[k]) for k in
                ("src", "seq", "deliver_rel", "bytes")}
        rows, lanes = np.nonzero(mask)
        stream.append([
            (int(r), int(cols["src"][r, c]), int(cols["seq"][r, c]),
             int(cols["deliver_rel"][r, c]), int(cols["bytes"][r, c]))
            for r, c in zip(rows, lanes)])
        shift = window
    return state, stream, guards


# -- the headline: elastic == pre-provisioned, bitwise --------------------

@pytest.mark.slow  # 4-cell growth-parity matrix (~62s); stays GATING
# in CI's tier-1-overflow unfiltered step
@pytest.mark.parametrize("rr,aqm,no_loss", [
    (False, False, False),
    (True, False, False),
    (False, True, False),
    (True, True, True),
])
def test_elastic_parity_matrix(rr, aqm, no_loss):
    """A run that starts at (CE=4, CI=6) and grows on demand ends
    canonically bitwise-identical to a run pre-provisioned at the
    final capacity: same live state, same delivered stream, clean
    guards on both, and at least one growth actually happened."""
    params = _params(rr=rr)
    batches = _batches(6)
    policy = RingPolicy(mode="elastic", max_doublings=4,
                        egress_cap=4, ingress_cap=6)
    s_el, d_el, g_el = _drive(
        params, _init_state(params, 4, 6), batches, rr=rr, aqm=aqm,
        no_loss=no_loss, policy=policy, guards=make_guards(N))
    assert len(policy.trajectory.growth_events()) >= 1, \
        policy.trajectory.events
    cef, cif = policy.egress_cap, policy.ingress_cap
    s_pre, d_pre, g_pre = _drive(
        params, elastic.grow_state(_init_state(params, 4, 6), cef, cif),
        batches, rr=rr, aqm=aqm, no_loss=no_loss, policy=None,
        guards=make_guards(N), expect_clean=True)
    assert d_el == d_pre
    _assert_trees_equal(elastic.canonical_state(s_el),
                        elastic.canonical_state(s_pre))
    assert summarize(g_el)["clean"], summarize(g_el)
    assert summarize(g_pre)["clean"]
    # guard accumulators match too: re-executed attempts were restored
    # from the snapshot, never double-counted
    _assert_trees_equal(g_el, g_pre)


def test_elastic_zero_ring_drops():
    """The committed elastic stream never contains a ring-full drop
    (the overflowing attempts were discarded)."""
    params = _params()
    policy = RingPolicy(mode="elastic", max_doublings=4,
                        egress_cap=4, ingress_cap=6)
    s, _d, _g = _drive(params, _init_state(params, 4, 6), _batches(6),
                       rr=False, aqm=False, no_loss=False, policy=policy)
    assert int(np.asarray(s.n_overflow_dropped).sum()) == 0
    assert len(policy.trajectory.growth_events()) >= 1


# -- grow_state / canonical_state ----------------------------------------

def test_grow_state_matches_preprovisioned_fresh_world():
    world = profiling.build_world(16, warmup_windows=0, egress_cap=4,
                                  ingress_cap=8)
    grown = elastic.grow_state(world["state"], 8, 16)
    big = profiling.build_world(16, warmup_windows=0, egress_cap=8,
                                ingress_cap=16)["state"]
    _assert_trees_equal(grown, big)  # raw bitwise, not just canonical
    assert elastic.ring_dims(grown) == (8, 16)


def test_grow_state_noop_and_shrink_refused():
    st = make_state(4, egress_cap=8, ingress_cap=8)
    assert elastic.grow_state(st, 8, 8) is st
    with pytest.raises(ValueError, match="shrink"):
        elastic.grow_state(st, 4, 8)


def test_next_pow2():
    assert [next_pow2(v) for v in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 16]


# -- policy modes ---------------------------------------------------------

def test_strict_mode_raises_with_blame():
    params = _params()
    policy = RingPolicy(mode="strict", egress_cap=4, ingress_cap=6)
    with pytest.raises(CapacityError) as ei:
        _drive(params, _init_state(params, 4, 6), _batches(4),
               rr=False, aqm=False, no_loss=False, policy=policy)
    assert "strict" in str(ei.value)
    assert ei.value.blame  # per-host indices named


def test_fixed_mode_records_drop_once_and_commits():
    params = _params()
    policy = RingPolicy(mode="fixed", egress_cap=4, ingress_cap=6)
    s, _d, _g = _drive(params, _init_state(params, 4, 6), _batches(4),
                       rr=False, aqm=False, no_loss=False, policy=policy)
    assert int(np.asarray(s.n_overflow_dropped).sum()) > 0
    drops = [e for e in policy.trajectory.events
             if e["kind"] == "capacity-drop"]
    assert drops and elastic.ring_dims(s) == (4, 6)
    # once-per-run per ring, not once per window
    assert len([e for e in drops if e["ring"] == "egress"]) <= 1


def test_exhausted_budget_commits_with_drops():
    params = _params()
    policy = RingPolicy(mode="elastic", max_doublings=0,
                        egress_cap=4, ingress_cap=6)
    s, _d, _g = _drive(params, _init_state(params, 4, 6), _batches(4),
                       rr=False, aqm=False, no_loss=False, policy=policy)
    assert int(np.asarray(s.n_overflow_dropped).sum()) > 0
    assert any(e["kind"] == "capacity-exhausted"
               for e in policy.trajectory.events)
    assert not policy.trajectory.growth_events()


# -- recompile discipline -------------------------------------------------

def test_growth_recompiles_are_log2_bounded():
    """The PR-1 recompile-counter harness over grown ring shapes: ONE
    compile per (CE, CI) shape, every same-shape window a cache hit —
    so an elastic run pays at most 1 + growth-events compiles."""
    from shadow_tpu.analysis.recompile import CompileCounter

    counter = CompileCounter(
        window_step,
        static_argnames=("rr_enabled", "router_aqm", "no_loss"))
    params = _params()
    base = _init_state(params, 4, 6)
    key = jax.random.key(5)
    for ce, ci in [(4, 6), (8, 8), (16, 16)]:
        counter.expect(1)  # first sight of this ring shape
        st = elastic.grow_state(base, ce, ci)
        for r in range(3):
            st, _d, _n = counter(
                st, params, key, np.int32(0 if r == 0 else 10 * MS),
                np.int32(10 * MS), rr_enabled=False, router_aqm=False,
                no_loss=False)
    assert counter.unexpected_misses == 0, counter.log


# -- respawn workload is capacity-independent -----------------------------

def test_respawn_batch_capacity_independent():
    """The PHOLD respawn seq rank counts DUE lanes, not columns — the
    same delivered entries at different ring widths (due lanes sit at
    the row tail) must respawn identical (dst, seq) packets."""
    spawn_seq = jnp.asarray([100, 200], jnp.int32)

    def deliv(ci, due_per_row=(2, 1)):
        mask = np.zeros((2, ci), bool)
        src = np.zeros((2, ci), np.int32)
        seq = np.zeros((2, ci), np.int32)
        for r, k in enumerate(due_per_row):
            for j in range(k):
                c = ci - k + j  # tail lanes
                mask[r, c] = True
                src[r, c] = r + 3
                seq[r, c] = 50 + 10 * r + j
        return {"mask": jnp.asarray(mask), "src": jnp.asarray(src),
                "seq": jnp.asarray(seq)}

    outs = []
    for ci in (4, 8):
        mask, dst, _b, seq, _c = profiling.respawn_batch(
            deliv(ci), spawn_seq, jnp.int32(2), 16, ci)
        m = np.asarray(mask)
        outs.append((np.asarray(dst)[m].tolist(),
                     np.asarray(seq)[m].tolist()))
    assert outs[0] == outs[1]


# -- checkpoint/restore across a resize -----------------------------------

def _digest(*trees):
    h = hashlib.sha256()
    for tree in trees:
        for leaf in jax.tree.leaves(jax.device_get(tree)):
            arr = np.asarray(leaf)
            h.update(str(arr.dtype).encode())
            h.update(arr.tobytes())
    return h.hexdigest()


def test_plane_checkpoint_restores_into_grown_rings(tmp_path):
    from shadow_tpu.faults import (load_plane_checkpoint,
                                   save_plane_checkpoint)

    world = profiling.build_world(16, warmup_windows=2, egress_cap=32,
                                  ingress_cap=32)
    state = world["state"]
    key_data = jax.random.key_data(world["rng_root"])
    path = str(tmp_path / "ck")
    save_plane_checkpoint(path, state=state, clock_ns=123,
                          rng_key_data=key_data)
    from shadow_tpu.faults.checkpoint import load_checkpoint

    meta, _arrays = load_checkpoint(path)
    assert meta["ring_dims"] == {"egress_cap": 32, "ingress_cap": 32}

    # restore a CE=32 checkpoint into a CE=64/CI=64 world: digest must
    # equal growing the live state directly
    restored = load_plane_checkpoint(path, state_template=state,
                                     grow_to=(64, 64))
    assert elastic.ring_dims(restored["state"]) == (64, 64)
    assert _digest(restored["state"]) == \
        _digest(elastic.grow_state(state, 64, 64))
    # and the grown world steps identically to the directly-grown one
    out_a = window_step(restored["state"], world["params"],
                        world["rng_root"], jnp.int32(10 * MS),
                        world["window"], rr_enabled=False)
    out_b = window_step(elastic.grow_state(state, 64, 64),
                        world["params"], world["rng_root"],
                        jnp.int32(10 * MS), world["window"],
                        rr_enabled=False)
    assert _digest(out_a[0]) == _digest(out_b[0])


def test_plane_checkpoint_grow_to_refuses_shrink(tmp_path):
    from shadow_tpu.faults import (load_plane_checkpoint,
                                   save_plane_checkpoint)

    world = profiling.build_world(8, warmup_windows=0, egress_cap=16,
                                  ingress_cap=16)
    path = str(tmp_path / "ck")
    save_plane_checkpoint(path, state=world["state"], clock_ns=0,
                          rng_key_data=jax.random.key_data(
                              world["rng_root"]))
    with pytest.raises(ValueError, match="shrink"):
        load_plane_checkpoint(path, state_template=world["state"],
                              grow_to=(8, 16))


# -- config block ---------------------------------------------------------

BASE_CFG = """
general: {stop_time: 1s}
network: {graph: {type: 1_gbit_switch}}
hosts: {h0: {network_node_id: 0}}
"""


def test_capacity_config_block_parses():
    cfg = load_config_str(BASE_CFG + "capacity: {mode: elastic, "
                                     "max_doublings: 5}")
    assert cfg.capacity.mode == "elastic"
    assert cfg.capacity.max_doublings == 5
    # defaults
    cfg = load_config_str(BASE_CFG)
    assert cfg.capacity.mode == "fixed"
    assert cfg.capacity.max_doublings == 3


def test_capacity_config_validation():
    with pytest.raises(ConfigError, match="capacity.mode"):
        load_config_str(BASE_CFG + "capacity: {mode: rubber}")
    with pytest.raises(ConfigError, match="max_doublings"):
        load_config_str(BASE_CFG + "capacity: {max_doublings: -1}")
    with pytest.raises(ConfigError, match="unknown option"):
        load_config_str(BASE_CFG + "capacity: {bounce: 1}")


def test_non_power_of_two_egress_cap_parses():
    """The window step's one kernel takes any ring width: a non-power-
    of-two egress cap is a valid config (elastic growth then doubles to
    the next power of two)."""
    cfg = load_config_str(
        BASE_CFG + "experimental: {tpu_egress_cap: 20, tpu_ingress_cap: 6}")
    assert cfg.experimental.tpu_egress_cap == 20
    assert cfg.experimental.tpu_ingress_cap == 6


@pytest.mark.parametrize("cap_name", ["tpu_egress_cap", "tpu_ingress_cap",
                                      "tpu_compact_cap"])
def test_device_ring_cap_must_be_positive(cap_name):
    with pytest.raises(ConfigError, match=cap_name):
        load_config_str(BASE_CFG + f"experimental: {{{cap_name}: 0}}")


# -- trajectory record ----------------------------------------------------

def test_trajectory_record_shapes():
    t = CapacityTrajectory("elastic")
    ev = t.record_growth(time_ns=5, ring="egress", from_cap=4, to_cap=8,
                         overflow=3, plane="test")
    assert ev["kind"] == "capacity-growth" and ev["to"] == 8
    t.record_drop(time_ns=9, ring="ingress", cap=8, overflow=2,
                  plane="test", exhausted=True)
    assert [e["kind"] for e in t.events] == \
        ["capacity-growth", "capacity-exhausted"]
    assert t.as_dict()["mode"] == "elastic"
    assert len(t.growth_events()) == 1


def test_harvester_annotations_and_trace_instants(tmp_path):
    import json

    from shadow_tpu.telemetry import TelemetryHarvester, export

    sink = str(tmp_path / "hb.jsonl")
    h = TelemetryHarvester(interval_ns=MS, sink=sink)
    h.note_event({"kind": "capacity-growth", "time_ns": 17,
                  "ring": "egress", "from": 4, "to": 8})
    h.tick(MS, device={"pkts_out": np.asarray([1, 2], np.int32)})
    h.finalize()
    lines = [json.loads(line) for line in open(sink)]
    sims = [r for r in lines if r["type"] == "sim"]
    assert sims and sims[0]["annotations"][0]["kind"] == \
        "capacity-growth"
    trace_path = str(tmp_path / "trace.json")
    export.write_perfetto_trace(h.heartbeats, trace_path)
    trace = json.load(open(trace_path))
    instants = [e for e in trace["traceEvents"] if e["ph"] == "i"]
    assert instants and instants[0]["name"] == "capacity-growth"


# -- device transport: growth never perturbs the packet trace -------------

TRANSPORT_CFG = """
general: {{stop_time: 20s, seed: 1}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{use_tpu_transport: true, tpu_transport_mode: {mode},
               tpu_ingress_cap: {cap}}}
{capacity}
hosts:
  server:
    network_node_id: 0
    processes:
    - {{path: http-server, args: ["80", "131072"], start_time: 2s,
       expected_final_state: running}}
  client1:
    network_node_id: 0
    processes:
    - {{path: http-client, args: ["server", "80"], start_time: 3s}}
"""


def _run_transport(mode, cap, capacity=""):
    from shadow_tpu.core.manager import Manager
    from shadow_tpu.net import packet as packet_mod

    trace = []

    def hook(packet, status):
        from shadow_tpu.core import worker as worker_mod

        host = worker_mod.current_host()
        trace.append((host.name if host else None,
                      host.now() if host else -1, int(status),
                      packet.src, packet.dst, packet.payload_size()))

    cfg = load_config_str(TRANSPORT_CFG.format(
        mode=mode, cap=cap, capacity=capacity))
    mgr = Manager(cfg)
    old = packet_mod.status_trace_hook
    packet_mod.status_trace_hook = hook
    try:
        stats = mgr.run()
    finally:
        packet_mod.status_trace_hook = old
    return trace, stats, mgr


@pytest.mark.parametrize("mode", ["sync", "mirrored"])
def test_transport_elastic_growth_trace_parity(mode):
    """An elastic transport run started at tpu_ingress_cap=2 grows its
    in-flight rings and produces the EXACT packet-status event stream
    of a pre-provisioned run — with growth events recorded and zero
    mirror divergence."""
    t_big, s_big, _ = _run_transport(mode, 256)
    t_el, s_el, mgr = _run_transport(
        mode, 2, "capacity: {mode: elastic, max_doublings: 8}")
    assert s_big.process_failures == [] and s_el.process_failures == []
    assert t_big == t_el and len(t_big) > 100
    growths = [e for e in s_el.capacity_events
               if e["kind"] == "capacity-growth"]
    assert growths and growths[0]["ring"] == "transport-ingress"
    assert mgr.transport._ingress_cap > 2
    assert mgr.transport.divergence_count == 0
    assert s_big.capacity_events == []  # pre-provisioned: clean record


def test_transport_strict_raises_capacity_error():
    with pytest.raises(CapacityError, match="ingress-capacity"):
        _run_transport("sync", 2, "capacity: {mode: strict}")


def test_transport_top_level_strict_promotes_fixed_drops():
    """Top-level `strict: true` with the default fixed capacity mode
    also refuses silent ring drops (the satellite promotion)."""
    with pytest.raises(CapacityError):
        _run_transport("sync", 2, "strict: true")


# -- flow engine: the unified trajectory ----------------------------------

FLOW_GML = """\
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        node [ id 1 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        edge [ source 0 target 0 latency "5 ms" packet_loss 0.0 ]
        edge [ source 0 target 1 latency "40 ms" packet_loss 0.002 ]
        edge [ source 1 target 1 latency "5 ms" packet_loss 0.0 ]
      ]
"""


def _flow_cfg(extra=""):
    return (
        "general: {stop_time: 30s, seed: 1}\n"
        "experimental: {use_flow_engine: true}\n" + extra +
        "network:\n  graph:\n    type: gml\n    inline: |\n" + FLOW_GML +
        "hosts:\n"
        "  server:\n    network_node_id: 0\n    processes:\n"
        "    - {path: tgen-server, args: ['8888'], start_time: 1s,\n"
        "       expected_final_state: running}\n"
        "  client0:\n    network_node_id: 1\n    processes:\n"
        "    - {path: tgen-client, args: ['server', '8888', '20000',"
        " '1'], start_time: 2s}\n")


def _poison_first_attempt(monkeypatch, drops=3):
    from shadow_tpu.tpu import floweng

    calls = []
    real_make = floweng.make_flow_world
    real_results = floweng.flow_results

    def fake_make(lat, size, **kw):
        calls.append(kw.get("queue_slots"))
        return real_make(lat, size, **kw)

    def fake_results(world):
        res = real_results(world)
        if len(calls) == 1:
            res = dict(res)
            res["queue_drops"] = drops
        return res

    monkeypatch.setattr(floweng, "make_flow_world", fake_make)
    monkeypatch.setattr(floweng, "flow_results", fake_results)
    return calls


@pytest.mark.slow  # Manager-driven flow-engine run + poisoned rerun
# (~35s); stays GATING in CI's flow-engine-slow step (tier-1 budget)
def test_flowplan_ring_rerun_lands_in_trajectory(monkeypatch):
    from shadow_tpu.core.manager import Manager

    calls = _poison_first_attempt(monkeypatch)
    cfg = load_config_str(_flow_cfg())
    stats = Manager(cfg).run()
    assert calls == [256, 512]
    growths = [e for e in stats.capacity_events
               if e["kind"] == "capacity-growth"]
    assert growths == [{
        "kind": "capacity-growth", "time_ns": 30_000_000_000,
        "ring": "flow-queue", "from": 256, "to": 512, "overflow": 3,
        "plane": "floweng", "bucket_window_us": growths[0][
            "bucket_window_us"]}]
    assert stats.process_failures == []


@pytest.mark.slow  # Manager-driven flow-engine run (~22s); stays
# GATING in CI's tier-1-overflow unfiltered step
def test_flowplan_strict_refuses_ring_drops(monkeypatch):
    from shadow_tpu.core.manager import Manager

    _poison_first_attempt(monkeypatch)
    cfg = load_config_str(_flow_cfg("capacity: {mode: strict}\n"))
    with pytest.raises(CapacityError, match="flow engine"):
        Manager(cfg).run()


# -- chaos_smoke kill -> resume with growth mid-run (subprocess) ----------

@pytest.mark.slow
def test_chaos_smoke_kill_resume_parity_across_growth(tmp_path):
    """A killed-and-resumed elastic chaos run (growth events before the
    kill) finishes bitwise-identical to the uninterrupted one, growth
    history and all."""
    import json
    import subprocess

    repo = os.path.join(os.path.dirname(__file__), "..")
    base = [sys.executable, os.path.join(repo, "tools", "chaos_smoke.py"),
            "--hosts", "32", "--windows", "16", "--capacity", "elastic",
            "--egress-cap", "4", "--ingress-cap", "8"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    full = subprocess.run(base, capture_output=True, text=True, env=env,
                          cwd=repo)
    assert full.returncode == 0, full.stderr
    full_out = json.loads(full.stdout)
    assert full_out["capacity"]["growth_events"] >= 1

    ckpt_dir = str(tmp_path / "ckpts")
    killed = subprocess.run(
        base + ["--checkpoint-dir", ckpt_dir, "--checkpoint-every", "6",
                "--kill-at", "10"],
        capture_output=True, text=True, env=env, cwd=repo)
    assert killed.returncode == 137, killed.stderr
    resumed = subprocess.run(
        base + ["--resume", os.path.join(ckpt_dir, "ckpt-000000000006")],
        capture_output=True, text=True, env=env, cwd=repo)
    assert resumed.returncode == 0, resumed.stderr
    res_out = json.loads(resumed.stdout)
    assert res_out["state_digest"] == full_out["state_digest"]
    assert res_out["capacity"]["final"] == full_out["capacity"]["final"]
