"""Flow plane tests (tpu/flows.py, docs/robustness.md "Flow plane"):

- RTO twin parity at the clip boundaries: the device estimator helpers
  (`tpu/tcp.py` `_rtt_update`/`_rtt_backoff`/`_set_rto`) against the
  CPU `tcp/rtt.RttEstimator` — RTO_MIN/RTO_MAX clamps, the srtt==0
  first-sample fallback, backoff saturation at RTO_MAX — the edges the
  bitwise-parity contract (`_rto_from_estimate`'s twin comment) pins.
- flow state-machine units over synthetic delivered dicts: in-order
  credit, out-of-order buffering + hole-fill release, duplicate
  re-ack, cumulative-ack cwnd advance, RTO expiry -> go-back-N with
  exponential backoff and counted retransmissions.
- presence: all-inactive flow tables threaded through window_step are
  bitwise-invisible (state + metrics); unpack_planes grows the flows slot; chain_windows threads the plane
  and refuses the workload+flows combo.
- scenario integration (slow): a lossy `transport: flows` incast
  completes all phases deterministically with >0 retransmits; at
  loss_p=0 the flows run matches the direct run's phase completions;
  the flight recorder links drop_loss -> retransmit -> delivered.
- config: the `flows:` block (bare off/on, validation) and the
  Manager unsupported-combo warn / strict ConfigError.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shadow_tpu.tcp.rtt import (RTO_INIT_MS, RTO_MAX_MS, RTO_MIN_MS,
                                RttEstimator)
from shadow_tpu.tpu import flows, plane
from shadow_tpu.tpu import tcp as dtcp

MS = 1_000_000
WINDOW = jnp.int32(10 * MS)


def _mini_world(n=4, loss=0.0, ce=8, ci=8):
    params = plane.make_params(
        np.full((n, n), 1_000_000, np.int64),
        np.full((n, n), loss, np.float32),
        np.full(n, 1_000_000_000, np.int64))
    state = plane.make_state(n, egress_cap=ce, ingress_cap=ci,
                             params=params)
    return state, params, jax.random.key(0)


def _delivered(n=4, ci=8, entries=()):
    """Synthetic delivered dict; entries = (row, src, seq, sock)."""
    d = {
        "mask": np.zeros((n, ci), bool),
        "src": np.zeros((n, ci), np.int32),
        "seq": np.zeros((n, ci), np.int32),
        "sock": np.zeros((n, ci), np.int32),
        "bytes": np.zeros((n, ci), np.int32),
        "deliver_rel": np.zeros((n, ci), np.int32),
    }
    slot = {}
    for row, src, seq, sock in entries:
        c = slot.get(row, 0)
        slot[row] = c + 1
        d["mask"][row, c] = True
        d["src"][row, c] = src
        d["seq"][row, c] = seq
        d["sock"][row, c] = sock
    return {k: jnp.asarray(v) for k, v in d.items()}


# -- RTO twin parity at the clip boundaries -------------------------------


def _device_est(k=1):
    return flows.make_flow_state(k)


def _dev_update(fs, rtt_ms):
    return jax.vmap(dtcp._rtt_update, in_axes=(0, None))(
        fs, jnp.int32(rtt_ms))


def _dev_fields(fs):
    return (int(fs.srtt_ms[0]), int(fs.rttvar_ms[0]), int(fs.rto_ms[0]),
            int(fs.backoff_count[0]))


def _cpu_fields(est):
    return (est.srtt_ms, est.rttvar_ms, est.rto_ms, est.backoff_count)


def test_rto_twin_first_sample_fallback():
    # srtt == 0 means "no measurement yet": the first sample seeds
    # srtt = rtt, rttvar = rtt // 2 in BOTH twins, and reset_backoff
    # before any sample restores RTO_INIT, never 0
    est, fs = RttEstimator(), _device_est()
    assert _dev_fields(fs) == _cpu_fields(est) == (0, 0, RTO_INIT_MS, 0)
    est.update(300)
    fs = _dev_update(fs, 300)
    assert _dev_fields(fs) == _cpu_fields(est)
    assert est.srtt_ms == 300 and est.rttvar_ms == 150

    est2, fs2 = RttEstimator(), _device_est()
    est2.backoff()
    fs2 = jax.vmap(dtcp._rtt_backoff)(fs2)
    est2.reset_backoff()
    fs2 = jax.vmap(dtcp._rtt_reset_backoff)(fs2)
    assert _dev_fields(fs2) == _cpu_fields(est2)
    assert est2.rto_ms == RTO_INIT_MS


def test_rto_twin_min_clip():
    # a tiny (even non-positive) sample floors at 1 ms and the RTO
    # clips at RTO_MIN via the Linux mdev floor
    for rtt in (0, 1, 3):
        est, fs = RttEstimator(), _device_est()
        est.update(rtt)
        fs = _dev_update(fs, rtt)
        assert _dev_fields(fs) == _cpu_fields(est)
        assert est.rto_ms >= RTO_MIN_MS


def test_rto_twin_max_clip():
    # a huge sample clips the RTO at RTO_MAX in both twins
    est, fs = RttEstimator(), _device_est()
    est.update(10 * RTO_MAX_MS)
    fs = _dev_update(fs, 10 * RTO_MAX_MS)
    assert _dev_fields(fs) == _cpu_fields(est)
    assert est.rto_ms == RTO_MAX_MS


def test_rto_twin_backoff_saturation():
    # exponential backoff saturates at RTO_MAX and STAYS there; a
    # post-saturation reset restores the estimate-derived RTO
    est, fs = RttEstimator(), _device_est()
    est.update(250)
    fs = _dev_update(fs, 250)
    for i in range(14):
        est.backoff()
        fs = jax.vmap(dtcp._rtt_backoff)(fs)
        assert _dev_fields(fs) == _cpu_fields(est), f"step {i}"
    assert est.rto_ms == RTO_MAX_MS
    est.backoff()
    fs = jax.vmap(dtcp._rtt_backoff)(fs)
    assert est.rto_ms == RTO_MAX_MS
    assert _dev_fields(fs) == _cpu_fields(est)
    est.reset_backoff()
    fs = jax.vmap(dtcp._rtt_reset_backoff)(fs)
    assert _dev_fields(fs) == _cpu_fields(est)
    assert est.rto_ms < RTO_MAX_MS


def test_rto_twin_random_trace_parity():
    # a seeded mixed op trace stays field-identical end to end
    rng = np.random.default_rng(7)
    est, fs = RttEstimator(), _device_est()
    for i in range(60):
        op = rng.integers(0, 3)
        if op == 0:
            rtt = int(rng.integers(1, 5000))
            est.update(rtt)
            fs = _dev_update(fs, rtt)
        elif op == 1:
            est.backoff()
            fs = jax.vmap(dtcp._rtt_backoff)(fs)
        else:
            est.reset_backoff()
            fs = jax.vmap(dtcp._rtt_reset_backoff)(fs)
        assert _dev_fields(fs) == _cpu_fields(est), f"op {i}"


# -- flow state-machine units ---------------------------------------------


def _one_flow(stream=0):
    ft = flows.make_flow_tables([0], [1], [1400])
    fs = flows.make_flow_state(1)
    if stream:
        fs = fs._replace(stream_len=jnp.array([stream], jnp.int32))
    return ft, fs


def test_flow_recv_in_order_credit():
    ft, fs = _one_flow()
    dtag = int(flows.data_tag(np.int32(0)))
    d = _delivered(entries=[(1, 0, 0, dtag), (1, 0, 1, dtag),
                            (1, 0, 2, dtag)])
    fs2, credits = flows.flow_recv(ft, fs, d, WINDOW)
    assert int(fs2.rcv_nxt[0]) == 3
    assert bool(fs2.ack_pending[0])
    assert np.asarray(credits).tolist() == [0, 3, 0, 0]
    # the clock advanced one window in ms
    assert int(fs2.clock_ms[0]) == 10


def test_flow_recv_buffers_out_of_order_and_releases_on_hole_fill():
    ft, fs = _one_flow()
    dtag = int(flows.data_tag(np.int32(0)))
    # seq 1, 2 arrive first: buffered, no credit (hole at 0)
    fs2, credits = flows.flow_recv(
        ft, fs, _delivered(entries=[(1, 0, 1, dtag), (1, 0, 2, dtag)]),
        WINDOW)
    assert int(fs2.rcv_nxt[0]) == 0
    assert np.asarray(credits).sum() == 0
    assert bool(fs2.ack_pending[0])  # dup/OOO still re-arms the ack
    # the hole fills: the buffered run releases in one window
    fs3, credits = flows.flow_recv(
        ft, fs2, _delivered(entries=[(1, 0, 0, dtag)]), WINDOW)
    assert int(fs3.rcv_nxt[0]) == 3
    assert np.asarray(credits).tolist() == [0, 3, 0, 0]
    # bitmap shifted clean: bit 0 False again
    assert not bool(fs3.rcv_bits[0, 0])


def test_flow_recv_duplicate_rearms_ack_without_credit():
    ft, fs = _one_flow()
    fs = fs._replace(rcv_nxt=jnp.array([2], jnp.int32))
    dtag = int(flows.data_tag(np.int32(0)))
    fs2, credits = flows.flow_recv(
        ft, fs, _delivered(entries=[(1, 0, 0, dtag)]), WINDOW)
    assert int(fs2.rcv_nxt[0]) == 2
    assert np.asarray(credits).sum() == 0
    assert bool(fs2.ack_pending[0])


def test_flow_recv_foreign_traffic_is_inert():
    # untagged (sock 0/1) and endpoint-mismatched packets never touch
    # flow state — the all-inactive presence guarantee's mechanism
    ft, fs = _one_flow()
    dtag = int(flows.data_tag(np.int32(0)))
    d = _delivered(entries=[
        (1, 0, 5, 0),       # untagged
        (1, 0, 6, 1),       # reserved
        (2, 0, 0, dtag),    # wrong destination row
        (1, 3, 0, dtag),    # wrong source
    ])
    fs2, credits = flows.flow_recv(ft, fs, d, WINDOW)
    assert int(fs2.rcv_nxt[0]) == 0
    assert not bool(fs2.ack_pending[0])
    assert np.asarray(credits).sum() == 0


def test_flow_ack_advances_cwnd_and_rearms_rto():
    ft, fs = _one_flow(stream=8)
    state, _params, _root = _mini_world()
    # emit the initial window (arms the RTO + the RTT probe)
    state, fs = flows.flow_emit(ft, fs, state)[:2]
    assert int(fs.snd_nxt[0]) == 8
    assert bool(fs.rto_armed[0])
    assert int(fs.rtt_seq[0]) == 0
    cwnd0 = int(fs.cwnd[0])
    # a cumulative ack for 3 segments arrives two windows later
    atag = int(flows.ack_tag(np.int32(0)))
    d = _delivered(entries=[(0, 1, 3, atag)])
    fs2, _credits = flows.flow_recv(ft, fs, d, WINDOW)
    assert int(fs2.snd_una[0]) == 3
    assert int(fs2.cwnd[0]) == cwnd0 + 3  # slow start
    assert bool(fs2.rto_armed[0])  # data still outstanding
    # the probe (seq 0) was covered: an RTT sample landed
    assert int(fs2.srtt_ms[0]) > 0
    assert int(fs2.rtt_seq[0]) == -1
    # ack of everything disarms the timer
    d2 = _delivered(entries=[(0, 1, 8, atag)])
    fs3, _credits = flows.flow_recv(ft, fs2, d2, WINDOW)
    assert int(fs3.snd_una[0]) == 8
    assert not bool(fs3.rto_armed[0])


def test_flow_rto_fires_go_back_n():
    ft, fs = _one_flow(stream=4)
    state, _params, _root = _mini_world()
    state, fs = flows.flow_emit(ft, fs, state)[:2]
    assert int(fs.snd_nxt[0]) == 4 and int(fs.snd_max[0]) == 4
    deadline = int(fs.rto_deadline_ms[0])
    rto0 = int(fs.rto_ms[0])
    # a quiet window leaves the timer untouched...
    fs, credits = flows.flow_recv(ft, fs, _delivered(), WINDOW)
    assert np.asarray(credits).sum() == 0
    assert bool(fs.rto_armed[0])
    # ...then jump the flow clock to the deadline (the driver loop
    # would get here through `deadline // window_ms` quiet recvs — the
    # clock is the only recv effect on an idle window) and emit: fires
    fs = fs._replace(clock_ms=jnp.full_like(fs.clock_ms, deadline))
    state2, fs2 = flows.flow_emit(ft, fs, state)[:2]
    assert int(fs2.rto_fired[0]) == 1
    assert int(fs2.backoff_count[0]) == 1
    assert int(fs2.rto_ms[0]) == min(2 * rto0, RTO_MAX_MS)
    assert int(fs2.cwnd[0]) == dtcp.INITIAL_CWND  # Reno timeout reset
    # go-back-N: the whole unacked range re-emitted and counted
    assert int(fs2.snd_nxt[0]) == 4
    assert int(fs2.retransmit_count[0]) == 4
    assert int(fs2.retransmitted_bytes[0]) == 4 * 1400
    assert int(fs2.rtt_seq[0]) == -1  # Karn: probe abandoned
    # the per-host reduction agrees with the per-flow counter (the
    # tcp.retransmits_by_host twin; also what metrics.retransmits got)
    assert np.asarray(
        flows.retransmits_by_host(ft, fs2, 4)).tolist() == [4, 0, 0, 0]


def test_flow_emit_respects_cwnd_and_emit_cap():
    ft, fs = _one_flow(stream=100)
    fs = fs._replace(cwnd=jnp.array([3], jnp.int32))
    state, _params, _root = _mini_world()
    state, fs = flows.flow_emit(ft, fs, state)[:2]
    assert int(fs.snd_nxt[0]) == 3  # cwnd-limited below emit_cap
    fs = fs._replace(cwnd=jnp.array([100], jnp.int32))
    state, fs = flows.flow_emit(ft, fs, state)[:2]
    # emit_cap-limited per window
    assert int(fs.snd_nxt[0]) == 3 + flows.EMIT_CAP
    # the emit_cap knob (the `flows:` config block) overrides the lane
    # budget per call
    state, fs = flows.flow_emit(ft, fs, state, emit_cap=2)[:2]
    assert int(fs.snd_nxt[0]) == 3 + flows.EMIT_CAP + 2


def test_next_deadline_rel_ns():
    ft = flows.make_flow_tables([0, 2, -1], [1, 3, -1],
                                [100, 100, 100])
    fs = flows.make_flow_state(3)
    # nothing armed -> sentinel
    assert int(flows.next_deadline_rel_ns(ft, fs)) == flows.I32_MAX
    # two armed timers: the earliest pending deadline wins, relative
    # to the flow clock; an inactive slot's timer never counts
    fs = fs._replace(
        snd_una=jnp.asarray([0, 0, 0], jnp.int32),
        snd_nxt=jnp.asarray([2, 2, 2], jnp.int32),
        rto_armed=jnp.asarray([True, True, True]),
        rto_deadline_ms=jnp.asarray([500, 300, 1], jnp.int32),
        clock_ms=jnp.asarray([100, 100, 100], jnp.int32))
    assert int(flows.next_deadline_rel_ns(ft, fs)) == 200 * MS
    # already-due reads 0 (fires next window), never negative
    fs = fs._replace(clock_ms=jnp.asarray([600, 600, 600], jnp.int32))
    assert int(flows.next_deadline_rel_ns(ft, fs)) == 0


def test_enqueue_counts_lanes():
    ft = flows.make_flow_tables([0, 2], [1, 3], [100, 200])
    fs = flows.make_flow_state(2)
    ids = jnp.asarray([[0, 1, -1], [1, 1, 0]], jnp.int32)
    valid = jnp.asarray([[True, True, True], [True, False, True]])
    fs = flows.enqueue(ft, fs, ids, valid)
    assert np.asarray(fs.stream_len).tolist() == [2, 2]


# -- presence + threading -------------------------------------------------


def test_window_step_inactive_flows_bitwise_invisible():
    from shadow_tpu.guards import make_guards
    from shadow_tpu.telemetry import make_metrics

    state, params, root = _mini_world()
    state = plane.ingest(
        state, jnp.array([0, 1], jnp.int32), jnp.array([1, 2], jnp.int32),
        jnp.full(2, 1400, jnp.int32), jnp.arange(2, dtype=jnp.int32),
        jnp.arange(2, dtype=jnp.int32), jnp.zeros(2, bool))
    ft = flows.make_flow_tables(np.full(3, -1), np.full(3, -1),
                                np.full(3, 1400))
    fs = flows.make_flow_state(3)
    m0, g0 = make_metrics(4), make_guards(4)

    base = jax.jit(lambda st, m, g, sh: plane.window_step(
        st, params, root, sh, WINDOW, rr_enabled=False, metrics=m,
        guards=g))
    with_f = jax.jit(lambda st, m, g, fstate, sh: plane.window_step(
        st, params, root, sh, WINDOW, rr_enabled=False, metrics=m,
        guards=g, flows=(ft, fstate)))

    sa, ma, ga, sh = state, m0, g0, jnp.int32(0)
    sb, mb, gb, fsx = state, m0, g0, fs
    for _ in range(3):
        sa, da, na, ma, ga = base(sa, ma, ga, sh)
        sb, db, nb, mb, gb, fsx = with_f(sb, mb, gb, fsx, sh)
        sh = WINDOW
    for a, b in zip(jax.tree.leaves(sa), jax.tree.leaves(sb)):
        assert (np.asarray(a) == np.asarray(b)).all()
    for a, b in zip(jax.tree.leaves(ma), jax.tree.leaves(mb)):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert int(na) == int(nb)
    # guard contract: violation bits identically clean; only the
    # checks TALLY grows (the flow append is checked like any
    # producer's — docs/robustness.md "Flow plane")
    assert int(np.asarray(ga.violations).sum()) == 0
    assert int(np.asarray(gb.violations).sum()) == 0
    assert (np.asarray(gb.first_window)
            == np.asarray(ga.first_window)).all()
    assert int(gb.checks) > int(ga.checks)


def test_window_step_active_flow_completes_on_fifo_step():
    """An active flow threaded through the lean FIFO step (the bench's
    program) runs its exchange end to end: both segments go on the
    wire, the receiver credits them in order, one cumulative ack comes
    back, and the sender's RTO disarms — within five 10 ms windows over
    1 ms paths."""
    state, params, root = _mini_world()
    ft, fs = _one_flow(stream=2)
    step = jax.jit(lambda st, fstate, sh: plane.window_step(
        st, params, root, sh, WINDOW, rr_enabled=False,
        flows=(ft, fstate)))
    sh = jnp.int32(0)
    for _ in range(5):
        state, _d, _n, fs = step(state, fs, sh)
        sh = WINDOW
    assert int(fs.snd_nxt[0]) == 2 and int(fs.rcv_nxt[0]) == 2
    assert int(fs.snd_una[0]) == 2 and not bool(fs.rto_armed[0])
    assert int(np.asarray(state.n_sent).sum()) == 3  # 2 data + 1 ack


@pytest.mark.slow  # two eager window_step traces; CI's lossy-corpus
# job runs this file UNFILTERED so the case stays gating
def test_unpack_planes_flows_slot():
    state, params, root = _mini_world()
    ft, fs = _one_flow()
    out = plane.window_step(state, params, root, jnp.int32(0), WINDOW,
                            rr_enabled=False, flows=(ft, fs))
    (st, _d, _n), m, g, h, fr, fs2 = plane.unpack_planes(
        out, flows=fs)
    assert m is g is h is fr is None
    assert isinstance(fs2, flows.FlowState)
    assert type(st) is plane.NetPlaneState
    # legacy shape is untouched when the slot is not requested
    out2 = plane.window_step(state, params, root, jnp.int32(0), WINDOW,
                             rr_enabled=False)
    (st2, _d2, _n2), m2, g2, h2, fr2 = plane.unpack_planes(out2)
    assert m2 is None and fr2 is None


@pytest.mark.slow  # compiles the chained while_loop; CI runs this
# file unfiltered (lossy-corpus job) so the case stays gating
def test_chain_windows_flows_threads_and_refuses_workload_combo():
    state, params, root = _mini_world()
    ft, fs = _one_flow(stream=2)
    out = plane.chain_windows(
        state, params, root, jnp.int32(0), WINDOW, WINDOW,
        jnp.int32(200 * MS), jnp.int32(200 * MS),
        rr_enabled=False, flows=(ft, fs))
    fs2 = out[-1]
    assert isinstance(fs2, flows.FlowState)
    # the chain drove the flow's segments onto the wire
    assert int(fs2.snd_nxt[0]) == 2
    with pytest.raises(ValueError, match="not both"):
        plane.chain_windows(
            state, params, root, jnp.int32(0), WINDOW, WINDOW,
            jnp.int32(200 * MS), jnp.int32(200 * MS),
            rr_enabled=False, flows=(ft, fs),
            workload=(object(), object()))


# -- spec / compile -------------------------------------------------------


def _incast_raw(**over):
    raw = {
        "name": "t-incast", "family": "incast", "seed": 13,
        "hosts": 12, "windows": 64,
        "patterns": [{"kind": "incast", "first": 0, "count": 9,
                      "bytes": 8000, "rounds": 4}],
    }
    raw.update(over)
    return raw


def test_spec_lossy_requires_flows():
    from shadow_tpu.workloads.spec import ScenarioError, parse_scenario

    with pytest.raises(ScenarioError, match="transport: flows"):
        parse_scenario(_incast_raw(loss_p=0.1))
    with pytest.raises(ScenarioError, match="transport"):
        parse_scenario(_incast_raw(transport="tcp"))
    with pytest.raises(ScenarioError, match="window_ns"):
        parse_scenario(_incast_raw(transport="flows",
                                   window_ns=500_000))
    spec = parse_scenario(_incast_raw(transport="flows", loss_p=0.1))
    assert spec.transport == "flows" and spec.loss_p == 0.1


def test_spec_fingerprint_backward_stable():
    from shadow_tpu.workloads.spec import (parse_scenario,
                                           scenario_fingerprint)

    direct = parse_scenario(_incast_raw())
    explicit = parse_scenario(_incast_raw(transport="direct",
                                          loss_p=0.0))
    # default transport/loss add NO keys: pre-existing fingerprints
    # (and the golden corpus) are untouched by the new fields
    assert "transport" not in direct.as_dict()
    assert scenario_fingerprint(direct) == scenario_fingerprint(explicit)
    flowsy = parse_scenario(_incast_raw(transport="flows"))
    assert scenario_fingerprint(flowsy) != scenario_fingerprint(direct)


def test_compile_lowers_flow_tables():
    from shadow_tpu.workloads.compile import (compile_program,
                                              program_digest)
    from shadow_tpu.workloads.spec import parse_scenario

    direct = compile_program(parse_scenario(_incast_raw()))
    assert direct.flow_src is None and direct.lane_flow is None
    prog = compile_program(parse_scenario(_incast_raw(
        transport="flows")))
    # incast 8->1: 8 data flows + 8 sink->source ack-message flows
    assert prog.flow_src is not None
    F = prog.flow_src.shape[0]
    assert F == 16
    # every send lane of a participant maps to a flow with matching
    # endpoints and byte size
    for h in range(12):
        for p in range(int(prog.n_phases[h])):
            for k in range(prog.send_peer.shape[2]):
                peer = int(prog.send_peer[h, p, k])
                f = int(prog.lane_flow[h, p, k])
                if peer < 0:
                    assert f == -1
                    continue
                assert prog.flow_src[f] == h
                assert prog.flow_dst[f] == peer
                assert prog.flow_bytes[f] == prog.send_bytes[h, p, k]
    # the flow tables fold into the digest; the direct digest is
    # computed over the same first-six tables yet differs
    assert program_digest(prog) != program_digest(direct)


# -- config block + Manager -----------------------------------------------

BASE_CFG = ("general:\n  stop_time: 1s\n"
            "network:\n  graph:\n    type: 1_gbit_switch\n"
            "hosts:\n  a:\n    network_node_id: 0\n")


def test_flows_config_block():
    from shadow_tpu.core.config import ConfigError, load_config_str

    cfg = load_config_str(BASE_CFG)
    assert not cfg.flows.enabled
    assert cfg.flows.emit_cap == 8 and cfg.flows.recv_wnd == 64
    cfg = load_config_str(
        BASE_CFG + "flows:\n  enabled: true\n  emit_cap: 4\n"
                   "  recv_wnd: 32\n")
    assert cfg.flows.enabled and cfg.flows.emit_cap == 4
    # YAML 1.1 bare off/on coerce like workload / flight_recorder
    cfg = load_config_str(BASE_CFG + "flows: off\n")
    assert not cfg.flows.enabled
    cfg = load_config_str(BASE_CFG + "flows: on\n")
    assert cfg.flows.enabled
    with pytest.raises(ConfigError):
        load_config_str(BASE_CFG + "flows:\n  emit_cap: 0\n")
    with pytest.raises(ConfigError):
        load_config_str(BASE_CFG + "flows:\n  recv_wnd: 0\n")
    with pytest.raises(ConfigError, match="emit_cap"):
        load_config_str(
            BASE_CFG + "flows:\n  emit_cap: 16\n  recv_wnd: 8\n")
    with pytest.raises(ConfigError):
        load_config_str(BASE_CFG + "flows:\n  bogus: 1\n")


def test_manager_warns_on_flows(caplog):
    import logging

    from shadow_tpu.core.config import ConfigError, load_config_str
    from shadow_tpu.core.manager import Manager

    cfg = load_config_str(BASE_CFG + "flows: on\n")
    with caplog.at_level(logging.WARNING, logger="shadow_tpu.manager"):
        Manager(cfg)
    assert any("flows" in r.getMessage() for r in caplog.records)
    cfg = load_config_str(BASE_CFG + "strict: true\nflows: on\n")
    with pytest.raises(ConfigError):
        Manager(cfg)


# -- scenario integration (slow: full corpus-runner worlds) ---------------


@pytest.mark.slow
def test_lossy_incast_completes_deterministic():
    from shadow_tpu.workloads import runner
    from shadow_tpu.workloads.spec import parse_scenario

    spec = parse_scenario(_incast_raw(transport="flows", loss_p=0.05,
                                      windows=400))
    r1 = runner.run_scenario(spec, guards=True)
    assert r1["all_done"], r1
    assert r1["retransmits"] > 0
    assert r1["drops"]["loss"] > 0
    assert r1["guards"]["clean"], r1["guards"]
    assert r1["flows"]["segments_acked"] == r1["flows"][
        "segments_enqueued"]
    r2 = runner.run_scenario(spec, guards=True)
    assert r1["canonical_digest"] == r2["canonical_digest"]
    assert r1["phase_completion_ns"] == r2["phase_completion_ns"]


@pytest.mark.slow
def test_flow_knobs_plumb_from_runner():
    # the `flows:` config-block knobs reach the flow machine through
    # run_scenario (run_scenarios --config plumbs cfg.flows here): a
    # shrunken recv_wnd changes the receive-bitmap shape and the
    # record reports the effective knobs
    from shadow_tpu.workloads import runner
    from shadow_tpu.workloads.spec import parse_scenario

    spec = parse_scenario(_incast_raw(transport="flows"))
    rec = runner.run_scenario(spec, flow_emit_cap=4, flow_recv_wnd=16)
    assert rec["all_done"]
    assert rec["flows"]["emit_cap"] == 4
    assert rec["flows"]["recv_wnd"] == 16
    with pytest.raises(ValueError, match="emit_cap"):
        runner.run_scenario(spec, flow_emit_cap=32, flow_recv_wnd=16)


@pytest.mark.slow
def test_zero_loss_flows_matches_direct_completion():
    from shadow_tpu.workloads import runner
    from shadow_tpu.workloads.spec import parse_scenario

    rd = runner.run_scenario(parse_scenario(_incast_raw()))
    rf = runner.run_scenario(parse_scenario(_incast_raw(
        transport="flows")))
    assert rd["all_done"] and rf["all_done"]
    assert rf["phase_completion_ns"] == rd["phase_completion_ns"]
    assert rf["host_completion"] == rd["host_completion"]
    assert rf["retransmits"] == 0


@pytest.mark.slow
def test_flightrec_links_loss_to_retransmit():
    import io
    import json

    from shadow_tpu.workloads import runner
    from shadow_tpu.workloads.spec import parse_scenario

    spec = parse_scenario(_incast_raw(transport="flows", loss_p=0.05,
                                      windows=400))
    sink = io.StringIO()
    runner.run_scenario(spec, sample_every=1, hops_sink=sink)
    trails: dict[tuple, list] = {}
    kinds: dict[str, int] = {}
    for line in sink.getvalue().splitlines():
        h = json.loads(line)
        kinds[h["kind"]] = kinds.get(h["kind"], 0) + 1
        trails.setdefault((h["src"], h["seq"], h["dst"]),
                          []).append(h["kind"])
    assert kinds.get("rto_fired", 0) > 0
    assert kinds.get("retransmit", 0) > 0
    linked = [t for t in trails.values()
              if "drop_loss" in t and "retransmit" in t
              and "delivered" in t]
    assert linked, "no trail links a loss to its retransmission"
