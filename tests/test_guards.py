"""Guard plane tests (shadow_tpu/guards/, docs/robustness.md):

- device conservation guards are a bitwise-invisible presence switch
  (the guards-on/guards-off parity matrix across rr x aqm x no_loss)
  and report ZERO violations on clean runs;
- deliberate state tamper / counter corruption is caught with per-host
  blame (seeded counter-tamper -> GuardError, populated violation
  report, emergency checkpoint with a valid MANIFEST, finalized
  telemetry);
- cross-plane reconciliation flags exactly the disagreeing (host,
  counter) pairs;
- the virtual-time progress detector trips on a deliberately stalled
  run and names the blocked host;
- the `guards:` / `strict:` config blocks parse and validate.
"""

import json
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from shadow_tpu.core.config import (ConfigError,  # noqa: E402
                                    load_config_str)
from shadow_tpu.guards import (GUARD_CLOCK, GUARD_INGEST_FLOW,  # noqa: E402
                               GUARD_KEY_BUDGET, GUARD_RING_STRUCT,
                               GuardError, GuardLedger, GuardViolation,
                               HostWait, ProgressDetector, decode_bits,
                               make_guards, reconcile_fleet,
                               reconcile_per_host, summarize)
from shadow_tpu.tpu import ingest_rows, profiling  # noqa: E402
from shadow_tpu.tpu.plane import window_step  # noqa: E402

MS = 1_000_000


def _world(n=32, seed=0):
    return profiling.build_world(n, warmup_windows=2, seed=seed)


def _run_windows(world, n_windows, *, rr, aqm, no_loss, guards):
    state = world["state"]
    window = world["window"]
    params, root = world["params"], world["rng_root"]
    step = jax.jit(lambda st, sh, g: window_step(
        st, params, root, sh, window, rr_enabled=rr, router_aqm=aqm,
        no_loss=no_loss, guards=g))
    shift = jnp.int32(0)
    for _ in range(n_windows):
        out = step(state, shift, guards)
        if guards is not None:
            state, _delivered, _next, guards = out
        else:
            state, _delivered, _next = out
        shift = window
    return state, guards


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


# -- parity: guards are a bitwise-invisible presence switch ---------------

@pytest.mark.parametrize("rr,aqm,no_loss", [
    (False, False, False), (True, False, False),
    (False, True, False), (True, True, False),
    (False, False, True), (True, True, True),
])
def test_guards_parity_matrix(rr, aqm, no_loss):
    """guards=None and guards-threaded runs produce bitwise-identical
    simulation state, and the clean world records zero violations."""
    world = _world()
    s_off, _ = _run_windows(world, 5, rr=rr, aqm=aqm, no_loss=no_loss,
                            guards=None)
    s_on, g = _run_windows(world, 5, rr=rr, aqm=aqm, no_loss=no_loss,
                           guards=make_guards(32))
    _assert_trees_equal(s_off, s_on)
    summ = summarize(g)
    assert summ["clean"], summ
    assert summ["windows_checked"] == 5
    assert summ["checks_evaluated"] > 0


def test_ingest_rows_guard_parity_and_clean():
    world = _world()
    state = world["state"]
    N, CI = 32, world["ingress_cap"]
    deliv = world["delivered"]
    spawn_seq = jnp.full((N,), 10_000, jnp.int32)
    mask, dst, nbytes, seq, ctrl = profiling.respawn_batch(
        deliv, spawn_seq, jnp.int32(1), N, CI)
    plain = ingest_rows(state, dst, nbytes, seq, seq, ctrl, valid=mask)
    guarded, g = ingest_rows(state, dst, nbytes, seq, seq, ctrl,
                             valid=mask, guards=make_guards(N))
    _assert_trees_equal(plain, guarded)
    assert summarize(g)["clean"]


# -- device tamper detection ----------------------------------------------

def test_phantom_ring_slot_trips_ring_structure():
    """A phantom valid slot at the back of one ingress ring (the exact
    single-slot damage batched execution would hide) is caught at the
    next window with host blame and the window index."""
    world = _world()
    state = world["state"]
    CI = world["ingress_cap"]
    bad = state._replace(in_valid=state.in_valid.at[3, CI - 1].set(True))
    _s, _d, _n, g = window_step(
        bad, world["params"], world["rng_root"], jnp.int32(10 * MS),
        world["window"], rr_enabled=False, guards=make_guards(32))
    summ = summarize(g)
    assert not summ["clean"]
    assert summ["by_class"] == {"ring-structure": 1}
    assert summ["first_offenders"][0]["host_index"] == 3
    assert summ["first_offenders"][0]["first_window"] == 0


def test_negative_sort_key_trips_key_budget():
    """A negative priority in a live egress slot breaks the uint32
    packed-sort domain — the key-budget guard flags the host."""
    world = _world()
    state = world["state"]
    bad = state._replace(
        eg_valid=state.eg_valid.at[5, 0].set(True),
        eg_prio=state.eg_prio.at[5, 0].set(-7),
    )
    _s, _d, _n, g = window_step(
        bad, world["params"], world["rng_root"], jnp.int32(10 * MS),
        world["window"], rr_enabled=False, guards=make_guards(32))
    v = np.asarray(jax.device_get(g.violations))
    assert v[5] & GUARD_KEY_BUDGET


def test_clock_violation_sets_scalar_flag():
    world = _world()
    _s, _d, _n, g = window_step(
        world["state"], world["params"], world["rng_root"],
        jnp.int32(-5), world["window"], rr_enabled=False,
        guards=make_guards(32))
    assert int(jax.device_get(g.flags)) & GUARD_CLOCK
    assert "virtual-clock" in summarize(g)["scalar_flags"]


def test_decode_bits_names():
    assert decode_bits(0) == []
    assert decode_bits(GUARD_RING_STRUCT | GUARD_INGEST_FLOW) == [
        "ring-structure", "ingest-conservation"]


# -- reconciliation -------------------------------------------------------

def test_reconcile_per_host_agree_and_disagree():
    device = {"pkts_out": np.array([5, 3, 0], np.int64),
              "pkts_in": np.array([2, 2, 4], np.int64)}
    cpu = {"captured": np.array([5, 3, 0], np.int64),
           "released": np.array([2, 2, 4], np.int64)}
    pairs = (("pkts_out", "captured"), ("pkts_in", "released"))
    assert reconcile_per_host(1000, device, cpu, pairs,
                              ["a", "b", "c"]) == []
    cpu["released"][1] = 9  # one host's ledger disagrees
    found = reconcile_per_host(1000, device, cpu, pairs, ["a", "b", "c"])
    assert len(found) == 1
    v = found[0]
    assert (v.cls, v.check, v.host) == ("reconcile",
                                        "pkts_in-vs-released", "b")
    assert (v.expected, v.actual) == (9, 2)


def test_reconcile_per_host_caps_and_reports_truncation():
    n = 100
    device = {"pkts_out": np.arange(n, dtype=np.int64)}
    cpu = {"captured": np.arange(n, dtype=np.int64) + 1}  # all disagree
    found = reconcile_per_host(0, device, cpu,
                               (("pkts_out", "captured"),),
                               max_violations=8)
    assert len(found) == 9  # 8 + the truncation record
    assert found[-1].check == "per-host-mismatch-overflow"
    assert "92" in found[-1].detail


def test_reconcile_fleet():
    ok = reconcile_fleet(5, [("conservation", 10, 10, "d")])
    assert ok == []
    bad = reconcile_fleet(5, [("conservation", 10, 11, "leak")])
    assert len(bad) == 1 and bad[0].check == "conservation"


def test_guard_ledger_policies():
    ledger = GuardLedger(policies={"device": "warn",
                                   "reconcile": "abort"})
    v = GuardViolation(cls="device", check="x", time_ns=1)
    ledger.apply("device", [v])  # warn: records, no raise
    assert ledger.violations == [v]
    with pytest.raises(GuardError) as exc:
        ledger.apply("reconcile", [GuardViolation(
            cls="reconcile", check="y", time_ns=2)])
    assert exc.value.want_checkpoint is False
    ledger.policies["reconcile"] = "abort+checkpoint"
    with pytest.raises(GuardError) as exc:
        ledger.apply("reconcile", [GuardViolation(
            cls="reconcile", check="z", time_ns=3)])
    assert exc.value.want_checkpoint is True
    assert ledger.as_dict()["total"] == 3


# -- progress detection ---------------------------------------------------

def test_progress_detector_trips_after_budget_and_rearms():
    det = ProgressDetector(3)
    # warm-up observation establishes the clock; progress resets streak
    assert det.observe(10, events_delta=2, packets_delta=1) is None
    for t in (20, 30):
        assert det.observe(t, events_delta=0, packets_delta=0) is None
    diag = det.observe(40, events_delta=0, packets_delta=0)
    assert diag is not None
    assert diag.stalled_rounds == 3
    assert diag.first_stalled_ns == 20
    assert diag.window_start_ns == 40
    assert det.trips == 1
    # re-armed: the next stall needs a full fresh budget
    assert det.observe(50, events_delta=0, packets_delta=0) is None


def test_progress_detector_any_progress_resets():
    det = ProgressDetector(2)
    det.observe(1, events_delta=1, packets_delta=0)
    assert det.observe(2, events_delta=0, packets_delta=0) is None
    # a single executed event resets the streak
    assert det.observe(3, events_delta=1, packets_delta=0) is None
    assert det.observe(4, events_delta=0, packets_delta=0) is None
    assert det.observe(5, events_delta=0, packets_delta=0) is not None


def test_progress_detector_requires_time_advance():
    det = ProgressDetector(1)
    det.observe(7, events_delta=0, packets_delta=0)
    # same window start again: time did not advance, no stall counted
    assert det.observe(7, events_delta=0, packets_delta=0) is None
    assert det.observe(8, events_delta=0, packets_delta=0) is not None


def test_stall_diagnosis_describes_waiting_hosts():
    det = ProgressDetector(1)
    det.observe(0, events_delta=1, packets_delta=0)
    diag = det.observe(10, events_delta=0, packets_delta=0)
    diag.waiting = [HostWait("relay4", ["relay4.tgen.0"], None)]
    v = diag.to_violation()
    assert v.cls == "progress" and v.host == "relay4"
    assert "relay4.tgen.0" in v.detail
    assert "no queued events" in v.detail


# -- config ---------------------------------------------------------------

_BASE = ("general: {stop_time: 5s}\n"
         "network: {graph: {type: 1_gbit_switch}}\n"
         "hosts: {a: {network_node_id: 0}}\n")


def test_guards_config_block_parses():
    cfg = load_config_str(_BASE + """
guards:
  enabled: true
  device: warn
  reconcile: abort+checkpoint
  progress: off
  progress_rounds: 16
""")
    g = cfg.guards
    assert g.enabled and g.device == "warn"
    assert g.reconcile == "abort+checkpoint"
    # YAML 1.1 parses bare `off` as False; the policy field maps it back
    assert g.progress == "off"
    assert g.progress_rounds == 16
    assert g.active("device") and g.active("reconcile")
    assert not g.active("progress")
    # disabled master switch deactivates every class
    cfg2 = load_config_str(_BASE + "guards: {device: abort}\n")
    assert not cfg2.guards.active("device")


def test_guards_config_validation():
    with pytest.raises(ConfigError, match="guards.device"):
        load_config_str(_BASE + "guards: {device: explode}\n")
    with pytest.raises(ConfigError, match="progress_rounds"):
        load_config_str(_BASE + "guards: {progress_rounds: 0}\n")
    with pytest.raises(ConfigError, match="unknown option"):
        load_config_str(_BASE + "guards: {bogus: 1}\n")


def test_strict_config_parses():
    assert load_config_str(_BASE + "strict: true\n").strict
    assert not load_config_str(_BASE).strict
    with pytest.raises(ConfigError, match="strict"):
        load_config_str(_BASE + "strict: yes please\n")
    # general.progress stays a plain boolean (the off->policy mapping
    # must not leak onto it)
    cfg = load_config_str(
        _BASE.replace("stop_time: 5s", "stop_time: 5s, progress: off"))
    assert cfg.general.progress is False


# -- transport guard + reconciliation end-to-end --------------------------

_GUARDED_SIM = """
general: {{stop_time: 4s, seed: 7}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{use_tpu_transport: true, tpu_transport_mode: {mode},
               scheduler: serial}}
telemetry: {{enabled: true, interval: 1s, sink: {sink}, trace: off}}
guards: {{enabled: true{extra}}}
hosts:
  server:
    network_node_id: 0
    processes:
    - {{path: udp-echo-server, args: ["9000"], start_time: 1s,
       expected_final_state: running}}
  client:
    network_node_id: 0
    processes:
    - {{path: udp-client, args: ["server", "9000", "4", "50"],
       start_time: 2s}}
"""


def _guarded_manager(tmp_path=None, mode="sync", extra="", sink="off"):
    from shadow_tpu.core.manager import Manager

    cfg = load_config_str(_GUARDED_SIM.format(mode=mode, extra=extra,
                                              sink=sink))
    return Manager(cfg, data_dir=str(tmp_path) if tmp_path else None)


@pytest.mark.parametrize("mode", ["sync", "mirrored"])
def test_guarded_transport_run_is_clean(mode, tmp_path):
    """A healthy guarded run: zero violations from the device guard,
    the harvest-boundary reconciliation, the teardown reconciliation,
    and the progress detector — and the CPU ledger equals the device
    counters exactly."""
    mgr = _guarded_manager(tmp_path, mode=mode)
    stats = mgr.run()
    assert stats.process_failures == []
    assert mgr.guard_violations == []
    report = mgr.transport.guard_report()
    assert report is not None and report["clean"], report
    ledger = mgr.transport.cpu_ledger()
    device = {k: np.asarray(jax.device_get(v), np.int64)
              for k, v in mgr.transport.telemetry_arrays().items()}
    assert np.array_equal(device["pkts_out"], ledger["captured"])
    assert np.array_equal(device["pkts_in"], ledger["released"])
    assert ledger["captured"].sum() == stats.packets_sent
    # the run-long report artifact records a clean run
    rep = json.load(open(tmp_path / "guards-report.json"))
    assert rep["clean"] and rep["total"] == 0


def test_counter_tamper_aborts_with_postmortem_bundle(
        tmp_path, monkeypatch):
    """The seeded counter-tamper proof: a device counter that reads 3
    high for one host trips reconciliation at the FIRST harvest
    boundary; under abort+checkpoint the run dies as a GuardError with
    host blame and the offending counter pair, leaves an emergency
    checkpoint with a valid MANIFEST, a populated guards-report.json,
    and a finalized telemetry sink."""
    from shadow_tpu.faults.checkpoint import load_checkpoint
    from shadow_tpu.tpu.transport import DeviceTransport

    orig = DeviceTransport.telemetry_arrays

    def tampered(self):
        out = orig(self)
        out["pkts_out"] = out["pkts_out"].at[0].add(3)
        return out

    monkeypatch.setattr(DeviceTransport, "telemetry_arrays", tampered)
    sink = str(tmp_path / "telemetry.jsonl")
    mgr = _guarded_manager(tmp_path, extra=", reconcile: abort+checkpoint",
                           sink=sink)
    with pytest.raises(GuardError) as exc:
        mgr.run()
    err = exc.value
    assert err.want_checkpoint
    assert err.violations[0].check == "pkts_out-vs-captured"
    assert err.violations[0].host == "server"
    # emergency checkpoint: present, MANIFEST checksums verify, and it
    # carries the violation ledger
    meta, _arrays = load_checkpoint(
        str(tmp_path / "checkpoints" / "emergency"))
    assert meta["reason"] == "emergency"
    assert meta["guards"]["total"] >= 1
    # populated violation report
    rep = json.load(open(tmp_path / "guards-report.json"))
    assert not rep["clean"] and rep["by_class"] == {"reconcile": 1}
    assert rep["violations"][0]["host"] == "server"
    # telemetry finalized: the sink holds the buffered heartbeats
    assert os.path.getsize(sink) > 0


def test_counter_tamper_plain_abort_skips_checkpoint(
        tmp_path, monkeypatch):
    """Plain `abort` dies with the report but opts out of the
    emergency checkpoint (abort+checkpoint is the postmortem bundle)."""
    from shadow_tpu.tpu.transport import DeviceTransport

    orig = DeviceTransport.telemetry_arrays

    def tampered(self):
        out = orig(self)
        out["pkts_in"] = out["pkts_in"].at[1].add(1)
        return out

    monkeypatch.setattr(DeviceTransport, "telemetry_arrays", tampered)
    mgr = _guarded_manager(tmp_path, extra=", reconcile: abort")
    with pytest.raises(GuardError) as exc:
        mgr.run()
    assert not exc.value.want_checkpoint
    assert not os.path.exists(tmp_path / "checkpoints" / "emergency")
    rep = json.load(open(tmp_path / "guards-report.json"))
    assert rep["total"] >= 1


def test_cli_exit_guard_is_5(tmp_path, monkeypatch):
    """EXIT_GUARD is 5 in the documented table, and the CLI maps a
    GuardError onto it (in-process main so the tamper monkeypatch
    holds)."""
    from shadow_tpu import cli
    from shadow_tpu.tpu.transport import DeviceTransport

    assert cli.EXIT_GUARD == 5

    orig = DeviceTransport.telemetry_arrays

    def tampered(self):
        out = orig(self)
        out["pkts_out"] = out["pkts_out"].at[0].add(2)
        return out

    monkeypatch.setattr(DeviceTransport, "telemetry_arrays", tampered)
    cfg = tmp_path / "sim.yaml"
    cfg.write_text(_GUARDED_SIM.format(
        mode="sync", extra=", reconcile: abort+checkpoint", sink="off")
        .replace("general: {stop_time: 4s, seed: 7}",
                 "general: {stop_time: 4s, seed: 7, data_directory: %s}"
                 % (tmp_path / "data")))
    rc = cli.main([str(cfg)])
    assert rc == 5
    assert (tmp_path / "data" / "guards-report.json").is_file()


# -- progress detection end-to-end ----------------------------------------

class _PhantomTransport:
    """A next-event source that keeps advertising pending device work
    which never materializes — the zero-progress livelock the detector
    exists to catch."""

    divergence_count = 0
    verified_windows = 0
    in_flight = 3

    def __init__(self):
        self.next_pending_abs = None

    def release(self, start_ns, end_ns, horizon_ns=None,
                runahead_ns=None, stop_ns=None):
        self.next_pending_abs = end_ns + 1_000_000  # always "1ms away"

    def finish_round(self, start_ns, end_ns):
        pass

    def finalize(self):
        pass

    def guard_report(self):
        return None


_STALL_SIM = """
general: {{stop_time: 3s, seed: 3, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
experimental: {{scheduler: serial, host_heartbeat_interval: null}}
guards: {{enabled: true, progress: {policy}, progress_rounds: 40}}
hosts:
  server:
    network_node_id: 0
    processes:
    - {{path: udp-echo-server, args: ["9000"], start_time: 1s,
       expected_final_state: running}}
"""


def _stalled_manager(policy):
    from shadow_tpu.core.manager import Manager

    mgr = Manager(load_config_str(_STALL_SIM.format(policy=policy)))
    # the deliberately stalled world: the server blocks on recv forever
    # while a phantom next-event source keeps the round loop spinning
    mgr.transport = _PhantomTransport()
    return mgr


def test_manager_detects_stalled_host_and_aborts():
    mgr = _stalled_manager("abort")
    with pytest.raises(GuardError) as exc:
        mgr.run()
    v = exc.value.violations[0]
    assert v.cls == "progress" and v.check == "zero-progress-livelock"
    # the diagnosis names the blocked host, its process, and the
    # phantom device population
    assert v.host == "server"
    assert "server.udp-echo-server.0" in v.detail
    assert "device in-flight: 3" in v.detail
    assert "40 consecutive rounds" in v.detail


def test_manager_stall_warn_policy_records_and_completes():
    mgr = _stalled_manager("warn")
    stats = mgr.run()
    assert stats.process_failures == []  # the server is expected running
    assert mgr.guard_violations, "warn policy must still record the stall"
    assert all(v.cls == "progress" for v in mgr.guard_violations)
    assert mgr._progress.trips >= 1


# -- strict mode ----------------------------------------------------------

_FLOW_GML = """
      graph [
        node [ id 0 bandwidth_up "1 Gbit" bandwidth_down "1 Gbit" ]
        edge [ source 0 target 0 latency "5 ms" packet_loss 0.0 ]
      ]
"""


def _flow_cfg(extra=""):
    return ("general: {stop_time: 10s, seed: 1}\n"
            "experimental: {use_flow_engine: true}\n"
            + extra +
            "network:\n  graph:\n    type: gml\n    inline: |\n"
            + _FLOW_GML +
            "hosts:\n"
            "  server:\n    network_node_id: 0\n    processes:\n"
            "    - {path: tgen-server, args: ['8888'], start_time: 1s,\n"
            "       expected_final_state: running}\n"
            "  client0:\n    network_node_id: 0\n    processes:\n"
            "    - {path: tgen-client, args: ['server', '8888', '50000',"
            " '1'], start_time: 2s}\n")


@pytest.mark.parametrize("extra,needle", [
    ("telemetry: {enabled: true}\n", "telemetry"),
    ("faults: {watchdog: 10s}\n", "faults"),
    ("faults: {events: [{at: 1s, kind: iface_down, host: server}]}\n",
     "faults"),
    ("guards: {enabled: true}\n", "guards"),
])
def test_strict_promotes_flow_engine_combos(extra, needle, caplog):
    import logging

    from shadow_tpu.core.manager import Manager

    # default: log-and-ignore — the Manager builds, a warning names the
    # dropped feature
    with caplog.at_level(logging.WARNING, logger="shadow_tpu.manager"):
        Manager(load_config_str(_flow_cfg(extra)))
    assert any(needle in r.message and "not supported" in r.message
               for r in caplog.records)
    # strict: the same combo is a ConfigError (exit 2) at build time
    with pytest.raises(ConfigError, match="strict mode"):
        Manager(load_config_str("strict: true\n" + _flow_cfg(extra)))


def test_plane_kernel_no_op_warns_and_strict_refuses():
    """`experimental.plane_kernel` used to parse and then do nothing in
    a Manager-driven run; the option is gone, so a config that sets it
    never reaches the Manager: it is refused as an unknown option, with
    and without `strict: true`."""
    cfg = ("general: {stop_time: 1s, seed: 1}\n"
           "experimental: {plane_kernel: pallas}\n"
           "network:\n  graph:\n    type: 1_gbit_switch\n"
           "hosts:\n  peer0:\n    network_node_id: 0\n")
    for text in (cfg, "strict: true\n" + cfg):
        with pytest.raises(ConfigError,
                           match="unknown option 'plane_kernel'"):
            load_config_str(text)


# -- the device retransmits producer (telemetry satellite) ----------------

def test_transport_retransmits_producer_feeds_harvest():
    """`DeviceTransport.attach_tcp_source` + `tcp.retransmits_by_host`
    + `telemetry.add_retransmits` wire the device `retransmits` field
    end to end: per-connection counters reduce to per-host totals and
    ride the harvester into per-host heartbeat lines."""
    import io

    from shadow_tpu.analysis.jaxpr_audit import _StubHost, _StubRouting
    from shadow_tpu.telemetry import TelemetryHarvester
    from shadow_tpu.tpu import tcp as dtcp
    from shadow_tpu.tpu.transport import DeviceTransport

    n = 4
    dt = DeviceTransport([_StubHost(i + 1, i % 3) for i in range(n)],
                         _StubRouting(3), None, egress_cap=8,
                         ingress_cap=8, mode="sync", compact_cap=16)
    plane = dtcp.make_tcp_plane(6, reass_slots=4)
    plane = plane._replace(retransmit_count=jnp.asarray(
        [2, 0, 1, 3, 0, 5], jnp.int32))
    conn_host = jnp.asarray([0, 0, 1, 3, 3, 3], jnp.int32)
    dt.attach_tcp_source(lambda: plane, conn_host)

    arrays = dt.telemetry_arrays()
    assert np.array_equal(np.asarray(arrays["retransmits"]),
                          [2, 1, 0, 8])

    sink = io.StringIO()
    h = TelemetryHarvester(interval_ns=1_000, sink=sink)
    h.tick(1_000, device=arrays)
    h.finalize()
    lines = [json.loads(line) for line in
             sink.getvalue().strip().splitlines()]
    sim = [r for r in lines if r["type"] == "sim"][0]
    assert sim["device_totals"]["retransmits"] == 11
    host4 = [r for r in lines
             if r["type"] == "host" and r["host_id"] == 4][0]
    assert host4["device"]["retransmits"] == 8
