"""The flow engine on its normal path (YAML -> `Manager.run` ->
`core/flowplan.run_flow_simulation` -> `tpu/floweng`) against the plain
per-flow reference of the benchmark (`benchmark/reference/tgen_flows.py`:
a TCP machine of its own under the engine's documented windows, wire and
app), flow for flow and exactly: completion time, bytes read, segments,
wire drops and retransmissions of every flow, and the reconciled stats.

The reference's machine is also held against a second witness, the CPU
object plane's `TcpConnection` (wire sequence numbers near the 32-bit
wrap) under the same windows.

Also: a repeat run in one process compiles nothing, and the engine's
named scopes reach the lowered program without adding an op."""

import contextlib
import functools
import importlib.util
import os
import re

from dataclasses import replace

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from shadow_tpu.core import flowplan  # noqa: E402
from shadow_tpu.core.config import load_config_str  # noqa: E402
from shadow_tpu.core.manager import Manager  # noqa: E402
from shadow_tpu.tcp import TcpConnection  # noqa: E402
from shadow_tpu.tcp.connection import MSS, Segment  # noqa: E402
from shadow_tpu.tpu import floweng  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/reference/tgen_flows.py", "tgen_flows_reference")
drv = _load("benchmark/drivers/tgen_flows.py", "tgen_flows_driver")

# 20 clients of 4 servers over a 5-node mesh; self-loops as slow as the
# mesh's fastest edge, so every flow lands in the 20 ms bucket. Rings of
# 4 segments hold these flows; every case shares what it can of the
# compiled window programs (one per bucket shape and step cap).
BASE = {"n_hosts": 24, "n_servers": 4, "n_nodes": 5, "graph_seed": 2**31 + 17,
        "directed": False, "latency_ms": [20, 40], "loss": [0.0, 0.0], "self_loop_ms": 20,
        "bandwidth": "1 Gbit"}
TRAFFIC = {"transfer_bytes": 40_000, "transfers_per_client": 1,
           "start_s": [1, 3],
           "server_start_s": 1, "stop_s": 6}
QUEUE_SLOTS = 4

CASES = {
    "lossless": {},
    "lossy": {"loss": [0.02, 0.05]},
    "asymmetric": {"directed": True, "loss": [0.01, 0.02]},
    # 26 clients: 8 share their server's node (5 ms), 18 do not (20 ms)
    "two_buckets": {"n_hosts": 30, "self_loop_ms": 5, "loss": [0.01, 0.02]},
    "ring_rerun": {"loss": [0.01, 0.02]},
    "step_cap_rerun": {"loss": [0.01, 0.02]},
}


def _run(monkeypatch, case, seed):
    cfg = {**BASE, **CASES[case]}
    opts = {}
    # rings of 2 overflow here; the re-run doubles them to 4
    monkeypatch.setattr(flowplan, "QUEUE_SLOTS",
                        2 if case == "ring_rerun" else QUEUE_SLOTS)
    if case == "step_cap_rerun":
        # one scheduled event and one pull per fused step: these flows
        # need up to 4 steps in a window, so a cap of 2 saturates and
        # the restart with a doubled cap runs clean
        opts = {"sched_batch": 1, "pull_cap": 1}
        monkeypatch.setattr(flowplan, "STEP_CAP", 2)
        monkeypatch.setattr(floweng, "run_to_completion", functools.partial(
            floweng.run_to_completion, **opts))
    dep = ref.deployment(cfg, TRAFFIC, seed)
    stats = Manager(load_config_str(drv.yaml_text(dep))).run()
    want = ref.TgenReference(dep, queue_slots=flowplan.QUEUE_SLOTS,
                             opts=opts)
    return stats, want


@pytest.mark.parametrize("case", list(CASES))
def test_engine_equals_reference_flow_for_flow(monkeypatch, case):
    stats, reference = _run(monkeypatch, case, seed=2**31 + 17)
    got = drv.program_output(stats)
    want = reference.run()
    for k in ref.PER_FLOW:
        assert got[k].tolist() == want[k].tolist(), k
    sizes = [f["size"] for f in reference.flows]
    assert ref.compare(got, want, sizes) == {
        "flows_differing": 0, "bytes_gap": 0, "stats_gap": 0}
    assert stats.process_failures == []
    flows = reference.flows
    if case != "lossless":
        assert want["retransmits"].sum() > 0
    else:
        assert want["wire_drops"].sum() == 0
    if case == "asymmetric":
        assert any(f["lat_us"] != f["lat_back_us"] for f in flows)
    assert len(reference.buckets()) == (2 if case == "two_buckets" else 1)
    if case in ("ring_rerun", "step_cap_rerun"):
        assert stats.flow_counters["reruns"] > 0
    else:
        assert stats.flow_counters["reruns"] == 0
    if case == "ring_rerun":
        assert any(e["kind"] == "capacity-growth"
                   for e in stats.capacity_events)


def test_repeat_run_compiles_nothing(monkeypatch):
    monkeypatch.setattr(flowplan, "QUEUE_SLOTS", QUEUE_SLOTS)
    dep = ref.deployment(BASE, TRAFFIC, 3)
    config = load_config_str(drv.yaml_text(dep))
    first = Manager(config).run()
    compiles = []

    def on_duration(event, _dur, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        second = Manager(config).run()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert second.flow_complete_us.tolist() == \
        first.flow_complete_us.tolist()
    c = second.flow_counters
    assert c["dispatches"] > 0 and c["windows"] > 0
    # per bucket: a probe every 3 dispatches, the saturation read, the
    # result read
    assert c["syncs"] == c["dispatches"] // 3 + 2


def _lowered(fn, *args):
    """(the lowered program's text with op locations, its op count)."""
    low = jax.jit(fn).lower(*args)
    return (low.as_text(debug_info=True),
            len(re.findall(r"stablehlo\.", low.as_text())))


def test_scopes_name_the_phases_and_add_no_op(monkeypatch):
    world = floweng.make_flow_world(np.array([20_000, 30_000]),
                                    np.array([10_000, 20_000]),
                                    queue_slots=8)

    def run(w):  # a fresh function per lowering: no cached trace
        return floweng.run_windows(w, n_windows=2, window_us=20_000)

    def status(w):
        return floweng._status_flags(w)

    scoped, n_scoped = _lowered(run, world)
    probe, n_probe = _lowered(status, world)
    for scope in ("flow_sched", "flow_app", "flow_pull", "flow_barrier"):
        assert f"{scope}/" in scoped, scope
    assert "flow_status/" in probe
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    bare, n_bare = _lowered(lambda w: run(w), world)
    assert "flow_sched" not in bare
    assert n_bare == n_scoped > 100
    assert _lowered(lambda w: status(w), world)[1] == n_probe


# -- the CPU object plane as a second witness of the reference's machine ------

class _HeldStore:
    """The engine's out-of-order store (the reference's `Store`) in the
    bytes interface `TcpConnection` keeps its reassembly in."""

    def __init__(self):
        self.inner = ref.Store()

    @property
    def segments(self):
        return {s: bytes(n) for s, n in self.inner.ranges.items()}

    def insert(self, off, data):
        self.inner.insert(off, len(data))

    def drain_from(self, off):
        new = self.inner.drain(off)
        return new, ([bytes(new - off)] if new > off else [])

    def byte_count(self):
        return self.inner.held()

    held = byte_count


class _Deps:
    def __init__(self, lane):
        self.lane = lane
        self.deadline_ms = 0

    def now(self):
        return self.lane.t * 1000

    def set_timer(self, delay_ns, _callback):
        # the engine keeps one deadline per timer, in whole ms
        self.deadline_ms = self.lane.t // 1000 + delay_ns // 1_000_000

    def random_u32(self):
        # sequence numbers that cross 2^32 within the first transfer
        return (2**32 - 30_000 - 7919 * self.lane.lane_id) & 0xFFFFFFFF

    def notify(self):
        pass


class _EngineConn(TcpConnection):
    """`TcpConnection` with the engine's store, macro-segments and
    millisecond deadlines."""

    def __init__(self, lane, gso_segs):
        super().__init__(_Deps(lane))
        self._reassembly = _HeldStore()
        self.gso_segs = gso_segs
        self.rto_deadline_ms = 0
        self.persist_deadline_ms = 0

    def _arm_rto(self):
        super()._arm_rto()
        self.rto_deadline_ms = self.deps.deadline_ms

    def _arm_persist(self):
        was = self._persist_armed
        super()._arm_persist()
        if not was:
            self.persist_deadline_ms = self.deps.deadline_ms

    def _enter_time_wait(self):
        super()._enter_time_wait()
        self.rto_deadline_ms = self.deps.deadline_ms

    def _build_data(self):
        # TcpConnection._build_data with gso_segs x MSS in place of one MSS
        off, cap = self._sacked.next_unsacked(self.snd_nxt)
        if off != self.snd_nxt:
            self.snd_nxt = off
            self.snd_max = max(self.snd_max, off)
        window = min(self.cong.cwnd * MSS, self.snd_wnd)
        n = min(MSS * self.gso_segs, self.stream_len - off,
                window - (off - self.snd_una), cap)
        if n <= 0:
            return self._build_ack()
        self.snd_nxt = off + n
        self.snd_max = max(self.snd_max, self.snd_nxt)
        self._ack_pending = False
        if not self._rto_armed:
            self._arm_rto()
        flags = ref.ACK | (ref.PSH if self.snd_nxt >= self.stream_len
                           else 0)
        return self._stamp(Segment(
            flags=flags, seq=self._wire_seq(off), ack=self._wire_ack(),
            window=self._advertised_window(False), payload=bytes(n),
            sack=self._sack_blocks()))


class Witness:
    """`_EngineConn` behind the interface of the reference's `Conn`."""

    def __init__(self, lane, gso_segs):
        self.c = _EngineConn(lane, gso_segs)
        self.store = self.c._reassembly

    state = property(lambda self: self.c.state.name)
    error = property(lambda self: self.c.error)
    rto_armed = property(lambda self: self.c._rto_armed)
    rto_deadline_ms = property(lambda self: self.c.rto_deadline_ms)
    persist_armed = property(lambda self: self.c._persist_armed)
    persist_deadline_ms = property(lambda self: self.c.persist_deadline_ms)
    ordered = property(lambda self: self.c._ordered_bytes)
    fin_received = property(lambda self: self.c.fin_received)
    ack_pending = property(lambda self: self.c._ack_pending)
    retransmits = property(lambda self: self.c.retransmit_count)

    def open_active(self):
        self.c.open_active()

    def open_passive(self, syn):
        self.c.open_passive(syn)

    def on_segment(self, seg):
        self.c.on_segment(seg)

    def fire_rto(self):
        self.c._on_rto_fire(self.c._rto_gen)

    def fire_persist(self):
        self.c._on_persist_fire(self.c._persist_gen)

    def expire_time_wait(self):
        self.c._enter_closed(None)

    def read(self, n):
        return len(self.c.read(n))

    def send_space(self):
        return self.c.send_space()

    def write(self, n):
        self.c.write(bytes(n))

    def close(self):
        self.c.close()

    def next_kind(self):
        return self.c._next_kind()

    def next_segment(self):
        return self.c.next_segment()

    @staticmethod
    def paylen(seg):
        return len(seg.payload)

    @staticmethod
    def cut(seg, start, n):
        return replace(seg, seq=(seg.seq + start) & 0xFFFFFFFF,
                       payload=seg.payload[start:start + n])


WITNESS_CASES = {
    "lossless": ({}, {}, 256),
    "lossy": ({"loss": [0.02, 0.05]}, {}, 256),
    "asymmetric": ({"directed": True, "loss": [0.03, 0.08]}, {}, 256),
    "two_buckets": ({"n_hosts": 30, "self_loop_ms": 5,
                     "loss": [0.02, 0.05]}, {}, 256),
    "ring_rerun": ({"loss": [0.01, 0.02]}, {}, 2),
    "one_mss_steps": ({"loss": [0.05, 0.1]},
                      {"gso_segs": 1, "ack_every": 1, "sched_batch": 1,
                       "pull_cap": 1}, 256),
    # most transfers give up after their retries
    "give_up": ({"loss": [0.3, 0.5]}, {}, 256),
}


@pytest.mark.parametrize("case", list(WITNESS_CASES))
def test_reference_machine_equals_object_plane_witness(case):
    cfg, opts, slots = WITNESS_CASES[case]
    dep = ref.deployment({**BASE, **cfg}, TRAFFIC, 2**31 + 17)
    plain = ref.TgenReference(dep, queue_slots=slots, opts=opts).run()
    witness = ref.TgenReference(dep, queue_slots=slots, opts=opts,
                                machine=Witness).run()
    for k in (*ref.PER_FLOW, "queue_drops"):
        assert plain[k].tolist() == witness[k].tolist(), k
    for k in ("packets_sent", "packets_dropped", "failures"):
        assert plain[k] == witness[k], k
    if case != "lossless":
        assert plain["retransmits"].sum() > 0
    if case == "ring_rerun":
        assert plain["queue_drops"].sum() == 0 < ref.TgenReference(
            dep, queue_slots=slots, max_doublings=0, opts=opts
        ).run()["queue_drops"].sum()
    if case == "give_up":
        assert plain["failures"] > 0
