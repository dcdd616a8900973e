"""The flow-engine scope reader and the least-bytes count behind
`floweng.hbm_roofline`, on hand-made traces and shapes."""

import importlib.util
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import flowscopes, scopes, tracing

MS = 1_000_000
HERE = os.path.dirname(os.path.abspath(__file__))


def hand_ops():
    """One TPU plane. Module `jit_run_windows` 0-10 ms holds a while op
    0-9 (self 2 ms) with a sched op 1-4, a pull op 4-7 and an app op
    8-9; module `jit__status_flags` 20-21 holds one status op; an op at
    50-60 ms falls outside the window (0-40 ms)."""
    dev = "/device:TPU:0"
    ev = [(1, 0, 9 * MS), (2, 1 * MS, 4 * MS), (3, 4 * MS, 7 * MS),
          (4, 8 * MS, 9 * MS), (5, 20 * MS, 21 * MS), (6, 50 * MS, 60 * MS)]
    body = "jit(run_windows)/while/body/"
    meta = {
        1: ("%while.3", "jit(run_windows)/while:"),
        2: ("%fusion.1", body + "flow_sched/while/body/select_n:"),
        3: ("%scatter.2", body + "flow_pull/while/body/scatter:"),
        4: ("%fusion.9", body + "flow_app/add:"),
        5: ("%reduce.1", "jit(_status_flags)/flow_status/reduce_and:"),
        6: ("%fusion.5", body + "flow_sched/add:"),
    }
    return flowscopes.DeviceOps(
        ops={dev: tuple(np.array(c, np.int64) for c in zip(*ev))},
        meta={dev: meta})


def test_scope_of_takes_the_innermost_flow_scope():
    assert flowscopes.scope_of(
        "jit(run_windows)/while/body/flow_pull/while/body/scatter:") \
        == "flow_pull"
    assert flowscopes.scope_of(
        "jit(run_windows)/flow_barrier/cond/flow_pull/x:") == "flow_pull"
    assert flowscopes.scope_of("jit(run_windows)/while/cond/lt:") == "other"
    assert flowscopes.scope_of("") == "other"


def test_phases_partition_the_device_time():
    got = flowscopes.phase_seconds(hand_ops(), (0, 40 * MS))
    assert got == {"flow_sched": pytest.approx(3e-3),
                   "flow_app": pytest.approx(1e-3),
                   "flow_pull": pytest.approx(3e-3),
                   "flow_barrier": 0.0,
                   "flow_status": pytest.approx(1e-3),
                   "other": pytest.approx(2e-3)}
    # every op inside the window's modules lands in one bucket
    mid, start, end = hand_ops().ops["/device:TPU:0"]
    total = sum(tracing.self_times(list(zip(mid, start, end)), 0,
                                   40 * MS).values())
    assert sum(got.values()) == pytest.approx(total)


def test_no_named_scope_means_no_reading():
    ops = hand_ops()
    dev = "/device:TPU:0"
    ops.meta[dev] = {k: (h, "jit(f)/while/body/add:")
                     for k, (h, _) in ops.meta[dev].items()}
    assert flowscopes.phase_seconds(ops, (0, 40 * MS)) is None
    assert flowscopes.phase_seconds(hand_ops(), (30 * MS, 40 * MS)) \
        is None


def test_reader_of_a_recorded_trace_agrees_with_the_wire_reader():
    with open(os.path.join(HERE, "data", "tiny_tpu.xplane.pb"), "rb") as f:
        data = f.read()
    fast, plain = flowscopes.parse(data), scopes.parse(data)
    assert set(fast.ops) == set(plain.ops) == {"/device:TPU:0"}
    for dev, (mid, start, end) in fast.ops.items():
        assert list(zip(mid.tolist(), start.tolist(), end.tolist())) \
            == plain.ops[dev]
        assert fast.meta[dev] == plain.meta[dev]
    assert any(tf for _h, tf in fast.meta["/device:TPU:0"].values())


def nested_intervals(rng, n, lo=0, hi=10**6, depth=0):
    """Up to n (key, start, end) intervals in [lo, hi], siblings apart or
    touching, each possibly holding nested ones."""
    out, t = [], lo
    while t < hi and len(out) < n:
        end = min(hi, t + int(rng.integers(1, max(2, hi - t))))
        out.append((int(rng.integers(0, 20)), t, end))
        if depth < 4 and end - t > 2 and rng.random() < 0.5:
            out += nested_intervals(rng, n - len(out), t, end, depth + 1)
        t = end + int(rng.integers(0, 3))
    return out


@pytest.mark.parametrize("seed", range(4))
def test_self_times_equal_the_stack_count(seed):
    rng = np.random.default_rng(seed)
    ev = nested_intervals(rng, 2000)
    rng.shuffle(ev)
    lo, hi = int(rng.integers(0, 1000)), int(rng.integers(10**5, 10**6))
    want = tracing.self_times([(str(k), s, e) for k, s, e in ev], lo, hi)
    mid, ns = flowscopes.self_times(*zip(*ev), lo, hi)
    got = {}
    for k, t in zip(mid.tolist(), ns.tolist()):
        got[str(k)] = got.get(str(k), 0) + t / 1e9
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k], abs=1e-12)


def test_reader_gives_ms_per_run(monkeypatch):
    monkeypatch.setattr(flowscopes, "load", lambda _d: hand_ops())
    ctx = SimpleNamespace(trace=tracing.Trace(), window=(0, 40 * MS),
                          run={"runs": 2})
    sched = flowscopes.reader(__file__, "flow_sched")
    every = flowscopes.reader(__file__)
    assert sched(ctx) == pytest.approx(1.5)
    assert every(ctx) == pytest.approx(5.0)
    ctx.trace = None
    assert every(ctx) is None


def test_span_ms_per_run_sums_the_named_spans():
    tr = tracing.Trace(ops={"/device:TPU:0": [("x", 0, 1)]},
                       spans=[("bench.window", 0, 100 * MS),
                              ("flow.plan", 1 * MS, 3 * MS),
                              ("flow.world", 5 * MS, 6 * MS),
                              ("flow.dispatch", 6 * MS, 9 * MS),
                              ("flow.plan", 120 * MS, 130 * MS)])
    ctx = SimpleNamespace(trace=tr, window=(0, 100 * MS), run={"runs": 1})
    assert flowscopes.span_ms_per_run(ctx, ("flow.plan", "flow.world")) \
        == pytest.approx(3.0)
    ctx.trace = tracing.Trace(spans=tr.spans)  # no TPU plane: no reading
    assert flowscopes.span_ms_per_run(ctx, ("flow.plan",)) is None


def test_least_bytes_by_hand():
    # 41 TCP words, 32 of SACK scoreboard, 64 of out-of-order store, 18
    # of app and wire; 15 words a ring slot
    assert flowscopes.conn_bytes(0) == 4 * 155
    assert flowscopes.conn_bytes(256) == 4 * (155 + 256 * 15) == 15_980
    buckets = [{"window_us": 20_000, "flows": 3, "last_complete_us": 50_000},
               {"window_us": 5_000, "flows": 1, "last_complete_us": 5_000}]
    # with 4 ring slots a connection holds 620 + 240 bytes, read once and
    # written once per window: 6 connections x 3 windows, 2 x 1
    assert flowscopes.least_bytes(4, buckets) == 2 * 860 * (6 * 3 + 2 * 1)


def test_roofline_share_from_device_time():
    path = os.path.join(HERE, os.pardir, "metrics", "floweng.hbm_roofline.py")
    spec = importlib.util.spec_from_file_location("roof", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    buckets = [{"window_us": 1, "flows": 1, "last_complete_us": 1}]
    # two connections of 860 bytes, read and written: 3,440 bytes at
    # 3.44 GB/s is 1 us; 2 us of device time -> 50%
    assert mod.share(4, buckets, 3.44e9, 2e-3) == pytest.approx(50.0)
