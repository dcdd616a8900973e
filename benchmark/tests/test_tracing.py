"""The trace reduction on hand-made intervals, and the loader on a small
trace recorded on a TPU v5e (data/tiny_tpu.xplane.pb)."""

import os

import pytest

from benchmark import tracing

MS = 1_000_000
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_trace():
    """Window 0..100 ms. Device A: ops at 10-25, 25-40, 60-70;
    device B: 0-50. Module `jit_phold_chain` covers 10-40 and 60-70 on A."""
    tr = tracing.Trace()
    tr.ops["/device:TPU:0"] = [("sort", 10 * MS, 25 * MS),
                               ("gather", 25 * MS, 40 * MS),
                               ("sort", 60 * MS, 70 * MS)]
    tr.ops["/device:TPU:1"] = [("fusion", 0, 50 * MS)]
    tr.modules["/device:TPU:0"] = [("jit_phold_chain(1)", 10 * MS, 40 * MS),
                                   ("jit_phold_chain(1)", 60 * MS, 70 * MS),
                                   ("jit_other", 80 * MS, 81 * MS)]
    tr.spans = [("bench.window", 0, 100 * MS),
                ("chain.dispatch", 0, 5 * MS),
                ("chain.readback", 40 * MS, 100 * MS)]
    return tr


def test_window_is_the_bench_window_span():
    assert tracing.window_of(hand_trace()) == (0, 100 * MS)


def test_union_merges_overlaps_and_clips():
    iv = [("a", 10, 30), ("b", 20, 40), ("c", 60, 70), ("d", 95, 200)]
    assert tracing.union(iv, 0, 100) == [(10, 40), (60, 70), (95, 100)]
    assert tracing.busy_ns(iv, 0, 100) == 30 + 10 + 5


def test_busy_is_averaged_over_devices_that_ran():
    busy_s, window_s = tracing.device_busy(hand_trace(), (0, 100 * MS))
    # A: 40 ms busy (10-40, 60-70), B: 50 ms
    assert busy_s == pytest.approx(0.045)
    assert window_s == pytest.approx(0.1)


def test_no_device_op_means_no_reading():
    tr = tracing.Trace(spans=[("bench.window", 0, 10)])
    assert tracing.device_busy(tr, (0, 10)) is None
    assert tracing.module_seconds(tr, (0, 10), "phold_chain") is None


def test_module_seconds_sums_matching_modules():
    s = tracing.module_seconds(hand_trace(), (0, 100 * MS), "phold_chain")
    assert s == pytest.approx(0.040)


def test_top_ops_ranks_by_device_time():
    top = tracing.top_ops(hand_trace(), (0, 100 * MS))
    assert [n for n, _ in top] == ["fusion", "sort", "gather"]
    assert top[0][1] == pytest.approx(0.05)
    assert top[1][1] == pytest.approx(0.025)


def test_self_time_leaves_out_nested_ops():
    # a while op 0-100 holding a sort 10-30 and a gather 40-50, which
    # holds a fusion 42-44
    ops = [("while", 0, 100), ("sort", 10, 30), ("gather", 40, 50),
           ("fusion", 42, 44)]
    st = tracing.self_times(ops, 0, 100)
    assert st == {"while": pytest.approx(70e-9), "sort": pytest.approx(20e-9),
                  "gather": pytest.approx(8e-9),
                  "fusion": pytest.approx(2e-9)}


def test_op_label_keeps_name_type_and_opcode():
    hlo = ("%fusion.227 = pred[1048576]{0:T(1024)(128)(4,1)S(1)} "
           "fusion(pred[32768,32]{1,0} %copy.597), kind=kLoop")
    assert tracing.op_label(hlo) == "%fusion.227 pred[1048576] fusion"
    assert tracing.op_label("%while.76 = (s32[], s32[2]) while(%t)") \
        == "%while.76"


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = tracing.idle_gaps(hand_trace(), (0, 100 * MS))
    # device A idles 0-10, 40-60, 70-100
    assert gaps == [["chain.readback", pytest.approx(0.03)],
                    ["chain.readback", pytest.approx(0.02)],
                    ["chain.dispatch", pytest.approx(0.01)]]


def test_loader_reads_a_recorded_tpu_trace(tmp_path):
    src = os.path.join(DATA, "tiny_tpu.xplane.pb")
    if not os.path.exists(src):
        pytest.skip("no recorded trace in benchmark/tests/data")
    d = tmp_path / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "x.xplane.pb").write_bytes(open(src, "rb").read())
    tr = tracing.load(str(tmp_path))
    window = tracing.window_of(tr)
    assert window is not None
    busy_s, window_s = tracing.device_busy(tr, window)
    assert 0 < busy_s <= window_s
    assert tracing.top_ops(tr, window)
    assert {s[0] for s in tr.spans} >= {"chain.dispatch", "chain.readback"}
