"""Per-section device time from named scopes (benchmark/scopes.py).

Pins both halves of the measurement:

- the program: the PHOLD chain body the benchmark compiles, and the
  AQM / legacy-sort / presence-plane variant of `window_step`, carry
  every section's `jax.named_scope` in their ops' `op_name` metadata;
- the reader: the XSpace wire-format reader against the TPU trace
  fixture (`benchmark/tests/data/tiny_tpu.xplane.pb`, recorded on a
  v5e), the attribution on hand-made intervals, and the metric files on
  a synthetic trace, next to the metric readers that were there before.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import scopes, tracing  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
FIXTURE = os.path.join(BENCH, "tests", "data", "tiny_tpu.xplane.pb")
US = 1_000
MS = 1_000_000

#: the sections of the XLA direct path (FIFO, direct release, no plane)
DIRECT = ("rebase_refill", "egress_order", "loss_latency",
          "ingress_compact", "routing_rank", "routing_place", "release_due",
          "egress_compact", "next_event", "respawn", "ingest_rows")
#: the new metrics and the scopes each reads
NEW_METRICS = {
    "window_step.egress_order.device_ms_per_window": ("egress_order",),
    "window_step.loss_latency.device_ms_per_window": ("loss_latency",),
    "window_step.ingress_compact.device_ms_per_window": ("ingress_compact",),
    "window_step.routing_rank.device_ms_per_window": ("routing_rank",),
    "window_step.routing_place.device_ms_per_window": ("routing_place",),
    "window_step.release_due.device_ms_per_window": ("release_due",),
    "window_step.egress_compact.device_ms_per_window": ("egress_compact",),
    "chain.respawn_ingest.device_ms_per_window": ("respawn", "ingest_rows"),
    "chain.other.device_ms_per_window": (scopes.OTHER,),
}


def _op_scopes(hlo_text: str) -> set:
    return {scopes.scope_of(n)
            for n in re.findall(r'op_name="([^"]*)"', hlo_text)}


# -- the program ---------------------------------------------------------------

def _bench_chain_text():
    """The benchmark's PHOLD chain (window_step + respawn_batch +
    ingest_rows in a lax.scan) at 64 hosts, compiled."""
    from benchmark import run as bench_run
    from shadow_tpu.tpu import make_params, make_state

    with open(os.path.join(BENCH, "configs", "phold-32k.json")) as f:
        cfg = dict(json.load(f), n_hosts=64)
    with open(os.path.join(BENCH, "traffic", "load4.json")) as f:
        traffic = json.load(f)
    drv = bench_run.load_module(os.path.join(BENCH, "drivers", "phold.py"),
                                "bench_driver_phold_scopes")
    run = drv.Run(cfg, traffic, 2**31 + 7, jax.profiler.TraceAnnotation)
    chain = run._chain(jnp.int32(cfg["window_ms"] * MS))
    inp = run.inputs
    params = make_params(inp["latency_ns"], inp["loss"], inp["up_bw_bps"],
                         host_node=inp["host_node"])
    state = make_state(64, egress_cap=cfg["egress_cap"],
                       ingress_cap=cfg["ingress_cap"])
    return chain.lower(state, jnp.zeros((64,), jnp.int32),
                       jnp.arange(4, dtype=jnp.int32), params,
                       jnp.zeros((2,), jnp.uint32)).compile().as_text()


def _aqm_step_text():
    """`window_step` on the AQM path with the legacy sorts and the
    metrics / guards / histogram planes threaded, compiled."""
    from shadow_tpu.guards import make_guards
    from shadow_tpu.telemetry import make_histograms, make_metrics
    from shadow_tpu.tpu import make_params, make_state
    from shadow_tpu.tpu.plane import window_step

    n = 16
    params = make_params(np.full((4, 4), 5 * MS, np.int32),
                         np.zeros((4, 4), np.float32),
                         np.full((n,), 10**9, np.int64),
                         host_node=(np.arange(n) % 4).astype(np.int32))
    state = make_state(n, egress_cap=8, ingress_cap=8, params=params)

    def step(state, key, metrics, guards, hist):
        return window_step(state, params, key, jnp.int32(0),
                           jnp.int32(MS), router_aqm=True,
                           packed_sort=False, metrics=metrics,
                           guards=guards, hist=hist)

    return jax.jit(step).lower(
        state, jax.random.key(0), make_metrics(n), make_guards(n),
        make_histograms(n)).compile().as_text()


@pytest.mark.parametrize("build,expected", [
    (_bench_chain_text, DIRECT),
    (_aqm_step_text, ("rebase_refill", "egress_order", "loss_latency",
                      "ingress_compact", "routing_rank", "routing_place",
                      "codel_drain", "egress_compact", "next_event",
                      "metrics", "guards", "hist")),
], ids=["bench_chain", "aqm_legacy_planes"])
def test_compiled_ops_carry_every_section_scope(build, expected):
    found = _op_scopes(build())
    missing = set(expected) - found
    assert not missing, f"no op carries the scope(s) {sorted(missing)}"


# -- the reader ------------------------------------------------------------------

def test_wire_reader_finds_tf_op_on_the_tpu_fixture():
    with open(FIXTURE, "rb") as f:
        planes = scopes.parse(f.read())
    meta = planes.meta["/device:TPU:0"]
    tf_op = {hlo.split(" = ")[0]: path for hlo, path in meta.values()}
    assert tf_op["%convolution_reduce_fusion"] == "jit(<lambda>)/dot_general:"
    assert tf_op["%copy-done"] == ""


def test_wire_reader_times_match_profile_data():
    tr = tracing.load(os.path.dirname(FIXTURE))
    with open(FIXTURE, "rb") as f:
        planes = scopes.parse(f.read())
    for plane, ops in tr.ops.items():
        assert [(s, e) for _k, s, e in planes.ops[plane]] == \
            [(s, e) for _n, s, e in ops]
        assert [m[:3] for m in planes.modules[plane]] == tr.modules[plane]


def test_clock_offset_pairs_executions_with_host_callbacks():
    with open(FIXTURE, "rb") as f:
        planes = scopes.parse(f.read())
    # three executions (run_id 4, 5, 6); each host CompleteCallbacks
    # event starts this many ns after the device execution's end
    assert scopes.clock_offsets(planes) == [2_009_236, 1_843_524, 1_824_559]
    first = planes.modules["/device:TPU:0"][0]
    assert scopes.clock_offsets(planes, (first[2] + 1, 2**62)) == \
        [1_843_524, 1_824_559]


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(phold_chain)/while/body/egress_order/sort:", "egress_order"),
    ("egress_order/cond/branch_0_fun/jit(take_along_axis):",
     "egress_order"),
    ("ingest_rows/cond/branch_1_fun/routing_rank/sort:", "routing_rank"),
    ("x/release_due/unknown/gather:", "release_due"),
    ("jit(phold_chain)/while/body/dynamic_update_slice:", scopes.OTHER),
    ("", scopes.OTHER),
])
def test_innermost_known_scope_wins(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_buckets_partition_the_chain_modules_op_time():
    # a chain execution 10-40 holding a while op with three body ops,
    # a second execution 60-80 clipped by the window at 70, and another
    # module's op at 85-90
    ops = [("while", 10, 40), ("sort", 12, 20), ("gather", 20, 30),
           ("copy", 30, 31), ("tail", 60, 80), ("other_mod", 85, 90)]
    paths = {"while": "jit(phold_chain)/while:",
             "sort": "egress_order/sort:",
             "gather": "ingest_rows/routing_place/gather:",
             "copy": "", "tail": "x/release_due/y:",
             "other_mod": "egress_order/z:"}
    modules = [("jit_phold_chain(3)", 10, 40), ("jit_phold_chain(3)", 60, 80),
               ("jit_iota(4)", 85, 90)]
    got = scopes.section_seconds(ops, paths, modules, (0, 70))
    assert got == {
        scopes.OTHER: pytest.approx((30 - 8 - 10 - 1 + 1) * 1e-9),
        "egress_order": pytest.approx(8e-9),
        "routing_place": pytest.approx(10e-9),
        "release_due": pytest.approx(10e-9),
    }
    # every op of the chain lands in one bucket: the buckets add up to
    # the busy time inside the chain's executions
    busy = tracing.busy_ns(ops[:4], 10, 40) + tracing.busy_ns(ops[4:5], 60, 70)
    assert sum(got.values()) == pytest.approx(busy * 1e-9)
    assert scopes.section_seconds(ops, paths, modules[2:], (0, 100),
                                  match="phold_chain") is None


def test_a_program_without_scopes_reads_nothing():
    planes = scopes.Planes(
        ops={"/device:TPU:0": [(1, 0, 10)]},
        meta={"/device:TPU:0": {1: ("%sort.1 = sort", "jit(f)/sort:")}},
        modules={"/device:TPU:0": [("jit_phold_chain(1)", 0, 10, 1)]})
    assert scopes.sections(planes, (0, 10)) is None
    assert scopes.sections(scopes.Planes(), (0, 10)) is None


# -- the metric files on a synthetic trace ------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _plane(pid: int, name: str, lines: dict, tf_ops=None) -> bytes:
    """An XPlane: `lines` maps a line name to [(event name, start_ns,
    end_ns)]; `tf_ops` gives event metadata a `tf_op` stat."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    fields = [(1, pid), (2, name)]
    for li, (lname, evs) in enumerate(lines.items()):
        events = [(4, _msg((1, ids[n]), (2, s * 1000), (3, (e - s) * 1000)))
                  for n, s, e in evs]
        fields.append((3, _msg((1, li), (2, lname), (3, 0), *events)))
    for n, i in ids.items():
        stats = ([(5, _msg((1, 1), (5, tf_ops[n])))]
                 if tf_ops and n in tf_ops else [])
        fields.append((4, _msg((1, i), (2, _msg((1, i), (2, n), *stats)))))
    fields.append((5, _msg((1, 1), (2, _msg((1, 1), (2, "tf_op"))))))
    return _msg(*fields)


def _synthetic_trace(scoped: bool) -> bytes:
    """Window 0-100 us; two chain executions (10-40, 50-70 us) and one
    other module; two `chain.launch` spans of 1 and 3 us."""
    paths = {
        "%while.1 = while": "jit(phold_chain)/while:",
        "%fusion.1 = sort": "egress_order/sort:",
        "%fusion.2 = gather": "loss_latency/gather:",
        "%fusion.3 = sort": "ingress_compact/sort:",
        "%fusion.4 = sort": "routing_rank/sort:",
        "%fusion.5 = scatter": "routing_place/scatter:",
        "%fusion.6 = sort": "release_due/sort:",
        "%fusion.7 = sort": "egress_compact/sort:",
        "%fusion.8 = add": "respawn/add:",
        "%fusion.9 = sort": "ingest_rows/cond/branch_1_fun/sort:",
        "%copy.1 = copy": "",
        "%iota.1 = iota": "jit(iota)/iota:",
    }
    if not scoped:
        paths = {k: "jit(phold_chain)/while/body/" + v.rsplit("/", 1)[-1]
                 if v else v for k, v in paths.items()}
    ops = [("%while.1 = while", 10, 40)]
    t = 11
    for name in list(paths)[1:11]:  # ten 2 us ops in the first execution
        ops.append((name, t, t + 2))
        t += 2
    ops += [("%fusion.1 = sort", 50, 60), ("%copy.1 = copy", 60, 70),
            ("%iota.1 = iota", 80, 81)]
    dev = _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit_phold_chain(7)", 10 * US, 40 * US),
                        ("jit_phold_chain(7)", 50 * US, 70 * US),
                        ("jit_iota(8)", 80 * US, 81 * US)],
        "XLA Ops": [(n, s * US, e * US) for n, s, e in ops],
    }, paths)
    host = _plane(2, "/host:CPU", {"python": [
        ("bench.window", 0, 100 * US), ("chain.launch", 1 * US, 2 * US),
        ("chain.launch", 45 * US, 48 * US)]})
    return _msg((1, dev), (1, host))


def _ctx_root(tmp_path, data: bytes):
    """A checkout-shaped tree holding the metric files and a trace where
    benchmark/run.py writes it; returns (root, ctx)."""
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    tmp_path / "benchmark" / "metrics")
    d = tmp_path / ".bench_trace" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(data)
    tr = tracing.load(str(tmp_path / ".bench_trace"))
    ctx = SimpleNamespace(trace=tr, window=tracing.window_of(tr),
                          run={"windows": 4,
                               "state_shapes": [((4, 16), "int32")]},
                          peaks={"hbm_bytes_per_s": 819e9},
                          compiles_in_window=0)
    return tmp_path, ctx


def _read(root, name, ctx):
    path = os.path.join(root, "benchmark", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_new_metrics_partition_the_chain_time(tmp_path):
    data = _synthetic_trace(scoped=True)
    root, ctx = _ctx_root(tmp_path, data)
    # 10-40 us and 50-70 us of chain over 4 windows
    chain_ms = _read(root, "window_step.device_ms_per_window", ctx)
    assert chain_ms == pytest.approx(50e-3 / 4)
    got = {n: _read(root, n, ctx) for n in NEW_METRICS}
    each = 2e-3 / 4  # one 2 us op per section in the first execution
    assert got["window_step.egress_order.device_ms_per_window"] == \
        pytest.approx(each + 10e-3 / 4)
    for n in ("loss_latency", "ingress_compact", "routing_rank",
              "routing_place", "release_due", "egress_compact"):
        assert got[f"window_step.{n}.device_ms_per_window"] == \
            pytest.approx(each)
    assert got["chain.respawn_ingest.device_ms_per_window"] == \
        pytest.approx(2 * each)
    # the while op's own 10 us and the unscoped copy's 2 + 10 us
    assert got["chain.other.device_ms_per_window"] == \
        pytest.approx(22e-3 / 4)
    assert sum(got.values()) == pytest.approx(chain_ms)
    assert _read(root, "driver.chain_launch_ms", ctx) == \
        pytest.approx(2e-3)


def test_new_metrics_read_nothing_without_scopes_or_tpu(tmp_path):
    root, ctx = _ctx_root(tmp_path, _synthetic_trace(scoped=False))
    assert _read(root, "window_step.device_ms_per_window", ctx) is not None
    for n in NEW_METRICS:
        assert _read(root, n, ctx) is None
    untraced = SimpleNamespace(trace=None, window=None, run={"windows": 4})
    for n in (*NEW_METRICS, "driver.chain_launch_ms"):
        assert _read(root, n, untraced) is None


def test_existing_readers_keep_their_fixture_values(tmp_path):
    with open(FIXTURE, "rb") as f:
        root, ctx = _ctx_root(tmp_path, f.read())
    before = {
        "device.idle_share": pytest.approx(99.92305661373666),
        "window_step.device_ms_per_window": None,
        "window_step.hbm_roofline": None,
        "driver.compiles_in_window": 0,
    }
    # the new readers run first: they must leave the shared context as
    # the existing readers expect it
    for n in (*NEW_METRICS, "driver.chain_launch_ms"):
        assert _read(root, n, ctx) is None
    assert {n: _read(root, n, ctx) for n in before} == before
    assert tracing.top_ops(ctx.trace, ctx.window)[0] == \
        ["%convolution_reduce_fusion f32[] fusion", pytest.approx(1.821e-06)]
    assert tracing.idle_gaps(ctx.trace, ctx.window)[:2] == [
        ["chain.readback", pytest.approx(0.001934104)],
        ["chain.dispatch", pytest.approx(0.000450227)]]


def test_report_lists_sections_top_ops_and_offsets(tmp_path):
    root, _ctx = _ctx_root(tmp_path, _synthetic_trace(scoped=True))
    rep = scopes.report(str(root / ".bench_trace"), windows=4)
    assert rep["total"] == pytest.approx(50e-3 / 4)
    assert rep["sections"]["egress_order"] == pytest.approx(12e-3 / 4)
    assert rep["top_ops"][scopes.OTHER][0][0] in ("%while.1", "%copy.1")
    assert rep["clock_offset_ms"]["n"] == 0
