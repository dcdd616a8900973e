"""shadowscope run-ledger contract pins.

Pins every clause of telemetry/tracer.py's contract
(docs/observability.md "Run ledger"):

- **Schema drift guard.** `RUNLEDGER_SCHEMA` and the span-record field
  set (`SPAN_FIELDS`) are pinned verbatim: any field change must bump
  the version or fail here, and `read_ledger` refuses a ledger stamped
  with a different schema rather than mis-attributing fields.
- **Presence invisibility.** A traced `run_scenario` returns a record
  byte-identical to the untraced run — golden tuple AND full record
  surface — on a lossy corpus entry and on a faulted run (the SL501
  discipline, enforced by parity rather than a jaxpr taint proof:
  the tracer has no device surface). CI's trace-parity gate runs the
  FULL corpus with `--trace --check` against the unchanged golden
  file; the @slow cases here are its unfiltered pytest half.
- **One artifact, two spellings.** The ledger's folded memo record is
  the SAME dict the scenario record (and so `--memo-report`)
  publishes; `memo_view` is a filtered view, not a second measurement.
- **Chrome trace well-formedness.** The exported trace is valid JSON,
  every driver child slice nests inside its parent span slice, and
  both clock tracks are named in `otherData.clocks`.
- **Ensemble percentile-of-percentiles.** `histo.ensemble_percentiles`
  matches a hand-computed 2-world case (median averages the pair) and
  emits min/median/max error bars for a 4-world run.
"""

from __future__ import annotations

import json

import pytest

jax = pytest.importorskip("jax")

from shadow_tpu.telemetry import histo, tracer  # noqa: E402

N = 8


# ---------------------------------------------------------------------------
# schema drift guard


def test_schema_version_pinned():
    assert tracer.RUNLEDGER_SCHEMA == "runledger-v1"
    assert tracer.SPAN_FIELDS == (
        "kind", "seq", "r0", "r1", "windows", "mode", "wall_t0_ms",
        "wall_ms", "dispatch_ms", "memo_ms", "hook_ms")
    assert tracer.WALL_FIELDS == frozenset(
        {"wall_t0_ms", "wall_ms", "dispatch_ms", "memo_ms", "hook_ms"})
    assert tracer.SPAN_MODES == ("execute", "replay", "ffwd", "ensemble")


def test_span_record_fields_match_pin():
    t = tracer.RunTracer("pin", backend={"platform": "cpu",
                                         "device_kind": "cpu"})
    rec = t.span(0, 4, mode="execute", t0=t.clock())
    assert tuple(rec.keys()) == tracer.SPAN_FIELDS
    # optional fields ride AFTER the pinned prefix
    rec2 = t.span(4, 8, mode="execute", t0=t.clock(),
                  growth=[{"kind": "capacity-growth"}], span_salt="ab")
    assert tuple(rec2.keys())[:len(tracer.SPAN_FIELDS)] == \
        tracer.SPAN_FIELDS


def test_read_ledger_refuses_schema_drift(tmp_path):
    t = tracer.RunTracer("rt", backend={"platform": "cpu",
                                        "device_kind": "cpu"})
    t.span(0, 2, mode="execute", t0=t.clock())
    t.close()
    path = tmp_path / "run.ledger.jsonl"
    t.write(str(path))
    records = tracer.load_ledger(str(path))
    assert [r["kind"] for r in records] == ["meta", "span", "end"]

    lines = path.read_text().splitlines()
    head = json.loads(lines[0])
    head["schema"] = "runledger-v999"
    with pytest.raises(ValueError, match="schema mismatch"):
        tracer.read_ledger([json.dumps(head)] + lines[1:])
    with pytest.raises(ValueError, match="meta"):
        tracer.read_ledger(lines[1:])  # headless ledger refuses too


def test_phase_totals_attribution():
    t = tracer.RunTracer("pt", backend={"platform": "cpu",
                                        "device_kind": "cpu"})
    t0 = t.clock()
    t.span(0, 4, mode="execute", t0=t0, dispatch_ms=2.0, memo_ms=0.5,
           hook_ms=0.25)
    t.span(4, 8, mode="replay", t0=t0, hook_ms=0.25)
    t.span(8, 16, mode="ffwd", t0=t0)
    t.close()
    ph = tracer.phase_totals(t.records)
    assert ph["spans"] == 3
    assert ph["windows"] == 16
    assert ph["dispatch_ms"] == 2.0
    assert ph["memo_ms"] == 0.5
    assert ph["hook_ms"] == 0.5
    assert ph["execute_spans"] == 1
    assert ph["replay_spans"] == 1
    assert ph["ffwd_spans"] == 1
    assert ph["ensemble_spans"] == 0
    assert "run_wall_ms" in ph


# ---------------------------------------------------------------------------
# chrome-trace export: valid JSON, nested driver slices, named clocks


def _synthetic_ledger():
    return [
        {"kind": "meta", "schema": tracer.RUNLEDGER_SCHEMA,
         "label": "synthetic",
         "backend": {"platform": "cpu", "device_kind": "cpu"}},
        {"kind": "span", "seq": 0, "r0": 0, "r1": 8, "windows": 8,
         "mode": "execute", "wall_t0_ms": 0.0, "wall_ms": 10.0,
         "dispatch_ms": 6.0, "memo_ms": 1.0, "hook_ms": 2.0,
         "growth": [{"kind": "capacity-growth", "ring": "egress"}]},
        {"kind": "harvest", "wall_t0_ms": 10.5, "r": 8},
        {"kind": "span", "seq": 1, "r0": 8, "r1": 16, "windows": 8,
         "mode": "replay", "wall_t0_ms": 11.0, "wall_ms": 1.0,
         "dispatch_ms": 0.0, "memo_ms": 0.0, "hook_ms": 0.5},
        {"kind": "end", "wall_ms": 12.5, "spans": 2, "windows": 16},
    ]


class _Annotations:
    """A stand-in for `jax.profiler.TraceAnnotation` that logs every
    span entered and exited."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **args):
        log = self.log

        class _Span:
            def __enter__(self):
                log.append(("enter", name, args))

            def __exit__(self, *exc):
                log.append(("exit", name, args))
                return False

        return _Span()


@pytest.mark.parametrize("with_tracer", [False, True])
def test_phase_opens_a_profiler_span_and_times_it(monkeypatch, with_tracer):
    """`tracer.phase` enters and exits one annotation with its
    arguments, and gives the ledger its wall ms only with a tracer."""
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    t = tracer.RunTracer("t", backend={"platform": "cpu"}) \
        if with_tracer else None
    with tracer.phase("chain.launch", t, r0=4, r1=8) as p:
        assert ann.log == [("enter", "chain.launch", {"r0": 4, "r1": 8})]
    assert ann.log[-1] == ("exit", "chain.launch", {"r0": 4, "r1": 8})
    if with_tracer:
        assert p.ms >= 0.0 and p.t0 > 0.0
    else:
        assert (p.ms, p.t0) == (0.0, 0.0)
    # an exception closes the span and propagates
    with pytest.raises(KeyError):
        with tracer.phase("chain.hook", t):
            raise KeyError("x")
    assert ann.log[-1] == ("exit", "chain.hook", {})


def test_chrome_trace_valid_and_nested(tmp_path):
    out = tmp_path / "trace.json"
    info = tracer.write_chrome_trace(_synthetic_ledger(), str(out))
    trace = json.loads(out.read_text())  # valid JSON or this raises
    assert info["events"] == len(trace["traceEvents"])
    clocks = trace["otherData"]["clocks"]
    assert "driver (wall time)" in clocks
    assert "simulation (virtual time)" in clocks

    slices = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    parents = [e for e in slices if e["name"].startswith(
        ("execute", "replay", "ffwd", "ensemble"))]
    children = [e for e in slices if e not in parents]
    assert len(parents) == 2
    assert children, "wall split must render as child slices"
    eps = 1e-6
    for child in children:
        assert any(
            p["ts"] - eps <= child["ts"] and
            child["ts"] + child["dur"] <= p["ts"] + p["dur"] + eps
            for p in parents), (child, parents)
    instants = [e for e in trace["traceEvents"] if e.get("ph") == "i"]
    assert any(e["name"] == "harvest" for e in instants)
    # every driver row stays off the simulation pids
    for e in slices + instants:
        assert e["pid"] == tracer.DRIVER_PID


def test_chrome_trace_merges_sim_rows(tmp_path):
    heartbeats = [
        {"type": "sim", "time_ns": 1_000, "windows": 1, "events": 3},
        {"type": "host", "time_ns": 1_000, "host_id": 0,
         "host": "h0", "counters": {"bytes_out": 64, "bytes_in": 0}},
    ]
    out = tmp_path / "merged.json"
    tracer.write_chrome_trace(_synthetic_ledger(), str(out),
                              heartbeats=heartbeats)
    trace = json.loads(out.read_text())
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert tracer.DRIVER_PID in pids
    assert len(pids) > 1, "simulation rows must merge beside the driver"


# ---------------------------------------------------------------------------
# ensemble percentile of percentiles


def test_ensemble_percentiles_hand_computed_two_worlds():
    # bucket upper edge is 2^(i+1):
    # world A: all mass in bucket 3 -> every percentile = 16
    # world B: all mass in bucket 5 -> every percentile = 64
    a = [0] * histo.HIST_BUCKETS
    a[3] = 10
    b = [0] * histo.HIST_BUCKETS
    b[5] = 10
    assert histo.percentiles(a)["p50"] == 16
    assert histo.percentiles(b)["p50"] == 64
    pp = histo.ensemble_percentiles([a, b])
    for q in ("p50", "p90", "p99", "p999"):
        # median of a 2-world ensemble averages the pair: (16+64)/2
        assert pp[q] == {"min": 16, "median": 40.0, "max": 64,
                         "worlds": 2}, q


def test_ensemble_percentiles_four_world_error_bars():
    worlds = []
    for shift in range(4):
        counts = [0] * histo.HIST_BUCKETS
        counts[4 + shift] = 100
        worlds.append(counts)
    pp = histo.ensemble_percentiles(worlds)
    bars = pp["p50"]
    assert bars["worlds"] == 4
    assert bars["min"] == 32 and bars["max"] == 256
    assert bars["min"] <= bars["median"] <= bars["max"]


def test_ensemble_percentiles_refuses_empty():
    with pytest.raises(ValueError):
        histo.ensemble_percentiles([])


def test_telemetry_report_ensemble_cli(tmp_path, capsys):
    import tools.telemetry_report as tr

    paths = []
    for w in range(4):
        counts = [0] * histo.HIST_BUCKETS
        counts[4 + w] = 100
        path = tmp_path / f"w{w}.jsonl"
        path.write_text(json.dumps(
            {"type": "sim", "time_ns": 1_000,
             "hist": {histo.HIST_PREFIX + "delivery_ns": counts}}) + "\n")
        paths.append(str(path))
    assert tr.main([*paths, "--ensemble", "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["worlds"] == 4
    bars = rep["percentile_of_percentiles"]["delivery_ns"]["p50"]
    assert set(bars) == {"min", "median", "max", "worlds"}


# ---------------------------------------------------------------------------
# presence invisibility + memo agreement (@slow: full scenario
# executions — CI's trace-parity gate runs these unfiltered alongside
# `tools/run_scenarios.py --trace --check`, the shared-driver-gate
# pattern)


def _load(name):
    import os

    from shadow_tpu.workloads import load_scenario_file

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return load_scenario_file(os.path.join(repo, "scenarios", name))


@pytest.mark.slow
@pytest.mark.parametrize("yaml_name", ["rpc_fanout_lossy.yaml",
                                       "incast.yaml"])
def test_traced_golden_scenario_record_identical(yaml_name):
    from shadow_tpu.workloads import runner

    spec = _load(yaml_name)
    plain = runner.run_scenario(spec)
    t = tracer.RunTracer(spec.name)
    traced = runner.run_scenario(spec, tracer=t)
    # the FULL record surface, not just the golden tuple: the ledger
    # is a separate artifact and the record carries zero wall time
    assert traced == plain, spec.name
    assert runner.golden_entry(traced) == runner.golden_entry(plain)
    spans = [r for r in t.records if r.get("kind") == "span"]
    assert spans and all(r["mode"] == "execute" for r in spans)
    assert sum(r["windows"] for r in spans) == spec.windows


@pytest.mark.slow
def test_traced_faulted_run_record_identical_with_span_salts():
    from shadow_tpu.workloads import runner

    spec = _load("rpc_fanout.yaml")
    plain = runner.run_scenario(spec, use_default_faults=True)
    t = tracer.RunTracer(spec.name)
    traced = runner.run_scenario(spec, use_default_faults=True,
                                 tracer=t)
    assert traced == plain
    spans = [r for r in t.records if r.get("kind") == "span"]
    assert spans
    # faulted spans stamp the fault-span fingerprint on the ledger
    assert all("span_salt" in r for r in spans), spans


@pytest.mark.slow
def test_memo_report_and_ledger_memo_record_agree():
    from shadow_tpu.workloads import runner

    spec = _load("ring_allreduce.yaml")
    t = tracer.RunTracer(spec.name)
    rec = runner.run_scenario(spec, memo=True, tracer=t)
    view = tracer.memo_view(t.records)
    assert view is not None
    # one artifact, two spellings: the record's memo report (what
    # --memo-report publishes per scenario) IS the ledger's
    assert view == rec["memo"]
    assert view["hits"] + view["misses"] > 0
    # replay/ffwd spans land on the ledger when the cache hits
    modes = {r["mode"] for r in t.records if r.get("kind") == "span"}
    if view["hits"]:
        assert modes & {"replay", "ffwd"}, modes
