#!/usr/bin/env python3
"""Determinism harness: run a config repeatedly — and across scheduler /
parallelism variants — and diff the deterministic artifacts.

Parity: reference determinism CI (`src/test/determinism/CMakeLists.txt` —
determinism1a/1b run identical sims twice and diff; determinism2 repeats
with `--scheduler thread-per-host` to prove event order is independent of
the parallelization strategy). Here the deterministic artifacts are
sim-stats.json (minus wall_seconds), the per-host pcap captures (exact
packet timing and content), process stdout/stderr, and — when the config
sets `experimental.strace_logging_mode: deterministic` — every managed
process's full .strace syscall trace, the reference CI's own diff target
(every hosts/ file is hashed, so strace coverage is automatic).

Usage:
  python tools/compare_runs.py <config.yaml> [--runs 2]       # repeat-diff
  python tools/compare_runs.py <config.yaml> --matrix         # vary
      scheduler (serial / thread-per-core / thread-per-host) and
      parallelism (1 / 2 / 4) and require identical artifacts across all
  python tools/compare_runs.py --bench BEFORE.json AFTER.json # diff two
      bench.py records: headline events/s plus the per-section ms deltas
      (the `sections` field), so the BENCH_r*.json trajectory shows WHERE
      time went (docs/performance.md)
  python tools/compare_runs.py --scenarios BEFORE.json AFTER.json # diff
      two tools/run_scenarios.py records: headline completion-time
      deltas per scenario family plus per-scenario event/completion
      tables (docs/workloads.md)
  python tools/compare_runs.py --memo BEFORE.json AFTER.json # diff two
      tools/run_scenarios.py --memo-report files: per-scenario cache
      economics (hits / misses / fast-forwarded windows / bytes), with
      the loud MEANINGLESS banner when the backend fingerprints differ
      (docs/performance.md "Steady-state memoization")
  python tools/compare_runs.py --trace BEFORE.json AFTER.json # diff two
      tools/run_scenarios.py --trace-report files: per-scenario wall-
      time attribution deltas (total / dispatch / memo / hook, span
      modes) from the shadowscope run ledgers, with the loud
      MEANINGLESS banner when the backend fingerprints differ —
      wall-clock numbers never compare across containers
      (docs/observability.md "Run ledger")
Exit 0 when all runs match bit-for-bit (--bench/--scenarios: always);
1 otherwise.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def comparable_stats(data_dir: str) -> dict:
    """A run's sim-stats.json minus the fields that are not simulation
    state."""
    with open(os.path.join(data_dir, "sim-stats.json")) as fh:
        stats = json.load(fh)
    stats.pop("wall_seconds", None)  # legitimately nondeterministic
    # the round COUNT is loop progress, not simulation state: a managed
    # process death is posted by the wall-clock watcher thread, so the
    # round boundary it drains at may differ while every simulated
    # observable (packets, syscalls, strace bytes, final states) is
    # identical
    stats.pop("rounds", None)
    return stats


def run_once(config: str, data_dir: str,
             extra_args: "Sequence[str]" = ()) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "shadow_tpu", config, "-d", data_dir, "--force",
         *extra_args],
        env=env, capture_output=True, text=True,
    )
    if proc.returncode != 0:
        print(proc.stdout, proc.stderr, file=sys.stderr)
        raise SystemExit(f"run failed (exit {proc.returncode})")
    digest = {"sim-stats": comparable_stats(data_dir)}
    hosts_dir = os.path.join(data_dir, "hosts")
    if os.path.isdir(hosts_dir):
        for host in sorted(os.listdir(hosts_dir)):
            for f in sorted(os.listdir(os.path.join(hosts_dir, host))):
                path = os.path.join(hosts_dir, host, f)
                with open(path, "rb") as fh:
                    digest[f"{host}/{f}"] = hashlib.sha256(fh.read()).hexdigest()
    return digest


# scheduler × parallelism variants for --matrix (determinism2 analogue);
# parallelism is pinned explicitly so a single-core runner cannot silently
# collapse the threaded variants to SerialScheduler (parallelism auto =
# min(cores, hosts))
MATRIX = [
    ("serial-p1", ["--scheduler", "serial", "--parallelism", "1"]),
    ("tpc-p2", ["--scheduler", "thread-per-core", "--parallelism", "2"]),
    ("tpc-p4", ["--scheduler", "thread-per-core", "--parallelism", "4"]),
    ("tph-p4", ["--scheduler", "thread-per-host", "--parallelism", "4"]),
]


def _load_bench(path: str) -> dict:
    with open(path) as fh:
        rec = json.load(fh)
    return rec.get("parsed", rec)  # the PR driver wraps the JSON line


def _delta_table(label: str, s0: dict, s1: dict, width: int = 24,
                 unit: str = "ms"):
    """The shared per-key before/after/ratio printer (bench sections,
    scenario completion tables, and the static cost reports all use
    the same shape; `unit` labels the value columns)."""
    names = sorted(set(s0) | set(s1),
                   key=lambda n: -float(s0.get(n, s1.get(n, 0)) or 0))
    print(f"{label:<{width}} {'before ' + unit:>12} "
          f"{'after ' + unit:>12} {'ratio':>7}")
    for name in names:
        a, b = s0.get(name), s1.get(name)
        ratio = (f"{a / b:.2f}x" if a and b else "-")
        fmt = lambda x: f"{x:.2f}" if x is not None else "-"
        print(f"{name:<{width}} {fmt(a):>12} {fmt(b):>12} {ratio:>7}")


def bench_delta(before_path: str, after_path: str) -> int:
    """Print the headline + per-section deltas between two bench.py JSON
    records (informational — always exits 0). Records measured on
    mismatched backends (the `backend` platform/device-kind
    fingerprint bench.py stamps) get a LOUD warning and no speedup
    verdict: a CPU-container number against an accelerator-container
    number is how the PR-7 false regression happened
    (docs/performance.md)."""
    before, after = _load_bench(before_path), _load_bench(after_path)
    b0, b1 = before.get("backend"), after.get("backend")
    if b0 != b1:
        print("=" * 70)
        print(f"WARNING: backend fingerprints differ — before={b0} "
              f"after={b1}.")
        print("Cross-container throughput ratios are MEANINGLESS; the "
              "delta below is\nprinted for completeness only. "
              "Re-measure both records on one container.")
        print("=" * 70)
    v0, v1 = float(before.get("value", 0)), float(after.get("value", 0))
    speedup = (v1 / v0) if v0 else float("nan")
    verdict = ("  (MISMATCHED BACKENDS — not a speedup)"
               if b0 != b1 else "")
    print(f"events/s: {v0:,.0f} -> {v1:,.0f}  ({speedup:.2f}x){verdict}"
          f"  [hosts {before.get('hosts')} -> {after.get('hosts')}]")
    s0 = dict(before.get("sections") or {})
    s1 = dict(after.get("sections") or {})
    # windows_per_sync is a dimensionless driver ratio riding in
    # `sections` for the trajectory record — print it as one, never as
    # a millisecond row in the table below
    w0 = s0.pop("windows_per_sync", None)
    w1 = s1.pop("windows_per_sync", None)
    if w0 is not None or w1 is not None:
        print(f"windows/sync: {w0} -> {w1}")
    if not (s0 or s1):
        print("(no `sections` field in either record: per-section "
              "device time comes from a profiler trace's named scopes, "
              "benchmark/scopes.py)")
        return 0
    _delta_table("section", s0, s1)
    return 0


def _scenario_completions(path: str) -> tuple[dict, dict, dict]:
    """Load a run_scenarios.py record file -> (per-family completion ms,
    per-scenario completion ms, per-scenario fingerprints)."""
    with open(path) as fh:
        records = json.load(fh).get("records", [])
    family: dict[str, float] = {}
    per_scenario: dict[str, float] = {}
    fps: dict[str, str] = {}
    for rec in records:
        hc = rec.get("host_completion") or {}
        done_ms = (hc.get("max_ns") / 1e6
                   if hc.get("max_ns") is not None else None)
        fps[rec["name"]] = rec.get("fingerprint", "")
        if done_ms is None:
            continue  # incomplete scenario: no headline time
        per_scenario[rec["name"]] = done_ms
        fam = rec.get("family", "?")
        family[fam] = max(family.get(fam, 0.0), done_ms)
    return family, per_scenario, fps


def scenarios_delta(before_path: str, after_path: str) -> int:
    """Print headline completion-time deltas per scenario family, then
    the per-scenario table, between two tools/run_scenarios.py record
    files (informational — always exits 0). Completion times are the
    virtual host_completion.max_ns headline (straggler-inclusive); a
    fingerprint mismatch is flagged since the delta then compares two
    DIFFERENT scenarios, not two runs of one."""
    f0, s0, fp0 = _scenario_completions(before_path)
    f1, s1, fp1 = _scenario_completions(after_path)
    print("scenario-family completion (virtual ms, max over family):")
    _delta_table("family", f0, f1)
    print()
    print("per-scenario completion (virtual ms):")
    _delta_table("scenario", s0, s1)
    for name in sorted(set(fp0) & set(fp1)):
        if fp0[name] != fp1[name]:
            print(f"NOTE: {name}: scenario fingerprint changed between "
                  f"the records — this is a different scenario, not a "
                  f"behavior delta")
    return 0


def _memo_report(path: str) -> tuple[dict | None, dict]:
    """Load a run_scenarios.py --memo-report file -> (backend
    fingerprint, scenario name -> memo stats dict)."""
    with open(path) as fh:
        rec = json.load(fh)
    return rec.get("backend"), dict(rec.get("scenarios") or {})


def memo_delta(before_path: str, after_path: str) -> int:
    """Print per-scenario memo cache-economics deltas (hits, misses,
    fast-forwarded windows, cached bytes) between two run_scenarios.py
    --memo-report files (informational — always exits 0). Reports from
    mismatched backends get the loud banner: memo keys digest device
    bytes, so two containers legitimately populate DIFFERENT caches —
    a hit-rate regression across containers is a fingerprint delta,
    not a memo-plane one (the bench backend-fingerprint rule,
    docs/performance.md)."""
    b0, s0 = _memo_report(before_path)
    b1, s1 = _memo_report(after_path)
    if b0 != b1:
        print("=" * 70)
        print(f"WARNING: backend fingerprints differ — before={b0} "
              f"after={b1}.")
        print("Memo keys digest device bytes, so cross-container "
              "hit-rate deltas are\nMEANINGLESS; the tables below are "
              "printed for completeness only.\nRe-measure both "
              "reports on one container.")
        print("=" * 70)

    def table(metric, unit="count"):
        t0 = {k: v.get(metric) for k, v in s0.items()
              if v.get(metric) is not None}
        t1 = {k: v.get(metric) for k, v in s1.items()
              if v.get(metric) is not None}
        if t0 or t1:
            _delta_table(f"scenario ({metric})", t0, t1, width=32,
                         unit=unit)
            print()

    table("hits")
    table("misses")
    table("fast_forwarded_windows", "windows")
    table("unstable_skips")
    table("bytes_cached", "B")
    return 0


def _trace_report(path: str) -> tuple[dict | None, dict]:
    """Load a run_scenarios.py --trace-report file -> (backend
    fingerprint, scenario name -> phase-totals dict)."""
    with open(path) as fh:
        rec = json.load(fh)
    return rec.get("backend"), dict(rec.get("scenarios") or {})


def trace_delta(before_path: str, after_path: str) -> int:
    """Print per-scenario wall-time attribution deltas between two
    run_scenarios.py --trace-report files (informational — always
    exits 0). Every value here is wall clock, so this mode carries the
    loudest version of the backend-fingerprint rule: a ledger from a
    different container times a different machine, and the banner says
    so before any table prints (docs/observability.md "Run ledger")."""
    b0, s0 = _trace_report(before_path)
    b1, s1 = _trace_report(after_path)
    if b0 != b1:
        print("=" * 70)
        print(f"WARNING: backend fingerprints differ — before={b0} "
              f"after={b1}.")
        print("Every number on a run ledger is wall clock, so "
              "cross-container deltas are\nMEANINGLESS; the tables "
              "below are printed for completeness only.\nRe-trace "
              "both runs on one container.")
        print("=" * 70)

    def table(metric, unit="ms"):
        t0 = {k: v.get(metric) for k, v in s0.items()
              if v.get(metric) is not None}
        t1 = {k: v.get(metric) for k, v in s1.items()
              if v.get(metric) is not None}
        if t0 or t1:
            _delta_table(f"scenario ({metric})", t0, t1, width=32,
                         unit=unit)
            print()

    table("wall_ms")
    table("dispatch_ms")
    table("memo_ms")
    table("hook_ms")
    table("replay_ms")
    table("ffwd_ms")
    table("spans", "count")
    table("growth_events", "count")
    return 0


def _slo_report(path: str) -> tuple[dict | None, dict]:
    """Load a run_scenarios.py --slo-report file -> (backend
    fingerprint, scenario name -> {compute, slo} dict)."""
    with open(path) as fh:
        rec = json.load(fh)
    return rec.get("backend"), dict(rec.get("scenarios") or {})


def slo_delta(before_path: str, after_path: str) -> int:
    """Print per-scenario serving-SLO deltas between two
    run_scenarios.py --slo-report files (informational — always exits
    0): request-sojourn/wait percentiles in virtual ms plus the
    compute-plane served/queued/overflow totals. The values are
    VIRTUAL time (deterministic), so no backend banner — but a missed
    SLO target in the AFTER record is called out per scenario
    (docs/workloads.md 'SLO record schema')."""
    _, s0 = _slo_report(before_path)
    _, s1 = _slo_report(after_path)

    def table(pick, label, unit="ms"):
        t0 = {k: pick(v) for k, v in s0.items() if pick(v) is not None}
        t1 = {k: pick(v) for k, v in s1.items() if pick(v) is not None}
        if t0 or t1:
            _delta_table(f"scenario ({label})", t0, t1, width=32,
                         unit=unit)
            print()

    for q in ("p99", "p999"):
        table(lambda v, q=q: (v["slo"]["sojourn_ns"].get(q, 0) / 1e6
                              if "slo" in v else None),
              f"sojourn {q}")
        table(lambda v, q=q: (v["slo"]["wait_ns"].get(q, 0) / 1e6
                              if "slo" in v else None),
              f"wait {q}")
    for metric in ("served", "queued", "overflow"):
        table(lambda v, m=metric: (v.get("compute") or {}).get(m),
              metric, unit="count")
    missed = [(name, q, t)
              for name, v in sorted(s1.items())
              for q, t in (v.get("slo", {}).get("targets") or {}).items()
              if not t.get("met", True)]
    for name, q, t in missed:
        print(f"SLO MISS (after): {name} {q} measured "
              f"{t['measured_ns']} ns > target {t['target_ns']} ns")
    return 0


def _cost_metrics(path: str) -> tuple[str | None, dict]:
    """Load a shadowlint --cost-report record -> (platform key,
    entry short-name -> metrics dict)."""
    with open(path) as fh:
        rec = json.load(fh)
    per_entry = {}
    for section in rec.get("entries", []):
        short = section["entry"].rsplit(":", 1)[-1]
        per_entry[short] = dict(section.get("metrics") or {})
    return rec.get("platform"), per_entry


def cost_delta(before_path: str, after_path: str) -> int:
    """Print per-entry flops / bytes-accessed / fusion-count deltas
    between two shadowlint cost reports (informational — always exits
    0). Reports whose PLATFORM keys differ get the loud banner: the
    static-analysis twin of the bench backend-fingerprint rule — an
    accelerator compile diffed against a CPU compile is a different
    program, not a cost delta (docs/performance.md)."""
    p0, e0 = _cost_metrics(before_path)
    p1, e1 = _cost_metrics(after_path)
    if p0 != p1:
        print("=" * 70)
        print(f"WARNING: platform keys differ — before={p0} "
              f"after={p1}.")
        print("The two reports budget DIFFERENT compiled programs; "
              "the deltas below are\nprinted for completeness only. "
              "Regenerate both reports on one platform.")
        print("=" * 70)

    def table(metric, unit):
        s0 = {k: v.get(metric) for k, v in e0.items()
              if v.get(metric) is not None}
        s1 = {k: v.get(metric) for k, v in e1.items()
              if v.get(metric) is not None}
        if s0 or s1:
            _delta_table(f"entry ({metric})", s0, s1, width=32,
                         unit=unit)
            print()

    table("flops", "flops")
    table("bytes_accessed", "B")
    table("fusions", "count")
    table("big_boundaries", "count")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config", nargs="?")
    ap.add_argument("--runs", type=int, default=None,
                    help="repeat count (incompatible with --matrix)")
    ap.add_argument(
        "--matrix", action="store_true",
        help="vary scheduler and parallelism instead of repeating",
    )
    ap.add_argument(
        "--bench", nargs=2, metavar=("BEFORE", "AFTER"), default=None,
        help="diff two bench.py JSON records (headline + section deltas) "
             "instead of running the determinism harness",
    )
    ap.add_argument(
        "--scenarios", nargs=2, metavar=("BEFORE", "AFTER"), default=None,
        help="diff two tools/run_scenarios.py record files (completion-"
             "time deltas per scenario family) instead of running the "
             "determinism harness",
    )
    ap.add_argument(
        "--cost", nargs=2, metavar=("BEFORE", "AFTER"), default=None,
        help="diff two shadowlint --cost-report records (per-entry "
             "flops/bytes/fusion-count deltas; loud banner when the "
             "platform keys differ) instead of running the "
             "determinism harness",
    )
    ap.add_argument(
        "--memo", nargs=2, metavar=("BEFORE", "AFTER"), default=None,
        help="diff two tools/run_scenarios.py --memo-report files "
             "(per-scenario hit/miss/fast-forward/bytes deltas; loud "
             "banner when the backend fingerprints differ) instead "
             "of running the determinism harness",
    )
    ap.add_argument(
        "--trace", nargs=2, metavar=("BEFORE", "AFTER"), default=None,
        help="diff two tools/run_scenarios.py --trace-report files "
             "(per-scenario wall-time attribution deltas from the run "
             "ledgers; loud banner when the backend fingerprints "
             "differ) instead of running the determinism harness",
    )
    ap.add_argument(
        "--slo", nargs=2, metavar=("BEFORE", "AFTER"), default=None,
        help="diff two tools/run_scenarios.py --slo-report files "
             "(per-scenario serving sojourn/wait percentile + "
             "compute-plane totals deltas; SLO misses in the AFTER "
             "record are called out) instead of running the "
             "determinism harness",
    )
    args = ap.parse_args(argv)
    modes = [m for m in (args.bench, args.scenarios, args.cost,
                         args.memo, args.trace, args.slo)
             if m is not None]
    if len(modes) > 1:
        ap.error("--bench/--scenarios/--cost/--memo/--trace/--slo are "
                 "mutually exclusive")
    if args.bench is not None:
        if args.config or args.matrix or args.runs is not None:
            ap.error("--bench takes exactly two bench JSONs and no config")
        return bench_delta(*args.bench)
    if args.scenarios is not None:
        if args.config or args.matrix or args.runs is not None:
            ap.error("--scenarios takes exactly two scenario record "
                     "files and no config")
        return scenarios_delta(*args.scenarios)
    if args.cost is not None:
        if args.config or args.matrix or args.runs is not None:
            ap.error("--cost takes exactly two cost reports and no "
                     "config")
        return cost_delta(*args.cost)
    if args.memo is not None:
        if args.config or args.matrix or args.runs is not None:
            ap.error("--memo takes exactly two memo reports and no "
                     "config")
        return memo_delta(*args.memo)
    if args.trace is not None:
        if args.config or args.matrix or args.runs is not None:
            ap.error("--trace takes exactly two trace reports and no "
                     "config")
        return trace_delta(*args.trace)
    if args.slo is not None:
        if args.config or args.matrix or args.runs is not None:
            ap.error("--slo takes exactly two slo reports and no "
                     "config")
        return slo_delta(*args.slo)
    if args.config is None:
        ap.error("config is required (or use --bench)")
    if args.matrix and args.runs is not None:
        ap.error("--runs and --matrix are mutually exclusive")

    variants = (
        MATRIX if args.matrix
        else [(f"run{i}", []) for i in range(args.runs or 2)]
    )
    digests = []
    with tempfile.TemporaryDirectory() as tmp:
        for name, extra in variants:
            digests.append(
                (name, run_once(args.config, os.path.join(tmp, name), extra))
            )
    base_name, base = digests[0]
    ok = True
    for name, d in digests[1:]:
        if d != base:
            ok = False
            for key in sorted(set(base) | set(d)):
                if base.get(key) != d.get(key):
                    print(f"MISMATCH {base_name} vs {name}: {key}")
                    print(f"  {base_name}: {base.get(key)}")
                    print(f"  {name}: {d.get(key)}")
    print("DETERMINISTIC" if ok else "NONDETERMINISTIC")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
