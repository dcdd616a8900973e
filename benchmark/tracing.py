"""Reduce a `jax.profiler` trace to what the per-layer metrics read.

`load(dir)` reads the newest `.xplane.pb` under `dir` with
`jax.profiler.ProfileData` and returns a `Trace`: per device plane the
op intervals (line "XLA Ops") and module executions (line "XLA
Modules"), and the host spans that the harness annotated. Everything
after that is plain arithmetic on (name, start_ns, end_ns) tuples, so it
is tested on hand-made intervals (benchmark/tests/test_tracing.py)."""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
# host spans the harness writes; gaps are labelled by the innermost one
HOST_PREFIXES = ("bench.", "chain.", "setup.", "flow.")


_HLO = re.compile(r"^(%[^ ]+) = ([a-z0-9]+\[[0-9,]*\])[^ ]* ([a-z-]+)\(")


def op_label(hlo: str) -> str:
    """"%fusion.7 = s32[512,32]{1,0:T(8,128)} fusion(...), ..." ->
    "%fusion.7 s32[512,32] fusion"; tuples keep the bare op name."""
    m = _HLO.match(hlo)
    if m:
        return " ".join(m.groups())
    return hlo.split(" = ")[0]


@dataclass
class Trace:
    # device plane name -> [(op name, start_ns, end_ns)]
    ops: dict = field(default_factory=dict)
    # device plane name -> [(module name, start_ns, end_ns)]
    modules: dict = field(default_factory=dict)
    # [(span name, start_ns, end_ns)] from the host plane
    spans: list = field(default_factory=list)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[plane.name] = [(op_label(e.name), e.start_ns,
                                           e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    tr.modules[plane.name] = [
                        (e.name, e.start_ns, e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                tr.spans += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(HOST_PREFIXES)]
    return tr


def window_of(tr: Trace):
    """The traced window: the harness's `bench.window` host span."""
    w = [s for s in tr.spans if s[0] == WINDOW_SPAN]
    if not w:
        return None
    return w[-1][1], w[-1][2]


def union(intervals, lo, hi):
    """Merged, clipped [(start, end)] of the given intervals."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals
                if e > lo and s < hi)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(intervals, lo, hi) -> float:
    return float(sum(e - s for s, e in union(intervals, lo, hi)))


def device_busy(tr: Trace, window):
    """(busy seconds averaged over the devices that ran an op, window
    seconds), or None when no device op ran in the window."""
    lo, hi = window
    per = [busy_ns(ops, lo, hi) for ops in tr.ops.values()]
    per = [b for b in per if b > 0]
    if not per:
        return None
    return sum(per) / len(per) / 1e9, (hi - lo) / 1e9


def module_seconds(tr: Trace, window, match: str):
    """Device seconds of module executions whose name contains `match`,
    summed over devices (within the window), or None if none ran."""
    lo, hi = window
    tot, n = 0.0, 0
    for mods in tr.modules.values():
        for name, s, e in mods:
            if match in name and e > lo and s < hi:
                tot += min(e, hi) - max(s, lo)
                n += 1
    return tot / 1e9 if n else None


def self_times(ops, lo, hi):
    """{op name: seconds} of self time within [lo, hi]: each op's span
    less the spans of the ops nested in it (a while loop's body ops sit
    inside the while op on the same line)."""
    ev = sorted(((max(s, lo), min(e, hi), n) for n, s, e in ops
                 if e > lo and s < hi), key=lambda x: (x[0], -x[1]))
    out = defaultdict(float)
    stack = []  # [start, end, name, child ns]

    def pop():
        s, e, n, child = stack.pop()
        out[n] += (e - s - child) / 1e9

    for s, e, n in ev:
        while stack and s >= stack[-1][1]:
            pop()
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, n, 0])
    while stack:
        pop()
    return out


def top_ops(tr: Trace, window, k: int = 10):
    """[[op name, seconds]] of the k device ops with the most self time,
    summed over devices."""
    lo, hi = window
    acc = defaultdict(float)
    for ops in tr.ops.values():
        for n, t in self_times(ops, lo, hi).items():
            acc[n] += t
    return [[n, t] for n, t in sorted(acc.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(tr: Trace, window, k: int = 10):
    """[[host span, seconds]] of the k longest device-idle gaps in the
    window (on the first device that ran anything), each labelled by
    the innermost harness span covering the gap's middle."""
    lo, hi = window
    planes = [p for p, ops in sorted(tr.ops.items())
              if busy_ns(ops, lo, hi) > 0]
    if not planes:
        return []
    busy = union(tr.ops[planes[0]], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        cover = [sp for sp in tr.spans
                 if sp[1] <= mid <= sp[2] and sp[0] != WINDOW_SPAN]
        label = (min(cover, key=lambda sp: sp[2] - sp[1])[0]
                 if cover else "no span")
        out.append([label, (e - s) / 1e9])
    return out
