"""Device time of the chain program per named section, from the trace.

The program puts a `jax.named_scope` on every section of
`plane.window_step` (`egress_order`, `routing_rank`, ...), on
`workloads/phold.respawn_batch` (`respawn`) and on `plane.ingest_rows`
(`ingest_rows`). XLA keeps the scope path as each op's `op_name`, and the
TPU profiler writes it into the `.xplane.pb` as the `tf_op` stat of the
op's event metadata, e.g. `jit(phold_chain)/while/body/egress_order/sort:`.
`jax.profiler.ProfileData` does not expose event-metadata stats, so this
module reads the XSpace protobuf with a small wire-format reader (the
schema is `tsl/profiler/protobuf/xplane.proto`; no protobuf package is
needed).

Each device op that runs inside an execution of the chain module (an
`XLA Modules` event whose name contains `phold_chain`) within the
harness's `bench.window` span gets its self time (`tracing.self_times`)
and goes to the innermost scope of its path that is in `SECTIONS`, or to
`OTHER`. Every op lands in one bucket, so the buckets partition the
device time of the chain's ops. A fusion that spans two sections carries
the path of its root op only.

Run on a trace directory it prints the per-section table, the top ops of
each section, and the offset of the device clock from the host's:

    python3 benchmark/scopes.py .bench_trace --windows 96
"""

from __future__ import annotations

import functools
import glob
import json
import os
import statistics
import struct
import sys
from collections import defaultdict
from dataclasses import dataclass, field

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import tracing  # noqa: E402

MODULE = "phold_chain"
OTHER = "other"
#: the scopes the program names, innermost wins
SECTIONS = (
    "rebase_refill", "egress_order", "loss_latency", "ingress_compact",
    "routing_rank", "routing_place", "release_due", "codel_drain",
    "egress_compact", "next_event", "metrics", "guards", "hist",
    "flightrec", "flows", "compute", "respawn", "ingest_rows",
)
LAUNCH_SPAN = "chain.launch"


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo=0, hi=None):
    """(field number, value) of one message in buf[lo:hi]: an int for
    varint and fixed fields, a (start, end) pair for length-delimited
    ones."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif wire == 1:
            val, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wire == 5:
            val, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stats(buf, spans, names):
    """{stat name: str or int} of repeated XStat messages (a string held
    by reference resolves through the stat metadata names)."""
    out = {}
    for span in spans:
        mid, val = None, None
        for num, v in _fields(buf, *span):
            if num == 1:
                mid = v
            elif num in (3, 4):
                val = v
            elif num == 5:
                val = _str(buf, v)
            elif num == 7:
                val = names.get(v)
        if mid in names:
            out[names[mid]] = val
    return out


@dataclass
class Planes:
    # device plane name -> [(metadata id, start_ns, end_ns)] of "XLA Ops"
    ops: dict = field(default_factory=dict)
    # device plane name -> {metadata id: (HLO text, tf_op path or "")}
    meta: dict = field(default_factory=dict)
    # device plane name -> [(module name, start_ns, end_ns, run_id)]
    modules: dict = field(default_factory=dict)
    # [(run_id, start_ns, end_ns)] of the host's CompleteCallbacks events
    callbacks: list = field(default_factory=list)


def _plane(buf, span, out: Planes):
    name, lines, ev_meta, st_meta = None, [], [], []
    for num, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            ev_meta.append(v)
        elif num == 5:
            st_meta.append(v)
    device = name is not None and name.startswith("/device:TPU:")
    if not device and name != "/host:CPU":
        return
    stat_names = {}
    for entry in st_meta:
        for num, v in _fields(buf, *entry):
            if num == 2:
                sid, sname = None, None
                for n2, v2 in _fields(buf, *v):
                    if n2 == 1:
                        sid = v2
                    elif n2 == 2:
                        sname = _str(buf, v2)
                stat_names[sid] = sname
    meta = {}
    for entry in ev_meta:
        for num, v in _fields(buf, *entry):
            if num != 2:
                continue
            mid, mname, mstats = None, "", []
            for n2, v2 in _fields(buf, *v):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    mname = _str(buf, v2)
                elif n2 == 5:
                    mstats.append(v2)
            tf_op = ""
            if device and mstats:
                tf_op = _stats(buf, mstats, stat_names).get("tf_op") or ""
            meta[mid] = (mname, tf_op)
    if device:
        out.meta[name] = meta
    for lspan in lines:
        lname, ts, events = None, 0, []
        for num, v in _fields(buf, *lspan):
            if num == 2:
                lname = _str(buf, v)
            elif num == 3:
                ts = v
            elif num == 4:
                events.append(v)
        if device and lname not in ("XLA Ops", "XLA Modules"):
            continue
        for espan in events:
            mid, off, dur, stats = None, 0, 0, []
            for num, v in _fields(buf, *espan):
                if num == 1:
                    mid = v
                elif num == 2:
                    off = v
                elif num == 3:
                    dur = v
                elif num == 4:
                    stats.append(v)
            # the same rounding as jax.profiler.ProfileData
            s = ts + off // 1000
            e = s + dur // 1000
            if lname == "XLA Ops":
                out.ops.setdefault(name, []).append((mid, s, e))
            elif lname == "XLA Modules":
                run = _stats(buf, stats, stat_names).get("run_id")
                out.modules.setdefault(name, []).append(
                    (meta.get(mid, ("", ""))[0], s, e, run))
            elif meta.get(mid, ("",))[0] == "CompleteCallbacks":
                run = _stats(buf, stats, stat_names).get("run_id")
                out.callbacks.append((run, s, e))


def parse(data: bytes) -> Planes:
    """The TPU planes' ops, op metadata and modules, and the host's
    CompleteCallbacks events, of one serialized XSpace."""
    buf = memoryview(data)
    out = Planes()
    for num, v in _fields(buf):
        if num == 1:
            _plane(buf, v, out)
    return out


@functools.lru_cache(maxsize=1)
def _parse_file(path: str, _mtime_ns: int, _size: int) -> Planes:
    with open(path, "rb") as f:
        return parse(f.read())


def load(trace_dir: str) -> Planes | None:
    """`parse` of the newest `.xplane.pb` under `trace_dir`, or None.
    The last file read is kept, so every metric of a run parses it
    once."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return None
    st = os.stat(paths[-1])
    return _parse_file(paths[-1], st.st_mtime_ns, st.st_size)


# -- attribution ---------------------------------------------------------------

def scope_of(tf_op: str) -> str:
    """The innermost scope of an op path that is in SECTIONS, else
    OTHER: "jit(f)/while/body/routing_rank/sort:" -> "routing_rank"."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    for part in reversed(path.split("/")):
        if part in SECTIONS:
            return part
    return OTHER


def section_seconds(ops, tf_ops: dict, modules, window, match=MODULE):
    """{scope or OTHER: device seconds} of the ops inside executions of
    the modules whose name contains `match`, clipped to `window`, each op
    counted by its self time. `ops` is [(key, start_ns, end_ns)],
    `tf_ops` maps a key to its op path, `modules` is [(name, start_ns,
    end_ns, ...)] of the same device. None when no such module ran."""
    lo, hi = window
    out, ran = defaultdict(float), False
    for mod in modules:
        name, s, e = mod[0], mod[1], mod[2]
        if match not in name or e <= lo or s >= hi:
            continue
        ran = True
        for key, t in tracing.self_times(ops, max(s, lo), min(e, hi)).items():
            out[scope_of(tf_ops.get(key, ""))] += t
    return dict(out) if ran else None


def sections(planes: Planes, window):
    """`section_seconds` summed over the TPU planes, or None when no
    chain module ran or no op of it carries one of SECTIONS (a program
    that names no scope)."""
    tot, ran = defaultdict(float), False
    for plane, ops in planes.ops.items():
        tf_ops = {k: m[1] for k, m in planes.meta.get(plane, {}).items()}
        got = section_seconds(ops, tf_ops, planes.modules.get(plane, []),
                              window)
        if got is not None:
            ran = True
            for k, v in got.items():
                tot[k] += v
    if not ran or set(tot) <= {OTHER}:
        return None
    return {k: tot.get(k, 0.0) for k in (*SECTIONS, OTHER)}


def clock_offsets(planes: Planes, window=None):
    """[ns] from the end of each device module execution (in `window`)
    to the start of the host's CompleteCallbacks event with the same
    run_id: how far the device clock runs behind the host's, plus the
    callback's wake-up. The host marks completion only for executions
    it waits on (on the PHOLD cells, the small `jit_add` after each
    chain), so the pairs are those, not the chain's own executions."""
    cb = {run: s for run, s, _e in planes.callbacks if run is not None}
    out = []
    for mods in planes.modules.values():
        for _name, s, e, run in mods:
            if run not in cb:
                continue
            if window is not None and (e <= window[0] or s >= window[1]):
                continue
            out.append(cb[run] - e)
    return out


def _trace_dir(metric_file: str) -> str:
    """`<checkout>/.bench_trace` for `<checkout>/benchmark/metrics/x.py`:
    where benchmark/run.py writes the trace of a `--trace 1` run."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(metric_file))))
    return os.path.join(root, ".bench_trace")


def reader(metric_file: str, *scopes: str):
    """`read(ctx)` for a metric file: device ms per window of the chain
    ops in `scopes` (OTHER for the rest), or None when the run has no
    trace, no TPU plane, or no named scope."""
    trace_dir = _trace_dir(metric_file)

    def read(ctx):
        if ctx.trace is None or ctx.window is None \
                or not ctx.run.get("windows"):
            return None
        planes = load(trace_dir)
        if planes is None:
            return None
        got = sections(planes, ctx.window)
        if got is None:
            return None
        return 1e3 * sum(got[s] for s in scopes) / ctx.run["windows"]

    return read


def launch_ms(ctx):
    """Mean host ms of the program's `chain.launch` spans in the window,
    or None when there are none or the trace has no TPU plane (a CPU
    rehearsal reads no chip metric)."""
    if ctx.trace is None or ctx.window is None or not ctx.trace.ops:
        return None
    lo, hi = ctx.window
    d = [e - s for n, s, e in ctx.trace.spans
         if n == LAUNCH_SPAN and s >= lo and e <= hi]
    return sum(d) / len(d) / 1e6 if d else None


# -- report ------------------------------------------------------------------

def _quartiles(xs):
    if len(xs) < 2:
        return xs[0] if xs else None, None, None
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    return q2, q1, q3


def report(trace_dir: str, windows: int | None = None, k: int = 3) -> dict:
    """Per-section device ms (per window with `windows`), the top `k`
    ops of each section by self time, and the device-host clock offset,
    over the trace's `bench.window` span (the whole trace without one)."""
    planes = load(trace_dir)
    if planes is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    window = tracing.window_of(tracing.load(trace_dir)) or (-2**62, 2**62)
    per = 1e3 / windows if windows else 1e3
    top = defaultdict(lambda: defaultdict(float))
    for plane, ops in planes.ops.items():
        meta = planes.meta.get(plane, {})
        for name, s, e, _run in planes.modules.get(plane, []):
            if MODULE not in name or e <= window[0] or s >= window[1]:
                continue
            st = tracing.self_times(ops, max(s, window[0]), min(e, window[1]))
            for key, t in st.items():
                hlo, tf_op = meta.get(key, ("", ""))
                top[scope_of(tf_op)][(tracing.op_label(hlo), tf_op)] += t
    secs = {s: sum(ops.values()) for s, ops in top.items()}
    offs = clock_offsets(planes, window)
    med, q1, q3 = _quartiles([o / 1e6 for o in offs])
    return {
        "unit": "ms per window" if windows else "ms",
        "sections": {s: per * t for s, t in
                     sorted(secs.items(), key=lambda x: -x[1])},
        "total": per * sum(secs.values()),
        "top_ops": {s: [[label, tf_op, per * t] for (label, tf_op), t in
                        sorted(ops.items(), key=lambda x: -x[1])[:k]]
                    for s, ops in top.items()},
        "clock_offset_ms": {"n": len(offs), "median": med, "q1": q1,
                            "q3": q3},
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--windows", type=int, default=None,
                    help="divide by the windows the run executed")
    ap.add_argument("--top", type=int, default=3)
    args = ap.parse_args(argv)
    print(json.dumps(report(args.trace_dir, args.windows, args.top),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
