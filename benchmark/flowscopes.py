"""Device time of the flow engine per named phase, from the trace.

The program runs each phase of a flow-engine window under a
`jax.named_scope` (`tpu/floweng.py`): `flow_sched` (scheduled events),
`flow_app` (read, write, close), `flow_pull` (the pull loop and the
scatter into the peer rings), `flow_barrier` (the end-of-window ACK
flush and clock update), and the completion probe under `flow_status`.
The TPU profiler keeps the scope path as the `tf_op` stat of each op's
event metadata in the `.xplane.pb`.

A flow-engine run makes millions of small device ops, so this module
reads the trace with the `protobuf` package's compiled parser, through
a message schema for the part of `tsl/profiler/protobuf/xplane.proto`
it needs (field numbers as there), and keeps each device's ops as
arrays. Every device op within the harness's `bench.window` span gets
its self time (its span, clipped to the window, less the spans of the
ops nested in it, as `tracing.self_times` counts it) and goes to the
innermost of `SCOPES` on its path, or to `OTHER` (the window loop's own
ops, world builds, eager ops). On a flow-engine cell the device runs
nothing else, so the buckets partition the engine's device time.

    python3 benchmark/flowscopes.py .bench_trace --runs 1
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmark import scopes, tracing  # noqa: E402

SCOPES = ("flow_sched", "flow_app", "flow_pull", "flow_barrier",
          "flow_status")
OTHER = "other"
DEVICE = "/device:TPU:"


def scope_of(tf_op: str) -> str:
    """The innermost part of an op path that is in SCOPES, else OTHER:
    "jit(run_windows)/while/body/flow_pull/scatter:" -> "flow_pull"."""
    path = tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return OTHER


# -- reading the trace ---------------------------------------------------------

@dataclass
class DeviceOps:
    # device plane name -> (metadata ids, start ns, end ns), int64 arrays
    ops: dict = field(default_factory=dict)
    # device plane name -> {metadata id: (HLO text, tf_op path or "")}
    meta: dict = field(default_factory=dict)


@functools.lru_cache(maxsize=1)
def _xspace():
    """The XSpace message class, for the fields this module reads."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    fd = descriptor_pb2.FieldDescriptorProto
    one, many = fd.LABEL_OPTIONAL, fd.LABEL_REPEATED
    i64, u64, s, m = fd.TYPE_INT64, fd.TYPE_UINT64, fd.TYPE_STRING, \
        fd.TYPE_MESSAGE
    schema = {
        "XStat": [("metadata_id", 1, i64, one), ("uint64_value", 3, u64, one),
                  ("int64_value", 4, i64, one), ("str_value", 5, s, one),
                  ("ref_value", 7, u64, one)],
        "XEvent": [("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
                   ("duration_ps", 3, i64, one)],
        "XLine": [("name", 2, s, one), ("timestamp_ns", 3, i64, one),
                  ("events", 4, "XEvent", many)],
        "XEventMetadata": [("id", 1, i64, one), ("name", 2, s, one),
                           ("stats", 5, "XStat", many)],
        "XStatMetadata": [("id", 1, i64, one), ("name", 2, s, one)],
        # a proto map is a repeated (key 1, value 2) entry on the wire
        "EventMetadataEntry": [("key", 1, i64, one),
                               ("value", 2, "XEventMetadata", one)],
        "StatMetadataEntry": [("key", 1, i64, one),
                              ("value", 2, "XStatMetadata", one)],
        "XPlane": [("name", 2, s, one), ("lines", 3, "XLine", many),
                   ("event_metadata", 4, "EventMetadataEntry", many),
                   ("stat_metadata", 5, "StatMetadataEntry", many)],
        "XSpace": [("planes", 1, "XPlane", many)],
    }
    proto = descriptor_pb2.FileDescriptorProto(
        name="benchmark_xplane_subset.proto", package="benchmark_xplane",
        syntax="proto3")
    for name, fields in schema.items():
        msg = proto.message_type.add(name=name)
        for fname, num, typ, label in fields:
            f = msg.field.add(name=fname, number=num, label=label)
            if isinstance(typ, str):
                f.type, f.type_name = m, f".benchmark_xplane.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchmark_xplane.XSpace"))


def _tf_op(md, stat_names: dict) -> str:
    for st in md.stats:
        if stat_names.get(st.metadata_id) == "tf_op":
            # a string is held in place or by reference to a stat name
            return st.str_value or stat_names.get(st.ref_value, "")
    return ""


def parse(data: bytes) -> DeviceOps:
    """The TPU planes' "XLA Ops" events and op metadata of one
    serialized XSpace."""
    out = DeviceOps()
    for plane in _xspace().FromString(data).planes:
        if not plane.name.startswith(DEVICE):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        out.meta[plane.name] = {
            e.key: (e.value.name, _tf_op(e.value, stat_names))
            for e in plane.event_metadata}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            ev = line.events
            n = len(ev)
            mid = np.fromiter((e.metadata_id for e in ev), np.int64, n)
            off = np.fromiter((e.offset_ps for e in ev), np.int64, n)
            dur = np.fromiter((e.duration_ps for e in ev), np.int64, n)
            # the same rounding as jax.profiler.ProfileData
            start = line.timestamp_ns + off // 1000
            out.ops[plane.name] = (mid, start, start + dur // 1000)
    return out


@functools.lru_cache(maxsize=1)
def _parse_file(path: str, _mtime_ns: int, _size: int) -> DeviceOps:
    with open(path, "rb") as f:
        return parse(f.read())


def load(trace_dir: str) -> DeviceOps | None:
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

def self_times(mid, start, end, lo, hi):
    """(metadata ids, self ns) of the ops that overlap [lo, hi], each
    clipped to it: its span less the spans of the ops directly nested in
    it, the parent being the latest-starting op still open when it
    starts (ties: the longer first), as `tracing.self_times` counts."""
    mid, start, end = (np.asarray(x, np.int64) for x in (mid, start, end))
    s, e = np.maximum(start, lo), np.minimum(end, hi)
    keep = e > s
    mid, s, e = mid[keep], s[keep], e[keep]
    order = np.lexsort((-e, s))
    mid, s, e = mid[order], s[order], e[order]
    dur = e - s
    # ops open when op i starts: those before it that have not ended
    depth = np.arange(len(s)) - np.searchsorted(np.sort(e), s, "right")
    child = np.zeros(len(s), np.int64)
    for d in range(1, int(depth.max(initial=0)) + 1):
        up, here = np.flatnonzero(depth == d - 1), np.flatnonzero(depth == d)
        parent = up[np.searchsorted(up, here) - 1]
        np.add.at(child, parent, dur[here])
    return mid, dur - child


def _phase_ns(ops: DeviceOps, window):
    """{plane: (metadata ids, self ns, scope index)} within the window."""
    out = {}
    for plane, (mid, start, end) in ops.ops.items():
        mid, ns = self_times(mid, start, end, *window)
        if not len(mid):
            continue
        meta = ops.meta.get(plane, {})
        keys, inv = np.unique(mid, return_inverse=True)
        index = {name: i for i, name in enumerate((*SCOPES, OTHER))}
        scope = np.array([index[scope_of(meta.get(int(k), ("", ""))[1])]
                          for k in keys], np.int64)[inv]
        out[plane] = (mid, ns, scope)
    return out


_last: list = []  # [(ops, window, answer)] of the last reduction


def phase_seconds(ops: DeviceOps, window):
    """{scope or OTHER: device seconds} of self time, summed over the TPU
    planes, of the ops within `window`; None when no op ran there or no
    op carries one of SCOPES (a program that names none). The last
    answer is kept, so the metrics of one run reduce the trace once."""
    if not (_last and _last[0][0] is ops and _last[0][1] == tuple(window)):
        _last[:] = [(ops, tuple(window), _phase_seconds(ops, window))]
    return _last[0][2]


def _phase_seconds(ops: DeviceOps, window):
    names = (*SCOPES, OTHER)
    tot = np.zeros(len(names))
    got = _phase_ns(ops, window)
    for _mid, ns, scope in got.values():
        tot += np.bincount(scope, weights=ns, minlength=len(names))
    if not got or not tot[:len(SCOPES)].any():
        return None
    return {k: float(t) / 1e9 for k, t in zip(names, tot)}


def reader(metric_file: str, *names: str):
    """`read(ctx)` for a metric file: device ms per run of the ops in
    `names` (every bucket when none is named), or None when the run has
    no trace, no TPU plane, or no named scope."""
    trace_dir = scopes._trace_dir(metric_file)

    def read(ctx):
        if ctx.trace is None or ctx.window is None \
                or not ctx.run.get("runs"):
            return None
        ops = load(trace_dir)
        if ops is None:
            return None
        got = phase_seconds(ops, ctx.window)
        if got is None:
            return None
        keys = names or tuple(got)
        return 1e3 * sum(got[k] for k in keys) / ctx.run["runs"]

    return read


def span_ms_per_run(ctx, names) -> float | None:
    """Host ms per run of the program's spans in `names` inside the
    window, or None when there are none or the trace has no TPU plane
    (a CPU rehearsal reads no chip metric)."""
    if ctx.trace is None or ctx.window is None or not ctx.trace.ops \
            or not ctx.run.get("runs"):
        return None
    lo, hi = ctx.window
    d = [e - s for n, s, e in ctx.trace.spans
         if n in names and s >= lo and e <= hi]
    return sum(d) / 1e6 / ctx.run["runs"] if d else None


# One connection's state as the engine's contract needs it, in 4-byte
# words, listed by hand (the reference's `Conn` and `Lane`), so that the
# count is the same whatever implements the engine.
CONN_WORDS = {
    # state, error, snd_una, snd_nxt, snd_max, snd_wnd, stream_len,
    # fin requested/sent/acked, SYN outstanding, SYN sends, retransmit,
    # probe and RST pending, NewReno recovery point, go-back-N mark,
    # retransmissions
    "tcp_send": 18,
    # rcv_nxt, unread in-order bytes, FIN received, FIN offset, ACK pending
    "tcp_receive": 5,
    # own and peer window scale, scaling on, SACK on, timestamp to echo
    "tcp_options": 5,
    # srtt, rttvar, RTO, backoffs, cwnd, ssthresh, phase, duplicate ACKs,
    # avoidance count
    "rtt_and_reno": 9,
    # RTO armed and deadline, persist armed and deadline
    "timers": 4,
    "sack_scoreboard": 16 * 2,  # 16 ranges, start and end
    "out_of_order_store": 32 * 2,  # 32 ranges, start and end
    # opened, close sent, written, read, total, start, latency, loss,
    # loss-hash lane, clock, completion, emissions, units, wire drops,
    # ring drops, unacknowledged segments, ring head and count
    "app_and_wire": 18,
}
# A ring slot: arrival time, then flags, seq, ack, window, payload
# length, timestamp, echo, three SACK blocks (start, end), the SYN's
# window scale and SACK-permitted.
SEGMENT_WORDS = 15


def conn_bytes(queue_slots: int) -> int:
    """Bytes of one connection's state and segment ring."""
    return 4 * (sum(CONN_WORDS.values()) + queue_slots * SEGMENT_WORDS)


def least_bytes(queue_slots: int, buckets) -> int:
    """The least HBM bytes of one run: every connection's state and
    `queue_slots` ring slots (`conn_bytes`) read once and written once
    per lookahead window of its bucket, up to the bucket's last
    completion. `buckets` is [{"window_us", "flows",
    "last_complete_us"}]."""
    per_conn = conn_bytes(queue_slots)
    total = 0
    for b in buckets:
        windows = -(-int(b["last_complete_us"]) // int(b["window_us"]))
        total += 2 * (2 * int(b["flows"])) * per_conn * windows
    return total


def report(trace_dir: str, runs: int | None = None, k: int = 3) -> dict:
    """Per-phase device ms (per run with `runs`) and the top `k` ops of
    each phase by self time, over the trace's `bench.window` span."""
    ops = load(trace_dir)
    if ops is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    window = tracing.window_of(tracing.load(trace_dir)) or (-2**62, 2**62)
    per = 1e3 / runs if runs else 1e3
    top = defaultdict(lambda: defaultdict(float))
    for plane, (mid, ns, scope) in _phase_ns(ops, window).items():
        meta = ops.meta.get(plane, {})
        keys, inv = np.unique(mid, return_inverse=True)
        secs = np.bincount(inv, weights=ns) / 1e9
        at = np.empty(len(keys), np.int64)  # an event of each op
        at[inv] = np.arange(len(inv))
        for key, t, i in zip(keys, secs, at):
            hlo, tf_op = meta.get(int(key), ("", ""))
            top[(*SCOPES, OTHER)[scope[i]]][(tracing.op_label(hlo),
                                            tf_op)] += t
    secs = {s: sum(by_op.values()) for s, by_op in top.items()}
    return {
        "unit": "ms per run" if runs else "ms",
        "phases": {s: per * t for s, t in
                   sorted(secs.items(), key=lambda x: -x[1])},
        "total": per * sum(secs.values()),
        "top_ops": {s: [[label, tf_op, per * t] for (label, tf_op), t in
                        sorted(by_op.items(), key=lambda x: -x[1])[:k]]
                    for s, by_op in top.items()},
    }


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--runs", type=int, default=None,
                    help="divide by the runs the window made")
    ap.add_argument("--top", type=int, default=3)
    args = ap.parse_args(argv)
    print(json.dumps(report(args.trace_dir, args.runs, args.top), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
