"""Time the window step's routing stage on the device in several forms.

The routing stage (`plane._route_scatter`, packed path) orders every
egress slot of an [N, CE] world by (destination, deliver time, source,
seq) with one flat sort, then lands the arrivals in the [N, CI]
ingress rows with one stacked gather. The order forms (`orders()`)
differ in how the payload columns (seq, sock, bytes, deliver) reach
arrival order:

- `gathers`: the sort carries its keys only; each column is read by a
  flat gather through the composed permutation (sorted position ->
  seq-permuted slot -> original slot), five B-wide gathers in all;
- `sort_carry`: the sort carries the row-permuted seq, sock and bytes
  as payload, and the deliver column comes back from its key
  (`plane._routing_order`, what the plane runs);
- `stacked`: the sort carries its keys only; the composed permutation
  is built as in `gathers`, and the four columns are read by ONE
  gather of a stacked [B, 4] array;
- `slot_carry`: the sort carries each slot's original flat index as
  its one payload, which is the composed permutation, and the four
  columns are read by one [B, 4] gather as in `stacked`.

Each is a drop-in for `plane._routing_order`, so a form can also be
patched into the whole window step. The stage forms (`stages()`) pair
an order with a placement: `parent` is `gathers` with `concat_place`
(one [6, N, CI] gather over the streams concatenated with the ingress
rows); `sort_carry`, `stacked` and `slot_carry` use
`plane._routing_place` (one gather of [B, 5] stream rows);
`carry_concat` and `carry_columns` are `sort_carry`'s order with
`concat_place` and with `column_place` (the streams stacked [5, B],
gathered along their second axis).

Every order form bounds the buckets with `plane._segment_bounds`.
`--stage bounds` times that step alone (`bounds()`), over the sorted
bucket ids of the same busy window: `searchsorted` is
`jnp.searchsorted` (a binary search, one scalar gather of N + 1
indices per step) and `blocked` is `plane._segment_bounds` (what the
plane runs: a compare-reduce against every 128th row head, then two
row gathers).

For each egress width it prints one JSON line per form: device ms per
stage (median of `--runs` timed calls, each ending in
`block_until_ready`), compile seconds and the compiled code's bytes.
The forms' outputs must be bitwise equal, or the script fails.

    python tools/bench_route_place.py --rows 32768 --widths 16,128,256
    python tools/bench_route_place.py --stage bounds --widths 16,128,256

Timings mean something only on the chip; the script refuses a CPU
backend unless `--allow-cpu` is given (a rehearsal of the script).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def orders():
    """Each form as a drop-in for `plane._routing_order`: the same
    arguments, the same (streams, offsets, counts)."""
    import jax
    import jax.numpy as jnp

    from shadow_tpu.tpu import plane

    def keys_only(sent, eg_dst, eg_seq, deliver_rel, *payload):
        """The routing sort over (bucket, deliver, slot) carrying
        `payload` (row-permuted [N, CE] columns)."""
        N, CE = eg_dst.shape
        row_perm = plane._seq_row_perm(eg_seq)
        take = lambda a: plane._row_take(a, row_perm).reshape(-1)
        bucket = jnp.where(sent & (eg_dst >= 0) & (eg_dst < N), eg_dst, N)
        deliver_key = take(deliver_rel).astype(jnp.uint32) ^ plane._SIGN32
        pos = jnp.arange(N * CE, dtype=jnp.int32)
        o_dst, _, o_pos, *o_payload = jax.lax.sort(
            (take(bucket), deliver_key, pos, *map(take, payload)),
            dimension=0, is_stable=False, num_keys=3)
        bounds = plane._segment_bounds(o_dst, N)
        return (row_perm, o_pos, bounds[:-1], bounds[1:] - bounds[:-1],
                *o_payload)

    def gathers(sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel):
        row_perm, o_pos, offsets, counts = keys_only(
            sent, eg_dst, eg_seq, deliver_rel)
        CE = row_perm.shape[1]
        flat = lambda a: a.reshape(-1)
        g = (o_pos // CE) * CE + flat(row_perm)[o_pos]
        streams = (o_pos // CE, flat(eg_seq)[g], flat(eg_sock)[g],
                   flat(eg_bytes)[g], flat(deliver_rel)[g])
        return streams, offsets, counts

    def stacked_by(o_pos, g, eg_seq, eg_sock, eg_bytes, deliver_rel):
        cols = jnp.stack([eg_seq, eg_sock, eg_bytes, deliver_rel],
                         axis=-1).reshape(-1, 4)[g]  # one [B, 4] gather
        return (o_pos // eg_seq.shape[1], cols[:, 0], cols[:, 1],
                cols[:, 2], cols[:, 3])

    def stacked(sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel):
        row_perm, o_pos, offsets, counts = keys_only(
            sent, eg_dst, eg_seq, deliver_rel)
        CE = row_perm.shape[1]
        g = (o_pos // CE) * CE + row_perm.reshape(-1)[o_pos]
        return (stacked_by(o_pos, g, eg_seq, eg_sock, eg_bytes,
                           deliver_rel), offsets, counts)

    def slot_carry(sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel):
        N, CE = eg_dst.shape
        slot = (jnp.arange(N, dtype=jnp.int32)[:, None] * CE
                + jnp.arange(CE, dtype=jnp.int32)[None, :])
        _, o_pos, offsets, counts, g = keys_only(
            sent, eg_dst, eg_seq, deliver_rel, slot)
        return (stacked_by(o_pos, g, eg_seq, eg_sock, eg_bytes,
                           deliver_rel), offsets, counts)

    return {"gathers": gathers, "sort_carry": plane._routing_order,
            "stacked": stacked, "slot_carry": slot_carry}


def concat_place(streams, offsets, take_n, n_valid_in, in_deliver_c,
                 in_src_c, in_seq_c, in_sock_c, in_bytes_c, in_valid_c):
    """The parent's placement: one [6, N, CI] gather over the streams
    concatenated with the ingress rows."""
    import jax.numpy as jnp

    from shadow_tpu.tpu.plane import I32_MAX

    N, CI = in_src_c.shape
    B = streams[0].shape[0]
    flat = lambda a: a.reshape(-1)
    bases = jnp.stack([
        flat(in_src_c), flat(in_seq_c), flat(in_sock_c), flat(in_bytes_c),
        flat(jnp.where(in_valid_c, in_deliver_c, I32_MAX)),
        flat(in_valid_c.astype(jnp.int32))])
    arrivals = jnp.stack([*streams, jnp.ones((B,), jnp.int32)])
    combined = jnp.concatenate([bases, arrivals], axis=1)
    ci_col = jnp.arange(CI, dtype=jnp.int32)[None, :]
    nv = n_valid_in[:, None]
    append = (ci_col >= nv) & (ci_col < nv + take_n[:, None])
    stream_idx = jnp.clip(offsets[:, None] + ci_col - nv, 0, B - 1)
    rows = jnp.arange(N, dtype=jnp.int32)[:, None]
    idx = jnp.where(append, N * CI + stream_idx, rows * CI + ci_col)
    merged = combined[:, idx]
    return (merged[0], merged[1], merged[2], merged[3], merged[4],
            merged[5] != 0)


def column_place(streams, offsets, take_n, n_valid_in, in_deliver_c,
                 in_src_c, in_seq_c, in_sock_c, in_bytes_c, in_valid_c):
    """`plane._routing_place` with the streams stacked [5, B] and
    gathered along their second axis."""
    import jax.numpy as jnp

    from shadow_tpu.tpu.plane import I32_MAX

    CI = in_src_c.shape[1]
    B = streams[0].shape[0]
    ci_col = jnp.arange(CI, dtype=jnp.int32)[None, :]
    nv = n_valid_in[:, None]
    append = (ci_col >= nv) & (ci_col < nv + take_n[:, None])
    stream_idx = jnp.clip(offsets[:, None] + ci_col - nv, 0, B - 1)
    arrivals = jnp.stack(streams)[:, stream_idx]  # [5, N, CI]
    bases = (in_src_c, in_seq_c, in_sock_c, in_bytes_c,
             jnp.where(in_valid_c, in_deliver_c, I32_MAX))
    return (*(jnp.where(append, a, b) for a, b in zip(arrivals, bases)),
            append | in_valid_c)


def stages():
    """Each form of the whole routing stage (`_routing_rank` +
    `_routing_place`) as (order, placement)."""
    from shadow_tpu.tpu import plane

    o = orders()
    forms = {"parent": (o["gathers"], concat_place),
             "sort_carry": (o["sort_carry"], plane._routing_place),
             "stacked": (o["stacked"], plane._routing_place),
             "slot_carry": (o["slot_carry"], plane._routing_place),
             "carry_concat": (o["sort_carry"], concat_place),
             "carry_columns": (o["sort_carry"], column_place)}
    return {name: _stage(*f) for name, f in forms.items()}


def bounds(n: int):
    """Each form of the bucket-bounds step: sorted [B] bucket ids in,
    the [n + 1] segment bounds out."""
    import jax.numpy as jnp

    from shadow_tpu.tpu import plane

    def searchsorted(o_dst):
        return jnp.searchsorted(
            o_dst, jnp.arange(n + 1, dtype=jnp.int32)).astype(jnp.int32)

    return {"searchsorted": searchsorted,
            "blocked": lambda o_dst: plane._segment_bounds(o_dst, n)}


def _stage(order, place):
    import jax.numpy as jnp

    def run(sent, eg_dst, eg_seq, eg_sock, eg_bytes, deliver_rel,
            in_cols, in_valid, n_valid):
        streams, offsets, counts = order(sent, eg_dst, eg_seq, eg_sock,
                                         eg_bytes, deliver_rel)
        take_n = jnp.minimum(counts, jnp.int32(in_valid.shape[1]) - n_valid)
        return place(streams, offsets, take_n, n_valid, *in_cols, in_valid)
    return run


def _inputs(rng, n: int, ce: int, ci: int):
    """A busy window: three in four slots sent, to uniform destinations,
    deliver times within 2 ms, ingress rows half full on average."""
    import jax.numpy as jnp
    import numpy as np

    i32 = lambda a: jnp.asarray(a, jnp.int32)
    n_valid = rng.integers(0, ci // 2 + 1, n)
    return (jnp.asarray(rng.integers(0, 4, (n, ce)) > 0),
            i32(rng.integers(0, n, (n, ce))),
            i32(rng.integers(0, 1 << 20, (n, ce))),
            i32(rng.integers(0, 40, (n, ce))),
            i32(rng.integers(100, 1500, (n, ce))),
            i32(rng.integers(0, 2_000_000, (n, ce))),
            tuple(i32(rng.integers(0, 1 << 20, (n, ci))) for _ in range(5)),
            jnp.asarray(np.arange(ci)[None, :] < n_valid[:, None]),
            i32(n_valid))


def _sorted_buckets(inputs):
    """The routing sort's bucket column of a busy window: each sent
    slot's destination, N for the rest, sorted."""
    import jax.numpy as jnp

    sent, eg_dst = inputs[:2]
    n = eg_dst.shape[0]
    return jnp.sort(jnp.where(sent, eg_dst, n).reshape(-1)), n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=32768)
    ap.add_argument("--widths", default="16,128,256",
                    help="egress ring widths CE")
    ap.add_argument("--ingress", type=int, default=32,
                    help="ingress ring width CI")
    ap.add_argument("--stage", choices=("route", "bounds"),
                    default="route",
                    help="the whole routing stage, or its bucket bounds")
    ap.add_argument("--forms",
                    help="comma-separated forms, the first the reference "
                         "(default: every form of the stage)")
    ap.add_argument("--runs", type=int, default=7, help="timed calls")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("bench_route_place: no accelerator (pass --allow-cpu to "
              "rehearse)", file=sys.stderr)
        return 2
    rng = np.random.default_rng(args.seed)
    for ce in (int(w) for w in args.widths.split(",")):
        inputs = _inputs(rng, args.rows, ce, args.ingress)
        if args.stage == "route":
            table = stages()
        else:
            keys, n = _sorted_buckets(inputs)
            table, inputs = bounds(n), (keys,)
        forms = args.forms.split(",") if args.forms else list(table)
        outs = {}
        for name in forms:
            t0 = time.perf_counter()
            compiled = jax.jit(table[name]).lower(*inputs).compile()
            compile_s = time.perf_counter() - t0
            outs[name] = jax.block_until_ready(compiled(*inputs))
            times = []
            for _ in range(args.runs):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(*inputs))
                times.append(time.perf_counter() - t0)
            print(json.dumps({
                "stage": args.stage, "rows": args.rows, "egress": ce,
                "ingress": args.ingress, "form": name,
                "ms": statistics.median(times) * 1e3,
                "compile_s": compile_s,
                "code_bytes": compiled.memory_analysis()
                .generated_code_size_in_bytes,
                "device": dev.device_kind,
            }), flush=True)
        as_tuple = lambda o: o if isinstance(o, tuple) else (o,)
        ref = as_tuple(outs.pop(forms[0]))
        for name, out in outs.items():
            out = as_tuple(out)
            if not all(np.array_equal(np.asarray(a), np.asarray(b))
                       for a, b in zip(out, ref)):
                print(f"bench_route_place: {name} differs from "
                      f"{forms[0]} at CE={ce}", file=sys.stderr)
                return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
