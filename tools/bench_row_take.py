"""Time `plane._row_take`'s select chain against XLA's gather on the device.

For each row width C and dtype, apply a random per-row permutation of
[N, C] to itself `--reps` times inside one jitted loop, once with the
select chain (`plane._row_take`) and once with XLA's gather
(`jnp.take_along_axis`), and print one JSON line per (C, dtype, form):
device ms per application (median of `--runs` timed calls, each ending
in `block_until_ready`) and the compile seconds. A width at which the
gather wins would call for a gather above it in `_row_take`.

    python tools/bench_row_take.py --rows 32768 --widths 16,32,64,128

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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=32768)
    ap.add_argument("--widths", default="16,32,64,128")
    ap.add_argument("--reps", type=int, default=20,
                    help="applications per timed call")
    ap.add_argument("--runs", type=int, default=5, help="timed calls")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from shadow_tpu.tpu import plane

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.allow_cpu:
        print("bench_row_take: no accelerator (pass --allow-cpu to "
              "rehearse)", file=sys.stderr)
        return 2
    forms = {
        "select": plane._row_take,
        "gather": lambda a, p: jnp.take_along_axis(a, p, axis=1),
    }
    rng = np.random.default_rng(args.seed)
    for c in (int(w) for w in args.widths.split(",")):
        perm = jnp.asarray(np.argsort(rng.random((args.rows, c)), axis=1)
                           .astype(np.int32))
        for dtype in (jnp.int32, jnp.bool_):
            a = jnp.asarray(rng.integers(0, 2 if dtype == jnp.bool_ else 1 << 30,
                                         (args.rows, c))).astype(dtype)
            outs = {}
            for name, form in forms.items():
                loop = jax.jit(lambda x, p, form=form: jax.lax.fori_loop(
                    0, args.reps, lambda _, y: form(y, p), x))
                t0 = time.perf_counter()
                compiled = loop.lower(a, perm).compile()
                compile_s = time.perf_counter() - t0
                outs[name] = compiled(a, perm).block_until_ready()
                times = []
                for _ in range(args.runs):
                    t0 = time.perf_counter()
                    compiled(a, perm).block_until_ready()
                    times.append(time.perf_counter() - t0)
                print(json.dumps({
                    "rows": args.rows, "width": c,
                    "dtype": jnp.dtype(dtype).name, "form": name,
                    "ms_per_take": statistics.median(times) * 1e3 / args.reps,
                    "compile_s": compile_s, "device": dev.device_kind,
                }), flush=True)
            if not np.array_equal(np.asarray(outs["select"]),
                                  np.asarray(outs["gather"])):
                print(f"bench_row_take: forms differ at C={c} {dtype}",
                      file=sys.stderr)
                return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
