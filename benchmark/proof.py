#!/usr/bin/env python3
"""Readings behind the limits of `correct` (PERF.md section 2).

    python3 benchmark/proof.py control --workload <cell> --seeds a,b,c --windows n
        the control (the reference with one stated guarantee broken, put
        in the program's place) against the reference, at the cell's own
        size, for each seed: its readings are the upper ends.
    python3 benchmark/proof.py fault --workload <cell> --kind <k> --seed s --seconds t
        one harness run with a fault planted in the timed path
        (benchmark/faults.py); its result line has to read not correct.

The benchmark's own runs never run this."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark import run as harness  # noqa: E402


def control(args):
    _bench, cell, cfg, traffic = harness.resolve(REPO, args.workload)
    drv = harness.load_module(os.path.join(HERE, "drivers",
                                           f"{cfg['driver']}.py"), "drv")
    ref = harness.load_module(os.path.join(HERE, "reference",
                                           f"{cfg['reference']}.py"), "ref")
    for seed in (int(s) for s in args.seeds.split(",")):
        inp = drv.make_inputs(cfg, traffic, seed)
        t0 = time.monotonic()
        want = ref.PholdReference(inp).run(args.windows).dense()
        got = ref.PholdReference(inp, control=args.control).run(
            args.windows).dense()
        found = ref.compare(got, want)
        print(json.dumps({"cell": cell["name"], "seed": seed,
                          "windows": args.windows, "control": args.control,
                          "readings": found,
                          "seconds": time.monotonic() - t0}), flush=True)


def fault(args):
    from benchmark import faults
    from shadow_tpu import tpu

    tpu.window_step = faults.planted(args.kind, tpu.window_step)
    return harness.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--seconds", str(args.seconds)])


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--windows", type=int, required=True)
    c.add_argument("--control", default="tiebreak")
    f = sub.add_parser("fault")
    f.add_argument("--workload", required=True)
    f.add_argument("--kind", required=True)
    f.add_argument("--seed", type=int, required=True)
    f.add_argument("--seconds", type=float, default=3)
    args = ap.parse_args()
    return control(args) if args.mode == "control" else fault(args)


if __name__ == "__main__":
    sys.exit(main() or 0)
