#!/usr/bin/env python3
"""Readings behind the limits of `correct` on the tgen flow-engine cells
(PERF.md section 2).

    python3 benchmark/proof_flows.py control --workload <cell> --seeds a,b,c
        the control (the reference with one stated guarantee broken: loss
        keyed by the flow's index in plan order, not its lane in its
        bucket's world) against the reference, at the cell's own size,
        for each seed: its readings are the upper ends.
    python3 benchmark/proof_flows.py fault --workload <cell> --kinds k,... --seed s --seconds t
        one harness run per fault, in one process, with the fault planted
        in the timed path; each result line has to read not correct.

Faults (`planted`):
- "unchanged": the fourth dispatch of every bucket's run hands back the
  world it was given with only its clock moved on, so that second's
  events are lost;
- "late": one flow's completion time comes out 1 us late;
- "half": half of the flows' bytes read are cut in half.

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

KINDS = ("unchanged", "late", "half")
UNCHANGED_DISPATCH = 3


def planted(kind: str, floweng):
    """{attribute of `floweng`: replacement} planting the fault."""
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; one of {KINDS}")
    if kind == "unchanged":
        real_runner = floweng._runner

        def runner(*a, **kw):
            run = real_runner(*a, **kw)
            calls = []

            def lossy(w):
                out = run(w)
                calls.append(1)
                if len(calls) != UNCHANGED_DISPATCH + 1:
                    return out
                new, steps = out
                return w._replace(clock_us=new.clock_us,
                                  conn_t=new.conn_t), steps

            return lossy

        return {"_runner": runner}
    real_results = floweng.flow_results

    def flow_results(world):
        res = dict(real_results(world))
        if kind == "late":
            done = res["complete_us"].copy()
            done[0] += 1
            res["complete_us"] = done
        else:
            got = res["bytes_read"].copy()
            half = len(got) // 2
            got[:half] //= 2
            res["bytes_read"] = got
        return res

    return {"flow_results": flow_results}


def control(args):
    _bench, cell, cfg, traffic = harness.resolve(REPO, args.workload)
    drv = harness.load_module(os.path.join(HERE, "drivers",
                                           f"{cfg['driver']}.py"), "drv")
    ref = drv._load_reference(cfg["reference"])
    for seed in (int(s) for s in args.seeds.split(",")):
        dep = ref.deployment(cfg, traffic, seed)
        t0 = time.monotonic()
        plain = drv.make_reference(ref, cfg, dep)
        want = plain.run()
        got = drv.make_reference(ref, cfg, dep, args.control).run()
        found = ref.compare(got, want, [f["size"] for f in plain.flows])
        print(json.dumps({"cell": cell["name"], "seed": seed,
                          "control": args.control, "readings": found,
                          "seconds": time.monotonic() - t0}), flush=True)


def fault(args):
    from shadow_tpu.tpu import floweng

    rc = 0
    for kind in args.kinds.split(","):
        saved = {}
        for name, fn in planted(kind, floweng).items():
            saved[name] = getattr(floweng, name)
            setattr(floweng, name, fn)
        try:
            print(json.dumps({"fault": kind}), flush=True)
            rc |= harness.main(["--workload", args.workload, "--seed",
                                str(args.seed), "--seconds",
                                str(args.seconds)])
        finally:
            for name, fn in saved.items():
                setattr(floweng, name, fn)
    return rc


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    c = sub.add_parser("control")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--control", default="plan_order_loss")
    f = sub.add_parser("fault")
    f.add_argument("--workload", required=True)
    f.add_argument("--kinds", default=",".join(KINDS))
    f.add_argument("--seed", type=int, required=True)
    f.add_argument("--seconds", type=float, default=1)
    args = ap.parse_args()
    return control(args) if args.mode == "control" else fault(args)


if __name__ == "__main__":
    sys.exit(main() or 0)
