#!/usr/bin/env python3
"""Profile the device-plane window step per section.

Times every section of `plane.window_step` (qdisc sort, RR tensors,
loss+latency gathers, routing scatter — split into its routing_rank /
routing_place sub-sections — ingress compaction, CoDel drain, ...) as
isolated jitted micro-kernels at one or more bench-ladder shapes, and
emits a JSON cost breakdown. This is the measurement substrate for
every window-step optimization claim: run it with `--legacy-sort` to
price the pre-diet variadic sorts against the packed-key default.

    python tools/profile_plane.py                       # default shapes
    python tools/profile_plane.py --hosts 1024 --reps 5
    python tools/profile_plane.py --legacy-sort -o before.json
    python tools/profile_plane.py \
        --sections routing_scatter,routing_rank,routing_place

See docs/performance.md for the cost model the sections map onto.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--hosts", default="1024,8192",
                    help="comma-separated host counts (default 1024,8192)")
    ap.add_argument("--reps", type=int, default=20,
                    help="timed repetitions per section (default 20)")
    ap.add_argument("--egress-cap", type=int, default=16)
    ap.add_argument("--ingress-cap", type=int, default=32)
    ap.add_argument("--nodes", type=int, default=64,
                    help="graph nodes for the path tables (default 64)")
    ap.add_argument("--rr", action="store_true",
                    help="profile with the RR qdisc compiled in")
    ap.add_argument("--legacy-sort", action="store_true",
                    help="time the pre-diet variadic sorts "
                         "(packed_sort=False) for before/after comparison")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of sections to time")
    ap.add_argument("-o", "--out", default=None,
                    help="also write the JSON report to this path")
    args = ap.parse_args(argv)

    from shadow_tpu.tpu import profiling

    shapes = []
    for n in (int(h) for h in args.hosts.split(",") if h.strip()):
        shapes.append(profiling.profile_sections(
            n, reps=args.reps, rr_enabled=args.rr,
            packed_sort=not args.legacy_sort,
            n_nodes=args.nodes, egress_cap=args.egress_cap,
            ingress_cap=args.ingress_cap,
            sections=(args.sections.split(",") if args.sections else None),
        ))
    report = {"metric": "plane_section_ms", "shapes": shapes}
    text = json.dumps(report, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
