#!/usr/bin/env python3
"""Record a profiler trace of a short window of one cell and print its
structure and reduction: how the test fixture of ``trace_reduce`` was
made, and how to look at a trace by hand.

    python3 chipbench/record_trace.py --workload <cell> --seed <n> \
        --seconds 0.2 --out <dir>
"""
from __future__ import annotations

import argparse
from collections import Counter

import _tool


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=0.2)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the cell's rate (req/s)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    spec = _tool.start()

    from jax.profiler import ProfileData

    from chipbench import trace_reduce
    from chipbench.harness import serve_window, setup
    from chipbench.traffic import make_schedule

    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(args.workload)
    if args.rate:
        traffic = dict(traffic, rate_rps=args.rate)
    served = setup(spec, cfg, args.seed)
    sched = make_schedule(traffic, args.seconds, args.seed, cfg["frame_pool"])
    w = serve_window(served.server, served.frames, sched, trace_dir=args.out,
                     trace_seconds=args.seconds)
    path = trace_reduce.find_xplane(args.out)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            names = Counter(e.name for e in line.events)
            print("  line", repr(line.name), sum(names.values()), "events;",
                  names.most_common(12))
    s = trace_reduce.summarize(pd)
    _tool.emit(xplane=path, steps=len(w.step_begin), window_s=s.window_s,
               busy_s=s.busy_s, idle_by_host=s.idle_by_host,
               modules=s.modules, top_ops=s.top_ops(20),
               longest_gaps=s.longest_gaps)


if __name__ == "__main__":
    main()
