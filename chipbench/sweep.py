#!/usr/bin/env python3
"""Find a configuration's knee: the highest offered rate at which the
server answers at least 99% of the window's requests inside the window.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --shares 0.6,0.8,0.9,1.0,1.1

One process: set-up once, then the capacity of back-to-back full steps
(``n_slots`` over the median full-step time), then one open-loop window
per share of that capacity, with the cell's arrival process at that
rate.  Prints one JSON line per window.
"""
from __future__ import annotations

import argparse
import time

import _tool


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--shares", default="0.6,0.8,0.9,0.95,1.0,1.05,1.1,1.2")
    args = ap.parse_args()
    spec = _tool.start()

    import numpy as np

    from chipbench import stats
    from chipbench.harness import serve_window, setup
    from chipbench.traffic import make_schedule

    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(args.workload)
    served = setup(spec, cfg, args.seed)
    server, frames = served.server, served.frames
    walls = []
    for k in range(64):
        for j in range(cfg["n_slots"]):
            server.submit(j, frames[(k + j) % len(frames)])
        t = time.perf_counter()
        server.step()
        walls.append(time.perf_counter() - t)
    full_step = float(np.median(walls[8:]))
    capacity = cfg["n_slots"] * cfg["client_batch"] / full_step
    _tool.emit(full_step_ms=1e3 * full_step, capacity_rps=capacity)
    for k, share in enumerate(float(s) for s in args.shares.split(",")):
        tr = dict(traffic, rate_rps=share * capacity)
        sched = make_schedule(tr, args.seconds, args.seed + 1 + k,
                              cfg["frame_pool"])
        w = serve_window(server, frames, sched)
        lat = stats.latencies_s(sched.due, w.done)
        _tool.emit(share=share, rate_rps=tr["rate_rps"], requests=len(sched),
                   answered_in_window=float(np.mean(w.done <= args.seconds)),
                   p50_ms=1e3 * stats.percentile(lat, 50),
                   p95_ms=1e3 * stats.percentile(lat, 95),
                   served_per_step=float(np.mean(w.step_served)),
                   step_ms_p50=1e3 * float(np.median(w.step_end - w.step_begin)))


if __name__ == "__main__":
    main()
