#!/usr/bin/env python3
"""Record a profiler trace of a short window of one cell with the
server's own spans on, and print their reduction (``span_reduce``) beside
``trace_reduce``'s: how the fixture of ``span_reduce`` was made.

    python3 chipbench/record_spans.py --workload <cell> --seed <n> \
        --seconds 0.2 --out <dir>

The server is built as a run of the cell builds it; after its warm-up it
is given a ``repro.obs.Recorder`` that annotates the profiler, so each
``server.*`` span lands on the trace's host line in the device's
timebase.  ``--trace-seconds`` and ``--skip`` profile and read part of a
longer window, as a traced run of the cell does (2 s and 0.25 s).
"""
from __future__ import annotations

import argparse
from collections import Counter

import _tool


def record(served, sched, out: str, *, trace_seconds: float,
           skip_s: float = 0.0) -> dict:
    """Serve ``sched`` with the spans on, profiling its first
    ``trace_seconds`` into ``out``; the two reductions of that trace."""
    import jax
    import numpy as np
    from jax.profiler import ProfileData

    from chipbench import span_reduce, trace_reduce
    from chipbench.harness import serve_window
    from repro.obs import Recorder

    rec = Recorder(annotate=jax.profiler.TraceAnnotation)
    served.server.obs = rec
    w = serve_window(served.server, served.frames, sched, trace_dir=out,
                     trace_seconds=trace_seconds)
    path = trace_reduce.find_xplane(out)
    pd = ProfileData.from_file(path)
    spans = span_reduce.summarize(pd, skip_s=skip_s)
    host = trace_reduce.summarize(pd, skip_s=skip_s)
    counts = Counter(n.name for n in spans.nodes if n.inside)
    top = sorted(spans.idle_by_path.items(), key=lambda kv: -kv[1])[:12]
    wait = rec.metrics.get("runtime.queue_wait_s")
    return dict(
        xplane=path, steps=len(w.step_begin), window_s=spans.window_s,
        busy_s=spans.busy_s, programs=spans.programs, spans=dict(counts),
        span_ms_p50={k: 1e3 * float(np.median(spans.seconds(k)))
                     for k in counts},
        idle_by_path=[[" > ".join(p) or "none", s] for p, s in top],
        idle_by_host=host.idle_by_host, readings=spans.readings(),
        queue_wait_ms_p50=1e3 * wait.percentile(50) if wait else None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=0.2)
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="profile only the window's first seconds")
    ap.add_argument("--skip", type=float, default=0.0,
                    help="leave the traced span's first seconds unread")
    ap.add_argument("--rate", type=float, default=None,
                    help="override the cell's rate (req/s)")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    spec = _tool.start()

    from chipbench.harness import setup
    from chipbench.traffic import make_schedule

    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(args.workload)
    if args.rate:
        traffic = dict(traffic, rate_rps=args.rate)
    served = setup(spec, cfg, args.seed)
    sched = make_schedule(traffic, args.seconds, args.seed, cfg["frame_pool"])
    _tool.emit(**record(served, sched, args.out,
                        trace_seconds=args.trace_seconds or args.seconds,
                        skip_s=args.skip))


if __name__ == "__main__":
    main()
