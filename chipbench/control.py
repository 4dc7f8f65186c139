#!/usr/bin/env python3
"""Readings that the limits of ``compare.py`` are set from.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, in one process: set-up as a run makes it, the cell's own
open-loop window, then the reference.  Prints per seed the program's
widest logit gap over every answered request (the number a run
compares), and the control's: the same reference computed in bfloat16,
over the same images.  Also printed, for the record: the share of the
frames' int8 codes that differ from the reference's, and the gaps of the
program and of the reference against the reference at "highest".
"""
from __future__ import annotations

import argparse
import gc

import _tool


def widest(logits, ref):
    import numpy as np
    ref = np.asarray(ref, np.float64)
    gap = np.abs(np.asarray(logits, np.float64) - ref).max(-1)
    return float((gap / np.abs(ref).max(-1)).max())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    spec = _tool.start()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import compare
    from chipbench.harness import reference_outputs, serve_window, setup
    from chipbench.traffic import make_schedule
    from repro.runtime import wire as W

    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    traffic = spec.traffic(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        served = setup(spec, cfg, seed)
        sched = make_schedule(traffic, args.seconds, seed, cfg["frame_pool"])
        w = serve_window(served.server, served.frames, sched)
        served.server = None
        gc.collect()
        jax.clear_caches()
        logits, codes, _ = reference_outputs(spec, cfg, served)
        gaps = compare.logit_gaps(w.answers, sched.pick, logits)
        frame_codes = np.stack([W.from_bytes(f).data[0] for f in served.frames])
        moved = frame_codes.astype(int) != codes.astype(int)
        c_logits, c_codes, _ = reference_outputs(spec, cfg, served,
                                                 dtype=jnp.bfloat16)
        h_logits, _, _ = reference_outputs(
            spec, dict(cfg, matmul_precision="highest"), served)
        served_per_image = {}
        for rid, got in w.answers.items():
            served_per_image.setdefault(int(sched.pick[rid]), got.reshape(-1))
        imgs = sorted(served_per_image)
        _tool.emit(
            seed=seed, requests=len(sched), answered=len(w.answers),
            program_logit_gap=float(np.nanmax(gaps)),
            control_logit_gap=widest(c_logits, logits),
            program_codes_moved=float(moved.mean()),
            program_codes_max_step=int(np.abs(frame_codes.astype(int)
                                              - codes.astype(int)).max()),
            control_codes_moved=float((c_codes != codes).mean()),
            reference_vs_highest=widest(logits, h_logits),
            program_vs_highest=widest(
                np.stack([served_per_image[i] for i in imgs]), h_logits[imgs]),
            control_vs_highest=widest(c_logits, h_logits))
        del served
        gc.collect()


if __name__ == "__main__":
    main()
