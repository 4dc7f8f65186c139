"""``repro.api`` facade benchmark: Study-pipeline overhead vs calling the
legacy modules directly.

The facade's promise is zero-cost: the Study chain dispatches to exactly
the functions a hand-stitched script would call (saliency -> ranking ->
measure_flow -> suggest), so its orchestration overhead must stay under
5% — gated via ``perf_compare gate --kind api`` against
``benchmarks/baselines/bench_api_quick.json``.

Writes a JSON artifact (results/api/bench_api.json) for CI upload.

  PYTHONPATH=src python -m benchmarks.bench_api [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.api import QoSRequirements, Study
from repro.api.types import legal_split_candidates
from repro.core import qos as Q
from repro.core.saliency import candidate_split_points, cumulative_saliency
from repro.models.vgg import vgg_cifar
from repro.netsim.simulator import flow_latency_s, measure_flow

from .common import RESULTS_DIR

QOS = QoSRequirements(max_latency_s=10.0, min_accuracy=0.0)


def _study_pipeline(model, params, x, labels):
    study = Study(model, params=params, seed=0)
    study._x, study._labels = x, labels         # identical profiling input
    return study.profile().candidates().simulate().suggest(QOS)


def _direct_pipeline(model, params, x, labels, scenario):
    """The same design flow, hand-stitched from the legacy modules."""
    from repro.models.vgg import feature_index
    li = feature_index(model)
    cs = cumulative_saliency(model, params, x, labels, layer_idx=li)
    points = candidate_split_points(model, cs, li, top_n=3)
    if not points:
        ranked = sorted(legal_split_candidates(model, cs, li),
                        key=lambda c: -c.accuracy_proxy)
        points = [c.split_layer for c in ranked[:3]]
    cands = Q.rank_candidates(cs, li, points)
    netcfg = scenario.netcfg()
    input_bytes = int(np.prod(x.shape[1:])) * 4
    verdicts = []
    for cand in cands:
        scen = cand.scenario(scenario.edge, scenario.server)
        flow = measure_flow(scen, netcfg, model, params, input_bytes,
                            n_frames=scenario.n_frames)
        verdicts.append(Q.SimVerdict(cand, flow_latency_s(flow),
                                     cand.accuracy_proxy))
    return Q.suggest(verdicts, QOS)


def _paired_ratio(fa, fb, iters: int) -> tuple:
    """(ratio a/b, best a, best b) over one window of interleaved runs.

    Process CPU time, not wall clock: the facade's cost is pure Python
    orchestration, and CPU time is blind to the other tenants of a
    shared runner.  Within the window, two aggregate estimators are both
    consistent for the true ratio — total-time ratio (load amortises
    over the horizon) and best-of-iters ratio (both mins converge to the
    unloaded cost) — and their min discards the residual same-process
    noise (GC, XLA thread scheduling) that inflates one of them.
    """
    tas, tbs = [], []
    for _ in range(iters):
        t0 = time.process_time()
        fa()
        tas.append(time.process_time() - t0)
        t0 = time.process_time()
        fb()
        tbs.append(time.process_time() - t0)
    ratio = min(sum(tas) / sum(tbs), min(tas) / min(tbs))
    return ratio, min(tas), min(tbs)


def bench_overhead(iters: int) -> dict:
    from repro.api.study import StudyScenario
    model = vgg_cifar(n_classes=8, input_hw=16, width_mult=0.25)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = np.asarray(rng.standard_normal((8, 16, 16, 3)), np.float32)
    labels = np.asarray(rng.integers(0, 8, 8), np.int32)
    scenario = StudyScenario()

    study = lambda: _study_pipeline(model, params, x, labels)
    direct = lambda: _direct_pipeline(model, params, x, labels, scenario)
    b_study, b_direct = study(), direct()       # warm the jit caches
    assert b_study.candidate.label == b_direct.candidate.label, \
        "facade and direct pipeline disagree — benchmark is meaningless"
    # three independent measurement windows, gated on their *median*: a
    # noise burst can corrupt one window in either direction without
    # moving the verdict, while a genuine facade regression (a stage
    # running twice, accidental recompute) shifts all three and trips
    # the <5% ceiling
    runs = sorted(_paired_ratio(study, direct, iters) for _ in range(3))
    ratio, study_s, direct_s = runs[1]
    return {
        "iters": iters,
        "direct_s": direct_s,
        "study_s": study_s,
        "window_ratios": [round(r[0], 4) for r in runs],
        "study_overhead_pct": (ratio - 1.0) * 100.0,
        "suggested": b_study.candidate.label,
    }


def run(fast: bool = False, out_path: str = None) -> list:
    """The ``benchmarks.run`` registry entrypoint (same contract as the
    other benches: write the JSON artifact, return metric rows)."""
    iters = 15 if fast else 40
    doc = {"quick": fast, "overhead": bench_overhead(iters)}
    o = doc["overhead"]
    # flat copy of the gated metric for perf_compare's path digging
    doc["study_overhead_pct"] = o["study_overhead_pct"]
    out_path = out_path or os.path.join(RESULTS_DIR, "api", "bench_api.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(doc, fh, indent=1)
    return [
        ("api.direct_ms", 0.0, round(o["direct_s"] * 1e3, 3)),
        ("api.study_ms", 0.0, round(o["study_s"] * 1e3, 3)),
        ("api.study_overhead_pct", 0.0,
         round(o["study_overhead_pct"], 2)),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (fewer timing iterations)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    out = args.out or os.path.join(RESULTS_DIR, "api", "bench_api.json")
    run(fast=args.quick, out_path=out)
    with open(out) as fh:
        o = json.load(fh)["overhead"]
    print(f"direct pipeline  {o['direct_s'] * 1e3:9.2f} ms")
    print(f"Study pipeline   {o['study_s'] * 1e3:9.2f} ms")
    print(f"facade overhead  {o['study_overhead_pct']:9.2f} %  "
          f"(suggests {o['suggested']})")
    print(f"wrote {out}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
