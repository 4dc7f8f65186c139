"""Multi-tier pipeline benchmark: 3-stage execution vs multi-hop
simulation, and pipelined microbatching vs sequential scheduling.

One bandwidth-bound device -> edge -> cloud scenario, measured three ways:

* **executed** — the live 3-stage ``SplitRuntime`` at a 2-cut pair
  (stage compute is real wall clock, the two wire hops are netsim-priced
  on the actual payload bytes);
* **simulated sequential** — ``measure_flow`` over the same 2-hop
  ``NetworkPath`` with the analytic per-stage cost model;
* **simulated pipelined** — the same flow chopped into microbatches so
  hop-k transfer overlaps stage-k+1 compute
  (``netsim.simulator.simulate_pipeline``).

The pipelined-vs-sequential speedup and both simulated latencies are
deterministic (event engine + analytic stage times) and are the CI gate
metrics; the simulated-vs-executed error is wall-clock-sensitive and
gates only on a generous absolute ceiling.

  PYTHONPATH=src python -m benchmarks.bench_pipeline [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.core.scenarios import PLATFORMS, Scenario
from repro.core.split import SplitPlan
from repro.netsim.channel import Channel
from repro.netsim.simulator import (NetworkConfig, NetworkPath,
                                    flow_latency_s, measure_flow)
from repro.runtime.engine import SplitRuntime

from .common import RESULTS_DIR


def _model(quick: bool):
    import jax
    from repro.models.vgg import vgg_cifar
    if quick:
        model = vgg_cifar(n_classes=8, input_hw=16, width_mult=0.25)
        return model, model.init(jax.random.PRNGKey(0))
    from benchmarks.common import trained_vgg
    return trained_vgg()


def _pick_pair(model) -> tuple:
    """An early/late 2-cut pair (big first payload, real middle stage)."""
    cuts = model.cut_points()
    return cuts[len(cuts) // 4], cuts[(3 * len(cuts)) // 4]


def run(fast: bool = False, out_path: str = None) -> list:
    model, params = _model(fast)
    pair = _pick_pair(model)
    batch = 16
    iters = 5 if fast else 10
    n_micro = 4
    # bandwidth-bound hops with comparable busy time (fast LAN carrying
    # the big early payload, slow WAN carrying the pooled-down one): the
    # overlap regime where microbatching pays
    path = NetworkPath((
        NetworkConfig("tcp", Channel(1e-3, 100e6, 100e6, seed=1)),
        NetworkConfig("tcp", Channel(1e-3, 25e6, 25e6, seed=2)),
    ))
    tiers = (PLATFORMS["edge-embedded"], PLATFORMS["edge-accelerator"],
             PLATFORMS["server-gpu"])

    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch,) + tuple(model.input_shape)
                            ).astype(np.float32)

    rt = SplitRuntime(model, params, pair, channel=list(path.hops),
                      quantize=False)
    res = rt.infer(x, iters=iters)
    exec_s = res.total_s

    # compression=1.0: the runtime ships the raw f32 activation (no AE),
    # so the analytic payload model must price the uncompressed wire too
    sc = Scenario("SC", SplitPlan(None, splits=pair, compression=1.0),
                  edge=tiers[0], server=tiers[-1])
    flow = measure_flow(sc, path, model, params, x[0].nbytes, n_frames=4,
                        batch=batch, tiers=tiers, n_micro=n_micro)
    seq_s = flow_latency_s(flow)
    pipe = flow["pipeline"]

    report = {
        "quick": fast,
        "model": model.name,
        "splits": list(pair),
        "batch": batch,
        "n_micro": n_micro,
        "pipeline": {
            "sequential_ms": seq_s * 1e3,
            "pipelined_ms": pipe.latency_s * 1e3,
            "speedup": pipe.speedup,
            "stage_ms": [s * 1e3 for s in flow["stage_s"]],
            "hop_bytes": flow["hop_bytes"],
        },
        "sim_vs_exec": {
            "exec_ms": exec_s * 1e3,
            "sim_sequential_ms": seq_s * 1e3,
            "err_analytic_pct": abs(seq_s - exec_s) / exec_s * 100,
            "exec_stage_ms": [s * 1e3 for s in res.stage_s],
            "exec_transfer_ms": res.transfer_s * 1e3,
            "exec_wire_bytes": res.wire_bytes,
            "sim_wire_bytes": flow["wire_bytes"],
        },
    }
    out_path = out_path or os.path.join(RESULTS_DIR, "pipeline",
                                        "bench_pipeline.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)

    return [
        ("pipeline.sequential_ms", 0.0,
         round(report["pipeline"]["sequential_ms"], 3)),
        ("pipeline.pipelined_ms", 0.0,
         round(report["pipeline"]["pipelined_ms"], 3)),
        ("pipeline.speedup", 0.0, round(report["pipeline"]["speedup"], 3)),
        ("sim_vs_exec.exec_ms", 0.0,
         round(report["sim_vs_exec"]["exec_ms"], 3)),
        ("sim_vs_exec.err_analytic_pct", 0.0,
         round(report["sim_vs_exec"]["err_analytic_pct"], 1)),
    ]


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="untrained small model (CI smoke)")
    ap.add_argument("--out", default=None, help="JSON artifact path")
    args = ap.parse_args()
    for row in run(fast=args.quick, out_path=args.out):
        print(",".join(map(str, row)))
