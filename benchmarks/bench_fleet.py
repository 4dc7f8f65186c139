"""Fleet subsystem benchmark: traffic generation, cluster event
throughput, and planner search cost.

Writes a JSON artifact (results/fleet/bench_fleet.json) for CI upload and
prints the standard ``name,us_per_call,derived`` rows.

  PYTHONPATH=src python -m benchmarks.bench_fleet [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core.qos import QoSRequirements
from repro.fleet import (ClusterConfig, ClusterSim, DeviceClass,
                         DeploymentPlanner, SearchSpace, generate_trace)
from repro.netsim.channel import Channel
from repro.serving.engine import BatchCostModel

from .common import RESULTS_DIR


def _mix():
    return [DeviceClass.make("mcu", Channel(2e-3, 10e6, 10e6,
                                            loss_rate=0.08, seed=1), weight=2.0),
            DeviceClass.make("edge-embedded",
                             Channel(5e-4, 100e6, 100e6, loss_rate=0.02,
                                     seed=2), weight=1.5),
            DeviceClass.make("edge-accelerator",
                             Channel(1e-4, 1e9, 1e9, seed=3), weight=1.0)]


def bench_traffic(n: int) -> dict:
    out = {}
    for pattern in ("poisson", "bursty", "diurnal"):
        t0 = time.perf_counter()
        tr = generate_trace(_mix(), n, 500.0, pattern=pattern, seed=0)
        dt = time.perf_counter() - t0
        out[pattern] = {"n": n, "gen_s": dt, "req_per_s": n / dt,
                        "horizon_s": tr.horizon_s}
    return out


def bench_cluster(n: int) -> dict:
    """Event throughput at overload (every request queues and batches)."""
    tr = generate_trace(_mix(), n, 5000.0, seed=1)
    cost = BatchCostModel(flops_per_item=5e7, flops_per_s=60e12,
                          fixed_overhead_s=2e-4)
    sim = ClusterSim(cost, ClusterConfig(n_replicas=2, max_batch=16,
                                         batch_window_s=1e-3))
    sim.offer_trace((r.rid, r.t_arrival) for r in tr.requests)
    t0 = time.perf_counter()
    stats = sim.run()
    dt = time.perf_counter() - t0
    events = sim.q.n_fired + sim.q.n_cancelled
    return {"n_requests": n, "sim_s": dt, "events": events,
            "events_per_s": events / dt, "served": len(stats.served),
            "p50_ms": stats.percentile(50) * 1e3,
            "p99_ms": stats.percentile(99) * 1e3,
            "mean_batch": stats.mean_batch(),
            "cancelled_timers": sim.q.n_cancelled}


def bench_planner(n: int, quick: bool) -> dict:
    """Search-cost benchmark on the small VGG (accuracy via analytic proxy
    in --quick so CI needs no training; measured accuracy otherwise)."""
    import jax
    from repro.models.vgg import feature_index, vgg_cifar

    if quick:
        model = vgg_cifar(n_classes=8, input_hw=16, width_mult=0.25)
        params = model.init(jax.random.PRNGKey(0))

        def accuracy_fn(scenario, netcfg):
            base = 0.9 if scenario.kind != "LC" else 0.6
            return base - (netcfg.channel.loss_rate
                           if netcfg.protocol == "udp" else 0.0)
        kw = dict(accuracy_fn=accuracy_fn, input_bytes=16 * 16 * 3 * 4)
    else:
        from benchmarks.common import trained_vgg
        from repro.data.synthetic import toy_images
        model, params = trained_vgg()
        xs, ys = toy_images(32, hw=16, seed=55)
        kw = dict(eval_data=(xs, ys))

    fi = feature_index(model)
    cs = np.linspace(1.0, 0.2, len(fi))
    legal = set(model.cut_points())
    sps = tuple(sp for sp in fi if sp in legal)[:4]
    planner = DeploymentPlanner(model, params, cs_curve=cs, layer_idx=fi, **kw)
    space = SearchSpace(split_points=sps, batch_sizes=(1, 8, 32),
                        replica_counts=(1, 2), top_k_splits=2)
    mix = _mix()
    trace = generate_trace(mix, n, 400.0, pattern="diurnal", seed=42)
    t0 = time.perf_counter()
    points = planner.search(trace, mix, space)
    search_s = time.perf_counter() - t0
    front = planner.pareto_front(points)
    qos = QoSRequirements(max_latency_s=0.05, min_accuracy=0.5)
    feasible = sum(p.satisfies(qos) for p in points)
    plans = planner.suggest(qos, (trace, mix), space, points=points)
    return {"n_requests": n, "search_s": search_s, "n_points": len(points),
            "points_per_s": len(points) / search_s,
            "pareto_size": len(front), "n_feasible": feasible,
            "n_classes_planned": sum(p is not None for p in plans.values())}


def run(fast: bool = False, out_path: str = None) -> list:
    n = 1000 if fast else 5000
    report = {"quick": fast,
              "traffic": bench_traffic(n),
              "cluster": bench_cluster(n),
              "planner": bench_planner(min(n, 1000), quick=fast)}
    out_path = out_path or os.path.join(RESULTS_DIR, "fleet",
                                        "bench_fleet.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    tr, cl, pl = report["traffic"], report["cluster"], report["planner"]
    return [
        ("fleet.traffic.poisson_req_per_s", 0.0, int(tr["poisson"]["req_per_s"])),
        ("fleet.cluster.events_per_s", 0.0, int(cl["events_per_s"])),
        ("fleet.cluster.mean_batch", 0.0, round(cl["mean_batch"], 2)),
        ("fleet.cluster.p99_ms", 0.0, round(cl["p99_ms"], 3)),
        ("fleet.planner.points_per_s", 0.0, round(pl["points_per_s"], 1)),
        ("fleet.planner.pareto_size", 0.0, pl["pareto_size"]),
        ("fleet.planner.n_feasible", 0.0, pl["n_feasible"]),
    ]


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small trace + analytic accuracy proxy (CI smoke)")
    ap.add_argument("--out", default=None, help="JSON artifact path")
    args = ap.parse_args()
    for row in run(fast=args.quick, out_path=args.out):
        print(",".join(map(str, row)))
