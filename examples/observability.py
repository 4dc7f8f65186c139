"""One telemetry report across the whole pipeline: plan, simulate the
fleet, execute the runtime — then open the trace in Perfetto.

``study.observe()`` arms a recorder; every stage that runs afterwards
records into it:

  1. ``suggest(qos, tiers=...)``: the two-phase tier planner leaves
     ``planner.screen`` / ``planner.refine`` phase spans and combo
     counters,
  2. a fleet ``ClusterSim`` fed a seeded diurnal trace (the same
     recorder via ``report.recorder``) emits per-request lifecycle
     spans — wire -> queue wait -> service — per-replica batch tracks,
     and windowed fleet time series (arrival rate, queue depth,
     utilization, p50/p99),
  3. ``deploy()`` + ``infer``: the live split runtime reconstructs a
     per-stage/per-hop span tree (encode -> transfer -> decode) that
     reconciles exactly to its measured total latency.

Two exports close the loop:

* ``results/obs/trace.json``      — both clocks (open in
  https://ui.perfetto.dev: pid 1 = simulated time, pid 2 = wall time),
* ``results/obs/fleet_trace.json`` — simulated clock only.  Every event
  in it derives from seeded simulation, so the file is bit-reproducible
  run to run: CI uploads it as an artifact and identical inputs must
  yield an identical file.

Run:  PYTHONPATH=src python examples/observability.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.api import (Channel, DeviceClass, QoSRequirements, Study, Tier,
                       TierTopology, generate_trace)
from repro.fleet.cluster import ClusterConfig, ClusterSim
from repro.serving.engine import BatchCostModel

SEED_STUDY = 0
SEED_TRACE = 42       # recorded on Trace.seed -> reproducible artifact
OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "obs")


def main():
    study = Study("vgg16", seed=SEED_STUDY)
    report = study.observe(window_s=0.02)

    print("== 1. tier planning under observation ==")
    topo = TierTopology((
        Tier("edge", "edge-embedded", Channel(1e-3, 20e6, 20e6, seed=1)),
        Tier("cloud", "server-gpu"),
    ))
    best = study.suggest(QoSRequirements(max_latency_s=10.0,
                                         min_accuracy=0.0), tiers=topo)
    print(f"   best plan: cut after layer {best.splits[0]}, "
          f"pipelined {best.latency_s * 1e3:.2f} ms")
    planner_spans = [s for s in report.spans if s.cat == "planner"]
    for s in planner_spans:
        print(f"   span {s.name}: {s.dur * 1e3:.1f} ms  {s.args}")

    print("== 2. fleet simulation on the shared recorder ==")
    mix = [DeviceClass.make("mcu", Channel(2e-3, 10e6, 10e6, seed=1),
                            weight=2.0),
           DeviceClass.make("edge-embedded", Channel(5e-4, 100e6, 100e6,
                                                     seed=2))]
    trace = generate_trace(mix, 400, 300.0, pattern="diurnal",
                           seed=SEED_TRACE)
    print(f"   trace: {len(trace)} requests over {trace.horizon_s:.2f} s "
          f"(seed={trace.seed})")
    cost = BatchCostModel.for_split(study.model, study.params,
                                    best.splits[0], study.scenario.server)
    sim = ClusterSim(cost, ClusterConfig(n_replicas=2, max_batch=8),
                     obs=report.recorder)
    wire_bytes = study.input_bytes
    for r in trace.requests:
        sim.offer(r.rid, r.t_arrival, tx_s=5e-4, tx_bytes=wire_bytes)
    stats = sim.run()
    print(f"   served {len(stats.served)} in {stats.batches} batches, "
          f"p99 {stats.percentile(99) * 1e3:.2f} ms")
    t, depth = report.timeseries("fleet.queue_depth")
    _, util = report.timeseries("fleet.utilization")
    print(f"   windowed series: {len(t)} samples, "
          f"max queue depth {depth.max():.0f}, "
          f"mean utilization {util.mean():.1%}")

    print("== 3. live runtime under observation ==")
    runtime = study.deploy()
    x = np.asarray(study._x[:2])
    result = runtime.infer(x, iters=3)
    root = result.trace
    leaves = [s for s in root.walk() if not s.children and s is not root]
    print(f"   infer {result.total_s * 1e3:.3f} ms == "
          f"{sum(s.dur for s in leaves) * 1e3:.3f} ms over "
          f"{len(leaves)} leaf spans "
          f"({', '.join(c.name for c in root.children)})")

    print("== 4. export ==")
    os.makedirs(OUT_DIR, exist_ok=True)
    both = os.path.join(OUT_DIR, "trace.json")
    sim_only = os.path.join(OUT_DIR, "fleet_trace.json")
    report.to_chrome_trace(both)
    report.to_chrome_trace(sim_only, clock="sim",
                           metadata={"trace_seed": trace.seed,
                                     "study_seed": SEED_STUDY})
    print(f"   {both} (both clocks — open in https://ui.perfetto.dev)")
    print(f"   {sim_only} (simulated clock only, bit-reproducible)")
    print()
    print(report.summary())


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
