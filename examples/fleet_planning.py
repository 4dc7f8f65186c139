"""Fleet-scale deployment planning through ``repro.api``: which splits
for this *population*?

The single-link quickstart answers "which design for one client".  This
one scales the question to a deployment with one Study object:

  1. ``fit`` + ``profile`` + ``candidates``: CS curve and split points,
  2. ``bottlenecks``: AEs for the top CS-ranked cuts,
  3. describe the fleet — three device classes behind different channels —
     and generate a 1000-request diurnal trace over the mix,
  4. ``simulate(fleet=...)``: search split x protocol x batch x replicas
     per device class (accuracy measured by netsim on loss-corrupted
     tensors, queueing by the fleet cluster model),
  5. ``pareto()``: the per-class front over (p99, accuracy, server FLOPs/s),
  6. ``suggest()`` one QoS-feasible plan per class, then jointly validate
     the chosen plans against the mixed trace on shared replicas.

Run:  PYTHONPATH=src python examples/fleet_planning.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.api import (Channel, DeviceClass, INTERFACES, QoSRequirements,
                       Study, generate_trace, simulate_deployment,
                       toy_image_iter, toy_images)

# Every random draw in this walkthrough is seeded explicitly so the run —
# and any trace artifact exported from it — is bit-reproducible in CI.
SEED_STUDY = 0       # Study params / synthetic sample
SEED_DATA = 55       # toy evaluation images
SEED_AE = 9          # bottleneck AE data stream
SEED_TRACE = 42      # fleet arrival trace (recorded on Trace.seed)


def main():
    print("== 1. model + CS curve ==")
    xs, ys = toy_images(64, hw=16, seed=SEED_DATA)
    lc = Study("vgg16", seed=SEED_STUDY).fit(steps=30)
    study = Study("vgg16", data=(xs[:32], ys[:32]), seed=SEED_STUDY,
                  lc=(lc.model, lc.params)).fit(steps=300)
    print(f"   test accuracy: {study.eval_accuracy():.3f}")
    study.profile().candidates(top_n=3)
    cands = [c.split_layer for c in study.split_candidates()]
    print(f"   candidate split points: {cands}")

    print("== 2. bottleneck AEs for the top cuts ==")
    study.bottlenecks(steps=150, lr=2e-3, cuts=cands[:2],
                      data_iter=toy_image_iter(32, hw=16, seed=SEED_AE))

    print("== 3. the fleet: 3 device classes, 1000-request diurnal trace ==")
    mix = [
        DeviceClass.make("mcu",
                         Channel(2e-3, 10e6, 10e6, loss_rate=0.08, seed=1),
                         weight=2.0),
        DeviceClass.make("edge-embedded",
                         Channel(5e-4, INTERFACES["fast-ethernet"],
                                 INTERFACES["fast-ethernet"],
                                 loss_rate=0.02, seed=2),
                         weight=1.5),
        DeviceClass.make("edge-accelerator",
                         Channel(1e-4, INTERFACES["gigabit"],
                                 INTERFACES["gigabit"], seed=3),
                         weight=1.0),
    ]
    trace = generate_trace(mix, 1000, 400.0, pattern="diurnal",
                           seed=SEED_TRACE)
    assert trace.seed == SEED_TRACE      # provenance rides the Trace
    for d in mix:
        sub = trace.for_device(d.name)
        print(f"   {d.name:18s} {len(sub.requests):4d} requests "
              f"({len(sub.requests) / len(trace.requests):.0%}), "
              f"loss {d.channel.loss_rate:.0%}")
    print(f"   horizon {trace.horizon_s:.2f} s, "
          f"mean rate {trace.mean_rate_hz():.0f} req/s")

    print("== 4. search split x protocol x batch x replicas ==")
    study.simulate(fleet=(trace, mix),
                   protocols=("tcp", "udp"), batch_sizes=(1, 8, 32),
                   replica_counts=(1, 2), top_k_splits=2,
                   include_rc=True, include_lc=True)
    print(f"   evaluated {len(study.plan_points)} deployment options")

    qos = QoSRequirements(max_latency_s=0.05, min_accuracy=0.5)
    print(f"== 5. Pareto front (QoS: p99 <= {qos.max_latency_s * 1e3:.0f} ms, "
          f"accuracy >= {qos.min_accuracy}) ==")
    hdr = (f"   {'device':18s} {'design':7s} {'proto':5s} {'b':>3s} {'r':>2s} "
           f"{'p50 ms':>8s} {'p99 ms':>8s} {'acc':>6s} {'srv GFLOP/s':>12s}  qos")
    print(hdr)
    for p in study.pareto():
        print(f"   {p.device:18s} {p.label:7s} {str(p.protocol):5s} "
              f"{p.max_batch:3d} {p.n_replicas:2d} {p.p50_s * 1e3:8.2f} "
              f"{p.p99_s * 1e3:8.2f} {p.accuracy:6.3f} "
              f"{p.server_flops_per_s / 1e9:12.2f}  "
              f"{'YES' if p.satisfies(qos) else 'no'}")

    print("== 6. suggested per-class plans + joint validation ==")
    plans = study.suggest(qos)
    feasible = 0
    for name, p in plans.items():
        if p is None:
            print(f"   {name:18s} -> no feasible design (relax QoS or "
                  f"change the network)")
        else:
            feasible += 1
            print(f"   {name:18s} -> {p.label} over {p.protocol}, "
                  f"batch {p.max_batch}, {p.n_replicas} replica(s): "
                  f"p99 {p.p99_s * 1e3:.2f} ms, acc {p.accuracy:.3f}")
    report = simulate_deployment(plans, trace, mix, study.planner)
    for (split, b, r, _w), g in sorted(report.items(),
                                       key=lambda kv: str(kv[0])):
        print(f"   shared cluster split={split} batch={b} replicas={r}: "
              f"{g['n_served']} served from {', '.join(g['devices'])} | "
              f"p50 {g['p50_s'] * 1e3:.2f} ms, p99 {g['p99_s'] * 1e3:.2f} ms, "
              f"mean batch {g['mean_batch']:.1f}, "
              f"util {g['utilization']:.0%}, drops {g['drop_fraction']:.1%}")
    print(f"\nFEASIBLE DEPLOYMENTS: {feasible}/{len(mix)} device classes")
    if feasible == 0:
        raise SystemExit("no QoS-feasible deployment found")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
