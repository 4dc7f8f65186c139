"""A planner-suggested 2-cut device -> edge -> cloud pipeline, executed
end-to-end on CPU — driven entirely through the ``repro.api`` facade.

The multi-tier design loop in one script:

 1. ``suggest(qos, tiers=...)`` searches every legal cut list x
    stage->tier assignment over a 3-tier topology, pricing each design
    sequentially *and* as a pipelined microbatch schedule (hop-k
    transfer overlapping stage-k+1 compute);
 2. ``deploy()`` executes the winning cut list live: a 3-stage
    ``SplitRuntime`` whose two wire hops ride the topology's links, with
    per-stage and per-hop wall-clock timing;
 3. the same design is re-simulated over the explicit ``path=`` mode to
    show the pipelined-vs-sequential latency the planner traded on.

Run:  PYTHONPATH=src python examples/multi_tier.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.api import (Channel, QoSRequirements, Study, Tier, TierTopology)


def main():
    # device -> edge over a bandwidth-bound wireless link, edge -> cloud
    # over a faster wired one
    topo = TierTopology((
        Tier("device", "mcu", Channel(1e-3, 20e6, 20e6, seed=1)),
        Tier("edge", "edge-accelerator", Channel(1e-3, 30e6, 30e6, seed=2)),
        Tier("cloud", "server-gpu"),
    ))
    study = Study("vgg16", batch=16)
    model = study.model
    print(f"model: {model.name}, {len(model.layers)} layers, "
          f"legal cuts {model.cut_points()}")

    # --- 1. search cut-list x tier-assignment --------------------------
    study.profile()
    plan = study.suggest(QoSRequirements(max_latency_s=0.25,
                                         min_accuracy=0.4),
                         tiers=topo, cut_counts=[2])
    assert plan is not None, "planner found no feasible tier plan"
    print(f"planner suggests cuts {plan.splits} on "
          f"{' -> '.join(plan.stage_tiers)}: pipelined "
          f"{plan.latency_s * 1e3:.2f} ms vs sequential "
          f"{plan.sequential_s * 1e3:.2f} ms "
          f"({plan.speedup:.2f}x, {plan.n_micro} microbatches, "
          f"CS proxy {plan.accuracy_proxy:.2f})")
    runners_up = [p for p in study.tier_plans[:4] if p is not plan]
    for p in runners_up[:3]:
        print(f"  also evaluated: cuts {p.splits} on "
              f"{' -> '.join(p.stage_tiers)} "
              f"({p.latency_s * 1e3:.2f} ms)")

    # --- 2. execute the 3-stage pipeline live --------------------------
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 16, 16, 3)).astype(np.float32)
    rt = study.deploy()
    res = rt.infer(x, iters=3)
    ref = rt.reference(x)
    agree = (np.argmax(res.logits, -1) == np.argmax(ref, -1)).all()
    print(f"executed {len(res.stage_s)} stages: "
          + " | ".join(f"stage{k} {s * 1e3:.3f} ms"
                       for k, s in enumerate(res.stage_s)))
    for k, hop in enumerate(res.hops):
        print(f"  hop{k} (after cut {hop['cut']}): {hop['bytes']} B, "
              f"transfer {hop['transfer_s'] * 1e3:.3f} ms")
    print(f"total {res.total_s * 1e3:.3f} ms | argmax agrees with "
          f"unsplit: {agree}")

    # --- 3. pipelined vs sequential on the explicit path ---------------
    study.simulate(path=topo.path(), tiers=topo.platforms, top_m=4)
    for v in study.verdicts:
        print(f"simulated {v.candidate.label}: pipelined "
              f"{v.latency_s * 1e3:.2f} ms vs sequential "
              f"{v.meta['sequential_s'] * 1e3:.2f} ms "
              f"({v.meta['speedup']:.2f}x)")
    print("ok")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
