"""Live split-execution at a planner-suggested cut, end-to-end on CPU —
driven entirely through the ``repro.api`` Study facade.

The full calibrated-planning loop in one script:

 1. ``simulate(fleet=...)`` searches split x protocol x batch x replicas
    and ``suggest`` picks a deployment for an edge device class;
 2. ``deploy()`` *executes* that cut live: head forward, bottleneck int8
    wire (Pallas kernel path, auto-routed to the pure-JAX reference on
    CPU), netsim-priced transfer, tail forward;
 3. ``calibrate()`` turns the runtime's measurements into a
    CalibrationTable; re-running ``simulate`` then prices the same flow
    from measurements, and the two latencies are compared;
 4. five edge clients share one TailServer, batching tail requests
    through the slot pool.

Run:  PYTHONPATH=src python examples/split_runtime.py
"""
import os
import sys
from dataclasses import replace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.api import (Channel, DeviceClass, QoSRequirements, Study,
                       StudyScenario, generate_trace, run_clients)


def main():
    channel = Channel(5e-4, 100e6, 100e6, loss_rate=0.02, seed=2)
    study = Study("vgg16", StudyScenario(edge="edge-embedded",
                                         channel=channel))
    model = study.model
    print(f"model: {model.name}, {len(model.layers)} layers, "
          f"legal cuts {model.cut_points()}")

    # --- 1. planner suggests a cut for the edge class ------------------
    device = DeviceClass.make("edge-embedded", channel)
    trace = generate_trace([device], 200, 60.0, seed=0)
    study.profile().candidates(top_n=4)
    study.simulate(fleet=(trace, [device]), include_rc=False,
                   batch_sizes=(1, 8), replica_counts=(1, 2))
    plans = study.suggest(QoSRequirements(max_latency_s=0.2,
                                          min_accuracy=0.1))
    plan = plans[device.name]
    assert plan is not None, "planner found no feasible deployment"
    split = plan.split_layer
    print(f"planner suggests {plan.label} over {plan.protocol} "
          f"(batch={plan.max_batch}, replicas={plan.n_replicas}, "
          f"p99={plan.p99_s * 1e3:.2f} ms) -> executing cut {split}")
    # the simulated-vs-executed comparison below must price the wire over
    # the protocol the runtime actually executes with
    study.scenario = replace(study.scenario, protocol=plan.protocol or "tcp")

    # --- 2. execute the suggested cut live -----------------------------
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 16, 16, 3)).astype(np.float32)
    rt = study.deploy(device=device.name)
    res = rt.infer(x, iters=5)
    ref = rt.reference(x)
    agree = (np.argmax(res.logits, -1) == np.argmax(ref, -1)).all()
    print(f"executed: head {res.head_s * 1e3:.3f} ms | wire "
          f"{res.wire_bytes} B / {res.transfer_s * 1e3:.3f} ms | tail "
          f"{res.tail_s * 1e3:.3f} ms | total {res.total_s * 1e3:.3f} ms | "
          f"argmax agrees with unsplit: {agree}")

    # --- 3. calibrate the simulator with the measurements --------------
    def sc_latency(s: Study) -> tuple:
        v = next(v for v in s.verdicts if v.candidate.split_layer == split)
        return v.latency_s, v.meta["cost_source"]

    study.simulate()                       # analytic costs (study link)
    pa, src_a = sc_latency(study)
    study.calibrate(splits=[split], iters=5)
    study.simulate()                       # same link, measured costs
    pm, src_m = sc_latency(study)
    print(f"simulator: {src_m}-cost {pm * 1e3:.3f} ms "
          f"({abs(pm - res.total_s) / res.total_s * 100:.1f}% off executed) "
          f"vs {src_a} {pa * 1e3:.3f} ms "
          f"({abs(pa - res.total_s) / res.total_s * 100:.1f}% off)")

    # --- 4. five clients, one batched tail server ----------------------
    clients = [rng.standard_normal((1, 16, 16, 3)).astype(np.float32)
               for _ in range(5)]
    results, server = run_clients(study.model, study.params, split, clients,
                                  n_slots=2, quantize=True)
    occ = ",".join(map(str, server.occupancy))
    print(f"multi-client: {server.n_served} tail requests in "
          f"{server.n_batches} batched steps (occupancy {occ})")
    assert sorted(results) == list(range(5))
    print("ok")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
