"""Chaos walkthrough: the split runtime surviving a hostile link.

A seeded :class:`~repro.runtime.faults.FaultPlan` injects transfer
drops, frame corruption and a mid-run tail-server blackout into the
live split runtime; the :class:`~repro.runtime.faults.RecoveryPolicy`
answers with RTO-derived timeouts, capped exponential backoff, codec
downgrade and — when the server leg is hopeless — full local fallback.
The contract demonstrated here:

 1. every request completes within its deadline budget — 100%
    completion, no exceptions escape;
 2. retried (non-degraded) requests produce logits *bit-identical* to
    the fault-free run — recovery is invisible to the model;
 3. degraded requests are flagged in ``RuntimeResult.meta`` and priced
    honestly (backoff + timeout seconds land in ``total_s``);
 4. the whole schedule is deterministic: rerunning this script yields
    the same faults, the same retries, the same bytes.

Run:  PYTHONPATH=src python examples/chaos_runtime.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.api import Channel, Study, StudyScenario
from repro.runtime.faults import FaultPlan, RecoveryPolicy


def main():
    channel = Channel(2e-3, 50e6, 100e6, loss_rate=0.02, seed=2)
    study = Study("vgg16", StudyScenario(edge="edge-embedded",
                                         channel=channel))
    cut = study.model.cut_points()[1]
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((1, 16, 16, 3)).astype(np.float32)
          for _ in range(8)]

    # fault-free reference: the bit-identity baseline
    clean = study.deploy(candidate=f"SC@{cut}")
    base = [np.asarray(clean.infer(x, iters=1).logits) for x in xs]

    # a hostile link: 35% drops, 25% corrupt frames, and the tail
    # server goes dark for a window mid-run
    plan = FaultPlan(seed=7, drop_rate=0.35, corrupt_rate=0.25,
                     straggle_rate=0.1, straggle_s=0.02,
                     blackouts=((0.05, 0.08),))
    policy = RecoveryPolicy(max_attempts=6, deadline_s=2.0,
                            downgrade_after=2)
    report = study.observe()
    rt = study.deploy(candidate=f"SC@{cut}", faults=plan,
                      recovery=policy)
    done = degraded = identical = 0
    for rid, x in enumerate(xs):
        r = rt.infer(x, iters=1, rid=rid)
        done += 1
        rv = r.meta["recovery"]
        if r.meta["degraded"]:
            degraded += 1
        elif np.array_equal(np.asarray(r.logits), base[rid]):
            identical += 1
        flags = []
        if rv["local_fallback"]:
            flags.append("local-fallback")
        elif r.meta["degraded"]:
            flags.append("degraded")
        print(f"  rid={rid}: {sum(rv['faults'].values())} faults, "
              f"{rv['retries']} retries, "
              f"backoff {rv['backoff_s'] * 1e3:.1f} ms, "
              f"total {r.total_s * 1e3:.1f} ms"
              + (f"  [{','.join(flags)}]" if flags else ""))
    print(f"completion: {done}/{len(xs)} "
          f"({identical} bit-identical to fault-free, {degraded} degraded)")
    assert done == len(xs), "every request must complete"
    assert identical + degraded == done

    counters = {k: v for k, v in report.metrics.snapshot().items()
                if k.startswith(("runtime.fault.", "runtime.retry."))}
    print("telemetry:")
    for k, v in counters.items():
        print(f"  {k} = {v:g}")
    assert counters.get("runtime.retry.attempts", 0) > 0

    # determinism: a fresh runtime under the same plan reproduces the
    # run exactly — logits, fault counts, backoff schedule
    rt2 = study.deploy(candidate=f"SC@{cut}", faults=plan,
                       recovery=policy)
    for rid, x in enumerate(xs):
        a = rt.infer(x, iters=1, rid=rid)
        b = rt2.infer(x, iters=1, rid=rid)
        assert np.array_equal(np.asarray(a.logits), np.asarray(b.logits))
        assert a.meta["recovery"]["faults"] == b.meta["recovery"]["faults"]
    print("determinism: second runtime reproduced the run exactly")
    print("ok")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
