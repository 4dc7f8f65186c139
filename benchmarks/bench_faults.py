"""Fault-tolerance benchmark: recovery correctness, cost, and the
zero-fault contract.

Three seeded scenarios run the live split runtime through the fault
layer (ISSUE 10):

1. **zero-fault** — ``faults=None``: the fast path.  Asserted in-bench:
   the wire bytes are the historical SEI1 layout bit-for-bit (magic,
   header, payload — no CRC pair).  Any drift here is a wire-format regression, not noise.
2. **chaos** — drops + corruption + stragglers on every request.  The
   acceptance floor asserted in-bench: **100% completion** within the
   deadline budget, and every *non-degraded* request's logits are
   bit-identical to the zero-fault run.
3. **blackout** — the tail server goes dark permanently; every request
   must land on the local-fallback rung.

Fault counts, retry totals, backoff seconds and the virtual recovery
overhead are all deterministic functions of the FaultPlan seed (the
runtime prices timeouts/backoff on the simulated clock), so they gate
on the exact-replay band in ``perf_compare``; wall-clock overhead is
reported, not gated.

  PYTHONPATH=src python -m benchmarks.bench_faults [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.netsim.channel import Channel
from repro.runtime import wire as W
from repro.runtime.engine import SplitRuntime
from repro.runtime.faults import FaultPlan, RecoveryPolicy

from .common import RESULTS_DIR


def _model(quick: bool):
    import jax
    from repro.models.vgg import vgg_cifar
    if quick:
        model = vgg_cifar(n_classes=8, input_hw=16, width_mult=0.25)
        return model, model.init(jax.random.PRNGKey(0))
    from benchmarks.common import trained_vgg
    return trained_vgg()


def _assert_zero_fault_bytes(rt, x):
    """The zero-fault wire is the historical SEI1 frame, byte for byte."""
    import struct

    import jax.numpy as jnp
    f0 = rt.part.stage(0)(jnp.asarray(x))
    pkt = W.encode_activation(f0, rt.part.ae_map.get(rt.part.splits[0]))
    buf = W.to_bytes(pkt)
    head = (W.MAGIC + struct.pack("<BB", W._KINDS.index(pkt.kind), len(pkt.shape))
            + struct.pack(f"<{len(pkt.shape)}I", *pkt.shape))
    want = head + pkt.data.tobytes() + pkt.scales.tobytes()
    if buf != want:
        raise AssertionError(
            f"zero-fault frame drifted from the SEI1 layout "
            f"({len(buf)} vs {len(want)} B)")


def run(fast: bool = False, out_path: str = None) -> list:
    model, params = _model(fast)
    split = model.cut_points()[1]
    n_req = 6 if fast else 16
    ch = Channel(latency_s=2e-3, capacity_bps=50e6, interface_bps=100e6,
                 seed=0)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((1,) + tuple(model.input_shape)
                              ).astype(np.float32) for _ in range(n_req)]

    # --- 1. zero-fault: the fast path and its byte contract -------------
    rt0 = SplitRuntime(model, params, split, channel=ch, quantize=True)
    _assert_zero_fault_bytes(rt0, xs[0])
    base = []
    clean_total = 0.0
    for x in xs:
        r = rt0.infer(x, iters=1)
        base.append(np.asarray(r.logits))
        clean_total += r.total_s

    # --- 2. chaos: drops + corruption + stragglers ----------------------
    plan = FaultPlan(seed=7, drop_rate=0.35, corrupt_rate=0.25,
                     straggle_rate=0.1, straggle_s=0.01)
    pol = RecoveryPolicy(max_attempts=6, deadline_s=5.0, downgrade_after=2)
    rt = SplitRuntime(model, params, split, channel=ch, quantize=True,
                      faults=plan, recovery=pol)
    done = degraded = identical = 0
    faults = {}
    retries = timeouts = downgrades = fallbacks = 0
    backoff_s = chaos_total = 0.0
    for rid, x in enumerate(xs):
        r = rt.infer(x, iters=1, rid=rid)
        done += 1
        chaos_total += r.total_s
        rec = r.meta["recovery"]
        for k, v in rec["faults"].items():
            faults[k] = faults.get(k, 0) + v
        retries += rec["retries"]
        timeouts += rec["timeouts"]
        downgrades += len(rec["downgrades"])
        fallbacks += bool(rec["local_fallback"])
        backoff_s += rec["backoff_s"]
        if r.meta["degraded"]:
            degraded += 1
        elif np.array_equal(np.asarray(r.logits), base[rid]):
            identical += 1
    if done != n_req:
        raise AssertionError(f"completion {done}/{n_req} under chaos")
    if identical + degraded != n_req:
        raise AssertionError(
            f"{n_req - degraded - identical} retried requests diverged "
            f"from the fault-free logits")

    # --- 3. blackout: the server leg is hopeless ------------------------
    black = FaultPlan(seed=1, blackouts=((0.0, 1e9),))
    rtb = SplitRuntime(model, params, split, channel=ch, quantize=True,
                       faults=black,
                       recovery=RecoveryPolicy(max_attempts=3))
    n_fallback = 0
    for rid, x in enumerate(xs):
        r = rtb.infer(x, iters=1, rid=rid)
        if r.meta["local_fallback"]:
            n_fallback += 1
    if n_fallback != n_req:
        raise AssertionError(
            f"blackout: {n_fallback}/{n_req} requests fell back locally")

    report = {
        "quick": fast,
        "model": model.name,
        "split": split,
        "n_requests": n_req,
        "zero_fault": {
            # asserted above; recorded so the gate notices if the
            # assertion is ever deleted
            "sei1_bit_identical": 1.0,
        },
        "chaos": {
            "completion_rate": done / n_req,
            "identical": identical,
            "degraded": degraded,
            "faults": faults,
            "retries": retries,
            "timeouts": timeouts,
            "downgrades": downgrades,
            "local_fallbacks": fallbacks,
            "backoff_s": backoff_s,
            # virtual seconds the recovery machinery added per request
            # (timeout waits + backoff, on the simulated clock)
            "overhead_ms_per_req": (chaos_total - clean_total) / n_req * 1e3,
        },
        "blackout": {
            "fallback_rate": n_fallback / n_req,
        },
    }
    out_path = out_path or os.path.join(RESULTS_DIR, "faults",
                                        "bench_faults.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)

    c = report["chaos"]
    return [
        ("faults.zero_fault.sei1_bit_identical", 0.0, 1.0),
        ("faults.chaos.completion_rate", 0.0, c["completion_rate"]),
        ("faults.chaos.retries", 0.0, c["retries"]),
        ("faults.chaos.downgrades", 0.0, c["downgrades"]),
        ("faults.chaos.backoff_s", 0.0, round(c["backoff_s"], 6)),
        ("faults.chaos.overhead_ms_per_req", 0.0,
         round(c["overhead_ms_per_req"], 3)),
        ("faults.blackout.fallback_rate", 0.0,
         report["blackout"]["fallback_rate"]),
    ]


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="untrained small model, 6 requests (CI smoke)")
    ap.add_argument("--out", default=None, help="JSON artifact path")
    args = ap.parse_args()
    for row in run(fast=args.quick, out_path=args.out):
        print(",".join(map(str, row)))
