"""Split-runtime benchmark: executed latency vs simulator prediction.

For a grid of split points the same cut is (a) *executed* by the live
runtime (head -> int8 wire -> tail, per-stage wall clock, transfer priced
on the actual payload bytes) and (b) *predicted* by
``netsim.simulator.measure_flow`` twice — with the analytic
FLOPs/throughput cost model, and with the measured
:class:`~repro.runtime.calibrate.CalibrationTable` the runtime itself
emitted.  The per-split prediction error is the repo's ground-truth check
that the simulators mean something (paper claim iii), and the JSON
artifact is the CI regression gate's input.

  PYTHONPATH=src python -m benchmarks.bench_runtime [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import os

import numpy as np

from repro.core.scenarios import Scenario
from repro.core.split import SplitPlan
from repro.netsim.channel import Channel
from repro.netsim.simulator import (NetworkConfig, flow_latency_s,
                                    measure_flow)
from repro.runtime.calibrate import calibrate
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


def _pick_splits(model, k: int = 4) -> list:
    cuts = model.cut_points()
    idx = np.linspace(0, len(cuts) - 1, min(k, len(cuts))).astype(int)
    return sorted({cuts[i] for i in idx})


def run(fast: bool = False, out_path: str = None) -> list:
    model, params = _model(fast)
    splits = _pick_splits(model, 3 if fast else 5)
    iters = 7 if fast else 10
    batch = 4                        # deterministic wire time dominates
    ch = Channel(latency_s=5e-4, capacity_bps=100e6, interface_bps=100e6,
                 seed=0)
    netcfg = NetworkConfig("tcp", ch)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch,) + tuple(model.input_shape)
                            ).astype(np.float32)
    input_bytes = x.nbytes

    rows = []
    table = None
    for split in splits:
        # calibrate and execute back-to-back so host-load drift between
        # the two passes doesn't masquerade as simulator error
        table = calibrate(model, params, [split], x=x, iters=iters,
                          include_rc=False, include_lc=False)
        rt = SplitRuntime(model, params, split, channel=ch, quantize=True)
        res = rt.infer(x, iters=iters)
        sc = Scenario("SC", SplitPlan(split))
        flow_m = measure_flow(sc, netcfg, model, params, input_bytes,
                              cost=table, batch=batch)
        flow_a = measure_flow(sc, netcfg, model, params, input_bytes,
                              batch=batch)
        exec_s = res.total_s
        pred_m, pred_a = flow_latency_s(flow_m), flow_latency_s(flow_a)
        rows.append({
            "split": split,
            "exec_ms": exec_s * 1e3,
            "sim_measured_ms": pred_m * 1e3,
            "sim_analytic_ms": pred_a * 1e3,
            "err_measured_pct": abs(pred_m - exec_s) / exec_s * 100,
            "err_analytic_pct": abs(pred_a - exec_s) / exec_s * 100,
            "wire_bytes_exec": res.wire_bytes,
            "wire_bytes_sim": flow_m["wire_bytes"],
            "head_ms": res.head_s * 1e3,
            "tail_ms": res.tail_s * 1e3,
            "transfer_ms": res.transfer_s * 1e3,
            # host-side work around the wire hop: codec dispatch +
            # serialise/parse + codec compute
            "boundary_overhead_ms": (res.encode_s + res.decode_s) * 1e3,
        })

    report = {
        "quick": fast,
        "model": model.name,
        "n_splits": len(splits),
        "splits": rows,
        "max_err_measured_pct": max(r["err_measured_pct"] for r in rows),
        "mean_err_measured_pct": float(np.mean([r["err_measured_pct"]
                                                for r in rows])),
        "mean_err_analytic_pct": float(np.mean([r["err_analytic_pct"]
                                                for r in rows])),
    }
    out_path = out_path or os.path.join(RESULTS_DIR, "runtime",
                                        "bench_runtime.json")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)

    out = []
    for r in rows:
        out.append((f"runtime.split{r['split']}.exec_ms", 0.0,
                    round(r["exec_ms"], 3)))
        out.append((f"runtime.split{r['split']}.err_measured_pct", 0.0,
                    round(r["err_measured_pct"], 1)))
        out.append((f"runtime.split{r['split']}.err_analytic_pct", 0.0,
                    round(r["err_analytic_pct"], 1)))
    out.append(("runtime.max_err_measured_pct", 0.0,
                round(report["max_err_measured_pct"], 1)))
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="untrained small model, 3 splits (CI smoke)")
    ap.add_argument("--out", default=None, help="JSON artifact path")
    args = ap.parse_args()
    for row in run(fast=args.quick, out_path=args.out):
        print(",".join(map(str, row)))
