"""§Perf hillclimb 3 (paper-representative): the cross-pod split wire.

Lowers the 2-stage multipod split pipeline (llama3-8b, 2x16x16 mesh) in
three wire configurations and measures the collective-permute bytes that
cross the pod boundary per step:

  raw      — no bottleneck: the bf16 residual stream crosses the link
  ae_f32   — paper-faithful 50% undercomplete AE, f32 latent on the wire
  ae_int8  — + int8 wire quantisation (what the `bottleneck_compress`
             Pallas kernel fuses on TPU): codes + one f32 scale/token

Run standalone:  PYTHONPATH=src python -m benchmarks.bench_multipod_wire
(sets the 512-device emulation flag itself; from benchmarks.run it only
executes when the device count allows).
"""
from __future__ import annotations

import json
import os


def _measure(mesh_shape=(2, 4, 4), batch=32, seq=2048, n_micro=4):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import bottleneck as B
    from repro.core.split import multipod_split_step
    from repro.launch.hlo_cost import HloCost
    from repro.models import transformer as T

    cfg = get_config("llama3-8b")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(mesh_shape, ("pod", "data", "model"))
    pstruct = jax.eval_shape(lambda k: T.init_params(k, cfg),
                             jax.ShapeDtypeStruct((2,), jnp.uint32))
    ae_struct = jax.eval_shape(
        lambda k: B.init_bottleneck(k, (cfg.d_model,), 0.5),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    out = {}
    for name, ae, quant in (("raw", None, False),
                            ("ae_f32", ae_struct, False),
                            ("ae_int8", ae_struct, True)):
        def step(params, aep, toks):
            return multipod_split_step(params, cfg, {"tokens": toks}, mesh,
                                       ae=aep, n_micro=n_micro,
                                       quantize_wire=quant)

        with mesh:
            lowered = jax.jit(step).lower(pstruct, ae, tokens)
            compiled = lowered.compile()
        hc = HloCost(compiled.as_text())
        cp = hc.collective_summary().get("collective-permute",
                                         {"wire_bytes": 0, "count": 0})
        out[name] = {"permute_wire_bytes": cp["wire_bytes"],
                     "permute_count": cp["count"]}
    return out


def run(fast: bool = False):
    import jax
    if len(jax.devices()) < 32:
        return [("multipod_wire.skipped_needs_device_emulation", 0.0, 1)]
    res = _measure()
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "perf_multipod_wire.json")
    with open(path, "w") as f:
        json.dump(res, f, indent=1)
    rows = []
    for k, v in res.items():
        rows.append((f"multipod_wire.{k}.bytes", 0.0, v["permute_wire_bytes"]))
    if res["ae_int8"]["permute_wire_bytes"]:
        rows.append(("multipod_wire.raw_over_int8", 0.0,
                     round(res["raw"]["permute_wire_bytes"]
                           / res["ae_int8"]["permute_wire_bytes"], 2)))
    return rows


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    for r in run():
        print(",".join(map(str, r)))
