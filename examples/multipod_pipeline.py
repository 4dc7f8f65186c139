import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Multi-pod split computing (the TPU adaptation, DESIGN.md §3).

The paper's head/bottleneck/tail triple mapped onto a 2-pod mesh: the cut
becomes the cross-pod stage boundary, the bottleneck compresses the
activation crossing the inter-pod link, and `lax.ppermute` is the wire.
Runs on whatever even number of devices it is given (8 emulated host
devices by default on the CPU) as a (pod=2, data=n/2) mesh and validates
the pipelined output against the single-program forward.

Run:  PYTHONPATH=src python examples/multipod_pipeline.py
"""
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import get_config
from repro.core import bottleneck as B
from repro.core.split import multipod_split_step
from repro.models import transformer as T
from repro.models.common import reduced


def main():
    n = len(jax.devices())
    if n < 2 or n % 2:
        raise SystemExit(f"needs an even number of devices (>= 2), got {n}")
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, n // 2), ("pod", "data"))
    cfg = reduced(get_config("llama3-8b"), n_layers=4, dtype="float32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    batch = {"tokens": tokens}

    # reference: ordinary single-program forward
    out = T.forward(params, cfg, batch)
    ref = np.asarray(T.logits_from_x(params, cfg, out["x"]))

    # 2-stage pipeline without bottleneck: must match exactly
    got = np.asarray(multipod_split_step(params, cfg, batch, mesh,
                                         ae=None, n_micro=4))
    err = np.abs(got - ref).max()
    print(f"pipeline (no bottleneck) vs forward: max err {err:.2e}")
    assert err < 1e-3

    # with a (random) 50% bottleneck on the wire: output degrades gracefully
    ae = B.init_bottleneck(jax.random.PRNGKey(2), (cfg.d_model,), rate=0.5)
    got_ae = np.asarray(multipod_split_step(params, cfg, batch, mesh,
                                            ae=ae, n_micro=4))
    print(f"pipeline with 50% bottleneck: output delta {np.abs(got_ae - ref).mean():.3f} "
          f"(wire payload halved: {cfg.d_model} -> {B.latent_channels(cfg.d_model, 0.5)} ch)")
    print("cross-pod hop carries", B.latent_channels(cfg.d_model, 0.5) * 4,
          "bytes/token instead of", cfg.d_model * 4)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
