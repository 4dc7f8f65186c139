"""End-to-end serving driver (deliverable b): batched requests against a
small transformer, served in the RC and SC styles.

The LM is trained briefly on the synthetic token stream, then:
  * a batch of prompts is served with the ServingEngine (prefill+decode),
  * the same inference is mapped onto the paper's split execution: the
    first half of the blocks is the "edge" head, the bottleneck compresses
    the residual stream (int8 wire payload via the Pallas-kernel path's
    reference), the netsim prices the transfer.

Run:  PYTHONPATH=src python examples/serve_split.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.synthetic import token_iter
from repro.kernels import ref as kref
from repro.models.common import reduced
from repro.models.layered import transformer_as_layered
from repro.netsim.channel import Channel
from repro.netsim.protocols import simulate_transfer
from repro.serving.engine import Request, ServingEngine
from repro.training.optimizer import OptConfig
from repro.training.train import init_train_state, make_train_step


def main():
    cfg = reduced(get_config("llama3-8b"), vocab=128, n_layers=4)
    print(f"model: {cfg.name} ({cfg.n_layers}L d={cfg.d_model})")

    # --- quick train so generations are non-trivial -------------------
    oc = OptConfig(lr=3e-3)
    params, opt = init_train_state(jax.random.PRNGKey(0), cfg, oc)
    step = jax.jit(make_train_step(cfg, oc))
    it = token_iter(8, 64, cfg.vocab, seed=0)
    for i in range(60):
        b = next(it)
        params, opt, m = step(params, opt,
                              {k: jnp.asarray(v) for k, v in b.items()})
    print(f"trained 60 steps, final loss {float(m['loss']):.3f}")

    # --- batched serving ----------------------------------------------
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 12).astype(np.int32),
                    max_new=8) for i in range(4)]
    engine = ServingEngine(cfg, params, cache_slots=64)
    done = engine.run(reqs)
    for r in done:
        print(f"request {r.rid}: prompt[:4]={r.prompt[:4].tolist()} -> {r.out}")

    # --- the same model through the split-computing lens ---------------
    lay = transformer_as_layered(cfg, params)
    cut = lay.cut_points()[len(lay.cut_points()) // 2]
    batch = {"tokens": jnp.asarray(np.stack([r.prompt for r in reqs]))}
    # head forward: embed + first blocks
    x = lay.layers[0].apply({}, batch)
    for l in lay.layers[1:cut + 1]:
        x = l.apply({}, x)
    # bottleneck-compress the wire payload (int8 + per-row scales)
    n, s, d = x.shape
    w = jax.random.normal(jax.random.PRNGKey(1), (d, d // 2)) * 0.05
    q8, scales = kref.bottleneck_compress_ref(x.reshape(n * s, d).astype(jnp.float32),
                                              w, jnp.zeros((d // 2,)))
    wire_bytes = q8.size + scales.size * 4
    raw_bytes = x.size * 2
    print(f"split after block {cut}: wire payload {wire_bytes} B "
          f"(raw residual would be {raw_bytes} B, {raw_bytes / wire_bytes:.1f}x larger)")
    ch = Channel(latency_s=5e-3, capacity_bps=160e6, interface_bps=160e6,
                 loss_rate=0.01, seed=0)  # Wi-Fi edge uplink
    tr = simulate_transfer("tcp", int(wire_bytes), ch)
    tr_raw = simulate_transfer("tcp", int(raw_bytes), ch)
    print(f"Wi-Fi transfer: compressed {tr.duration_s * 1e3:.1f} ms vs "
          f"raw {tr_raw.duration_s * 1e3:.1f} ms")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
