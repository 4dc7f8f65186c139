"""Microbenchmarks: wall-clock us/call for the framework's hot host-side
paths (netsim event engine, saliency pass, kernels in interpret mode are
correctness-only and excluded from timing)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.saliency import cumulative_saliency
from repro.data.synthetic import toy_images
from repro.models.vgg import feature_index
from repro.netsim.channel import Channel
from repro.netsim.protocols import simulate_tcp, simulate_udp

from .common import timed, trained_vgg


def run(fast: bool = False):
    rows = []
    ch = Channel(100e-6, 1e9, 1e9, loss_rate=0.05, seed=0)
    us, r = timed(lambda: simulate_tcp(100_000, ch), iters=3)
    rows.append(("micro.netsim.tcp_100kB_us", us, r.n_transmissions))
    us, r = timed(lambda: simulate_udp(100_000, ch), iters=10)
    rows.append(("micro.netsim.udp_100kB_us", us, r.n_packets))

    model, params = trained_vgg()
    xs, ys = toy_images(8, hw=16, seed=1)
    fi = feature_index(model)
    us, _ = timed(lambda: cumulative_saliency(model, params, jnp.asarray(xs),
                                              jnp.asarray(ys), layer_idx=fi),
                  iters=2)
    rows.append(("micro.saliency.cs_curve_8imgs_us", us, len(fi)))

    fwd = jax.jit(lambda x: model.apply(params, x))
    x = jnp.asarray(xs)
    us, _ = timed(fwd, x, iters=10)
    rows.append(("micro.vgg.fwd_b8_us", us, 0))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
