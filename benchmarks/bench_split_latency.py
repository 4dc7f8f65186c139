"""Paper Fig. 3: SC frame latency vs packet-loss rate, TCP, 1 Gb/s channel,
20 FPS (0.05 s) application constraint — split at feature ops 11 vs 15.

Uses the *full* VGG16 at 224x224 (the paper's actual network — Fig. 3
needs payload sizes and FLOPs, not accuracy): op 11 = block4_conv2,
op 15 = block5_conv2, 50%-compression bottleneck on the wire (f32 latent,
paper-faithful).  Expected (paper §V-B): the deeper split (15) ships 4x
fewer bytes and stays under 0.05 s at every loss rate; the shallow split
(11) violates the constraint beyond a few % loss.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from repro.core import stats as S
from repro.core.qos import QoSRequirements
from repro.core.scenarios import PLATFORMS
from repro.models.vgg import feature_index, vgg16
from repro.netsim.channel import Channel
from repro.netsim.protocols import simulate_transfer

from .common import RESULTS_DIR

LOSS_RATES = [0.0, 0.01, 0.02, 0.03, 0.05, 0.08, 0.12]
QOS = QoSRequirements(max_latency_s=0.05)   # 20 FPS conveyor belt
COMPRESSION = 0.5
WIRE_BYTES_PER_ELEM = 4                      # paper AE: f32 latent


def run(fast: bool = False):
    model = vgg16()
    params = model.init(jax.random.PRNGKey(0))
    rows_tbl = S.summary(model, params, batch=1)
    fi = feature_index(model)
    # Orin-class edge accelerator: with a Nano-class 0.5 TF/s edge the head
    # compute (41-58 ms) dominates and inverts the paper's ordering; the
    # paper's Fig. 3 latencies are transmission-dominated (EXPERIMENTS.md)
    edge, server = PLATFORMS["edge-accelerator"], PLATFORMS["server-gpu"]

    out_rows, table = [], {}
    for op in (11, 15):                      # paper's Fig. 3 split points
        cut = fi[op - 1]                     # op index (1-based) -> layer idx
        head_f, tail_f = S.flops_split(model, params, cut, batch=1)
        feat = rows_tbl[cut].output_shape
        wire = int(np.prod(feat[1:-1])) * int(feat[-1] * COMPRESSION) \
            * WIRE_BYTES_PER_ELEM
        compute_s = edge.compute_time(head_f) + server.compute_time(tail_f)
        lat = {}
        for p in (LOSS_RATES[::2] if fast else LOSS_RATES):
            ch = Channel(1e-3, 1e9, 1e9, loss_rate=p, seed=3)
            transfers = [simulate_transfer("tcp", wire, ch, stream=s)
                         for s in range(16)]
            lat[p] = compute_s + float(np.mean([t.duration_s for t in transfers]))
        table[f"SC@{op}"] = {"wire_bytes": wire, "compute_s": compute_s,
                             "latency": lat}
        worst = max(lat.values())
        out_rows.append((f"fig3.SC@{op}.wire_bytes", 0.0, wire))
        out_rows.append((f"fig3.SC@{op}.latency_at_max_loss_s", 0.0,
                         round(worst, 5)))
        out_rows.append((f"fig3.SC@{op}.meets_20fps_all_loss", 0.0,
                         int(all(l <= QOS.max_latency_s for l in lat.values()))))
    os.makedirs(os.path.join(RESULTS_DIR, "paper"), exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "paper", "fig3_split_latency.json"), "w") as f:
        json.dump({"qos_max_latency_s": QOS.max_latency_s, "curves": table},
                  f, indent=1)
    return out_rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
