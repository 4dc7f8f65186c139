"""Paper Fig. 4: RC accuracy (left) and latency (right) vs packet loss,
TCP vs UDP, 1 Gb/s full-duplex channel.

Expected (paper §V-C): TCP accuracy is loss-invariant but latency grows;
UDP latency is loss-invariant but accuracy falls (no recovery — the
receiver runs inference on the corrupted input tensor)."""
from __future__ import annotations

import json
import os

from repro.core.scenarios import Scenario
from repro.data.synthetic import toy_images
from repro.netsim.channel import Channel
from repro.netsim.simulator import ApplicationSimulator, NetworkConfig

from .common import RESULTS_DIR, trained_vgg

LOSS_RATES = [0.0, 0.05, 0.1, 0.2, 0.3]


def run(fast: bool = False):
    model, params = trained_vgg()
    xs, ys = toy_images(64 if fast else 128, hw=16, seed=777)
    rc = Scenario("RC")
    table = {"tcp": {}, "udp": {}}
    for proto in ("tcp", "udp"):
        for p in (LOSS_RATES[::2] if fast else LOSS_RATES):
            net = NetworkConfig(proto, Channel(100e-6, 1e9, 1e9,
                                               loss_rate=p, seed=11))
            sim = ApplicationSimulator(model, params, net)
            v = sim.simulate(rc, xs, ys, n_frames=8)
            table[proto][p] = {"accuracy": v.accuracy, "latency_s": v.latency_s}
    os.makedirs(os.path.join(RESULTS_DIR, "paper"), exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "paper", "fig4_protocol.json"), "w") as f:
        json.dump(table, f, indent=1)
    t, u = table["tcp"], table["udp"]
    ps = sorted(t)
    rows = [
        ("fig4.tcp.acc_flat", 0.0,
         int(abs(t[ps[0]]["accuracy"] - t[ps[-1]]["accuracy"]) < 1e-9)),
        ("fig4.tcp.latency_grows", 0.0,
         int(t[ps[-1]]["latency_s"] > t[ps[0]]["latency_s"])),
        ("fig4.udp.acc_drops", 0.0,
         int(u[ps[-1]]["accuracy"] < u[ps[0]]["accuracy"])),
        ("fig4.udp.latency_flat", 0.0,
         int(abs(u[ps[-1]]["latency_s"] - u[ps[0]]["latency_s"])
             < 0.2 * u[ps[0]]["latency_s"] + 1e-9)),
        ("fig4.udp.acc_at_max_loss", 0.0, u[ps[-1]]["accuracy"]),
        ("fig4.tcp.lat_at_max_loss_s", 0.0, t[ps[-1]]["latency_s"]),
    ]
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
