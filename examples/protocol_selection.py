"""Transmission-protocol selection (paper §V-C, Fig. 4).

Sweeps packet-loss rates over TCP and UDP for the RC scenario and prints
the accuracy/latency trade-off the engineer would use to pick a protocol
under the application's QoS.

Run:  PYTHONPATH=src python examples/protocol_selection.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks.common import trained_vgg
from repro.core.qos import QoSRequirements
from repro.core.scenarios import Scenario
from repro.data.synthetic import toy_images
from repro.netsim.channel import Channel
from repro.netsim.simulator import ApplicationSimulator, NetworkConfig


def main():
    model, params = trained_vgg()
    xs, ys = toy_images(128, hw=16, seed=777)
    qos = QoSRequirements(max_latency_s=0.0005, min_accuracy=0.8)
    print(f"QoS: latency <= {qos.max_latency_s * 1e3} ms, accuracy >= {qos.min_accuracy}")
    print(f"{'proto':6s} {'loss':>5s} {'acc':>7s} {'lat ms':>8s}  feasible")
    for proto in ("tcp", "udp"):
        for loss in (0.0, 0.05, 0.1, 0.2, 0.3):
            net = NetworkConfig(proto, Channel(100e-6, 1e9, 1e9,
                                               loss_rate=loss, seed=11))
            sim = ApplicationSimulator(model, params, net)
            v = sim.simulate(Scenario("RC"), xs, ys, n_frames=8)
            ok = v.satisfies(qos)
            print(f"{proto:6s} {loss:5.2f} {v.accuracy:7.3f} "
                  f"{v.latency_s * 1e3:8.3f}  {'YES' if ok else 'no'}")
    print("\nreading: TCP keeps accuracy but blows the latency budget under "
          "loss; UDP keeps latency but loses accuracy — pick per QoS.")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
