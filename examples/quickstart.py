"""Quickstart: the full Split-Et-Impera design flow through ``repro.api``.

One ``Study`` object carries the whole pipeline (paper Fig. 1):

  1. train a small VGG on the conveyor-belt toy task (paper §V scenario),
  2. compute the Grad-CAM Cumulative Saliency curve (Fig. 1-i),
  3. pick candidate split points at the CS local maxima,
  4. train bottleneck AEs and simulate LC / RC / SC over a TCP channel
     (Fig. 1-ii),
  5. let the QoS matcher suggest the best design (Fig. 1-iii).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.api import (Channel, NetworkConfig, QoSRequirements, Study,
                       toy_image_iter, toy_images)


def main():
    print("== 1. train the model (paper §V: Adam, lr 5e-3) ==")
    xs, ys = toy_images(64, hw=16, seed=55)
    # LC runs a weaker local model (the whole point of the LC/RC trade-off)
    lc = Study("vgg16").fit(steps=30)
    study = Study("vgg16", data=(xs[:32], ys[:32]),
                  lc=(lc.model, lc.params)).fit(steps=300)
    print(f"   test accuracy: {study.eval_accuracy():.3f}")

    print("== 2. cumulative saliency curve ==")
    study.profile()
    for l, v in zip(study.layer_idx, study.cs_curve):
        print(f"   layer {l:2d}: {'#' * int(v * 40)} {v:.3f}")

    print("== 3. candidate split points (CS local maxima) ==")
    study.candidates(top_n=3)
    for c in study.candidate_list:
        print(f"   {c.label:8s} accuracy proxy {c.accuracy_proxy:.3f}")

    print("== 4. communication-aware simulation (TCP, 1 Gb/s, 2% loss) ==")
    study.bottlenecks(steps=150, lr=2e-3,
                      data_iter=toy_image_iter(32, hw=16, seed=9))
    net = NetworkConfig("tcp", Channel(100e-6, 1e9, 1e9, loss_rate=0.02,
                                       seed=0))
    study.simulate(network=net)
    for v in study.verdicts:
        print(f"   {v.candidate.label:8s} latency {v.latency_s * 1e3:8.2f} ms  "
              f"accuracy {v.accuracy:.3f}  "
              f"wire {v.meta.get('wire_bytes', 0):>8d} B")

    print("== 5. QoS suggestion (20 FPS, accuracy >= 0.5) ==")
    qos = QoSRequirements(max_latency_s=0.05, min_accuracy=0.5)
    best = study.suggest(qos)
    if best is None:
        print("   no design meets the constraints — relax QoS or change network")
    else:
        print(f"   suggested design: {best.candidate.label} "
              f"({best.latency_s * 1e3:.2f} ms, acc {best.accuracy:.3f})")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
