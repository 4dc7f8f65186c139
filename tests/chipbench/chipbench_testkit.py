"""Helpers of the chip benchmark's CPU tests: a copy of the benchmark with
one more configuration and cell, at a size the CPU runs in seconds."""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_CONFIG = {
    "name": "tiny-vgg", "source": "https://arxiv.org/abs/1409.1556",
    "reference": "vgg", "builder": "repro.models.vgg:build_vgg",
    "builder_args": ["plan", "input_hw", "in_ch", "n_classes",
                     "classifier_width"],
    "plan": [8, "M", 16, "M"], "input_hw": 16, "in_ch": 3, "n_classes": 10,
    "classifier_width": 32, "dtype": "float32", "matmul_precision": "default",
    "wire": "ae8", "cut": 2, "ae_latent": 4, "n_slots": 4, "client_batch": 1,
    "frame_pool": 8, "limits": {"unanswered": 0, "logit_gap": 1e-3},
}
TINY_CELL = {"name": "tiny-vgg.poisson", "config": "tiny-vgg",
             "traffic": "poisson", "chips": 1,
             "why": "a test cell at a size the CPU runs in seconds"}
TINY_TRAFFIC = {"arrivals": "poisson", "rate_rps": 1000.0}


def make_checkout(root: str) -> str:
    """Copy ``BENCHMARK.json`` and ``chipbench/`` under ``root`` and add the
    tiny configuration and cell as new files and entries.  Returns
    ``root``."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(root, "chipbench")
    with open(os.path.join(bench, "configs", "tiny-vgg.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(bench, "workloads", "tiny-vgg.poisson.json"),
              "w") as f:
        json.dump(TINY_TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-vgg", "source": TINY_CONFIG["source"],
                            "file": "chipbench/configs/tiny-vgg.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append(TINY_CELL)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"].startswith("latency_") or m.get("moves") == "latency_p50_ms":
            m["workloads"].append(TINY_CELL["name"])
    with open(path, "w") as f:
        json.dump(spec, f)
    return root
