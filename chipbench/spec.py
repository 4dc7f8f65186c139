"""Find the benchmark's parts by name, starting from ``BENCHMARK.json``.

Nothing here knows a particular configuration, cell or metric: a new one
is a new file plus its entry in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Spec:
    """``BENCHMARK.json`` of the checkout at ``root``, and lookups by name."""
    root: str
    data: dict

    @classmethod
    def load(cls, root: str = CHECKOUT) -> "Spec":
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            return cls(root, json.load(f))

    @property
    def bench_dir(self) -> str:
        return os.path.join(self.root, "chipbench")

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.data["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, cell: str) -> dict:
        """The cell's traffic parameters: ``workloads/<cell>.json``."""
        with open(os.path.join(self.bench_dir, "workloads", f"{cell}.json")) as f:
            return json.load(f)

    def metrics_for(self, cell: str, trace: bool) -> list:
        """The metric entries a run of ``cell`` reports: its end-to-end
        metrics untraced, its per-layer metrics traced.  A per-layer
        metric without a ``workloads`` key goes to every cell that reports
        the end-to-end metric it moves."""
        e2e = [m for m in self.data["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]

    def reader(self, metric: str):
        """The ``read(record)`` function of ``metrics/<metric>.py``, or of
        ``metrics/<family>.py`` for a metric named ``<family>.<suffix>``."""
        base = os.path.join(self.bench_dir, "metrics")
        for stem in (metric, metric.split(".", 1)[0]):
            path = os.path.join(base, f"{stem}.py")
            if os.path.exists(path):
                return _load_module(path, f"chipbench_metric_{stem}").read
        raise FileNotFoundError(f"no reader for metric {metric!r} in {base}")

    def reference(self, cfg: dict):
        """The plain reference module named by a configuration."""
        path = os.path.join(self.bench_dir, "references",
                            f"{cfg['reference']}.py")
        return _load_module(path, f"chipbench_reference_{cfg['reference']}")

    def peaks(self, device_kind: str) -> dict:
        """Published peaks of one chip of ``device_kind``; a kind missing
        from ``peaks.json`` is an error, never a default."""
        with open(os.path.join(self.bench_dir, "peaks.json")) as f:
            table = json.load(f)["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                           f"({', '.join(table)})")
        return table[device_kind]
