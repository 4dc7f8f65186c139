"""Configurations, cells and metric readers are found by name, so a new
one is new files plus entries in BENCHMARK.json; and BENCHMARK.json keeps
to the shape the benchmark's contract gives it."""
import json
import os
import re

import pytest

import chipbench_testkit as tk
from chipbench.spec import Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return Spec.load(tk.REPO)


def test_every_cell_finds_its_files(spec):
    for cell in spec.data["workloads"]:
        cfg = spec.config(cell["config"])
        assert cfg["name"] == cell["config"]
        assert spec.traffic(cell["name"])["rate_rps"] > 0
        assert spec.reference(cfg).forward
        for m in spec.metrics_for(cell["name"], trace=False) + \
                spec.metrics_for(cell["name"], trace=True):
            assert callable(spec.reader(m["name"]))


def test_each_cell_reports_setup_another_end_to_end_and_a_layer(spec):
    for cell in spec.data["workloads"]:
        e2e = {m["name"] for m in spec.metrics_for(cell["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = spec.metrics_for(cell["name"], True)
        assert per_layer and all(m["moves"] in e2e for m in per_layer)


def test_a_cell_added_as_files_is_found(tmp_path):
    """A copy of the benchmark with a new configuration, cell and metric,
    each a new file plus an entry, and no file of the copy edited but
    BENCHMARK.json."""
    root = tk.make_checkout(str(tmp_path))
    with open(os.path.join(root, "chipbench", "metrics", "served_count.py"),
              "w") as f:
        f.write("def read(rec):\n    return 7.0\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    data["per_layer"].append({
        "name": "served_count.lat", "unit": "req", "better": "higher",
        "source": "program_counter", "layer": "serving loop",
        "moves": "latency_p50_ms", "workloads": ["tiny-vgg.poisson"]})
    with open(path, "w") as f:
        json.dump(data, f)
    spec = Spec.load(root)
    assert spec.cell("tiny-vgg.poisson")["config"] == "tiny-vgg"
    assert spec.config("tiny-vgg")["cut"] == 2
    assert spec.traffic("tiny-vgg.poisson")["arrivals"] == "poisson"
    names = [m["name"] for m in spec.metrics_for("tiny-vgg.poisson", True)]
    assert "served_count.lat" in names and "step_wall_ms_p50.lat" in names
    assert spec.reader("served_count.lat")(None) == 7.0
    with pytest.raises(KeyError):
        spec.cell("no-such-cell")


def test_unknown_device_kind_is_an_error(spec):
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_benchmark_json_shape(spec):
    d = spec.data
    assert set(d) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert d["command"][1].startswith(d["paths"][0] + "/")
    assert 1 <= d["run_seconds"] <= 51
    for p in d["paths"]:
        assert os.path.isdir(os.path.join(tk.REPO, p)) and not p.startswith("/")
    files = set()
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"] not in files
        assert c["file"].startswith(tuple(p + "/" for p in d["paths"]))
        files.add(c["file"])
    used = {w["config"] for w in d["workloads"]}
    assert used == {c["name"] for c in d["configs"]}
    pairs = set()
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    names = [m["name"] for m in d["end_to_end"] + d["per_layer"]]
    assert len(names) == len(set(names))
    for m in d["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in d["end_to_end"])
    for m in d["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in d["end_to_end"] + d["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
