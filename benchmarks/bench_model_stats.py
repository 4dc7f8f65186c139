"""Paper Tables I-II: the VGG16 network summary and aggregate statistics.

Exact targets from the paper: 138,357,544 params, 247.74 G mult-adds
(batch 16), 1735.26 MB forward/backward size."""
from __future__ import annotations

import json
import os

import jax

from repro.core import stats as S
from repro.models.vgg import vgg16

from .common import RESULTS_DIR


def run(fast: bool = False):
    model = vgg16()
    params = model.init(jax.random.PRNGKey(0))
    rows_tbl = S.summary(model, params, batch=16)
    t = S.totals(model, params, batch=16)
    os.makedirs(os.path.join(RESULTS_DIR, "paper"), exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "paper", "table1_2_stats.json"), "w") as f:
        json.dump({"totals": t,
                   "layers": [{"name": r.name, "kind": r.kind,
                               "shape": list(r.output_shape),
                               "params": r.n_params,
                               "mult_adds": r.mult_adds} for r in rows_tbl]},
                  f, indent=1)
    return [
        ("table2.total_params", 0.0, t["total_params"]),
        ("table2.params_match_paper", 0.0, int(t["total_params"] == 138_357_544)),
        ("table2.mult_adds_G", 0.0, round(t["mult_adds_G"], 2)),
        ("table2.fwd_bwd_MB", 0.0, round(t["fwd_bwd_MB"], 2)),
        ("table2.total_MB", 0.0, round(t["total_MB"], 2)),
    ]


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
