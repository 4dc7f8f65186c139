"""The control, the plain reference computed in bfloat16 in the
program's place, fails the comparison that the sound program passes."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_testkit as tk
from chipbench import compare
from chipbench.harness import reference_outputs, run_cell, setup
from chipbench.spec import Spec


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return Spec.load(tk.make_checkout(str(tmp_path_factory.mktemp("co"))))


def limits():
    """The limits of the benchmark's own configurations."""
    out = []
    for name in ("vgg16-block2pool", "vgg16-block5pool"):
        with open(os.path.join(tk.REPO, "chipbench", "configs",
                               f"{name}.json")) as f:
            out.append(json.load(f)["limits"]["logit_gap"])
    return out


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
def test_control_fails_where_the_program_passes(spec, seed):
    cfg = spec.config("tiny-vgg")
    served = setup(spec, cfg, seed)
    ref = reference_outputs(spec, cfg, served)[0]
    ctl = reference_outputs(spec, cfg, served, dtype=jnp.bfloat16)[0]
    answers = {i: ctl[i] for i in range(cfg["frame_pool"])}
    pick = np.arange(cfg["frame_pool"])
    control_gap = compare.logit_gaps(answers, pick, ref).max()
    r = run_cell(spec, "tiny-vgg.poisson", seed, 0.3, False,
                 require_tpu=False, compile_cache=False, log=lambda m: None)
    program_gap = r["checks"]["logit_gap"]["value"]
    assert r["correct"]
    for limit in limits() + [cfg["limits"]["logit_gap"]]:
        assert program_gap <= limit < control_gap
