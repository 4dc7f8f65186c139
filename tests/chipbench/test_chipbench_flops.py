"""Operations and bytes of the served VGG16 path, from the configuration."""
import json
import os

import pytest

from chipbench_testkit import REPO
from chipbench import flops


def config(name):
    with open(os.path.join(REPO, "chipbench", "configs", f"{name}.json")) as f:
        return json.load(f)


B2, B5 = config("vgg16-block2pool"), config("vgg16-block5pool")


def test_vgg16_totals():
    assert flops.macs(B2) == 15_470_264_320            # 15.47 GMACs
    assert sum(r.weights for r in flops.layer_table(B2)) == 138_357_544


def test_tails_at_the_two_cuts():
    assert flops.tail_macs(B2) == pytest.approx(10.76e9, rel=1e-3)
    assert flops.tail_macs(B5) == 123_633_664         # 123.6 M MACs
    assert flops.boundary(B2) == (56 * 56, 128)
    assert flops.boundary(B5) == (7 * 7, 512)


def test_layer_table_matches_the_programs_model():
    """Same layers, shapes and mult-adds as the program's VGG builder
    and its ``core.stats`` counter."""
    import jax

    from repro.core import stats
    from repro.models.vgg import vgg16

    model = vgg16()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    rows = stats.summary(model, params, batch=1)
    ours = flops.layer_table(B2)
    assert [r.kind for r in rows] == [r.kind for r in ours]
    assert [r.output_shape[1:] for r in rows] == [r.out_shape for r in ours]
    assert [r.mult_adds for r in rows] == [r.macs for r in ours]


def test_costs_of_decode_and_tail():
    ops, nbytes = flops.decode_cost(B2)
    n, c, l = 56 * 56, 128, 64
    assert ops == n * l + 2 * n * l * c + n * c
    assert nbytes == n * l + 4 * n + 2 * l * c + 4 * c + 4 * n * c
    ops, nbytes = flops.tail_cost(B5, 16)
    assert ops == 2 * 16 * 123_633_664
    # weights at two bytes, biases, input and logits at four
    assert nbytes == (2 * 123_633_664 + 4 * (4096 + 4096 + 1000)
                      + 16 * 4 * (25088 + 1000))
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(ops, nbytes, peaks) == pytest.approx(nbytes / 819e9)
