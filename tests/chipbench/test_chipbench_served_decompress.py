"""``bottleneck_decompress_roofline`` reads the ae8 decode kernel where
the tail server's program runs it (inside ``jit__lambda``, one call per
step over the whole pool) and reads nothing in a trace whose decode ran
in ``jit__decode_jit`` per frame, as the recorded fixture's did."""
import json
import os
from types import SimpleNamespace

import pytest

import chipbench_testkit
from chipbench import flops, trace_reduce
from chipbench.spec import Spec

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "b5pool_window.xplane.pb")
METRIC = "bottleneck_decompress_roofline.lat"


@pytest.fixture(scope="module")
def spec():
    return Spec.load(chipbench_testkit.REPO)


def _record(spec, trace, config="vgg16-block5pool"):
    path = os.path.join(spec.bench_dir, "configs", f"{config}.json")
    with open(path) as f:
        cfg = json.load(f)
    return SimpleNamespace(trace=trace, cfg=cfg,
                           peaks=spec.peaks("TPU v5 lite"))


def _summary(ops):
    return trace_reduce.TraceSummary(window_s=1.0, busy_s=0.5, n_devices=1,
                                     ops=ops)


@pytest.mark.parametrize("config", ["vgg16-block2pool", "vgg16-block5pool"])
def test_one_call_per_step_over_the_pool(spec, config):
    rec = _record(spec, None, config)
    images = rec.cfg["n_slots"] * rec.cfg["client_batch"]
    least = flops.least_seconds(*flops.decode_cost(rec.cfg, images),
                                rec.peaks)
    rec.trace = _summary({
        ("jit__lambda", "bottleneck_decompress.1"): [4, 8 * least],
        ("jit__lambda", "fusion.3"): [4, 1.0],
        ("jit__decode_jit", "bottleneck_decompress"): [9, 1e-6]})
    assert spec.reader(METRIC)(rec) == pytest.approx(50.0)


def test_reads_nothing_without_the_kernel_in_the_served_program(spec):
    read = spec.reader(METRIC)
    assert read(_record(spec, None)) is None
    assert read(_record(spec, _summary(
        {("jit__lambda", "fusion.3"): [4, 1.0]}))) is None


def test_reads_nothing_where_each_frame_had_its_own_decode(spec):
    """The recorded fixture decoded per frame in ``jit__decode_jit``:
    ``decompress_roofline`` reads it, this metric does not."""
    from jax.profiler import ProfileData
    rec = _record(spec, trace_reduce.summarize(ProfileData.from_file(FIXTURE)))
    assert spec.reader(METRIC)(rec) is None
    assert spec.reader("decompress_roofline.lat")(rec) > 0
