"""The reduction from a profiler trace to busy time, program and kernel
time, and idle gaps, checked on a trace recorded on a TPU v5e.

The fixture is ``fixtures/b5pool_window.xplane.pb``: 0.2 s of the
``vgg16-b5pool`` server under Poisson arrivals at 300 req/s, recorded
with ``python3 chipbench/record_trace.py --workload vgg16-b5pool.poisson
--seed 5 --seconds 0.2 --rate 300 --out <dir>`` on one chip.

The expected numbers were worked out apart from ``trace_reduce``: from
the trace-viewer JSON that the profiler wrote beside the ``.xplane.pb``
(``*.trace.json.gz``: the same events, in microseconds, under other
names), with a sweep over the sorted start and end points of the
device's "XLA Ops" events, clipped to the span from the first host
annotation's start to the last one's end; program runs and kernel calls
by counting that JSON's events by name.  The step and request counts
come from the host side of the recording: 23 ``serve_step`` annotations
(the recording's 24th step came in the drain, after the profiler
stopped), each running the tail program once, and 55 requests, each
running one decode.
"""
import os

import pytest

import chipbench_testkit  # noqa: F401
from chipbench import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                       "b5pool_window.xplane.pb")

# from the trace-viewer JSON (see the module's docstring)
WINDOW_S = 0.203613385
BUSY_S = 0.008788647
STEPS = 23            # serve_step annotations == tail program runs
REQUESTS = 55         # decode programs == decompress kernel calls
KERNEL_S = 7.4125e-05
TAIL_S = 0.007903206


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return trace_reduce.summarize(ProfileData.from_file(FIXTURE))


def test_window_and_busy_time(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(WINDOW_S, rel=1e-6)
    assert summary.busy_s == pytest.approx(BUSY_S, rel=1e-3)
    assert summary.idle_share == pytest.approx(1 - BUSY_S / WINDOW_S, rel=1e-3)


def test_programs_and_kernel(summary):
    assert summary.module_seconds(lambda m: m == "jit__lambda") == \
        (STEPS, pytest.approx(TAIL_S, rel=1e-3))
    calls, secs = summary.op_seconds(
        lambda m, op: m == "jit__decode_jit"
        and op.split(".")[0] == "bottleneck_decompress")
    assert calls == REQUESTS
    assert secs == pytest.approx(KERNEL_S, rel=1e-3)
    assert summary.modules["jit__decode_jit"][0] == REQUESTS


def test_idle_gaps_add_up(summary):
    idle = sum(summary.idle_by_host.values())
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert set(summary.idle_by_host) <= {"serve_step", "submit",
                                        "wait_arrivals", "none"}
    gaps = [s for _, s in summary.longest_gaps]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10


def test_skip_leaves_out_the_start(summary):
    from jax.profiler import ProfileData
    later = trace_reduce.summarize(ProfileData.from_file(FIXTURE), skip_s=0.05)
    assert later.window_s == pytest.approx(summary.window_s - 0.05, abs=1e-9)
    assert later.busy_s <= summary.busy_s


def test_op_names():
    assert trace_reduce.op_name(
        "%fusion.2 = bf16[16,4096]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[16,25088]"
        "{1,0:T(8,128)(2,1)S(1)} %reshape.1), kind=kOutput") == \
        ("fusion.2", "bf16[16,4096] fusion")
    assert trace_reduce.op_name(
        "%slice-start.1 = ((bf16[4096,4096]{1,0:T(8,128)(2,1)}), bf16[1024,4096]"
        "{1,0}, s32[]{:S(2)}) slice-start(x)")[1] == \
        "((bf16[4096,4096]), bf16[1024,4096], s32[]) slice-start"
    assert trace_reduce.module_name("jit__lambda(5344685735798289724)") == \
        "jit__lambda"
