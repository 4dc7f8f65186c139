"""The reduction of the server's own spans in a profiler trace: on
hand-made events; on a trace of a program without those spans (the
``trace_reduce`` fixture), where every per-layer number is ``None``; and
on a trace recorded on a TPU v5e with the spans on.

That fixture is ``fixtures/b5pool_spans.xplane.pb``: 0.2 s of the
``vgg16-b5pool`` server under Poisson arrivals at 300 req/s, recorded with
``python3 chipbench/record_spans.py --workload vgg16-b5pool.poisson
--seed 5 --seconds 0.2 --rate 300 --out <dir>`` on one chip.  Its
expected numbers were worked out apart from both reducers, from the
trace-viewer JSON the profiler wrote beside it (microseconds, three
decimals): a sweep over the sorted start and end points of the "XLA Ops"
events, clipped to the span from the first harness annotation's start to
the last one's end, gives the idle gaps; a gap counts inside a span name
when some event of that name on the serving thread holds its middle;
spans, frames per ``server.inputs`` and program runs are counted among
the events wholly inside that span.

Hand-made timeline, in microseconds (one device, window 100-1010):

    host   serve_step 100-900 > server.step 110-890 >
             admit 110-120, inputs 120-520 > frame 120-320 (parse 120-150,
             decode 150-250, scatter 250-320), frame 320-520 (parse
             320-350, decode 350-450, scatter 450-520); tail 520-560;
             fetch 560-890
           wait_arrivals 900-960, submit 1000-1010
    device 50-130, 200-240, 410-440, 560-800, 970-985 (one program each)

Idle gaps (middle -> innermost span): 130-200 (165, decode), 240-410
(325, parse), 440-560 (500, scatter), 800-970 (885, fetch), 985-1010
(997, none): 555 us idle in a 910 us window, 355 us busy.
"""
import os

import pytest

import chipbench_testkit  # noqa: F401
from chipbench import span_reduce, trace_reduce

US = 1000          # ns

HOST = [(n, s * US, e * US) for n, s, e in [
    ("serve_step", 100, 900), ("server.step", 110, 890),
    ("server.admit", 110, 120), ("server.inputs", 120, 520),
    ("server.frame", 120, 320), ("server.parse", 120, 150),
    ("server.decode", 150, 250), ("server.scatter", 250, 320),
    ("server.frame", 320, 520), ("server.parse", 320, 350),
    ("server.decode", 350, 450), ("server.scatter", 450, 520),
    ("server.tail", 520, 560), ("server.fetch", 560, 890),
    ("wait_arrivals", 900, 960), ("submit", 1000, 1010),
    ("PjitFunction(_decode_jit)", 160, 170),     # not a span of ours
]]
OPS = [("op", s * US, e * US) for s, e in
       [(50, 130), (200, 240), (410, 440), (560, 800), (970, 985)]]
STEP = ("serve_step", "server.step")
FRAME = STEP + ("server.inputs", "server.frame")

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
WINDOW_FIXTURE = os.path.join(FIXTURES, "b5pool_window.xplane.pb")
SPANS_FIXTURE = os.path.join(FIXTURES, "b5pool_spans.xplane.pb")

# from the trace-viewer JSON (see the module's docstring)
WINDOW_S = 0.217174635
BUSY_S = 0.009200473
IDLE_IN_SERVE_STEP_S = 0.195416005
IDLE_IN_INPUTS_S = 0.137626488
STEPS = 24            # server.step, each inside one serve_step
FRAMES = 60
PROGRAMS = 396
FETCH_MS_P50 = 1.051975
INPUTS_MS_PER_REQUEST_P50 = 2.7967875


@pytest.fixture(scope="module")
def hand():
    return span_reduce.reduce_events(HOST, [(OPS, OPS)])


def test_window_busy_and_programs(hand):
    assert hand.window_s == pytest.approx(910e-6)
    assert hand.busy_s == pytest.approx(355e-6)
    assert hand.programs == 4          # the one from 50 us starts outside


def test_idle_goes_to_the_innermost_span(hand):
    want = {FRAME + ("server.decode",): 70e-6,
            FRAME + ("server.parse",): 170e-6,
            FRAME + ("server.scatter",): 120e-6,
            STEP + ("server.fetch",): 170e-6,
            (): 25e-6}
    assert hand.idle_by_path.keys() == want.keys()
    for path, s in want.items():
        assert hand.idle_by_path[path] == pytest.approx(s)
    assert hand.idle_within("server.inputs") == pytest.approx(360e-6)
    assert hand.idle_within("serve_step") == pytest.approx(530e-6)


def test_attributed_and_unattributed_idle_add_up(hand):
    inside = sum(s for p, s in hand.idle_by_path.items() if p)
    assert inside + hand.idle_by_path[()] == \
        pytest.approx(hand.window_s - hand.busy_s, rel=1e-9)


def test_per_request_readings(hand):
    r = hand.readings()
    assert r["inputs_ms_per_request_p50"] == pytest.approx(0.2)  # 400 us / 2
    assert r["fetch_ms_p50"] == pytest.approx(0.33)
    assert r["idle_in_inputs_share"] == pytest.approx(100 * 360 / 910)
    assert r["programs_per_request"] == pytest.approx(2.0)


def test_spans_reaching_into_the_window_nest_but_do_not_count():
    later = span_reduce.reduce_events(HOST, [(OPS, OPS)], skip_s=50e-6)
    assert later.window_s == pytest.approx(860e-6)
    # the step and its inputs began before the window: not timed, but the
    # first gap (now 150-200) still lands in the first frame's decode
    assert later.spans("server.step") == []
    assert later.inputs_ms_per_request_p50() is None
    assert later.fetch_ms_p50() == pytest.approx(0.33)
    assert later.idle_by_path[FRAME + ("server.decode",)] == \
        pytest.approx(50e-6)


def test_nest_and_innermost_at_shared_edges():
    order, parents = span_reduce.nest([("c", 5, 10), ("a", 0, 10),
                                       ("b", 0, 5)])
    assert [s[0] for s in order] == ["a", "b", "c"]
    assert parents == [-1, 0, 0]
    starts = [s[1] for s in order]
    # a shared edge goes to the span that starts there, as trace_reduce does
    assert span_reduce.innermost(order, parents, starts, 5) == 2
    assert span_reduce.innermost(order, parents, starts, 3) == 1
    assert span_reduce.innermost(order, parents, starts, 11) == -1


def test_a_trace_without_server_spans_reads_none():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(WINDOW_FIXTURE)
    spans = span_reduce.summarize(pd)
    host = trace_reduce.summarize(pd)
    assert set(spans.readings().values()) == {None}
    assert spans.window_s == host.window_s
    assert spans.busy_s == pytest.approx(host.busy_s, rel=1e-9)
    assert spans.programs == sum(c for c, _ in host.modules.values())
    assert {(p[0] if p else "none"): s
            for p, s in spans.idle_by_path.items()} == \
        pytest.approx(host.idle_by_host, rel=1e-9)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(SPANS_FIXTURE)
    return pd, span_reduce.summarize(pd), trace_reduce.summarize(pd)


def test_every_span_lies_in_a_serve_step_on_one_host_line(recorded):
    pd, spans, _ = recorded
    ours = [[ev for ev in trace_reduce._events(line)
             if ev[0].startswith(span_reduce.PREFIX)
             or ev[0] == "serve_step"]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines]
    ours = [evs for evs in ours if evs]
    assert len(ours) == 1
    harness = [ev for ev in ours[0] if ev[0] == "serve_step"]
    servers = [ev for ev in ours[0] if ev[0] != "serve_step"]
    assert sum(ev[0] == "server.step" for ev in servers) == STEPS
    for _, s, e in servers:
        assert any(hs <= s and e <= he for _, hs, he in harness)
    for n in spans.spans("server.step"):
        assert spans.nodes[n.parent].name == "serve_step"


def test_idle_in_serve_step_is_trace_reduces(recorded):
    _, spans, host = recorded
    assert spans.window_s == pytest.approx(WINDOW_S, rel=1e-6)
    assert spans.busy_s == pytest.approx(BUSY_S, rel=1e-3)
    assert spans.idle_within("serve_step") == \
        pytest.approx(host.idle_by_host["serve_step"], rel=1e-6)
    assert spans.idle_within("serve_step") == \
        pytest.approx(IDLE_IN_SERVE_STEP_S, rel=1e-5)
    assert spans.idle_within("server.inputs") == \
        pytest.approx(IDLE_IN_INPUTS_S, rel=1e-5)


def test_recorded_readings(recorded):
    _, spans, _ = recorded
    assert len(spans.spans("server.frame")) == FRAMES
    assert spans.programs == PROGRAMS
    r = spans.readings()
    assert r["inputs_ms_per_request_p50"] == \
        pytest.approx(INPUTS_MS_PER_REQUEST_P50, rel=1e-6)
    assert r["fetch_ms_p50"] == pytest.approx(FETCH_MS_P50, rel=1e-6)
    assert r["idle_in_inputs_share"] == \
        pytest.approx(100 * IDLE_IN_INPUTS_S / WINDOW_S, rel=1e-5)
    assert r["programs_per_request"] == pytest.approx(PROGRAMS / FRAMES)
