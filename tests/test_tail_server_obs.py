"""``TailServer`` telemetry: the ``server.*`` span tree of each serving
step, the frames-staged counter, the queue-wait and queue-depth entries, the profiler hook of
``Tracer.span``, and the null recorder's silence."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import NULL, Recorder, Tracer
from repro.runtime import wire as W
from repro.runtime.engine import TailServer
from repro.runtime.partition import make_partition

CLIENTS = [10, 11, 12, 13, 14]
STEP_PARTS = ["server.admit", "server.inputs", "server.tail", "server.fetch"]
FRAME_PARTS = ["server.parse", "server.stage"]


class FakeAnnotate:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))
                return False

        return _Ann()


def _frames(model, params, cut, xs):
    part = make_partition(model, params, cut)
    return part, [W.to_bytes(W.encode_activation(part.head(jnp.asarray(x))))
                  for x in xs]


def _serve(part, frames, obs=None):
    server = TailServer(part, n_slots=2, obs=obs)
    for cid, buf in zip(CLIENTS, frames):
        server.submit(cid, buf)
    return server, server.drain()


@pytest.fixture(scope="module")
def served(vgg_small, toy_data):
    model, params = vgg_small
    xs, _ = toy_data
    part, frames = _frames(model, params, model.cut_points()[3],
                           [xs[i:i + 1] for i in range(len(CLIENTS))])
    annotate = FakeAnnotate()
    rec = Recorder(annotate=annotate)
    server, out = _serve(part, frames, rec)
    return part, frames, rec, annotate, server, out


def _steps(rec):
    return [s for s in rec.tracer.spans if s.name == "server.step"]


def _nested_in_order(parent, children):
    t = parent.t0
    for c in children:
        assert c.t0 >= t and c.t1 >= c.t0
        t = c.t1
    assert t <= parent.t1


def test_each_step_holds_admit_inputs_tail_fetch(served):
    _, _, rec, _, server, out = served
    assert sorted(out) == CLIENTS
    steps = _steps(rec)
    assert len(steps) == server.n_batches == 3      # 5 requests, 2 slots
    for s in steps:
        assert s.cat == "runtime" and s.tid == TailServer.TRACK
        assert [c.name for c in s.children] == STEP_PARTS
        _nested_in_order(s, s.children)
    assert [s.args["admitted"] for s in steps] == [2, 2, 1]
    assert [s.args["queued"] for s in steps] == [3, 1, 0]
    for a, b in zip(steps, steps[1:]):
        assert a.t1 <= b.t0


def test_inputs_hold_one_frame_per_admitted_request(served):
    _, _, rec, _, _, _ = served
    rids = []
    for s in _steps(rec):
        inputs = s.children[1]
        frames = inputs.children[:-1]
        assert len(frames) == s.args["admitted"]
        assert {f.name for f in frames} == {"server.frame"}
        _nested_in_order(inputs, inputs.children)
        for f in frames:
            assert [c.name for c in f.children] == FRAME_PARTS
            _nested_in_order(f, f.children)
        assert [f.args["slot"] for f in frames] == list(range(len(frames)))
        rids += [f.args["rid"] for f in frames]
    assert rids == CLIENTS


def test_inputs_end_with_one_upload_of_the_pool(served):
    part, frames, rec, _, server, _ = served
    pkt = W.from_bytes(frames[0])
    pool_bytes = 2 * (pkt.data.nbytes + pkt.scales.nbytes)
    for s in _steps(rec):
        upload = s.children[1].children[-1]
        assert upload.name == "server.upload" and not upload.children
        assert upload.args == {"frames": s.args["admitted"],
                               "bytes": pool_bytes}


def test_queue_wait_and_depth(served):
    _, _, rec, _, _, _ = served
    wait = rec.metrics.get("runtime.queue_wait_s")
    assert wait.n == len(CLIENTS) and wait.vmin >= 0.0
    # the last request waited through the two steps before its own
    steps = _steps(rec)
    assert wait.vmax >= steps[1].t1 - steps[0].t0 - 1e-9
    assert rec.metrics.get("runtime.queue_depth").value == 0.0


def test_submit_and_admit_stamped_on_the_recorder_clock(served):
    part, frames, _, _, _, _ = served
    rec = Recorder()
    server = TailServer(part, n_slots=2, obs=rec)
    server.submit(7, frames[0])
    req = server.queue[0]
    assert 0.0 < req.t_submit <= rec.tracer.wall_now()
    server.step()
    assert req.t_admit >= req.t_submit
    assert rec.metrics.get("runtime.queue_wait_s").total == \
        pytest.approx(req.t_admit - req.t_submit)


def test_annotations_enter_and_exit_in_nesting_order(served):
    _, _, rec, annotate, _, _ = served
    log = annotate.log
    stack = []
    for kind, name in log:
        if kind == "enter":
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    # one enter per recorded span, in the spans' pre-order
    entered = [name for kind, name in log if kind == "enter"]
    assert entered == [s.name for s in rec.tracer.spans]
    assert entered.count("server.frame") == len(CLIENTS)


def test_add_and_instant_never_annotate():
    annotate = FakeAnnotate()
    tr = Tracer(annotate=annotate)
    tr.add("sim", 0.0, 1.0)
    tr.instant("mark", 2.0, clock="wall")
    assert annotate.log == []
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    assert annotate.log == [("enter", "outer"), ("enter", "inner"),
                            ("exit", "inner"), ("exit", "outer")]


def test_null_default_serves_the_same_logits_and_records_nothing(served):
    part, frames, _, _, _, recorded = served
    server, out = _serve(part, frames)
    assert server.obs is NULL
    assert sorted(out) == CLIENTS
    for cid in CLIENTS:
        np.testing.assert_array_equal(out[cid], recorded[cid])
    assert NULL.tracer.spans == () and NULL.metrics.names() == []
    assert server.n_batches == 3 and server.occupancy == [2, 2, 1]


def test_null_span_is_shared():
    from repro.obs import _NULL_SPAN
    assert NULL.tracer.span("server.frame", tid="t", cat="runtime") \
        is _NULL_SPAN


def test_study_serves_through_its_recorder():
    import jax

    from repro.api import Study
    study = Study("vgg16", seed=0)
    plain = study.deploy(candidate="SC@8", serve=True)
    assert plain.obs is NULL
    report = study.observe()
    assert report.recorder.tracer.annotate is jax.profiler.TraceAnnotation
    server = study.deploy(candidate="SC@8", serve=True)
    assert server.obs is report.recorder


def test_obs_imports_without_jax():
    import repro
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    code = ("import sys; import repro.obs; "
            "assert 'jax' not in sys.modules, 'repro.obs imported jax'")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=src))
