"""``TailServer.step`` stages every frame on the host, uploads the pool
once and runs one program (``Partition.served_tail``: decode prologue +
tail).  Each served request must equal the per-request eager path
``part.tail(W.decode_activation(W.from_bytes(buf), ae))``, frames of
several kinds may share a step, and a frame the partition cannot serve
is refused with a ``WireError``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bottleneck as B
from repro.runtime import wire as W
from repro.runtime.engine import TailServer
from repro.runtime.partition import make_partition

N_SLOTS = 3
FILLS = {"one": 1, "full": N_SLOTS, "over": N_SLOTS + 2}


def _ae_for(model, params, cut, rate=0.5):
    shapes = model.activation_shapes(params, 1)
    return B.init_bottleneck(jax.random.PRNGKey(1), shapes[cut], rate=rate)


@pytest.fixture(scope="module")
def parts(vgg_small):
    """One partition per payload kind, all cut at the same layer."""
    model, params = vgg_small
    cut = model.cut_points()[3]
    plain = make_partition(model, params, cut)
    return {"f32": plain, "int8": plain,
            "ae8": make_partition(model, params, cut,
                                  _ae_for(model, params, cut))}


def _frame(part, x, kind):
    f = part.head(jnp.asarray(x))
    return W.to_bytes(W.encode_activation(
        f, part.ae if kind == "ae8" else None, quantize=kind != "f32"))


def _eager(part, buf):
    return np.asarray(part.tail(W.decode_activation(W.from_bytes(buf),
                                                    part.ae)))


def _client_inputs(xs, n, batch):
    return [xs[i * batch:(i + 1) * batch] for i in range(n)]


@pytest.mark.parametrize("fill", sorted(FILLS))
@pytest.mark.parametrize("client_batch", [1, 2])
@pytest.mark.parametrize("kind", ["ae8", "int8", "f32"])
def test_batched_step_equals_per_request_tail(parts, toy_data, kind,
                                              client_batch, fill):
    part = parts[kind]
    xs, _ = toy_data
    n = FILLS[fill]
    frames = [_frame(part, x, kind)
              for x in _client_inputs(xs, n, client_batch)]
    server = TailServer(part, n_slots=N_SLOTS, client_batch=client_batch)
    for cid, buf in enumerate(frames):
        server.submit(cid, buf)
    out = server.drain()
    assert sorted(out) == list(range(n))
    assert server.n_batches == -(-n // N_SLOTS)
    for cid, buf in enumerate(frames):
        assert out[cid].shape[0] == client_batch
        np.testing.assert_allclose(out[cid], _eager(part, buf), atol=1e-5)


@pytest.mark.parametrize("kind", ["ae8", "int8"])
def test_multi_cut_partition_serves_everything_after_the_first_cut(
        vgg_small, toy_data, kind):
    model, params = vgg_small
    xs, _ = toy_data
    cuts = model.cut_points()
    ae = {cuts[1]: _ae_for(model, params, cuts[1])} if kind == "ae8" \
        else None
    part = make_partition(model, params, (cuts[1], cuts[3]), ae)
    assert part.n_stages == 3
    frames = [_frame(part, x, kind) for x in _client_inputs(xs, 4, 1)]
    server = TailServer(part, n_slots=N_SLOTS)
    for cid, buf in enumerate(frames):
        server.submit(cid, buf)
    out = server.drain()
    for cid, buf in enumerate(frames):
        np.testing.assert_allclose(out[cid], _eager(part, buf), atol=1e-5)
    # it runs every stage after the first cut, not the last one alone
    x = jnp.asarray(xs[:1])
    got = part.served_tail("f32")((part.head(x), None))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(part.forward_stages(x)), atol=1e-5)


def test_step_never_calls_the_eager_decode(parts, toy_data, monkeypatch):
    part = parts["ae8"]
    xs, _ = toy_data
    frames = [_frame(part, x, "ae8") for x in _client_inputs(xs, 4, 1)]
    want = [_eager(part, buf) for buf in frames]

    def boom(*a, **kw):
        raise AssertionError("step called W.decode_activation")

    monkeypatch.setattr(W, "decode_activation", boom)
    server = TailServer(part, n_slots=N_SLOTS)
    for cid, buf in enumerate(frames):
        server.submit(cid, buf)
    out = server.drain()
    for cid in range(len(frames)):
        np.testing.assert_allclose(out[cid], want[cid], atol=1e-5)


def _refused(server, good, bad):
    """Submit ``good`` then ``bad`` into one step: the step raises, the
    bad frame leaves the pool, the good one is served by the next step."""
    server.submit(0, good)
    server.submit(1, bad)
    with pytest.raises(W.WireError) as ei:
        server.step()
    assert server.rejected == [1] and server.n_rejected == 1
    out = server.step()
    assert sorted(out) == [0]
    return str(ei.value)


def test_batch_one_frame_into_a_batch_two_slot_is_refused(parts, toy_data):
    part = parts["int8"]
    xs, _ = toy_data
    good = _frame(part, xs[:2], "int8")
    bad = _frame(part, xs[2:3], "int8")
    server = TailServer(part, n_slots=N_SLOTS, client_batch=2)
    msg = _refused(server, good, bad)
    shape = W.from_bytes(good).shape
    assert f"int8 {tuple(W.from_bytes(bad).shape)}" in msg
    assert f"int8 {tuple(shape)}" in msg


def test_frame_of_another_kind_is_refused(parts, toy_data):
    """A kind the partition cannot decode: ae8 without a bottleneck AE."""
    xs, _ = toy_data
    server = TailServer(parts["int8"], n_slots=N_SLOTS)
    msg = _refused(server, _frame(parts["int8"], xs[:1], "int8"),
                   _frame(parts["ae8"], xs[1:2], "ae8"))
    assert "no bottleneck AE" in msg


def _serve_and_check(server, part, frames, first_cid=0):
    for i, buf in enumerate(frames):
        server.submit(first_cid + i, buf)
    out = server.step()
    assert sorted(out) == list(range(first_cid, first_cid + len(frames)))
    for i, buf in enumerate(frames):
        np.testing.assert_allclose(out[first_cid + i], _eager(part, buf),
                                   atol=1e-5)


def test_frames_of_every_kind_share_one_step(parts, toy_data):
    """The codec downgrade ladder mixes kinds: one step serves them all."""
    part = parts["ae8"]
    xs, _ = toy_data
    frames = [_frame(part, xs[i:i + 1], k)
              for i, k in enumerate(["ae8", "int8", "f32"])]
    server = TailServer(part, n_slots=N_SLOTS)
    _serve_and_check(server, part, frames)
    assert server.n_batches == 1 and server.n_rejected == 0


def test_kind_switches_between_steps(parts, toy_data):
    part = parts["ae8"]
    xs, _ = toy_data
    server = TailServer(part, n_slots=N_SLOTS)
    for step, kind in enumerate(["int8", "f32", "ae8", "int8"]):
        frames = [_frame(part, xs[i:i + 1], kind) for i in range(2)]
        _serve_and_check(server, part, frames, first_cid=2 * step)
    assert server.n_batches == 4


def test_refused_first_frame_fixes_no_layout(vgg_small, parts, toy_data):
    """A stray frame from another cut, staged first, is refused and leaves
    nothing behind: frames of every kind are served after it."""
    model, params = vgg_small
    xs, _ = toy_data
    part = parts["ae8"]
    other = make_partition(model, params, model.cut_points()[1])
    server = TailServer(part, n_slots=N_SLOTS)
    server.submit(9, _frame(other, xs[:1], "f32"))
    good = [_frame(part, xs[i:i + 1], k)
            for i, k in enumerate(["int8", "ae8"])]
    for i, buf in enumerate(good):
        server.submit(i, buf)
    with pytest.raises(W.WireError, match="f32"):
        server.step()
    assert server.rejected == [9]
    out = server.step()
    assert sorted(out) == [0, 1]
    for i, buf in enumerate(good):
        np.testing.assert_allclose(out[i], _eager(part, buf), atol=1e-5)
    _serve_and_check(server, part, [_frame(part, xs[2:3], "f32")],
                     first_cid=2)


def test_unparsable_frame_leaves_the_pool(parts, toy_data):
    xs, _ = toy_data
    part = parts["int8"]
    server = TailServer(part, n_slots=N_SLOTS)
    good = _frame(part, xs[:1], "int8")
    _refused(server, good, good[:len(good) // 2])


def test_frame_from_another_cut_is_refused(vgg_small, parts, toy_data):
    model, params = vgg_small
    xs, _ = toy_data
    part = parts["int8"]
    other = make_partition(model, params, model.cut_points()[1])
    server = TailServer(part, n_slots=N_SLOTS)
    _refused(server, _frame(part, xs[:1], "int8"),
             _frame(other, xs[1:2], "int8"))


def test_ae8_frame_without_an_ae_is_refused(parts, toy_data):
    xs, _ = toy_data
    server = TailServer(parts["int8"], n_slots=N_SLOTS)
    server.submit(0, _frame(parts["ae8"], xs[:1], "ae8"))
    with pytest.raises(W.WireError, match="no bottleneck AE"):
        server.step()
    assert not server.pool.any_active() and server.rejected == [0]
