"""``Partition``'s programs take their layers' weights as jit arguments:
no weight is compiled in as a constant, only the boundary is donated,
and the weights are held on the device as the matmuls consume them
(``device_weights``: bf16 kernels where a TPU rounds to bf16, else the
f32 parameters)."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bottleneck as B
from repro.runtime import partition as P
from repro.runtime import wire as W
from repro.runtime.partition import device_weights, make_partition

_CONST = re.compile(r"stablehlo\.constant .*: tensor<([0-9x]*)[a-z]")


def _ae_partition(model, params, n_cuts: int = 1):
    cuts = model.cut_points()
    splits = (cuts[3],) if n_cuts == 1 else (cuts[1], cuts[3])
    ae = B.init_bottleneck(jax.random.PRNGKey(1),
                           model.activation_shapes(params, 1)[splits[0]],
                           rate=0.5)
    return make_partition(model, params, splits, ae)


def _boundary(part, x):
    """The ae8 wire tensors of ``x`` at the first cut, on the device."""
    return jax.jit(W.encode_arrays)(part.head(x), part.ae)


def _scaled(params, k):
    return jax.tree.map(lambda v: v * k, params)


def _lowered(model, params, which: str, x):
    if which == "multi_cut_tail":
        part = _ae_partition(model, params, n_cuts=2)
        return part._tail.lower(part.head(x))
    part = _ae_partition(model, params)
    if which == "served_tail":
        return part.served_tail("ae8").lower(_boundary(part, x))
    if which == "head":
        return part.stage(0).lower(x)
    return part.stage(1).lower(part.head(x))


def _largest_constant(text: str) -> int:
    sizes = [math.prod(int(d) for d in dims.split("x") if d)
             for dims in _CONST.findall(text)]
    return max(sizes, default=0)


@pytest.mark.parametrize("which", ["served_tail", "stage", "multi_cut_tail",
                                   "head"])
def test_no_weight_is_compiled_in(vgg_small, toy_data, which):
    model, params = vgg_small
    x = jnp.asarray(toy_data[0][:2])
    smallest_kernel = min(int(np.prod(p["w"].shape)) for p in params
                          if "w" in p)
    text = _lowered(model, params, which, x).as_text()
    assert _largest_constant(text) < smallest_kernel
    rescaled = _lowered(model, _scaled(params, 3.0), which, x).as_text()
    assert len(rescaled) == len(text)
    assert rescaled == text


def test_served_tail_donates_the_boundary_only(vgg_small, toy_data,
                                               monkeypatch):
    """Donation requested (as off the CPU): only the boundary's buffers
    are donated, and two calls in a row, each on a fresh boundary, give
    equal logits with every weight still alive."""
    monkeypatch.setattr(P, "_donate", lambda argnum: (argnum,))
    model, params = vgg_small
    part = _ae_partition(model, params)
    x = jnp.asarray(toy_data[0][:2])
    prog = part.served_tail("ae8")
    (w_info, ae_info, bd_info), _ = prog.lower(_boundary(part, x)).args_info
    assert not any(a.donated for a in jax.tree.leaves(w_info))
    assert not any(a.donated for a in jax.tree.leaves(ae_info))
    assert all(a.donated for a in jax.tree.leaves(bd_info))
    first = np.asarray(prog(_boundary(part, x)))
    second = np.asarray(prog(_boundary(part, x)))
    np.testing.assert_array_equal(first, second)
    live = jax.tree.leaves(part._weights()) + jax.tree.leaves(part.ae)
    assert live and not any(a.is_deleted() for a in live)


@pytest.mark.parametrize("platform,precision,rounds", [
    ("cpu", None, False),
    ("cpu", "bfloat16", False),
    ("tpu", "highest", False),
    ("tpu", "float32", False),
    ("tpu", "high", False),
    ("tpu", None, True),
    ("tpu", "default", True),
    ("tpu", "bfloat16", True),
])
def test_device_weights_follow_the_precision(vgg_small, platform, precision,
                                             rounds):
    _, params = vgg_small
    got = device_weights(params, platform, precision)
    if not rounds:
        assert got is params
        return
    assert len(got) == len(params)
    for g, p in zip(got, params):
        assert g.keys() == p.keys()
        if "w" in p:
            assert g["w"].dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(g["w"]), np.asarray(p["w"].astype(jnp.bfloat16)))
            assert g["b"].dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(g["b"]),
                                          np.asarray(p["b"]))


def test_bf16_copy_upcasts_exactly(vgg_small, toy_data):
    """The program casts the bf16 kernels back to f32 exactly: a
    partition holding the bf16 copy gives the logits of one whose f32
    kernels were rounded to bf16 beforehand."""
    model, params = vgg_small
    x = jnp.asarray(toy_data[0][:2])
    rounded = [{k: v.astype(jnp.bfloat16).astype(jnp.float32)
                if k == "w" else v for k, v in p.items()} for p in params]
    cut = model.cut_points()[3]
    want = np.asarray(make_partition(model, rounded, cut).full(x))
    part = make_partition(model, params, cut)
    part._platform = "tpu"              # hold the copy a TPU would
    assert part._weights()[0]["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(part.full(x)), want)


def test_served_tail_under_highest_equals_tail_of_decode(vgg_small, toy_data):
    model, params = vgg_small
    part = _ae_partition(model, params)
    x = jnp.asarray(toy_data[0][:2])
    buf = W.to_bytes(W.encode_activation(part.head(x), part.ae))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(part.tail(W.decode_activation(W.from_bytes(buf),
                                                        part.ae)))
        pkt = W.from_bytes(buf)
        got = np.asarray(part.served_tail("ae8")(
            (jnp.asarray(pkt.data), jnp.asarray(pkt.scales))))
    np.testing.assert_allclose(got, want, atol=1e-5)
