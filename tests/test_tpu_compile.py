"""Compile rehearsals of the served path for a described TPU v5e chip.

Nothing here runs on a chip.  The TPU compiler that ships with libtpu
compiles for a v5e that is described, not attached, and refuses what the
chip would refuse: a block not aligned to the tiling, or more VMEM than a
kernel may use.  The widths are the paper's VGG16 at batch 8: two conv
cuts, the fc cut (C=4096) and the flatten cut (C=25088), each with the
default 50% latent.

The topology is described only inside the module fixture: the TPU
library may be loaded by one process at a time, so a test worker loads it
only when it is handed this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bottleneck as B
from repro.kernels.bottleneck_compress import bottleneck_compress_any
from repro.kernels.bottleneck_decompress import bottleneck_decompress_any
from repro.models.vgg import vgg16
from repro.runtime import wire as W
from repro.runtime.partition import device_weights, make_partition

BATCH = 8
CUT_SHAPES = {
    "conv28x28x256": (BATCH, 28, 28, 256),
    "conv14x14x512": (BATCH, 14, 14, 512),
    "fc4096": (BATCH, 4096),
    "flatten25088": (BATCH, 25088),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs outside
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip cannot be read back from
        # the persistent cache without one; keep it out of the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _rows(shape) -> int:
    n = 1
    for d in shape[:-1]:
        n *= d
    return n


@pytest.mark.parametrize("cut", sorted(CUT_SHAPES))
def test_compress_compiles_for_v5e(one_chip, cut):
    shape = CUT_SHAPES[cut]
    c = shape[-1]
    l = B.latent_channels(c, 0.5)
    fn = jax.jit(lambda f, w, b: bottleneck_compress_any(f, w, b,
                                                         backend="kernel"))
    compiled = fn.lower(_spec(one_chip, shape), _spec(one_chip, (c, l)),
                        _spec(one_chip, (l,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cut", sorted(CUT_SHAPES))
def test_decompress_compiles_for_v5e(one_chip, cut):
    shape = CUT_SHAPES[cut]
    c = shape[-1]
    l = B.latent_channels(c, 0.5)
    fn = jax.jit(lambda q, s, w, b: bottleneck_decompress_any(
        q, s, w, b, backend="kernel"))
    compiled = fn.lower(_spec(one_chip, shape[:-1] + (l,), jnp.int8),
                        _spec(one_chip, (_rows(shape), 1)),
                        _spec(one_chip, (l, c)),
                        _spec(one_chip, (c,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_vgg16_tail_stage_compiles_for_v5e(one_chip):
    """The server's program after block4_pool at full width: the ae8 wire
    decode kernel as prologue, then VGG16's block5 and classifier, with
    the weights passed as arguments (shapes only, nothing allocated)."""
    model = vgg16()
    cut = 23                                      # block4_pool: 14x14x512
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(model.init, key)
    boundary = model.activation_shapes(params, BATCH)[cut]
    ae = jax.eval_shape(lambda k: B.init_bottleneck(k, boundary[1:], 0.5),
                        key)
    l = ae["enc"]["w"].shape[1]

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    def tail(p, ae, q, s):
        f = W.decode_arrays("ae8", q, s, ae, backend="kernel")
        return model.apply_range(p, f, cut + 1, len(model.layers))

    compiled = jax.jit(tail).lower(
        on_chip(params), on_chip(ae),
        _spec(one_chip, boundary[:-1] + (l,), jnp.int8),
        _spec(one_chip, (_rows(boundary), 1))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_vgg16_served_tail_reads_bf16_kernels_on_v5e(one_chip):
    """``Partition.served_tail`` after block5_pool at full width, its
    weights as a TPU at the default precision holds them: each kernel is
    read as its bf16 argument, and the program makes no f32 copy of it."""
    model = vgg16()
    cut = 30                                      # block5_pool: 7x7x512
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params = jax.eval_shape(model.init, key)
    boundary = model.activation_shapes(params, BATCH)[cut]
    ae = jax.eval_shape(lambda k: B.init_bottleneck(k, boundary[1:], 0.5),
                        key)
    prog = make_partition(model, params, cut, ae).served_tail("ae8")
    tail = params[prog.a:prog.b]
    weights = jax.eval_shape(lambda p: device_weights(p, "tpu", None), tail)

    def on_chip(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    l = ae["enc"]["w"].shape[1]
    text = prog.fn.lower(
        on_chip(weights), on_chip((ae,)),
        (_spec(one_chip, boundary[:-1] + (l,), jnp.int8),
         _spec(one_chip, (_rows(boundary), 1)))).compile().as_text()
    kernels = [",".join(map(str, p["w"].shape)) for p in tail if "w" in p]
    assert len(kernels) == 3
    for dims in kernels:
        assert f"bf16[{dims}]" in text
        assert f"f32[{dims}]" not in text
