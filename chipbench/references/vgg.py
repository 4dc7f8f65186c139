"""Plain reference of the served VGG split, and the data it is run on.

VGG16 is config D of Simonyan & Zisserman (arXiv:1409.1556), as in
torchvision: 3x3 SAME convolutions with bias and ReLU, 2x2 max-pools,
flatten, and three linear layers with ReLUs between the first two.  The
split follows Split-Et-Impera (arXiv:2303.12524): the layers up to the
cut run on the edge, a bottleneck encoder ``relu(f @ We + be)`` projects
each spatial row of the boundary activation to ``ae_latent`` channels,
symmetric int8 quantisation with one scale per row (``amax / 127``)
makes the wire codes, and the server dequantises, applies the decoder
``z @ Wd + bd`` and runs the layers after the cut.

Written in straightforward ``jax.numpy``; it imports nothing of the
program under test.  Matmuls and convolutions run at the precision the
configuration states (``matmul_precision``): "default" is what XLA does
with float32 on a TPU, operands rounded to bfloat16 and products summed
in float32.  ``dtype=bfloat16`` computes every tensor in bfloat16: the
control, which the comparison has to reject.

``make_inputs`` makes the weights, the bottleneck and the pool of input
images from one key: the harness hands the same arrays to the program and
to this reference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.flops import boundary, layer_table

# Biases are drawn, not zero, so that a path that drops one shows.
BIAS_STD = 0.05

_PRECISION = {"default": jax.lax.Precision.DEFAULT,
              "highest": jax.lax.Precision.HIGHEST}


def make_inputs(cfg: dict, key) -> tuple:
    """``(params, ae, images)`` from ``key``; trace it under one ``jit``.

    ``params`` has one dict per layer in the program's layout (conv
    ``w`` is HWIO); the bottleneck is ``{"enc": {"w", "b"}, "dec": {"w",
    "b"}}``; ``images`` is ``(frame_pool, hw, hw, in_ch)``.
    """
    table = layer_table(cfg)
    keys = jax.random.split(key, 2 * len(table) + 5)
    params = []
    for i, row in enumerate(table):
        if not row.params:
            params.append({})
            continue
        wshape = row.params["w"]
        fan_in = math.prod(wshape[:-1])
        gain = 2.0 if row.kind == "conv" else 1.0
        params.append({
            "w": jax.random.normal(keys[2 * i], wshape, jnp.float32)
            * math.sqrt(gain / fan_in),
            "b": jax.random.normal(keys[2 * i + 1], row.params["b"],
                                   jnp.float32) * BIAS_STD})
    _, c = boundary(cfg)
    l = cfg["ae_latent"]
    k = keys[2 * len(table):]
    ae = {"enc": {"w": jax.random.normal(k[0], (c, l)) * math.sqrt(2.0 / c),
                  "b": jax.random.normal(k[1], (l,)) * BIAS_STD},
          "dec": {"w": jax.random.normal(k[2], (l, c)) * math.sqrt(1.0 / l),
                  "b": jax.random.normal(k[3], (c,)) * BIAS_STD}}
    hw = cfg["input_hw"]
    images = jax.random.normal(k[4], (cfg["frame_pool"], hw, hw,
                                      cfg["in_ch"]), jnp.float32)
    return params, ae, images


def _mm(a, w, dtype, prec):
    return jnp.matmul(a, w.astype(dtype), precision=prec,
                      preferred_element_type=dtype)


def _layer(kind: str, p: dict, x, dtype, prec):
    if kind == "conv":
        y = jax.lax.conv_general_dilated(
            x, p["w"].astype(dtype), window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=dtype)
        return y + p["b"].astype(dtype)
    if kind == "linear":
        return _mm(x, p["w"], dtype, prec) + p["b"].astype(dtype)
    if kind == "relu":
        return jnp.maximum(x, 0)
    if kind == "pool":
        return jax.lax.reduce_window(x, jnp.array(-jnp.inf, dtype), jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    if kind == "flatten":
        return x.reshape(x.shape[0], -1)
    raise ValueError(f"unknown layer kind {kind!r}")


def forward(cfg: dict, params: list, ae: dict, x, *, dtype=jnp.float32):
    """Images -> ``(logits f32, codes int8, row scales f32)`` of the served
    split.  Codes are ``(B, *spatial, L)``, scales ``(B, *spatial, 1)``."""
    prec = _PRECISION[cfg["matmul_precision"]]
    table = layer_table(cfg)
    cut = cfg["cut"]
    h = x.astype(dtype)
    for row, p in zip(table[:cut + 1], params[:cut + 1]):
        h = _layer(row.kind, p, h, dtype, prec)
    z = jnp.maximum(_mm(h, ae["enc"]["w"], dtype, prec)
                    + ae["enc"]["b"].astype(dtype), 0)
    amax = jnp.max(jnp.abs(z), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / jnp.array(127.0, dtype),
                  jnp.array(1.0, dtype))
    q = jnp.clip(jnp.round(z / s), -127, 127)
    h = _mm(q * s, ae["dec"]["w"], dtype, prec) + ae["dec"]["b"].astype(dtype)
    for row, p in zip(table[cut + 1:], params[cut + 1:]):
        h = _layer(row.kind, p, h, dtype, prec)
    return h.astype(jnp.float32), q.astype(jnp.int8), s.astype(jnp.float32)
