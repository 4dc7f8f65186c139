"""Pallas TPU kernel for the split-point wire decompression — the
stage-prologue mirror of ``bottleneck_compress``.

On the receiving stage the int8 wire payload must become the boundary
activation again: dequantise (per-row scale) and apply the bottleneck
AE-decoder projection.  Run eagerly that is two dispatches with an f32
latent round-tripping through HBM between them; fused, the latent lives
only in VMEM and the kernel writes the reconstructed activation directly
— which lets ``runtime.partition`` compose it with the next stage's
layers into one jitted callable (decode as the stage prologue).

Grid: (n_tiles, c_tiles) over the *output* (N, C); the contraction over
the latent L is undercomplete by construction (L = rate * C, rate <= 1)
so a whole (L, bc) decoder slab fits in VMEM and each block is one
dequant + one MXU matmul — no accumulation scratch needed.  Tiles are
MXU-aligned (128).

Validated against ``ref.bottleneck_decode_ref`` in interpret mode; the
backend contract (auto -> kernel on TPU, pure-JAX ref elsewhere) is
shared with the compress side via ``bottleneck_compress.resolve_backend``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bottleneck_compress import (_LANE, _compiler_params, _fit_lane_block,
                                  _pad_to, resolve_backend)


def _kernel(q_ref, s_ref, w_ref, b_ref, o_ref):
    z = q_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    o_ref[...] = (jax.lax.dot(z, w_ref[...].astype(jnp.float32))
                  + b_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("bn", "bc", "interpret"))
def bottleneck_decompress(q: jax.Array, s: jax.Array, w: jax.Array,
                          b: jax.Array, *, bn: int = 128, bc: int = 512,
                          interpret: bool = False) -> jax.Array:
    """q: (N, L) int8 codes; s: (N, 1) f32 row scales; w: (L, C); b: (C,).

    Returns the reconstructed f32 boundary activation (N, C).  The output
    block ``bc`` shrinks for wide latents so the double-buffered
    ``(L, bc)`` decoder slab fits VMEM.
    """
    n, l = q.shape
    c = w.shape[1]
    lp = _pad_to(l, _LANE)

    def vmem_bytes(bc_):
        return (2 * bn_ * lp + 2 * bn_ * _LANE * 4 + 2 * _pad_to(l, 8) * bc_ * 4
                + 2 * 8 * bc_ * 4 + 2 * bn_ * bc_ * 4 + bn_ * (lp + bc_) * 4)

    bn_ = min(bn, n)
    bc_ = _fit_lane_block(min(bc, c), vmem_bytes)
    assert n % bn_ == 0 and c % bc_ == 0
    nn, nc = n // bn_, c // bc_

    return pl.pallas_call(
        _kernel,
        grid=(nn, nc),
        in_specs=[
            pl.BlockSpec((bn_, l), lambda i, j: (i, 0)),
            pl.BlockSpec((bn_, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((l, bc_), lambda i, j: (0, j)),
            pl.BlockSpec((1, bc_), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn_, bc_), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.float32),
        compiler_params=_compiler_params(("parallel", "parallel"),
                                         vmem_bytes(bc_)),
        interpret=interpret,
    )(q, s, w, b.reshape(1, c))


def bottleneck_decompress_any(q: jax.Array, s: jax.Array, w: jax.Array,
                              b: jax.Array, *, backend: str | None = None,
                              bn: int = 128, bc: int = 512) -> jax.Array:
    """Shape-flexible, backend-routed decode: the runtime's entry point.

    Accepts codes with any leading dims ``(..., L)`` and scales
    ``(..., 1)``; pads N up to the kernel's row-tile multiple (zero rows
    decode to the bias and are dropped) and the output channels C up to
    the lane tile (extra decoder columns are zero and sliced off), and
    routes per :func:`resolve_backend` — the Pallas kernel on TPU, the
    jnp reference otherwise — so the exact same activation is
    reconstructed on every host.

    Returns the boundary activation f32 ``(..., C)``.
    """
    from . import ref as _ref

    lead = q.shape[:-1]
    l = q.shape[-1]
    c = w.shape[1]
    q2 = q.reshape(-1, l)
    s2 = s.reshape(-1, 1)
    n = q2.shape[0]
    mode = resolve_backend(backend)
    if mode == "ref":
        f = _ref.bottleneck_decode_ref(q2, s2, w, b)
    else:
        np_ = _pad_to(n, bn) if n > bn and n % bn else n
        cp = _pad_to(c, bc) if c > bc and c % bc else c
        qp = jnp.zeros((np_, l), q2.dtype).at[:n].set(q2)
        sp = jnp.ones((np_, 1), jnp.float32).at[:n].set(s2)
        wp = jnp.zeros((l, cp), w.dtype).at[:, :c].set(w)
        bp = jnp.zeros((cp,), b.dtype).at[:c].set(b)
        f = bottleneck_decompress(qp, sp, wp, bp, bn=bn, bc=bc,
                                  interpret=(mode == "interpret"))
        f = f[:n, :c]
    return f.reshape(lead + (c,))
